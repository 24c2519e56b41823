"""The xing4_0 decoder (``models/xing4.py``): four f32 residual streams
mixed by manifold-constrained hyper-connections round latent attention
with YaRN positions and a biased sigmoid router — against the benchmark's
plain reference (``benchmark/reference/xing4.py``: f32, HIGHEST, the
un-absorbed attention at every position, the Sinkhorn rounds as written,
no cache, nothing of the program imported), at small sizes on the CPU with
seeded weights drawn as the benchmark draws them.

Tolerances, and why. With f32 storage the program's products are the CPU's
f32 products and differ from the reference's in the order of accumulation
only (the absorbed form against the expanded one, a cache against a full
pass, ``(x P) r`` against ``(x r) P`` in the hyper-connection's norm): rows
read 4e-7 to 8e-7 on logits of order 1, and 2e-5 holds every row. The same
reference with its streams kept in bf16 between sublayers reads 2e-3 to
3e-3 a row, and with the hyper-connections' per-token part dropped 0.2 to
0.5: both fail that tolerance on every row, which is what holds the
program to "the four streams and every coefficient f32". With bf16 storage
every matmul operand is rounded to 8 bits of mantissa; over three layers
rows read 5e-3 to 8e-3 here, so 2.5e-2 passes them with three times of
room, and the reference with fp8 operands reads 0.1 to 0.2: it fails on
most rows. Where two router scores lie closer than the rounding upstream
of them the fourth place goes to another expert and the row moves by a
whole expert's output, so the bf16 comparison is on the 90th percentile of
the rows' errors (as ``tests/test_glm4_moe_lite.py`` has it) and the
control's on its median; the f32 comparisons are on every row.
"""
import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers, monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.models import decoder
from paddle_tpu.models.xing4 import (Xing4Config, _block,
                                     build_xing4_generative)
from paddle_tpu.ops import moe as moe_ops

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import xing4 as ref                      # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

BF16 = ml_dtypes.bfloat16
F32_TOL, BF16_TOL = 2e-5, 2.5e-2
PUBLISHED_YARN = {"type": "yarn", "factor": 64, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096}


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rope_scaling": cfg.rope_scaling,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.route_scale,
            "expert_offset": cfg.expert_offset,
            "rms_norm_eps": cfg.rms_norm_eps, "hc_mult": cfg.hc_mult,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps,
            "mhc_h_res_clamp_min": cfg.hc_res_clamp[0],
            "mhc_h_res_clamp_max": cfg.hc_res_clamp[1]}


def _drawn(name, shape, cfg, rng):
    """A parameter as the benchmark draws its kind (``reference.xing4``
    ``param_spec``), from numpy's generator."""
    n = cfg.hc_mult
    if name.endswith("_router_bias"):
        return rng.uniform(-0.1, 0.1, shape)
    if name.endswith("_scale"):
        return rng.uniform(0.9, 1.1, shape)
    if name.endswith("_alpha"):
        return rng.uniform(0.5, 1.5, shape)
    if name.endswith("_bias"):
        w = rng.uniform(-1, 1, shape)
        w[2 * n:] += 4 * np.eye(n).ravel()
        return w
    return rng.normal(size=shape) * cfg.initializer_range


def _session(cfg, seed=3, **geometry):
    """The builder's programs and seeded weights planted in the scope."""
    with un.guard():
        net = build_xing4_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    rng = np.random.default_rng(seed)
    params = {}
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        scope.set_var(p.name, _drawn(p.name, have.shape, cfg, rng).astype(
            have.dtype))
        params[p.name] = jnp.asarray(scope.find_var(p.name))
    return net, exe, scope, params


def _prefill_feed(net, bucket, prompts, slots):
    R = net["prefill"][bucket]["rows"]
    feed = {"prompt_ids": np.zeros((R, bucket), np.int64),
            "prompt_pos": np.tile(np.arange(bucket, dtype=np.int64), (R, 1)),
            "prompt_mask": np.zeros((R, bucket), np.float32),
            "prompt_len": np.ones((R, 1), np.int64),
            "slot_mask": np.zeros((R, 1), np.float32),
            "slot_ids": np.zeros((R, 1), np.int64)}
    for r, (p, slot) in enumerate(zip(prompts, slots)):
        feed["prompt_ids"][r, :len(p)] = p
        feed["prompt_mask"][r, :len(p)] = 1.0
        feed["prompt_len"][r, 0] = len(p)
        feed["slot_mask"][r, 0] = 1.0
        feed["slot_ids"][r, 0] = slot
    return feed


def _serve(net, exe, scope, bucket, prompts, slots, steps):
    """Prefill ``prompts`` into ``slots``, decode ``steps`` tokens
    greedily; the logits of the prefill's last row and of every step
    ([slot, 1 + steps, V]) and the tokens chosen."""
    pf, dec = net["prefill"][bucket], net["decode"]
    lg, tok = exe.run(pf["main"], scope=scope,
                      feed=_prefill_feed(net, bucket, prompts, slots),
                      fetch_list=[pf["last_logits"], pf["first_token"]])
    first = {s: (lg[r], tok[r]) for r, s in enumerate(slots)}
    logits, toks = [], []
    for _ in range(steps):
        lg, tok = exe.run(dec["main"], feed={}, scope=scope,
                          fetch_list=[dec["logits"], dec["next_token"]])
        logits.append(lg)
        toks.append(tok.copy())
    return {s: (np.stack([first[s][0]] + [l[s] for l in logits]),
                np.concatenate([first[s][1]] + [t[s] for t in toks]))
            for s in slots}


def _ref_rows(served, prompts, slots, params, rc, steps, **kw):
    out = []
    for p, s in zip(prompts, slots):
        ids = jnp.asarray(np.concatenate([p, served[s][1][:-1]]))
        out.append(np.asarray(ref.logits(params, ids, rc, **kw))[
            len(p) - 1:len(p) + steps])
    return out


def _row_errors(served, slots, rows):
    return np.sort(np.concatenate([
        np.abs(served[s][0] - f).max(-1) for s, f in zip(slots, rows)]))


def _p90(rows):
    return rows[int(0.9 * (len(rows) - 1))]


def _tiny(dtype, **over):
    return Xing4Config.tiny(dtype=dtype, initializer_range=0.05, **over)


# -- prefill, then decode through the latent cache ---------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_equals_the_reference_full_pass(dtype):
    """Prompts of unequal length in one bucket (5, 40 and 23 rows of 48:
    past the 32 original positions of the tiny YaRN), three of four slots,
    eight decode steps; the fourth slot idles. The prefill's last row comes
    from the expanded attention, every step after it from the absorbed
    form over the cache the prefill wrote; the four streams go through
    both. In f32 the controls fail the tolerance the program passes."""
    cfg = _tiny(dtype)
    net, exe, scope, params = _session(
        cfg, batch_slots=4, max_seq=64, page_size=8, prompt_buckets=(48,))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, L) for L in (5, 40, 23)]
    slots = [2, 0, 3]
    idle = {n: np.asarray(scope.find_var(n))[1].copy()
            for n in net["state_vars"]}
    served = _serve(net, exe, scope, 48, prompts, slots, 8)
    rc = _ref_cfg(cfg)
    rows = _row_errors(served, slots,
                       _ref_rows(served, prompts, slots, params, rc, 8))
    assert len(rows) == 27
    if dtype == "float32":
        assert rows[-1] < F32_TOL
        for control in (dict(stream_dtype=jnp.bfloat16),
                        dict(per_token=False)):
            worse = _row_errors(served, slots, _ref_rows(
                served, prompts, slots, params, rc, 8, **control))
            assert worse[0] > 10 * F32_TOL, control
    else:
        assert _p90(rows) < BF16_TOL
        worse = _row_errors(served, slots, _ref_rows(
            served, prompts, slots, params, rc, 8, precision="fp8"))
        assert worse[len(worse) // 2] > BF16_TOL
    # the idle slot's gate was never opened: its state is what it was
    for n, before in idle.items():
        np.testing.assert_array_equal(np.asarray(scope.find_var(n))[1],
                                      before)


def test_a_refilled_slot_starts_from_its_own_prompt():
    """Slot 1 is filled, decoded, refilled with a shorter prompt while
    slot 0 keeps decoding: the rows the first request left past the new
    length are never read, and the neighbour does not notice."""
    cfg = _tiny("float32")
    net, exe, scope, params = _session(
        cfg, batch_slots=2, max_seq=64, page_size=8, prompt_buckets=(32,),
        prefill_rows=1)
    rng = np.random.default_rng(5)
    rc = _ref_cfg(cfg)
    mine = rng.integers(1, cfg.vocab_size, 17)
    got = _serve(net, exe, scope, 32, [mine], [0], 2)[0]
    for L in (30, 9, 3):
        p = rng.integers(1, cfg.vocab_size, L)
        served = _serve(net, exe, scope, 32, [p], [1], 3)
        assert _row_errors(served, [1], _ref_rows(
            served, [p], [1], params, rc, 3))[-1] < F32_TOL
    # slot 0 decoded 9 more tokens meanwhile: its whole answer is the
    # reference's greedy continuation (one compiled pass over padded ids:
    # no row looks to its right)
    full = jax.jit(lambda ids: ref.logits(params, ids, rc))
    ids = np.concatenate([mine, got[1]])
    for _ in range(9):
        padded = np.zeros(40, np.int64)
        padded[:len(ids)] = ids
        nxt = int(np.argmax(np.asarray(full(jnp.asarray(padded)))[
            len(ids) - 1]))
        ids = np.append(ids, nxt)
    assert int(np.asarray(scope.find_var("xing_gen_tokens"))[0, 0]) == \
        int(ids[len(mine) + 2 + 9])


# -- YaRN -----------------------------------------------------------------------

def _rotary(x, pos, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype=str(x.dtype),
                         append_batch_size=False)
        pv = layers.data("pos", shape=list(pos.shape), dtype="int64",
                         append_batch_size=False)
        out = layers.rotary_embedding(xv, pv, **kw)
    op = [o for o in main.global_block.ops if o.type == "rotary_embedding"][0]
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "pos": pos}, fetch_list=[out])[0]
    return got, op


def test_yarn_frequencies_and_scale_are_the_closed_form():
    """The published ``rope_scaling`` over 64 rotary dims at theta 10,000:
    the pairs below 10 keep their frequency, the pairs from 23 turn 64
    times slower, the ramp between is linear; the cos/sin factor is 1 and
    the softmax scale 192^-1/2 x (0.1 ln 64 + 1)^2."""
    yarn = moe_ops.yarn_attrs({f"yarn_{k}": v for k, v in dict(
        factor=64.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0).items()})
    inv = moe_ops.yarn_inv_freq(10000.0, 64, **yarn)
    j = np.arange(32)
    plain = 10000.0 ** (-2.0 * j / 64)
    lo = np.floor(64 * np.log(4096 / (32 * 2 * np.pi)) / (2 * np.log(1e4)))
    hi = np.ceil(64 * np.log(4096 / (1 * 2 * np.pi)) / (2 * np.log(1e4)))
    assert (lo, hi) == (10, 23)
    r = 1 - np.clip((j - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(inv, (1 - r) * plain / 64 + r * plain,
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    assert (np.diff(inv) < 0).all()
    np.testing.assert_allclose(inv, ref.inv_freq(64, 10000, PUBLISHED_YARN),
                               rtol=1e-6)
    assert moe_ops.yarn_cos_scale(**yarn) == 1.0
    m = 0.1 * np.log(64) + 1
    assert moe_ops.yarn_softmax_scale(192, 64.0, 1.0) == pytest.approx(
        192 ** -0.5 * m * m) == pytest.approx(0.14468, rel=1e-4)
    assert moe_ops.yarn_softmax_scale(192, 64.0, 0.0) == 192 ** -0.5
    # a builder's configuration: the scale reaches both routes' op
    assert decoder.latent_softmax_scale(Xing4Config()) == pytest.approx(
        0.14468, rel=1e-4)
    assert decoder.latent_softmax_scale(
        Xing4Config(rope_scaling=None)) is None


def test_the_rotary_op_turns_by_yarn_and_without_it_is_bit_for_bit_todays():
    """With ``yarn`` the op's output is the reference's rotary at every
    position (below and past the original length) and carries the
    attribute set; without it the attributes are the defaults and the
    output is, bit for bit, what the op computed before it had them: the
    plain frequencies by the expression it always used."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 3, 40, 16)).astype(np.float32)
    pos = np.stack([np.arange(40), np.arange(5000, 5040)]).astype(np.int64)
    yarn = dict(PUBLISHED_YARN, original_max_position_embeddings=32,
                beta_fast=4, factor=8, mscale=0.7)
    got, op = _rotary(x, pos, theta=10000.0, yarn=yarn)
    assert op.attr("yarn_factor") == 8.0 and \
        op.attr("yarn_original_max_position") == 32
    for b in range(2):
        want = ref.rotary(jnp.asarray(x[b]), jnp.asarray(pos[b]), 10000.0,
                          yarn)
        np.testing.assert_allclose(got[b], want, atol=2e-5)
    plain, op = _rotary(x, pos, theta=10000.0)
    assert op.attr("yarn_factor") == 0.0
    assert np.abs(plain - got).max() > 0.1

    def todays(xv, pos):        # ops/moe.py's rule at the parent commit
        B, _, S, D = xv.shape
        inv = 10000.0 ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        ang = pos.reshape(B, 1, S, 1).astype(jnp.float32) * inv
        spread = lambda t: jnp.repeat(t, 2, axis=-1)
        first = jnp.arange(0, D, 2)
        second = first + 1
        cos, sin = spread(jnp.cos(ang)), spread(jnp.sin(ang))
        swap = jnp.zeros((D, D), xv.dtype).at[second, first].set(-1).at[
            first, second].set(1)
        turned = jnp.matmul(xv, swap, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
        return (xv.astype(jnp.float32) * cos + turned * sin).astype(xv.dtype)

    np.testing.assert_array_equal(
        plain, np.asarray(jax.jit(todays)(jnp.asarray(x), jnp.asarray(pos))))


def test_the_softmax_scale_reaches_both_routes_of_the_attention():
    """``latent_attention`` with ``scale``: the prefill and the decode
    ops of a YaRN configuration carry 0.3204 x (24^-1/2 m^2), the ops of
    one without carry the default 0, and the two programs' logits differ."""
    with un.guard():
        net = build_xing4_generative(Xing4Config.tiny(), batch_slots=2,
                                     max_seq=16, page_size=8,
                                     prompt_buckets=(8,))
        plain = build_xing4_generative(
            Xing4Config.tiny(rope_scaling=None), batch_slots=2, max_seq=16,
            page_size=8, prompt_buckets=(8,))
    scales = lambda n: {round(op.attr("scale"), 6) for prog in (
        n["decode"]["main"], n["prefill"][8]["main"])
        for op in prog.global_block.ops if op.type == "latent_attention"}
    m = 0.1 * np.log(8) + 1
    assert scales(net) == {round(24 ** -0.5 * m * m, 6)}
    assert scales(plain) == {0.0}
    yarns = lambda n: {op.attr("yarn_factor") for op in
                       n["decode"]["main"].global_block.ops
                       if op.type == "rotary_embedding"}
    assert yarns(net) == {8.0} and yarns(plain) == {0.0}


# -- the eight shares add up to the uncut layer --------------------------------

def _one_layer(cfg, i, x, positions, lens, params):
    """``_block`` of layer ``i`` alone on whole sequences' streams ``x``
    [R, S, n, C] (the prefill form over a scratch cache), with this
    share's parameters planted."""
    R, S = x.shape[:2]
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        data = lambda n, a: layers.data(n, shape=list(a.shape),
                                        dtype=str(a.dtype),
                                        append_batch_size=False)
        mask = (np.arange(S)[None] < lens[:, None]).astype(np.float32)
        xv, pv, mv = data("x", x), data("pos", positions), data("mask", mask)
        cache = layers.create_global_var([R, 1, S, 128], 0.0, "float32",
                                         persistable=True)
        zero = layers.fill_constant([R, 1], "int64", 0)

        def attend(i, q, c, k_rope, w_kvb):
            return layers.latent_attention(
                q, c, k_rope, w_kvb, cache, zero, cfg.qk_nope_head_dim,
                mode="prefill", page_size=8,
                scale=decoder.latent_softmax_scale(cfg))

        y = _block(xv, i, cfg, pv, mv, attend)[0]
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    lo = cfg.expert_offset
    for name, value in params.items():
        if scope.find_var(name) is None:
            continue
        held = value[lo:lo + cfg.experts_held] if value.ndim == 3 else value
        assert scope.find_var(name).shape == held.shape, name
        scope.set_var(name, held)
    return exe.run(main, feed={"x": x, "pos": positions, "mask": mask},
                   fetch_list=[y], scope=scope)[0]


def _layer_inputs(full, seed=5):
    rng = np.random.default_rng(seed)
    R, S = 2, 24
    x = rng.normal(size=(R, S, full.hc_mult, full.hidden_size)).astype(
        np.float32)
    return x, np.tile(np.arange(S, dtype=np.int64), (R, 1)), \
        np.array([24, 13])


def _ref_layer(params, x, lens, i, rc):
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    return [np.asarray(ref.layer(jnp.asarray(x[r, :n]), jp, i, rc, mm,
                                 lambda a: a)) for r, n in enumerate(lens)]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips of 2. A layer's write is ``H_res X +
    H_post^T y`` with ``y`` the feed-forward's output, linear in ``y``
    with coefficients every share computes alike, so what a share's layer
    writes beyond the layer with its routed experts silent is its experts'
    part mixed back; the eight parts, with what all compute alike counted
    once, are the reference's layer with every expert held. f32."""
    base = dict(dtype="float32", initializer_range=0.05)
    full = Xing4Config.tiny(experts_held=16, **base)
    _, _, _, params = _session(full, batch_slots=1, max_seq=8, page_size=8,
                               prompt_buckets=(8,))
    params = {k: np.asarray(v) for k, v in params.items()}
    x, pos, lens = _layer_inputs(full)
    share = lambda off, p: _one_layer(
        Xing4Config.tiny(experts_held=2, expert_offset=off, **base),
        1, x, pos, lens, p)
    shares = [share(off, params) for off in range(0, 16, 2)]
    none = share(0, {k: (np.zeros_like(v) if v.ndim == 3 else v)
                     for k, v in params.items()})
    got = sum(shares) - 7 * none
    want = _ref_layer(params, x, lens, 1, dict(_ref_cfg(full),
                                               expert_offset=0))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r], atol=F32_TOL)
    assert min(np.abs(s - none).max() for s in shares) > 1e-3


def test_the_leading_layers_are_dense():
    """Below ``first_k_dense`` a layer has no router and no experts: one
    gated feed-forward of ``dense_intermediate_size`` behind the attention,
    between the same two hyper-connections; the reference's."""
    full = Xing4Config.tiny(dtype="float32", initializer_range=0.05,
                            first_k_dense=2, num_layers=3)
    net, _, _, params = _session(full, batch_slots=1, max_seq=8, page_size=8,
                                 prompt_buckets=(8,))
    names = [p.name for p in
             net["decode"]["main"].global_block.all_parameters()]
    assert {"xing_l0_mlp_gate_w", "xing_l1_mlp_gate_w", "xing_l2_router_w",
            "xing_l0_hc_attn_proj", "xing_l2_hc_ffn_bias"} <= set(names)
    assert not [n for n in names if n.startswith(("xing_l0_router",
                                                  "xing_l1_shared"))
                or "xing_l2_mlp" in n]
    assert params["xing_l0_mlp_gate_w"].shape == (64, 96)
    assert params["xing_l1_hc_attn_proj"].shape == (24, 256)
    params = {k: np.asarray(v) for k, v in params.items()}
    x, pos, lens = _layer_inputs(full, seed=8)
    got = _one_layer(full, 1, x, pos, lens, params)
    want = _ref_layer(params, x, lens, 1, _ref_cfg(full))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r], atol=F32_TOL)


# -- the engine ---------------------------------------------------------------

_ANSWERS = {}


@pytest.mark.parametrize("rows", [None, 1])
def test_engine_serves_the_tiny_model(rows):
    """Exact accounting, no compile after warm-up, answers of the asked
    length, the latent caches planted with their own shapes, the
    hyper-connections', the attention's and the expert op's statistics on
    the monitor; with a prefill that carries every slot or one sequence.
    Same weights, same prompts, greedy: the answers do not depend on how
    many sequences a prefill carries. Eight requests on four slots."""
    cfg = Xing4Config.tiny()
    with un.guard():
        net = build_xing4_generative(
            cfg, batch_slots=4, max_seq=64, page_size=8,
            prompt_buckets=(16, 32), prefill_rows=rows)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    assert eng.warm_up() == 3
    count = lambda name, **lab: sum(
        v["value"] for v in monitor.get_registry().to_dict().get(
            name, {"values": []})["values"]
        if all(v["labels"].get(k) == w for k, w in lab.items()))
    tracked = ("hyper_connection_rows_total", "hyper_connection_calls_total",
               "latent_attention_calls_total",
               "moe_dropped_assignments_total")
    before = {(n, ph): count(n, phase=ph) for n in tracked
              for ph in ("decode", "prefill")}
    rng = np.random.default_rng(0)
    sizes = [(5, 9), (16, 12), (29, 3), (12, 14), (7, 11), (3, 1), (32, 6),
             (20, 8)]
    prompts = [rng.integers(1, 128, n) for n, _ in sizes]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, sizes)]
        outs = [f.result(timeout=300)[0] for f in futs]
    assert [len(o) for o in outs] == [m for _, m in sizes]
    same = _ANSWERS.setdefault("answers", outs)
    assert all(np.array_equal(a, b) for a, b in zip(same, outs))
    assert eng.accounting()["exact"]
    assert eng.generation_stats()["decode_recompiles"] == 0
    kinds = net["cache_kinds"]
    assert set(kinds.values()) == {"latent"} and len(kinds) == 3
    for n in kinds:     # a row of 32 + 8 numbers in one tile of 128 lanes
        v = scope.find_var(n)
        assert v.shape == (4, 1, 64, 128) and v.dtype == BF16
    moved = {k: count(k[0], phase=k[1]) - v for k, v in before.items()}
    assert moved["moe_dropped_assignments_total", "decode"] == 0
    # two hyper-connections a layer: six calls for every three of the
    # attention's, and a decode step mixes all four slots' rows
    for phase in ("decode", "prefill"):
        calls = moved["hyper_connection_calls_total", phase]
        assert calls == 2 * moved["latent_attention_calls_total", phase] > 0
    assert moved["hyper_connection_rows_total", "decode"] == \
        4 * moved["hyper_connection_calls_total", "decode"]
    fams = monitor.get_registry().to_dict()
    err = fams["hyper_connection_res_sum_err_max"]["values"][0]["value"]
    assert 0 < err < 0.2
    assert {v["labels"]["layer"] for v in
            fams["latent_attention_rows_total"]["values"]} >= {"0", "1", "2"}


def test_the_answers_are_the_references_greedy_continuations():
    """What the engine serves is, token for token, what the reference's
    full pass picks: a slot refilled, an idle slot, prompts of unequal
    length in a bucket."""
    cfg = Xing4Config.tiny(dtype="float32")
    with un.guard():
        net = build_xing4_generative(
            cfg, batch_slots=3, max_seq=64, page_size=8,
            prompt_buckets=(16, 32), prefill_rows=2)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    params = {p.name: jnp.asarray(scope.find_var(p.name)) for p in
              net["decode"]["main"].global_block.all_parameters()}
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    eng.warm_up()
    rng = np.random.default_rng(1)
    sizes = [(5, 9), (16, 5), (29, 3), (12, 7), (7, 6), (3, 1), (32, 6)]
    prompts = [rng.integers(1, 128, n) for n, _ in sizes]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, sizes)]
        outs = [f.result(timeout=300)[0] for f in futs]
    rc = _ref_cfg(cfg)
    for p, o in zip(prompts, outs):
        ids = jnp.asarray(np.concatenate([p, o[:-1]]))
        lg = np.asarray(ref.logits(params, ids, rc))[len(p) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(o)), o]
        assert gap.max() < F32_TOL
