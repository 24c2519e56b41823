"""The glm4_moe_lite decoder (``models/glm4_moe_lite.py``): latent
attention over a latent cache, expanded in prefill and absorbed in decode,
a sigmoid router whose choice carries a bias its weights do not, a dense
first layer — against the benchmark's plain reference
(``benchmark/reference/glm4_moe_lite.py``: f32, HIGHEST, the un-absorbed
form at every position, no cache, nothing of the program imported), at
small sizes on the CPU with seeded weights.

Tolerances, and why. With f32 storage the program's products are the
CPU's f32 products and differ from the reference's in the order of
accumulation only (the absorbed form against the expanded one, a cache
against a full pass): rows read 3e-7 to 8e-7 on logits of order 1, and
2e-5 holds every row. With bf16 storage every matmul operand is rounded to
8 bits of mantissa, and the absorbed query and the latent output once more
than the expanded form rounds; over three layers rows read 4e-3 to 6e-3
here, so 2e-2 passes them with three times of room. The same reference
with its latent rows kept in fp8 reads 1.5e-2 to 1.2e-1 a row (the least
on a prompt of three rows, which has read next to nothing back), and with
fp8 operands 7.6e-2 to 2.2e-1: both fail it on most rows.
Where two router scores lie closer than the rounding upstream of them the
fourth place goes to another expert and the row moves by a whole expert's
output, so the bf16 comparison is on the 90th percentile of the rows'
errors (as ``tests/test_cohere_moe.py`` has it) and the controls' on their
median; the f32 comparisons are on every row.
"""
import os
import sys

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers, monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig, _block,
                                             build_glm4_moe_lite_generative)

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import glm4_moe_lite as ref              # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

BF16 = ml_dtypes.bfloat16
F32_TOL, BF16_TOL = 2e-5, 2e-2


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.route_scale,
            "expert_offset": cfg.expert_offset,
            "rms_norm_eps": cfg.rms_norm_eps}


def _session(cfg, seed=3, **geometry):
    """The builder's programs, and seeded weights drawn as the benchmark
    draws them (norm scales around 1, the selection bias in -0.1..0.1),
    planted in the scope."""
    with un.guard():
        net = build_glm4_moe_lite_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    rng = np.random.default_rng(seed)
    params = {}
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_router_bias"):
            w = rng.uniform(-0.1, 0.1, have.shape)
        elif p.name.endswith("_scale"):
            w = rng.uniform(0.9, 1.1, have.shape)
        else:
            w = rng.normal(size=have.shape) * cfg.initializer_range
        scope.set_var(p.name, w.astype(have.dtype))
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
    ([slot, 1 + steps, V], the prefill's by row) and the tokens chosen."""
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
    out = {}
    for s in slots:
        out[s] = (np.stack([first[s][0]] + [l[s] for l in logits]),
                  np.concatenate([first[s][1]] + [t[s] for t in toks]))
    return out


def _ref_rows(served, prompts, slots, params, rc, steps, *args, **kw):
    """The reference's logits at every served position, by request."""
    out = []
    for p, s in zip(prompts, slots):
        ids = jnp.asarray(np.concatenate([p, served[s][1][:-1]]))
        out.append(np.asarray(ref.logits(params, ids, rc, *args, **kw))[
            len(p) - 1:len(p) + steps])
    return out


def _row_errors(served, prompts, slots, params, rc, steps):
    full = _ref_rows(served, prompts, slots, params, rc, steps)
    return np.sort(np.concatenate([
        np.abs(served[s][0] - f).max(-1) for s, f in zip(slots, full)]))


def _p90(rows):
    return rows[int(0.9 * (len(rows) - 1))]


def _tiny(dtype, **over):
    return Glm4MoeLiteConfig.tiny(dtype=dtype, initializer_range=0.05, **over)


# -- prefill, then decode through the latent cache ---------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_equals_the_reference_full_pass(dtype):
    """Prompts of unequal length in one bucket (5, 40 and 23 rows of 48),
    three of four slots, eight decode steps; the fourth slot idles. The
    prefill's last row comes from the expanded form, every step after it
    from the absorbed form over the cache the prefill wrote."""
    cfg = _tiny(dtype)
    net, exe, scope, params = _session(
        cfg, batch_slots=4, max_seq=64, page_size=8, prompt_buckets=(48,))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, L) for L in (5, 40, 23)]
    slots = [2, 0, 3]
    idle = {n: np.asarray(scope.find_var(n))[1].copy()
            for n in net["state_vars"]}
    served = _serve(net, exe, scope, 48, prompts, slots, 8)
    rows = _row_errors(served, prompts, slots, params, _ref_cfg(cfg), 8)
    assert len(rows) == 27
    if dtype == "float32":
        assert rows[-1] < F32_TOL
    else:
        assert _p90(rows) < BF16_TOL
    # the idle slot's gate was never opened: its state is what it was
    for n, before in idle.items():
        np.testing.assert_array_equal(np.asarray(scope.find_var(n))[1],
                                      before)


@pytest.mark.parametrize("control", ["fp8", "cache:fp8"])
def test_fp8_fails_the_tolerance_that_bf16_passes(control):
    """The reference with fp8 operands, or with its latent rows kept in
    fp8 between writing and reading, against itself in f32 at the served
    positions: the controls the bf16 tolerance has to catch."""
    cfg = _tiny("bfloat16")
    net, exe, scope, params = _session(
        cfg, batch_slots=3, max_seq=64, page_size=8, prompt_buckets=(32,))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, L) for L in (14, 3, 32)]
    slots = [0, 1, 2]
    served = _serve(net, exe, scope, 32, prompts, slots, 8)
    rc = _ref_cfg(cfg)
    rows = _row_errors(served, prompts, slots, params, rc, 8)
    kw = (dict(cache_dtype=jnp.float8_e4m3fn) if control == "cache:fp8"
          else dict(precision="fp8"))
    full = _ref_rows(served, prompts, slots, params, rc, 8)
    low = _ref_rows(served, prompts, slots, params, rc, 8, **kw)
    worse = np.sort(np.concatenate(
        [np.abs(a - b).max(-1) for a, b in zip(full, low)]))
    assert _p90(rows) < BF16_TOL < worse[len(worse) // 2]


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
        assert _row_errors(served, [p], [1], params, rc, 3)[-1] < F32_TOL
    # slot 0 decoded 9 more tokens meanwhile: its whole answer is the
    # reference's greedy continuation
    ids = np.concatenate([mine, got[1]])
    for _ in range(9):
        nxt = int(np.argmax(np.asarray(
            ref.logits(params, jnp.asarray(ids), rc))[-1]))
        ids = np.append(ids, nxt)
    assert int(np.asarray(scope.find_var("glm_gen_tokens"))[0, 0]) == \
        int(ids[len(mine) + 2 + 9])


# -- the router ---------------------------------------------------------------

def _moe(h, wr, wg, wu, wd, flash="auto", **kw):
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        feed = dict(h=h, wr=wr, wg=wg, wu=wu, wd=wd)
        with un.guard(), fluid.program_guard(main, startup):
            data = lambda n, a: layers.data(n, shape=list(a.shape),
                                            dtype="float32",
                                            append_batch_size=False)
            bias = kw.pop("select_bias", None)
            if bias is not None:
                feed["bias"] = bias
                kw["select_bias"] = data("bias", bias)
            out, stats = layers.moe_experts(
                *(data(n, a) for n, a in list(feed.items())[:5]), **kw)
        return fluid.Executor(fluid.CPUPlace()).run(
            main, feed=feed, fetch_list=[out, stats])
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})


def _moe_formula(h, wr, wg, wu, wd, k, off, bias, scale):
    T, H = h.shape
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ wr)))
    top = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    silu = lambda a: a / (1 + np.exp(-a))
    want = np.zeros((T, H))
    for t in range(T):
        for e in top[t]:
            if off <= e < off + wg.shape[0]:
                y = (silu(h[t] @ wg[e - off]) * (h[t] @ wu[e - off])) \
                    @ wd[e - off]
                want[t] += scale * s[t, e] / s[t, top[t]].sum() * y
    return want, top


@pytest.mark.parametrize("flash", ["auto", "always"])
def test_the_bias_changes_the_choice_and_not_the_weights(flash):
    """``moe_experts`` with ``select_bias`` and ``route_scale`` on both
    routes against the formula: the four largest of ``score + bias``, the
    unbiased scores of the chosen normalised and scaled by 1.8. The bias
    moves the choice on most rows here; without it the op is what it was."""
    rng = np.random.default_rng(4)
    T, H, F, E, Eh, off, k = 16, 128, 32, 16, 8, 4, 4
    h = rng.normal(size=(T, H)).astype(np.float32)
    w = lambda *s: (rng.normal(size=s) * 0.1).astype(np.float32)
    args = (h, w(H, E), w(Eh, H, F), w(Eh, H, F), w(Eh, F, H))
    bias = rng.uniform(-0.3, 0.3, E).astype(np.float32)
    kw = dict(num_experts=E, top_k=k, expert_offset=off)
    got, st = _moe(*args, flash=flash, select_bias=bias, route_scale=1.8,
                   **kw)
    want, top = _moe_formula(*args, k, off, bias, 1.8)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert st[-2] == T * k and st[-1] == 0
    plain, top0 = _moe_formula(*args, k, off, 0.0, 1.0)
    assert (np.sort(top, -1) != np.sort(top0, -1)).any(-1).sum() > T // 2
    np.testing.assert_allclose(_moe(*args, flash=flash, **kw)[0], plain,
                               atol=2e-5)
    # a bias that moves no choice moves nothing: the weights do not read it
    np.testing.assert_allclose(
        _moe(*args, flash=flash, select_bias=np.full(E, 0.25, np.float32),
             **kw)[0], plain, atol=2e-5)


def test_equal_scores_go_to_the_lower_index():
    """Rows of zeros score every expert 0.5: with a zero bias the first
    four experts are chosen, each weighted 1.8 / 4; a bias lifts another
    four in front of them."""
    T, H, F, E, k = 8, 128, 32, 16, 4
    rng = np.random.default_rng(6)
    w = lambda *s: (rng.normal(size=s) * 0.1).astype(np.float32)
    h = np.zeros((T, H), np.float32)
    args = (h, w(H, E), w(E, H, F), w(E, H, F), w(E, F, H))
    kw = dict(num_experts=E, top_k=k, route_scale=1.8)
    _, st = _moe(*args, select_bias=np.zeros(E, np.float32), **kw)
    assert list(st[:E]) == [T] * 4 + [0] * 12
    lift = np.zeros(E, np.float32)
    lift[[9, 3, 12, 5]] = 0.1
    _, st = _moe(*args, select_bias=lift, **kw)
    assert [e for e in range(E) if st[e]] == [3, 5, 9, 12]


# -- the eight shares add up to the uncut layer --------------------------------

def _one_layer(cfg, i, x, positions, lens, params):
    """``_block`` of layer ``i`` alone on whole sequences ``x`` [R, S, H]
    (the prefill form over a scratch cache), with this share's parameters
    planted."""
    R, S, _ = x.shape
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
                mode="prefill", page_size=8)

        y, _, _ = _block(xv, i, cfg, pv, mv, attend)
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
    x = rng.normal(size=(R, S, full.hidden_size)).astype(np.float32)
    return x, np.tile(np.arange(S, dtype=np.int64), (R, 1)), \
        np.array([24, 13])


def _ref_layer(params, x, lens, i, rc):
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    return [np.asarray(ref.layer(jnp.asarray(x[r, :n]), jp, i, rc, mm,
                                 lambda a: a)) for r, n in enumerate(lens)]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips of 2: what each share's layer adds to the
    stream beyond attention and the shared expert (which all compute
    alike) is its experts' part; the eight parts, with attention and the
    shared expert counted once, are the reference's layer with every
    expert held. f32 storage."""
    base = dict(dtype="float32", initializer_range=0.05)
    full = Glm4MoeLiteConfig.tiny(experts_held=16, **base)
    _, _, _, params = _session(full, batch_slots=1, max_seq=8, page_size=8,
                               prompt_buckets=(8,))
    params = {k: np.asarray(v) for k, v in params.items()}
    x, pos, lens = _layer_inputs(full)
    share = lambda off, p: _one_layer(
        Glm4MoeLiteConfig.tiny(experts_held=2, expert_offset=off, **base),
        1, x, pos, lens, p)
    shares = [share(off, params) for off in range(0, 16, 2)]
    # the layer with the routed experts silent = attention + shared
    none = share(0, {k: (np.zeros_like(v) if v.ndim == 3 else v)
                     for k, v in params.items()})
    got = sum(shares) - 7 * none
    want = _ref_layer(params, x, lens, 1, dict(_ref_cfg(full),
                                               expert_offset=0))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r], atol=F32_TOL)
    assert min(np.abs(s - none).max() for s in shares) > 1e-3


def test_the_first_layer_is_dense():
    """Layer 0 has no router and no experts: one gated feed-forward of
    ``dense_intermediate_size`` behind the attention, the reference's."""
    full = Glm4MoeLiteConfig.tiny(dtype="float32", initializer_range=0.05)
    net, _, _, params = _session(full, batch_slots=1, max_seq=8, page_size=8,
                                 prompt_buckets=(8,))
    names = [p.name for p in
             net["decode"]["main"].global_block.all_parameters()]
    assert "glm_l0_mlp_gate_w" in names and "glm_l1_router_w" in names
    assert not [n for n in names if n.startswith("glm_l0_router")
                or n.startswith("glm_l0_shared") or "glm_l1_mlp" in n]
    assert params["glm_l0_mlp_gate_w"].shape == (64, 96)
    params = {k: np.asarray(v) for k, v in params.items()}
    x, pos, lens = _layer_inputs(full, seed=8)
    got = _one_layer(full, 0, x, pos, lens, params)
    want = _ref_layer(params, x, lens, 0, _ref_cfg(full))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r], atol=F32_TOL)


# -- the engine ---------------------------------------------------------------

_ANSWERS = {}


@pytest.mark.parametrize("rows", [None, 2, 1])
def test_engine_serves_the_tiny_model(rows):
    """Exact accounting, no compile after warm-up, answers of the asked
    length, the latent caches planted with their own shapes, the
    attention's and the expert op's statistics on the monitor under their
    layers; with a prefill that carries every slot, two sequences, or one.
    Same weights, same prompts, greedy: the answers do not depend on how
    many sequences a prefill carries. Eight requests on four slots: every
    slot is refilled."""
    cfg = Glm4MoeLiteConfig.tiny()
    with un.guard():
        net = build_glm4_moe_lite_generative(
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
    before = {n: count(n) for n in ("moe_dropped_assignments_total",)}
    before_decode = count("latent_attention_rows_total", phase="decode")
    before_calls = count("latent_attention_calls_total", phase="decode")
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
    assert count("moe_dropped_assignments_total") == \
        before["moe_dropped_assignments_total"]
    fams = monitor.get_registry().to_dict()
    assert {v["labels"]["kind"] for v in
            fams["serving_kv_cache_bytes"]["values"]} >= {"latent"}
    # the expert op's counters carry the model's layer numbers (layer 0 is
    # dense), the attention's every layer
    assert {v["labels"]["layer"] for v in
            fams["latent_attention_rows_total"]["values"]} == {"0", "1", "2"}
    assert {"1", "2"} <= {v["labels"]["layer"] for v in
                          fams["moe_expert_tokens_total"]["values"]}
    # a decode step reads whole blocks: at 64 rows in pages of 8 the cache
    # is one block, so every execution is charged 4 slots x 64 rows
    calls = count("latent_attention_calls_total", phase="decode") \
        - before_calls
    assert calls > 0 and count("latent_attention_rows_total",
                               phase="decode") - before_decode == 256 * calls
    assert fams["decode_attention_walk_share"]["values"]


def test_the_answers_are_the_references_greedy_continuations():
    """What the engine serves is, token for token, what the reference's
    full pass picks: a slot refilled, an idle slot, prompts of unequal
    length in a bucket."""
    cfg = Glm4MoeLiteConfig.tiny(dtype="float32")
    with un.guard():
        net = build_glm4_moe_lite_generative(
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
        # the served token's logit is the reference's best, to the f32
        # tolerance (an exact tie-break is not asked of a different order
        # of accumulation)
        gap = lg.max(-1) - lg[np.arange(len(o)), o]
        assert gap.max() < F32_TOL
