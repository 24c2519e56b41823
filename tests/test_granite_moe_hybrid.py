"""The granitemoehybrid decoder (``models/granite_moe_hybrid.py``): Mamba-2
layers with a scan state beside one attention layer without positions,
four scalar multipliers, a tied head, softmax routing with an added shared
expert of its own width — against the benchmark's plain reference
(``benchmark/reference/granitemoehybrid.py``: f32, HIGHEST, a token at a
time, no cache, nothing of the program imported), at small sizes on the CPU
with seeded weights.

Tolerances, and why. The embedding is the head, so it is drawn at a range
of its own (0.005 beside 0.1: at the matrices' range the multiplier of 12
makes every token's best successor itself, and no comparison of choices
can fail), and logits are divided by ``logits_scaling`` 16: they read 0.009
at the most. With f32 storage the program's products are the CPU's f32
products and differ from the reference's in the order of accumulation only
(a cache against a full pass; with the kernels interpreted, the chunked
scan against a token loop): 1.1e-8 at the most here, so 1e-7 on every row.
A scan state kept in bf16 between tokens reads 7e-7 to 1.3e-5 on those rows:
it fails that tolerance on every row. With bf16 storage every matmul
operand is rounded to 8 bits of mantissa; over five layers that reads 0.3e-4
to 2.4e-4 here (three sets of weights), so 4e-4 passes it on the 90th
percentile of the rows (where two router logits lie closer than the
rounding upstream of them the k-th place goes to another expert and the row
moves by a whole expert's output, as ``tests/test_cohere_moe.py`` has it),
and the same reference computed in fp8 operands reads 0.85e-3 to 2.3e-3,
more on every row, and fails it.
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
from paddle_tpu.models.decoder import Mix as _Mix
from paddle_tpu.models.granite_moe_hybrid import (
    ATTENTION, MAMBA, GraniteMoeHybridConfig, _block,
    build_granite_moe_hybrid_generative)

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import granitemoehybrid as ref           # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

BF16 = ml_dtypes.bfloat16
F32_TOL, BF16_TOL = 1e-7, 4e-4


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "layer_types": list(cfg.layer_types),
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "mamba_n_heads": cfg.mamba_n_heads,
            "mamba_d_head": cfg.mamba_d_head,
            "mamba_d_state": cfg.mamba_d_state,
            "mamba_d_conv": cfg.mamba_d_conv,
            "mamba_n_groups": cfg.mamba_n_groups,
            "num_experts_per_tok": cfg.top_k,
            "expert_offset": cfg.expert_offset,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.rms_norm_eps}


def _session(cfg, seed=3, **geometry):
    """The builder's programs, and seeded weights drawn as the benchmark
    draws them (norm scales, the skip, decay rates and time steps away from
    their neutral values), planted in the scope."""
    with un.guard():
        net = build_granite_moe_hybrid_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    rng = np.random.default_rng(seed)
    params = {}
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_a_log"):
            w = rng.uniform(0.0, np.log(16.0), have.shape)
        elif p.name.endswith("_dt_bias"):
            w = rng.uniform(-6.9, -2.25, have.shape)
        elif p.name.endswith("_scale") or p.name.endswith("_d"):
            w = rng.uniform(0.9, 1.1, have.shape)
        elif p.name.endswith("_conv_b"):
            w = rng.uniform(-0.1, 0.1, have.shape)
        elif p.name.endswith("_conv_w"):
            w = rng.normal(size=have.shape) * 0.5
        elif p.name.endswith("_word_emb"):
            w = rng.normal(size=have.shape) * cfg.embedding_range
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


def _row_errors(served, prompts, slots, params, rc, steps, **ref_kw):
    errs = []
    for p, s in zip(prompts, slots):
        lg, toks = served[s]
        ids = jnp.asarray(np.concatenate([p, toks[:-1]]))
        rows = slice(len(p) - 1, len(p) + steps)
        full = np.asarray(ref.logits(params, ids, rc, **ref_kw))[rows]
        errs += list(np.abs(lg - full).max(-1))
    return np.sort(errs)


def _p90(rows):
    return rows[int(0.9 * (len(rows) - 1))]


def _tiny(dtype, **over):
    return GraniteMoeHybridConfig.tiny(dtype=dtype, initializer_range=0.1,
                                       embedding_range=0.005, **over)


# -- (a) prefill, then decode through both kinds of state ------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_equals_the_reference_full_pass(dtype):
    """Prompts of unequal length in one bucket (5, 40 and 23 rows of 48:
    not whole chunks of the scan, padding behind each), three of four
    slots, eight decode steps; the fourth slot idles."""
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
    # the logits are the small ones the four multipliers make of them
    assert 0.002 < np.abs(served[0][0]).max() < 0.05
    # the idle slot's gate was never opened: its state is what it was
    for n, before in idle.items():
        np.testing.assert_array_equal(np.asarray(scope.find_var(n))[1],
                                      before)


def test_a_refilled_slot_starts_from_its_own_prompt():
    """Slot 1 is filled, decoded, refilled twice with other prompts while
    slot 0 keeps decoding: each refill overwrites the scan's state and the
    tail (nothing is added to what the slot held), and the neighbour does
    not notice."""
    cfg = _tiny("float32")
    net, exe, scope, params = _session(
        cfg, batch_slots=2, max_seq=64, page_size=8, prompt_buckets=(32,),
        prefill_rows=1)
    rng = np.random.default_rng(5)
    rc = _ref_cfg(cfg)
    mine = rng.integers(1, cfg.vocab_size, 17)
    got = _serve(net, exe, scope, 32, [mine], [0], 2)[0]
    for L in (9, 30, 3):
        p = rng.integers(1, cfg.vocab_size, L)
        served = _serve(net, exe, scope, 32, [p], [1], 3)
        assert _row_errors(served, [p], [1], params, rc, 3)[-1] < F32_TOL
    # slot 0 decoded 9 more tokens meanwhile: its current token's logit is
    # the reference's best after the greedy continuation so far
    ids = np.concatenate([mine, got[1]])
    for _ in range(9):
        lg = np.asarray(ref.logits(params, jnp.asarray(ids), rc))[-1]
        ids = np.append(ids, int(np.argmax(lg)))
    last = int(np.asarray(scope.find_var("gmh_gen_tokens"))[0, 0])
    lg = np.asarray(ref.logits(params, jnp.asarray(ids[:-1]), rc))[-1]
    assert lg.max() - lg[last] < F32_TOL


def test_a_bf16_scan_state_fails_the_f32_tolerance():
    """The reference with its state rounded to bf16 between tokens, against
    itself in f32, over 300 rows: the control a tolerance has to catch. And
    over those rows the state neither dies nor blows up."""
    cfg = _tiny("float32")
    net, exe, scope, params = _session(
        cfg, batch_slots=1, max_seq=8, page_size=8, prompt_buckets=(8,))
    ids = jnp.asarray(np.random.default_rng(2).integers(1, 128, 300))
    rc = _ref_cfg(cfg)
    full = np.asarray(ref.logits(params, ids, rc))
    low = np.asarray(ref.logits(params, ids, rc, state_dtype=jnp.bfloat16))
    err = np.abs(full - low).max(-1)[-100:]
    assert err.min() > 2 * F32_TOL and np.median(err) > 5 * F32_TOL
    assert np.isfinite(full).all() and np.abs(full[-1]).max() > 0.001


def test_fp8_operands_fail_the_tolerance_that_bf16_passes():
    cfg = _tiny("bfloat16")
    net, exe, scope, params = _session(
        cfg, batch_slots=3, max_seq=64, page_size=8, prompt_buckets=(32,))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, L) for L in (14, 3, 32)]
    served = _serve(net, exe, scope, 32, prompts, [0, 1, 2], 8)
    rc = _ref_cfg(cfg)
    rows = _row_errors(served, prompts, [0, 1, 2], params, rc, 8)
    fp8 = []
    for p, s in zip(prompts, range(3)):
        ids = jnp.asarray(np.concatenate([p, served[s][1][:-1]]))
        at = slice(len(p) - 1, len(p) + 8)
        fp8 += list(np.abs(
            np.asarray(ref.logits(params, ids, rc, "fp8"))[at]
            - np.asarray(ref.logits(params, ids, rc))[at]).max(-1))
    assert _p90(rows) < BF16_TOL < min(fp8)


# -- (c) the two shares add up to the uncut layer -----------------------------

def _one_layer(cfg, i, x, lens, params):
    """``_block`` of layer ``i`` alone on whole sequences ``x`` [R, S, H],
    with this share's parameters planted."""
    R, S, _ = x.shape
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        data = lambda n, a: layers.data(n, shape=list(a.shape),
                                        dtype=str(a.dtype),
                                        append_batch_size=False)
        mask = (np.arange(S)[None] < lens[:, None]).astype(np.float32)
        xv, mv = data("x", x), data("mask", mask)
        state = layers.create_global_var(
            [R, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state], 0.0,
            "float32", persistable=True)
        tail = layers.create_global_var(
            [R, cfg.mamba_d_conv - 1, cfg.conv_channels], 0.0, "float32",
            persistable=True)
        bias = layers.unsqueeze(
            layers.scale(mv, scale=10000.0, bias=-10000.0), [1, 2])

        def attend(i, q, k, v):
            return layers.fused_multihead_attention(
                q, k, v, bias_qk=bias, causal=True,
                scale=cfg.attention_multiplier, is_test=True)

        def recur(i, xbc, conv_w, conv_b, dt, a_log, dt_bias, d):
            return layers.mamba2_scan(
                xbc, conv_w, conv_b, dt, a_log, dt_bias, d, state, tail, mv,
                cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                chunk=cfg.mamba_chunk_size)

        y, _, _ = _block(xv, i, cfg, mv, _Mix(attend, recur))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    lo = cfg.expert_offset
    for name, value in params.items():
        if scope.find_var(name) is None:
            continue
        held = value[lo:lo + cfg.experts_held] if value.ndim == 3 else value
        assert scope.find_var(name).shape == held.shape, name
        scope.set_var(name, held)
    return exe.run(main, feed={"x": x, "mask": mask}, fetch_list=[y],
                   scope=scope)[0]


@pytest.mark.parametrize("i", [0, 2])
def test_the_two_shares_add_up_to_the_uncut_layer(i):
    """16 experts over 2 chips of 8 (offsets 0 and half): what each share's
    layer adds to the stream beyond the mixer and the shared expert (which
    both compute alike) is its experts' part; the two parts, with the mixer
    and the shared expert counted once, are the reference's layer with
    every expert held. Layer 0 is Mamba-2, layer 2 attention. f32
    storage."""
    base = dict(dtype="float32", initializer_range=0.1, embedding_range=0.005)
    full = GraniteMoeHybridConfig.tiny(experts_held=16, **base)
    assert full.layer_types[i] == (MAMBA if i == 0 else ATTENTION)
    net, _, _, params = _session(full, batch_slots=1, max_seq=8,
                                 page_size=8, prompt_buckets=(8,))
    params = {k: np.asarray(v) for k, v in params.items()}
    rng = np.random.default_rng(5)
    R, S, H = 2, 24, full.hidden_size
    x = rng.normal(size=(R, S, H)).astype(np.float32)
    lens = np.array([24, 13])
    half = lambda off: GraniteMoeHybridConfig.tiny(
        experts_held=8, expert_offset=off, **base)
    shares = [_one_layer(half(off), i, x, lens, params) for off in (0, 8)]
    none = _one_layer(half(0), i, x, lens,
                      {k: (np.zeros_like(v) if v.ndim == 3 else v)
                       for k, v in params.items()})
    # none: the layer with the routed experts silent = mixer + shared
    got = shares[0] + shares[1] - none
    rc = dict(_ref_cfg(full), expert_offset=0)
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    for r in range(R):
        want = np.asarray(ref.layer(jnp.asarray(x[r, :lens[r]]), jp, i, rc,
                                    mm, lambda a: a))
        np.testing.assert_allclose(got[r, :lens[r]], want, atol=2e-5)
    assert np.abs(shares[0] - none).max() > 0.002    # the experts did speak


def test_a_shared_expert_of_its_own_width():
    """``decoder.ffn`` builds the shared expert at
    ``shared_intermediate_size`` where the configuration has one, and at
    ``num_shared_experts`` routed widths where it has not."""
    cfg = GraniteMoeHybridConfig.tiny()
    with un.guard():
        net = build_granite_moe_hybrid_generative(cfg, prompt_buckets=(8,),
                                                  max_seq=8)
    shapes = {p.name: tuple(p.shape) for p in
              net["decode"]["main"].global_block.all_parameters()}
    assert shapes["gmh_l0_shared_gate_w"] == (64, 64)
    assert shapes["gmh_l0_shared_down_w"] == (64, 64)
    assert shapes["gmh_l0_gate_w"] == (4, 64, 32)
    assert "gmh_lm_head" not in shapes and "gmh_l2_in_w" not in shapes
    assert shapes["gmh_l0_in_w"] == (64, 2 * 128 + 2 * 32 + 8)


# -- (d) the engine ------------------------------------------------------------------

_ANSWERS = {}


@pytest.mark.parametrize("rows", [None, 2, 1])
def test_engine_serves_the_tiny_model(rows):
    """Exact accounting, no compile after warm-up, answers of the asked
    length, both kinds of state planted with their own shapes and types,
    the scan's statistics on the monitor under ``ssm_*`` (and nothing
    under the delta rule's ``gdn_*``); with a prefill that carries every
    slot, two sequences, or one. Same weights, same prompts, greedy: the
    answers do not depend on how many sequences a prefill carries. Eight
    requests on four slots: every slot is refilled."""
    cfg = GraniteMoeHybridConfig.tiny()
    with un.guard():
        net = build_granite_moe_hybrid_generative(
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
    names = ("moe_dropped_assignments_total", "ssm_tokens_total",
             "ssm_calls_total", "gdn_tokens_total", "gdn_calls_total")
    before = {n: count(n) for n in names}
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
    assert sorted(set(kinds.values())) == ["full", "recurrent"]
    assert sum(k == "recurrent" for k in kinds.values()) == 2 * 4
    for n, kind in kinds.items():
        v = scope.find_var(n)
        if kind == "full":
            assert v.shape == (4, 2, 64, 16) and v.dtype == BF16
        else:
            assert v.shape in ((4, 8, 16, 32), (4, 3, 192)) \
                and v.dtype == np.float32
    for n in ("moe_dropped_assignments_total", "gdn_tokens_total",
              "gdn_calls_total"):
        assert count(n) == before[n]
    fams = monitor.get_registry().to_dict()
    # the scan advanced every prompt row once (prefill) and every answer
    # token but each request's first once (decode), in each Mamba-2 layer
    n_prompt = sum(n for n, _ in sizes)
    n_decode = sum(m - 1 for _, m in sizes)
    assert {v["labels"]["layer"] for v in
            fams["ssm_tokens_total"]["values"]} == {"0", "1", "3", "4"}
    grew = count("ssm_tokens_total") - before["ssm_tokens_total"]
    assert 4 * (n_prompt + n_decode) <= grew \
        <= 4 * (n_prompt + n_decode + 4 * 8)
    assert count("ssm_tokens_total", phase="prefill") >= 4 * n_prompt
    assert count("ssm_calls_total") > before["ssm_calls_total"]
    routes = {(v["labels"]["op"], v["labels"]["route"]) for v in
              fams["kernel_route_total"]["values"]}
    assert ("mamba2_scan", "primitive") in routes


def test_the_answers_are_the_references_greedy_continuations():
    """What the engine served (a prefill of two sequences, chained decode)
    is, token for token, what the reference's full pass picks: a slot
    refilled, an idle slot, prompts of unequal length in a bucket."""
    cfg = GraniteMoeHybridConfig.tiny(dtype="float32")
    with un.guard():
        net = build_granite_moe_hybrid_generative(
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


def test_the_builder_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        GraniteMoeHybridConfig.tiny(layer_types=(MAMBA, "rwkv"))
    with pytest.raises(ValueError, match="mamba_n_groups"):
        GraniteMoeHybridConfig.tiny(mamba_n_groups=2)
    with pytest.raises(ValueError, match="heads of"):
        GraniteMoeHybridConfig.tiny(mamba_n_heads=4)
    with pytest.raises(ValueError, match="prompt buckets"):
        build_granite_moe_hybrid_generative(prompt_buckets=(128,), max_seq=64)
