"""The qwen3_next decoder (``models/qwen3_next.py``): Gated DeltaNet
layers with a recurrent state beside a gated-attention K/V cache, softmax
routing with a gated shared expert — against the benchmark's plain
reference (``benchmark/reference/qwen3_next.py``: f32, HIGHEST, a token at
a time, no cache, nothing of the program imported), at small sizes on the
CPU with seeded weights.

Tolerances, and why. With f32 storage the program's products are the
CPU's f32 products and differ from the reference's in the order of
accumulation only (the chunked scan against a token loop, a cache against
a full pass): 3e-4 on logits of order 1. With bf16 storage every matmul
operand is rounded to 8 bits of mantissa; over four layers that reads 6e-3
to 5e-2 here (logits of order 1.4), so 8e-2 passes it, and the same
reference computed in fp8 operands reads 0.15 or more on every row and
fails it. Where two router
scores lie closer than the rounding upstream of them the k-th place goes
to another expert and the row moves by a whole expert's output, so the
bf16 comparison is on the 90th percentile of the rows' errors (as
``tests/test_cohere_moe.py`` has it); the f32 comparisons are on every
row. A recurrent state kept in bf16 between tokens fails the f32
tolerance on every row, on most by more than an order.
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
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig, _block,
                                          build_qwen3_next_generative)

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import qwen3_next as ref                 # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

BF16 = ml_dtypes.bfloat16
F32_TOL, BF16_TOL = 3e-4, 8e-2


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "full_attention_interval": cfg.full_attention_interval,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "num_experts_per_tok": cfg.top_k,
            "expert_offset": cfg.expert_offset,
            "rms_norm_eps": cfg.rms_norm_eps}


def _session(cfg, seed=3, **geometry):
    """The builder's programs, and seeded weights drawn as the benchmark
    draws them (norm scales and decay rates away from their neutral
    values), planted in the scope."""
    with un.guard():
        net = build_qwen3_next_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    rng = np.random.default_rng(seed)
    params = {}
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_a_log"):
            w = rng.uniform(np.log(0.25), np.log(2.0), have.shape)
        elif p.name.endswith("_dt_bias"):
            w = rng.uniform(-4.0, -2.0, have.shape)
        elif p.name.endswith("gnorm_scale"):
            w = rng.uniform(0.9, 1.1, have.shape)
        elif p.name.endswith("_scale"):
            w = rng.uniform(-0.1, 0.1, have.shape)
        elif p.name.endswith("_conv_w"):
            w = rng.normal(size=have.shape) * 0.5
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
    return Qwen3NextConfig.tiny(dtype=dtype, initializer_range=0.05, **over)


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
    # the idle slot's gate was never opened: its state is what it was
    for n, before in idle.items():
        np.testing.assert_array_equal(np.asarray(scope.find_var(n))[1],
                                      before)


def test_a_refilled_slot_starts_from_its_own_prompt():
    """Slot 1 is filled, decoded, refilled twice with other prompts while
    slot 0 keeps decoding: each refill overwrites the recurrent state and
    the tail (nothing is added to what the slot held), and the neighbour
    does not notice."""
    cfg = _tiny("float32")
    net, exe, scope, params = _session(
        cfg, batch_slots=2, max_seq=64, page_size=8, prompt_buckets=(32,),
        prefill_rows=1)
    rng = np.random.default_rng(5)
    rc = _ref_cfg(cfg)
    mine = rng.integers(1, cfg.vocab_size, 17)
    got = _serve(net, exe, scope, 32, [mine], [0], 2)[0]
    neighbour = [got[1]]
    for L in (9, 30, 3):
        p = rng.integers(1, cfg.vocab_size, L)
        served = _serve(net, exe, scope, 32, [p], [1], 3)
        assert _row_errors(served, [p], [1], params, rc, 3)[-1] < F32_TOL
        # slot 0 decoded 3 more tokens meanwhile
        neighbour.append(np.asarray(
            scope.find_var("qn_gen_tokens"))[0].copy())
    # slot 0's whole answer is the reference's greedy continuation
    ids = np.concatenate([mine, got[1]])
    for _ in range(9):
        nxt = int(np.argmax(np.asarray(
            ref.logits(params, jnp.asarray(ids), rc))[-1]))
        ids = np.append(ids, nxt)
    assert int(neighbour[-1][0]) == int(ids[len(mine) + 2 + 9])


def test_a_bf16_recurrent_state_fails_the_f32_tolerance():
    """The reference with its state rounded to bf16 between tokens, against
    itself in f32, over 300 rows: the control a tolerance has to catch."""
    cfg = _tiny("float32", num_layers=3)
    net, exe, scope, params = _session(
        cfg, batch_slots=1, max_seq=8, page_size=8, prompt_buckets=(8,))
    ids = jnp.asarray(np.random.default_rng(2).integers(1, 128, 300))
    rc = _ref_cfg(cfg)
    full = np.asarray(ref.logits(params, ids, rc))
    low = np.asarray(ref.logits(params, ids, rc, state_dtype=jnp.bfloat16))
    err = np.abs(full - low).max(-1)[-100:]
    assert err.min() > 5 * F32_TOL and np.median(err) > 30 * F32_TOL


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

def _one_layer(cfg, i, x, positions, lens, params):
    """``_block`` of layer ``i`` alone on whole sequences ``x`` [R, S, H],
    with this share's parameters planted."""
    R, S, _ = x.shape
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        data = lambda n, a: layers.data(n, shape=list(a.shape),
                                        dtype=str(a.dtype),
                                        append_batch_size=False)
        mask = (np.arange(S)[None] < lens[:, None]).astype(np.float32)
        xv, pv, mv = data("x", x), data("pos", positions), data("mask", mask)
        state = layers.create_global_var(
            [R, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
             cfg.linear_value_head_dim], 0.0, "float32", persistable=True)
        tail = layers.create_global_var(
            [R, cfg.linear_conv_kernel_dim - 1, cfg.conv_channels], 0.0,
            "float32", persistable=True)
        bias = layers.unsqueeze(
            layers.scale(mv, scale=10000.0, bias=-10000.0), [1, 2])

        def attend(i, q, k, v):
            return layers.fused_multihead_attention(
                q, k, v, bias_qk=bias, causal=True,
                scale=cfg.head_dim ** -0.5, is_test=True)

        def recur(i, mixed, conv_w, a, b, a_log, dt_bias):
            return layers.gated_delta_rule(
                mixed, conv_w, a, b, a_log, dt_bias, state, tail, mv,
                cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                cfg.linear_key_head_dim, cfg.linear_value_head_dim)

        y, _, _ = _block(xv, i, cfg, pv, mv, _Mix(attend, recur))
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


@pytest.mark.parametrize("i", [0, 3])
def test_the_two_shares_add_up_to_the_uncut_layer(i):
    """16 experts over 2 chips of 8: what each share's layer adds to the
    stream beyond the mixer and the shared expert (which both compute
    alike) is its experts' part; the two parts, with the mixer and the
    shared expert counted once, are the reference's layer with every
    expert held. Layer 0 is linear, layer 3 full. f32 storage."""
    base = dict(dtype="float32", initializer_range=0.05)
    full = Qwen3NextConfig.tiny(experts_held=16, **base)
    net, _, _, params = _session(full, batch_slots=1, max_seq=8,
                                 page_size=8, prompt_buckets=(8,))
    params = {k: np.asarray(v) for k, v in params.items()}
    rng = np.random.default_rng(5)
    R, S, H = 2, 24, full.hidden_size
    x = rng.normal(size=(R, S, H)).astype(np.float32)
    lens = np.array([24, 13])
    pos = np.tile(np.arange(S, dtype=np.int64), (R, 1))
    shares = [_one_layer(Qwen3NextConfig.tiny(experts_held=8,
                                              expert_offset=off, **base),
                         i, x, pos, lens, params) for off in (0, 8)]
    none = _one_layer(Qwen3NextConfig.tiny(experts_held=8, expert_offset=0,
                                           **base), i, x, pos, lens,
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
        np.testing.assert_allclose(got[r, :lens[r]], want, atol=F32_TOL)
    assert np.abs(shares[0] - none).max() > 0.01     # the experts did speak


# -- (e) one expert op, two score functions -------------------------------------

@pytest.mark.parametrize("score_fn", ["sigmoid", "softmax"])
@pytest.mark.parametrize("flash", ["auto", "always"])
def test_one_expert_op_scores_by_sigmoid_or_softmax(score_fn, flash):
    """The same ``moe_experts`` with either score function, on both routes,
    against the formula: the top_k of the scores, normalised over the
    chosen, times each held expert's gated feed-forward."""
    rng = np.random.default_rng(4)
    T, H, F, E, Eh, off, k = 16, 128, 32, 16, 8, 4, 4
    h = rng.normal(size=(T, H)).astype(np.float32)
    w = lambda *s: (rng.normal(size=s) * 0.1).astype(np.float32)
    wr, wg, wu, wd = w(H, E), w(Eh, H, F), w(Eh, H, F), w(Eh, F, H)
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            data = lambda n, a: layers.data(n, shape=list(a.shape),
                                            dtype="float32",
                                            append_batch_size=False)
            out, stats = layers.moe_experts(
                data("h", h), data("wr", wr), data("wg", wg), data("wu", wu),
                data("wd", wd), num_experts=E, top_k=k, expert_offset=off,
                score_fn=score_fn)
        got, st = fluid.Executor(fluid.CPUPlace()).run(
            main, feed=dict(h=h, wr=wr, wg=wg, wu=wu, wd=wd),
            fetch_list=[out, stats])
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    logit = h.astype(np.float64) @ wr
    if score_fn == "sigmoid":
        s = 1 / (1 + np.exp(-logit))
    else:
        s = np.exp(logit - logit.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
    top = np.argsort(-s, axis=-1, kind="stable")[:, :k]
    want = np.zeros((T, H))
    silu = lambda a: a / (1 + np.exp(-a))
    for t in range(T):
        for e in top[t]:
            if off <= e < off + Eh:
                y = (silu(h[t] @ wg[e - off]) * (h[t] @ wu[e - off])) \
                    @ wd[e - off]
                want[t] += s[t, e] / s[t, top[t]].sum() * y
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert st[-2] == T * k and st[-1] == 0


# -- (d) the engine ------------------------------------------------------------------

_ANSWERS = {}


@pytest.mark.parametrize("rows", [None, 2, 1])
def test_engine_serves_the_tiny_model(rows):
    """Exact accounting, no compile after warm-up, answers of the asked
    length, both kinds of state planted with their own shapes and types,
    the rule's and the expert op's statistics on the monitor; with a
    prefill that carries every slot, two sequences, or one.
    Same weights, same prompts, greedy: the answers do not depend on how
    many sequences a prefill carries. Eight requests on four slots: every
    slot is refilled."""
    cfg = Qwen3NextConfig.tiny()
    with un.guard():
        net = build_qwen3_next_generative(
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
    before = {n: count(n) for n in ("moe_dropped_assignments_total",
                                    "gdn_tokens_total", "gdn_calls_total")}
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
    for n, kind in kinds.items():
        v = scope.find_var(n)
        if kind == "full":
            assert v.shape == (4, 2, 64, 16) and v.dtype == BF16
        else:
            assert v.shape in ((4, 4, 16, 16), (4, 3, 128)) \
                and v.dtype == np.float32
    assert count("moe_dropped_assignments_total") == \
        before["moe_dropped_assignments_total"]
    fams = monitor.get_registry().to_dict()
    assert {v["labels"]["kind"] for v in
            fams["serving_kv_cache_bytes"]["values"]} >= {"recurrent", "full"}
    # the rule advanced every prompt row once (prefill) and every answer
    # token but each request's first once (decode), in each linear layer
    n_prompt = sum(n for n, _ in sizes)
    n_decode = sum(m - 1 for _, m in sizes)
    assert {v["labels"]["layer"] for v in
            fams["gdn_tokens_total"]["values"]} == {"0", "1", "2"}
    grew = count("gdn_tokens_total") - before["gdn_tokens_total"]
    assert 3 * (n_prompt + n_decode) <= grew <= 3 * (n_prompt + n_decode + 4 * 8)
    assert count("gdn_calls_total") > before["gdn_calls_total"]


def test_the_answers_are_the_references_greedy_continuations():
    """What the engine served in the test above (whatever its prefill
    carried) is, token for token, what the reference's full pass picks: a
    slot refilled, an idle slot, prompts of unequal length in a bucket."""
    cfg = Qwen3NextConfig.tiny(dtype="float32")
    with un.guard():
        net = build_qwen3_next_generative(
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


@pytest.mark.parametrize("expected,tm", [(0, 16), (1, 16), (4, 16), (16, 16),
                                         (20, 32), (60, 64), (128, 128),
                                         (160, 256), (5000, 256)])
def test_a_tile_holds_the_rows_an_expert_expects(expected, tm):
    """The grouped matmul's tile: one packed sublane tile of 16 rows in a
    decode step (1.25 rows an expert here, 4 in Command A+'s), 256 in a
    large prefill, and between them the power of two that holds an
    expert's rows, so its weights are not read once for every 16 rows."""
    from paddle_tpu.ops.moe import _tile_rows

    assert _tile_rows(expected) == tm
