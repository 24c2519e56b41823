"""The cohere2_moe decoder (``models/cohere_moe.py``) and the ops it
brought — rotary, routed experts that are told which experts they hold,
grouped-query window attention — against the benchmark's plain reference
(``benchmark/reference/cohere2_moe.py``: f32, HIGHEST, no cache, nothing
of the program imported), at small sizes on the CPU with seeded weights.

Tolerances, and why. With f32 storage the program's products are the
CPU's f32 products and differ from the reference's only in the order of
accumulation: 2e-4 on numbers of order 1. With bf16 storage every matmul
operand is rounded to 8 bits of mantissa (2^-9 relative) and projection
outputs once more; over four layers that reads 1e-2 to 3e-2 on logits of
order 1 here, so 6e-2 passes it, and the same reference computed in fp8
operands (2^-4 relative), the nearest precision below, reads 0.2 or more
on every row and fails it. One thing no tolerance covers: where two router
scores lie closer than the rounding of what feeds them, the k-th place
goes to another expert and the row moves by a whole expert's output (0.09
and 0.4 here). At these widths (64 dims, 4 of 16 experts) that is one row
in twenty, so the bf16 comparison is on the 90th percentile of the rows'
errors; the f32 comparisons are on every row.
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
from paddle_tpu.models.cohere_moe import (CohereMoeConfig,
                                          build_cohere_moe_generative)
from paddle_tpu.models.decoder import ffn as _ffn

# the benchmark's directory is on the path only while its reference is
# imported: it has a ``tools`` package of its own, which would shadow the
# repository's for every test this process runs later
_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import cohere2_moe as ref                # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

BF16 = ml_dtypes.bfloat16
F32_TOL, BF16_TOL = 2e-4, 6e-2


def _run(build, feed, flash="auto"):
    """One program built by ``build()`` (returns its fetches), run once."""
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            fetches = build()
        exe = fluid.Executor(fluid.CPUPlace())
        return exe.run(main, feed=feed, fetch_list=list(fetches))
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})


def _data(name, a):
    return layers.data(name, shape=list(a.shape), dtype=str(a.dtype),
                       append_batch_size=False)


# -- (a) the ops alone ---------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 8e-3)])
def test_rotary_matches_the_interleaved_pair_formula(dtype, tol):
    """f32: the pair swap is an exact product and the blend one rounding;
    bf16: the result is rounded to 8 bits once."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 3, 5, 16)).astype(np_dtype(dtype))
    pos = rng.integers(0, 5000, size=(2, 5)).astype(np.int64)
    got, = _run(lambda: [layers.rotary_embedding(
        _data("x", x), _data("pos", pos), theta=50000.0)],
        {"x": x, "pos": pos})
    want = np.stack([ref.rotary(jnp.asarray(x[b], jnp.float32),
                                jnp.asarray(pos[b]), 50000.0)
                     for b in range(2)])
    assert got.dtype == x.dtype
    np.testing.assert_allclose(got.astype(np.float32), want, atol=tol)


def _expert_case(case, rng, T=16, H=128, F=128, E=16):
    x = rng.normal(size=(T, H)).astype(np.float32)
    wr = (rng.normal(size=(H, E)) * 0.3).astype(BF16)
    if case == "ties":          # experts 4 and 5 score alike for every row
        wr[:, 5] = wr[:, 4]
    if case == "starved":       # expert 5 is nobody's choice
        x[:, 0] = 5.0
        wr[0, 5] = -10.0
    w = lambda *s: (rng.normal(size=s) * 0.1).astype(BF16)
    return x, wr, w(E, H, F), w(E, H, F), w(E, F, H)


@pytest.mark.parametrize("flash", ["never", "always"])
@pytest.mark.parametrize("case", ["random", "ties", "starved"])
def test_routed_experts_compute_the_held_part(case, flash):
    """Experts 4..7 of 16 held, top 4: the op's output is the reference's
    sum over those four, on the primitive route and through the Pallas
    kernels (interpreted). 3e-2: bf16 operands against the f32 reference
    on outputs of order 1."""
    x, wr, wg, wu, wd = _expert_case(case, np.random.default_rng(7))
    off, Eh, k = 4, 4, 4
    feed = dict(x=x, wr=wr, wg=wg[off:off + Eh], wu=wu[off:off + Eh],
                wd=wd[off:off + Eh])
    out, stats = _run(lambda: layers.moe_experts(
        *(_data(n, feed[n]) for n in ("x", "wr", "wg", "wu", "wd")),
        num_experts=16, top_k=k, expert_offset=off), feed, flash)
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    cfg = {"num_experts_per_tok": k, "expert_offset": off}
    # the reference indexes the stacked weights by held position
    want = ref.routed_part(jnp.asarray(x), {
        "p_router_w": jnp.asarray(wr), "p_gate_w": jnp.asarray(feed["wg"]),
        "p_up_w": jnp.asarray(feed["wu"]),
        "p_down_w": jnp.asarray(feed["wd"])}, "p", cfg, mm)
    np.testing.assert_allclose(out, want, atol=3e-2)
    idx, _ = ref.route(jnp.asarray(x), wr, k)
    counts = [(np.asarray(idx) == off + e).sum() for e in range(Eh)]
    assert stats.tolist() == counts + [16 * k, 0]
    if case == "starved":
        assert counts[1] == 0
    if case == "ties":          # the lower index wins the last place
        assert counts[0] >= counts[1]


@pytest.mark.parametrize("flash", ["never", "always"])
def test_masked_rows_are_routed_nowhere(flash):
    """Padding and the rows of slots a dispatch does not serve: no
    assignment, no count, an output of exactly 0; the real rows are what
    they are without the mask."""
    x, wr, wg, wu, wd = _expert_case("random", np.random.default_rng(9))
    mask = (np.arange(16) % 3 != 0).astype(np.float32)
    feed = dict(x=x, wr=wr, wg=wg[:4], wu=wu[:4], wd=wd[:4], mask=mask)
    names = ("x", "wr", "wg", "wu", "wd")

    def build(masked):
        return layers.moe_experts(
            *(_data(n, feed[n]) for n in names), num_experts=16, top_k=4,
            token_mask=_data("mask", mask) if masked else None)

    out, stats = _run(lambda: build(True), feed, flash)
    full, full_stats = _run(lambda: build(False),
                            {n: feed[n] for n in names}, flash)
    real = mask > 0
    assert np.all(out[~real] == 0.0) and np.abs(out[real]).max() > 0.01
    np.testing.assert_allclose(out[real], full[real], atol=1e-6)
    assert stats[-2] == 4 * real.sum() and full_stats[-2] == 4 * 16
    assert stats[:4].sum() < full_stats[:4].sum() and stats[-1] == 0


def _naive_attention(q, k, v, lengths, window, scale):
    """q [B, Hq, Sq, D] at positions lengths-Sq..lengths-1 against
    k, v [B, Hkv, Sk, D] holding positions 0..Sk-1."""
    B, Hq, Sq, D = q.shape
    G = Hq // k.shape[1]
    k, v = np.repeat(k, G, axis=1), np.repeat(v, G, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qpos = (lengths[:, None] - Sq + np.arange(Sq))[:, None, :, None]
    kpos = np.arange(k.shape[2])[None, None, None, :]
    seen = (kpos <= qpos) & ((qpos - kpos < window) if window else True)
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("flash", ["never", "always"])
@pytest.mark.parametrize("window", [0, 48])
def test_prefill_attention_grouped_query_and_window(window, flash):
    rng = np.random.default_rng(3)
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    q, k, v = (rng.normal(size=(B, h, S, D)).astype(np.float32)
               for h in (Hq, Hkv, Hkv))
    got, = _run(lambda: [layers.fused_multihead_attention(
        _data("q", q), _data("k", k), _data("v", v), causal=True,
        is_test=True, window=window)], dict(q=q, k=k, v=v), flash)
    want = _naive_attention(q, k, v, np.full(B, S), window, D ** -0.5)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("flash", ["never", "always"])
@pytest.mark.parametrize("ring", [False, True])
def test_decode_attention_grouped_query_and_ring(ring, flash):
    """One step at per-sequence positions; with a window the cache is a
    ring of 16 rows and the positions have passed it, so the new row
    overwrites the oldest and every row is visible."""
    rng = np.random.default_rng(4)
    B, Hq, Hkv, S, D = 3, 4, 2, 16, 32
    window = 16 if ring else 0
    pos = np.array([[37], [16], [5]] if ring else [[9], [0], [15]],
                   np.int64)
    hist = rng.normal(size=(2, B, Hkv, 64, D)).astype(np.float32)
    cache = np.zeros((2, B, Hkv, S, D), np.float32)
    for b in range(B):          # positions before pos[b], where they live
        for p in range(max(0, pos[b, 0] - S + 1) if ring else 0, pos[b, 0]):
            cache[:, b, :, p % S] = hist[:, b, :, p]
    q = rng.normal(size=(B, Hq, 1, D)).astype(np.float32)
    new = hist[np.arange(2)[:, None], np.arange(B), :, pos[:, 0]][
        :, :, :, None]                                   # [2, B, Hkv, 1, D]
    feed = dict(q=q, kn=new[0], vn=new[1], ck=cache[0], cv=cache[1], pos=pos)

    def build():
        ck, cv = _data("ck", cache[0]), _data("cv", cache[1])
        out = layers.fused_decode_attention(
            _data("q", q), _data("kn", new[0]), _data("vn", new[1]), ck, cv,
            _data("pos", pos), page_size=8, window=window)
        return out, ck

    got, ck2 = _run(build, feed, flash)
    want = np.stack([_naive_attention(
        q[b:b + 1], hist[0, b:b + 1, :, :pos[b, 0] + 1],
        hist[1, b:b + 1, :, :pos[b, 0] + 1], pos[b] + 1, window,
        D ** -0.5)[0] for b in range(B)])
    np.testing.assert_allclose(got, want, atol=2e-5)
    for b in range(B):
        np.testing.assert_array_equal(ck2[b, :, pos[b, 0] % S],
                                      hist[0, b, :, pos[b, 0]])


# -- (b) prefill, then decode through the cache ------------------------------

def _session(cfg, **geometry):
    with un.guard():
        net = build_cohere_moe_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    params = {p.name: jnp.asarray(scope.find_var(p.name))
              for p in net["decode"]["main"].global_block.all_parameters()}
    return net, exe, scope, params


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "layer_types": list(cfg.layer_types),
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size,
            "num_experts_per_tok": cfg.top_k,
            "num_shared_experts": cfg.num_shared_experts,
            "expert_offset": cfg.expert_offset,
            "sliding_window": cfg.sliding_window,
            "rope_theta": cfg.rope_theta,
            "layer_norm_eps": cfg.layer_norm_eps,
            "logit_scale": cfg.logit_scale}


def _served_logits(net, exe, scope, bucket, prompts, steps):
    """Prefill the prompts, decode ``steps`` tokens greedily; the logits
    of the prefill's last row and of every step, and the tokens chosen."""
    B = net["batch_slots"]
    feed = {"prompt_ids": np.zeros((B, bucket), np.int64),
            "prompt_pos": np.tile(np.arange(bucket, dtype=np.int64), (B, 1)),
            "prompt_mask": np.zeros((B, bucket), np.float32),
            "prompt_len": np.ones((B, 1), np.int64),
            "slot_mask": np.ones((B, 1), np.float32),
            "slot_ids": np.arange(B, dtype=np.int64)[:, None]}
    for b, p in enumerate(prompts):
        feed["prompt_ids"][b, :len(p)] = p
        feed["prompt_mask"][b, :len(p)] = 1.0
        feed["prompt_len"][b, 0] = len(p)
    pf, dec = net["prefill"][bucket], net["decode"]
    lg, tok = exe.run(pf["main"], feed=feed, scope=scope,
                      fetch_list=[pf["last_logits"], pf["first_token"]])
    logits, toks = [lg], [tok.copy()]
    for _ in range(steps):
        lg, tok = exe.run(dec["main"], feed={}, scope=scope,
                          fetch_list=[dec["logits"], dec["next_token"]])
        logits.append(lg)
        toks.append(tok.copy())
    return np.stack(logits, 1), np.concatenate(toks, 1)     # [B, 1+steps, V]


def _against_reference(cfg, geometry, bucket, prompt_lens, steps, seed=11,
                       control=False):
    net, exe, scope, params = _session(cfg, **geometry)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, L) for L in prompt_lens]
    served, toks = _served_logits(net, exe, scope, bucket, prompts, steps)
    rc = _ref_cfg(cfg)
    rows_of = {"program": [], "fp8": []}      # each row's largest logit error
    for b, p in enumerate(prompts):
        ids = jnp.asarray(np.concatenate([p, toks[b, :-1]]))
        rows = slice(len(p) - 1, len(p) + steps)
        full = np.asarray(ref.logits(params, ids, rc))[rows]
        rows_of["program"] += list(np.abs(served[b] - full).max(-1))
        if control:
            low = np.asarray(ref.logits(params, ids, rc, "fp8"))[rows]
            rows_of["fp8"] += list(np.abs(low - full).max(-1))
    return {k: np.sort(v) for k, v in rows_of.items()}


def _p90(rows):
    return rows[int(0.9 * (len(rows) - 1))]


CASES = {
    # name: (dtype, window, max_seq, page, bucket, prompt lengths, steps)
    "inside_the_window_f32": ("float32", 64, 32, 8, 16, (5, 16, 9), 6),
    "inside_the_window_bf16": ("bfloat16", 64, 32, 8, 16, (5, 16, 9), 6),
    "ring_wraps_f32": ("float32", 16, 64, 8, 16, (14, 3, 16), 12),
    "ring_wraps_bf16": ("bfloat16", 16, 64, 8, 16, (14, 3, 16), 12),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_equals_the_reference_full_pass(case):
    dtype, window, max_seq, page, bucket, lens, steps = CASES[case]
    cfg = CohereMoeConfig.tiny(sliding_window=window, dtype=dtype,
                               initializer_range=0.15)
    rows = _against_reference(
        cfg, dict(batch_slots=len(lens), max_seq=max_seq, page_size=page,
                  prompt_buckets=(bucket,)), bucket, lens, steps)["program"]
    if dtype == "float32":
        assert rows[-1] < F32_TOL
    else:
        assert _p90(rows) < BF16_TOL


def test_decode_crosses_the_published_window_of_4096():
    """Tiny widths at the published window: a prompt of 4,090 tokens in a
    bucket of 4,096, then 12 steps; the sliding layer's ring of 4,096 rows
    wraps at position 4,096 while the full layer keeps all 4,224."""
    cfg = CohereMoeConfig(
        vocab_size=64, hidden_size=32, num_layers=2,
        layer_types=("sliding_attention", "full_attention"), num_heads=2,
        num_kv_heads=1, head_dim=16, intermediate_size=16, num_experts=8,
        top_k=2, num_shared_experts=1, experts_held=2, sliding_window=4096,
        dtype="float32", initializer_range=0.2)
    rows = _against_reference(
        cfg, dict(batch_slots=1, max_seq=4224, page_size=128,
                  prompt_buckets=(4096,)), 4096, (4090,), 12)["program"]
    assert len(rows) == 13 and rows[-1] < F32_TOL


# -- (e) a lower precision fails where the configuration's passes ------------

def test_fp8_operands_fail_the_tolerance_that_bf16_passes():
    dtype, window, max_seq, page, bucket, lens, steps = CASES[
        "ring_wraps_bf16"]
    cfg = CohereMoeConfig.tiny(sliding_window=window, dtype=dtype,
                               initializer_range=0.15)
    rows = _against_reference(
        cfg, dict(batch_slots=len(lens), max_seq=max_seq, page_size=page,
                  prompt_buckets=(bucket,)), bucket, lens, steps,
        control=True)
    assert _p90(rows["program"]) < BF16_TOL < rows["fp8"][0]


# -- (c) the shares add up to the uncut layer ----------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips of 2: the routed parts of all eight
    ``expert_offset``s, plus the shared experts (which every chip computes
    alike) counted once, are the reference's feed-forward with every
    expert held. f32 storage, so the sum is exact to accumulation order."""
    base = dict(dtype="float32", initializer_range=0.15, num_layers=1,
                layer_types=("full_attention",))
    full = CohereMoeConfig.tiny(experts_held=16, **base)
    rng = np.random.default_rng(5)
    T, H, F = 24, full.hidden_size, full.intermediate_size
    h = rng.normal(size=(1, T, H)).astype(np.float32)
    w = lambda *s: (rng.normal(size=s) * 0.15).astype(np.float32)
    P = "cmoe_l0"
    params = {f"{P}_router_w": w(H, 16), f"{P}_gate_w": w(16, H, F),
              f"{P}_up_w": w(16, H, F), f"{P}_down_w": w(16, F, H),
              f"{P}_shared_gate_w": w(H, 2 * F),
              f"{P}_shared_up_w": w(H, 2 * F),
              f"{P}_shared_down_w": w(2 * F, H)}
    routed, shared = [], []
    for off in range(0, 16, 2):
        cfg = CohereMoeConfig.tiny(experts_held=2, expert_offset=off, **base)
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            x = _data("h", h)
            r, s, _ = _ffn(x, x, P, cfg)
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(startup, scope=scope)
        for name, value in params.items():
            held = value[off:off + 2] if value.ndim == 3 else value
            assert scope.find_var(name).shape == held.shape
            scope.set_var(name, held)
        r, s = exe.run(main, feed={"h": h}, fetch_list=[r, s], scope=scope)
        routed.append(r[0])
        shared.append(s[0])
    for s in shared[1:]:
        np.testing.assert_array_equal(s, shared[0])
    rc = dict(_ref_cfg(full), expert_offset=0)
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    want = (ref.routed_part(jnp.asarray(h[0]), jp, P, rc, mm)
            + ref.shared_part(jnp.asarray(h[0]), jp, P, rc, mm))
    np.testing.assert_allclose(sum(routed) + shared[0], want, atol=F32_TOL)
    assert np.abs(want).max() > 0.05             # not a sum of zeros


_ANSWERS = {}       # (window, max_seq) -> the first variant's answers


# -- (d) the engine ---------------------------------------------------------------

@pytest.mark.parametrize("window,max_seq,rows", [(16, 64, None), (64, 32, 2),
                                                 (16, 64, 3)])
def test_engine_serves_the_tiny_model(window, max_seq, rows):
    """Exact accounting, no compile after warm-up, answers of the asked
    length, both kinds of cache planted in bf16 with their own row counts,
    and the expert op's statistics on the monitor; with a prefill that
    carries every slot, or 2 or 3 sequences a dispatch (six requests on
    four slots then take several dispatches). Same startup seed, same
    prompts, greedy: the answers do not depend on how many sequences a
    prefill carries."""
    cfg = CohereMoeConfig.tiny(sliding_window=window)
    with un.guard():
        net = build_cohere_moe_generative(
            cfg, batch_slots=4, max_seq=max_seq, page_size=8,
            prompt_buckets=(16,), prefill_rows=rows)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    assert eng.warm_up() == 2
    dropped = lambda: monitor.metric_value(
        "moe_dropped_assignments_total", 0.0)
    before = dropped()
    rng = np.random.default_rng(0)
    sizes = [(5, 9), (16, 12), (9, 3), (12, 14), (7, 11), (3, 1)]
    with eng:
        futs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
                for n, m in sizes]
        outs = [f.result(timeout=300)[0] for f in futs]
    assert [len(o) for o in outs] == [m for _, m in sizes]
    same = _ANSWERS.setdefault((window, max_seq), outs)
    assert all(np.array_equal(a, b) for a, b in zip(same, outs))
    assert eng.accounting()["exact"]
    assert eng.generation_stats()["decode_recompiles"] == 0
    rows = {n: scope.find_var(n).shape[2] for pair in net["cache_vars"]
            for n in pair}
    kinds = net["cache_kinds"]
    assert {rows[n] for n in rows if kinds[n] == "window"} == {
        min(window, max_seq)}
    assert {rows[n] for n in rows if kinds[n] == "full"} == {max_seq}
    assert all(scope.find_var(n).dtype == BF16 for n in rows)
    assert dropped() == before
    fams = monitor.get_registry().to_dict()
    share = fams["moe_local_assignment_share"]["values"][0]["value"]
    assert 0.0 < share < 0.5                    # 2 of 16 experts held
    assert {v["labels"]["kind"] for v in
            fams["serving_kv_cache_bytes"]["values"]} >= {"window", "full"}


def test_a_prompt_of_three_windows_is_folded_into_the_ring():
    """A window of 8 under a bucket of 32: prompts of three windows and
    more (and one inside the window) are served, the sliding layers' rings
    taking each sequence's last 8 positions at ``position % 8``; 20 decode
    steps wrap every ring twice more. Logits against the reference's full
    pass, as for a bucket inside the window."""
    cfg = CohereMoeConfig.tiny(sliding_window=8, dtype="float32",
                               initializer_range=0.15)
    rows = _against_reference(
        cfg, dict(batch_slots=4, max_seq=64, page_size=8,
                  prompt_buckets=(32,)), 32, (24, 5, 32, 27), 20)["program"]
    assert len(rows) == 4 * 21 and rows[-1] < F32_TOL
