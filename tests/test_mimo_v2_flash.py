"""The mimo_v2_flash decoder (``models/mimo_v2_flash.py``) and what it
brought, against the benchmark's plain reference
(``benchmark/reference/mimo_v2_flash.py``: f32, HIGHEST, no cache, no ring,
nothing of the program imported), at small sizes on the CPU with seeded
weights: window layers with a sink column beside full layers with other
head counts, keys wider than values, a prefill whose prompt passes the
window folded into the ring, and decode steps that wrap it.

Tolerances as ``tests/test_cohere_moe.py`` has them, and for its reasons:
f32 storage differs from the reference in the order of accumulation only
(2e-4 on logits of order 1); with bf16 storage the comparison is on the 90th
percentile of the rows' errors, since a router's k-th place can go to
another expert under rounding, and the reference in fp8 operands fails it.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.models.decoder import ffn as _ffn
from paddle_tpu.models.mimo_v2_flash import (MimoV2FlashConfig,
                                             build_mimo_v2_flash_generative)

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import mimo_v2_flash as ref              # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

F32_TOL, BF16_TOL = 2e-4, 6e-2
WINDOW = 8


def _tiny(**over):
    return MimoV2FlashConfig.tiny(initializer_range=0.15, **over)


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "hybrid_layer_pattern": list(cfg.layer_pattern),
            "moe_layer_freq": list(cfg.moe_layer_freq),
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "v_head_dim": cfg.v_head_dim,
            "swa_num_attention_heads": cfg.swa_num_heads,
            "swa_num_key_value_heads": cfg.swa_num_kv_heads,
            "swa_head_dim": cfg.swa_head_dim,
            "swa_v_head_dim": cfg.swa_v_head_dim,
            "rope_theta": cfg.rope_theta,
            "swa_rope_theta": cfg.swa_rope_theta,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "sliding_window": cfg.sliding_window,
            "attention_value_scale": cfg.value_scale,
            "add_swa_attention_sink_bias": cfg.swa_sink,
            "add_full_attention_sink_bias": cfg.full_sink,
            "num_experts_per_tok": cfg.top_k,
            "expert_offset": cfg.expert_offset,
            "layernorm_epsilon": cfg.rms_norm_eps}


def _session(cfg, **geometry):
    with un.guard():
        net = build_mimo_v2_flash_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    params = {p.name: jnp.asarray(scope.find_var(p.name))
              for p in net["decode"]["main"].global_block.all_parameters()}
    return net, exe, scope, params


def _prefill_feed(net, bucket, prompts, slots=None, rows=None):
    R = rows or net["batch_slots"]
    feed = {"prompt_ids": np.zeros((R, bucket), np.int64),
            "prompt_pos": np.tile(np.arange(bucket, dtype=np.int64), (R, 1)),
            "prompt_mask": np.zeros((R, bucket), np.float32),
            "prompt_len": np.ones((R, 1), np.int64),
            "slot_mask": np.zeros((R, 1), np.float32),
            "slot_ids": np.zeros((R, 1), np.int64)}
    for b, p in enumerate(prompts):
        feed["prompt_ids"][b, :len(p)] = p
        feed["prompt_mask"][b, :len(p)] = 1.0
        feed["prompt_len"][b, 0] = len(p)
        feed["slot_mask"][b, 0] = 1.0
        feed["slot_ids"][b, 0] = b if slots is None else slots[b]
    return feed


def _served_logits(net, exe, scope, bucket, prompts, steps):
    """Prefill the prompts, decode ``steps`` tokens greedily; the logits
    of the prefill's last row and of every step, and the tokens chosen."""
    pf, dec = net["prefill"][bucket], net["decode"]
    lg, tok = exe.run(pf["main"], feed=_prefill_feed(net, bucket, prompts),
                      scope=scope,
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


# prompts shorter than the window, equal to it, and several times it (one
# the whole bucket, one ending inside a ring's turn); 20 steps wrap a ring
# of 8 rows twice and more
GEOMETRY = dict(batch_slots=5, max_seq=64, page_size=8, prompt_buckets=(32,))
LENS, STEPS = (5, 8, 27, 32, 17), 20
CASES = {
    # name: (dtype, flash flag, configuration overrides)
    "f32": ("float32", "auto", {}),
    "f32_kernels": ("float32", "always", {}),
    "f32_keys_in_wider_rows": ("float32", "auto", {"key_cache_dim": 32}),
    "f32_sinks_on_both_kinds": ("float32", "auto", {"full_sink": True}),
    "bf16": ("bfloat16", "auto", {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_equals_the_reference_full_pass(case):
    """Prefill through the fold, then decode through ring and cache,
    against the reference's one full pass: on the generic routes, and with
    ``FLAGS_use_flash_attention=always`` through the three Pallas kernels
    in interpret mode (flash forward with its skipped blocks, the fold,
    the decode kernel)."""
    dtype, flash, over = CASES[case]
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        rows = _against_reference(_tiny(dtype=dtype, **over), GEOMETRY, 32,
                                  LENS, STEPS)["program"]
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    assert len(rows) == len(LENS) * (STEPS + 1)
    if dtype == "float32":
        assert rows[-1] < F32_TOL
    else:
        assert _p90(rows) < BF16_TOL


def test_a_bucket_inside_the_window_is_written_at_row_zero():
    """The same model under a window no bucket passes: nothing is folded
    (the statistics say so) and the logits are the reference's."""
    cfg = _tiny(dtype="float32", sliding_window=64)
    rows = _against_reference(
        cfg, dict(batch_slots=3, max_seq=64, page_size=8,
                  prompt_buckets=(16,)), 16, (5, 16, 9), 6)["program"]
    assert rows[-1] < F32_TOL


def test_fp8_operands_fail_the_tolerance_that_bf16_passes():
    rows = _against_reference(_tiny(dtype="bfloat16"), GEOMETRY, 32, LENS,
                              STEPS, control=True)
    assert _p90(rows["program"]) < BF16_TOL < rows["fp8"][0]


def test_the_sink_changes_the_logits():
    """Sinks drawn at 1.0 carry weight: the reference without them is
    another model, by far more than the tolerance."""
    cfg = _tiny(dtype="float32")
    net, exe, scope, params = _session(cfg, **GEOMETRY)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, 24))
    with_sink = np.asarray(ref.logits(params, ids, _ref_cfg(cfg)))
    without = np.asarray(ref.logits(
        params, ids, dict(_ref_cfg(cfg), add_swa_attention_sink_bias=False)))
    assert np.abs(with_sink - without).max() > 100 * F32_TOL


@pytest.mark.parametrize("flash", ["auto", "always"])
def test_a_prefill_row_leaves_every_other_slots_state_bit_identical(flash):
    """Two sequences of a dispatch of three rows name slots 3 and 1: the
    rings and caches of slots 0, 2 and 4 keep every bit, whatever they
    held, on the generic fold and on the Pallas one."""
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        cfg = _tiny(dtype="float32")
        with un.guard():
            net = build_mimo_v2_flash_generative(cfg, prefill_rows=3,
                                                 **GEOMETRY)
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(net["startup"], scope=scope)
        rng = np.random.default_rng(1)
        before = {}
        for name, (shape, dt) in net["state_vars"].items():
            v = (rng.normal(size=shape) if "kv" in name
                 else np.zeros(shape)).astype(np_dtype(dt))
            before[name] = v
            scope.set_var(name, v)
        prompts = [rng.integers(1, 128, n) for n in (29, 6)]
        pf = net["prefill"][32]
        exe.run(pf["main"], scope=scope, fetch_list=[pf["first_token"]],
                feed=_prefill_feed(net, 32, prompts, slots=(3, 1), rows=3))
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    changed = 0
    for pair in net["cache_vars"]:
        for name in pair:
            after = np.asarray(scope.find_var(name))
            for slot in (0, 2, 4):
                np.testing.assert_array_equal(after[slot],
                                              before[name][slot])
            changed += int((after[[1, 3]] != before[name][[1, 3]]).any())
    assert changed == 2 * cfg.num_layers


def test_the_state_table_has_a_pair_a_layer_by_its_kind():
    cfg = _tiny(key_cache_dim=32)
    with un.guard():
        net = build_mimo_v2_flash_generative(cfg, **GEOMETRY)
    sv, kinds = net["state_vars"], net["cache_kinds"]
    shapes = {(kinds[k], sv[k][0], sv[v][0]) for k, v in net["cache_vars"]}
    assert shapes == {
        ("full", (5, 1, 64, 32), (5, 1, 64, 16)),
        ("window", (5, 2, WINDOW, 32), (5, 2, WINDOW, 16))}
    # at the published widths a key of 192 numbers lies in 256 lanes
    k, v = MimoV2FlashConfig().cache_shapes(1, 128, 4096)
    assert (k, v) == ((128, 8, 128, 256), (128, 8, 128, 128))
    k, v = MimoV2FlashConfig().cache_shapes(0, 128, 4096)
    assert (k, v) == ((128, 4, 4096, 256), (128, 4, 4096, 128))


def _data(name, a):
    return fluid.layers.data(name, shape=list(a.shape), dtype=str(a.dtype),
                             append_batch_size=False)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 experts over 16 chips of 1: the routed parts of all sixteen
    ``expert_offset``s are the reference's feed-forward with every expert
    held (there is no shared expert, and attention, which every chip
    computes alike over its own streams, is counted once: it is not in
    the sum). f32 storage, so the sum is exact to accumulation order."""
    base = dict(dtype="float32", initializer_range=0.15)
    rng = np.random.default_rng(5)
    full = MimoV2FlashConfig.tiny(experts_held=16, **base)
    T, H, F = 24, full.hidden_size, full.intermediate_size
    h = rng.normal(size=(1, T, H)).astype(np.float32)
    w = lambda *s: (rng.normal(size=s) * 0.15).astype(np.float32)
    P = "mimo_l1"
    params = {f"{P}_router_w": w(H, 16), f"{P}_gate_w": w(16, H, F),
              f"{P}_up_w": w(16, H, F), f"{P}_down_w": w(16, F, H),
              f"{P}_router_bias": rng.uniform(-0.1, 0.1, 16).astype(
                  np.float32)}
    routed = []
    for off in range(16):
        cfg = MimoV2FlashConfig.tiny(experts_held=1, expert_offset=off,
                                     **base)
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            x = _data("h", h)
            r, s, _ = _ffn(x, x, P, cfg)
            assert s is None                    # no shared expert
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(startup, scope=scope)
        for name, value in params.items():
            held = value[off:off + 1] if value.ndim == 3 else value
            assert scope.find_var(name).shape == held.shape
            scope.set_var(name, held)
        routed.append(exe.run(main, feed={"h": h}, fetch_list=[r],
                              scope=scope)[0][0])
    rc = dict(_ref_cfg(full), expert_offset=0)
    mm = lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    want = ref.routed_part(jnp.asarray(h[0]), jp, P, rc, mm)
    np.testing.assert_allclose(sum(routed), want, atol=F32_TOL)
    assert np.abs(want).max() > 0.05             # not a sum of zeros
    # a share alone is a sixteenth of the experts, not the layer
    assert np.abs(routed[0] - want).max() > 0.05


@pytest.mark.parametrize("rows", [None, 2])
def test_engine_serves_prompts_past_the_window(rows):
    """Through ``GenerativeEngine``: exact accounting, no compile after
    warm-up, answers of the asked length, and the families this builder
    binds on the monitor: the router's, the fold's rows, the flash
    forward's blocks and the decode kernel's rows by kind of cache."""
    cfg = _tiny()
    with un.guard():
        net = build_mimo_v2_flash_generative(
            cfg, batch_slots=4, max_seq=64, page_size=8,
            prompt_buckets=(16, 32), prefill_rows=rows)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    assert eng.warm_up() == 3
    value = lambda name, **lab: monitor.metric_value(name, 0.0, **lab)
    before = {k: value("serving_prefill_window_rows_total", what=k)
              for k in ("kept", "dropped")}
    rng = np.random.default_rng(0)
    sizes = [(5, 9), (30, 12), (9, 20), (17, 14), (32, 11), (3, 1)]
    with eng:
        futs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
                for n, m in sizes]
        outs = [f.result(timeout=300)[0] for f in futs]
    assert [len(o) for o in outs] == [m for _, m in sizes]
    assert eng.accounting()["exact"]
    assert eng.generation_stats()["decode_recompiles"] == 0
    # two window layers, a K and a V ring each; a prompt keeps its last 8
    kept = sum(min(n, WINDOW) for n, _ in sizes) * 4
    dropped = sum(max(n - WINDOW, 0) for n, _ in sizes) * 4
    got = {k: value("serving_prefill_window_rows_total", what=k) - before[k]
           for k in ("kept", "dropped")}
    assert got == {"kept": kept, "dropped": dropped}
    fams = monitor.get_registry().to_dict()
    labels = lambda fam: {tuple(sorted(v["labels"].items()))
                          for v in fams[fam]["values"]}
    assert {(("kind", "full"),), (("kind", "window"),)} <= labels(
        "decode_attention_rows_total")
    assert (("kind", "window"), ("what", "skipped")) in labels(
        "flash_attention_blocks_total")
    assert {v["labels"]["kind"] for v in
            fams["serving_kv_cache_bytes"]["values"]} >= {"window", "full"}
    assert value("moe_dropped_assignments_total") == 0
