"""The ``.hybrid`` per-layer metrics of ``qwen3-next-ep2.decode-long-prompts``:
the kernel-name patterns of their files against the names as the TPU
compiler prints them for this configuration (``tools/deviceless_stored.py
--config qwen3-next-ep2-serve --hlo``) and nothing else, the ops-and-bytes
functions of ``kernel_costs_hybrid.py`` against counts made by hand, the
roofline reader on a made-up window (and on a program without the
counters: nothing, no raise), and the fp8 control against the tiny
configuration's limit."""
import os

import numpy as np
import pytest

import harness
import kernel_costs_hybrid as costs
from readers import kernel_roofline, kernel_roofline_in, trace_op_share

CELL = "qwen3-next-ep2.decode-long-prompts"
BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CFG = harness.load_json(harness.HERE, "configs", "qwen3-next-ep2-serve.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NAMES = sorted(m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".hybrid"))
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in NAMES}

# left-hand sides and targets of the Mosaic calls in the compiled prefill
# and decode programs of a described v5e, with a fusion that reads one
HLO = {
    "scan": '%gdn_chunk_scan.5 = (f32[2,32,2048,128]{3,2,1,0:T(8,128)}, '
            'f32[2,32,128,128]{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c, '
            '%d, %e), custom_call_target="tpu_custom_call"',
    "step": '%gdn_decode_step.3 = (f32[64,4,8,128]{3,2,1,0:T(8,128)}, '
            'f32[64,32,128,128]{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c), '
            'custom_call_target="tpu_custom_call"',
    "gate_up": '%moe_expert_matmul.56 = bf16[4736,512]{1,0:T(8,128)(2,1)}'
               ' custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.9 = f32[4736,2048]{1,0:T(8,128)} '
            'custom-call(%a, %b, %moe_expert_matmul.8, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.28 = f32[64,512]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "decode": '%decode_attention.28 = bf16[128,16,256]{2,1,0:T(8,128)(2,1)'
              'S(1)} custom-call(%a, %b, %c, %d), '
              'custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.4 = (bf16[32,2048,256]{2,1,0}, '
             'f32[32,8,2048]{2,1,0}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"',
    "fusion": '%fusion.40 = f32[64,32,128]{2,1,0} '
              'fusion(f32[64,4,8,128]{3,2,1,0} %gdn_decode_step.3), '
              'kind=kLoop',
}


def cell_invariants(bench: dict) -> None:
    """What this file holds of ``BENCHMARK.json``, on the tree's or on one
    with further cells appended (``test_layer_metric_files.py``
    ``test_a_cell_can_be_appended``): no count of anything."""
    names = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".hybrid")}
    # every entry of the cell has its file; a file may wait for its entry
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))
        if n.endswith(".hybrid.json")}
    assert names and names <= files
    for m in bench["per_layer"]:
        if m["name"].endswith(".hybrid"):
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["decode_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-ep2-serve", "decode-long-prompts", 1)


def test_the_cell_has_its_hybrid_metrics_and_only_lists_itself():
    cell_invariants(BENCH)


@pytest.mark.parametrize("metric,hits", [
    ("gdn_scan_time_pct.hybrid", {"scan"}),
    ("gdn_scan_roofline_pct.hybrid", {"scan"}),
    ("gdn_step_time_pct.hybrid", {"step"}),
    ("gdn_step_roofline_pct.hybrid", {"step"}),
    ("expert_time_pct.hybrid", {"gate_up", "down"}),
    ("expert_matmul_roofline_pct.hybrid", {"gate_up", "down"}),
    ("decode_kernel_time_pct.hybrid", {"decode"}),
    ("flash_fwd_time_pct.hybrid", {"flash"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_the_costs_match_the_hand_counts():
    # one decode step of one linear layer, 64 slots: 32 heads x a 128 x 128
    # f32 state read and written, three 128 x 128 products a head
    ops, moved = costs.gdn_step_cost(64, 32, 128, 128)
    assert moved == 64 * 32 * 128 * 128 * 4 * 2 == 268_435_456
    assert ops == 64 * 32 * 3 * 2 * 128 * 128 == 201_326_592
    # the bytes bound it: 0.33 ms against a microsecond of multiplies
    assert moved / PEAKS["hbm_bytes_per_s"] > 100 * ops / PEAKS[
        "bf16_flops_per_s"]
    # a prefill of 8,192 real rows through one linear layer: 6 x 128 x 128
    # operations a head and row; q, k (16 heads), v, o (32 heads) in f32
    ops, moved = costs.gdn_scan_cost(8192, 16, 32, 128, 128)
    assert ops == 8192 * 32 * 6 * 128 * 128 == 25_769_803_776
    assert moved == 8192 * (2 * 2048 + 2 * 4096) * 4 == 402_653_184
    # one decode step of one layer: 64 x 10 choices, half local, some 183
    # of 256 held experts hit; an expert is three 2048 x 512 matrices
    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    assert (H, F, CFG["intermediate_size"]) == (2048, 512, 5120)
    ops, moved = costs.expert_matmul_cost(320, 183, H, F)
    assert moved == 183 * 3 * 2048 * 512 * 2 == 1_151_336_448
    assert ops == 2 * 3 * 2048 * 512 * 320


def _counters(decode_calls, prefill_calls):
    c = {}
    for layer in range(4):
        for phase, calls, tokens, hit in (
                ("decode", decode_calls, 320.0, 183.0),
                ("prefill", prefill_calls, 9000.0, 256.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["moe_expert_calls_total" + lab] = float(calls)
            c["moe_expert_tokens_total" + lab] = calls * tokens
            c["moe_experts_hit_total" + lab] = calls * hit
    for layer in range(3):
        for phase, calls, tokens in (("decode", decode_calls, 63.0),
                                     ("prefill", prefill_calls, 1800.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["gdn_calls_total" + lab] = float(calls)
            c["gdn_tokens_total" + lab] = calls * tokens
    return c


def test_roofline_reader_finds_its_cost_module(monkeypatch):
    counters = _counters(2000, 300)
    step, calls = costs.gdn_step_seconds(CFG, counters, PEAKS)
    assert calls == 3 * 2000
    assert step == pytest.approx(
        3 * 2000 * 63 * 32 * 128 * 128 * 4 * 2 / 819e9, rel=1e-6)
    scan, calls = costs.gdn_scan_seconds(CFG, counters, PEAKS)
    assert calls == 3 * 300
    assert scan == pytest.approx(
        3 * 300 * 1800 * (2 * 2048 + 2 * 4096) * 4 / 819e9, rel=1e-6)
    experts, calls = costs.moe_expert_matmul_seconds(CFG, counters, PEAKS)
    assert calls == 2 * 4 * 2300
    assert experts == pytest.approx(
        4 * 2000 * 183 * 3 * 2048 * 512 * 2 / 819e9
        + 4 * 300 * 256 * 3 * 2048 * 512 * 2 / 819e9, rel=1e-6)
    ops = [(HLO["step"], 0, 600_000), (HLO["step"], 9, 600_009),
           (HLO["scan"], 0, 5_000_000), (HLO["router"], 0, 100_000)]
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_in.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters}
    args = FILES["gdn_step_roofline_pct.hybrid"]["args"]
    got = kernel_roofline_in.read(ctx, **args)
    assert got == pytest.approx(100.0 * (step / 6000) / 600e-6)
    assert 0 < got < 100
    assert 0 < kernel_roofline_in.read(
        ctx, **FILES["gdn_scan_roofline_pct.hybrid"]["args"]) < 100
    # no matching operation in the trace, a program without the counters
    # (the parent commit), or no trace: nothing, and no raise
    for name in ("gdn_step_roofline_pct.hybrid",
                 "gdn_scan_roofline_pct.hybrid",
                 "expert_matmul_roofline_pct.hybrid"):
        args = FILES[name]["args"]
        assert kernel_roofline_in.read(dict(ctx, counters={}),
                                       **args) is None
        assert kernel_roofline_in.read(dict(ctx, trace=None), **args) is None
    assert kernel_roofline_in.read(
        ctx, **FILES["expert_matmul_roofline_pct.hybrid"]["args"]) is None


@pytest.mark.parametrize("tokens,tm,counts", [
    # a decode step: 1.25 rows an expert, most hit once, one with 17
    (64, 16, [1, 2, 0, 17, 1, 1, 3, 2] * 32),
    # a prefill of 4,096 tokens: 80 rows an expert, even and skewed
    (4096, 16, [80] * 256),
    (4096, 16, [1, 15, 16, 17, 400, 0, 70, 121] * 32),
])
def test_the_expert_cost_never_passes_what_the_kernel_itself_does(
        tokens, tm, counts):
    """As ``test_moe_metrics.py`` has it for Command A+, at this model's
    shapes: whole tiles of ``tm`` rows, a weight block read once for the
    consecutive tiles of its expert at the least."""
    from paddle_tpu.kernels.moe import gmm_blocks

    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    tiles = sum(-(-c // tm) for c in counts)
    hit = sum(1 for c in counts if c)
    done_ops = moved = 0
    for K, N, mats in ((H, F, 2), (F, H, 1)):
        tk, tn = gmm_blocks(K, N)
        done_ops += 2 * tm * K * N * mats * tiles
        moved += hit * mats * K * N * 2        # each hit expert, once
    ops, need = costs.expert_matmul_cost(sum(counts), hit, H, F)
    assert ops <= done_ops and need <= moved


def test_fp8_control_fails_the_tiny_limit_that_the_reference_passes():
    """Reference against reference at the rehearsal's sizes: the
    reference's own choices score 0, the fp8-operand control's lie further
    below the best than the tiny configuration's limit, and a recurrent
    state kept in bf16 moves the choices too."""
    import jax.numpy as jnp

    from reference import qwen3_next as ref

    cell = harness.Cell(BENCH, CELL, rehearse=True)
    limit = cell.config["check"]["logit_gap_limit"]
    model = ref.model_config(cell.config)
    w = dict(ref.make_weights(ref.param_spec(model), 11))
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(1, model["vocab_size"], 96), jnp.int32)
    best = jnp.argmax(ref.logits(w, ids, model), axis=-1).astype(jnp.int32)
    served, ctl = ref.gaps_fn(model, "fp8")(w, ids, best)
    assert float(jnp.max(served)) == 0.0
    assert float(jnp.max(ctl)) > limit, float(jnp.max(ctl))
    _, low = ref.gaps_fn(model, "state:bf16")(w, ids, best)
    assert float(jnp.max(low)) > 0.0
