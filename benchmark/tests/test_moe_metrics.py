"""The ``.moe`` per-layer metrics: the kernel-name patterns of their files
against the names as the TPU compiler prints them for this configuration
(``tools/deviceless_stored.py --hlo``), the ops-and-bytes functions of
``kernel_costs.py`` against counts made by hand, the roofline reader on a
made-up window, and the bound that keeps a roofline share under 100%: for
the shapes of this cell the functions count no more than the kernel's own
tiling moves and multiplies."""
import pytest

import harness
import kernel_costs
from readers import kernel_roofline, trace_op_share

CFG = harness.load_json(harness.HERE, "configs",
                        "command-a-plus-ep8-serve.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in ("expert_time_pct.moe", "decode_kernel_time_pct.moe",
                   "expert_matmul_roofline_pct.moe")}

# left-hand sides and targets of the Mosaic calls in the compiled decode and
# prefill programs of a described v5e, with a fusion that reads one
HLO = {
    "gate_up": '%moe_expert_matmul.56 = bf16[768,4096]{1,0:T(8,128)(2,1)S(1)}'
               ' custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.9 = f32[69632,4096]{1,0:T(8,128)} '
            'custom-call(%a, %b, %moe_expert_matmul.8, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.28 = f32[64,128]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "decode": '%decode_attention.28 = bf16[512,16,128]{2,1,0:T(8,128)(2,1)'
              'S(1)} custom-call(%a, %b, %c, %d), '
              'custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.4 = (bf16[8192,128,128]{2,1,0}, '
             'f32[8192,8,128]{2,1,0}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"',
    "fusion": '%broadcast_select_fusion.40 = bf16[768,4096]{1,0} '
              'fusion(f32[768]{0} %moe_router.28), kind=kLoop',
}


@pytest.mark.parametrize("metric,hits", [
    ("expert_time_pct.moe", {"gate_up", "down"}),
    ("expert_matmul_roofline_pct.moe", {"gate_up", "down"}),
    ("decode_kernel_time_pct.moe", {"decode"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_expert_matmul_cost_matches_the_hand_count():
    # one decode step of one layer: 64 tokens x 8 choices, an eighth local,
    # all 16 held experts hit. A row: three 4096 x 4096 products.
    ops, moved = kernel_costs.expert_matmul_cost(64, 16, 4096, 4096)
    assert ops == 2 * 3 * 4096 * 4096 * 64 == 6_442_450_944
    assert moved == 16 * 3 * 4096 * 4096 * 2 == 1_610_612_736     # bf16
    # the bytes bound it: 1.97 ms against 0.03 ms of multiplies
    assert moved / PEAKS["hbm_bytes_per_s"] > 50 * ops / PEAKS[
        "bf16_flops_per_s"]


def _counters(decode_calls, prefill_calls, layers=4):
    c = {}
    for layer in range(layers):
        for phase, calls, tokens, hit in (
                ("decode", decode_calls, 64.0, 15.5),
                ("prefill", prefill_calls, 8192.0, 16.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["moe_expert_calls_total" + lab] = float(calls)
            c["moe_expert_tokens_total" + lab] = calls * tokens
            c["moe_experts_hit_total" + lab] = calls * hit
    return c


def test_roofline_reader_compares_a_call_with_a_call(monkeypatch):
    counters = _counters(400, 90)
    least, calls = kernel_costs.moe_expert_matmul_seconds(CFG, counters,
                                                          PEAKS)
    # decode: 4 layers x 400 executions x 15.5 experts' weights; prefill:
    # 4 x 90 x 8192 rows x 100.7 MFLOP x 2; two kernel calls an execution
    assert least == pytest.approx(
        4 * 400 * 15.5 * 3 * 4096 * 4096 * 2 / 819e9
        + 4 * 90 * 2 * 3 * 4096 * 4096 * 8192 / 197e12, rel=1e-6)
    assert calls == 2 * 4 * (400 + 90)
    # a profile with three matching calls of 2 ms and one router call
    ops = [(HLO["gate_up"], 0, 2_000_000), (HLO["down"], 5, 2_000_005),
           (HLO["gate_up"], 9, 2_000_009), (HLO["router"], 0, 100_000)]
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters}
    args = FILES["expert_matmul_roofline_pct.moe"]["args"]
    assert kernel_roofline.read(ctx, **args) == pytest.approx(
        100.0 * (least / calls) / 2e-3)
    # a program without the counters (the parent), or no trace: nothing
    assert kernel_roofline.read(dict(ctx, counters={}), **args) is None
    assert kernel_roofline.read(dict(ctx, trace=None), **args) is None


@pytest.mark.parametrize("tokens,tm,counts", [
    # a decode step: 4 rows an expert, one starved, one with 17 (two tiles)
    (64, 16, [4, 3, 0, 17, 5, 4, 4, 2, 6, 4, 3, 5, 4, 1, 4, 4]),
    # every assignment of a decode step on one expert
    (64, 16, [512] + [0] * 15),
    # a prefill of 8 x 128 tokens, even and skewed (tiles of 16 rows)
    (1024, 16, [64] * 16),
    (1024, 16, [1, 15, 16, 17, 400, 0, 70, 90] + [64] * 8),
    # the tiling of a large batch: 64 x 128 tokens in tiles of 256 rows
    (8192, 256, [1, 255, 256, 257, 4000, 0, 700, 900] + [512] * 8),
])
def test_the_cost_never_passes_what_the_kernel_itself_does(tokens, tm,
                                                           counts):
    """What ``kernels/moe.py`` moves and multiplies for these counts, from
    its own tiling (whole tiles of ``tm`` rows; a weight block is read for
    every tile of its expert; blocks of ``gmm_blocks``), is at least what
    the cost functions count, in operations and in bytes: so the least
    time is a lower bound of any time the kernel can take, and a share of
    it over a measured time stays under 100%."""
    from paddle_tpu.kernels.moe import gmm_blocks

    H = F = 4096
    tiles = sum(-(-c // tm) for c in counts)
    done_ops = moved = 0
    for K, N, mats, out_bytes in ((H, F, 2, 2), (F, H, 1, 4)):
        tk, tn = gmm_blocks(K, N)
        steps = tiles * (N // tn) * (K // tk)
        done_ops += 2 * tm * tk * tn * mats * steps
        moved += steps * (tm * tk * 2 + mats * tk * tn * 2)
        moved += tiles * tm * N * out_bytes
    ops, need = kernel_costs.expert_matmul_cost(
        sum(counts), sum(1 for c in counts if c), H, F)
    assert ops <= done_ops and need <= moved
    least = max(ops / PEAKS["bf16_flops_per_s"],
                need / PEAKS["hbm_bytes_per_s"])
    kernel = max(done_ops / PEAKS["bf16_flops_per_s"],
                 moved / PEAKS["hbm_bytes_per_s"])
    assert 0 < least <= kernel
