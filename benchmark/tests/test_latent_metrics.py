"""The ``.latent`` per-layer metrics of ``glm-4.7-flash-ep8.decode-reasoning``:
the kernel-name patterns of their files against the names as the TPU
compiler prints them for this configuration (``tools/deviceless_stored.py
--config glm-4.7-flash-ep8-serve --hlo``) and nothing else, the
ops-and-bytes functions of ``kernel_costs_latent.py`` against counts made
by hand, the roofline reader on a made-up window (and on a program without
the counters: nothing, no raise), the configuration's file against the
catalog's numbers, and the fp8 controls against the tiny configuration's
limit."""
import os

import numpy as np
import pytest

import harness
import kernel_costs
import kernel_costs_latent as costs
from readers import kernel_roofline, kernel_roofline_in, trace_op_share

CELL = "glm-4.7-flash-ep8.decode-reasoning"
BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CFG = harness.load_json(harness.HERE, "configs",
                        "glm-4.7-flash-ep8-serve.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NAMES = sorted(m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".latent"))
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in NAMES}

# left-hand sides and targets of the Mosaic calls in the compiled prefill
# and decode programs of a described v5e, with a fusion that reads one
HLO = {
    "mla": '%mla_decode_attention.56 = bf16[128,32,512]{2,1,0:T(8,128)(2,1)}'
           ' custom-call(%a, %b, %c), '
           'custom_call_target="tpu_custom_call"',
    "gate_up": '%moe_expert_matmul.100 = bf16[640,1536]{1,0:T(8,128)(2,1)'
               'S(1)} custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.101 = f32[640,2048]{1,0:T(8,128)S(1)} '
            'custom-call(%a, %b, %moe_expert_matmul.100, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.49 = f32[128,64]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.4 = (bf16[20,1024,256]{2,1,0}, '
             'f32[20,8,1024]{2,1,0}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"',
    "decode": '%decode_attention.28 = bf16[128,16,256]{2,1,0:T(8,128)(2,1)'
              'S(1)} custom-call(%a, %b, %c, %d), '
              'custom_call_target="tpu_custom_call"',
    "fusion": '%fusion.40 = bf16[128,20,512]{2,1,0} '
              'fusion(bf16[128,32,512]{2,1,0} %mla_decode_attention.56), '
              'kind=kLoop',
}


def cell_invariants(bench: dict) -> None:
    """What this file holds of ``BENCHMARK.json``, on the tree's or on one
    with further cells appended (``test_layer_metric_files.py``
    ``test_a_cell_can_be_appended``): no count of anything."""
    names = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".latent")}
    # every entry of the cell has its file; a file may wait for its entry
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))
        if n.endswith(".latent.json")}
    assert names and names <= files
    for m in bench["per_layer"]:
        if m["name"].endswith(".latent"):
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["decode_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash-ep8-serve", "decode-reasoning", 1)


def test_the_cell_has_its_latent_metrics_and_only_they_list_it():
    cell_invariants(BENCH)


@pytest.mark.parametrize("metric,hits", [
    ("mla_decode_time_pct.latent", {"mla"}),
    ("mla_decode_roofline_pct.latent", {"mla"}),
    ("expert_time_pct.latent", {"gate_up", "down"}),
    ("flash_fwd_time_pct.latent", {"flash"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    published = dict(
        hidden_size=2048, num_attention_heads=20, num_key_value_heads=20,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, moe_intermediate_size=1536,
        intermediate_size=10240, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1, n_group=1,
        topk_group=1, rope_theta=1000000, rms_norm_eps=1e-05,
        model_type="glm4_moe_lite", topk_method="noaux_tc",
        norm_topk_prob=True, max_position_embeddings=202752)
    assert {k: CFG[k] for k in published} == published
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "glm-4.7-flash-ep8-serve")
    assert sorted(CFG["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "num_nextn_predict_layers"])
    for key, cut in CFG["reduced"].items():
        assert CFG[key] == cut["here"] != cut["published"]
    d = CFG["deployment"]
    assert (d["num_experts_total"], d["vocab_size_total"],
            d["num_hidden_layers_total"]) == (64, 154880, 47)
    assert CFG["n_routed_experts"] * d["chips_per_layer"] == 64
    assert CFG["vocab_size"] * d["chips_per_layer"] == 154880
    assert CFG["source"] == entry["source"]
    # the parameters held, as ISSUE 33 reckons them: 911.6M
    from reference import glm4_moe_lite as ref
    spec = ref.param_spec(ref.model_config(CFG))
    held = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert 911e6 < held < 913e6


def test_the_costs_match_the_hand_counts():
    # one decode step of one layer, 128 slots at 1,750 rows each, walked in
    # blocks of 1,024: 2,048 rows a slot. 20 heads x (576 + 512)
    # multiply-adds a row; the row's 576 bf16 numbers once
    rows = 128 * 2048
    ops, moved = costs.mla_decode_cost(rows, 20, 512, 64)
    assert moved == rows * 1152 == 301_989_888
    assert ops == rows * 20 * 2 * 1088 == 11_408_506_880
    # 38 operations a byte against the chip's 240: the bytes bound it
    assert 37 < ops / moved < 39
    assert moved / PEAKS["hbm_bytes_per_s"] > 6 * ops / PEAKS[
        "bf16_flops_per_s"]
    # one decode step of one layer with experts: 128 x 4 choices, an
    # eighth local, all 8 held experts hit; an expert is three 2048 x 1536
    # matrices (the dense layer's 10,240 is not an expert's width)
    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    assert (H, F, CFG["intermediate_size"]) == (2048, 1536, 10240)
    ops, moved = kernel_costs.expert_matmul_cost(64, 8, H, F)
    assert moved == 8 * 3 * 2048 * 1536 * 2 == 150_994_944
    assert ops == 2 * 3 * 2048 * 1536 * 64


def _counters(decode_calls, prefill_calls):
    c = {}
    for layer in range(8):
        for phase, calls, rows in (("decode", decode_calls, 128 * 2048.0),
                                   ("prefill", prefill_calls, 1024.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["latent_attention_calls_total" + lab] = float(calls)
            c["latent_attention_rows_total" + lab] = calls * rows
    for layer in range(1, 8):
        for phase, calls, tokens, hit in (
                ("decode", decode_calls, 64.0, 8.0),
                ("prefill", prefill_calls, 400.0, 8.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["moe_expert_calls_total" + lab] = float(calls)
            c["moe_expert_tokens_total" + lab] = calls * tokens
            c["moe_experts_hit_total" + lab] = calls * hit
    return c


def test_roofline_reader_finds_its_cost_module(monkeypatch):
    counters = _counters(4000, 300)
    mla, calls = costs.mla_decode_seconds(CFG, counters, PEAKS)
    assert calls == 8 * 4000          # the prefill's rows are not its work
    assert mla == pytest.approx(8 * 4000 * 128 * 2048 * 1152 / 819e9,
                                rel=1e-6)
    experts, calls = costs.moe_expert_matmul_seconds(CFG, counters, PEAKS)
    assert calls == 2 * 7 * 4300
    assert experts == pytest.approx(
        7 * 4300 * 8 * 3 * 2048 * 1536 * 2 / 819e9, rel=1e-6)
    ops = [(HLO["mla"], 0, 500_000), (HLO["mla"], 9, 500_009),
           (HLO["gate_up"], 0, 150_000), (HLO["down"], 0, 100_000),
           (HLO["router"], 0, 100_000)]
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_in.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters}
    args = FILES["mla_decode_roofline_pct.latent"]["args"]
    got = kernel_roofline_in.read(ctx, **args)
    assert got == pytest.approx(100.0 * (mla / 32000) / 500e-6)
    assert 0 < got < 100
    # a program without the counters (the parent commit), or no trace:
    # nothing, and no raise
    assert kernel_roofline_in.read(dict(ctx, counters={}), **args) is None
    assert kernel_roofline_in.read(dict(ctx, trace=None), **args) is None


def test_the_kernel_cost_never_passes_what_the_kernel_itself_does():
    """The kernel scores every row of every block it fetches, for 32
    sublane rows where 20 are heads, over 640 lanes where 576 hold
    numbers, and moves the blocks' bytes: the cost function counts the
    same rows, 20 heads, and the 576 numbers a row has."""
    from paddle_tpu.kernels.latent_attention import latent_block_rows
    from paddle_tpu.kernels.decode_attention import last_live_block

    bk = latent_block_rows(4096, 640, np.dtype("uint16"), 128)
    lengths = np.array([1, 700, 1024, 1025, 3000, 4096])
    rows = int(((last_live_block(lengths, 1, bk, 4096 // bk) + 1) * bk).sum())
    assert rows == bk * (1 + 1 + 1 + 2 + 3 + 4)
    ops, need = costs.mla_decode_cost(rows, 20, 512, 64)
    assert ops <= rows * 32 * 2 * (640 + 512) and need < rows * 640 * 2


def test_fp8_controls_fail_the_tiny_limit_that_the_reference_passes():
    """Reference against reference at the rehearsal's sizes: the
    reference's own choices score 0, the fp8-operand control's lie further
    below the best than the tiny configuration's limit, and a latent cache
    kept in fp8 moves the choices too."""
    import jax.numpy as jnp

    from reference import glm4_moe_lite as ref

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
    _, low = ref.gaps_fn(model, "cache:fp8")(w, ids, best)
    assert float(jnp.max(low)) > 0.0
