"""The ``.mhc`` per-layer metrics of
``xing4.0-29b-a4b-ep8.decode-prompt-heavy``: the kernel-name patterns of
their files against the names as the TPU compiler prints them for this
configuration (``tools/deviceless_stored.py --config
xing4.0-29b-a4b-ep8-serve --hlo``) and nothing else, the ops-and-bytes
function of ``kernel_costs_mhc.py`` against counts made by hand, the
roofline reader on a made-up window (and on a program without the
counters: nothing, no raise) with the cost a floor, the configuration's
file against the catalog's numbers, and the controls against the tiny
configuration's limit."""
import os

import numpy as np
import pytest

import harness
import kernel_costs_mhc as costs
from readers import kernel_roofline, kernel_roofline_in, trace_op_share

CELL = "xing4.0-29b-a4b-ep8.decode-prompt-heavy"
CONFIG = "xing4.0-29b-a4b-ep8-serve"
BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CFG = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NAMES = sorted(m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".mhc"))
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in NAMES}

# left-hand sides and targets of the Mosaic calls in the compiled prefill
# and decode programs of a described v5e, with a fusion that reads one
HLO = {
    "hc_read": '%hc_read.112 = (f32[256,3584]{1,0:T(8,128)}, '
               'f32[256,128]{1,0:T(8,128)S(1)}, f32[16,128]{1,0:T(8,128)}) '
               'custom-call(%bitcast.2252, %get-tuple-element.7475, %b), '
               'custom_call_target="tpu_custom_call"',
    "hc_write": '%hc_write.113 = f32[256,14336]{1,0:T(8,128)S(1)} '
                'custom-call(%custom-call.344, %fusion.1211, %pad.912), '
                'custom_call_target="tpu_custom_call"',
    "mla": '%mla_decode_attention.56 = bf16[256,32,512]{2,1,0:T(8,128)(2,1)'
           'S(1)} custom-call(%a, %b, %c), '
           'custom_call_target="tpu_custom_call"',
    "gate_up": '%moe_expert_matmul.84 = bf16[1152,1024]{1,0:T(8,128)(2,1)'
               'S(1)} custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.85 = f32[1152,3584]{1,0:T(8,128)S(1)} '
            'custom-call(%a, %b, %moe_expert_matmul.84, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.42 = f32[256,64]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.8 = (bf16[32,1024,128]{2,1,0:T(8,128)'
             '(2,1)S(1)}, f32[32,8,1024]{2,1,0:T(8,128)}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"',
    "fusion": '%fusion.1211 = f32[256,3584]{1,0} fusion(f32[256,3584]{1,0} '
              '%hc_read.113), kind=kLoop',
    "staged": '%custom-call.344 = f32[256,14336]{1,0:T(8,128)S(1)} '
              'custom-call(%slice-done.339, %hc_write.112), '
              'custom_call_target="ConcatBitcast"',
}


def cell_invariants(bench: dict) -> None:
    """What this file holds of ``BENCHMARK.json``, on the tree's or on one
    with further cells appended (``test_layer_metric_files.py``
    ``test_a_cell_can_be_appended``): no count of anything."""
    names = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".mhc")}
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))
        if n.endswith(".mhc.json")}
    assert names and names <= files
    for m in bench["per_layer"]:
        if m["name"].endswith(".mhc"):
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["decode_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-prompt-heavy", 1)


def test_the_cell_has_its_mhc_metrics_and_only_they_list_it():
    cell_invariants(BENCH)
    assert {"hc_time_pct.mhc", "hc_roofline.mhc",
            "mla_decode_roofline_pct.mhc", "prefill_share_pct.mhc",
            "setup_warm_up_s.mhc"} <= set(NAMES)
    for name, spec in FILES.items():
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert (spec["name"], spec["unit"], spec["moves"], spec["layer"],
                spec["source"]) == (name, entry["unit"], entry["moves"],
                                    entry["layer"], entry["source"])


@pytest.mark.parametrize("metric,hits", [
    ("hc_time_pct.mhc", {"hc_read", "hc_write"}),
    ("hc_roofline.mhc", {"hc_read", "hc_write"}),
    ("mla_decode_time_pct.mhc", {"mla"}),
    ("mla_decode_roofline_pct.mhc", {"mla"}),
    ("expert_time_pct.mhc", {"gate_up", "down"}),
    ("flash_fwd_time_pct.mhc", {"flash"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    """Every number of the catalog's row (``architectures.jsonl``,
    ``Xing4.0-29B-A4B``) under its key, but the five the file lists as
    reduced; the nested group whole."""
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=2,
        hidden_act="silu", hidden_size=3584, intermediate_size=9216,
        kv_lora_rank=512, max_position_embeddings=262144,
        model_type="xing4_0", moe_intermediate_size=1024, moe_layer_freq=1,
        n_group=1, n_routed_experts=64, n_shared_experts=1,
        norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=4,
        num_hidden_layers=40, num_key_value_heads=32,
        num_nextn_predict_layers=1, hc_mult=4, hc_sinkhorn_iters=20,
        hc_eps=1e-06, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-06, rope_theta=10000,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        routed_scaling_factor=2, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
        v_head_dim=128, vocab_size=131072)
    row = {"config": published,
           "source_url": "https://huggingface.co/XingChen-AGI/"
                         "Xing4.0-29B-A4B/blob/main/config.json"}
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers", "max_position_embeddings"}
    for key, value in published.items():
        if key not in reduced:
            assert CFG[key] == value, key
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(CFG["reduced"]) == set(entry["reduced"]) == reduced
    for key, cut in CFG["reduced"].items():
        assert CFG[key] == cut["here"] != cut["published"] == \
            row["config"][key]
    assert entry["source"] == CFG["source"] == row["source_url"]
    d = CFG["deployment"]
    assert (d["num_experts_total"], d["vocab_size_total"],
            d["num_hidden_layers_total"], d["streams_per_chip"]) == (
        64, 131072, 40, 256)
    assert CFG["n_routed_experts"] * d["chips_per_layer"] == 64
    assert CFG["vocab_size"] * d["chips_per_layer"] == 131072
    assert CFG["num_hidden_layers"] * d["pipeline_stages"] == 40
    s = CFG["serving"]
    assert (s["slots"], s["max_seq"]) == (256, CFG["max_position_embeddings"])
    # the floors: both dense layers and six with experts, 8 experts held,
    # an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    # the parameters held, as ISSUE 52 reckons them: 1,144M
    from reference import xing4 as ref
    spec = ref.param_spec(ref.model_config(CFG))
    held = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert 1.140e9 < held < 1.148e9
    f32 = sum(int(np.prod(shape)) for shape, _, dt in spec.values()
              if dt == "float32")
    assert 5.5e6 < f32 < 5.7e6        # 16 hyper-connections and the norms


def test_the_traffic_is_the_issues():
    mix = harness.load_json(harness.HERE, "traffic",
                            "decode-prompt-heavy.json")
    assert (mix["generator"], mix["clients"], mix["pool"],
            mix["mix_seed"], mix["max_total"]) == (
        "closed_loop", 256, 256, 52001, 2048)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["answer_len"] == {"dist": "uniform", "min": 128, "max": 512}
    buckets = CFG["serving"]["prompt_buckets"]
    assert max(buckets) >= 1536 and all(b % 128 == 0 for b in buckets)
    # one warm request a bucket
    assert len(mix["warm"]) == len(buckets)
    for (prompt, _), lo, hi in zip(mix["warm"], [0] + buckets, buckets):
        assert lo < prompt <= hi


def test_the_costs_match_the_hand_counts():
    n, C = CFG["hc_mult"], CFG["hidden_size"]
    assert (n, C) == (4, 3584)
    weights = 24 * 14336 * 4
    # one decode step of one sublayer, 256 slots: 14.7 MB of streams stay
    # on the chip beside their rewritten copy (29 MB of 128 MiB), so only
    # the projection's 24 x 14,336 f32 have to cross
    ops, moved = costs.hyper_connection_cost(256, 1, n, C)
    assert moved == weights == 1_376_256
    assert ops == 256 * (2 * 14336 * 24 + 2 * 14336 * 6)
    # a prefill of 1,024 rows: two copies are 117 MB, they fit; of 1,280:
    # 147 MB, they cannot, and the streams (57,344 B a row) cross three
    # times: read for the read, read and written for the write
    assert costs.hyper_connection_cost(1024, 1, n, C)[1] == weights
    ops, moved = costs.hyper_connection_cost(1280, 1, n, C)
    assert moved == 1280 * 3 * 57344 + weights == 221_577_216
    # 4.9 operations a byte against the chip's 240: the bytes bound it
    assert 4 < ops / moved < 5.5
    # 40 calls of 1,536 rows are 40 times one
    assert costs.hyper_connection_cost(40 * 1536, 40, n, C)[1] == \
        40 * costs.hyper_connection_cost(1536, 1, n, C)[1]
    # the latent kernel at this configuration's 32 heads
    from kernel_costs_latent import mla_decode_cost
    ops, moved = mla_decode_cost(2048.0, 32, 512, 64)
    assert moved == 2048 * 1152 and ops == 2048 * 32 * 2 * 1088


def _counters(decode_calls, prefill_calls, bucket=1536):
    c = {}
    for phase, calls, rows in (("decode", decode_calls, 256),
                               ("prefill", prefill_calls, bucket)):
        lab = f"{{call_rows={rows},phase={phase}}}"
        c["hyper_connection_calls_total" + lab] = 16.0 * calls
        c["hyper_connection_rows_total" + lab] = 16.0 * calls * rows
        for layer in range(8):
            lab = f"{{layer={layer},phase={phase}}}"
            c["latent_attention_calls_total" + lab] = float(calls)
            c["latent_attention_rows_total" + lab] = calls * (
                256 * 1536.0 if phase == "decode" else float(bucket))
    return c


def test_roofline_reader_finds_its_cost_module(monkeypatch):
    counters = _counters(1600, 1500)
    # a window's snapshot holds every family: histograms' keys carry a
    # suffix behind their labels, a family without labels has none
    counters.update({"executor_step_seconds{path=run}_sum": 3.0,
                     "recompiles_total{}": 0.0,
                     "hyper_connection_res_sum_err_max{}": 0.01})
    least, calls = costs.hyper_connection_seconds(CFG, counters, PEAKS)
    assert calls == 2 * 16 * 3100     # a read and a write a sublayer
    weights = 24 * 14336 * 4
    assert least == pytest.approx(
        (16 * 1500 * 1536 * 3 * 57344 + 16 * 3100 * weights) / 819e9,
        rel=1e-6)
    # buckets that fit the chip: a decode step's call is bound by its
    # weights' bytes (1.7 us), a call of 1,024 rows by its operations
    # (881 MFLOP: 4.5 us)
    small, _ = costs.hyper_connection_seconds(
        CFG, _counters(1600, 1500, bucket=1024), PEAKS)
    assert small == pytest.approx(
        16 * 1600 * weights / 819e9
        + 16 * 1500 * 1024 * 2 * 14336 * 30 / 197e12, rel=1e-6)
    mla, mla_calls = costs.mla_decode_seconds(CFG, counters, PEAKS)
    assert mla_calls == 8 * 1600
    assert mla == pytest.approx(8 * 1600 * 256 * 1536 * 1152 / 819e9,
                                rel=1e-6)
    each = least / calls
    ops = [(HLO["hc_read"], 0, each * 1e9 * 1.5),
           (HLO["hc_write"], 9, 9 + each * 1e9 * 0.5),
           (HLO["mla"], 0, 500_000), (HLO["staged"], 0, 9e6),
           (HLO["router"], 0, 100_000)]
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_in.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters}
    args = FILES["hc_roofline.mhc"]["args"]
    # the kernels' operations lasting exactly the least seconds a call:
    # 100; any of them longer: less (the cost is a floor)
    assert kernel_roofline_in.read(ctx, **args) == pytest.approx(100.0)
    ops[0] = (HLO["hc_read"], 0, each * 1e9 * 2.0)
    assert kernel_roofline_in.read(ctx, **args) == pytest.approx(80.0)
    got = kernel_roofline_in.read(
        ctx, **FILES["mla_decode_roofline_pct.mhc"]["args"])
    assert got == pytest.approx(100.0 * (mla / mla_calls) / 500e-6)
    # a program without the counters (the parent commit), or no trace:
    # nothing, and no raise
    assert costs.hyper_connection_seconds(CFG, {}, PEAKS) is None
    assert kernel_roofline_in.read(dict(ctx, counters={}), **args) is None
    assert kernel_roofline_in.read(dict(ctx, trace=None), **args) is None


def test_the_cost_never_passes_what_the_kernels_themselves_move():
    """Where a call's streams have to cross HBM the kernels fetch a tile's
    once for the read and once for the write, write them once, and move
    the sublayer's output, the read's ``u`` and the 128 lanes of
    coefficients on top: the cost counts less, never more."""
    from paddle_tpu.kernels import hyper_connection as hc

    n, C, rows = 4, 3584, 1536
    assert rows % hc.ROW_TILE == 0 and hc.supports(rows, n, C)
    kernel_moves = 4.0 * (
        rows * (n * C + C + hc.COEF_LANES)            # the read: x, u, coef
        + n * (n + 2) * n * C                         # its projection, once
        + rows * (2 * n * C + C + hc.COEF_LANES))     # the write
    _, moved = costs.hyper_connection_cost(rows, 1, n, C)
    assert moved < kernel_moves < 1.18 * moved
    assert 2 * rows * n * C * 4 > costs.ON_CHIP_BYTES > 2 * 1024 * n * C * 4


def test_controls_fail_the_tiny_limit_that_the_reference_passes():
    """Reference against reference at the rehearsal's sizes: the
    reference's own choices score 0; the fp8-operand control's, the bf16
    streams' and the fixed hyper-connections' lie further below the best
    than the tiny configuration's limit or move the choices."""
    import jax.numpy as jnp

    from reference import xing4 as ref

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
    _, fixed = ref.gaps_fn(model, "hc:fixed")(w, ids, best)
    assert float(jnp.max(fixed)) > limit, float(jnp.max(fixed))
    _, low = ref.gaps_fn(model, "stream:bf16")(w, ids, best)
    assert float(jnp.max(low)) >= 0.0
