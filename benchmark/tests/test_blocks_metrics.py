"""The ``.blocks`` per-layer metrics of ``sdar-30b-a3b.decode-blocks`` and
the comparison that decides its ``correct``: the kernel-name patterns of
the metric files against the names as the TPU compiler prints them for
this configuration (``tools/deviceless_blocks.py --hlo``) and nothing
else, the ops-and-bytes functions of ``kernel_costs_blocks.py`` against
counts made by hand, the readers on a made-up window (and on a program
without the counters: nothing, no raise), the configuration's file against
the catalog's numbers, and the comparison's two gaps on a tiny model: 0
for the reference's own reveals, over the tiny limit for those of the
reference computed in fp8."""
import types

import os

import numpy as np
import pytest

import harness
import kernel_costs
import kernel_costs_blocks as costs
from readers import (counter_ratio, counter_share, kernel_roofline,
                     kernel_roofline_in, trace_op_share, trace_vocab_share)

CELL = "sdar-30b-a3b.decode-blocks"
BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CFG = harness.load_json(harness.HERE, "configs", "sdar-30b-a3b-serve.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NAMES = sorted(m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".blocks"))
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in NAMES}

# left-hand sides, shapes and targets of the device operations in the
# compiled prefill and chained decode programs of a described v5e
HLO = {
    "decode": '%decode_attention.42 = bf16[256,32,128]{2,1,0:T(8,128)(2,1)}'
              ' custom-call(%a, %b, %c, %d), '
              'custom_call_target="tpu_custom_call"',
    "gate_up": '%moe_expert_matmul.84 = bf16[4096,768]{1,0:T(8,128)(2,1)} '
               'custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.85 = f32[4096,2048]{1,0:T(8,128)} '
            'custom-call(%a, %b, %moe_expert_matmul.84, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.42 = f32[256,128]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.6 = (bf16[32,1024,128]{2,1,0}, '
             'f32[32,8,1024]{2,1,0}) custom-call(%a, %b, %c, %d), '
             'custom_call_target="tpu_custom_call"',
    "head": '%fusion.1168 = f32[64,4,151936]{2,0,1:T(8,128)} fusion('
            'bf16[151936,2048]{1,0:T(8,128)(2,1)} %get-tuple-element.14407, '
            'pred[151936]{0:T(1024)(128)(4,1)S(1)} %copy-done.10), '
            'kind=kOutput, calls=%fused_computation.211.clone.clone',
    "reveal": '%fusion.1170 = (f32[64,4]{1,0}, s32[64,4]{1,0}) fusion('
              'f32[64,4,151936]{2,0,1:T(8,128)} %fusion.1168), kind=kInput, '
              'calls=%fused_computation.213.clone.clone',
    "while": '%while.1124 = (s32[]{:T(128)}, s32[64,1]{0,1}, '
             'bf16[151936,2048]{1,0:T(8,128)(2,1)}) while(%tuple.2651), '
             'condition=%c, body=%b',
    "fusion": '%fusion.40 = bf16[256,32,128]{2,1,0} '
              'fusion(bf16[256,32,128]{2,1,0} %decode_attention.42), '
              'kind=kLoop',
}


def cell_invariants(bench: dict) -> None:
    """What this file holds of ``BENCHMARK.json``, on the tree's or on one
    with further cells appended (``test_layer_metric_files.py``
    ``test_a_cell_can_be_appended``): no count of anything."""
    names = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".blocks")}
    # every entry of the cell has its file; a file may wait for its entry
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))
        if n.endswith(".blocks.json")}
    assert names and names <= files
    for m in bench["per_layer"]:
        if m["name"].endswith(".blocks"):
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["decode_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-serve", "decode-blocks", 1)


def test_the_cell_has_its_blocks_metrics_and_only_they_list_it():
    cell_invariants(BENCH)


@pytest.mark.parametrize("metric,hits", [
    ("decode_kernel_time_pct.blocks", {"decode"}),
    ("block_attention_roofline_pct.blocks", {"decode"}),
    ("expert_time_pct.blocks", {"gate_up", "down"}),
    ("expert_matmul_roofline_pct.blocks", {"gate_up", "down"}),
    ("flash_fwd_time_pct.blocks", {"flash"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_the_head_and_the_reveal_are_the_operations_over_the_vocabulary():
    args = FILES["head_and_reveal_time_pct.blocks"]["args"]
    for key, line in HLO.items():
        ctx = {"config": CFG,
               "trace": {"busy_s": 2.0, "op_seconds": {line: 1.0}}}
        got = trace_vocab_share.read(ctx, **args)
        assert (got == pytest.approx(50.0)) if key in ("head", "reveal") \
            else got is None
    assert trace_vocab_share.read({"config": CFG, "trace": None},
                                  **args) is None


def test_the_file_holds_the_published_widths_and_names_its_cut():
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48,
        mlp_only_layers=[], model_type="sdar_moe",
        moe_intermediate_size=768, norm_topk_prob=True,
        num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
        num_key_value_heads=4, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    assert {k: CFG[k] for k in published} == published
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "sdar-30b-a3b-serve")
    assert list(CFG["reduced"]) == entry["reduced"] == ["num_hidden_layers"]
    cut = CFG["reduced"]["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == cut["here"] == 6
    assert cut["published"] == 48
    d = CFG["deployment"]
    assert (d["chips_per_layer"], d["num_experts_total"],
            d["vocab_size_total"], d["num_hidden_layers_total"],
            d["pipeline_stages"]) == (1, 128, 151936, 48, 8)
    assert CFG["source"] == entry["source"]
    for key in ("block_length", "denoising_steps", "remasking",
                "mask_token_id", "mask_id_excluded", "q_k_norms",
                "rotary_pairing", "no_shift", "prefill_rows",
                "decode_chunk"):
        assert key in CFG["assumed"], key
    # the parameters held, as ISSUE 41 reckons them: 4,361M
    from reference import sdar_moe as ref
    spec = ref.param_spec(ref.model_config(CFG))
    held = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert 4360e6 < held < 4362e6
    # the traffic never draws the mask id
    mix = harness.load_json(harness.HERE, "traffic", "decode-blocks.json")
    assert CFG["block_diffusion"]["mask_token_id"] == 0
    assert (mix["clients"], mix["pool"], mix["max_total"]) == (64, 64, 2048)
    assert CFG["serving"]["slots"] == 64
    for key in ("prompt_len", "answer_len"):
        assert mix[key]["dist"] == "uniform"
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"],
            mix["answer_len"]["min"], mix["answer_len"]["max"]) == (
        128, 1024, 128, 512)


def test_the_costs_match_the_hand_counts():
    # one decode forward of one layer: 64 slots whose blocks' rows see
    # 1,000 keys each. A key row: K and V of 4 heads x 128 in bf16, 2 KB;
    # 32 query heads x 4 rows x (128 + 128) multiply-adds
    keys = 64 * 1000
    ops, moved = costs.block_attention_cost(keys, 32, 4, 128, 4)
    assert moved == keys * 2048 == 131_072_000
    assert ops == keys * 32 * 4 * 2 * 256 == 4_194_304_000
    # 32 operations a byte against the chip's 240: the bytes bound it
    assert ops / moved == 32
    assert moved / PEAKS["hbm_bytes_per_s"] > 7 * ops / PEAKS[
        "bf16_flops_per_s"]
    # one decode forward of one layer's experts: 256 rows x 8 choices, all
    # 128 experts hit; an expert is three 2048 x 768 matrices (the dense
    # 6,144 is no expert's width)
    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    assert (H, F, CFG["intermediate_size"]) == (2048, 768, 6144)
    ops, moved = kernel_costs.expert_matmul_cost(2048, 128, H, F)
    assert moved == 128 * 3 * 2048 * 768 * 2 == 1_207_959_552
    assert ops == 2 * 3 * 2048 * 768 * 2048


def _counters(forwards, prefills):
    c = {"decode_attention_keys_total{}": forwards * 6 * 64 * 1000.0,
         "decode_attention_calls_total{}": forwards * 6.0}
    for layer in range(6):
        for phase, calls, tokens, hit in (
                ("decode", forwards, 2048.0, 128.0),
                ("prefill", prefills, 5000.0, 128.0)):
            lab = f"{{layer={layer},phase={phase}}}"
            c["moe_expert_calls_total" + lab] = float(calls)
            c["moe_expert_tokens_total" + lab] = calls * tokens
            c["moe_experts_hit_total" + lab] = calls * hit
    return c


def test_roofline_reader_finds_its_cost_module(monkeypatch):
    counters = _counters(2400, 120)
    attn, calls = costs.block_attention_seconds(CFG, counters, PEAKS)
    assert calls == 6 * 2400
    assert attn == pytest.approx(6 * 2400 * 64 * 1000 * 2048 / 819e9,
                                 rel=1e-6)
    experts, calls = costs.moe_expert_matmul_seconds(CFG, counters, PEAKS)
    assert calls == 2 * 6 * 2520
    assert experts == pytest.approx(
        6 * 2520 * 128 * 3 * 2048 * 768 * 2 / 819e9, rel=1e-6)
    ops = [(HLO["decode"], 0, 250_000), (HLO["decode"], 9, 250_009),
           (HLO["gate_up"], 0, 1_200_000), (HLO["down"], 0, 700_000),
           (HLO["router"], 0, 100_000)]
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_in.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters}
    got = kernel_roofline_in.read(
        ctx, **FILES["block_attention_roofline_pct.blocks"]["args"])
    assert got == pytest.approx(100.0 * (attn / 14400) / 250e-6)
    assert 0 < got < 100
    assert 0 < kernel_roofline_in.read(
        ctx, **FILES["expert_matmul_roofline_pct.blocks"]["args"]) < 100
    # a program without the counters (the parent commit), or no trace:
    # nothing, and no raise
    for name in ("block_attention_roofline_pct.blocks",
                 "expert_matmul_roofline_pct.blocks"):
        args = FILES[name]["args"]
        assert kernel_roofline_in.read(dict(ctx, counters={}),
                                       **args) is None
        assert kernel_roofline_in.read(dict(ctx, trace=None), **args) is None


def test_the_counter_metrics_on_a_made_up_window():
    counters = {"serving_block_forwards_total{kind=commit}": 1000.0,
                "serving_block_forwards_total{kind=denoise}": 2050.0,
                "serving_decode_tokens_total{}": 3900.0}
    ctx = {"counters": counters}
    assert counter_ratio.read(
        ctx, **FILES["forwards_per_token.blocks"]["args"]) == pytest.approx(
        3050 / 3900)
    assert counter_share.read(
        ctx, **FILES["commit_forward_pct.blocks"]["args"]) == pytest.approx(
        100 * 1000 / 3050)
    for name in ("forwards_per_token.blocks", "commit_forward_pct.blocks"):
        reader = counter_ratio if name.startswith("forwards") \
            else counter_share
        assert reader.read({"counters": {}}, **FILES[name]["args"]) is None


def test_the_kernel_cost_never_passes_what_the_kernel_itself_does():
    """The kernel fetches whole tiles up to each sequence's last live one
    and scores every row of them for 32 sublane rows a key/value head; the
    cost function counts the visible rows alone."""
    from paddle_tpu.kernels import decode_walk_blocks
    from paddle_tpu.kernels.decode_attention import kv_tile

    shape = (6, 4, 2048, 128)
    _, rows = kv_tile(4, 2048, 128, np.dtype("uint16"), 128)
    starts = np.array([0, 128, 500, 1000, 1536, 2044])
    fetched, _ = decode_walk_blocks(starts + 1, shape, np.dtype("uint16"),
                                    128, q_len=4)
    keys = int((starts + 4).sum())
    assert keys <= fetched * rows
    ops, need = costs.block_attention_cost(keys, 32, 4, 128, 4)
    assert need <= fetched * rows * 2 * 4 * 128 * 2
    assert ops <= fetched * rows * 4 * 32 * 4 * 128


# -- the comparison ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model with seeded weights, and four answers the
    reference generated itself."""
    import jax

    from reference import sdar_moe as ref

    cell = harness.Cell(BENCH, CELL, rehearse=True)
    model = ref.model_config(cell.config)
    w = dict(ref.make_weights(ref.param_spec(model), 11))
    rng = np.random.default_rng(11)
    fn = jax.jit(lambda ids: ref.logits(w, ids, model))
    sample = []
    for P, G in ((14, 10), (7, 9), (16, 12), (5, 7)):
        prompt = rng.integers(1, model["vocab_size"], P)
        total = -(-(P + G) // model["block_length"]) * model["block_length"]
        pad = lambda ids: fn(np.concatenate(
            [ids, np.zeros(32 - len(ids), np.int64)]))[:total]
        toks, at, forwards = ref.generate(w, prompt, G, model, pad)
        sample.append(types.SimpleNamespace(
            prompt=prompt, tokens=toks, forwards=forwards,
            fut=types.SimpleNamespace(revealed_at=lambda at=at: list(at))))
    return cell, ref, model, w, sample


def test_a_block_against_the_rows_before_it_is_the_full_pass(tiny):
    """``block_logits`` over ``keys_values`` of the final sequence gives
    the logits the full pass over ``[rows before; the block's state]``
    gave while the answer was generated."""
    import jax.numpy as jnp

    _, ref, model, w, sample = tiny
    L, M = model["block_length"], model["mask_token_id"]
    r = sample[0]
    ids = np.full(64, M, np.int32)
    n = len(r.prompt) + len(r.tokens)
    ids[:n] = np.concatenate([r.prompt, r.tokens])
    kvs = ref.keys_values(w, jnp.asarray(ids), model)
    seen = 0
    for start, state, lg in r.forwards:
        if lg is None or start + L > n:
            continue
        got = ref.block_logits(w, kvs, jnp.int32(start),
                               jnp.asarray(state, jnp.int32), model)
        np.testing.assert_allclose(np.asarray(got), lg, atol=2e-5)
        seen += 1
    assert seen >= 4


def test_the_references_own_reveals_score_zero_and_fp8s_fail_the_limit(tiny):
    from runners import serve_blocks

    cell, ref, model, w, sample = tiny
    chk = cell.config["check"]
    rows, forwards, tokens = serve_blocks.block_gaps(
        ref, w, model, sample, cell.config["serving"]["max_seq"], 11, "fp8")
    assert forwards >= 12 and tokens >= 20
    logit, conf, ctl_logit, ctl_conf = (np.array(c) for c in zip(*rows))
    # f32 against f32 in another order of accumulation: 0 or next to it
    assert logit.max() <= 1e-5 and conf.max() <= 1e-5
    assert (ctl_logit.max() > chk["logit_gap_limit"]
            or ctl_conf.max() > chk["confidence_gap_limit"]), (
        ctl_logit.max(), ctl_conf.max())


def test_a_block_past_the_answer_gives_its_first_forward_alone():
    from reference import sdar_moe as ref

    # a prompt of 6 and 5 answer tokens in blocks of 4: block 1 holds 2
    # prompt tokens, block 2 ends one position past the answer
    seq, at = [1, 2, 3, 4, 5, 6, 11, 12, 13, 14, 15], [1, 0, 0, 1, 0]
    first = ref.block_states(seq, 6, at, 1, 4, 0)
    assert [t for t, _, _ in first] == [0, 1]
    assert first[0][1].tolist() == [5, 6, 0, 0]     # the prompt's remainder
    assert first[0][2].tolist() == [False, False, False, True]
    assert first[1][1].tolist() == [5, 6, 0, 12]
    assert first[1][2].tolist() == [False, False, True, False]
    last = ref.block_states(seq, 6, at, 2, 4, 0)
    assert [t for t, _, _ in last] == [0]
    assert last[0][1].tolist() == [0, 0, 0, 0]
    assert last[0][2].tolist() == [True, False, True, False]
