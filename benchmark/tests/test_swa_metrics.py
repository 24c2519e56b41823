"""The ``.swa`` per-layer metrics of
``mimo-v2-flash-ep16.decode-mixed-lengths``: the kernel-name patterns of
their files against the names as the TPU compiler prints them for this
configuration (``tools/deviceless_stored.py --config
mimo-v2-flash-ep16-serve --hlo``) and nothing else, the ops-and-bytes
functions of ``kernel_costs_swa.py`` against counts made by hand, the
slice reader on a made-up profile (and on a program without the span
attributes: nothing, no raise), the configuration's file against the
catalog's numbers, the traffic file against the issue's, and the fp8
control against the tiny configuration's limit."""
import os

import numpy as np
import pytest

import harness
import kernel_costs
import kernel_costs_swa as costs
import traffic as traffic_mod
from readers import (counter_share, dispatch_join, kernel_roofline,
                     kernel_roofline_slice, trace_op_share)

CELL = "mimo-v2-flash-ep16.decode-mixed-lengths"
BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CFG = harness.load_json(harness.HERE, "configs",
                        "mimo-v2-flash-ep16-serve.json")
MIX = harness.load_json(harness.HERE, "traffic", "decode-mixed-lengths.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NAMES = sorted(m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".swa"))
# no entry reads a prefill's kernels from the device trace: the traced slice
# (13.3-17.3 s of a 40 s window) falls between this mix's first fill and
# its first completions and holds no prefill (PERF.md section 7)
FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in NAMES}

# left-hand sides and targets of the Mosaic calls in the compiled prefill
# and decode programs of a described v5e, with a fusion that reads one
HLO = {
    "decode": '%decode_attention.28 = bf16[512,16,128]{2,1,0:T(8,128)(2,1)'
              'S(1)} custom-call(%a, %b, %c, %d), '
              'custom_call_target="tpu_custom_call"',
    "flash": '%flash_attention_fwd.4 = (bf16[64,3584,128]{2,1,0}, '
             'f32[64,8,3584]{2,1,0}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"',
    "gate_up": '%moe_expert_matmul.100 = bf16[640,2048]{1,0:T(8,128)(2,1)'
               'S(1)} custom-call(%a, %b, %c, %d, %e), '
               'custom_call_target="tpu_custom_call"',
    "down": '%moe_expert_matmul.101 = f32[640,4096]{1,0:T(8,128)S(1)} '
            'custom-call(%a, %b, %moe_expert_matmul.100, %d), '
            'custom_call_target="tpu_custom_call"',
    "router": '%moe_router.49 = f32[128,256]{1,0:T(8,128)S(1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"',
    "fusion": '%fusion.40 = bf16[128,64,128]{2,1,0} '
              'fusion(bf16[512,16,128]{2,1,0} %decode_attention.28), '
              'kind=kLoop',
}


def cell_invariants(bench: dict) -> None:
    """What this file holds of ``BENCHMARK.json``, on the tree's or on one
    with further cells appended (``test_layer_metric_files.py``
    ``test_a_cell_can_be_appended``): no count of anything."""
    names = {m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".swa")}
    # every file of the cell has its entry, and every entry its file
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))
        if n.endswith(".swa.json")}
    assert names and names == files
    for m in bench["per_layer"]:
        if m["name"].endswith(".swa"):
            assert m["workloads"] == [CELL]
        else:
            assert CELL not in m.get("workloads", [])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["decode_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash-ep16-serve", "decode-mixed-lengths", 1)


def test_the_cell_has_its_swa_metrics_and_only_they_list_it():
    cell_invariants(BENCH)
    # the one place that holds the contract's room
    assert len(BENCH["per_layer"]) <= 128
    assert all(len(e["why"]) <= 200 for e in BENCH["workloads"]
               + BENCH["configs"])


@pytest.mark.parametrize("metric,hits", [
    ("decode_kernel_time_pct.swa", {"decode"}),
    ("decode_attention_roofline_pct.swa", {"decode"}),
    ("expert_time_pct.swa", {"gate_up", "down"}),
    ("expert_matmul_roofline_pct.swa", {"gate_up", "down"}),
])
def test_kernel_name_patterns(metric, hits):
    pattern = FILES[metric]["args"]["pattern"]
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, pattern=pattern)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    published = dict(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=4,
        head_dim=192, v_head_dim=128, swa_num_attention_heads=64,
        swa_num_key_value_heads=8, swa_head_dim=192, swa_v_head_dim=128,
        intermediate_size=16384, moe_intermediate_size=2048,
        num_experts_per_tok=8, sliding_window=128, sliding_window_size=128,
        attention_chunk_size=128, attention_value_scale=0.707,
        partial_rotary_factor=0.334, rope_theta=5000000,
        swa_rope_theta=10000, layernorm_epsilon=1e-05, n_group=1,
        topk_group=1, model_type="mimo_v2_flash", topk_method="noaux_tc",
        scoring_func="sigmoid", norm_topk_prob=True,
        add_swa_attention_sink_bias=True,
        add_full_attention_sink_bias=False, n_shared_experts=None,
        routed_scaling_factor=None, max_position_embeddings=262144)
    assert {k: CFG[k] for k in published} == published
    # the two published lists stay whole; the model's are their entries at
    # the layers held
    assert len(CFG["hybrid_layer_pattern"]) == len(
        CFG["moe_layer_freq"]) == 48
    assert [i for i, t in enumerate(CFG["hybrid_layer_pattern"])
            if t == 0] == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mimo-v2-flash-ep16-serve")
    assert sorted(CFG["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "num_nextn_predict_layers"])
    for key, cut in CFG["reduced"].items():
        assert CFG[key] == cut["here"] != cut["published"]
    d = CFG["deployment"]
    assert (d["num_experts_total"], d["vocab_size_total"],
            d["num_hidden_layers_total"]) == (256, 152576, 48)
    assert CFG["n_routed_experts"] * d["chips_per_layer"] == 256
    assert CFG["vocab_size"] * 8 == 152576
    assert CFG["source"] == entry["source"]
    from reference import mimo_v2_flash as ref
    model = ref.model_config(CFG)
    assert model["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert model["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    # the parameters held, as ISSUE 47 reckons them: 3,429.9M (and the
    # norms', sinks' and biases' few thousand)
    spec = ref.param_spec(model)
    held = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert 3429e6 < held < 3431e6
    assert all(isinstance(v, str) and len(v) > 20
               for v in CFG["assumed"].values())


def test_the_traffic_file_holds_the_issues_numbers():
    # the issue's numbers letter for letter; the mix's own seed is the
    # PR's number x 1000 + 1, as every mix's here, and not picked by its draw
    assert (MIX["generator"], MIX["clients"], MIX["pool"],
            MIX["max_total"]) == ("closed_loop", 128, 256, 4096)
    assert MIX["mix_seed"] == 47001
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.9, "min": 64, "max": 3584}
    assert MIX["answer_len"] == {"dist": "uniform", "min": 512,
                                 "max": 2048}
    prompts, answers = traffic_mod._sizes(MIX, MIX["pool"])
    s = CFG["serving"]
    assert prompts.min() >= 64 and prompts.max() <= max(s["prompt_buckets"])
    assert (prompts + answers).max() <= s["max_seq"] and answers.min() >= 1
    # all but a hundredth of the prompts pass the window (the lognormal's
    # share under 128 is 1.0%), and every bucket is longer than a ring, so
    # every prefill folds
    assert (prompts > CFG["sliding_window"]).mean() >= 0.98
    assert min(s["prompt_buckets"]) > CFG["sliding_window"]
    # every bucket is used, and has its warm request
    buckets = np.asarray(s["prompt_buckets"])
    used = {int(buckets[np.searchsorted(buckets, p)]) for p in prompts}
    assert used == set(s["prompt_buckets"])
    warm = {int(buckets[np.searchsorted(buckets, p)])
            for p, _ in MIX["warm"]}
    assert warm == used
    assert CFG["serving"]["slots"] == MIX["clients"]


def test_every_seed_sends_the_same_sizes_in_another_order():
    """The pool is one multiset of (prompt, answer) sizes under the mix's
    own seed; a run's seed only permutes it. A 40 s window sends some 210
    of the 256, the first 128 of them as the first fill: under the
    generator's plain permutation that half differs by seed, which was the
    3% between seeds (PERF.md section 6) and is why the cell's runner deals
    the same list in rounds (the tests below)."""
    fills = []
    for seed in (3470002101, 3470002102, 2 ** 31 + 11):
        reqs = traffic_mod.closed_loop(MIX, seed, CFG["vocab_size"])
        assert sorted((len(r.prompt), r.max_new) for r in reqs) == sorted(
            zip(*map(list, traffic_mod._sizes(MIX, MIX["pool"]))))
        assert all(r.prompt.max() < CFG["vocab_size"] for r in reqs)
        fills.append(sum(len(r.prompt) for r in reqs[:MIX["clients"]]))
    assert len(set(fills)) == len(fills)
    mean = sum(fills) / len(fills)
    assert all(abs(f - mean) / mean < 0.15 for f in fills)


def _dealt(seed):
    from runners import serve_rounds
    reqs = traffic_mod.closed_loop(MIX, seed, CFG["vocab_size"])
    return reqs, serve_rounds.in_rounds(reqs, seed, **MIX["deal"])


@pytest.mark.parametrize("seed", [3470002101, 7, 2 ** 31 + 11])
def test_the_rounds_deal_the_generators_own_requests(seed):
    """The cell's runner sends what the generator made for the seed: the
    same 256 request objects (sizes under ``mix_seed``, the seed's tokens),
    each once, in another order; and the same seed gives the same order."""
    assert CFG["runner"] == "serve_rounds"
    assert MIX["deal"] == {"rounds": 8, "answer_classes": 2}
    reqs, dealt = _dealt(seed)
    assert len(dealt) == MIX["pool"]
    assert sorted(map(id, dealt)) == sorted(map(id, reqs))
    again = _dealt(seed)[1]
    assert [(len(r.prompt), r.max_new) for r in again] == [
        (len(r.prompt), r.max_new) for r in dealt]
    assert all((a.prompt == b.prompt).all() for a, b in zip(again, dealt))


def test_a_round_holds_one_of_every_class_on_every_seed():
    """Sorted by prompt and cut into bands of 16, a band sorted by answer
    and cut in two: 32 classes of 8 neighbours, the same on every seed
    (ties aside). Each of the 8 rounds holds one request of each class, so
    the first fill (4 rounds) and any 32 consecutive requests offer the same
    mix of sizes whatever the seed; which neighbour comes in which round,
    and the order inside a round, are the seed's."""
    prompts, answers = traffic_mod._sizes(MIX, MIX["pool"])
    by_prompt = np.sort(prompts)
    fills, orders = [], []
    for seed in (3470002101, 3470002102, 2 ** 31 + 11, 5):
        _, dealt = _dealt(seed)
        sizes = [(len(r.prompt), r.max_new) for r in dealt]
        orders.append(sizes)
        for lo in range(0, 256, 32):
            rnd = sorted(p for p, _ in sizes[lo:lo + 32])
            # the k-th shortest prompt of a round lies in the k-th band
            for k in range(16):
                band = by_prompt[16 * k:16 * k + 16]
                assert band[0] <= rnd[2 * k] <= rnd[2 * k + 1] <= band[-1]
        fills.append(sum(p for p, _ in sizes[:MIX["clients"]]))
    assert len({tuple(o) for o in orders}) == len(orders)
    # the first fill's prompt tokens: within 3% between seeds, where the
    # plain permutation's differ by up to 15% (the test above)
    mean = sum(fills) / len(fills)
    assert all(abs(f - mean) / mean < 0.03 for f in fills)
    # whole rounds offer the same work but for which neighbours each class
    # has dealt so far (1%); 210 requests, what a window sends, end inside
    # the seventh round: prompt tokens within 3%
    for n, room in ((192, 0.01), (210, 0.03)):
        sent = [sum(p for p, _ in o[:n]) for o in orders]
        assert (max(sent) - min(sent)) / min(sent) < room


def test_rounds_of_a_pool_that_classes_do_not_divide():
    """Any pool: a class that is short takes part in fewer rounds."""
    from runners import serve_rounds
    reqs = [traffic_mod.Request(index=i, prompt=np.zeros(5 + i, np.int64),
                                max_new=3 + i % 4) for i in range(13)]
    dealt = serve_rounds.in_rounds(reqs, 1, rounds=4, answer_classes=2)
    assert sorted(map(id, dealt)) == sorted(map(id, reqs))
    # 13 = a band of 8 (two classes of 4) and a band of 5 (4 and 1):
    # rounds of 4, 3, 3, 3, each with two of the first band
    short = [len(r.prompt) - 5 < 8 for r in dealt]
    assert [sum(short[a:b]) for a, b in ((0, 4), (4, 7), (7, 10),
                                         (10, 13))] == [2, 2, 2, 2]


def test_the_costs_match_the_hand_counts():
    # one decode step of one full layer, 128 slots with 2,048 rows fetched
    # each: 4 key/value heads x (192 + 128) bf16 numbers a row; 64 query
    # heads each a product over 192 and one over 128
    rows = 128 * 2048
    ops, moved = costs.decode_attention_cost(rows, 64, 4, 192, 128)
    assert moved == rows * 4 * 320 * 2 == 671_088_640
    assert ops == rows * 64 * 2 * 320
    assert ops / moved == 16            # bound by the bytes, 16 against 240
    # a window layer's ring: 8 heads x 128 rows a slot, whatever the context
    ops, moved = costs.decode_attention_cost(128 * 128, 64, 8, 192, 128)
    assert moved == 128 * 128 * 8 * 640 == 83_886_080
    # the flash forward of one prompt of 1,000 real rows, one layer: a full
    # layer scores 1000 x 1001 / 2 pairs a head, a window layer 128 x 129 /
    # 2 + 872 x 128
    full, _ = costs.flash_fwd_cost([1000], 1, 64, 192, 128, 0)
    assert full == 64 * 500_500 * 2 * 320
    win, _ = costs.flash_fwd_cost([1000], 1, 64, 192, 128, 128)
    assert win == 64 * (8256 + 872 * 128) * 2 * 320
    assert 4 < full / win < 4.5
    # a prompt inside the window is causal in both
    assert costs.flash_fwd_cost([100], 1, 64, 192, 128, 128) == \
        costs.flash_fwd_cost([100], 1, 64, 192, 128, 0)
    # an expert is three 4096 x 2048 matrices (the dense layer's 16,384 is
    # not an expert's width)
    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    ops, moved = kernel_costs.expert_matmul_cost(64, 16, H, F)
    assert moved == 16 * 3 * 4096 * 2048 * 2 == 805_306_368


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs,
            "span_id": f"{name}{t0}", "parent_id": None, "trace_id": "t",
            "thread": "d"}


def test_slice_reader_reads_the_same_calls_on_both_sides(monkeypatch):
    """Two decode dispatches and one prefill inside the slice, a third
    decode outside it: the cost is of the joined dispatches' own rows and
    the time of the operations inside their modules, so a dispatch the
    slice does not hold moves neither."""
    joined = [
        {"path": "chained", "launch_t": 10.001, "module_start_ns": 1_000,
         "module_end_ns": 9_000},
        {"path": "run", "launch_t": 10.101, "module_start_ns": 10_000,
         "module_end_ns": 19_000},
        {"path": "chained", "launch_t": 10.201, "module_start_ns": 20_000,
         "module_end_ns": 29_000},
    ]
    rows = lambda full, window: {"attn_rows_full": full,
                                 "attn_rows_window": window}
    spans = [
        _span("serving.settle", 10.05, 10.06, launch_t0=10.0,
              **rows(16 * 128 * 2048, 16 * 128 * 128)),
        _span("serving.settle", 10.15, 10.16, launch_t0=10.1),
        _span("serving.settle", 10.25, 10.26, launch_t0=10.2,
              **rows(16 * 128 * 1024, 16 * 128 * 128)),
        _span("serving.settle", 11.0, 11.1, launch_t0=10.9,
              **rows(10 ** 12, 10 ** 12)),          # outside the slice
        _span("serving.settle", 9.0, 9.1),           # carries nothing
    ]
    ops = [(HLO["decode"], 2_000, 3_000), (HLO["decode"], 4_000, 6_000),
           (HLO["flash"], 11_000, 15_000), (HLO["decode"], 21_000, 22_500),
           (HLO["decode"], 40_000, 90_000),         # outside every module
           (HLO["fusion"], 2_000, 8_000)]
    monkeypatch.setattr(dispatch_join, "_joined", lambda ctx: joined)
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_slice.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "spans": spans, "counters": {}}
    got = kernel_roofline_slice.read(
        ctx, **FILES["decode_attention_roofline_pct.swa"]["args"])
    moved = (16 * 128 * 3072 * 4 + 2 * 16 * 128 * 128 * 8) * 640.0
    assert got == pytest.approx(
        100.0 * (moved / PEAKS["hbm_bytes_per_s"]) / 4.5e-6)
    # a program without the attributes (the parent commit), nothing
    # joined, or no trace: nothing, and no raise
    args = FILES["decode_attention_roofline_pct.swa"]["args"]
    bare = [dict(s, attrs={}) for s in spans]
    assert kernel_roofline_slice.read(dict(ctx, spans=bare), **args) is None
    assert kernel_roofline_slice.read(dict(ctx, trace=None), **args) is None
    monkeypatch.setattr(dispatch_join, "_joined", lambda ctx: None)
    assert kernel_roofline_slice.read(
        ctx, **FILES["decode_attention_roofline_pct.swa"]["args"]) is None


def test_expert_roofline_and_counter_shares_read_their_families(monkeypatch):
    """The expert matmul through the slice reader, both paths: a decode
    chunk of 16 steps x 6 layers with every held expert hit is bound by
    the experts' bytes, a prefill by its rows' products."""
    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    chunk = {"moe_expert_tokens": 96 * 64, "moe_experts_hit": 96 * 16,
             "moe_expert_calls": 96}
    prefill = {"moe_expert_tokens": 6 * 8000, "moe_experts_hit": 6 * 16,
               "moe_expert_calls": 6}
    least = costs.moe_expert_matmul_slice_seconds(CFG, [chunk, prefill, {}],
                                                  PEAKS)
    assert least == pytest.approx(
        96 * 16 * 3 * H * F * 2 / 819e9
        + 6 * 8000 * 2 * 3 * H * F / PEAKS["bf16_flops_per_s"], rel=1e-6)
    joined = [{"path": "chained", "launch_t": 10.001,
               "module_start_ns": 0, "module_end_ns": 300_000_000},
              {"path": "run", "launch_t": 10.401,
               "module_start_ns": 400_000_000, "module_end_ns": 500_000_000}]
    spans = [_span("serving.settle", 10.3, 10.31, launch_t0=10.0, **chunk),
             _span("serving.settle", 10.5, 10.51, launch_t0=10.4, **prefill)]
    ops = [(HLO["gate_up"], 1_000, 70_001_000),
           (HLO["down"], 80_000_000, 115_000_000),
           (HLO["gate_up"], 410_000_000, 418_000_000),
           (HLO["down"], 600_000_000, 900_000_000)]   # outside a module
    monkeypatch.setattr(dispatch_join, "_joined", lambda ctx: joined)
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_slice.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "spans": spans, "counters": {}}
    args = FILES["expert_matmul_roofline_pct.swa"]["args"]
    got = kernel_roofline_slice.read(ctx, **args)
    assert got == pytest.approx(100.0 * least / 0.113) and 0 < got < 100
    bare = [dict(s, attrs={"launch_t0": s["attrs"]["launch_t0"]})
            for s in spans]
    assert kernel_roofline_slice.read(dict(ctx, spans=bare), **args) is None
    counters = {
        "decode_attention_rows_total{kind=full}": 2 * 128 * 2048.0,
        "decode_attention_rows_total{kind=window}": 5 * 128 * 128.0,
        "flash_attention_blocks_total{kind=full,what=visited}": 2 * 64.0,
        "flash_attention_blocks_total{kind=window,what=visited}": 5 * 15.0,
        "flash_attention_blocks_total{kind=window,what=skipped}": 5 * 49.0}
    ctx = {"counters": counters}
    assert counter_share.read(
        ctx, **FILES["window_rows_share_pct.swa"]["args"]) == pytest.approx(
        100.0 * 5 * 128 / (2 * 2048 + 5 * 128))
    assert counter_share.read(
        ctx, **FILES["flash_blocks_skipped_pct.swa"]["args"]
    ) == pytest.approx(100.0 * 245 / (128 + 75 + 245))
    assert counter_share.read(
        {"counters": {}},
        **FILES["window_rows_share_pct.swa"]["args"]) is None


def test_a_light_slice_reads_its_own_calls_not_the_windows_mean(monkeypatch):
    """Why the expert share of this cell is read over the slice's own
    dispatches. Two decode chunks in the slice whose executions hit 12 of
    the 16 held experts, in a window whose mean execution (prefills and
    other contexts in it) hits all 16: the window's counters over the
    slice's mean time (``kernel_roofline_in``) read a third too high, past
    100, which no kernel does; the joined chunks' own counts over the time
    of the operations inside their modules read what the kernel did."""
    from readers import kernel_roofline_in

    H, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    expert = 3 * H * F * 2 / PEAKS["hbm_bytes_per_s"]   # one's bytes: 61 us
    # a chunk: 16 steps x 6 layers; a call pair takes 1.25 x its floor
    chunk = {"moe_expert_tokens": 96 * 48, "moe_experts_hit": 96 * 12,
             "moe_expert_calls": 96}
    pair_ns = 1.25 * 12 * expert * 1e9
    joined = [{"path": "chained", "launch_t": 10.001 + i, "module_start_ns":
               i * 1e9, "module_end_ns": (i + 1) * 1e9} for i in range(2)]
    spans = [_span("serving.settle", 10.5 + i, 10.6 + i, launch_t0=10.0 + i,
                   **chunk) for i in range(2)]
    ops = [(HLO["gate_up" if k % 2 == 0 else "down"],
            i * 1e9 + k * 2e6, i * 1e9 + k * 2e6 + pair_ns / 2)
           for i in range(2) for k in range(192)]
    counters = {}
    for layer in range(1, 7):
        lab = f"{{layer={layer},phase=decode}}"
        counters["moe_expert_calls_total" + lab] = 4000.0
        counters["moe_expert_tokens_total" + lab] = 4000 * 64.0
        counters["moe_experts_hit_total" + lab] = 4000 * 16.0
    monkeypatch.setattr(dispatch_join, "_joined", lambda ctx: joined)
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(kernel_roofline_slice.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    ctx = {"trace": {"window_s": 4.0}, "peaks": PEAKS, "config": CFG,
           "counters": counters, "spans": spans}
    args = FILES["expert_matmul_roofline_pct.swa"]["args"]
    assert kernel_roofline_slice.read(ctx, **args) == pytest.approx(80.0)
    window = kernel_roofline_in.read(
        ctx, pattern=args["pattern"], module="kernel_costs_hybrid",
        cost="moe_expert_matmul_seconds")
    assert window == pytest.approx(80.0 * 16 / 12) and window > 105


def test_expert_load_reads_the_ops_own_histogram():
    """``expert_load_max_over_mean.swa``: the mean of the histogram the
    expert op's counts feed, over the window; nothing where the program
    has no such family."""
    from readers import histogram_mean

    args = FILES["expert_load_max_over_mean.swa"]["args"]
    ctx = {"counters": {"moe_expert_load_max_over_mean_sum": 30.0,
                        "moe_expert_load_max_over_mean_count": 20.0}}
    assert histogram_mean.read(ctx, **args) == pytest.approx(1.5)
    assert histogram_mean.read({"counters": {}}, **args) is None


def test_the_cost_never_passes_what_the_kernels_themselves_do():
    """The decode kernel moves a key row of 256 lanes beside a value row of
    128 for every row of every block it fetches, and scores 16 sublane
    rows a key/value head: the cost counts the same rows at 192 + 128 and
    the query heads there are. The flash forward scores whole 128 x 128
    blocks: the cost counts the pairs the mask allows inside them."""
    from paddle_tpu.kernels import window_block_visits
    from paddle_tpu.kernels.decode_attention import (decode_walk_blocks,
                                                     kv_tile)

    lengths = np.array([1, 700, 1024, 1025, 3000, 4096])
    shape, bf16 = (6, 4, 4096, 256), np.dtype("uint16")
    _, tile = kv_tile(4, 4096, 256, bf16, 128, v_dim=128)
    blocks, _ = decode_walk_blocks(lengths, shape, bf16, 128, v_dim=128)
    rows = blocks * tile
    ops, need = costs.decode_attention_cost(rows, 64, 4, 192, 128)
    assert need < rows * 4 * (256 + 128) * 2
    assert ops <= rows * 4 * 16 * 2 * (256 + 128)
    for window in (0, 128):
        seen, _ = window_block_visits(3584, 3584, window)
        ops, _ = costs.flash_fwd_cost([3584], 1, 64, 192, 128, window)
        assert ops <= 64 * seen * 128 * 128 * 2 * (256 + 128)
    # a window layer of the longest bucket scores a fourteenth of a causal
    # layer's pairs, and visits a fourteenth of its grid
    seen, grid = window_block_visits(3584, 3584, 128)
    assert (seen, grid) == (55, 784)


def test_fp8_control_fails_the_tiny_limit_that_the_reference_passes():
    """Reference against reference at the rehearsal's sizes: the
    reference's own choices score 0, and the fp8-operand control's lie
    further below the best than the tiny configuration's limit."""
    import jax.numpy as jnp

    from reference import mimo_v2_flash as ref

    cell = harness.Cell(BENCH, CELL, rehearse=True)
    limit = cell.config["check"]["logit_gap_limit"]
    model = ref.model_config(cell.config)
    w = dict(ref.make_weights(ref.param_spec(model), 11))
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(1, model["vocab_size"], 96))
    own = jnp.argmax(ref.logits(w, ids, model), axis=-1)
    served, control = ref.gaps_fn(model, "fp8")(w, ids, own)
    assert float(jnp.max(served)) == 0.0
    assert float(jnp.max(control)) > limit
