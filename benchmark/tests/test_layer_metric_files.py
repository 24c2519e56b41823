"""Every file of ``layer_metrics/`` names a reader that exists and agrees
with its entry in ``BENCHMARK.json``; the metrics that read the program's
counters and spans find something to read in the CPU rehearsal of their
cell, the patterns of the device-trace metrics match the kernels' names as
the TPU compiler prints them (and nothing else), a roofline share reads 100
where the kernel's operations take exactly the cost function's least time,
and a next cell can be appended to ``BENCHMARK.json`` with no edit here."""
import collections
import glob
import importlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

import harness
from readers import dispatch_join, kernel_roofline, trace_op_share, xplane
from tools import ninth_cell

BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
FILES = {os.path.basename(p)[:-len(".json")]: harness.load_json(p)
         for p in sorted(glob.glob(os.path.join(harness.HERE,
                                                "layer_metrics", "*.json")))}


@pytest.mark.parametrize("name", sorted(FILES))
def test_file_names_a_reader_and_agrees_with_its_entry(name):
    spec = FILES[name]
    assert spec["name"] == name
    assert os.path.exists(os.path.join(harness.HERE, "readers",
                                       spec["reader"] + ".py"))
    entry = ENTRIES.get(name)
    if entry is not None:        # a file may wait for its cell
        for key in ("unit", "layer", "moves", "source"):
            assert spec[key] == entry[key], key


def every_entry_has_its_file(bench: dict, *more_dirs) -> None:
    files = set(FILES)
    for d in more_dirs:
        files |= {n[:-len(".json")] for n in os.listdir(d)}
    assert {m["name"] for m in bench["per_layer"]} <= files


def test_every_entry_has_its_file():
    every_entry_has_its_file(BENCH)


def cell_invariants(bench: dict, cell: str) -> None:
    """What holds of every cell, whatever else ``BENCHMARK.json`` lists:
    its per-layer metrics share one suffix that no other cell's carry, each
    moves an end-to-end metric the cell reports, and the cell reports the
    set-up time, another end-to-end metric and a per-layer one."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    per_layer = [m for m in bench["per_layer"]
                 if (cell in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]     # as harness.Cell
    suffixes = {m["name"].rsplit(".", 1)[1] for m in per_layer}
    assert len(suffixes) == 1, suffixes
    for m in bench["per_layer"]:
        if m["name"].rsplit(".", 1)[1] in suffixes:
            assert m["workloads"] == [cell], m["name"]
        else:
            assert cell not in m.get("workloads", []), m["name"]
    assert "setup_s" in reported and len(reported) >= 2
    assert per_layer and {m["moves"] for m in per_layer} <= reported
    assert sum(w["name"] == cell for w in bench["workloads"]) == 1


# a share of the chip's peak (the CPU has no entry in peaks.json)
NEEDS_THE_CHIP = {"mfu_pct.train"}
CELLS = [w["name"] for w in BENCH["workloads"]]
PROGRAM_METRICS = [(m["name"], cell) for cell in CELLS
                   for m in harness.Cell(BENCH, cell).per_layer
                   if m["source"] != "device_trace"
                   and m["name"] not in NEEDS_THE_CHIP]
_LISTED = collections.Counter(m for m, _ in PROGRAM_METRICS)


@pytest.fixture(scope="module")
def would_report():
    """``cell -> the names a traced CPU rehearsal of it would report``, one
    rehearsal a cell for the whole module."""
    ran = {}

    def of(cell):
        if cell not in ran:     # a rehearsal that fails is not run again
            ran[cell] = subprocess.run(
                [sys.executable, os.path.join(harness.HERE, "run.py"),
                 "--workload", cell, "--seed", "2147483777", "--seconds",
                 "2", "--trace", "1", "--rehearse"],
                capture_output=True, text=True, timeout=900,
                cwd=harness.REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        p = ran[cell]
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])["would_report"]
    return of


@pytest.mark.parametrize(
    "metric,cell", PROGRAM_METRICS,
    ids=[m if _LISTED[m] == 1 else f"{m}@{c}" for m, c in PROGRAM_METRICS])
def test_program_metrics_find_something_in_the_rehearsal(metric, cell,
                                                         would_report):
    """A traced rehearsal of its cell reports every per-layer metric that
    reads the program (counters, spans) or the benchmark's own clock; only
    the device trace has nothing to give on the CPU."""
    assert metric in would_report(cell)


# the left-hand sides of the Mosaic custom calls in the compiled HLO of a
# v5e (tests/test_deviceless_compile.py keeps them so), with one fusion;
# "append" is the K/V append of a chunk of rows (PR 34), a Mosaic call that
# is no attention and that no metric reads since PR 51
HLO = {
    "fwd": '%flash_attention_fwd.3 = (f32[24,512,64]{2,1,0:T(8,128)S(1)}, '
           'f32[24,8,512]{2,1,0}) custom-call(%a), '
           'custom_call_target="tpu_custom_call"',
    "dq": '%flash_attention_bwd_dq.1 = f32[24,512,64]{2,1,0} '
          'custom-call(%a), custom_call_target="tpu_custom_call"',
    "dkv": '%flash_attention_bwd_dkv.1 = (f32[24,512,64]{2,1,0}, '
           'f32[24,512,64]{2,1,0}) custom-call(%a), '
           'custom_call_target="tpu_custom_call"',
    "decode": '%decode_attention.1 = f32[96,8,64]{2,1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call"',
    "append": '%kv_append.5 = f32[64,12,64,1024]{3,2,1,0:T(8,128)} '
              'custom-call(%c, %n, %l), '
              'custom_call_target="tpu_custom_call"',
    "fusion": '%fusion.7 = f32[8]{0} fusion(f32[8]{0} %flash_attention_fwd.3)'
              ', kind=kLoop',
}


@pytest.mark.parametrize("metric,hits", [
    ("flash_fwd_time_pct.train", {"fwd"}),
    ("flash_bwd_time_pct.train", {"dq", "dkv"}),
    ("decode_kernel_time_pct.saturated", {"decode"}),
])
def test_kernel_share_patterns(metric, hits):
    args = FILES[metric]["args"]
    ctx = {"trace": {"busy_s": 10.0,
                     "op_seconds": {line: 1.0 for line in HLO.values()}}}
    assert trace_op_share.read(ctx, **args) == pytest.approx(
        10.0 * len(hits))
    for key, line in HLO.items():
        one = {"trace": {"busy_s": 1.0, "op_seconds": {line: 1.0}}}
        got = trace_op_share.read(one, **args)
        assert (got == pytest.approx(100.0)) if key in hits else got is None


# -- a roofline share cannot pass 100 ----------------------------------------

ROOFLINES = sorted(n for n in ENTRIES if "_roofline_pct." in n)
# a device operation of each kernel a cost function knows, as the TPU
# compiler names it
KERNELS = ("moe_expert_matmul", "decode_attention", "mla_decode_attention",
           "gdn_chunk_scan", "gdn_decode_step", "ssd_chunk_scan",
           "ssd_decode_step")
OP = '%{}.7 = f32[8,128]{{1,0}} custom-call(%a), ' \
     'custom_call_target="tpu_custom_call"'
# what the program counts of a window and notes of a dispatch, every family
# and attribute a cost function reads, at any plausible size
COUNTED = {"moe_expert_calls_total": 700.0, "moe_expert_tokens_total": 44800.0,
           "moe_experts_hit_total": 5600.0, "gdn_calls_total": 300.0,
           "gdn_tokens_total": 19200.0, "ssm_calls_total": 900.0,
           "ssm_tokens_total": 57600.0, "latent_attention_calls_total": 800.0,
           "latent_attention_rows_total": 2.1e8,
           "decode_attention_calls_total": 600.0,
           "decode_attention_keys_total": 3.9e7}
NOTED = {"moe_expert_tokens": 6144, "moe_experts_hit": 1536,
         "moe_expert_calls": 96, "attn_rows_full": 16 * 128 * 2048,
         "attn_rows_window": 16 * 128 * 128}


def _window(monkeypatch, cell: str, ops: list) -> dict:
    """A traced window made by hand: the counters above under both phases,
    two dispatches (a decode chunk, a prefill) joined to their modules with
    a settle span each, and ``ops`` as the profile's device operations."""
    counters = {f"{name}{{layer=0,phase={phase}}}": v / 2
                for name, v in COUNTED.items()
                for phase in ("decode", "prefill")}
    joined = [{"path": "chained", "launch_t": 10.001, "module_start_ns": 0,
               "module_end_ns": 4e9},
              {"path": "run", "launch_t": 10.101, "module_start_ns": 5e9,
               "module_end_ns": 9e9}]
    spans = [{"name": "serving.settle", "t0": t, "t1": t + 0.01,
              "attrs": dict(NOTED, launch_t0=t0)}
             for t, t0 in ((10.05, 10.0), (10.15, 10.1))]
    monkeypatch.setattr(dispatch_join, "_joined", lambda ctx: joined)
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "p")
    monkeypatch.setattr(xplane, "load",
                        lambda path: {"devices": {"d": {"ops": ops}}})
    return {"trace": {"window_s": 4.0}, "config": harness.Cell(BENCH,
                                                               cell).config,
            "peaks": harness.load_json(harness.HERE, "peaks.json")[
                "devices"]["TPU v5 lite"],
            "counters": counters, "spans": spans}


@pytest.mark.parametrize("metric", ROOFLINES)
def test_roofline_cost_is_a_floor(metric, monkeypatch):
    """Where the kernel's operations last exactly the least seconds its
    cost function gives for them, the share reads 100.0; with any of them
    longer it reads less. So a reading over 100 on the chip says that the
    count and the time are not of the same calls, never that a kernel beat
    its floor."""
    spec = FILES[metric]
    reader = importlib.import_module(f"readers.{spec['reader']}")
    args = spec["args"]
    (kernel,) = [k for k in KERNELS
                 if re.search(args["pattern"], OP.format(k))]
    ops = []
    ctx = _window(monkeypatch, ENTRIES[metric]["workloads"][0], ops)
    cost = getattr(importlib.import_module(args.get("module",
                                                    "kernel_costs")),
                   args["cost"])
    if spec["reader"] == "kernel_roofline_slice":
        # the joined dispatches of the entry's path, their noted work; an
        # operation inside each one's module
        starts = [t for path, t in (("chained", 1e9), ("run", 6e9))
                  if args.get("path") in (None, path)]
        each = cost(ctx["config"], [NOTED] * len(starts),
                    ctx["peaks"]) / len(starts)
    else:
        seconds, calls = cost(ctx["config"], ctx["counters"], ctx["peaks"])
        each, starts = seconds / calls, [1e9, 2e9, 6e9]
    assert each > 0
    ops[:] = [(OP.format(kernel), t, t + each * 1e9) for t in starts]
    # other kernels' operations, and this kernel's outside every joined
    # module, move a share read over the slice's own calls not at all
    ops += [(OP.format(k), 3e9, 3.5e9) for k in KERNELS if k != kernel]
    assert reader.read(ctx, **args) == pytest.approx(100.0, rel=1e-9)
    name, t0, t1 = ops[0]
    ops[0] = (name, t0, t1 + 0.25 * each * 1e9)
    slower = reader.read(ctx, **args)
    assert 100.0 / 1.25 - 1e-6 <= slower < 100.0


# -- the next cell is an addition ------------------------------------------------

def _own_invariants(cell: str):
    """``cell_invariants`` of the cell's own test file, where it has one."""
    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "test_*_metrics.py"))):
        with open(path) as f:
            if f'CELL = "{cell}"' not in f.read():
                continue
        spec = importlib.util.spec_from_file_location(
            "benchmark_tests." + os.path.basename(path)[:-3], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.cell_invariants
    return None


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_can_be_appended(cell, tmp_path):
    """With a made-up ninth configuration, cell and suffix of 16 entries at
    the end of every list, what this cell's tests hold of ``BENCHMARK.json``
    still holds, and so does every entry's file: the next cell's PR edits
    no test."""
    bench, files = ninth_cell.appended(BENCH, name="made-up-in-this-test",
                                       suffix="madeup")
    assert len(bench["per_layer"]) == len(BENCH["per_layer"]) + 16
    assert [len(bench[k]) - len(BENCH[k])
            for k in ("configs", "workloads", "end_to_end")] == [1, 1, 0]
    for new in files:
        if new.startswith("layer_metrics/"):
            (tmp_path / os.path.basename(new)).write_text("{}")
    every_entry_has_its_file(bench, tmp_path)
    cell_invariants(bench, cell)
    cell_invariants(bench, bench["workloads"][-1]["name"])
    own = _own_invariants(cell)
    if own is not None:
        own(bench)
