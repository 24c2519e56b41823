"""Every file of ``layer_metrics/`` names a reader that exists and agrees
with its entry in ``BENCHMARK.json``; the metrics that read the program's
counters and spans find something to read in the CPU rehearsal of their
cell, and the patterns of the device-trace metrics match the kernels'
names as the TPU compiler prints them (and nothing else)."""
import glob
import json
import os
import subprocess
import sys

import pytest

import harness
from readers import trace_op_share

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


def test_every_entry_has_its_file():
    assert set(ENTRIES) <= set(FILES)


# a share of the chip's peak, and the count of Pallas routes (the CPU takes
# the primitive routes)
NEEDS_THE_CHIP = {"mfu_pct.train", "pallas_routes.train"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_program_metrics_find_something_in_the_rehearsal(cell):
    """A traced rehearsal would report every per-layer metric of the cell
    that reads the program (counters, spans) or the benchmark's own
    clock; only the device trace has nothing to give on the CPU."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", cell, "--seed", "2147483777", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=harness.REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    want = {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"
            and m["name"] not in NEEDS_THE_CHIP}
    assert want - set(last["would_report"]) == set()


# the left-hand sides of the Mosaic custom calls in the compiled HLO of a
# v5e (tests/test_deviceless_compile.py keeps them so), with one fusion;
# "append" is the decode step's K/V append of PR 34, a Mosaic call that is
# no attention
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
    ("attention_time_pct.train", {"fwd", "dq", "dkv", "decode", "append"}),
    ("attention_time_pct.saturated", {"fwd", "decode"}),
    ("kv_append_time_pct.saturated", {"append"}),
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
