"""``run.py`` end to end on the CPU at the files' tiny ``rehearsal`` sizes,
for every cell of ``BENCHMARK.json``: the same control flow as on the chip
(seeded weights planted, warm-up, window, reference check, result line),
and never a metric printed under any name. Also: without ``--rehearse`` a
CPU run exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

import harness

BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args):
    return subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), *args],
        capture_output=True, text=True, env=ENV, timeout=900,
        cwd=harness.REPO)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_the_cpu(cell, trace):
    p = _run("--workload", cell, "--seed", "2147483659", "--seconds", "2",
             "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert "metrics" not in last and "device" not in last
    assert last["attempted"] > 0 and last["failed"] == 0
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert set(last["would_report"]) <= names


def test_no_accelerator_is_an_error_and_prints_no_result():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr
