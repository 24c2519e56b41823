"""``tools/turns.py``: a cell's runs with what their scheduler turns were
made of. Its arithmetic on hand-made counters, its two small helpers, and
one run of it end to end on the CPU at the rehearsal sizes."""
import json
import os
import subprocess
import sys

import pytest

import harness
from tools import turns


def test_put_sets_a_value_the_configuration_has_and_no_other():
    cfg = {"serving": {"generation": {"decode_chunk": 4}}}
    turns.put(cfg, "serving.generation.decode_chunk=16")
    assert cfg["serving"]["generation"]["decode_chunk"] == 16
    with pytest.raises(KeyError):
        turns.put(cfg, "serving.generation.decode_chunks=16")


def test_spread_leaves_out_the_farthest_run_where_that_narrows_it():
    runs = [100.0, 101.0, 102.0, 103.0, 104.0, 150.0]
    assert turns.trimmed_spread(runs) < turns.spread(runs)
    even = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    assert turns.trimmed_spread(even) <= turns.spread(even)
    assert turns.spread(even) == pytest.approx(3.5 / 102.5)


def test_arithmetic_of_a_window():
    """100 decode dispatches of 0.1 s, 90 prefills of 0.02 s that seated
    720 sequences, in a window of 13 s: 0.9 prefills a decode dispatch, 8
    sequences a prefill, 1.2 s outside the dispatches, 12 ms a turn."""
    delta = {
        "serving_decode_chunk_seconds{}_count": 100.0,
        "serving_decode_chunk_seconds{}_sum": 10.0,
        "serving_prefill_seconds{}_count": 90.0,
        "serving_prefill_seconds{}_sum": 1.8,
        "serving_first_token_seconds{}_count": 720.0,
        "executor_step_seconds{path=chained}_count": 100.0,
        "executor_step_seconds{path=chained}_sum": 9.5,
        "executor_step_seconds{path=run}_sum": 1.7,
        "executor_fetch_wait_seconds{path=chained}_sum": 9.0,
        "serving_loop_seconds{phase=settle}_sum": 0.4,
    }
    got = turns.arithmetic(delta, 13.0)
    assert got["prefills_per_decode"] == pytest.approx(0.9)
    assert got["sequences_per_prefill"] == pytest.approx(8.0)
    assert got["decode_ms"] == pytest.approx(100.0)
    assert got["prefill_ms"] == pytest.approx(20.0)
    assert got["walls_s"] == pytest.approx(11.8)
    assert got["wait_ms_per_turn"] == pytest.approx(12.0)
    assert got["chained_step_s"] == pytest.approx(9.5)
    assert got["fetch_wait_s"] == {"run": 0.0, "chained": 9.0}
    assert got["loop_s"]["settle"] == pytest.approx(0.4)


def test_a_run_end_to_end_at_the_rehearsal_sizes():
    """The child goes through the cell's runner as ``run.py`` does, with a
    setting replaced, and the parent reads its row: correct, and with as
    many decode dispatches as the executor's chained path counted."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "tools", "turns.py"),
         "--workload", "gpt2-base.decode-saturated", "--seeds", "2147483693",
         "--seconds", "1", "--rehearse",
         "--set", "serving.generation.decode_chunk=2"],
        capture_output=True, text=True, timeout=600, cwd=harness.REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0
    assert row["decode_dispatches"] == row["chained_steps"] > 0
    assert row["prefill_dispatches"] > 0
    assert row["sequences_per_prefill"] >= 1.0
    assert "decode_tokens_per_s" not in row      # a rehearsal: no metric
