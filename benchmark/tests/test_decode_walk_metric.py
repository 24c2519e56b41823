"""``decode_walk_pct.*`` is the window's mean of the program's histogram
``decode_attention_walk_share`` (per decode dispatch: k-blocks the decode
kernel fetches over k-blocks the residents' caches hold), in percent. A
program from before PR 28 has no such family: the reader returns None and
the metric is left out of the line, which is how the parent's traced runs
pass."""
import pytest

import harness
from readers import histogram_mean

NAMES = ["decode_walk_pct.saturated", "decode_walk_pct.moe"]


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_stored_histogram_in_percent(name):
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    assert spec["reader"] == "histogram_mean"
    # 140 dispatches in the window, their shares summing to 31.5
    stored = {"counters": {
        "decode_attention_walk_share{}_count": 140.0,
        "decode_attention_walk_share{}_sum": 31.5,
        "executor_step_seconds{path=chained}_count": 140.0,
        "executor_step_seconds{path=chained}_sum": 13.3}}
    assert histogram_mean.read(stored, **spec["args"]) == pytest.approx(22.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_family_reports_nothing(name):
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    parent = {"counters": {
        "executor_step_seconds{path=chained}_count": 140.0,
        "executor_step_seconds{path=chained}_sum": 32.4}}
    assert histogram_mean.read(parent, **spec["args"]) is None
