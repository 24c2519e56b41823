"""The readers of PR 39's metrics of the device's wait, on hand-made
windows: ``counter_share`` (``device_starved_pct.*``,
``prefill_useful_tokens_pct.*``) and ``dispatch_join``
(``dispatch_overhead_ms.*``, ``device_idle_window_pct.*``), the second also
through the program's own join on the fixture the program's tests keep as
data. A program without the families or the join (the parent commit), a
run without a profile: None, no raise."""
import json
import os

import pytest

import harness
from readers import counter_share, dispatch_join, kernel_roofline

FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in ("device_starved_pct.saturated",
                   "prefill_useful_tokens_pct.saturated",
                   "dispatch_overhead_ms.saturated",
                   "device_idle_window_pct.saturated",
                   "held_assignments_per_step.moe")}
WINDOW = {
    "executor_starved_seconds{path=chained}_sum": 0.5,
    "executor_starved_seconds{path=chained}_count": 100.0,
    "executor_starved_seconds{path=run}_sum": 0.3,
    "executor_starved_seconds{path=run}_count": 50.0,
    "executor_inflight_seconds{path=chained}_sum": 8.0,
    "executor_inflight_seconds{path=chained}_count": 100.0,
    "executor_inflight_seconds{path=run}_sum": 1.2,
    "executor_inflight_seconds{path=run}_count": 50.0,
    "serving_prefill_tokens_total{kind=prompt}": 640.0,
    "serving_prefill_tokens_total{kind=run}": 8192.0,
    "executor_steps_total{path=run}": 50.0,
}


def test_starved_share_is_starved_over_starved_plus_inflight():
    args = FILES["device_starved_pct.saturated"]["args"]
    assert counter_share.read({"counters": WINDOW}, **args) \
        == pytest.approx(100 * 0.8 / 10.0)
    # an executor that never waited reads 0, not nothing
    never = {k: (0.0 if "starved" in k else v) for k, v in WINDOW.items()}
    assert counter_share.read({"counters": never}, **args) == 0.0


def test_prefill_useful_tokens_is_prompt_over_run():
    args = FILES["prefill_useful_tokens_pct.saturated"]["args"]
    assert counter_share.read({"counters": WINDOW}, **args) \
        == pytest.approx(100 * 640 / 8192)


@pytest.mark.parametrize("name", ["device_starved_pct.saturated",
                                  "prefill_useful_tokens_pct.saturated"])
def test_counter_share_on_a_program_without_the_families(name):
    old = {"executor_steps_total{path=run}": 50.0,
           "serving_prefill_seconds{}_sum": 1.0}
    assert counter_share.read({"counters": old}, **FILES[name]["args"]) \
        is None
    assert counter_share.read({"counters": {}}, **FILES[name]["args"]) \
        is None


def _rows():
    mk = lambda path, wall, device: {"path": path, "launch_t": 10.0,
                                     "ready_t": 10.0 + wall,
                                     "device_s": device}
    return [mk("chained", 0.092, 0.090), mk("chained", 0.095, 0.091),
            mk("run", 0.036, 0.035)]


@pytest.fixture()
def joined(monkeypatch):
    """A profile to find and a join that returns ``_rows``."""
    import paddle_tpu.trace as program_trace

    said = []
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "x.pb")
    monkeypatch.setattr(dispatch_join, "say", said.append)
    monkeypatch.setattr(
        program_trace, "join_dispatches",
        lambda path, spans: {"joined": _rows(), "inside": 5, "no_module": 1,
                             "claimed_twice": 0, "cut": 2,
                             "modules_unclaimed": 3})
    return said


def test_overhead_is_the_mean_wall_less_device_time(joined):
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    args = FILES["dispatch_overhead_ms.saturated"]["args"]
    assert dispatch_join.read(ctx, **args) == pytest.approx(
        (2.0 + 4.0 + 1.0) / 3)
    # the second metric of the run reads the same join, and what was not
    # matched is said once
    assert dispatch_join.read(
        ctx, **FILES["device_idle_window_pct.saturated"]["args"]
    ) == pytest.approx(100 * (0.8 + 100 * 0.003 + 50 * 0.001) / 10.0)
    assert len(joined) == 1
    assert "3 of 5" in joined[0] and "no module 1" in joined[0] \
        and "cut by the slice's edge 2" in joined[0]


def test_idle_window_without_the_families_or_with_nothing_joined(joined,
                                                                 monkeypatch):
    args = FILES["device_idle_window_pct.saturated"]["args"]
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": {}}
    assert dispatch_join.read(ctx, **args) is None
    import paddle_tpu.trace as program_trace

    monkeypatch.setattr(
        program_trace, "join_dispatches",
        lambda path, spans: {"joined": [], "inside": 0, "no_module": 0,
                             "claimed_twice": 0, "cut": 0,
                             "modules_unclaimed": 0})
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    for name in ("dispatch_overhead_ms.saturated",
                 "device_idle_window_pct.saturated"):
        assert dispatch_join.read(ctx, **FILES[name]["args"]) is None


@pytest.mark.parametrize("ctx", [
    {"trace": None, "spans": [], "counters": WINDOW},       # a rehearsal
    {"spans": [], "counters": WINDOW},
])
def test_dispatch_join_without_a_trace(ctx):
    assert dispatch_join.read(ctx, value="overhead_ms") is None
    assert dispatch_join.read(ctx, value="idle_window_pct") is None


def test_dispatch_join_without_a_profile_or_without_the_join(monkeypatch):
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: None)
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    assert dispatch_join.read(ctx, value="overhead_ms") is None
    # the parent commit's trace package has no join
    import paddle_tpu.trace as program_trace

    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "x.pb")
    monkeypatch.delattr(program_trace, "join_dispatches")
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    assert dispatch_join.read(ctx, value="overhead_ms") is None


def test_dispatch_join_refuses_an_unknown_value(joined):
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    with pytest.raises(ValueError, match="unknown value"):
        dispatch_join.read(ctx, value="median_ms")


def test_through_the_programs_join_on_its_recorded_fixture(monkeypatch):
    from paddle_tpu.trace import dispatch_join as program_join

    with open(os.path.join(harness.REPO, "tests", "data",
                           "dispatch_join.json")) as f:
        rec = json.load(f)
    profile = {"modules": [tuple(m) for m in rec["profile"]["modules"]],
               "launches": [tuple(a) for a in rec["profile"]["launches"]],
               "extent": tuple(rec["profile"]["extent"])}
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "x.pb")
    monkeypatch.setattr(program_join, "load_profile", lambda path: profile)
    ctx = {"trace": {"busy_s": 1.0}, "spans": rec["spans"],
           "counters": WINDOW}
    # dispatch 11: 31.2 ms on the wall, 27.5 on the device; 12: 22 and 18
    assert dispatch_join.read(ctx, value="overhead_ms") == pytest.approx(
        (3.7 + 4.0) / 2)
    assert dispatch_join.read(ctx, value="idle_window_pct") \
        == pytest.approx(100 * (0.8 + 100 * 0.0037 + 50 * 0.004) / 10.0)


def test_held_assignments_reads_the_decode_executions():
    from readers import histogram_mean

    args = FILES["held_assignments_per_step.moe"]["args"]
    window = {"moe_held_assignments_per_step{phase=decode}_sum": 3840.0,
              "moe_held_assignments_per_step{phase=decode}_count": 64.0,
              "moe_held_assignments_per_step{phase=prefill}_sum": 9000.0,
              "moe_held_assignments_per_step{phase=prefill}_count": 4.0}
    assert histogram_mean.read({"counters": window}, **args) == 60.0
    assert histogram_mean.read({"counters": {}}, **args) is None
