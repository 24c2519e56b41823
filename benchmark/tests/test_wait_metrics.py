"""The readers of PR 39's metrics of the device's wait, on hand-made
windows: ``counter_share`` (``device_starved_pct.*``,
``prefill_useful_tokens_pct.*``), ``histogram_mean``
(``held_assignments_per_step.*``), and the join of the run's dispatches to
their device modules (``readers.dispatch_join``), which
``readers.kernel_roofline_slice`` reads through, also through the program's
own join on the fixture the program's tests keep as data. A program without
the families or the join (the parent commit), a run without a profile:
None, no raise. (The two metrics PR 39 built on the join's walls were
retired by PR 51: under PR 42's overlap a wall holds the chunk ahead.)"""
import json
import os

import pytest

import harness
from readers import (counter_share, dispatch_join, kernel_roofline,
                     kernel_roofline_slice)

FILES = {n: harness.load_json(harness.HERE, "layer_metrics", n + ".json")
         for n in ("device_starved_pct.saturated",
                   "device_starved_pct.hybrid",
                   "device_starved_pct.latent",
                   "prefill_useful_tokens_pct.saturated",
                   "held_assignments_per_step.moe",
                   "held_assignments_per_step.hybrid",
                   "held_assignments_per_step.latent")}
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
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


@pytest.mark.parametrize("name", ["device_starved_pct.saturated",
                                  "device_starved_pct.hybrid",
                                  "device_starved_pct.latent"])
def test_starved_share_is_starved_over_starved_plus_inflight(name):
    args = FILES[name]["args"]
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
    mk = lambda path, t, start: {"path": path, "launch_t": t,
                                 "module_start_ns": start,
                                 "module_end_ns": start + 90_000_000}
    return [mk("chained", 10.001, 0), mk("chained", 10.101, 100_000_000),
            mk("run", 10.201, 200_000_000)]


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


# a kernel's operations, one inside each joined module and one outside all
OPS = [("%k.1 = f32[8]{0} custom-call(%a)", 1_000_000, 21_000_000),
       ("%k.1 = f32[8]{0} custom-call(%a)", 101_000_000, 131_000_000),
       ("%k.2 = f32[8]{0} custom-call(%a)", 201_000_000, 211_000_000),
       ("%k.1 = f32[8]{0} custom-call(%a)", 400_000_000, 900_000_000)]


def _slice_ctx(monkeypatch, spans=None):
    """A traced window whose settle spans carry ``work`` seconds each, and
    a cost module that adds them up."""
    import types

    monkeypatch.setattr(kernel_roofline_slice.xplane, "load",
                        lambda path: {"devices": {"d": {"ops": OPS}}})
    monkeypatch.setitem(
        __import__("sys").modules, "made_up_costs", types.SimpleNamespace(
            work=lambda config, did, peaks: sum(d["work"] for d in did)
            or None))
    if spans is None:
        spans = [{"name": "serving.settle", "t0": t + 0.05, "t1": t + 0.06,
                  "attrs": {"launch_t0": t, "work": w}}
                 for t, w in ((10.0, 0.010), (10.1, 0.015), (10.2, 0.005))]
    return {"trace": {"busy_s": 1.0}, "spans": spans, "counters": WINDOW,
            "peaks": PEAKS, "config": {}}


ARGS = {"pattern": r"^%k[.\d]* = ", "module": "made_up_costs", "cost": "work"}


def test_the_join_is_made_once_a_run_and_says_what_it_missed(joined,
                                                             monkeypatch):
    ctx = _slice_ctx(monkeypatch)
    # every path: 30 ms of work over 60 ms of operations inside the modules
    assert kernel_roofline_slice.read(ctx, **ARGS) == pytest.approx(50.0)
    # the second metric of the run reads the same join, and what was not
    # matched is said once
    assert kernel_roofline_slice.read(ctx, path="chained", **ARGS) \
        == pytest.approx(100 * 0.025 / 0.050)
    assert kernel_roofline_slice.read(ctx, path="run", **ARGS) \
        == pytest.approx(100 * 0.005 / 0.010)
    assert len(joined) == 1
    assert "3 of 5" in joined[0] and "no module 1" in joined[0] \
        and "cut by the slice's edge 2" in joined[0]


def test_nothing_joined_or_nothing_noted_reads_nothing(joined, monkeypatch):
    import paddle_tpu.trace as program_trace

    # settle spans without the launch's time (the parent commit)
    bare = [{"name": "serving.settle", "t0": 10.05, "t1": 10.06,
             "attrs": {}}]
    assert kernel_roofline_slice.read(_slice_ctx(monkeypatch, bare),
                                      **ARGS) is None
    monkeypatch.setattr(
        program_trace, "join_dispatches",
        lambda path, spans: {"joined": [], "inside": 0, "no_module": 0,
                             "claimed_twice": 0, "cut": 0,
                             "modules_unclaimed": 0})
    assert kernel_roofline_slice.read(_slice_ctx(monkeypatch), **ARGS) \
        is None


@pytest.mark.parametrize("ctx", [
    {"trace": None, "spans": [], "counters": WINDOW},       # a rehearsal
    {"spans": [], "counters": WINDOW},
])
def test_dispatch_join_without_a_trace(ctx):
    assert dispatch_join._joined(dict(ctx)) is None
    assert kernel_roofline_slice.read(dict(ctx, peaks=PEAKS), **ARGS) is None


def test_dispatch_join_without_a_profile_or_without_the_join(monkeypatch):
    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: None)
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    assert dispatch_join._joined(ctx) is None
    # the parent commit's trace package has no join
    import paddle_tpu.trace as program_trace

    monkeypatch.setattr(kernel_roofline, "_newest_profile", lambda: "x.pb")
    monkeypatch.delattr(program_trace, "join_dispatches")
    ctx = {"trace": {"busy_s": 1.0}, "spans": [], "counters": WINDOW}
    assert dispatch_join._joined(ctx) is None


def test_a_dispatch_takes_the_last_launch_before_it(joined, monkeypatch):
    """A joined dispatch's work is what the settle span of the last launch
    the dispatch thread began before it noted: a span whose launch comes
    after every joined dispatch moves nothing."""
    ctx = _slice_ctx(monkeypatch)
    ctx["spans"].append({"name": "serving.settle", "t0": 11.0, "t1": 11.1,
                         "attrs": {"launch_t0": 10.9, "work": 10.0}})
    assert kernel_roofline_slice.read(ctx, **ARGS) == pytest.approx(50.0)


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
    rows = dispatch_join._joined(ctx)
    # dispatch 11: 27.5 ms on the device; 12: 18
    assert [round(d["device_s"], 4) for d in rows] == [0.0275, 0.018]
    assert all(d["module_start_ns"] < d["module_end_ns"]
               and d["path"] in ("run", "chained") for d in rows)
    assert dispatch_join._joined(ctx) is rows        # kept for the run


@pytest.mark.parametrize("name", ["held_assignments_per_step.moe",
                                  "held_assignments_per_step.hybrid",
                                  "held_assignments_per_step.latent"])
def test_held_assignments_reads_the_decode_executions(name):
    from readers import histogram_mean

    args = FILES[name]["args"]
    window = {"moe_held_assignments_per_step{phase=decode}_sum": 3840.0,
              "moe_held_assignments_per_step{phase=decode}_count": 64.0,
              "moe_held_assignments_per_step{phase=prefill}_sum": 9000.0,
              "moe_held_assignments_per_step{phase=prefill}_count": 4.0}
    assert histogram_mean.read({"counters": window}, **args) == 60.0
    assert histogram_mean.read({"counters": {}}, **args) is None
