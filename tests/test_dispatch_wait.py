"""The device's wait as the program counts it (ISSUE 39):

* ``executor_inflight_seconds`` + ``executor_starved_seconds`` tile an
  executor's life between its first launch and its last fetch return;
* ``trace.join_dispatches`` on a recorded fixture written as data
  (``tests/data/dispatch_join.json``): by id, by the anchor, a dispatch
  with no module, a module claimed twice, a dispatch the edge cuts;
* ``serving_prefill_tokens_total{kind}`` and
  ``moe_held_assignments_per_step{phase}``;
* ``tools/timeline.py --xplane`` as the join's first caller.
"""
import collections
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving, trace
from paddle_tpu.models.gpt import GptConfig, build_gpt_generative
from paddle_tpu.resilience import fault_plan_guard
from paddle_tpu.trace import dispatch_join

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "dispatch_join.json")


# ---------------------------------------------------------------------------
# in flight and starved
# ---------------------------------------------------------------------------

def _session():
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    # the life that is counted begins with the first dispatch of ``main``
    exe.forget_last_dispatch()
    monitor.reset()
    feed = {"x": np.ones((2, 4), np.float32)}

    def dispatch(path, return_numpy=True):
        if path == "run":
            return exe.run(main, feed=feed, fetch_list=[y.name],
                           scope=scope, return_numpy=return_numpy)
        return exe.run_chained(main, feed=feed, fetch_list=[y.name],
                               steps=2, scope=scope,
                               return_numpy=return_numpy)

    return exe, main, dispatch


@pytest.fixture()
def records():
    """Every ``StepRecord`` that ends while the test runs, monitor reset."""
    monitor.reset()
    seen = []
    hook = monitor.add_hook(on_step_end=seen.append)
    yield seen
    monitor.remove_hook(hook)


def _sums(path=None):
    labels = {} if path is None else {"path": path}
    out = []
    for fam in ("executor_inflight_seconds", "executor_starved_seconds"):
        snap = monitor.get_registry().to_dict().get(fam, {"values": []})
        vals = [c["value"] for c in snap["values"]
                if all(c["labels"].get(k) == v for k, v in labels.items())]
        out.append((sum(v["count"] for v in vals),
                    sum(v["sum"] for v in vals)))
    return out


@pytest.mark.parametrize("path", ["run", "chained"])
def test_inflight_and_starved_tile_an_executors_life(records, path):
    _, main, dispatch = _session()
    dispatch(path)                          # compiles; part of the life too
    for i in range(19):
        time.sleep(0.001 * (i % 3))
        dispatch(path)
    mine = [r for r in records if r.program_serial == main._serial]
    assert len(mine) == 20 and all(r.path == path for r in mine)
    (n_in, s_in), (n_st, s_st) = _sums(path)
    assert (n_in, n_st) == (20, 19)
    life = mine[-1].ready_t - mine[0].launch_t
    assert abs(s_in + s_st - life) < 1e-6
    assert s_st >= 0.015                    # the sleeps are starved time
    for before, r in zip(mine, mine[1:]):
        assert r.prev_ready_t == before.ready_t
        assert before.ready_t <= r.launch_t <= r.ready_t


def _union(intervals):
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        total += max(0.0, hi - max(lo, end))
        end = max(end, hi)
    return total


# the order of launches (L) and fetches taken later (T) of dispatches 0..n:
# each launched behind the one before it and fetched under its successor
# (the generative engine's order); two in flight behind a third; a serial
# dispatch between overlapped ones
OVERLAPS = {
    "one_ahead": "L0 L1 T0 L2 T1 L3 T2 T3",
    "two_ahead": "L0 L1 L2 T0 T1 L3 T2 T3",
    "drained_between": "L0 L1 T0 T1 L2 T2 L3 L4 T3 T4",
    "taken_out_of_order": "L0 L1 T1 T0 L2 T2",
}


@pytest.mark.parametrize("order", sorted(OVERLAPS))
@pytest.mark.parametrize("path", ["run", "chained"])
def test_overlapped_dispatches_tile_the_life_by_their_union(records, path,
                                                            order):
    """Fetches taken later (``FETCH_LATER``), with the next dispatch
    launched in between: in flight sums to the union of the launch-to-ready
    intervals, starved to the time with none open, never negative, and the
    two tile first launch to last fetch return exactly."""
    exe, main, dispatch = _session()
    dispatch(path)                                      # compiles
    exe.forget_last_dispatch()                          # the life starts
    monitor.reset()
    pending, serial = {}, []
    for i, move in enumerate(OVERLAPS[order].split()):
        time.sleep(0.001 * (i % 3))
        if move[0] == "L":
            pending[move[1:]] = dispatch(path, return_numpy=fluid.FETCH_LATER)
        else:
            out = pending.pop(move[1:]).take()
            assert isinstance(out[0], np.ndarray)
    time.sleep(0.002)
    serial.append(dispatch(path))                       # and a plain one
    mine = [r for r in records if r.program_serial == main._serial][1:]
    assert len(mine) == OVERLAPS[order].count("L") + 1
    assert all(r.ready_t is not None and r.head_t <= r.ready_t
               for r in mine)
    # its own from its launch on, or from the fetch return before its own;
    # taken before an older one, it tells that one's time too
    assert all(r.launch_t <= r.head_t for r in mine) \
        == (order != "taken_out_of_order")
    (n_in, s_in), (n_st, s_st) = _sums(path)
    life = max(r.ready_t for r in mine) - min(r.launch_t for r in mine)
    union = _union([(r.launch_t, r.ready_t) for r in mine])
    assert n_in == len(mine)
    assert abs(s_in - union) < 1e-6
    assert abs(s_in + s_st - life) < 1e-6
    assert s_st >= 0.002 and all(
        r.launch_t >= r.prev_ready_t for r in mine
        if r.prev_ready_t is not None)
    # a launch behind a dispatch still in flight observes no gap
    gaps = sum(r.prev_ready_t is not None for r in mine)
    assert n_st == gaps < len(mine)
    # the record of a fetch taken later: the call's wall and the fetch's
    for r in mine:
        assert r.duration_s >= r.fetch_wait_s > 0
    step, host, wait = (monitor.metric_value(n, path=path) for n in (
        "executor_step_seconds", "executor_host_seconds",
        "executor_fetch_wait_seconds"))
    assert step["count"] == host["count"] == wait["count"] == len(mine)
    assert host["sum"] + wait["sum"] == pytest.approx(step["sum"], rel=1e-9)


@pytest.mark.parametrize("path", ["run", "chained"])
def test_a_fetch_taken_later_is_taken_once_or_dropped(records, path):
    exe, main, dispatch = _session()
    dispatch(path)
    exe.forget_last_dispatch()
    monitor.reset()
    a = dispatch(path, return_numpy=fluid.FETCH_LATER)
    b = dispatch(path, return_numpy=fluid.FETCH_LATER)
    n = len(records)
    b.drop()                    # closes its record, observes no time
    assert len(records) == n + 1 and records[-1].ready_t is None
    a.take()
    assert records[-1].ready_t is not None
    for gone in (a, b):
        with pytest.raises(RuntimeError, match="taken or dropped"):
            gone.take()
    (n_in, _), (n_st, _) = _sums(path)
    assert (n_in, n_st) == (1, 0)
    assert monitor.metric_value("executor_steps_total", path=path) == 2


def test_the_deferred_fetch_span_joins_its_launch(records):
    """Traced: the ``executor.fetch`` of a fetch taken later is a child of
    the call that launched it, so ``dispatches_of`` still pairs launch and
    fetch return, with another dispatch's spans in between."""
    _, main, dispatch = _session()
    dispatch("chained")
    fluid.set_flags({"FLAGS_trace": 1})
    trace.clear()
    try:
        a = dispatch("chained", return_numpy=fluid.FETCH_LATER)
        b = dispatch("chained", return_numpy=fluid.FETCH_LATER)
        a.take()
        b.take()
        spans = trace.spans()
    finally:
        fluid.set_flags({"FLAGS_trace": 0})
        trace.clear()
    got = dispatch_join.dispatches_of(spans)
    assert [d["dispatch"] for d in got] \
        == [r.step_index for r in records[-2:]]
    for d, r in zip(got, records[-2:]):
        assert d["launch_t"] == r.launch_t and d["ready_t"] == r.ready_t
    assert got[1]["launch_t"] < got[0]["ready_t"] < got[1]["ready_t"]


@pytest.mark.parametrize("path", ["run", "chained"])
def test_a_dispatch_that_does_not_fetch_observes_neither(records, path):
    exe, main, dispatch = _session()
    dispatch(path)
    dispatch(path, return_numpy=False)
    dispatch(path, return_numpy=False)
    assert _sums(path) == [(1, pytest.approx(records[-3].ready_t
                                             - records[-3].launch_t)),
                           (0, 0)]
    assert records[-1].ready_t is None and records[-1].launch_t is not None
    # the next one that fetches starts a new stretch: in flight, no gap
    dispatch(path)
    (n_in, _), (n_st, _) = _sums(path)
    assert (n_in, n_st) == (2, 0)
    assert records[-1].prev_ready_t is None


@pytest.mark.parametrize("site", ["step", "hang"])
@pytest.mark.parametrize("path", ["run", "chained"])
def test_a_dispatch_that_raises(records, path, site):
    """``hang`` fires inside the launch: the dispatch observes nothing and
    the one after it no gap, so the sums are the two stretches' lengths.
    ``step`` fires before the launch: nothing was in flight, the gap runs
    on to the next launch and the tiling is unbroken."""
    _, main, dispatch = _session()
    for _ in range(3):
        dispatch(path)
    with fault_plan_guard(f"{site}:@1:RuntimeError"):
        with pytest.raises(RuntimeError):
            dispatch(path)
    for _ in range(3):
        dispatch(path)
    mine = [r for r in records if r.program_serial == main._serial]
    assert len(mine) == 7 and mine[3].ready_t is None
    (n_in, s_in), (n_st, s_st) = _sums(path)
    if site == "step":
        assert mine[3].launch_t is None
        assert (n_in, n_st) == (6, 5)
        assert mine[4].prev_ready_t == mine[2].ready_t
        life = mine[6].ready_t - mine[0].launch_t
    else:
        assert mine[3].launch_t is not None
        assert (n_in, n_st) == (6, 4)
        assert mine[4].prev_ready_t is None
        life = (mine[2].ready_t - mine[0].launch_t
                + mine[6].ready_t - mine[4].launch_t)
    assert abs(s_in + s_st - life) < 1e-6


def test_two_executors_interleaved_keep_their_own_gaps(records):
    _, main_a, a = _session()
    _, main_b, b = _session()
    for i in range(5):
        a("run")
        time.sleep(0.002)
        b("chained")
    total = 0.0
    for main in (main_a, main_b):
        mine = [r for r in records if r.program_serial == main._serial]
        assert len(mine) == 5
        for before, r in zip(mine, mine[1:]):
            assert r.prev_ready_t == before.ready_t
        total += mine[-1].ready_t - mine[0].launch_t
    (n_in, s_in), (n_st, s_st) = _sums()
    assert (n_in, n_st) == (10, 8)
    assert abs(s_in + s_st - total) < 1e-6


def test_forgetting_the_last_dispatch_and_monitor_off(records):
    exe, _, dispatch = _session()
    dispatch("run")
    exe.forget_last_dispatch()
    dispatch("run")
    assert _sums()[1] == (0, 0) and records[-1].prev_ready_t is None
    fluid.set_flags({"FLAGS_monitor": 0})
    try:
        n = len(records)
        dispatch("run")
        assert len(records) == n and exe._last_dispatch is None
    finally:
        fluid.set_flags({"FLAGS_monitor": 1})
    assert _sums()[0][0] == 2


def test_the_launch_span_names_its_dispatch_and_module(records):
    _, main, dispatch = _session()
    dispatch("run")
    fluid.set_flags({"FLAGS_trace": 1})
    trace.clear()
    try:
        dispatch("run")
        dispatch("chained")
        spans = trace.spans()
    finally:
        fluid.set_flags({"FLAGS_trace": 0})
        trace.clear()
    steps = [s for s in spans if s.name == "executor.step"]
    assert [s.attrs["module"] for s in steps] == ["jit_step_fn",
                                                  "jit_multi_fn"]
    assert [s.attrs["dispatch"] for s in steps] \
        == [r.step_index for r in records[-2:]]
    got = dispatch_join.dispatches_of(spans)
    assert [d["path"] for d in got] == ["run", "chained"]
    for d, r in zip(got, records[-2:]):
        assert d["dispatch"] == r.step_index
        assert d["launch_t"] == r.launch_t and d["ready_t"] == r.ready_t


@pytest.fixture(scope="module")
def off_cost():
    import tools.trace_check as trace_check

    return trace_check._dispatch_off_cost()


@pytest.mark.parametrize("check", [
    "dispatch_observes_when_monitor_on",
    "dispatch_reads_no_clock_when_off",
    "dispatch_observes_nothing_when_off",
    "launch_untimed_and_unannotated_when_off"])
def test_trace_checks_off_cost_gate_covers_the_dispatch(off_cost, check):
    assert set(off_cost) == {
        "dispatch_observes_when_monitor_on",
        "dispatch_reads_no_clock_when_off",
        "dispatch_observes_nothing_when_off",
        "launch_untimed_and_unannotated_when_off"}
    assert off_cost[check] is True


# ---------------------------------------------------------------------------
# the join, on a fixture written as data
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def _profile(recorded, ids=True):
    launches = [(d if ids else None, s, e)
                for d, s, e in recorded["profile"]["launches"]]
    return {"modules": [tuple(m) for m in recorded["profile"]["modules"]],
            "launches": launches,
            "extent": tuple(recorded["profile"]["extent"])}


def test_join_by_id(recorded):
    got = trace.join_dispatches(_profile(recorded), recorded["spans"])
    want = recorded["expect"]
    assert {k: got[k] for k in want["counts"]} == want["counts"]
    assert got["inside"] == (len(got["joined"]) + got["no_module"]
                             + got["claimed_twice"])
    assert [d["dispatch"] for d in got["joined"]] == want["joined"]
    assert all(d["by"] == "id" for d in got["joined"])
    first = got["joined"][0]
    # dispatch 11: launched at 100.010 s = 10 ms on the profile's clock,
    # module 12.5-40 ms, fetch back 41.2 ms after a wall of 31.2 ms
    assert first["path"] == "chained" and first["module"] == "jit_multi_fn"
    assert first["launch_latency_s"] == pytest.approx(0.0025)
    assert first["device_s"] == pytest.approx(0.0275)
    assert first["return_latency_s"] == pytest.approx(0.0012)
    assert (first["launch_latency_s"] + first["device_s"]
            + first["return_latency_s"]) == pytest.approx(
        first["ready_t"] - first["launch_t"])


def test_join_by_the_anchor_where_the_profile_has_no_ids(recorded):
    spans = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                            if k != "dispatch"}) for s in recorded["spans"]]
    assert trace.join_dispatches(_profile(recorded, ids=False),
                                 spans)["joined"] == []
    got = trace.join_dispatches(_profile(recorded, ids=False), spans,
                                anchor=tuple(recorded["anchor"]))
    want = recorded["expect"]
    assert {k: got[k] for k in want["counts"]} == want["counts"]
    assert all(d["by"] == "anchor" for d in got["joined"])
    by_id = trace.join_dispatches(_profile(recorded), recorded["spans"])
    for a, b in zip(got["joined"], by_id["joined"]):
        assert a["module_start_ns"] == b["module_start_ns"]
        assert a["device_s"] == b["device_s"]
        # the anchor is 20 us off the annotations' own clock readings
        assert a["launch_latency_s"] == pytest.approx(
            b["launch_latency_s"], abs=5e-5)


@pytest.mark.parametrize("dispatch,fate", [
    (10, "cut"), (13, "no_module"), (14, "claimed_twice"),
    (15, "claimed_twice"), (17, "cut"), (9, "outside")])
def test_what_is_not_joined_is_counted_not_guessed(recorded, dispatch, fate):
    """One dispatch at a time beside the two sound ones of the fixture."""
    keep = {11, 12, dispatch} | ({14, 15} if fate == "claimed_twice"
                                 else set())
    parents = {s["parent_id"] for s in recorded["spans"]
               if s["attrs"].get("dispatch") in keep}
    spans = [s for s in recorded["spans"]
             if s["span_id"] in parents or s["parent_id"] in parents]
    got = trace.join_dispatches(_profile(recorded), spans)
    assert [d["dispatch"] for d in got["joined"]] == [11, 12]
    counts = {k: got[k] for k in ("no_module", "claimed_twice", "cut")}
    want = dict.fromkeys(counts, 0)
    if fate == "claimed_twice":
        want[fate] = 2
    elif fate != "outside":
        want[fate] = 1
    assert counts == want


def test_join_of_nothing():
    empty = {"modules": [], "launches": [], "extent": None}
    assert trace.join_dispatches(empty, [])["joined"] == []
    assert dispatch_join.dispatches_of([]) == []


def test_load_profile_reads_a_recorded_v5e_profile():
    """The benchmark's recorded profile (PR 24) has six module runs and
    no launch annotation: the loader finds the first, not the second."""
    path = os.path.join(os.path.dirname(DATA), "..", "..", "benchmark",
                        "tests", "data", "small_v5e.xplane.pb")
    got = dispatch_join.load_profile(path)
    assert len(got["modules"]) == 6 and got["launches"] == []
    assert {dispatch_join._stem(m[0]) for m in got["modules"]} \
        == {"jit__lambda"}
    # the extent is the device's: its tracing began 47 ms after the host's
    lo, hi = got["extent"]
    assert lo == got["modules"][0][1] and hi >= got["modules"][-1][2]
    assert all(lo <= s < e <= hi for _, s, e in got["modules"])


def test_launch_annotation_lands_in_a_profile_with_its_dispatch(tmp_path,
                                                                records):
    """End to end on the CPU: the annotation a traced launch enters is in
    the profile with the span's id (no device plane here, so nothing is
    joined; the ids are what the join goes by on the chip)."""
    import glob

    import jax

    _, _, dispatch = _session()
    dispatch("run")
    fluid.set_flags({"FLAGS_trace": 1})
    trace.clear()
    try:
        jax.profiler.start_trace(str(tmp_path))
        dispatch("run")
        dispatch("chained")
        jax.profiler.stop_trace()
        spans = trace.spans()
    finally:
        fluid.set_flags({"FLAGS_trace": 0})
        trace.clear()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    prof = dispatch_join.load_profile(path)
    assert [a[0] for a in prof["launches"]] \
        == [r.step_index for r in records[-2:]]
    got = trace.join_dispatches(prof, spans)
    assert got["inside"] == 2 and got["no_module"] == 2


def test_timeline_tool_is_the_joins_first_caller(tmp_path, recorded,
                                                 monkeypatch, capsys):
    import tools.timeline as timeline

    spans = tmp_path / "spans.jsonl"
    with open(spans, "w") as f:
        for s in recorded["spans"]:
            f.write(json.dumps({
                "name": s["name"], "span_id": s["span_id"],
                "parent_id": s["parent_id"], "attrs": s["attrs"],
                "t0_epoch": s["t0"], "duration_s": s["t1"] - s["t0"],
                "trace_id": "t", "status": "ok", "thread": 1}) + "\n")
    monkeypatch.setattr(dispatch_join, "load_profile",
                        lambda path: _profile(recorded))
    out = tmp_path / "timeline.json"
    assert timeline.main(["--trace_path", str(spans), "--xplane", "x.pb",
                          "--timeline_path", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    device = [e for e in events if e["pid"] == 2]
    assert [e["args"]["dispatch"] for e in device] \
        == recorded["expect"]["joined"]
    assert device[0]["dur"] == pytest.approx(27500.0)
    assert device[0]["ts"] == pytest.approx((100.010 + 0.0025) * 1e6)
    assert "claimed_twice" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# what a prefill's rows were; the held experts' load
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 2, 4])
def test_prefill_tokens_prompt_against_run(rows):
    """``kind=run`` grows by ``rows x bucket`` a dispatch, and three
    newcomers take ``ceil(3 / rows)`` dispatches."""
    with un.guard():
        net = build_gpt_generative(GptConfig.tiny(), batch_slots=4,
                                   max_seq=128, page_size=32,
                                   prompt_buckets=(128,), prefill_rows=rows)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=4, queue_depth=16,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(
            decode_chunk=2, prefix_cache=False, chunked_prefill=False))
    eng.warm_up()
    monitor.reset()
    rng = np.random.RandomState(5)
    lengths = (17, 64, 101)
    reqs = [eng._build_gen_request(rng.randint(1, 100, n), 2, 1, None, None,
                                   None) for n in lengths]
    for slot, r in enumerate(reqs):
        r.slot = slot
        eng._slots[slot] = r
    eng._run_prefill(reqs)
    assert monitor.metric_value("serving_prefill_tokens_total",
                                kind="prompt") == sum(lengths)
    dispatches = -(-len(reqs) // rows)
    assert monitor.metric_value("serving_prefill_seconds")[
        "count"] == dispatches
    assert monitor.metric_value("serving_prefill_tokens_total",
                                kind="run") == dispatches * rows * 128
    assert all(r.prefilled for r in reqs)


def test_held_assignments_per_step_from_the_ops_counts():
    from paddle_tpu.ops.moe import count_expert_stats

    monitor.reset()
    # two steps x two layers x (3 held experts + made + dropped)
    stats = np.array([[[4, 0, 2, 16, 0], [1, 1, 1, 16, 0]],
                      [[0, 0, 0, 16, 0], [5, 2, 0, 16, 0]]])
    count_expert_stats("decode", stats, collections.Counter(), (), [16, 16])
    snap = monitor.metric_value("moe_held_assignments_per_step",
                                phase="decode")
    assert snap["count"] == 4 and snap["sum"] == 6 + 3 + 0 + 7
    assert monitor.metric_value("moe_held_assignments_per_step",
                                default=None, phase="prefill") is None


def test_live_tiles_from_the_ops_counts_and_its_tile_rule():
    """``moe_expert_tiles_total`` is the sum of ``ceil(count / tile rows)``
    over the held experts of every execution, by layer: over
    ``moe_experts_hit_total`` it is the tiles that rode one fetch of an
    expert's weights (1.0 under even routing, SDAR's skew about 1.7)."""
    from paddle_tpu.ops.moe import count_expert_stats

    monitor.reset()
    # two forwards x two layers x (4 held experts + made + dropped): the
    # first layer tiles by 16 rows, the second by 32
    stats = np.array([[[85, 0, 16, 17, 128, 0], [85, 0, 32, 33, 128, 0]],
                      [[1, 1, 1, 1, 128, 0], [0, 0, 0, 0, 128, 0]]])
    count_expert_stats("decode", stats, collections.Counter(), (3, 5),
                       [16, 32])
    value = lambda fam, layer: monitor.metric_value(fam, layer=layer,
                                                    phase="decode")
    ceil = lambda counts, tm: sum(-(-c // tm) for c in counts)
    assert value("moe_expert_tiles_total", "3") == (
        ceil([85, 0, 16, 17], 16) + ceil([1, 1, 1, 1], 16)) == 13
    assert value("moe_expert_tiles_total", "5") == ceil([85, 0, 32, 33],
                                                        32) == 6
    assert value("moe_experts_hit_total", "3") == 7
    assert value("moe_experts_hit_total", "5") == 3


def test_each_expert_ops_tile_rows_are_read_from_its_program():
    """The tile rule is the op's own (``ops.moe.expert_tile_rows``) over
    the static shapes of each phase's program: a decode forward of 4 slots
    x 4 rows and prefills of 2 x 16 and 2 x 32 rows, top 2 of 8 experts."""
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.sdar_moe import (SdarMoeConfig,
                                            build_sdar_moe_generative)
    from paddle_tpu.ops.moe import expert_tile_rows, program_tile_rows

    cfg = SdarMoeConfig.tiny(dtype="float32")
    with un.guard():
        net = build_sdar_moe_generative(cfg, batch_slots=4, max_seq=64,
                                        page_size=8, prompt_buckets=(16, 32),
                                        prefill_rows=2)
    layers = cfg.num_layers
    assert program_tile_rows(net["decode"]["expert_stats"]) == [16] * layers
    assert program_tile_rows(
        net["prefill"][32]["expert_stats"]) == [16] * layers
    assert expert_tile_rows(1024, 8, 128) == 64       # SDAR's longest prefill
    assert expert_tile_rows(256, 8, 128) == 16        # its decode forward
    assert expert_tile_rows(64 * 128, 8, 128) == 256
