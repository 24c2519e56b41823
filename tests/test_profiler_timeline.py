"""Profiler host-event path (ISSUE 3 satellites): RecordEvent aggregation,
span dump round-trip through tools/timeline.py into chrome-trace JSON,
stop_profiler's structured report + logging, lock-protected mutation, and
the executor's monitor spans landing in the same timeline."""
import json
import logging
import os
import threading
import time

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import profiler as prof
import tools.timeline as timeline


def test_record_event_aggregation_without_trace():
    prof.reset_profiler()
    with prof.RecordEvent("agg_test"):
        time.sleep(0.01)
    with prof.RecordEvent("agg_test"):
        pass
    cnt, tot = prof._host_events["agg_test"]
    assert cnt == 2
    assert tot >= 0.01


def test_profiler_roundtrip_to_chrome_trace(tmp_path):
    prof.reset_profiler()
    with prof.profiler(profile_path=str(tmp_path)):
        with prof.RecordEvent("span_outer"):
            with prof.RecordEvent("span_inner"):
                time.sleep(0.002)
        # executor activity inside the window: its monitor spans must land
        # in the same host timeline (the RecordEvent substrate)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[2], dtype="float32")
            y = fluid.layers.fc(x, 2)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
                    fetch_list=[y.name])
    assert (tmp_path / "host_events.json").exists()

    out = tmp_path / "timeline.json"
    assert timeline.convert(str(tmp_path), str(out)) == 0
    trace = json.loads(out.read_text())
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    # the launch itself is one timed site, the span ``executor.step``
    # (PR 39): no RecordEvent doubles it on every dispatch any more
    assert {"span_outer", "span_inner",
            "executor::trace_lower", "executor::xla_compile"} <= names
    assert "executor::step" not in names
    for e in events:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    # inner span nests inside outer on the same row
    outer = next(e for e in events if e["name"] == "span_outer")
    inner = next(e for e in events if e["name"] == "span_inner")
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_timeline_handles_empty_span_dump(tmp_path):
    """Satellite: an empty host_events.json used to NameError on the
    unbound base timestamp; it must emit a valid empty trace and exit 0."""
    (tmp_path / "host_events.json").write_text("[]")
    out = tmp_path / "timeline.json"
    assert timeline.convert(str(tmp_path), str(out)) == 0
    data = json.loads(out.read_text())
    assert data["traceEvents"] == []
    assert timeline.main(["--profile_path", str(tmp_path),
                          "--timeline_path", str(out)]) == 0


def test_timeline_missing_dump_still_errors(tmp_path):
    assert timeline.convert(str(tmp_path), str(tmp_path / "o.json")) == 1


def test_stop_profiler_returns_structure_and_logs(tmp_path, caplog, capsys):
    prof.reset_profiler()
    with caplog.at_level(logging.INFO, logger="paddle_tpu.profiler"):
        prof.start_profiler(profile_path=str(tmp_path))
        with prof.RecordEvent("structured_event"):
            time.sleep(0.001)
        report = prof.stop_profiler(sorted_key="calls")
    names = [r["name"] for r in report["events"]]
    assert "structured_event" in names
    row = report["events"][names.index("structured_event")]
    assert row["calls"] >= 1
    assert row["total_s"] > 0 and row["avg_s"] > 0
    assert report["sorted_by"] == "calls"
    assert report["spans_path"] and os.path.exists(report["spans_path"])
    # logged for servers/test suites...
    assert any("host event report" in r.message for r in caplog.records)
    # ...and still printed for CLI compat with the reference
    assert "structured_event" in capsys.readouterr().out


def test_record_event_threadsafe_against_stop(tmp_path):
    """Satellite: worker threads in RecordEvent.__exit__ race
    stop_profiler's snapshot-and-clear; under the shared lock this must
    neither lose the report nor corrupt the span list."""
    prof.reset_profiler()
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with prof.RecordEvent("worker_span"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    prof.start_profiler(profile_path=str(tmp_path))
    for t in threads:
        t.start()
    try:
        time.sleep(0.05)
        report = prof.stop_profiler()
    finally:
        stop.set()
        for t in threads:
            t.join()
    names = [r["name"] for r in report["events"]]
    assert "worker_span" in names
    spans = json.load(open(report["spans_path"]))
    # every dumped span is well-formed (no torn writes)
    for s in spans:
        assert s["t1"] >= s["t0"]
        assert isinstance(s["tid"], int)
