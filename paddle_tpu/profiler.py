"""Profiler: fluid.profiler API over jax.profiler.

Reference: python/paddle/fluid/profiler.py (:225 profiler context manager,
:127 start_profiler, :168 stop_profiler) and the C++ RecordEvent/CUPTI
tracer (platform/profiler.h, device_tracer.h). On TPU the equivalent
substrate is the XLA/XPlane trace: jax.profiler.trace writes a TensorBoard-
loadable (and Perfetto-convertible) dump — the tools/timeline.py role.
Op-level host annotations use jax.profiler.TraceAnnotation, the RecordEvent
analogue; the executor feeds its build and compile stages through
RecordEvent too, so they land in the same timeline (the launch of a step is
the span ``executor.step`` of ``paddle_tpu.trace``, not a RecordEvent).

Thread-safety: all host-side state (event aggregates, span list, tid map)
is guarded by one module lock — RecordEvent is used from DataLoader worker
threads while ``stop_profiler`` snapshots and clears from the main thread.

``stop_profiler`` returns the host report as a structure (and logs it via
``logging``) so test suites and servers can consume it; the printed table
remains for CLI compatibility with the reference.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import defaultdict

from .monitor.lockwitness import make_lock
from typing import Optional

import jax

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "RecordEvent", "cuda_profiler", "npu_profiler"]

log = logging.getLogger("paddle_tpu.profiler")

# one lock for every piece of host-side profiling state: RecordEvent
# exits on worker threads race stop_profiler's snapshot-and-clear
_lock = make_lock("profiler._lock")
_trace_dir: Optional[str] = None
_host_events = defaultdict(lambda: [0, 0.0])  # name -> [count, total_s]
# (name, t0_s, t1_s, small_tid, epoch0_s) while profiling: t0/t1 are
# perf_counter (durations), epoch0 is time.time() at __enter__ — the
# shared wall-clock anchor that lets tools/timeline.py merge these host
# events with paddle_tpu.trace spans on one Chrome timeline
_host_spans = []
_tid_map = {}     # thread ident -> stable small timeline row id


def start_profiler(state="All", tracer_option=None, profile_path="/tmp/profile"):
    global _trace_dir
    with _lock:
        _trace_dir = profile_path
    jax.profiler.start_trace(profile_path)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Stop tracing; aggregate and emit the host-side event report.

    Returns ``{"events": [{"name", "calls", "total_s", "avg_s"}, ...],
    "sorted_by": key, "spans_path": path-or-None}`` — the structure a test
    suite or server asserts on. The same table is logged at INFO on the
    ``paddle_tpu.profiler`` logger and printed (reference CLI behaviour).
    """
    global _trace_dir
    jax.profiler.stop_trace()
    with _lock:
        trace_dir, _trace_dir = _trace_dir, None
        spans = list(_host_spans)
        _host_spans.clear()
        events = {name: (cnt, tot)
                  for name, (cnt, tot) in _host_events.items()}
    report = _host_report(events, sorted_key)
    table = _format_host_report(report)
    if table:
        log.info("host event report (sorted by %s):\n%s",
                 report["sorted_by"], table)
        print(table)
    # span dump consumed by tools/timeline.py (the reference writes
    # profiler.proto consumed by its timeline.py; here it is JSON)
    if trace_dir:
        import json
        import os

        path = os.path.join(trace_dir, "host_events.json")
        with open(path, "w") as f:
            json.dump([{"name": n, "t0": a, "t1": b, "tid": t,
                        "epoch": e}
                       for n, a, b, t, e in spans], f)
        report["spans_path"] = path
    return report


def reset_profiler():
    with _lock:
        _host_events.clear()
        _host_spans.clear()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option=None):
    start_profiler(state, tracer_option, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class RecordEvent:
    """Host-side RAII marker (reference platform/profiler.h:81); shows up in
    the XPlane trace as a TraceAnnotation and in the host-side table."""

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._t0 = time.perf_counter()
        # wall-clock anchor at open: perf_counter deltas alone cannot be
        # merged with trace spans or other processes' dumps
        self._epoch0 = time.time()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        t1 = time.perf_counter()
        ident = threading.get_ident()
        with _lock:
            rec = _host_events[self.name]
            rec[0] += 1
            rec[1] += t1 - self._t0
            if _trace_dir is not None:
                tid = _tid_map.setdefault(ident, len(_tid_map))
                _host_spans.append((self.name, self._t0, t1, tid,
                                    self._epoch0))
        return False


def _host_report(events, sorted_key=None) -> dict:
    rows = [{"name": name, "calls": cnt, "total_s": tot,
             "avg_s": tot / cnt}
            for name, (cnt, tot) in events.items()]
    sorted_by = sorted_key or "total"
    if sorted_by == "total":
        rows.sort(key=lambda r: -r["total_s"])
    elif sorted_by == "calls":
        rows.sort(key=lambda r: -r["calls"])
    elif sorted_by == "ave":
        rows.sort(key=lambda r: -r["avg_s"])
    return {"events": rows, "sorted_by": sorted_by, "spans_path": None}


def _format_host_report(report: dict) -> str:
    if not report["events"]:
        return ""
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"]
    for r in report["events"]:
        lines.append(f"{r['name']:<40}{r['calls']:>8}"
                     f"{r['total_s']:>12.6f}{r['avg_s']:>12.6f}")
    return "\n".join(lines)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    """Compat no-op (reference profiler.py:39): TPU has no nvprof."""
    yield


npu_profiler = cuda_profiler
