#!/usr/bin/env python
"""Convert profiler/trace dumps to ONE Chrome tracing JSON
(chrome://tracing / Perfetto).

Reference: tools/timeline.py:21-25 — there the input is the C++ profiler's
profiler.proto; here it is two host-side sources sharing one wall-clock
anchor:

* ``host_events.json`` — the ``fluid.profiler.profiler(profile_path=...)``
  RecordEvent span dump (next to the XPlane trace, which itself opens
  directly in TensorBoard/Perfetto). Each span carries an ``epoch``
  anchor recorded at ``__enter__`` (spans written before that field
  existed fall back to a relative timeline).
* a ``paddle_tpu.trace`` span dump — JSONL from ``trace.export_jsonl``
  (``--trace_path``). Spans carry ``t0_epoch`` natively.

Both map onto the epoch clock, so a serving request's trace spans line up
against the executor's RecordEvent intervals in one merged timeline:
profiler rows under pid 0, trace spans under pid 1 (grouped per thread),
with trace/span ids in each event's ``args``.

With ``--xplane <profile.xplane.pb>`` beside ``--trace_path`` the device
modules of the profile are joined to the dispatches that launched them
(``paddle_tpu.trace.join_dispatches``; this one option imports the
framework and JAX) and land on a third row group, pid 2, each with its
launch latency, device time and return latency in ``args``; what could not
be joined is counted on standard output.

Usage:
    python tools/timeline.py --profile_path /tmp/profile \
                             --timeline_path /tmp/timeline.json
    python tools/timeline.py --trace_path spans.jsonl \
                             --timeline_path /tmp/timeline.json
    python tools/timeline.py --profile_path /tmp/profile \
                             --trace_path spans.jsonl \
                             --timeline_path /tmp/merged.json
    python tools/timeline.py --trace_path spans.jsonl \
                             --xplane /tmp/prof/.../host.xplane.pb \
                             --timeline_path /tmp/joined.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def _load_host_spans(profile_path: str) -> Optional[list]:
    src = profile_path
    if os.path.isdir(src):
        src = os.path.join(src, "host_events.json")
    if not os.path.exists(src):
        print(f"no host_events.json under {profile_path} — run under "
              f"fluid.profiler.profiler(profile_path=...)", file=sys.stderr)
        return None
    with open(src) as f:
        return json.load(f)


def _load_trace_spans(trace_path: str) -> Optional[list]:
    if not os.path.exists(trace_path):
        print(f"no trace span dump at {trace_path} — write one with "
              f"paddle_tpu.trace.export_jsonl(path)", file=sys.stderr)
        return None
    spans = []
    with open(trace_path) as f:
        for line in f:
            line = line.strip()
            if line:
                spans.append(json.loads(line))
    return spans


def _device_rows(xplane_path: str, tspans: list) -> List[dict]:
    """The profile's modules that ``join_dispatches`` ties to a dispatch
    among ``tspans``, as events on the spans' epoch clock (a module's
    start is its dispatch's launch plus the launch latency)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from paddle_tpu.trace import join_dispatches

    joined = join_dispatches(xplane_path, tspans)
    rows = joined.pop("joined")
    print(f"joined {len(rows)} of {joined['inside']} dispatches inside the "
          f"profile to a device module: {joined}")
    return [{"name": f"{d['module']} #{d['dispatch']}", "ph": "X",
             "ts": (d["launch_t"] + d["launch_latency_s"]) * 1e6,
             "dur": d["device_s"] * 1e6, "pid": 2, "tid": 0,
             "cat": "device",
             "args": {"dispatch": d["dispatch"], "path": d["path"],
                      "launch_latency_ms": 1e3 * d["launch_latency_s"],
                      "device_ms": 1e3 * d["device_s"],
                      "return_latency_ms": 1e3 * d["return_latency_s"]}}
            for d in rows]


def convert(profile_path: Optional[str], timeline_path: str,
            trace_path: Optional[str] = None,
            xplane_path: Optional[str] = None) -> int:
    host = _load_host_spans(profile_path) if profile_path else []
    if host is None:
        return 1
    tspans = _load_trace_spans(trace_path) if trace_path else []
    if tspans is None:
        return 1

    events: List[dict] = []
    # ---- profiler host events (pid 0) ---------------------------------
    # pre-anchor dumps (no 'epoch' field) only carry perf_counter deltas;
    # those get a relative timeline exactly as before — an empty profile
    # is still a valid run (the PR 3 fix), so base defaults to 0.0
    have_epoch = bool(host) and all("epoch" in s for s in host)
    if have_epoch:
        def host_ts(s):
            return s["epoch"] * 1e6
    else:
        base = min((s["t0"] for s in host), default=0.0)

        def host_ts(s):
            return (s["t0"] - base) * 1e6
    for s in host:
        events.append({
            "name": s["name"],
            "ph": "X",
            "ts": host_ts(s),
            "dur": (s["t1"] - s["t0"]) * 1e6,
            "pid": 0,
            "tid": s.get("tid", 0),
            "cat": "host",
        })
    # ---- trace spans (pid 1), same epoch clock ------------------------
    # NOTE: this mapping mirrors paddle_tpu.trace.to_chrome_events over
    # the to_dict() span shape — kept as a stdlib copy ON PURPOSE so
    # converting a JSON dump never imports the framework (and jax).
    # Change the event schema in BOTH places.
    if tspans and host and not have_epoch:
        print("warning: host_events.json predates the epoch anchor — "
              "profiler rows are on a RELATIVE clock and will not line "
              "up with the trace spans", file=sys.stderr)
    for s in tspans:
        if s.get("duration_s") is None:
            continue
        args = {"trace_id": s.get("trace_id", ""),
                "span_id": s.get("span_id", ""),
                "status": s.get("status", "")}
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        if s.get("error"):
            args["error"] = s["error"]
        args.update(s.get("attrs") or {})
        events.append({
            "name": s["name"],
            "ph": "X",
            "ts": s["t0_epoch"] * 1e6,
            "dur": s["duration_s"] * 1e6,
            "pid": 1,
            "tid": s.get("thread", 0),
            "cat": "trace",
            "args": args,
        })
    n_device = 0
    if xplane_path:
        device = _device_rows(xplane_path, tspans)
        n_device = len(device)
        events += device
    with open(timeline_path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    print(f"wrote {len(events)} events to {timeline_path} "
          f"({len(host)} profiler, "
          f"{len(events) - len(host) - n_device} trace, "
          f"{n_device} device)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile_path",
                    help="profiler dump dir (host_events.json)")
    ap.add_argument("--trace_path",
                    help="paddle_tpu.trace JSONL span dump to merge")
    ap.add_argument("--xplane",
                    help="a .xplane.pb profile: its device modules joined "
                         "to the dispatches of --trace_path")
    ap.add_argument("--timeline_path", required=True)
    args = ap.parse_args(argv)
    if not args.profile_path and not args.trace_path:
        ap.error("need --profile_path and/or --trace_path")
    if args.xplane and not args.trace_path:
        ap.error("--xplane joins to the spans of --trace_path")
    return convert(args.profile_path, args.timeline_path,
                   trace_path=args.trace_path, xplane_path=args.xplane)


if __name__ == "__main__":
    sys.exit(main())
