"""The reduction from a ``.xplane.pb`` profile to device busy time, the
operations that took most of it, and the idle gaps by what the host was
doing. Read with nothing but JAX (``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand, PR 24, see PERF.md): one plane
per chip named ``/device:TPU:<n>``; its line ``XLA Ops`` carries one event
per executed HLO operation (a fusion, a custom call, a copy), nested where
an operation contains others (``while``); ``XLA Modules`` carries one event
per executable run; ``Steps`` groups them. Times are nanoseconds from the
start of the profile. Host threads are lines of ``/host:CPU``.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ANCHOR = "benchmark.clock_anchor"


_LHS = re.compile(r"^%?([\w\-]+?)(?:\.\d+)* = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """A trace prints an operation as its whole HLO line. The short name
    keeps the stem of the result's name (numbers dropped) and what the
    operation is: ``step_fn custom-call:tpu_custom_call``, ``fusion
    fusion``, ``copy-start``. Operations of one kind add up under it."""
    lhs = _LHS.match(name)
    if not lhs:
        return name[:80]
    target = _TARGET.search(name)
    op = _OPCODE.search(name[lhs.end() - 1:])
    kind = f"custom-call:{target.group(1)}" if target else (
        op.group(1) if op else "?")
    return f"{lhs.group(1)} {kind}"


def _union(intervals):
    """Merged, sorted list of [start, end] from any list of intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def load(path: str, device_plane=DEVICE_PLANE, ops_line: str = OPS_LINE):
    """Planes of interest as plain lists: per device the (name, start_ns,
    end_ns) of every event on the ops line, and the start of the clock
    anchor on the host plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, anchor_ns = {}, None
    for plane in data.planes:
        if device_plane.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == ops_line:
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
            devices[plane.name] = {"ops": ops}
        elif plane.name.startswith("/host:") and anchor_ns is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor_ns = e.start_ns
                        break
                if anchor_ns is not None:
                    break
    return {"devices": devices, "anchor_ns": anchor_ns}


def reduce(loaded: dict, window_ns=None, host_spans=(), top: int = 10):
    """``window_ns`` = (lo, hi) on the trace's clock; default: from the
    first to the last device event. ``host_spans``: (name, start_ns,
    end_ns) on the trace's clock. Returns busy seconds averaged over the
    chips, the window's seconds, the top kinds of operation by summed
    seconds (``short_name`` of what the trace prints, averaged over chips;
    an operation that contains others counts its whole span, so the list
    is not a partition), and the idle gaps summed by the innermost host span over
    each gap's middle."""
    devices = loaded["devices"]
    if not devices:
        return None
    every = [ev for d in devices.values() for ev in d["ops"]]
    if not every:
        return None
    if window_ns is None:
        window_ns = (min(e[1] for e in every), max(e[2] for e in every))
    lo, hi = window_ns
    n = len(devices)
    busy_ns, by_op, gaps_by_span = 0.0, {}, {}
    for d in devices.values():
        merged = _union(_clip([(s, e) for _, s, e in d["ops"]], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in d["ops"]:
            c = _clip([(s, e)], lo, hi)
            if c:
                by_op[name] = by_op.get(name, 0.0) + (c[0][1] - c[0][0])
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2.0
            over = [(e - s, name) for name, s, e in host_spans
                    if s <= mid <= e]
            name = min(over)[1] if over else "(no program span)"
            gaps_by_span[name] = gaps_by_span.get(name, 0.0) + (ge - gs)
    rank = lambda d: [[k, v / n / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    by_kind = {}
    for name, v in by_op.items():
        k = short_name(name)
        by_kind[k] = by_kind.get(k, 0.0) + v
    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "chips": n, "device_ops": rank(by_kind),
            "idle_gaps": rank(gaps_by_span),
            "op_seconds": {k: v / n / 1e9 for k, v in by_op.items()}}


def spans_on_trace_clock(spans, anchor_perf_counter: float, anchor_ns):
    """The program's spans (host monotonic seconds) moved onto the trace's
    clock through the anchor, which was read on both."""
    if anchor_ns is None:
        return []
    to_ns = lambda t: anchor_ns + (t - anchor_perf_counter) * 1e9
    return [(s["name"], to_ns(s["t0"]), to_ns(s["t1"])) for s in spans]
