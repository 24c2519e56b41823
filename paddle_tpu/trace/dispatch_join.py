"""Join the program's dispatches to the device modules they launched.

A dispatch is one ``executor.step`` span (the launch call, ``launch_t`` at
its entry) with the ``executor.fetch`` span that follows it under the same
``executor.run`` / ``executor.run_chained`` (``ready_t`` at its exit). A
profile (``jax.profiler``, ``.xplane.pb``) holds, on its own clock, the
``TraceAnnotation`` each traced launch entered (named ``executor.step``,
with the ``dispatch`` id the span carries too) and, on the device's plane,
one ``XLA Modules`` event per executable run. :func:`join_dispatches` puts
the two together and splits a dispatch's wall in three:

* **launch latency**: launch call to the module's start (host work inside
  the call, the runtime's queue);
* **device time**: the module's duration;
* **return latency**: the module's end to the fetch's return (the runtime's
  completion, the copy back, the host thread's wake-up).

Nothing is guessed: a dispatch that finds no module, a module two
dispatches claim, and a dispatch the profile's edge cuts are counted and
left out. The device's clock and the host's are aligned by the runtime, to
about a millisecond in the profiles looked at: the sum of the two
latencies (the wall less the device time) does not depend on that, each of
them alone does.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = ["LAUNCH_SPAN", "load_profile", "dispatches_of", "join_dispatches"]

LAUNCH_SPAN = "executor.step"
_FETCH_SPAN = "executor.fetch"
_PATHS = {"executor.run": "run", "executor.run_chained": "chained"}
MODULES_LINE = "XLA Modules"


def load_profile(path: str, device_plane: str = "/device:TPU:0") -> dict:
    """What the join needs of a profile, as plain lists (nanoseconds on the
    profile's clock): ``modules`` [(name, start, end)] of the device
    plane's ``XLA Modules`` line, ``launches`` [(dispatch or None, start,
    end)] of the host planes' ``executor.step`` annotations, and ``extent``
    (first start, last end): the time both sides of the profile cover,
    which is the device plane's events where it has any (the device's
    tracing starts after the host's and stops before it), else every
    event's."""
    from jax.profiler import ProfileData

    modules, launches = [], []
    inf = float("inf")
    extent = {True: [inf, -inf], False: [inf, -inf]}     # by on_device
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name == device_plane
        on_host = plane.name.startswith("/host:")
        lo_hi = extent[on_device]
        for line in plane.lines:
            keep = on_device and line.name == MODULES_LINE
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                lo_hi[0], lo_hi[1] = min(lo_hi[0], start), max(lo_hi[1], end)
                if keep:
                    modules.append((e.name, start, end))
                elif on_host and e.name == LAUNCH_SPAN:
                    ident = dict(e.stats).get("dispatch")
                    launches.append((None if ident is None else int(ident),
                                     start, end))
    lo, hi = extent[True] if extent[True][0] <= extent[True][1] \
        else extent[False]
    return {"modules": sorted(modules, key=lambda m: m[1]),
            "launches": sorted(launches, key=lambda a: a[1]),
            "extent": (lo, hi) if lo <= hi else None}


def _fields(s) -> Tuple[str, str, Optional[str], dict, float, float]:
    """name, id, parent id, attributes, start and end (seconds on one host
    clock) of a span in any of its three shapes: a :class:`Span`, its
    ``to_dict()`` (``t0_epoch``, ``duration_s``) or a dict with ``t0`` and
    ``t1``."""
    if isinstance(s, dict):
        t0 = s["t0"] if "t0" in s else s["t0_epoch"]
        t1 = s["t1"] if "t1" in s else t0 + s["duration_s"]
        return (s["name"], s["span_id"], s.get("parent_id"),
                s.get("attrs") or {}, t0, t1)
    return (s.name, s.span_id, s.parent_id, s.attrs, s.t0_mono,
            s.t0_mono + s.duration_s)


def dispatches_of(span_list: Iterable[Any]) -> List[dict]:
    """The dispatches that fetched among ``span_list``, by launch time:
    ``dispatch`` (id or None), ``module``, ``path``, ``launch_t``,
    ``ready_t``."""
    launch, ready, paths = {}, {}, {}
    for s in span_list:
        name, ident, parent, attrs, t0, t1 = _fields(s)
        if name in _PATHS:
            paths[ident] = _PATHS[name]
        elif name == LAUNCH_SPAN and parent:
            launch[parent] = (attrs, t0)
        elif name == _FETCH_SPAN and parent:
            ready[parent] = t1
    out = [{"dispatch": attrs.get("dispatch"), "module": attrs.get("module"),
            "path": paths.get(parent), "launch_t": t0,
            "ready_t": ready[parent]}
           for parent, (attrs, t0) in launch.items() if parent in ready]
    return sorted(out, key=lambda d: d["launch_t"])


def _stem(module_event_name: str) -> str:
    """``jit_multi_fn(14836250070554842513)`` -> ``jit_multi_fn``."""
    return module_event_name.partition("(")[0]


def join_dispatches(profile, span_list=None,
                    anchor: Optional[Tuple[float, float]] = None) -> dict:
    """``profile``: a ``.xplane.pb`` path, or what :func:`load_profile`
    returns. ``span_list``: the program's spans (default: the collector's).
    The host's clock is tied to the profile's by id where the profile holds
    launch annotations with the spans' ``dispatch`` ids (each such launch
    then stands where the profiler saw it), and otherwise by ``anchor``, a
    (host seconds, profile nanoseconds) reading of one instant on both
    clocks, through which every launch inside the profile is placed on its
    clock, in the order the spans have. Without either nothing is joined.

    Returns ``joined`` (one dict a dispatch: ``dispatch``, ``path``,
    ``module``, ``launch_t``, ``ready_t``, ``launch_ns``, ``ready_ns``,
    ``module_start_ns``, ``module_end_ns``, ``launch_latency_s``,
    ``device_s``, ``return_latency_s``, ``by`` = "id" | "anchor"), and the
    counts ``inside`` (dispatches wholly inside the profile), ``no_module``,
    ``claimed_twice``, ``cut`` (by the profile's edge) and
    ``modules_unclaimed``. ``joined`` + ``no_module`` + ``claimed_twice``
    = ``inside``."""
    if isinstance(profile, str):
        profile = load_profile(profile)
    if span_list is None:
        from . import spans

        span_list = spans()
    out: Dict[str, Any] = {"joined": [], "inside": 0, "no_module": 0,
                           "claimed_twice": 0, "cut": 0,
                           "modules_unclaimed": 0}
    dispatches = dispatches_of(span_list)
    if not dispatches or not profile.get("extent"):
        return out
    seen = {ident: start for ident, start, _ in profile["launches"]
            if ident is not None}
    offsets = [seen[d["dispatch"]] - d["launch_t"] * 1e9
               for d in dispatches if d["dispatch"] in seen]
    if offsets:
        offset = statistics.median(offsets)
    elif anchor is not None:
        offset = anchor[1] - anchor[0] * 1e9
    else:
        return out
    lo, hi = profile["extent"]
    modules = profile["modules"]
    claims: Dict[int, List[dict]] = {}
    for d in dispatches:
        by_id = d["dispatch"] in seen
        launch_ns = seen[d["dispatch"]] if by_id \
            else d["launch_t"] * 1e9 + offset
        ready_ns = launch_ns + (d["ready_t"] - d["launch_t"]) * 1e9
        if ready_ns <= lo or launch_ns >= hi:
            continue                        # not in this profile at all
        if launch_ns < lo or ready_ns > hi:
            out["cut"] += 1
            continue
        out["inside"] += 1
        d = dict(d, launch_ns=launch_ns, ready_ns=ready_ns,
                 by="id" if by_id else "anchor")
        # the module this dispatch had in flight: of its executable's
        # name, and more than half of it inside the dispatch's wall
        best, best_overlap = None, 0.0
        for i, (name, start, end) in enumerate(modules):
            if start >= ready_ns:
                break
            if d["module"] and _stem(name) != d["module"]:
                continue
            overlap = min(end, ready_ns) - max(start, launch_ns)
            if overlap > best_overlap and 2 * overlap > end - start:
                best, best_overlap = i, overlap
        if best is None:
            out["no_module"] += 1
        else:
            claims.setdefault(best, []).append(d)
    for i, claimants in claims.items():
        if len(claimants) > 1:
            out["claimed_twice"] += len(claimants)
            continue
        d, (_, start, end) = claimants[0], modules[i]
        out["joined"].append(dict(
            d, module_start_ns=start, module_end_ns=end,
            launch_latency_s=(start - d["launch_ns"]) / 1e9,
            device_s=(end - start) / 1e9,
            return_latency_s=(d["ready_ns"] - end) / 1e9))
    out["joined"].sort(key=lambda d: d["launch_ns"])
    inside = [m for m in modules if lo <= m[1] and m[2] <= hi]
    out["modules_unclaimed"] = len(inside) - len(claims)
    return out
