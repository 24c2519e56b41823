"""A kernel's share of its roofline over the SAME calls on both sides: the
least time for the work of the dispatches the profiler's slice holds, over
the time the device trace gives the kernel's operations inside those
dispatches' modules. ``readers.kernel_roofline_in`` divides the whole
window's mean cost a call by the slice's mean time a call, which reads high
wherever the slice's calls are lighter than the window's (a ramp, another
mix of buckets); here nothing is a mean of another population.

The slice's dispatches are the ones the program's own join ties to a device
module (``readers.dispatch_join``, ``paddle_tpu.trace.join_dispatches``), of
``path`` (``chained``: decode chunks; ``run``: prefills; default: both). What each of them
did is on the ``serving.settle`` span that settled it, which carries the
launch's host time (``launch_t0``) beside what the engine counted for that
one dispatch. ``module`` (a top-level module of ``benchmark/``) holds
``cost``, a function ``(config, [those spans' attributes], peaks) -> least
seconds`` or None. The kernel's time is the summed duration of the device
operations whose name matches ``pattern`` between a joined module's start
and end.

No profile, a program without the join or without the attributes (the
parent commit), nothing joined, no matching operation: None."""
import bisect
import importlib
import re

from readers import dispatch_join, kernel_roofline, xplane


def read(ctx, pattern, module, cost, path=None):
    if not ctx.get("trace") or not ctx.get("peaks"):
        return None
    joined = [d for d in dispatch_join._joined(ctx) or ()
              if path in (None, d["path"])]
    settles = sorted(
        ((s["attrs"]["launch_t0"], s["attrs"]) for s in ctx["spans"]
         if s["name"] == "serving.settle" and "launch_t0" in s["attrs"]),
        key=lambda p: p[0])
    profile = kernel_roofline._newest_profile()
    if not joined or not settles or not profile:
        return None
    starts = [t for t, _ in settles]
    did, spans = [], []
    for d in joined:
        # the launch this dispatch's executor step belongs to: the last one
        # the dispatch thread began before it
        i = bisect.bisect_right(starts, d["launch_t"]) - 1
        if i >= 0:
            did.append(settles[i][1])
            spans.append((d["module_start_ns"], d["module_end_ns"]))
    least = getattr(importlib.import_module(module), cost)(
        ctx["config"], did, ctx["peaks"])
    if not least:
        return None
    pat = re.compile(pattern)
    took = sum(e - s
               for dev in xplane.load(profile)["devices"].values()
               for name, s, e in dev["ops"] if pat.search(name)
               and any(lo <= s and e <= hi for lo, hi in spans)) / 1e9
    if took <= 0:
        return None
    return 100.0 * least / took
