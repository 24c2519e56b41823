"""What the program's own join of its dispatches to the device modules
they launched (``paddle_tpu.trace.join_dispatches``) says of the run's
profile: the newest ``*.xplane.pb`` under ``harness.OUT_DIR/trace/``, as
``TraceWindow.xplane_path`` finds it, against ``ctx["spans"]``.

``value`` = ``overhead_ms``: the mean, over the traced slice's joined
dispatches, of the in-flight wall (launch call to fetch return) less the
module's time on the device: what the runtime and the host add around the
device's work, a dispatch. ``value`` = ``idle_window_pct``: the whole
window's idle share by the program's own count, (starved seconds + for
each ``path`` the window's dispatches times the slice's mean overhead on
that path) over (starved + in-flight seconds), the seconds and counts from
``executor_starved_seconds`` and ``executor_inflight_seconds`` over the
window. The overhead is measured with the profiler on, so where the
profiler lengthens a dispatch's wall the share is an upper bound.

No profile (a CPU rehearsal), a program without the join or without the
families (the parent commit), nothing joined: None. What the join could
not match is said on a line of its own, once a run."""
from harness import say, sum_matching
from readers import kernel_roofline


def _joined(ctx):
    if "_dispatch_join" not in ctx:
        ctx["_dispatch_join"] = None
        path = kernel_roofline._newest_profile() if ctx.get("trace") else None
        try:
            from paddle_tpu.trace import join_dispatches
        except ImportError:
            join_dispatches = None
        if path and join_dispatches:
            got = join_dispatches(path, ctx["spans"])
            say(f"dispatch_join: {len(got['joined'])} of {got['inside']} "
                f"dispatches inside the slice joined to a device module; "
                f"no module {got['no_module']}, claimed twice "
                f"{got['claimed_twice']}, cut by the slice's edge "
                f"{got['cut']}, modules unclaimed {got['modules_unclaimed']}")
            ctx["_dispatch_join"] = got["joined"] or None
    return ctx["_dispatch_join"]


def _overhead_s(rows):
    return sum(d["ready_t"] - d["launch_t"] - d["device_s"]
               for d in rows) / len(rows)


def read(ctx, value):
    rows = _joined(ctx)
    if not rows:
        return None
    if value == "overhead_ms":
        return 1e3 * _overhead_s(rows)
    if value != "idle_window_pct":
        raise ValueError(f"dispatch_join: unknown value {value!r}")
    c = ctx["counters"]
    starved = sum_matching(c, "executor_starved_seconds_sum")
    inflight = sum_matching(c, "executor_inflight_seconds_sum")
    if inflight <= 0:
        return None
    added = 0.0
    for path in sorted({d["path"] for d in rows}):
        n = sum_matching(c, "executor_inflight_seconds_count", path=path)
        added += n * _overhead_s([d for d in rows if d["path"] == path])
    return 100.0 * (starved + added) / (starved + inflight)
