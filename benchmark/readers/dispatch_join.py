"""What the program's own join of its dispatches to the device modules
they launched (``paddle_tpu.trace.join_dispatches``) says of the run's
profile: the newest ``*.xplane.pb`` under ``harness.OUT_DIR/trace/``, as
``TraceWindow.xplane_path`` finds it, against ``ctx["spans"]``. No metric's
file names this module: ``readers.kernel_roofline_slice`` reads through it,
once a run (the joined dispatches are kept in ``ctx``).

No profile (a CPU rehearsal), a program without the join (the parent
commit), nothing joined: None. What the join could not match is said on a
line of its own, once a run.

Until PR 51 two metrics were built here on a dispatch's in-flight wall less
its module's time (``dispatch_overhead_ms.*``, ``device_idle_window_pct.*``);
since PR 42 a wall holds the chunk ahead of it, both over-counted and are
gone. One restated from ``StepRecord.head_t`` would be a new reader."""
from harness import say
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
