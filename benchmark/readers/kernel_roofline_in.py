"""``readers.kernel_roofline`` with the cost function's module named in
the metric's file: ``module`` (a top-level module of ``benchmark/``) holds
``cost``, a function ``(config, counters, peaks) -> (least seconds of all
the kernel's calls the counters saw, calls)`` or None. Everything else is
that reader's: the trace's time is the mean duration of the device
operations whose name matches ``pattern``, read from the profile the run
has just written. A program without the counters (the parent commit), a
run without a trace, a trace without a matching operation: None."""
import importlib
import re

from readers import kernel_roofline, xplane


def read(ctx, pattern, module, cost, exclude="^$"):
    if not ctx.get("trace") or not ctx.get("peaks"):
        return None
    least = getattr(importlib.import_module(module), cost)(
        ctx["config"], ctx["counters"], ctx["peaks"])
    path = kernel_roofline._newest_profile()
    if not least or not least[1] or not path:
        return None
    pat, exc = re.compile(pattern), re.compile(exclude)
    took = [(e - s) / 1e9
            for dev in xplane.load(path)["devices"].values()
            for name, s, e in dev["ops"]
            if pat.search(name) and not exc.search(name)]
    if not took:
        return None
    seconds, calls = least
    return 100.0 * (seconds / calls) / (sum(took) / len(took))
