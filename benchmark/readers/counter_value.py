"""A monitor counter: its delta over the window, or with ``since`` =
"start" its value since the process began (for what is counted while
programs are lowered, before the window)."""
from harness import sum_matching


def read(ctx, name, labels=None, since="window"):
    src = ctx["counters_total"] if since == "start" else ctx["counters"]
    v = sum_matching(src, name, **(labels or {}))
    return v if v > 0 else None
