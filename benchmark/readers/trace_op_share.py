"""Share of the device's busy time spent in operations whose trace name
matches ``pattern`` (a regular expression), from the reduced profiler
trace. Events that contain other events (``while``) are left out of the
numerator by ``exclude``."""
import re


def read(ctx, pattern, exclude="^$"):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    pat, exc = re.compile(pattern), re.compile(exclude)
    hit = sum(v for k, v in t["op_seconds"].items()
              if pat.search(k) and not exc.search(k))
    if hit <= 0:
        return None
    return 100.0 * hit / t["busy_s"]
