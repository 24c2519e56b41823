"""Device idle share: 1 - (union of device-operation intervals) / window,
from the reduced profiler trace."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
