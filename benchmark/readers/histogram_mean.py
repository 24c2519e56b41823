"""Mean of a monitor histogram over the window, from its exact ``sum`` and
``count`` (delta of each across the window), times ``scale``."""
from harness import sum_matching


def read(ctx, name, labels=None, scale=1.0):
    labels = labels or {}
    n = sum_matching(ctx["counters"], name + "_count", **labels)
    if n <= 0:
        return None
    return scale * sum_matching(ctx["counters"], name + "_sum", **labels) / n
