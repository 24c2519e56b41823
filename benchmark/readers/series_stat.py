"""A statistic of something the benchmark sampled itself during the window
(``ctx['series'][name]``): ``mean`` or a percentile ``p<q>``."""
from harness import percentile


def read(ctx, name, stat="mean", scale=1.0):
    values = ctx["series"].get(name) or []
    if not values:
        return None
    if stat == "mean":
        return scale * sum(values) / len(values)
    return scale * percentile(values, float(stat[1:]))
