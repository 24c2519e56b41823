"""One sum of the window's counters as a share of another, in percent.
``part`` and ``of`` are lists of ``{"name": family, "labels": {...}}``; a
histogram's exact sum is read as ``<family>_sum``. A program without the
families (the parent commit of the PR that brought them) has nothing under
``of``: None."""
from harness import sum_matching


def _total(counters, terms):
    return sum(sum_matching(counters, t["name"], **t.get("labels", {}))
               for t in terms)


def read(ctx, part, of):
    whole = _total(ctx["counters"], of)
    if whole <= 0:
        return None
    return 100.0 * _total(ctx["counters"], part) / whole
