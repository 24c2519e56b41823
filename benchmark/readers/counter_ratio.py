"""One sum of the window's counters over another, as a plain ratio
(``readers.counter_share`` without the percent): ``part`` and ``of`` are
lists of ``{"name": family, "labels": {...}}``. A program without the
families (the parent commit of the PR that brought them) has nothing under
either: None."""
from readers.counter_share import _total


def read(ctx, part, of):
    whole, top = _total(ctx["counters"], of), _total(ctx["counters"], part)
    if whole <= 0 or top <= 0:
        return None
    return top / whole
