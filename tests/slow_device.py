"""A device that takes its time, for tests of what the serving loop hides
behind a running chunk: the tiny models' decode chunks take a few
milliseconds on the CPU, less than a scheduler turn's host work, so nothing
could be hidden behind them and no wait for newcomers would ever fit."""
import time

import numpy as np


class _Late:
    """A fetch whose copy to the host returns no sooner than ``at()``."""

    def __init__(self, value, at):
        self._value, self._at = value, at

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self._at() - time.perf_counter()))
        return np.asarray(self._value, dtype=dtype)


def slow_device(exe, wall: float) -> None:
    """Make ``exe``'s chained dispatches whose fetch is taken later take
    ``wall`` seconds each on a device that runs its queue in order: the
    fetch (inside its ``executor.fetch`` phase, where a real one blocks)
    returns no sooner than ``wall`` after the later of the dispatch's
    launch and the end of the one before it."""
    run_chained, free_at = exe.run_chained, [0.0]

    def paced(*a, **kw):
        launched = time.perf_counter()
        pending = run_chained(*a, **kw)
        done = []

        def at():
            if not done:
                free_at[0] = max(free_at[0], launched) + wall
                done.append(free_at[0])
            return done[0]

        pending._fetches = [_Late(v, at) for v in pending._fetches]
        return pending

    exe.run_chained = paced
