"""A closed loop's window closes with the first burst of tokens at or after
``seconds``: every token streamed up to then is counted, and the window's
length is what was measured, not what was asked for."""
import threading
import time

from runners import serve


class _Fut:
    def __init__(self):
        self.n = 0

    def tokens(self):
        return [0] * self.n


class _Rec:
    def __init__(self):
        self.fut, self.streamed_in_window = _Fut(), 0


def test_window_closes_after_the_next_whole_burst():
    recs = [_Rec() for _ in range(8)]
    for r in recs:
        r.fut.n = 10
    clock = serve._Clock(0.05)
    clock.open()

    def engine():                       # a burst 120 ms after the cut,
        time.sleep(0.17)                # handed over in two parts
        for r in recs[:4]:
            r.fut.n += 4
        time.sleep(0.005)
        for r in recs[4:]:
            r.fut.n += 4
        time.sleep(0.3)                 # the next one comes too late
        for r in recs:
            r.fut.n += 4

    t = threading.Thread(target=engine)
    t.start()
    time.sleep(max(0.0, clock.t_end - time.perf_counter()))
    sent = serve._close_after_next_burst(lambda: list(recs), clock)
    t.join()
    assert sum(r.streamed_in_window for r in sent) == 8 * 14
    assert 0.17 <= clock.seconds < 0.4
    assert abs(clock.t_end - clock.t_open - clock.seconds) < 1e-9


def test_window_closes_by_itself_when_nothing_streams(monkeypatch):
    monkeypatch.setattr(serve, "BURST_WAIT_S", 0.1)
    recs = [_Rec()]
    clock = serve._Clock(0.01)
    clock.open()
    time.sleep(0.01)
    serve._close_after_next_burst(lambda: list(recs), clock)
    assert 0.1 <= clock.seconds < 0.5
