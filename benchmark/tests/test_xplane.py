"""The reduction from a profile to busy time, top operations and idle gaps,
on a small trace recorded on a v5e (``tools/record_small_trace.py``, PR 24:
six 2048-wide bf16 matmuls in two groups of three, a 4 ms and an 8 ms host
sleep before the groups, each inside a recorded host span)."""
import json
import os

import pytest

from readers import trace_idle, trace_op_share, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    if not os.path.exists(TRACE):
        pytest.skip("no recorded trace in tests/data")
    meta = json.load(open(os.path.join(DATA, "small_v5e.json")))
    loaded = xplane.load(TRACE)
    assert list(loaded["devices"]) == ["/device:TPU:0"]
    assert loaded["anchor_ns"] is not None
    pc = meta["anchor_perf_counter"]
    to_ns = lambda t: loaded["anchor_ns"] + (t - pc) * 1e9
    host = xplane.spans_on_trace_clock(meta["spans"], pc,
                                       loaded["anchor_ns"])
    window = (to_ns(meta["t_started"]), to_ns(meta["t_stopped"]))
    return xplane.reduce(loaded, window, host), meta


def test_busy_and_window(reduced):
    r, meta = reduced
    assert r["chips"] == 1
    assert abs(r["window_s"] - (meta["t_stopped"] - meta["t_started"])) < 1e-6
    # six matmuls of 17 GFLOP each cannot take under 0.5 ms or over the
    # window; the two sleeps (12 ms) are idle
    assert 0.0005 < r["busy_s"] < r["window_s"] - 0.012


def test_top_operations_are_the_matmul_fusions(reduced):
    r, _ = reduced
    names = [n for n, _ in r["device_ops"]]
    assert len(names) <= 10 and names
    assert sum(s for _, s in r["device_ops"]) >= 0.9 * r["busy_s"]
    assert all(len(n) < 80 for n in names)       # short names, not HLO text


def test_idle_gaps_fall_under_the_host_spans_that_slept(reduced):
    r, _ = reduced
    gaps = dict(r["idle_gaps"])
    assert gaps.get("host.prepare", 0) >= 0.003
    assert gaps.get("host.fetch", 0) >= 0.007
    total = sum(gaps.values())
    assert abs(total - (r["window_s"] - r["busy_s"])) < 1e-6


def test_readers_on_the_reduced_trace(reduced):
    r, _ = reduced
    ctx = {"trace": r}
    idle = trace_idle.read(ctx)
    assert 0 < idle < 100
    assert abs(idle - 100 * (1 - r["busy_s"] / r["window_s"])) < 1e-9
    assert trace_op_share.read(ctx, pattern="tpu_custom_call") is None
    assert trace_op_share.read(ctx, pattern=".") > 99.0
    assert trace_idle.read({"trace": None}) is None


def test_short_name():
    long = ('%step_fn.12 = (f32[384,512,64]{2,1,0:T(8,128)}) custom-call('
            's32[3]{0} %pad), custom_call_target="tpu_custom_call", x={}')
    assert xplane.short_name(long) == "step_fn custom-call:tpu_custom_call"
    assert xplane.short_name("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), "
                             "kind=kLoop") == "fusion fusion"
