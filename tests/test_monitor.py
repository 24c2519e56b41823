"""paddle_tpu.monitor — metrics registry, executor instrumentation,
recompilation diagnostics, event hooks, and the metrics_report CI gate
(ISSUE 3 tentpole; reference platform/profiler.h gave Fluid this kind of
visibility per op — here it is per executor hot path)."""
import json
import logging

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor


@pytest.fixture(autouse=True)
def _fresh_monitor():
    monitor.reset()
    monitor.clear_hooks()
    yield
    monitor.reset()
    monitor.clear_hooks()


def _build_train():
    x = fluid.layers.data("x", shape=[4], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def _feed(batch=8, dtype=np.float32):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 4).astype(dtype),
            "y": rng.rand(batch, 1).astype(dtype)}


# -- registry --------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = monitor.MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2)
    assert c.value == 3
    c.labels(path="run").inc(5)
    assert c.labels(path="run").value == 5
    assert c.value == 3  # empty-label child is separate
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(7)
    g.dec(2)
    assert g.value == 5

    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.labels().snapshot()
    assert snap["count"] == 3
    assert snap["min"] == 0.05 and snap["max"] == 5.0
    assert snap["buckets"]["0.1"] == 1       # cumulative: <=0.1
    assert snap["buckets"]["1.0"] == 2       # <=1.0
    assert snap["buckets"]["+Inf"] == 3

    with pytest.raises(TypeError):
        reg.gauge("c_total")  # kind conflict


def test_registry_exporters_json_and_prometheus():
    reg = monitor.MetricsRegistry()
    reg.counter("x_total", "help text").labels(kind="a").inc(2)
    reg.histogram("t_seconds", buckets=(1.0,)).observe(0.5)
    d = json.loads(reg.to_json())  # round-trips through JSON
    assert d["x_total"]["kind"] == "counter"
    assert d["x_total"]["values"][0] == {"labels": {"kind": "a"},
                                         "value": 2}
    text = reg.to_prometheus()
    assert '# TYPE x_total counter' in text
    assert 'x_total{kind="a"} 2' in text
    assert 't_seconds_bucket{le="1.0"} 1' in text
    assert 't_seconds_count 1' in text


# -- executor instrumentation ---------------------------------------------

def test_two_run_repeat_reports_one_compile_one_hit():
    """Acceptance bar: a two-exe.run repeat of the same program shows
    exactly 1 compile + 1 cache hit in the metrics JSON."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed()
    with fluid.scope_guard(scope):
        exe.run(startup)
        monitor.reset()  # measurement window: just the two main runs
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
    snap = json.loads(json.dumps(monitor.snapshot(), default=str))
    lookups = {tuple(sorted(v["labels"].items())): v["value"]
               for v in snap["metrics"]
               ["executor_cache_lookups_total"]["values"]}
    assert lookups[(("path", "run"), ("result", "miss"))] == 1
    assert lookups[(("path", "run"), ("result", "hit"))] == 1
    compiles = snap["metrics"]["executor_compiles_total"]["values"]
    assert [v["value"] for v in compiles
            if v["labels"] == {"path": "run"}] == [1]
    assert snap["recompiles_total"] == 0
    # compile stage breakdown was measured (trace+lower / xla compile)
    stages = {tuple(v["labels"].items()): v["value"]
              for v in snap["metrics"]
              ["executor_compile_seconds"]["values"]}
    assert stages[(("stage", "trace_lower"),)]["count"] == 1
    assert stages[(("stage", "xla_compile"),)]["count"] == 1
    assert stages[(("stage", "xla_compile"),)]["sum"] > 0


def test_recompile_diagnostic_names_feed_signature_and_build_site():
    """Acceptance bar: changing the feed shape/dtype triggers a diagnostic
    naming the changed cache-key component and the program build site."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()   # build site recorded from THIS file
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(batch=8), fetch_list=[loss])
        assert monitor.recompile_count() == 0
        exe.run(main, feed=_feed(batch=16), fetch_list=[loss])   # shape
        exe.run(main, feed=_feed(batch=16, dtype=np.float64),
                fetch_list=[loss])                               # dtype
    evs = monitor.recompile_events()
    assert len(evs) == 2
    for ev in evs:
        assert ev.changed == ("feed_signature",)
        assert "test_monitor.py" in ev.build_site
    assert "(8, 4)" in evs[0].detail and "(16, 4)" in evs[0].detail
    assert "float64" in evs[1].detail
    assert monitor.recompile_count() == 2


def test_recompile_diagnostic_names_fetch_list_and_scope():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
        pred = main.global_block.ops  # noqa: F841  (site anchor)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = _feed()
    s1, s2 = fluid.Scope(), fluid.Scope()
    for s in (s1, s2):
        with fluid.scope_guard(s):
            exe.run(startup)
    with fluid.scope_guard(s1):
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[])       # fetch list changed
    ev = monitor.recompile_events()[-1]
    assert "fetch_list" in ev.changed
    with fluid.scope_guard(s2):
        exe.run(main, feed=feed, fetch_list=[])       # scope changed
    ev = monitor.recompile_events()[-1]
    assert "scope" in ev.changed


@pytest.mark.parametrize("path", ["run", "chained", "parallel"])
def test_xla_options_reach_the_compile_and_its_key(path):
    """FLAGS_xla_options on each dispatch path (Executor.run, run_chained,
    CompiledProgram): the options are handed to the compiler and sit in
    the step-cache key, an explicit '{}' is the same key as no options,
    and a malformed value raises before anything compiles."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    target = (fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name) if path == "parallel" else main)

    def dispatch():
        with fluid.scope_guard(scope):
            if path == "chained":
                return exe.run_chained(main, feed=_feed(),
                                       fetch_list=[loss], steps=2)
            return exe.run(target, feed=_feed(), fetch_list=[loss])

    def compiles():
        return len(monitor.recompile_events(recompiles_only=False))

    prev = fluid.get_flags(["FLAGS_xla_options"])
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
        monitor.reset()
        dispatch()
        assert compiles() == 1
        fluid.set_flags({"FLAGS_xla_options": "{}"})
        dispatch()
        assert compiles() == 1                       # same key: a hit
        fluid.set_flags(
            {"FLAGS_xla_options": "xla_cpu_enable_fast_min_max=true"})
        assert np.isfinite(np.asarray(dispatch()[0])).all()
        assert compiles() == 2
        assert monitor.recompile_events()[-1].changed == ("xla_options",)
        # an option the compiler does not know is refused by the compiler
        fluid.set_flags({"FLAGS_xla_options": "no_such_xla_option=1"})
        with pytest.raises(Exception, match="no_such_xla_option"):
            dispatch()
        fluid.set_flags({"FLAGS_xla_options": "not-a-pair"})
        with pytest.raises(ValueError, match="is not k=v"):
            dispatch()
    finally:
        fluid.set_flags(prev)


def test_recompile_warns_after_threshold(caplog):
    fluid.set_flags({"FLAGS_recompile_warn_threshold": 2})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_train()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.monitor"):
            with fluid.scope_guard(scope):
                exe.run(startup)
                for i in range(3):  # compile + 2 recompiles = threshold
                    exe.run(main, feed=_feed(batch=8 * (i + 1)),
                            fetch_list=[loss])
        warned = [r for r in caplog.records
                  if "recompiled 2 times" in r.message]
        assert len(warned) == 1
        assert "feed_signature" in warned[0].message
    finally:
        fluid.set_flags({"FLAGS_recompile_warn_threshold": 3})


def test_log_compiles_flag_logs_every_compile(caplog):
    fluid.set_flags({"FLAGS_log_compiles": 1})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_train()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with caplog.at_level(logging.INFO, logger="paddle_tpu.monitor"):
            with fluid.scope_guard(scope):
                exe.run(startup)
                exe.run(main, feed=_feed(), fetch_list=[loss])
                exe.run(main, feed=_feed(batch=4), fetch_list=[loss])
        msgs = [r.message for r in caplog.records]
        assert any("compiling program" in m for m in msgs)
        assert any("cache-key changed in feed_signature" in m for m in msgs)
    finally:
        fluid.set_flags({"FLAGS_log_compiles": 0})


def test_monitor_flag_disables_collection():
    fluid.set_flags({"FLAGS_monitor": 0})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_train()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_feed(), fetch_list=[loss])
    finally:
        fluid.set_flags({"FLAGS_monitor": 1})
    assert monitor.metric_value("executor_steps_total", default=None,
                                path="run") is None
    assert monitor.recompile_events(recompiles_only=False) == []


# -- hooks -----------------------------------------------------------------

def test_hooks_observe_steps_and_compiles():
    begins, ends, compiles = [], [], []
    hook = monitor.add_hook(on_step_begin=begins.append,
                            on_step_end=ends.append,
                            on_compile=compiles.append)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
    assert len(begins) == 3 and len(ends) == 3  # startup + 2 main runs
    run_ends = [e for e in ends if e.program_serial == main._serial]
    assert [e.cache_hit for e in run_ends] == [False, True]
    assert all(e.duration_s > 0 for e in run_ends)
    assert run_ends[0].feed_bytes == 8 * 4 * 4 + 8 * 4  # x f32 + y f32
    assert run_ends[0].fetch_bytes == 4                 # scalar f32 loss
    assert run_ends[0].donated_buffers > 0
    comp = [c for c in compiles if c.program_serial == main._serial]
    assert len(comp) == 1
    assert comp[0].trace_lower_s > 0 and comp[0].compile_s > 0
    n_before = len(ends)
    monitor.remove_hook(hook)
    with fluid.scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss])
    assert len(ends) == n_before  # unsubscribed


def test_step_end_fires_even_when_the_step_raises():
    """Review finding: a step that raises (FLAGS_check_nan_inf) must still
    pair step_begin with step_end — hooks tracking in-flight steps would
    otherwise desync and failed dispatches would vanish from the metrics."""
    begins, ends = [], []
    monitor.add_hook(on_step_begin=begins.append, on_step_end=ends.append)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        out = fluid.layers.mean(fluid.layers.log(x))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    fluid.set_flags({"FLAGS_check_nan_inf": 1})
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            with pytest.raises(FloatingPointError):
                exe.run(main, feed={"x": -np.ones((2, 4), np.float32)},
                        fetch_list=[out.name])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": 0})
    assert len(begins) == len(ends) == 2  # startup + the failing step
    assert monitor.metric_value("executor_steps_total", path="run") == 2


def test_raising_hook_does_not_break_execution():
    def bad_hook(rec):
        raise RuntimeError("observer crashed")

    monitor.add_hook(on_step_end=bad_hook)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (v,) = exe.run(main, feed=_feed(), fetch_list=[loss])
    assert np.isfinite(np.asarray(v)).all()


# -- donation stats on the run_chained kept-state fixture (PR 2) -----------

def test_chained_donation_stats_kept_vs_donated():
    """The fetched-param fixture: liveness refuses donation for the param
    (kept, threads the carry) while the rest of the state donates — the
    monitor must report both sides, plus per-dispatch iteration counts."""
    ends = []
    monitor.add_hook(on_step_end=ends.append)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
        param = next(v.name for v in main.global_block.vars.values()
                     if type(v).__name__ == "Parameter"
                     and v.name.endswith(".w_0"))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_chained(main, feed=_feed(), fetch_list=[loss, param],
                        steps=3)
    step = next(s for k, s in exe._cache.items() if k[0] == "chained")
    rec = next(e for e in ends if e.path == "chained")
    assert rec.iterations == 3
    assert rec.cache_hit is False
    assert rec.donated_buffers == len(step.donated_names) > 0
    assert rec.kept_buffers == len(step.kept_names) >= 1
    assert rec.donated_bytes > 0
    assert monitor.metric_value("executor_chained_iterations_total") == 3
    assert monitor.metric_value("executor_kept_buffers_total") >= 1


def test_aot_step_never_mutates_host_numpy_state():
    """The AOT fast path donates its state args; a host numpy param the
    user planted with scope.set_var must be copied, never zero-copy
    aliased — donating an aliased buffer would let XLA write the step
    output INTO the user's array (surfaced as an alignment-dependent
    test_pipeline failure). jit dispatch skips donation for numpy args;
    _own_donated restores that guarantee for the AOT executable."""
    import jax

    from paddle_tpu.executor import _own_donated

    w = np.ones((64, 64), np.float32)
    (owned,) = _own_donated([w])
    assert isinstance(owned, jax.Array)
    w[:] = 7  # mutating the host array must not reach the owned copy
    assert float(np.asarray(owned)[0, 0]) == 1.0

    # end-to-end: plant numpy params, train twice, host arrays stay intact
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _build_train()
    param = next(v.name for v in main.global_block.vars.values()
                 if type(v).__name__ == "Parameter")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.find_var(param)).copy()
        planted = w0.copy()
        scope.set_var(param, planted)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
        chained_planted = np.asarray(scope.find_var(param)).copy()
        scope.set_var(param, chained_planted)
        before = chained_planted.copy()
        exe.run_chained(main, feed=feed, fetch_list=[loss], steps=2)
    np.testing.assert_array_equal(planted, w0)
    np.testing.assert_array_equal(chained_planted, before)


# -- tools/metrics_report.py gate -----------------------------------------

def test_metrics_report_check_passes_and_writes_artifact(tmp_path):
    import tools.metrics_report as mr

    out = tmp_path / "metrics.json"
    assert mr.main(["--check", "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    by_name = {s["name"]: s for s in data["scenarios"]}
    # acceptance: the repeat scenario shows exactly 1 compile + 1 hit
    assert by_name["run_repeat"]["metrics"]["run_compiles"] == 1
    assert by_name["run_repeat"]["metrics"]["run_hits"] == 1
    assert data["check"]["status"] == "ok"
    assert data["snapshot"]["recompiles_total"] == 0


def test_metrics_report_check_fails_on_forced_recompiles(tmp_path):
    import tools.metrics_report as mr

    out = tmp_path / "metrics_forced.json"
    rc = mr.main(["--check", "--force-recompile", "2", "--json", str(out)])
    assert rc != 0
    data = json.loads(out.read_text())
    forced = next(s for s in data["scenarios"] if s.get("forced"))
    assert forced["metrics"]["recompiles"] == 2
    assert "feed_signature" in str(forced["diagnostic"])
    assert data["check"]["status"] == "fail"


# ---------------------------------------------------------------------------
# histogram quantile estimation (serving SLOs: p50/p99)
# ---------------------------------------------------------------------------

def test_histogram_quantiles_interpolate_within_buckets():
    from paddle_tpu.monitor.registry import Histogram
    import threading

    h = Histogram(threading.RLock(), buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 7.0):
        h.observe(v)
    p50 = h.quantile(0.5)
    assert 1.0 <= p50 <= 2.0, f"median of (0.5,1.5,1.5,3,7) ~ bucket (1,2], got {p50}"
    p99 = h.quantile(0.99)
    assert 4.0 <= p99 <= 7.0, "p99 lands in (4,8] but clamps to max=7"
    # clamping: a single observation pins every quantile to itself
    h1 = Histogram(threading.RLock(), buckets=(1.0, 2.0))
    h1.observe(1.7)
    assert h1.quantile(0.5) == h1.quantile(0.99) == 1.7


def test_histogram_quantiles_empty_and_overflow():
    from paddle_tpu.monitor.registry import Histogram
    import threading

    h = Histogram(threading.RLock(), buckets=(1.0,))
    assert h.quantile(0.5) is None
    with pytest.raises(ValueError):
        h.observe(1.0) or h.quantile(0.0)
    # +Inf bucket ranks report the observed max, not an invented bound
    h.observe(100.0)
    assert h.quantile(0.99) == 100.0


def test_histogram_snapshot_carries_p50_p99():
    monitor.reset()
    fam = monitor.histogram("unit_latency_seconds", "t")
    for v in (0.01, 0.02, 0.03, 0.04):
        fam.observe(v)
    snap = monitor.metric_value("unit_latency_seconds")
    assert snap["count"] == 4 and snap["p50"] is not None
    assert 0.01 <= snap["p50"] <= 0.03
    assert snap["p50"] <= snap["p99"] <= 0.04


def test_histogram_prometheus_exposition_conventions():
    """_bucket/_sum/_count lines, cumulative le counts ending at +Inf —
    what a Prometheus scraper of the serving sidecar expects."""
    monitor.reset()
    fam = monitor.histogram("unit_hist_seconds", "t")
    fam.labels(path="run").observe(0.002)
    fam.labels(path="run").observe(0.2)
    text = monitor.get_registry().to_prometheus()
    lines = [ln for ln in text.splitlines() if ln.startswith("unit_hist")]
    buckets = [ln for ln in lines if "_bucket" in ln]
    assert buckets and 'le="+Inf"' in buckets[-1]
    assert buckets[-1].endswith(" 2"), "+Inf bucket holds the total count"
    # cumulative: counts never decrease across the ordered buckets
    counts = [int(float(ln.rsplit(" ", 1)[1])) for ln in buckets]
    assert counts == sorted(counts)
    assert any(ln.startswith("unit_hist_seconds_sum") for ln in lines)
    assert any(ln.startswith("unit_hist_seconds_count") for ln in lines)
    assert "# TYPE unit_hist_seconds histogram" in text
