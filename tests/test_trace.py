"""paddle_tpu.trace: span model, cross-thread context propagation,
serving/trainer wiring, flight-recorder incidents, the cost-model pass
and its monitor MFU gauges, and the disabled-path overhead contract.
CI end-to-end proof: tools/trace_check.py (docs/OBSERVABILITY.md)."""
import os
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving, trace
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.resilience import fault_plan_guard


@pytest.fixture(autouse=True)
def _trace_isolation():
    """Tracing is process-global (flag + collector): every test starts
    disabled with an empty collector and leaves it that way."""
    fluid.set_flags({"FLAGS_trace": 0, "FLAGS_flight_recorder_size": 256})
    trace.get_collector().reset()
    yield
    fluid.set_flags({"FLAGS_trace": 0, "FLAGS_flight_recorder_size": 256})
    trace.get_collector().reset()


def _traced():
    fluid.set_flags({"FLAGS_trace": 1})


def _mlp():
    with un.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", shape=[6], dtype="float32")
            y = layers.fc(x, size=3)
    return main, startup, y


def _engine(**cfg):
    main, startup, y = _mlp()
    infer = main.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
    eng = serving.ServingEngine(
        infer, feed_names=["x"], fetch_list=[y.name], scope=scope,
        executor=exe,
        config=serving.ServingConfig(
            **{"max_batch": 4, "queue_depth": 32, **cfg}))
    return eng


def _feed(rows=1, seed=0):
    return {"x": np.random.RandomState(seed).rand(rows, 6)
            .astype(np.float32)}


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------

def test_span_parentage_ids_and_status():
    _traced()
    with trace.root_span("root", kind="test") as root:
        with trace.span("child") as child:
            child.set_attribute("k", 1)
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
    assert root.duration_s is not None and root.status == "ok"
    tree = trace.trace_tree(root.trace_id)
    assert [s.name for s in tree] == ["root", "child"]
    # error status + message captured on an exception exit
    with pytest.raises(ValueError):
        with trace.span("boom") as sp:
            raise ValueError("nope")
    assert sp.status == "error" and "ValueError" in sp.error


def test_root_span_ignores_ambient():
    _traced()
    with trace.span("outer") as outer:
        r = trace.root_span("fresh")
        assert r.trace_id != outer.trace_id and r.parent_id is None
        r.end()


def test_span_end_is_idempotent():
    _traced()
    sp = trace.start_span("once", parent=False)
    sp.end()
    d = sp.duration_s
    sp.end(error=RuntimeError("late"))
    assert sp.duration_s == d and sp.status == "ok"
    assert sum(1 for s in trace.spans() if s.span_id == sp.span_id) == 1


def test_disabled_is_noop_singleton_no_collection():
    assert not trace.enabled()
    spans = [trace.span("a"), trace.root_span("b"),
             trace.start_span("c")]
    assert all(s is trace.NOOP_SPAN for s in spans)
    with trace.span("d") as sp:
        sp.set_attribute("x", 1)
    assert trace.spans() == []
    # flag flips through set_flags are observed (epoch-cached read)
    _traced()
    assert trace.enabled()
    fluid.set_flags({"FLAGS_trace": 0})
    assert not trace.enabled()


def test_cross_thread_attach_parentage():
    _traced()
    root = trace.start_span("request", parent=False)
    seen = {}

    def worker():
        with trace.attach(root):
            with trace.span("dispatch") as d:
                seen["trace"] = d.trace_id
                seen["parent"] = d.parent_id
                seen["thread"] = d.thread
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.end()
    assert seen["trace"] == root.trace_id
    assert seen["parent"] == root.span_id
    assert seen["thread"] != root.thread


def test_exporters_chrome_and_jsonl(tmp_path):
    _traced()
    with trace.root_span("a"):
        with trace.span("b"):
            pass
    chrome = tmp_path / "t.json"
    jl = tmp_path / "t.jsonl"
    assert trace.export_chrome(str(chrome)) == 2
    assert trace.export_jsonl(str(jl)) == 2
    import json

    evs = json.load(open(chrome))["traceEvents"]
    assert all(e["ph"] == "X" and e["cat"] == "trace" for e in evs)
    assert all("trace_id" in e["args"] for e in evs)
    # epoch-anchored timestamps (merge contract with the profiler dump)
    assert all(e["ts"] > 1e15 for e in evs)   # µs since epoch


def test_timeline_merges_trace_and_profiler(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import timeline

    _traced()
    with trace.root_span("span_side"):
        pass
    jl = tmp_path / "spans.jsonl"
    trace.export_jsonl(str(jl))
    # a profiler host dump with the epoch anchor
    import json
    import time

    (tmp_path / "host_events.json").write_text(json.dumps(
        [{"name": "prof_side", "t0": 1.0, "t1": 1.5, "tid": 0,
          "epoch": time.time()}]))
    out = tmp_path / "merged.json"
    assert timeline.convert(str(tmp_path), str(out),
                            trace_path=str(jl)) == 0
    evs = json.load(open(out))["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {0, 1}
    # both sides on the epoch clock: within minutes of each other
    ts = sorted(e["ts"] for e in evs)
    assert ts[-1] - ts[0] < 300e6


# ---------------------------------------------------------------------------
# serving wiring
# ---------------------------------------------------------------------------

def test_serving_request_chain_cross_thread():
    _traced()
    eng = _engine()
    with eng:
        fut = eng.submit(_feed())
        fut.result(timeout=60)
    assert fut.trace_id
    tree = trace.trace_tree(fut.trace_id)
    names = [s.name for s in tree]
    assert names[0] == "serving.request"
    assert {"serving.submit", "serving.enqueue",
            "serving.dispatch"} <= set(names)
    root = tree[0]
    assert root.attrs["outcome"] == "completed"
    assert root.parent_id is None and root.duration_s is not None
    # submit-thread -> dispatch-thread propagation
    disp = next(s for s in tree if s.name == "serving.dispatch")
    assert disp.thread != root.thread
    assert disp.parent_id == root.span_id
    # the batch span links back to this request's trace
    batches = [s for s in trace.spans() if s.name == "serving.batch"]
    assert any(fut.trace_id in b.attrs.get("request_traces", "")
               for b in batches)
    # root closes after every child
    for s in tree[1:]:
        assert (root.t0_mono + root.duration_s) + 1e-6 >= \
            (s.t0_mono + s.duration_s)


def test_serving_typed_outcomes_carry_trace_ids():
    _traced()
    eng = _engine()
    # not started: typed EngineStopped at submit still ships a trace id
    with pytest.raises(serving.EngineStopped) as ei:
        eng.submit(_feed())
    assert ei.value.trace_id
    tree = trace.trace_tree(ei.value.trace_id)
    assert tree and tree[0].attrs["outcome"] == "rejected_stopped"
    acct = eng.accounting()
    assert acct["recent_outcomes"][-1]["trace_id"] == ei.value.trace_id
    assert acct["recent_outcomes"][-1]["outcome"] == "rejected_stopped"


def test_batch_failure_flight_recorder_dump():
    _traced()
    trace.clear_incidents()
    eng = _engine()
    with eng, fault_plan_guard("batch_dispatch:1:RuntimeError"):
        fut = eng.submit(_feed())
        with pytest.raises(serving.BatchFailed) as ei:
            fut.result(timeout=60)
    assert ei.value.trace_id == fut.trace_id
    incs = [i for i in trace.incidents() if i["kind"] == "batch_failed"]
    assert incs, "BatchFailed must dump the flight recorder"
    chain = {d["name"] for d in incs[-1]["recent_spans"]
             if d["trace_id"] == fut.trace_id}
    assert {"serving.request", "serving.submit", "serving.enqueue",
            "serving.dispatch"} <= chain
    req = next(d for d in incs[-1]["recent_spans"]
               if d["trace_id"] == fut.trace_id
               and d["name"] == "serving.request")
    assert req["attrs"]["outcome"] == "failed"
    assert req["status"] == "error"


def test_flight_recorder_disabled_loses_context():
    _traced()
    fluid.set_flags({"FLAGS_flight_recorder_size": 0})
    trace.get_collector().reset()   # re-derive ring sizing from flags
    trace.clear_incidents()
    eng = _engine()
    with eng, fault_plan_guard("batch_dispatch:1:RuntimeError"):
        fut = eng.submit(_feed())
        with pytest.raises(serving.BatchFailed):
            fut.result(timeout=60)
    incs = [i for i in trace.incidents() if i["kind"] == "batch_failed"]
    assert incs
    assert not incs[-1]["flight_recorder_enabled"]
    assert incs[-1]["recent_spans"] == []   # the negative control


def test_watchdog_hang_dumps_flight_recorder():
    _traced()
    trace.clear_incidents()
    eng = _engine()
    fluid.set_flags({"FLAGS_step_timeout_s": 2.0,
                     "FLAGS_watchdog_hard_exit": 0})
    try:
        with eng, fault_plan_guard("hang:@1:hang"):
            fut = eng.submit(_feed())
            with pytest.raises(serving.BatchFailed) as ei:
                fut.result(timeout=60)
    finally:
        fluid.set_flags({"FLAGS_step_timeout_s": 0.0,
                         "FLAGS_watchdog_hard_exit": 1})
    from paddle_tpu.resilience.distributed import WatchdogTimeout

    assert isinstance(ei.value.__cause__, WatchdogTimeout)
    incs = [i for i in trace.incidents()
            if i["kind"] == "watchdog_timeout"]
    assert incs, "watchdog expiry must dump the flight recorder"
    # the hung request's submit-side chain is in the expiry dump
    chain = {d["name"] for d in incs[-1]["recent_spans"]
             if d["trace_id"] == fut.trace_id}
    assert {"serving.submit", "serving.enqueue"} <= chain


# ---------------------------------------------------------------------------
# trainer wiring
# ---------------------------------------------------------------------------

def test_trainer_step_traces(tmp_path):
    _traced()

    def train_func():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(x, 1)
        return layers.mean(layers.square_error_cost(pred, y))

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(2):
            yield [(rng.rand(4).astype(np.float32),
                    rng.rand(1).astype(np.float32)) for _ in range(4)]

    ckpt = fluid.contrib.CheckpointConfig(str(tmp_path / "ck"),
                                          step_interval=2)
    with un.guard():
        tr = fluid.contrib.Trainer(train_func,
                                   lambda: fluid.optimizer.SGD(0.1),
                                   checkpoint_config=ckpt)
        tr.train(num_epochs=1, event_handler=lambda ev: None,
                 reader=lambda: reader(), feed_order=["x", "y"])
    roots = [s for s in trace.spans()
             if s.name == "trainer.step" and s.parent_id is None]
    assert len(roots) == 2
    for r in roots:
        assert r.attrs["outcome"] in ("ok", "graceful_exit")
        names = {s.name for s in trace.trace_tree(r.trace_id)}
        assert "trainer.data" in names and "executor.run" in names
    # the step_interval=2 save landed as a checkpoint child of step 2
    all_names = [s.name for s in trace.spans()]
    assert "trainer.checkpoint" in all_names


def test_trainer_post_dispatch_failure_not_labeled_ok(tmp_path):
    """A failure AFTER the dispatch (event handler, checkpoint write)
    must close the step trace with the error, never 'ok' — the flight
    recorder consulted for that incident would lie otherwise."""
    _traced()

    def train_func():
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(x, 1)
        return layers.mean(layers.square_error_cost(pred, y))

    def reader():
        rng = np.random.RandomState(0)
        yield [(rng.rand(4).astype(np.float32),
                rng.rand(1).astype(np.float32))]

    def handler(ev):
        if isinstance(ev, fluid.contrib.EndStepEvent):
            raise IOError("post-dispatch boom")

    with un.guard():
        tr = fluid.contrib.Trainer(train_func,
                                   lambda: fluid.optimizer.SGD(0.1))
        with pytest.raises(IOError):
            tr.train(num_epochs=1, event_handler=handler,
                     reader=lambda: reader(), feed_order=["x", "y"])
    root = next(s for s in trace.spans() if s.name == "trainer.step")
    assert root.status == "error"
    assert root.attrs["outcome"] == "OSError"
    assert "post-dispatch boom" in root.error


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_model_exact_small_program():
    from paddle_tpu.analysis.cost_model import estimate_cost

    with un.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", shape=[8], dtype="float32")
            y = layers.fc(x, size=3, bias_attr=False)  # mul only
    rep = estimate_cost(main, batch_size=4)
    # one mul: 2 * M(4) * K(8) * N(3) = 192 FLOPs
    assert rep.flops_by_op_type["mul"] == 192.0
    assert rep.flops_forward == rep.flops_total
    assert rep.param_bytes == 8 * 3 * 4
    assert rep.batch_size == 4 and rep.flops_per_byte > 0


def test_cost_model_conv_and_grads():
    from paddle_tpu.analysis.cost_model import estimate_cost

    with un.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            img = layers.data("img", shape=[3, 8, 8], dtype="float32")
            c = layers.conv2d(img, 4, 3, padding=1, bias_attr=False)
            loss = layers.mean(c)
        fluid.optimizer.SGD(0.1).minimize(loss)
    rep = estimate_cost(main, batch_size=2)
    # conv2d fwd: 2 * out(2*4*8*8) * (3*3*3) = 27648
    assert rep.flops_by_op_type["conv2d"] == 2 * (2 * 4 * 8 * 8) * 27
    # grad = exactly 2x forward for the matmul class
    assert rep.flops_by_op_type["conv2d_grad"] == \
        2 * rep.flops_by_op_type["conv2d"]
    assert rep.flops_backward > 0 and rep.flops_optimizer > 0


def test_cost_model_registered_as_pass():
    from paddle_tpu.analysis import CostReport
    from paddle_tpu.analysis.pass_manager import default_pass_manager

    with un.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", shape=[8], dtype="float32")
            y = layers.fc(x, size=3)
    res = default_pass_manager().run_pipeline(
        main, ["cost_model"], fetch_names=[y.name], batch_size=16,
        verify="none")
    rep = res.values["cost_model"]
    assert isinstance(rep, CostReport)
    assert rep.batch_size == 16 and rep.flops_total > 0
    assert res.diagnostics == []   # cost is information, not findings


def test_mfu_gauges_from_executor_and_serving():
    monitor.reset()
    main, startup, y = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((4, 6), np.float32)},
                fetch_list=[y.name])
    labels = dict(path="run", program=str(main._serial), batch="4")
    # the CPU is not in the peaks table (analysis.cost_model.DEVICE_PEAKS):
    # FLOPs and achieved TF/s are counted, but NO utilisation is claimed
    assert monitor.metric_value("executor_mfu", None, **labels) is None
    assert monitor.metric_value("executor_achieved_tflops", 0.0,
                                **labels) > 0
    assert monitor.metric_value("executor_model_gflops_per_step", 0.0,
                                program=str(main._serial),
                                batch="4") > 0
    # a device the table lists gets the gauge, against ITS peak
    monitor.observe_step_cost(main, 4, 1e-3, device_kind="TPU v5 lite")
    g = monitor.metric_value("executor_mfu", None, **labels)
    assert g is not None and 0 <= g < 1
    # serving bucket gauges
    eng = _engine()
    with eng:
        eng.submit(_feed()).result(timeout=60)
    snap = monitor.get_registry().to_dict()
    assert "serving_bucket_mfu" not in snap
    assert "serving_bucket_achieved_tflops" in snap


def test_resnet18_cost_ratio_against_analytic():
    """The 2-FLOPs/MAC convention against a hand-derived per-layer count
    for the CIFAR ResNet-18 probe (full ResNet-50/BERT-base checks run
    in tools/trace_check.py)."""
    from paddle_tpu.analysis.cost_model import estimate_cost
    from paddle_tpu.models.resnet import build_resnet

    with un.guard():
        net = build_resnet(depth=18, class_num=10,
                           image_shape=(3, 32, 32),
                           build_optimizer=False)
    infer = net["main"].clone(for_test=True)
    rep = estimate_cost(infer, batch_size=1)
    # dominant conv sum, hand-derived (2/MAC): ~70.8 MF for this stack
    assert 0.5e8 < rep.flops_total < 1.5e8
    conv = rep.flops_by_op_type["conv2d"]
    assert conv / rep.flops_total > 0.9


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------

def test_disabled_span_no_allocation():
    assert not trace.enabled()
    a = trace.span("hot")
    b = trace.span("hot")
    assert a is b is trace.NOOP_SPAN   # identity: zero allocation
    # record_incident with tracing off still returns a (context-free)
    # incident record and never raises
    inc = trace.record_incident("unit_test", detail="off")
    assert inc["recent_spans"] == []
