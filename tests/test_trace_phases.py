"""Phase spans and histograms (docs/OBSERVABILITY.md "Phase spans"): the
executor's call and the generative dispatch thread's loop are tiled by
named phases, each timed once for a span (``FLAGS_trace``) and a monitor
histogram (``FLAGS_monitor``); the Pallas kernels and the Fluid ops keep
names of the program's choosing in what JAX lowers."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving, trace
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.kernels import flash_attention, flash_attention_decode
from paddle_tpu.models.gpt import GptConfig, build_gpt_generative
from slow_device import slow_device

EXECUTOR_PHASES = {"executor.bind", "executor.feed", "executor.step",
                   "executor.fetch", "executor.writeback"}
LOOP_PHASES = ("idle_wait", "await_newcomers", "schedule", "admit", "feed",
               "settle", "publish")
# the leaves that tile the dispatch thread; spans nested deeper
# (retry.device_put, executor.compile) are detail inside one of them
LEAVES = EXECUTOR_PHASES | {"serving." + p for p in LOOP_PHASES}


@pytest.fixture(autouse=True)
def _trace_isolation():
    fluid.set_flags({"FLAGS_trace": 0})
    trace.get_collector().reset()
    yield
    fluid.set_flags({"FLAGS_trace": 0})
    trace.get_collector().reset()


def _overlaps(spans):
    """Pairs of consecutive spans (by start) of which the second starts
    before the first ends."""
    spans = sorted(spans, key=lambda s: s.t0_mono)
    return [(a.name, b.name) for a, b in zip(spans, spans[1:])
            if b.t0_mono < a.t0_mono + a.duration_s - 1e-9]


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mlp():
    """Wide enough that a step takes milliseconds on the CPU: the phases'
    own bookkeeping (microseconds) must not decide a coverage."""
    with un.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", shape=[256], dtype="float32")
            y = layers.data("y", shape=[1], dtype="float32")
            h = x
            for _ in range(4):
                h = layers.fc(h, size=256, act="relu")
            loss = layers.mean(layers.square_error_cost(layers.fc(h, 1), y))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(5)
    feed = {"x": rng.rand(512, 256).astype(np.float32),
            "y": rng.rand(512, 1).astype(np.float32)}
    return main, loss, exe, scope, feed


def _call(mlp, path):
    main, loss, exe, scope, feed = mlp
    if path == "run":
        return exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    return exe.run_chained(main, feed=feed, fetch_list=[loss], steps=2,
                           scope=scope)


@pytest.mark.parametrize("path,parent", [("run", "executor.run"),
                                         ("chained", "executor.run_chained")])
def test_executor_phases_tile_the_call(mlp, path, parent):
    _call(mlp, path)                                  # build the executable
    fluid.set_flags({"FLAGS_trace": 1})
    trace.clear()
    _call(mlp, path)
    spans = trace.spans()
    (root,) = [s for s in spans if s.name == parent]
    kids = [s for s in spans if s.parent_id == root.span_id]
    assert {s.name for s in kids} == EXECUTOR_PHASES
    assert _overlaps(kids) == []
    assert sum(s.duration_s for s in kids) >= 0.95 * root.duration_s
    by_name = {s.name: s for s in kids}
    assert by_name["executor.bind"].attrs["cache_hit"] is True
    assert by_name["executor.step"].attrs["cache_hit"] is True
    assert by_name["executor.feed"].attrs["bytes"] == 512 * 257 * 4
    assert by_name["executor.fetch"].attrs["bytes"] > 0
    assert root.attrs["program"] >= 0


@pytest.mark.parametrize("path", ["run", "chained"])
def test_step_seconds_is_host_plus_fetch_wait(mlp, path):
    monitor.reset()
    for _ in range(3):
        _call(mlp, path)
    step, host, wait = (monitor.metric_value(n, path=path) for n in (
        "executor_step_seconds", "executor_host_seconds",
        "executor_fetch_wait_seconds"))
    assert step["count"] == host["count"] == wait["count"] == 3
    assert wait["sum"] > 0 and host["sum"] > 0
    assert host["sum"] + wait["sum"] == pytest.approx(step["sum"], rel=1e-9)


# ---------------------------------------------------------------------------
# the generative dispatch thread
# ---------------------------------------------------------------------------

def _generate(traced: bool, idle_s: float = 0.0):
    """A tiny engine through warm-up, an idle start, two waves of requests
    (the second re-sends a prompt, so admission hits published pages, and
    its turns without newcomers run a chunk ahead) and an idle end. Returns
    the spans it recorded."""
    with un.guard():
        # a prefill row a slot: one prefill dispatch a bucket and wave, so
        # the thread's time is in the phases and not between many of them
        net = build_gpt_generative(GptConfig.tiny(), batch_slots=4,
                                   max_seq=64, page_size=8,
                                   prompt_buckets=(16, 32), prefill_rows=4)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=4, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=2))
    eng.warm_up()
    # a decode chunk that takes 20 ms, as a device's would: the thread
    # then has a chunk in flight to wait for newcomers under
    slow_device(exe, 0.02)
    fluid.set_flags({"FLAGS_trace": int(traced)})
    trace.clear()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, 9 + 3 * i) for i in range(6)]
    with eng:
        time.sleep(idle_s)
        # the second wave's answers are longer: turns without newcomers,
        # launched a chunk ahead, with two slots free and no queue
        for k, wave in enumerate((prompts, prompts[:2])):
            futs = [eng.submit(p, max_new_tokens=(2 + i % 4) * (1 + 2 * k))
                    for i, p in enumerate(wave)]
            for f in futs:
                f.result(timeout=120)
        time.sleep(idle_s)
    return trace.spans()


@pytest.fixture(scope="module")
def generation():
    monitor.reset()
    fluid.set_flags({"FLAGS_trace": 0})
    trace.get_collector().reset()
    spans = _generate(traced=True, idle_s=0.3)
    fluid.set_flags({"FLAGS_trace": 0})
    loop = {p: monitor.metric_value("serving_loop_seconds", None, phase=p)
            for p in LOOP_PHASES}
    warm = monitor.metric_value("serving_warm_up_seconds", None)
    trace.get_collector().reset()
    return spans, loop, warm


def test_dispatch_thread_is_tiled_by_leaves(generation):
    spans, _, _ = generation
    thread = next(s.thread_name for s in spans
                  if s.name == "serving.schedule")
    leaves = [s for s in spans
              if s.thread_name == thread and s.name in LEAVES]
    assert _overlaps(leaves) == []
    first = min(s.t0_mono for s in leaves)
    last = max(s.t0_mono + s.duration_s for s in leaves)
    covered = sum(s.duration_s for s in leaves)
    assert covered >= 0.99 * (last - first)
    # and not by idling alone: the busy part is mostly named too
    idle = sum(s.duration_s for s in leaves
               if s.name == "serving.idle_wait")
    assert covered - idle >= 0.8 * (last - first - idle)


def test_loop_phase_spans_carry_what_they_did(generation):
    spans, _, _ = generation
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    roots = {s.span_id: s.name for s in spans if s.name in (
        "serving.prefill", "serving.prefill_chunk", "serving.decode")}
    assert all(roots.get(s.parent_id) for s in by["serving.feed"])
    assert sum(s.attrs.get("newcomers", 0)          # none when stopping
               for s in by["serving.schedule"]) == 8
    assert sum(s.attrs["pages"] for s in by["serving.publish"]) >= 6
    assert all(s.attrs["host_bytes"] > 0 for s in by["serving.publish"]
               if s.attrs["pages"])
    assert sum(s.attrs["hits"] for s in by["serving.admit"]) == 2
    assert sum(s.attrs["rows"] for s in by["serving.admit"]) >= 16
    assert sum(s.attrs["tokens"] for s in by["serving.settle"]) == sum(
        2 + i % 4 for i in range(6)) + 6 + 9
    assert sum(s.attrs["finished"] for s in by["serving.settle"]) == 8
    # the chained dispatch has the launch span the plain one always had
    chained = {s.span_id for s in by["executor.run_chained"]}
    assert sum(s.parent_id in chained for s in by["executor.step"]) \
        == len(chained)


def test_a_chunk_launched_ahead_is_fetched_under_its_own_root(generation):
    """``serving.decode`` stays one span a dispatch, from its launch to
    its settle: its ``executor.fetch``, taken after the next chunk's
    launch, is a child of the call that launched it, and roots overlap
    where the leaves do not."""
    spans, _, _ = generation
    by_id = {s.span_id: s for s in spans}
    roots = sorted((s for s in spans if s.name == "serving.decode"),
                   key=lambda s: s.t0_mono)
    calls = {s.parent_id: s for s in spans
             if s.name == "executor.run_chained"}
    assert set(calls) == {r.span_id for r in roots}
    for r in roots:
        kids = [s for s in spans if s.parent_id == calls[r.span_id].span_id]
        assert sorted(s.name for s in kids).count("executor.fetch") == 1
        (fetch,) = [s for s in kids if s.name == "executor.fetch"]
        assert by_id[fetch.parent_id].name == "executor.run_chained"
        assert fetch.t0_mono + fetch.duration_s \
            <= r.t0_mono + r.duration_s + 1e-9
    ahead = [b for a, b in zip(roots, roots[1:])
             if b.t0_mono < a.t0_mono + a.duration_s]
    assert len(ahead) >= 2


@pytest.mark.parametrize("phase", LOOP_PHASES)
def test_every_loop_phase_is_observed(generation, phase):
    spans, loop, _ = generation
    assert loop[phase] is not None and loop[phase]["count"] > 0
    # one timing, two sinks: the histogram's sum is the spans' durations
    took = sum(s.duration_s for s in spans if s.name == "serving." + phase)
    assert loop[phase]["sum"] == pytest.approx(took, rel=1e-9)


def test_warm_up_gauge(generation):
    _, _, warm = generation
    assert warm is not None and warm > 0


def test_trace_off_records_no_span_and_computes_no_attribute(
        mlp, monkeypatch):
    calls = []
    for cls in (trace._Phase, trace._NoopPhase, trace.Span,
                trace._NoopSpan):
        for meth in ("set_attribute", "set_attributes"):
            if hasattr(cls, meth):
                monkeypatch.setattr(
                    cls, meth,
                    lambda self, *a, **k: calls.append((a, k)) or self)
    monitor.reset()
    assert _generate(traced=False) == []
    _call(mlp, "run")
    _call(mlp, "chained")
    assert trace.spans() == [] and calls == []
    assert trace.phase("x") is trace.NOOP_PHASE
    # the histograms are the always-on half
    assert monitor.metric_value("serving_loop_seconds",
                                phase="settle")["count"] > 0


# ---------------------------------------------------------------------------
# device names
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr, out=None):
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_eqns(sub, out)
    return out


def _flash(q, k, v):
    return flash_attention(q, k, v, interpret=True).sum()


_QKV = jnp.ones((2, 128, 64), jnp.float32)
KERNEL_CALLS = {
    "plain": {
        "flash_attention_fwd": lambda: jax.make_jaxpr(_flash)(_QKV, _QKV,
                                                              _QKV),
        "decode_attention": lambda: jax.make_jaxpr(
            lambda q, k, v: flash_attention_decode(
                q, k, v, np.array([5], np.int32), num_heads=2, page_size=8,
                interpret=True))(jnp.ones((2, 1, 64)), jnp.ones((2, 32, 64)),
                                 jnp.ones((2, 32, 64))),
    },
    "grad": dict.fromkeys(
        ("flash_attention_fwd", "flash_attention_bwd_dq",
         "flash_attention_bwd_dkv"),
        lambda: jax.make_jaxpr(jax.grad(_flash, argnums=(0, 1, 2)))(
            _QKV, _QKV, _QKV)),
}


@pytest.mark.parametrize("how,kernel", [
    (how, k) for how, ks in KERNEL_CALLS.items() for k in ks])
def test_pallas_kernel_carries_its_name(how, kernel):
    """``name=`` on the call, and as the INNERMOST scope of its name stack
    with the transforms wrapped around the outer ``pallas`` scope: XLA
    names the custom call after that innermost scope."""
    eqns = _pallas_eqns(KERNEL_CALLS[how][kernel]().jaxpr)
    named = [e for e in eqns if e.params["name"] == kernel]
    assert named, [e.params["name"] for e in eqns]
    for e in named:
        scopes = str(e.source_info.name_stack).split("/")
        assert scopes[-1] == kernel, scopes
        assert "pallas" in scopes[-2], scopes


@pytest.fixture(scope="module")
def lowered_text(mlp):
    main, loss, exe, scope, feed = mlp
    step = exe._compile(main, set(feed), [loss.name], scope)
    args = ([feed[n] for n in step.feed_names],
            [scope.find_var(n) for n in step.donated_names],
            [scope.find_var(n) for n in step.ro_names], jax.random.key(0))
    return step.fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("op_type", ["mul", "elementwise_add", "relu",
                                     "mean", "mul_grad", "sgd"])
def test_lowered_text_holds_fluid_op_types_as_scopes(mlp, lowered_text,
                                                     op_type):
    assert op_type in {op.type for op in mlp[0].global_block.ops}
    assert f"/{op_type}/" in lowered_text
