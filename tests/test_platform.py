"""The device a step runs on is never hidden (ISSUE 21).

* ``TPUPlace`` raises where JAX found no accelerator; entry points default
  to ``default_place()``.
* Kernel/layout routes follow the device the step is LOWERED for
  (``lowering.lowering_platform``), never the process default backend.
* The compile cache sits where ``JAX_COMPILATION_CACHE_DIR`` says, else at
  one fixed path inside the checkout.
* ``import paddle_tpu`` and building a ``Program`` initialise no backend;
  ``chip_smoke.py`` without a TPU fails, naming what it found.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor
from paddle_tpu.lowering import LowerCtx, lowering_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_place_raises_without_an_accelerator():
    with pytest.raises(RuntimeError, match="no accelerator #0.*CpuDevice"):
        fluid.TPUPlace().jax_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.CUDAPlace(0).jax_device()
    # an executor built on it fails at its first run, not on the host
    exe = fluid.Executor(fluid.TPUPlace())
    with pytest.raises(RuntimeError, match="no accelerator"):
        exe.run(fluid.Program(), scope=fluid.Scope())


def test_entry_points_share_the_executor_default_place():
    from paddle_tpu.executor import default_place

    # the suite holds JAX to the CPU, so the default place is the host...
    assert isinstance(default_place(), fluid.CPUPlace)
    assert isinstance(fluid.Executor().place, fluid.CPUPlace)
    trainer = fluid.contrib.Trainer(
        lambda: fluid.layers.mean(fluid.layers.fc(
            fluid.layers.data("x", shape=[4], dtype="float32"), 1)),
        lambda: fluid.optimizer.SGD(0.1))
    assert type(trainer.place) is type(fluid.Executor().place)


def test_default_place_is_the_accelerator_when_jax_has_one(monkeypatch):
    from paddle_tpu.executor import default_place

    # ...and wherever the default backend is an accelerator, the accelerator
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert isinstance(default_place(), fluid.TPUPlace)


def test_lowering_platform_reads_the_mesh_then_the_ctx():
    from paddle_tpu.parallel.sharding import make_mesh

    assert lowering_platform(None) is None
    assert lowering_platform(LowerCtx()) is None
    assert lowering_platform(LowerCtx(platform="tpu")) == "tpu"
    assert lowering_platform(LowerCtx(platform="tpu").with_uid(3)) == "tpu"
    # a mesh names its own devices, whatever the ctx was stamped with
    mesh = make_mesh({"dp": 2})
    assert lowering_platform(LowerCtx(platform="tpu", mesh=mesh)) == "cpu"
    assert lowering_platform(mesh=mesh) == "cpu"


def test_routes_key_on_the_lowering_platform():
    from paddle_tpu.ops.fused_attention import _route
    from paddle_tpu.ops.generation import _route_decode
    from paddle_tpu.ops.nn import _use_nhwc

    for platform, route in (("tpu", "pallas"), ("cpu", "primitive"),
                            (None, "primitive")):
        assert _route(512, 512, 0.1, platform=platform) == route
        assert _route_decode(1024, 128, q_len=8, platform=platform) == route
        assert _use_nhwc(LowerCtx(platform=platform)) == (platform == "tpu")
    # past the kernel's 8-row tile a chunk rides the primitive path
    assert _route_decode(1024, 128, q_len=128, platform="tpu") == "primitive"


def _attention_program():
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[2, 128, 8], dtype="float32")
            o = fluid.layers.fused_multihead_attention(x, x, x)
            loss = fluid.layers.mean(o)
    return main, startup, loss


def test_cpu_place_never_lowers_kernels_on_an_accelerator_host(monkeypatch):
    """The process default says 'tpu'; the executor's place says CPU. The
    step must take the primitive route (a Mosaic kernel cannot lower for
    the CPU) — and say so on ``kernel_route_total``."""
    main, startup, loss = _attention_program()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monitor.reset()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (out,) = exe.run(main, feed={"x": np.ones((2, 2, 128, 8),
                                                  np.float32)},
                         fetch_list=[loss])
    assert np.isfinite(out).all()
    routes = {(lab["op"], lab["route"]) for lab, _ in
              monitor.get_registry().get("kernel_route_total").children()
              if lab["program"] == str(main._serial)}
    assert routes == {("fused_multihead_attention", "primitive")}


def test_a_step_lowered_for_a_tpu_takes_the_kernel_in_a_cpu_process():
    """The other direction: this process defaults to the CPU, the step is
    lowered for a TPU — the Pallas kernel must be in the lowering."""
    from paddle_tpu.executor import analyze_block_io, make_step_fn

    main, _, loss = _attention_program()
    io = analyze_block_io(main.global_block, {"x"}, [loss.name])
    x = jax.ShapeDtypeStruct((2, 2, 128, 8), np.float32)
    key = jax.random.key(0)

    def lowered(platform):
        step = make_step_fn(main.global_block, io, [loss.name],
                            platform=platform)
        return jax.jit(step).trace([x], [], [], key).jaxpr

    assert "pallas_call" in str(lowered("tpu"))
    assert "pallas_call" not in str(lowered("cpu"))
    assert "pallas_call" not in str(lowered(None))


def test_compile_cache_dir_obeys_the_environment(monkeypatch, tmp_path):
    from paddle_tpu import compile_cache

    # what conftest's enable_compile_cache() left this process with
    in_use = jax.config.jax_compilation_cache_dir
    preset = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = compile_cache.compile_cache_dir()
    assert fixed == os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == fixed      # not a temp dir
    # the suite itself runs on it, and git ignores it
    assert in_use == (preset or fixed)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    # with the variable set nothing is set in code: JAX reads it itself
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == in_use


_NO_BACKEND = """
import jax._src.xla_bridge as xb
import paddle_tpu as fluid
assert not xb._backends, ("import", list(xb._backends))
from paddle_tpu.models.bert import BertConfig, build_bert_pretrain
from paddle_tpu.models.gpt import GptConfig, build_gpt_generative
from paddle_tpu.models.resnet import build_resnet
build_bert_pretrain(BertConfig.tiny(), seq_len=128, amp=True)
build_gpt_generative(GptConfig.tiny())
build_resnet(depth=18, class_num=10, image_shape=(3, 32, 32), amp=True)
fluid.Executor                       # the class, not an instance
assert not xb._backends, ("build", list(xb._backends))
print("no backend initialised")
"""


def test_import_and_program_build_initialise_no_backend():
    """A parent that imports the package and builds Programs must not take
    the chip: one process owns it, and a child that needs it would fail
    or hang."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)       # nothing pins the platform here
    r = subprocess.run([sys.executable, "-c", _NO_BACKEND], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "no backend initialised" in r.stdout


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "CpuDevice" in r.stderr and "JAX_PLATFORMS='cpu'" in r.stderr
    # the device is named first, and no result line is printed
    assert "platform cpu" in r.stdout
    assert '"ok"' not in r.stdout


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver parses the LAST stdout line: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) and no other key — the first PR 21
    submission was refused for carrying ``legs``/``claim`` there."""
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    devices = jax.devices()
    got = json.loads(chip_smoke.result_line(devices))
    assert got == {"ok": True,
                   "device": {"platform": devices[0].platform,
                              "kind": devices[0].device_kind,
                              "count": len(devices)}}
    assert isinstance(got["device"]["count"], int)
    # success prints it last: nothing follows it in main()
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert src.rstrip().split("print(result_line(devices), flush=True)")[1] \
        .strip().startswith("return 0")


def test_a_compiler_refusal_reaches_the_caller_once(monkeypatch):
    """A deterministic XLA refusal (out of HBM, a Mosaic kernel the
    compiler rejects) is raised ONCE with its own text: not retried as
    'transient', not retried through jit — each attempt would be another
    multi-minute compile that fails the same way. (The real thing, on the
    chip: tests/test_tpu_smoke.py.)"""
    from jax import stages

    main, startup, loss = _attention_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    monitor.reset()
    calls = []

    def refuse(self, *a, **k):
        calls.append(self)
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm.")

    monkeypatch.setattr(stages.Lowered, "compile", refuse)
    feed = {"x": np.ones((2, 2, 128, 8), np.float32)}
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED.*memory space hbm"):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert len(calls) == 1
    assert monitor.metric_value("resilience_retries_total", 0.0,
                                site="compile") == 0
    # the step is not poisoned into a jit fallback: with the compiler
    # willing again, the same executor builds and runs it
    monkeypatch.undo()
    (out,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(out).all()
