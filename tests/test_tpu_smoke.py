"""On-chip smoke suite (VERDICT r2 item 10): run with

    PADDLE_TPU_TESTS=1 python -m pytest tests/test_tpu_smoke.py -m tpu -q

on a host with a real accelerator. The CPU suite auto-skips these. Covers
the TPU-numerics policy (bf16 matmul tolerance), one real train step, and
the recompute remat surviving into the chip executable.

The quickest proof that the system starts on the chip at the real widths
is ``python chip_smoke.py`` at the repo root (BERT-base training and
GPT-2-base serving end to end, every Pallas route against its oracle);
``ci/run_ci.sh tpu`` runs it before this file.
"""
import numpy as np
import pytest

import paddle_tpu as fluid

pytestmark = pytest.mark.tpu


def test_bf16_matmul_tolerance():
    """bf16 MXU matmul vs fp64-ish numpy oracle: the tolerance policy
    (SURVEY §7 hard-part 4) — bf16 has ~3 decimal digits; rtol 2e-2 over a
    256-deep contraction is the documented budget."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    a = rng.randn(128, 256).astype(np.float32)
    b = rng.randn(256, 128).astype(np.float32)
    got = np.asarray(jnp.matmul(a.astype(jnp.bfloat16),
                                b.astype(jnp.bfloat16)).astype(jnp.float32))
    want = a @ b
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-1)


def test_one_train_step_on_chip():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[64], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 64, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    xb, yb = rng.randn(32, 64).astype(np.float32), rng.randn(32, 1).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        first = last = None
        for _ in range(10):
            (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss.name])
            last = float(np.asarray(lv).reshape(-1)[0])
            first = first if first is not None else last
    assert np.isfinite(last) and last < first


def test_recompute_remat_survives_to_executable():
    """On TPU the jax.checkpoint remat must reach the binary: the recompute
    code makes the generated executable strictly larger while argument/out
    sizes stay equal (CPU CSE merges it away, so this only proves out here)."""
    import jax

    from test_recompute import _lowered

    plain = _lowered(False, width=256, depth=8, batch=256).compile()
    rc = _lowered(True, width=256, depth=8, batch=256).compile()
    pa, ra = plain.memory_analysis(), rc.memory_analysis()
    assert ra.argument_size_in_bytes == pa.argument_size_in_bytes
    assert ra.generated_code_size_in_bytes > pa.generated_code_size_in_bytes


def test_hbm_compile_error_reaches_the_caller_once(monkeypatch):
    """A program whose activations cannot fit the chip: the TPU compiler's
    own RESOURCE_EXHAUSTED must reach the caller of ``exe.run`` after ONE
    build — not retried as transient, not retried through jit."""
    import jax
    from jax import stages

    from paddle_tpu import monitor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[64], dtype="float32")
        h = x
        # 8 x [262144, 4096] f32 = 32 GiB the backward needs live (tanh's
        # gradient needs its output; a relu net keeps bits and just fits)
        for _ in range(8):
            h = fluid.layers.fc(h, 4096, act="tanh")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    builds = []
    real = stages.Lowered.compile

    def counting(self, *a, **k):
        builds.append(self)
        return real(self, *a, **k)

    monkeypatch.setattr(stages.Lowered, "compile", counting)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        builds.clear()
        monitor.reset()
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED") as err:
            exe.run(main, feed={"x": np.ones((262144, 64), np.float32)},
                    fetch_list=[loss.name])
    assert "hbm" in str(err.value)
    assert len(builds) == 1
    assert monitor.metric_value("resilience_retries_total", 0.0,
                                site="compile") == 0
