"""RecomputeOptimizer (gradient checkpointing): numerical equivalence with
the plain path, and a compiled peak-memory reduction proof (reference
optimizer.py:3074 RecomputeOptimizer / backward.py:555)."""
import numpy as np
import pytest

import paddle_tpu as fluid


def _build(recompute, width=256, depth=6, ckpt_every=2):
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[width], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = x
            ckpts = []
            for i in range(depth):
                h = fluid.layers.fc(h, width, act="relu")
                if (i + 1) % ckpt_every == 0:
                    ckpts.append(h)
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            opt = fluid.optimizer.Adam(learning_rate=0.01)
            if recompute:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints(ckpts)
            opt.minimize(loss)
    return main, startup, loss


def _train(recompute, steps=6, batch=32, **kw):
    main, startup, loss = _build(recompute, **kw)
    main.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    xb = rng.randn(batch, kw.get("width", 256)).astype(np.float32)
    yb = rng.randn(batch, 1).astype(np.float32)
    out = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss.name])
            out.append(float(np.asarray(lv).reshape(-1)[0]))
    return out


def test_recompute_matches_plain_training():
    base = _train(False, width=64, depth=4)
    rc = _train(True, width=64, depth=4)
    np.testing.assert_allclose(base, rc, rtol=1e-4, atol=1e-6)
    assert base[-1] < base[0]


def test_recompute_segments_inserted():
    main, _, _ = _build(True, width=32, depth=6, ckpt_every=2)
    types = [op.type for op in main.global_block.ops]
    assert types.count("recompute_segment") >= 2
    assert types.count("recompute_segment_grad") >= 2
    # internals of a segment are demoted out of the global block
    sub = main.blocks[main.global_block.ops[
        types.index("recompute_segment")].attrs["sub_block"]]
    assert sub.ops and sub.vars


def _lowered(recompute, width=256, depth=8, batch=256):
    import jax

    main, startup, loss = _build(recompute, width=width, depth=depth)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.zeros((batch, width), np.float32),
            "y": np.zeros((batch, 1), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        step = exe._get_compiled(main, feed, [loss.name], scope)
        feed_vals = [jax.ShapeDtypeStruct(feed[n].shape, feed[n].dtype)
                     for n in step.feed_names]
        don = [scope.find_var(n) for n in step.donated_names]
        ro = [scope.find_var(n) for n in step.ro_names]
        key = jax.random.key(0)
        return step.fn.lower(feed_vals, don, ro, key)


def test_recompute_remat_in_lowered_hlo():
    """The lowered program must carry the rematerialisation: recomputed
    segment matmuls (extra dots) behind optimization barriers, so the fwd
    activations inside segments are not operands of backward ops.

    Peak-liveness byte counts are not assertable on the CPU: XLA CPU's
    CompiledMemoryStats.temp_size is liveness-blind (identical for
    jax.checkpoint'd and plain jax.grad of a deep MLP). On real TPU the
    remat survives to the executable (generated_code_size grows by the
    recompute code); see test_tpu_smoke.py for the on-chip check."""
    plain = _lowered(False).as_text()
    rc = _lowered(True).as_text()
    assert rc.count("stablehlo.dot") > plain.count("stablehlo.dot")
    assert "optimization_barrier" in rc
    assert "optimization_barrier" not in plain


def test_recompute_program_serializes_and_runs():
    main, startup, loss = _build(True, width=32, depth=4)
    main.random_seed = 3
    clone = fluid.Program.from_json(main.to_json())
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(8, 32).astype(np.float32),
            "y": rng.randn(8, 1).astype(np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        (a,) = exe.run(main, feed=feed, fetch_list=[loss.name])
    exe2 = fluid.Executor(fluid.CPUPlace())  # fresh step counter: same init
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup)
        (b,) = exe2.run(clone, feed=feed, fetch_list=[loss.name])
    np.testing.assert_allclose(a, b, rtol=1e-5)
