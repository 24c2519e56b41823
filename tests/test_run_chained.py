"""Executor.run_chained — K scanned steps must equal K separate run() calls.

This is the compiled-train-loop role (reference trainer.cc RunFromDataset
runs the loop outside Python); the generative engine decodes a chunk of
tokens through it.
"""
import numpy as np

import paddle_tpu as fluid


def _build(with_bn=False):
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=8, act="relu")
    if with_bn:
        h = fluid.layers.batch_norm(input=h)
    pred = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _feed():
    rng = np.random.RandomState(3)
    return {"x": rng.rand(8, 4).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32)}


def test_chained_matches_sequential_runs():
    for with_bn in (False, True):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            loss = _build(with_bn)
            main, startup = (fluid.default_main_program(),
                             fluid.default_startup_program())
            feed = _feed()
            exe = fluid.Executor(fluid.CPUPlace())

            s1 = fluid.Scope()
            with fluid.scope_guard(s1):
                exe.run(startup)
                seq = [float(np.asarray(exe.run(main, feed=feed,
                                                fetch_list=[loss])[0]))
                       for _ in range(4)]
            exe2 = fluid.Executor(fluid.CPUPlace())
            s2 = fluid.Scope()
            with fluid.scope_guard(s2):
                exe2.run(startup)
                chained = exe2.run_chained(main, feed=feed,
                                           fetch_list=[loss], steps=4)
            got = np.asarray(chained[0]).reshape(-1)
            assert got.shape == (4,)
            # same math modulo per-step dropout keys (none here) — the loss
            # trajectory must match the sequential path step for step
            np.testing.assert_allclose(got, seq, rtol=2e-5, atol=1e-6)
            # final state matches too (params after 4 updates)
            params = [v.name for v in main.global_block.vars.values()
                      if type(v).__name__ == "Parameter"]
            assert params
            for n in params:
                np.testing.assert_allclose(s1.numpy(n), s2.numpy(n),
                                           rtol=2e-5, atol=1e-6)


def test_chained_inference_no_state():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(input=x, size=2, act="softmax")
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        infer = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        feed = {"x": np.random.RandomState(0).rand(4, 4).astype(np.float32)}
        with fluid.scope_guard(scope):
            exe.run(startup)
            one = exe.run(infer, feed=feed, fetch_list=[pred])[0]
            stacked = exe.run_chained(infer, feed=feed, fetch_list=[pred],
                                      steps=3)[0]
        assert np.asarray(stacked).shape == (3,) + np.asarray(one).shape
        for i in range(3):
            np.testing.assert_allclose(np.asarray(stacked)[i],
                                       np.asarray(one), rtol=1e-6)


def test_chained_fetched_param_threads_without_donation():
    """A fetched parameter is donation-unsafe (PT500): run_chained must keep
    it OUT of the donated jit args but still thread it through the scan
    carry — reading it as a loop-invariant would hand every iteration the
    stale pre-run value."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        loss = _build()
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        param = next(v.name for v in main.global_block.vars.values()
                     if type(v).__name__ == "Parameter"
                     and v.name.endswith(".w_0"))
        feed = _feed()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            stacked = exe.run_chained(main, feed=feed,
                                      fetch_list=[loss, param], steps=3)
        step = next(s for k, s in exe._cache.items() if k[0] == "chained")
        assert param not in step.donated_names  # liveness refused donation
        assert param in step.kept_names and param in step.carried_names
        ws = np.asarray(stacked[1])
        assert ws.shape[0] == 3
        # the param moves every step (carried, not loop-invariant), and the
        # scope ends at the last fetched value
        assert not np.array_equal(ws[0], ws[1])
        assert not np.array_equal(ws[1], ws[2])
        np.testing.assert_allclose(scope.numpy(param), ws[2], rtol=1e-6)


def test_scope_serial_keys_cache_not_id():
    """r5 advisor finding: the compile cache keyed on id(scope), which can
    alias after GC hands a dead scope's address to a fresh Scope. Scopes now
    carry a monotonic serial used in every executor cache key."""
    a, b = fluid.Scope(), fluid.Scope()
    assert a._serial != b._serial
    seen = {a._serial, b._serial}
    del a, b
    import gc

    gc.collect()
    c = fluid.Scope()
    assert c._serial not in seen  # serials never recycle, unlike id()


def test_chained_serializes_inference_with_identity_carry():
    """The r03->r05 ResNet-50 infer bench discontinuity (ISSUE 13
    satellite): a for_test clone's only carried state is identity-written
    batch_norm statistics (use_global_stats writes MeanOut = Mean), so the
    old `not carried` trigger skipped the anti-hoisting chain, XLA's
    while-loop simplifier saw the fixed-point carry, hoisted the body, and
    the chained per-step time differenced to ~zero. Non-training programs
    must now ALWAYS engage the chain — and stay numerically identical to
    single runs (the perturbation is runtime-zero)."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        loss = _build(with_bn=True)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        infer = main.clone(for_test=True)
        feed = _feed()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            one = exe.run(infer, feed=feed, fetch_list=[loss.name])[0]
            stacked = exe.run_chained(infer, feed=feed,
                                      fetch_list=[loss.name], steps=3)[0]
            # training program for contrast: carried params chain it
            exe.run_chained(main, feed=feed, fetch_list=[loss.name],
                            steps=2, scope=scope)
    steps = {}
    for key, step in exe._cache.items():
        if key[0] == "chained":
            steps[key[1][0]] = step
    infer_step = steps[infer._serial]
    train_step = steps[main._serial]
    # the infer program carries BN stats (identity) yet must chain; the
    # training program chains through its genuinely-updated params
    assert infer_step.carried_names, "bn stats should be carried state"
    assert infer_step.needs_chain is True
    assert train_step.needs_chain is False
    for i in range(3):
        np.testing.assert_allclose(np.asarray(stacked)[i],
                                   np.asarray(one), rtol=1e-6)


def test_chained_feedless_state_program_no_hoist_warning():
    """A feed-less program whose per-step variation lives in persistable
    carried state (the GPT decode shape: KV caches / token carry) must
    not warn about hoisting — the body reads the carry it rewrites, so
    XLA cannot hoist it, and the warning would fire on every serving
    decode dispatch."""
    import warnings
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fluid.layers.create_global_var(shape=[1], value=0.0,
                                           dtype="float32",
                                           persistable=True)
        fluid.layers.increment(v, value=1.0, in_place=True)
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            with warnings.catch_warnings():
                warnings.simplefilter("error", RuntimeWarning)
                stacked = exe.run_chained(main, fetch_list=[v.name],
                                          steps=3)[0]
    # genuinely serialized: each step sees the previous step's counter
    np.testing.assert_allclose(np.asarray(stacked).reshape(-1),
                               [1.0, 2.0, 3.0])
