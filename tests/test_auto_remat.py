"""FLAGS_auto_recompute — the Pass 6 auto-remat chooser (analysis/remat.py):
memory_plan-scored checkpoint selection over a rebuilt program, wired into
Executor.run / run_chained. Bit-identical training is the hard contract
(tests/test_recompute.py proves it for manual checkpoints; these prove the
automatic chooser inherits it), plus budget fitting, inference/manual
programs passing through untouched, and compile-cache separation.

Also hosts the dtype-truncation regression test for this round's satellite:
ops that request 64-bit dtypes from jax must canonicalize via jnp_dtype
BEFORE the jnp call, or every traced op warns under disabled x64."""
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu.analysis.remat import (auto_recompute_program,
                                       remat_candidates)

WIDTH, DEPTH, BATCH = 128, 8, 256


def _build(width=WIDTH, depth=DEPTH, seed=11):
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[width], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = x
            acts = []
            for _ in range(depth):
                h = fluid.layers.fc(h, width, act="relu")
                acts.append(h.name)
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    main.random_seed = seed
    return main, startup, loss, acts


def _feed(width=WIDTH, batch=BATCH):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(batch, width).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


@pytest.fixture
def _flags():
    prev = fluid.get_flags(["FLAGS_auto_recompute", "FLAGS_remat_budget_mb"])
    yield
    fluid.set_flags(prev)


def _train(auto, chained=False, steps=5, fetch_extra=None):
    main, startup, loss, acts = _build()
    fluid.set_flags({"FLAGS_auto_recompute": auto})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed()
    fetches = [loss.name] + (fetch_extra(acts) if fetch_extra else [])
    out = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        if chained:
            outs = exe.run_chained(main, feed=feed, fetch_list=fetches,
                                   steps=steps, scope=scope)
            out = [float(np.asarray(outs[0]).reshape(-1)[i])
                   for i in range(steps)]
        else:
            for _ in range(steps):
                vals = exe.run(main, feed=feed, fetch_list=fetches)
                out.append(float(np.asarray(vals[0]).reshape(-1)[0]))
    ran = next((p for k, p in exe._remat_cache.items()
                if k[0][0] == main._serial), main)
    segs = sum(1 for op in ran.global_block.ops
               if op.type == "recompute_segment")
    return out, segs, exe, main, ran


def test_candidates_found():
    main, _, loss, _ = _build()
    cands = remat_candidates(main, batch_size=BATCH)
    assert len(cands) >= DEPTH  # at least one seam per fc layer
    for c in cands:
        assert c.nbytes > 0
        assert main.global_block.has_var(c.var_name)


def test_auto_remat_bit_identical_run(_flags):
    plain, seg0, _, _, _ = _train(False)
    remat, seg1, _, _, _ = _train(True)
    assert seg0 == 0
    assert seg1 > 0
    assert plain == remat  # bit-identical, not allclose
    assert plain[0] != plain[-1]  # params actually updated


def test_auto_remat_bit_identical_chained(_flags):
    plain, _, _, _, _ = _train(False, chained=True)
    remat, segs, _, _, _ = _train(True, chained=True)
    assert segs > 0
    assert plain == remat


def test_predicted_peak_drops(_flags):
    _, segs, exe, main, ran = _train(True)
    assert segs > 0 and ran is not main
    kw = dict(feed_names=["x", "y"], batch_size=BATCH)
    assert ran.memory_plan(**kw).peak_bytes < main.memory_plan(
        **kw).peak_bytes


def test_budget_respected():
    main, _, loss, _ = _build()
    free = auto_recompute_program(main, feed_names=["x", "y"],
                                  fetch_names=[loss.name], batch_size=BATCH)
    assert free.applied and free.n_segments > 0
    # a budget between the best-achievable and plain peaks must be honored
    budget_mb = max(1, (free.peak_after >> 20) + 1 +
                    ((free.peak_before - free.peak_after) >> 21))
    dec = auto_recompute_program(main, feed_names=["x", "y"],
                                 fetch_names=[loss.name], batch_size=BATCH,
                                 budget_mb=budget_mb)
    assert dec.applied
    assert dec.peak_after <= budget_mb << 20
    # cheapest-first: the fitting set should checkpoint at least as densely
    # as the unconstrained sqrt(N) pick
    assert len(dec.checkpoints) >= len(free.checkpoints)
    # a budget the PLAIN program already fits must refuse outright — the
    # cheapest fitting set is no checkpoints at all
    roomy = auto_recompute_program(
        main, feed_names=["x", "y"], fetch_names=[loss.name],
        batch_size=BATCH, budget_mb=(free.peak_before >> 20) + 64)
    assert not roomy.applied and "already fits" in roomy.reason


def test_inference_program_untouched(_flags):
    main, _, loss, _ = _build()
    infer = main.clone(for_test=True)
    fluid.set_flags({"FLAGS_auto_recompute": 1})
    exe = fluid.Executor(fluid.CPUPlace())
    out = exe._maybe_auto_remat(infer, _feed(), [loss.name])
    assert out is infer  # no backward ops -> pass-through, same object
    dec = auto_recompute_program(infer, feed_names=["x", "y"],
                                 fetch_names=[loss.name], batch_size=BATCH)
    assert not dec.applied and "no backward" in dec.reason


def test_manual_recompute_program_refused():
    """A program the user already checkpointed via RecomputeOptimizer must
    pass through untouched — double-remat would recompute recomputes."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[32], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = x
            ckpts = []
            for i in range(4):
                h = fluid.layers.fc(h, 32, act="relu")
                if i % 2:
                    ckpts.append(h)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, 1), y))
            opt = fluid.optimizer.RecomputeOptimizer(
                fluid.optimizer.Adam(learning_rate=0.01))
            opt._set_checkpoints(ckpts)
            opt.minimize(loss)
    dec = auto_recompute_program(main, feed_names=["x", "y"],
                                 fetch_names=[loss.name], batch_size=64)
    assert not dec.applied and "recompute segments" in dec.reason


def test_run_chained_cache_separation(_flags):
    """One executor, same program, flag flipped between dispatches: the
    remat variant must compile into its OWN cache entry (fresh program
    serial), never alias the plain one."""
    main, startup, loss, _ = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = _feed()

    def chained(auto):
        fluid.set_flags({"FLAGS_auto_recompute": auto})
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            # startup via a FRESH executor: the shared one's seed counter
            # advances per dispatch, which would re-roll the param init
            # between the plain and remat passes
            fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
            outs = exe.run_chained(main, feed=feed, fetch_list=[loss.name],
                                   steps=4, scope=scope)
        return [float(np.asarray(outs[0]).reshape(-1)[i]) for i in range(4)]

    plain = chained(False)
    remat = chained(True)
    assert plain == remat
    chained_serials = {k[1][0] for k in exe._cache if k[0] == "chained"}
    assert main._serial in chained_serials
    assert len(chained_serials) == 2  # plain + remat entries, disjoint


def test_fetching_intermediate_survives_auto_remat(_flags):
    """Transparent remat must never break a fetch: fetched activations are
    kept as segment outputs (extra_live), unlike the manual API where
    demotion is the documented trade."""
    def fetch_mid(acts):
        return [acts[len(acts) // 2]]

    plain, _, _, _, _ = _train(False, fetch_extra=fetch_mid)
    remat, segs, _, _, _ = _train(True, fetch_extra=fetch_mid)
    assert segs > 0
    assert plain == remat


def test_remat_rng_ops_replay(_flags):
    """Dropout inside a segment replays bit-identically (uid-keyed PRNG)."""
    def build_do():
        with un.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", shape=[WIDTH], dtype="float32")
                y = fluid.layers.data("y", shape=[1], dtype="float32")
                h = x
                for _ in range(6):
                    h = fluid.layers.fc(h, WIDTH, act="relu")
                    h = fluid.layers.dropout(h, 0.3)
                loss = fluid.layers.mean(fluid.layers.square_error_cost(
                    fluid.layers.fc(h, 1), y))
                fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        main.random_seed = 5
        return main, startup, loss

    feed = _feed()

    def train(auto):
        main, startup, loss = build_do()
        fluid.set_flags({"FLAGS_auto_recompute": auto})
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        out = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(4):
                (lv,) = exe.run(main, feed=feed, fetch_list=[loss.name])
                out.append(float(np.asarray(lv).reshape(-1)[0]))
        segs = sum(1 for p in exe._remat_cache.values()
                   for op in p.global_block.ops
                   if op.type == "recompute_segment")
        return out, segs

    plain, _ = train(False)
    remat, segs = train(True)
    assert segs > 0
    assert plain == remat


def test_changed_fetch_list_gets_its_own_transform(_flags):
    """The remat cache is keyed on the fetch list: a transform built for
    fetch=[loss] keeps only loss alive across segments, so a later run
    fetching a mid activation must trigger its own rebuild instead of
    hitting a cached program that demoted that activation."""
    main, startup, loss, acts = _build()
    fluid.set_flags({"FLAGS_auto_recompute": 1})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed()
    mid = acts[len(acts) // 2]
    with fluid.scope_guard(scope):
        exe.run(startup)
        (l1,) = exe.run(main, feed=feed, fetch_list=[loss.name])
        l2, mid_val = exe.run(main, feed=feed, fetch_list=[loss.name, mid])
    assert np.isfinite(np.asarray(mid_val)).all()
    assert np.asarray(mid_val).shape == (BATCH, WIDTH)
    # two distinct transforms were cached for MAIN (one per fetch list)
    assert len({k[3] for k in exe._remat_cache
                if k[0][0] == main._serial}) == 2


def test_bert_tiny_bit_identical(_flags):
    """The acceptance shape: a BERT training program (embeddings with tied
    weights, attention, layer_norm, dropout, AMP policy) auto-remats with
    no user checkpoints, drops the predicted peak, and trains
    bit-identically."""
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    cfg = BertConfig.tiny()
    seq, batch = 32, 8
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq)),
        "pos_ids": np.tile(np.arange(seq), (batch, 1)),
        "sent_ids": np.zeros((batch, seq)),
        "input_mask": np.ones((batch, seq), np.float32),
        "mask_label": rng.randint(0, cfg.vocab_size, (batch, seq)),
        "next_sent_label": rng.randint(0, 2, (batch, 1)),
    }
    for k in ("src_ids", "pos_ids", "sent_ids", "mask_label",
              "next_sent_label"):
        feed[k] = feed[k].astype(np.int64)

    def train(auto):
        with un.guard():
            model = build_bert_pretrain(cfg, seq_len=seq, amp=True)
        model["main"].random_seed = 3
        fluid.set_flags({"FLAGS_auto_recompute": auto})
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        out = []
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            for _ in range(3):
                (lv,) = exe.run(model["main"], feed=feed,
                                fetch_list=[model["loss"].name])
                out.append(np.asarray(lv).tobytes())
        ran = next((p for k, p in exe._remat_cache.items()
                    if k[0][0] == model["main"]._serial), model["main"])
        segs = sum(1 for op in ran.global_block.ops
                   if op.type == "recompute_segment")
        return out, segs, ran, model["main"]

    plain, seg0, _, _ = train(False)
    remat, seg1, ran, main = train(True)
    assert seg0 == 0 and seg1 > 0
    assert plain == remat  # loss bit patterns, step for step
    kw = dict(feed_names=sorted(feed), batch_size=batch)
    assert ran.memory_plan(**kw).peak_bytes < main.memory_plan(
        **kw).peak_bytes


# ---------------------------------------------------------------------------
# satellite: dtype-truncation warnings are gone at every jnp boundary
# ---------------------------------------------------------------------------

def test_no_dtype_truncation_warnings():
    """cast / fill_constant / sequence_mask / one_hot requesting int64 must
    canonicalize via jnp_dtype before the jnp call: with x64 disabled the
    old np_dtype path emitted one UserWarning per traced op (bench/CI log
    spam). simplefilter('error') turns any regression into a hard fail."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8], dtype="float32")
            ids = fluid.layers.data("ids", shape=[1], dtype="int64")
            c = fluid.layers.cast(x, "int64")              # astype path
            fc64 = fluid.layers.fill_constant([8], "int64", 3)
            oh = fluid.layers.one_hot(ids, depth=4)
            sm = fluid.layers.sequence_mask(
                fluid.layers.cast(x, "int32"), maxlen=4, dtype="int64")
            s = (fluid.layers.cast(c, "float32")
                 + fluid.layers.cast(fc64, "float32")
                 + fluid.layers.reduce_mean(oh)
                 + fluid.layers.reduce_mean(
                     fluid.layers.cast(sm, "float32")))
            loss = fluid.layers.mean(s)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.zeros((4, 8), np.float32),
            "ids": np.zeros((4, 1), np.int64)}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with fluid.scope_guard(scope):
            exe.run(startup)
            (out,) = exe.run(main, feed=feed, fetch_list=[loss.name])
    assert np.isfinite(np.asarray(out)).all()


def test_no_truncation_warning_on_argmax_astype_path():
    """The astype flavour of the BENCH-tail spam (ISSUE 13 satellite):
    argmax/top_k cast their indices to int64 via ``Array.astype`` — with a
    failed-open x64 probe that emitted one UserWarning per traced op."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8], dtype="float32")
            am = fluid.layers.arg_max(x, axis=1)
            fc64 = fluid.layers.fill_constant([4], "int64", 3)
            s = (fluid.layers.cast(am, "float32")
                 + fluid.layers.reduce_mean(
                     fluid.layers.cast(fc64, "float32")))
            outv = fluid.layers.mean(s)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with fluid.scope_guard(scope):
            exe.run(startup)
            (out,) = exe.run(main, feed={"x": np.zeros((4, 8), np.float32)},
                             fetch_list=[outv.name])
    assert np.isfinite(np.asarray(out)).all()


def test_jnp_dtype_follows_the_x64_config():
    """jnp_dtype requests exactly what jax will deliver, read from
    ``jax.config.jax_enable_x64`` (no probe computation — it runs at
    program-build time, tests/test_platform.py)."""
    import jax

    from paddle_tpu.core import types as t

    assert t.jnp_dtype("int64") == np.dtype("int32")
    assert t.jnp_dtype("float64") == np.dtype("float32")
    assert t.jnp_dtype("uint64") == np.dtype("uint32")
    # narrow + float dtypes pass through untouched
    assert t.jnp_dtype("int32") == np.dtype("int32")
    assert t.jnp_dtype("bfloat16").name == "bfloat16"
    with jax.enable_x64(True):
        assert t.jnp_dtype("int64") == np.dtype("int64")
        assert t.jnp_dtype("float64") == np.dtype("float64")
