"""Flash-attention Pallas kernel + fused_multihead_attention op.

CPU suite runs the kernel via the pallas interpreter (dropout excluded —
the TPU PRNG has no interpret lowering); the `tpu` marker cases cover the
compiled Mosaic path including in-kernel dropout. Oracle: the primitive
softmax composition (which is also the op's off-TPU lowering), matching
reference semantics of fused attention (operators/fused/ role)."""
import dataclasses
import functools
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels import flash_attention, flash_attention_with_lse

RNG = np.random.RandomState(3)
HP = jax.lax.Precision.HIGHEST


def _ref(q, k, v, bias=None, causal=False, num_heads=1):
    D = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k, precision=HP) * (D ** -0.5)
    if bias is not None:
        s = s + jnp.repeat(bias, num_heads, axis=0)[:, None, :]
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        m = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(m[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v, precision=HP)


def _qkv(BH=4, S=256, D=64):
    return tuple(jnp.asarray(RNG.randn(BH, S, D).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_bias", [False, True])
def test_kernel_forward_matches_reference(causal, use_bias):
    q, k, v = _qkv()
    H = 2
    bias = (jnp.asarray(np.where(RNG.rand(2, 256) > 0.25, 0.0,
                                 -10000.0).astype(np.float32))
            if use_bias else None)
    o = flash_attention(q, k, v, bias=bias, causal=causal, num_heads=H,
                        interpret=True)
    o_ref = _ref(q, k, v, bias, causal, num_heads=H)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=1e-4)


def test_kernel_gradients_match_reference():
    q, k, v = _qkv(BH=2, S=128)
    bias = jnp.asarray(np.where(RNG.rand(2, 128) > 0.25, 0.0,
                                -10000.0).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(
            q, k, v, bias=bias, num_heads=1, interpret=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(_ref(q, k, v, bias, num_heads=1)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_lse_combination_differentiates():
    """The ring-attention contract: splitting keys in two kernel calls and
    recombining through lse must equal whole attention — for values AND
    gradients (the kernel honours the lse cotangent)."""
    q, k, v = _qkv(BH=2, S=256)
    k1, k2, v1, v2 = k[:, :128], k[:, 128:], v[:, :128], v[:, 128:]

    def combined(q, k1, k2, v1, v2):
        o1, l1 = flash_attention_with_lse(q, k1, v1, interpret=True)
        o2, l2 = flash_attention_with_lse(q, k2, v2, interpret=True)
        l = jnp.logaddexp(l1, l2)
        o = (o1 * jnp.exp(l1 - l)[..., None]
             + o2 * jnp.exp(l2 - l)[..., None])
        return jnp.sum(jnp.tanh(o))

    def whole(q, k1, k2, v1, v2):
        return jnp.sum(jnp.tanh(_ref(q, jnp.concatenate([k1, k2], 1),
                                     jnp.concatenate([v1, v2], 1))))

    np.testing.assert_allclose(combined(q, k1, k2, v1, v2),
                               whole(q, k1, k2, v1, v2), rtol=1e-5)
    g1 = jax.grad(combined, argnums=(0, 1, 2, 3, 4))(q, k1, k2, v1, v2)
    g2 = jax.grad(whole, argnums=(0, 1, 2, 3, 4))(q, k1, k2, v1, v2)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_fully_masked_rows_zero_output_and_grads():
    """A query whose every key is CAUSALLY masked (all keys in the future,
    the ring-attention first-block case): O = 0, grads = 0, no NaNs.

    (An all--10000 additive bias is NOT this case: constant shifts cancel
    in softmax, so such rows attend uniformly — matching the primitive
    path's semantics.)"""
    from paddle_tpu.kernels import flash_attention_with_lse

    q, k, v = _qkv(BH=2, S=128)

    def run(q, k, v):
        # k_offset=128 > every q position -> every key masked for every row
        return flash_attention_with_lse(q, k, v, causal=True,
                                        q_offset=0, k_offset=128,
                                        interpret=True)

    o, lse = run(q, k, v)
    assert bool(jnp.all(o == 0.0))
    assert bool(jnp.all(jnp.isneginf(lse)))
    g = jax.grad(lambda *a: jnp.sum(run(*a)[0]), argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert bool(jnp.all(jnp.isfinite(a)))
        assert bool(jnp.all(a == 0.0))


def test_op_level_kernel_vs_primitive_path():
    """The registered op under FLAGS_use_flash_attention=always (interpret
    kernel) must match =never (primitive path) through a whole Program —
    on one device, and over a dp x tp mesh, where GSPMD cannot partition a
    Mosaic kernel and the op runs it per shard under shard_map."""
    from paddle_tpu import flags
    from paddle_tpu.parallel.sharding import make_mesh

    def run(mode, mesh=None):
        flags.set_flags({"FLAGS_use_flash_attention": mode})
        try:
            with un.guard():
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup):
                    q = fluid.layers.data("q", shape=[2, 128, 32],
                                          dtype="float32")
                    k = fluid.layers.data("k", shape=[2, 128, 32],
                                          dtype="float32")
                    v = fluid.layers.data("v", shape=[2, 128, 32],
                                          dtype="float32")
                    m = fluid.layers.data("m", shape=[128], dtype="float32")
                    out = fluid.layers.fused_multihead_attention(
                        q, k, v, bias_qk=m, is_test=True)
                    loss = fluid.layers.mean(out)
                exe = fluid.Executor(fluid.CPUPlace())
                scope = fluid.Scope()
                rng = np.random.RandomState(5)
                feed = {n: rng.randn(4, 2, 128, 32).astype(np.float32)
                        for n in ("q", "k", "v")}
                feed["m"] = np.where(rng.rand(4, 128) > 0.3, 0.0,
                                     -10000.0).astype(np.float32)
                prog = main if mesh is None else fluid.CompiledProgram(
                    main).with_data_parallel(places=make_mesh(mesh))
                with fluid.scope_guard(scope):
                    exe.run(startup)
                    res = exe.run(prog, feed=feed,
                                  fetch_list=[out.name, loss.name])
                return [np.asarray(r) for r in res]
        finally:
            flags.set_flags({"FLAGS_use_flash_attention": "auto"})

    o_prim, l_prim = run("never")
    for o_kernel, l_kernel in (run("always"),
                               run("always", mesh={"dp": 2, "tp": 2})):
        np.testing.assert_allclose(o_kernel, o_prim, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(l_kernel, l_prim, rtol=1e-5)


def test_bert_attention_uses_fused_op():
    """models/bert.py emits fused_multihead_attention, not the unfused
    matmul/softmax chain."""
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    with un.guard():
        model = build_bert_pretrain(BertConfig.tiny(), seq_len=128,
                                    build_optimizer=False)
    types = [op.type for op in model["main"].global_block.ops]
    assert types.count("fused_multihead_attention") == 2  # tiny: 2 layers
    assert "softmax" not in types  # attention softmax is inside the op


# --------------------------------------------------------------------------
# fused_multihead_attention_grad: the backward kernels on the forward op's
# saved Out and SoftmaxLse; the generic vjp path wherever those are absent
# --------------------------------------------------------------------------

ATTN, ATTN_GRAD = "fused_multihead_attention", "fused_multihead_attention_grad"


def _attention_grads(mode, use_bias=False, causal=False, window=0,
                     keep_lse=True, mesh=None, kv_heads=2, seq_par=False,
                     amp_attention=False, roundtrip=False, S=128):
    """Out and the gradients of Q, K, V of ``mean(tanh(attention))``
    through a Program and the executor, with the routes the program's
    attention ops noted. ``keep_lse=False`` deletes the op's SoftmaxLse
    output before the backward is appended: the program an older build
    would have made, whose gradient op has only the generic path."""
    from paddle_tpu import flags, monitor
    from paddle_tpu.lowering import AmpPolicy
    from paddle_tpu.parallel.sharding import make_mesh

    flags.set_flags({"FLAGS_use_flash_attention": mode})
    try:
        with un.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                q = fluid.layers.data("q", shape=[2, S, 32], dtype="float32")
                k, v = (fluid.layers.data(n, shape=[kv_heads, S, 32],
                                          dtype="float32") for n in "kv")
                for t in (q, k, v):
                    t.stop_gradient = False
                m = fluid.layers.data("m", shape=[S], dtype="float32")
                out = fluid.layers.fused_multihead_attention(
                    q, k, v, bias_qk=m if use_bias else None, causal=causal,
                    window=window, sequence_parallel=seq_par)
                if not keep_lse:
                    del main.global_block.ops[-1].outputs["SoftmaxLse"]
                loss = fluid.layers.mean(fluid.layers.tanh(out))
                grads = fluid.backward.calc_gradient([loss], [q, k, v])
        if roundtrip:
            main = fluid.Program.from_json(main.to_json())
        if amp_attention:
            main._amp_policy = AmpPolicy({ATTN}, ())
        rng = np.random.RandomState(5)
        feed = {"q": rng.randn(4, 2, S, 32).astype(np.float32)}
        for n in "kv":
            feed[n] = rng.randn(4, kv_heads, S, 32).astype(np.float32)
        feed["m"] = np.where(rng.rand(4, S) > 0.3, 0.0,
                             -10000.0).astype(np.float32)
        prog = main if mesh is None else fluid.CompiledProgram(
            main).with_data_parallel(places=make_mesh(mesh))
        monitor.reset()
        with fluid.scope_guard(fluid.Scope()):
            res = fluid.Executor(fluid.CPUPlace()).run(
                prog, feed=feed,
                fetch_list=[out.name] + [g.name for g in grads])
        return [np.asarray(r) for r in res], _routes(main)
    finally:
        flags.set_flags({"FLAGS_use_flash_attention": "auto"})


def _routes(program, grid=False):
    """{(op, route): lowerings} of one program from kernel_route_total: the
    paths its ops took, or (``grid``) the grids their flash forwards built
    (``op="<op>.grid"``, PR 50)."""
    from paddle_tpu import monitor

    fam = monitor.get_registry().get("kernel_route_total")
    return {(lab["op"], lab["route"]): int(n.value)
            for lab, n in (fam.children() if fam else ())
            if lab["program"] == str(program._serial)
            and lab["op"].endswith(".grid") == grid}


@pytest.mark.parametrize("use_bias,causal,window", [
    (False, False, 0), (True, False, 0), (False, True, 0), (True, True, 0),
    (False, True, 64), (True, True, 64)])
def test_grad_rule_equals_the_generic_vjp_bit_for_bit(use_bias, causal,
                                                      window):
    """The same kernels on bit-equal operands: riding the saved residuals
    changes no digit of dQ, dK, dV, and drops the second forward call."""
    kw = dict(use_bias=use_bias, causal=causal, window=window, S=256)
    saved, routes = _attention_grads("always", **kw)
    generic, old_routes = _attention_grads("always", keep_lse=False, **kw)
    for a, b in zip(saved, generic):
        np.testing.assert_array_equal(a, b)
    assert routes == {(ATTN, "pallas-interpret"): 1,
                      (ATTN_GRAD, "pallas-interpret"): 1}
    assert old_routes == {(ATTN, "pallas-interpret"): 2,
                          (ATTN_GRAD, "primitive"): 1}


def test_grad_rule_casts_operands_as_the_amp_policy_does():
    """Were attention on the AMP white list, the rule must hand the
    kernels the operands the forward saw (bf16) and return f32 grads."""
    saved, routes = _attention_grads("always", amp_attention=True,
                                     use_bias=True)
    generic, _ = _attention_grads("always", amp_attention=True,
                                  use_bias=True, keep_lse=False)
    assert routes[(ATTN_GRAD, "pallas-interpret")] == 1
    assert saved[0].dtype == jnp.bfloat16
    for a, b in zip(saved[1:], generic[1:]):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)


def _pallas_calls(jaxpr, out=None):
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            out[name] = out.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("amp", [False, True])
def test_bert_training_step_runs_the_forward_kernel_once_a_layer(amp):
    """A 2-layer BERT step holds one forward and one of each backward
    kernel per layer (it held two forwards a layer while the grad op
    differentiated the forward rule), and the counter says every backward
    rode the saved residuals."""
    from paddle_tpu import flags, monitor
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    import dataclasses

    # the in-kernel dropout has no interpret-mode lowering
    cfg = dataclasses.replace(BertConfig.tiny(), attention_dropout=0.0)
    flags.set_flags({"FLAGS_use_flash_attention": "always"})
    try:
        with un.guard():
            model = build_bert_pretrain(cfg, seq_len=128, amp=amp)
        main = model["main"]
        feeds = {n: v for n, v in main.global_block.vars.items()
                 if getattr(v, "is_data", False)}
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        monitor.reset()
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            step = exe._compile(main, set(feeds), [model["loss"].name],
                                scope)
            args = ([jax.ShapeDtypeStruct((2,) + tuple(feeds[n].shape[1:]),
                                          np.dtype(feeds[n].dtype))
                     for n in step.feed_names],
                    [scope.find_var(n) for n in step.donated_names],
                    [scope.find_var(n) for n in step.ro_names],
                    jax.random.key(0))
            calls = _pallas_calls(step.fn.trace(*args).jaxpr.jaxpr)
    finally:
        flags.set_flags({"FLAGS_use_flash_attention": "auto"})
    assert calls == {"flash_attention_fwd": cfg.num_layers,
                     "flash_attention_bwd_dq": cfg.num_layers,
                     "flash_attention_bwd_dkv": cfg.num_layers}
    assert _routes(main) == {
        (ATTN, "pallas-interpret"): cfg.num_layers,
        (ATTN_GRAD, "pallas-interpret"): cfg.num_layers}


@pytest.mark.parametrize("case", ["primitive", "ring"])
def test_grad_rule_falls_back_where_no_kernel_ran(case):
    """The primitive route and ring attention keep no log-sum-exp: their
    gradient is the forward rule's vjp, as before the op had a rule."""
    kw = (dict(mode="never", use_bias=True) if case == "primitive" else
          dict(mode="always", causal=True, seq_par=True,
               mesh={"dp": 2, "sp": 2}))
    with_slot, routes = _attention_grads(**kw)
    without, _ = _attention_grads(keep_lse=False, **kw)
    for a, b in zip(with_slot, without):
        np.testing.assert_array_equal(a, b)
    assert routes[(ATTN_GRAD, "primitive")] == 1
    assert (ATTN_GRAD, "pallas-interpret") not in routes
    # today's gradients: the plain path's, whatever route computed them
    plain, _ = _attention_grads("never", use_bias=case == "primitive",
                                causal=case == "ring")
    for a, b in zip(with_slot, plain):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)


def test_program_without_the_saved_lse_still_differentiates():
    """A program saved before the op had SoftmaxLse: loaded back, its
    gradient op finds no residual and differentiates the forward rule."""
    old, routes = _attention_grads("always", use_bias=True, keep_lse=False,
                                   roundtrip=True)
    new, _ = _attention_grads("always", use_bias=True, roundtrip=True)
    assert routes == {(ATTN, "pallas-interpret"): 2,
                      (ATTN_GRAD, "primitive"): 1}
    for a, b in zip(old, new):
        np.testing.assert_array_equal(a, b)


def test_grad_rule_refuses_grouped_query_heads():
    with pytest.raises(Exception, match="backward with grouped-query heads"):
        _attention_grads("always", causal=True, kv_heads=1)
    # the primitive route sums over a group's heads itself
    grads, _ = _attention_grads("never", causal=True, kv_heads=1)
    assert grads[2].shape == (4, 1, 128, 32)


def test_grad_rule_under_a_dp_tp_mesh_equals_one_device():
    """Four virtual devices, batch over dp and heads over tp: the backward
    kernels run per shard as the forward does, on the shard's own rows of
    the saved Out and SoftmaxLse."""
    one, _ = _attention_grads("always", use_bias=True)
    four, routes = _attention_grads("always", use_bias=True,
                                    mesh={"dp": 2, "tp": 2})
    assert routes == {(ATTN, "pallas-interpret"): 1,
                      (ATTN_GRAD, "pallas-interpret"): 1}
    for a, b in zip(four, one):
        np.testing.assert_array_equal(a, b)


def test_verifier_accepts_the_grad_op_with_its_residuals():
    from paddle_tpu.analysis import Severity, verify_program
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    with un.guard():
        model = build_bert_pretrain(BertConfig.tiny(), seq_len=128)
    main = model["main"]
    (grad_op,) = [op for op in main.global_block.ops
                  if op.type == ATTN_GRAD][:1]
    assert {"__out__Out", "__out__SoftmaxLse", "Out@GRAD"} <= set(
        grad_op.inputs)
    diags = verify_program(main, fetch_names=[model["loss"].name])
    bad = [d for d in diags if d.severity == Severity.ERROR
           or (d.op_type or "").startswith(ATTN)]
    assert not bad, [f"{d.code}: {d.message}" for d in bad]


def test_served_prefill_with_the_extra_output_clones_saves_and_loads(
        tmp_path):
    """A forward-only program carries the SoftmaxLse variable and never
    computes with it: clone(for_test), save and load must not mind."""
    from paddle_tpu.models.gpt import GptConfig, build_gpt_prefill

    B, S = 2, 16
    with un.guard():
        net = build_gpt_prefill(GptConfig.tiny(), B, S, max_seq=32, rows=B)
    main = net["main"]
    attn = [op for op in main.global_block.ops if op.type == ATTN]
    assert attn and all(op.outputs.get("SoftmaxLse") for op in attn)
    rng = np.random.RandomState(0)
    feed = {"prompt_ids": rng.randint(0, 64, (B, S)).astype(np.int64),
            "prompt_pos": np.tile(np.arange(S, dtype=np.int64), (B, 1)),
            "prompt_mask": np.ones((B, S), np.float32),
            "prompt_len": np.full((B, 1), S, np.int64),
            "slot_mask": np.ones((B, 1), np.float32),
            "slot_ids": np.arange(B, dtype=np.int64)[:, None]}
    fetch = net["first_token"]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(net["startup"])
        for name, (shape, dt) in net["state_vars"].items():
            scope.set_var(name, np.zeros(shape, np_dtype(dt)))
        test_prog = main.clone(for_test=True)
        (want,) = exe.run(test_prog, feed=feed, fetch_list=[fetch.name])
        fluid.io.save_inference_model(str(tmp_path / "m"), sorted(feed),
                                      [fetch], exe, main_program=main)
    with fluid.scope_guard(fluid.Scope()):
        prog, feed_names, fetches = fluid.io.load_inference_model(
            str(tmp_path / "m"), exe)
        (got,) = exe.run(prog, feed={n: feed[n] for n in feed_names},
                         fetch_list=fetches)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.tpu
def test_tpu_compiled_kernel_and_dropout():
    """Compiled Mosaic path on the real chip: numerics + in-kernel PRNG
    dropout determinism (same seed -> same mask in fwd and recompute)."""
    q, k, v = _qkv(BH=2, S=256)
    o = flash_attention(q, k, v, num_heads=1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=1e-4)
    o1 = flash_attention(q, k, v, dropout_rate=0.5, seed=7, num_heads=1)
    o2 = flash_attention(q, k, v, dropout_rate=0.5, seed=7, num_heads=1)
    o3 = flash_attention(q, k, v, dropout_rate=0.5, seed=8, num_heads=1)
    assert bool(jnp.all(o1 == o2))
    assert not bool(jnp.all(o1 == o3))
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, dropout_rate=0.1, seed=3, num_heads=1)))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


# -- a sink column, values narrower than keys, blocks a window hides ----------

def _ref_sink(q, k, v, sink, window, num_heads):
    """The softmax with the extra column written out: a head's scalar
    joins the scores of every query row and carries no value."""
    G = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, G, 0), jnp.repeat(v, G, 0)
    s = jnp.einsum("bqd,bkd->bqk", q, k, precision=HP) * q.shape[-1] ** -0.5
    S = q.shape[1]
    d = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = (d >= 0) & ((d < window) if window else True)
    s = jnp.where(seen[None], s, -jnp.inf)
    if sink is not None:
        col = jnp.tile(sink, q.shape[0] // num_heads)[:, None, None]
        s = jnp.concatenate([s, jnp.broadcast_to(col, (q.shape[0], S, 1))],
                            axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :S]
    return jnp.einsum("bqk,bkd->bqd", p, v, precision=HP)


def _wide_keys(B=2, H=4, Hkv=2, S=64, Dk=24, Dv=16):
    mk = lambda *s: jnp.asarray(RNG.randn(*s).astype(np.float32))
    return (mk(B * H, S, Dk), mk(B * Hkv, S, Dk), mk(B * Hkv, S, Dv),
            mk(H))


@pytest.mark.parametrize("window", [0, 8, 20, 40])
@pytest.mark.parametrize("with_sink", [False, True])
def test_kernel_takes_a_sink_and_values_narrower_than_keys(window,
                                                           with_sink):
    """Keys of 24 beside values of 16 (the published 192 / 128 at an eighth),
    grouped-query heads, a window and a sink column, against the softmax
    with the extra column written out."""
    q, k, v, sink = _wide_keys()
    sink = sink if with_sink else None
    got = flash_attention(q, k, v, causal=True, num_heads=4, block_q=8,
                          block_k=8, interpret=True, window=window,
                          sink=sink)
    assert got.shape == (8, 64, 16)
    np.testing.assert_allclose(got, _ref_sink(q, k, v, sink, window, 4),
                               atol=2e-6)


@pytest.mark.parametrize("bq,bk,window,visited", [
    (8, 8, 8, 15),      # the diagonal block and the one before it
    (8, 8, 1, 8), (8, 8, 20, 26), (16, 8, 12, 14), (8, 16, 12, 14)])
def test_blocks_a_window_hides_are_skipped_and_change_nothing(bq, bk, window,
                                                              visited):
    """A windowed forward walks only the blocks a q-block's window can
    touch: the grid is shorter, the count says what it visits, and the
    output is the dense grid's."""
    import sys

    from paddle_tpu.kernels import window_block_visits

    fa = sys.modules["paddle_tpu.kernels.flash_attention"]
    q, k, v, sink = _wide_keys()
    kw = dict(causal=True, num_heads=4, block_q=bq, block_k=bk,
              interpret=True, window=window, sink=sink)
    cut = flash_attention(q, k, v, **kw)
    seen, grid = window_block_visits(64, 64, window, bq, bk)
    assert (seen, grid) == (visited, (64 // bq) * (64 // bk))
    # the same call on the dense grid: every block visited
    cfg, _, scalars = fa._prepare(q, k, None, True, None, 0.0, 0, 0, 0, 4,
                                  bq, bk, True, window)
    cfg = dataclasses.replace(cfg, has_sink=True)
    whole, _ = fa._fwd(cfg, q, k, v, None, scalars, sink, form="dense")
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole), atol=1e-6,
                               rtol=0)
    # and the cut grid really is shorter: the visible pairs alone
    _, walk = fa._forward_grid(cfg, 64, 64, 24, 16, 4)
    assert (walk.form, walk.steps) == ("flat", visited)


def test_windowed_forward_with_traced_offsets_visits_one_block_more():
    """Ring attention hands the kernel traced offsets: the blocks a q-block
    touches may then straddle one more, and the result is the same."""
    q, k, v, _ = _wide_keys()
    v = k[..., :24]
    fn = jax.jit(lambda qo, ko: flash_attention_with_lse(
        q, k, v, causal=True, num_heads=4, block_q=8, block_k=8,
        interpret=True, window=12, q_offset=qo, k_offset=ko)[0])
    got = fn(jnp.int32(0), jnp.int32(0))
    np.testing.assert_allclose(got, _ref_sink(q, k, v, None, 12, 4),
                               atol=2e-6)


def test_backward_refuses_a_sink_and_unequal_widths():
    q, k, v, sink = _wide_keys(H=2, Hkv=2)
    with pytest.raises(Exception):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, num_heads=2, block_q=8, block_k=8,
            interpret=True, sink=sink).sum())(q)


@pytest.mark.parametrize("flash", ["never", "always"])
def test_op_computes_the_sink_and_widths_on_both_routes(flash):
    """``fused_multihead_attention`` with ``Sink`` and a narrower ``V``:
    the primitive route and the kernel (interpret) give the written-out
    softmax."""
    B, H, Hkv, S, Dk, Dv = 2, 4, 2, 32, 24, 16
    mk = lambda *s: RNG.randn(*s).astype(np.float32)
    q, k, v, sink = mk(B, H, S, Dk), mk(B, Hkv, S, Dk), mk(B, Hkv, S, Dv), \
        mk(H)
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            feeds = [fluid.layers.data(n, shape=list(a.shape),
                                       dtype="float32",
                                       append_batch_size=False)
                     for n, a in (("q", q), ("k", k), ("v", v),
                                  ("sink", sink))]
            out = fluid.layers.fused_multihead_attention(
                *feeds[:3], causal=True, is_test=True, window=8,
                sink=feeds[3])
        got = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"q": q, "k": k, "v": v, "sink": sink},
            fetch_list=[out])[0]
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    want = _ref_sink(jnp.asarray(q.reshape(B * H, S, Dk)),
                     jnp.asarray(k.reshape(B * Hkv, S, Dk)),
                     jnp.asarray(v.reshape(B * Hkv, S, Dv)),
                     jnp.asarray(sink), 8, H)
    assert got.shape == (B, H, S, Dv)
    np.testing.assert_allclose(got.reshape(B * H, S, Dv), want, atol=3e-6)


# -- the forward's grid: forms, heights, layouts (PR 50) ----------------------

_FORMS = ("dense", "guarded", "flat")
_HEIGHTS = (128, 256, 384, 512)
# name -> (options of the call, D, Dv, query heads, key/value heads, traced)
_MASKS = {
    "causal": (dict(), 16, 16, 2, 2),
    "window200": (dict(window=200), 16, 16, 2, 2),
    "window128_sink": (dict(window=128, sink=True), 16, 16, 2, 2),
    "kv_group2": (dict(), 16, 16, 4, 2),
    "causal_block4": (dict(causal_block=4), 16, 16, 2, 2),
    "key_bias": (dict(bias=True), 16, 16, 2, 2),
    "wide_keys": (dict(), 24, 16, 2, 2),
    "offsets": (dict(q_offset=256, k_offset=128), 16, 16, 2, 2),
    "traced_offsets": (dict(q_offset=256, k_offset=128, traced=True), 16,
                       16, 2, 2),
    # keys from position 640 on: the q-blocks before it see none
    "no_key_seen": (dict(k_offset=640), 16, 16, 2, 2),
}
_S = 1536           # whole blocks of 128, 256, 384 and 512 rows


def _fa():
    return sys.modules["paddle_tpu.kernels.flash_attention"]


@functools.lru_cache(maxsize=None)
def _mask_case(name):
    opts, D, Dv, H, Hkv = _MASKS[name]
    rng = np.random.RandomState(len(name))
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q, k, v = mk(H, _S, D), mk(Hkv, _S, D), mk(Hkv, _S, Dv)
    bias = (jnp.asarray(np.where(rng.rand(1, _S) < 0.2, -1e4, 0.0)
                        .astype(np.float32)) if opts.get("bias") else None)
    sink = mk(H) if opts.get("sink") else None
    return q, k, v, bias, sink


def _forward_as(name, form, height, lanes):
    """The forward alone of mask ``name`` at a forced form, height and
    layout: (o, lse)."""
    fa = _fa()
    opts = _MASKS[name][0]
    q, k, v, bias, sink = _mask_case(name)

    def call(q_off, k_off):
        cfg, b, scalars = fa._prepare(
            q, k, bias, True, None, 0.0, 0, q_off, k_off, q.shape[0],
            height, 128, True, opts.get("window", 0),
            opts.get("causal_block", 0))
        if sink is not None:
            cfg = dataclasses.replace(cfg, has_sink=True)
        return fa._fwd(cfg, q, k, v, b, scalars, sink, form=form,
                       lanes=lanes)

    offs = opts.get("q_offset", 0), opts.get("k_offset", 0)
    if opts.get("traced"):
        return jax.jit(call)(*map(jnp.int32, offs))
    return call(*offs)


@functools.lru_cache(maxsize=None)
def _dense_128(name):
    return _forward_as(name, "dense", 128, False)


def _grid_cases():
    for name, (opts, *_rest) in _MASKS.items():
        for form in _FORMS:
            if form == "flat" and opts.get("traced"):
                continue        # a table needs offsets known on the host
            for h in _HEIGHTS:
                for layout in ("rows", "lanes"):
                    yield pytest.param(name, form, h, layout,
                                       id=f"{name}-{form}-q{h}-{layout}")


@pytest.mark.parametrize("name,form,height,layout", list(_grid_cases()))
def test_forward_is_the_dense_128_row_grids_at_every_form_and_height(
        name, form, height, layout):
    """``o`` and ``lse`` do not depend on the grid: every form (dense,
    guarded, flat), q-block height and tile layout gives what the dense
    128 x 128 grid in the [queries, keys] layout gives, on the interpreter
    (to the last bits of f32: its programs differ, the chip's bits are
    ``tools/probe_flash_forward.py --check``'s)."""
    o, lse = _forward_as(name, form, height, layout == "lanes")
    want_o, want_lse = _dense_128(name)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-6,
                               rtol=0)
    dead = np.isneginf(np.asarray(want_lse))
    assert np.array_equal(np.isneginf(np.asarray(lse)), dead)
    np.testing.assert_allclose(np.asarray(lse)[~dead],
                               np.asarray(want_lse)[~dead], atol=5e-6,
                               rtol=0)
    if name == "no_key_seen":       # rows before the first key: zeros
        assert dead[:, :640].all() and not dead[:, 640:].any()
        assert not np.asarray(o)[:, :640].any()


@pytest.mark.parametrize("name", sorted(
    n for n, m in _MASKS.items()        # the composition takes no offsets
    if not (m[0].get("q_offset") or m[0].get("k_offset"))))
def test_dense_128_row_grid_is_the_written_out_softmax(name):
    """The yardstick of the test above against the primitive composition."""
    from paddle_tpu.ops.fused_attention import _primitive_attention

    opts = _MASKS[name][0]
    q, k, v, bias, sink = _mask_case(name)
    want = _primitive_attention(
        None, q, k, v, bias, True, q.shape[-1] ** -0.5, 0.0, True,
        opts.get("window", 0), opts.get("causal_block", 0), sink)
    np.testing.assert_allclose(np.asarray(_dense_128(name)[0]),
                               np.asarray(want), atol=3e-6, rtol=0)


@pytest.mark.parametrize("kw,label,visits", [
    # MiMo-V2-Flash's full layers (keys 192, values 128) and window layers
    (dict(sq=3584, head_dim=192, v_dim=128), "q512xk128/flat", (448, 784)),
    (dict(sq=1024, head_dim=192, v_dim=128), "q512xk128/flat", (48, 64)),
    (dict(sq=256, head_dim=192, v_dim=128), "q256xk128/dense", (4, 4)),
    (dict(sq=3584, head_dim=192, v_dim=128, window=128), "q128xk128/flat",
     (55, 784)),
    (dict(sq=256, head_dim=192, v_dim=128, window=128), "q128xk128/flat",
     (3, 4)),
    # GLM-4.7-Flash's latent prefill; SDAR's causal by blocks of 4
    (dict(sq=768, head_dim=256), "q384xk128/flat", (27, 36)),
    (dict(sq=1024, head_dim=128, causal_block=4), "q512xk128/flat",
     (48, 64)),
    # GPT-2: f32, heads of 64 (no whole lane tile): the rows layout at 128
    (dict(sq=512, head_dim=64, itemsize=4), "q128xk128/flat", (10, 16)),
    (dict(sq=128, head_dim=64, itemsize=4), "q128xk128/dense", (1, 1)),
    # Command A+: one block. A bucket no tall block divides: 128 rows
    (dict(sq=128, head_dim=128), "q128xk128/dense", (1, 1)),
    (dict(sq=640, head_dim=128), "q128xk128/flat", (15, 25)),
])
def test_the_grid_is_chosen_from_the_shape_and_the_mask(kw, label, visits):
    from paddle_tpu.kernels import flash_block_visits, flash_forward_grid

    sq = kw.pop("sq")
    assert flash_forward_grid(sq, sq, causal=True, **kw) == label
    assert flash_block_visits(sq, sq, **kw) == visits


@pytest.mark.parametrize("kw,label", [
    (dict(causal=False), "q128xk128/dense"),            # BERT's forward
    (dict(causal=True, dropout=True), "q128xk128/flat"),
    (dict(causal=True, static_offsets=False), "q512xk128/guarded"),
    (dict(causal=True, window=256), "q256xk128/flat"),
    (dict(causal=True, v_dim=64), "q128xk128/flat"),    # no whole lane tile
])
def test_what_keeps_the_forward_at_128_rows(kw, label):
    from paddle_tpu.kernels import flash_forward_grid

    assert flash_forward_grid(1024, 1024, 128, **kw) == label


def test_gradient_is_the_same_whatever_grid_the_forward_takes():
    """The backward kernels keep 128 x 128 and are handed the forward's
    residuals: the gradient of a call whose forward takes 512-row blocks
    with queries in lanes is that of the call held to 128 rows."""
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(2, 512, 128).astype(np.float32))
               for _ in range(3))
    fa = _fa()
    cfg, _, _ = fa._prepare(q, k, None, True, None, 0.0, 0, 0, 0, 1, None,
                            128, True, 0)
    tall, walk = fa._forward_grid(cfg, 512, 512, 128, 128, 4)
    assert (tall.block_q, walk.lanes, cfg.block_q) == (512, True, 128)

    def loss(block_q):
        def f(q, k, v):
            o, lse = flash_attention_with_lse(
                q, k, v, causal=True, interpret=True, block_q=block_q)
            return jnp.sum(o * o) + jnp.sum(jnp.sin(lse))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(loss(None), loss(128)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=1e-5)


def test_a_table_too_long_for_smem_walks_the_guarded_grid():
    fa = _fa()
    cfg = fa._shape_cfg(128 * 200, 128 * 200, True, 0, 0, 128, 128)
    _, walk = fa._forward_grid(cfg, 128 * 200, 128 * 200, 128, 128, 2)
    assert walk.form == "guarded" and walk.steps == 200


@pytest.mark.parametrize("S,window,use_bias,label", [
    (1024, 0, False, "q512xk128/flat"), (256, 128, False, "q128xk128/flat"),
    (1024, 0, True, "q512xk128/flat"), (128, 0, True, "q128xk128/dense")])
def test_op_notes_the_grid_its_forward_takes(S, window, use_bias, label):
    """``kernel_route_total{op="fused_multihead_attention.grid"}``: the
    q-block's height and the form of the walk, a lowering."""
    B, H, D = 1, 2, 128
    mk = lambda *s: RNG.randn(*s).astype(np.float32)
    feed = {"q": mk(B, H, S, D), "k": mk(B, H, S, D), "v": mk(B, H, S, D)}
    if use_bias:
        feed["bias"] = np.zeros((B, S), np.float32)
    fluid.set_flags({"FLAGS_use_flash_attention": "always"})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            data = {n: fluid.layers.data(n, shape=list(a.shape),
                                         dtype="float32",
                                         append_batch_size=False)
                    for n, a in feed.items()}
            out = fluid.layers.fused_multihead_attention(
                data["q"], data["k"], data["v"], bias_qk=data.get("bias"),
                causal=True, is_test=True, window=window)
        got = fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                                   fetch_list=[out])[0]
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    assert _routes(main, grid=True) == {
        ("fused_multihead_attention.grid", label): 1}
    flat = lambda t: jnp.asarray(t.reshape(B * H, S, D))
    want = _ref_sink(flat(feed["q"]), flat(feed["k"]), flat(feed["v"]),
                     None, window, H)
    np.testing.assert_allclose(got.reshape(B * H, S, D), want, atol=3e-6)
