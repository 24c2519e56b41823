"""Flash-attention Pallas kernel + fused_multihead_attention op.

CPU suite runs the kernel via the pallas interpreter (dropout excluded —
the TPU PRNG has no interpret lowering); the `tpu` marker cases cover the
compiled Mosaic path including in-kernel dropout. Oracle: the primitive
softmax composition (which is also the op's off-TPU lowering), matching
reference semantics of fused attention (operators/fused/ role)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
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
