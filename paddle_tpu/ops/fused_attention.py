"""fused_multihead_attention: the `operators/fused/` role on TPU.

The reference ships hand-fused kernels where op-by-op execution leaves
performance on the table (reference: paddle/fluid/operators/fused/
fused_embedding_fc_lstm_op.cc, fusion_lstm_op.cc; the xbyak JIT framework
operators/jit/kernel_base.h). On TPU the one attention-shaped fusion XLA
cannot do itself — never materialising the [S, S] score matrix — is the
Pallas flash-attention kernel (kernels/flash_attention.py). This op routes:

- step lowered for a TPU (``lowering.lowering_platform``) + supported
  shapes -> compiled Pallas kernel (in-kernel PRNG dropout, online
  softmax, two-kernel flash backward);
- anything else -> an equivalent primitive composition that XLA fuses as
  well as it can (and which serves as the numerics oracle in tests).

`FLAGS_use_flash_attention` = auto|always|never picks the path explicitly;
`always` off-TPU runs the kernel in interpret mode (slow — test use only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import IOSpec, register_op, x
from .. import flags
from ..lowering import (amp_cast_ins, generic_grad, lowering_platform,
                        note_kernel_route)


def _route(sq: int, sk: int, dropout: float, platform=None) -> str:
    """'pallas' | 'pallas-interpret' | 'primitive' for a step lowered for
    ``platform``."""
    from ..kernels import classify_shapes

    mode = flags.flag("use_flash_attention")
    if mode == "never":
        return "primitive"
    kind, reason = classify_shapes(sq, sk)
    if kind == "unsupported":
        if mode == "always":
            raise ValueError(
                f"FLAGS_use_flash_attention=always but seq lengths "
                f"({sq}, {sk}) have no kernel tiling: {reason}")
        return "primitive"
    if platform == "tpu":
        return "pallas"
    if mode == "always":
        if dropout > 0.0:
            # loud, not a silent primitive fallback: 'always' is a promise
            # that the kernel runs, and the TPU PRNG the in-kernel dropout
            # needs has no interpret-mode lowering
            raise NotImplementedError(
                "FLAGS_use_flash_attention=always with attn_dropout>0 "
                "requires a step lowered for a TPU (in-kernel PRNG "
                "dropout)")
        return "pallas-interpret"
    return "primitive"


def _primitive_attention(ctx, q, k, v, bias, causal, scale, dropout,
                         is_test, window=0, causal_block=0, sink=None):
    """[BH, S, D] oracle path; matches the kernel semantics exactly.
    ``sink`` [heads]: a head's scalar as one more column of its softmax,
    which carries no value."""
    prec = ("highest" if q.dtype == jnp.float32 else "default")
    if k.shape[0] != q.shape[0]:            # grouped-query heads
        G = q.shape[0] // k.shape[0]
        k, v = jnp.repeat(k, G, axis=0), jnp.repeat(v, G, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q, k, precision=prec) * scale
    if bias is not None:
        H = q.shape[0] // bias.shape[0]
        s = s + jnp.repeat(bias.astype(s.dtype), H, axis=0)[:, None, :]
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qi, kj = jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
        if causal_block:        # a query stands at its block's last row
            qi = (qi // causal_block + 1) * causal_block - 1
        d = qi - kj
        m = (d >= 0) & (d < window) if window else d >= 0
        s = jnp.where(m[None], s, jnp.asarray(-1e30, s.dtype))
    if sink is not None:
        col = jnp.tile(sink.astype(s.dtype), q.shape[0] // sink.shape[0])
        s = jnp.concatenate(
            [s, jnp.broadcast_to(col[:, None, None], s.shape[:2] + (1,))],
            axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :k.shape[1]]
    if dropout > 0.0 and not is_test:
        keep = jax.random.bernoulli(ctx.rng(), 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v, precision=prec)


class _Plan:
    """What the forward rule and the gradient rule both read off one op
    instance: checked shapes and resolved options."""

    def __init__(self, ins, attrs):
        q, k = x(ins, "Q"), x(ins, "K")
        self.B, self.H, self.Sq, self.D = q.shape
        self.Hkv, self.Sk = k.shape[1], k.shape[2]
        self.Dv = x(ins, "V").shape[3]
        self.scale = attrs["scale"] or float(self.D) ** -0.5
        self.is_test = bool(attrs.get("is_test"))
        self.dropout = 0.0 if self.is_test else float(attrs["attn_dropout"])
        self.causal = bool(attrs["causal"])
        self.window = int(attrs.get("window") or 0)
        self.causal_block = int(attrs.get("causal_block") or 0)
        self.ring = bool(attrs.get("sequence_parallel"))
        H, Hkv, window = self.H, self.Hkv, self.window
        if H % Hkv or (window and not self.causal):
            raise ValueError(
                f"fused_multihead_attention: {H} query heads over {Hkv} "
                f"key/value heads; window={window} needs causal")
        if self.causal_block and (window or not self.causal):
            raise ValueError(
                f"fused_multihead_attention: causal_block="
                f"{self.causal_block} needs causal and no window")
        if self.ring and (window or Hkv != H or self.causal_block):
            raise NotImplementedError(
                "sequence_parallel attention with a window, grouped-query "
                "heads or a block-causal mask: the ring path carries none")

    def rides_the_ring(self, mesh) -> bool:
        """sequence_parallel under a mesh with a real 'sp' axis; without
        one a 1-shard ring IS plain attention."""
        return (self.ring and mesh is not None and "sp" in mesh.axis_names
                and mesh.shape["sp"] > 1)

    def route(self, ctx) -> str:
        return _route(self.Sq, self.Sk, self.dropout,
                      platform=lowering_platform(ctx))

    def kernel_options(self, route) -> dict:
        return dict(causal=self.causal, scale=self.scale,
                    dropout=self.dropout, window=self.window,
                    causal_block=self.causal_block,
                    interpret=(route == "pallas-interpret"))


def _key_bias(ins):
    """BiasQK as the [B, S] the attention paths take."""
    bias = x(ins, "BiasQK")
    if bias is not None and bias.ndim == 4:          # [B, 1, 1, S]
        return bias.reshape(bias.shape[0], bias.shape[-1])
    if bias is not None and bias.ndim != 2:
        raise ValueError(
            f"BiasQK must be [B, S] or [B, 1, 1, S], got {bias.shape}")
    return bias


def _op_seed(ctx):
    """Deterministic seed tied to the forward op instance: the grad op's
    ctx carries the forward uid, so the backward kernels regenerate the
    forward's dropout masks."""
    return jax.lax.convert_element_type(
        jax.random.bits(ctx.rng(), (), jnp.uint32) >> 1, jnp.int32)


def _run_kernel(ctx, kernel, blocks, out_ranks):
    """One device, or already inside a shard_map body (a pipeline stage):
    the kernel sees its own block. Under a mesh: per shard."""
    seed = _op_seed(ctx)
    if ctx.mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(seed, *blocks)
    return _kernel_on_mesh(kernel, ctx.mesh, seed, blocks, out_ranks)


def _fused_mha_grad(ctx, ins, attrs):
    """Gradients of Q, K, V from the residuals the forward op kept. Where
    the forward ran the flash kernel and its ``SoftmaxLse`` is at hand, the
    two backward kernels take ``__out__Out`` and that log-sum-exp: no
    forward call. Anywhere else (the primitive route, ring attention, a
    program built before the op had the output) the forward rule is
    differentiated as for any op (``lowering.generic_grad``)."""
    plan = _Plan(ins, attrs)
    o, lse, do = (x(ins, "__out__Out"), x(ins, "__out__SoftmaxLse"),
                  x(ins, "Out@GRAD"))
    route = "primitive"
    if all(t is not None for t in (o, lse, do)) \
            and not plan.rides_the_ring(ctx.mesh):
        route = plan.route(ctx)
    note_kernel_route(ctx, "fused_multihead_attention_grad", route)
    if route == "primitive":
        return generic_grad(ctx, "fused_multihead_attention", ins, attrs)

    # the operands as the forward rule saw them; the gradients go back to
    # the types the program holds
    seen = amp_cast_ins(ctx, "fused_multihead_attention",
                        {s: ins[s] for s in ("Q", "K", "V", "BiasQK")
                         if s in ins})
    kernel = functools.partial(_kernel_attention_bwd,
                               **plan.kernel_options(route))
    grads = _run_kernel(
        ctx, kernel,
        [x(seen, "Q"), x(seen, "K"), x(seen, "V"), _key_bias(seen), o, lse,
         do.astype(o.dtype).reshape(o.shape)],
        out_ranks=(4, 4, 4))
    return {s + "@GRAD": [g.astype(x(ins, s).dtype)]
            for s, g in zip(("Q", "K", "V"), grads)}


@register_op("fused_multihead_attention",
             inputs=[IOSpec("Q"), IOSpec("K"), IOSpec("V"),
                     IOSpec("BiasQK", optional=True, no_grad=True),
                     IOSpec("Sink", optional=True, no_grad=True)],
             outputs=["Out",
                      IOSpec("SoftmaxLse", optional=True, no_grad=True)],
             attrs={"causal": False, "scale": 0.0, "attn_dropout": 0.0,
                    "is_test": False, "sequence_parallel": False,
                    "window": 0, "causal_block": 0},
             needs_rng=True, grad_lower=_fused_mha_grad)
def _fused_mha(ctx, ins, attrs):
    """Q/K/V: [B, num_heads, S, head_dim]. BiasQK: additive key bias,
    [B, S] or [B, 1, 1, S] (the models/bert.py padding-mask encoding).
    scale 0.0 means 1/sqrt(head_dim).

    ``SoftmaxLse`` ([B, num_heads, S], float32; optional) is the softmax's
    log-sum-exp per query row, kept for the gradient op as ``layer_norm``
    keeps ``Mean``/``Variance``. Only the flash-kernel routes emit it (the
    kernel writes it anyway); ``fused_multihead_attention_grad`` reads it
    with ``Out`` and then runs the backward kernels alone. On the
    primitive route, under ring attention, or in a program whose op lacks
    the output, the gradient differentiates this rule instead
    (``kernel_route_total{op="fused_multihead_attention_grad"}`` says
    which, per program).

    ``sequence_parallel=True`` lowers onto ring attention over the mesh's
    'sp' axis (parallel/ring_attention.py — K/V blocks rotate via
    lax.ppermute, the online-softmax state combines across ring steps):
    the context-parallel long-sequence path, reachable from the fluid API
    instead of only from the parallel package (VERDICT r4 item 8).

    ``K``/``V`` may carry a whole fraction of ``Q``'s heads (grouped-query
    attention, inference only): query head ``n`` reads key/value head
    ``n // group``. ``window`` > 0 (with ``causal``) is a sliding window:
    key ``j`` is visible to query ``i`` iff ``0 <= i - j < window``.
    ``causal_block`` = L > 0 (with ``causal``, no window) is the mask of a
    block-diffusion prefill, causal by blocks of L rows: key ``j`` is
    visible to query ``i`` iff ``j // L <= i // L``.

    ``V`` may be [B, heads, S, Dv] with another width than the keys'
    (``Out`` is then [B, num_heads, S, Dv]). ``Sink`` [num_heads] float32
    (optional): a head's scalar joins its softmax as one more column that
    carries no value. Both inference only, like grouped-query heads."""
    q, k, v = x(ins, "Q"), x(ins, "K"), x(ins, "V")
    plan = _Plan(ins, attrs)
    B, H, Sq, D = q.shape
    sink = x(ins, "Sink")
    if plan.rides_the_ring(ctx.mesh) and (sink is not None
                                          or plan.Dv != D):
        raise NotImplementedError(
            "sequence_parallel attention with a sink or values of another "
            "width than the keys: the ring path carries neither")

    if plan.rides_the_ring(ctx.mesh):
        if x(ins, "BiasQK") is not None:
            raise NotImplementedError(
                "sequence_parallel attention with BiasQK: fold padding "
                "into the sequence instead — the ring path has no "
                "global [B, S] bias plumbing yet")
        if plan.dropout > 0.0:
            raise NotImplementedError(
                "sequence_parallel attention with attn_dropout>0: the "
                "ring path's per-block kernels do not coordinate a "
                "global dropout mask")
        from ..parallel.ring_attention import ring_attention

        o = ring_attention(q.transpose(0, 2, 1, 3),
                           k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3),
                           ctx.mesh, seq_axis="sp", causal=plan.causal,
                           scale=plan.scale)
        return {"Out": [o.transpose(0, 2, 1, 3)]}

    route = plan.route(ctx)
    note_kernel_route(ctx, "fused_multihead_attention", route)
    if plan.causal_block:
        note_kernel_route(ctx, "fused_multihead_attention.causal_block",
                          route)
    if route == "primitive":
        o = _primitive_attention(ctx, q.reshape(B * H, Sq, D),
                                 k.reshape(B * plan.Hkv, plan.Sk, D),
                                 v.reshape(B * plan.Hkv, plan.Sk, plan.Dv),
                                 _key_bias(ins), plan.causal, plan.scale,
                                 plan.dropout, plan.is_test, plan.window,
                                 plan.causal_block, sink)
        return {"Out": [o.reshape(B, H, Sq, plan.Dv)]}

    from ..kernels import flash_forward_grid

    # the grid the forward builds for this shape and mask: its q-block's
    # height and how it walks the (q-block, k-block) pairs
    note_kernel_route(
        ctx, "fused_multihead_attention.grid", flash_forward_grid(
            Sq, plan.Sk, D, plan.Dv, q.dtype.itemsize, causal=plan.causal,
            window=plan.window, causal_block=plan.causal_block,
            dropout=plan.dropout > 0.0))
    kernel = functools.partial(_kernel_attention,
                               **plan.kernel_options(route))
    o, lse = _run_kernel(ctx, kernel, [q, k, v, _key_bias(ins), sink],
                         out_ranks=(4, 3))
    return {"Out": [o], "SoftmaxLse": [lse]}


def _kernel_attention(seed, q, k, v, bias=None, sink=None, *, causal, scale,
                      dropout, interpret, window=0, causal_block=0):
    """The flash kernel over one [B, H, S, D] block (bias [B, Sk], sink
    [H]): the output and its log-sum-exp [B, H, Sq]."""
    from ..kernels import flash_attention_with_lse

    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    o, lse = flash_attention_with_lse(
        q.reshape(B * H, Sq, D), k.reshape(B * Hkv, Sk, D),
        v.reshape(B * Hkv, Sk, Dv), bias=bias, causal=causal, scale=scale,
        dropout_rate=dropout, seed=seed, num_heads=H, interpret=interpret,
        window=window, causal_block=causal_block, sink=sink)
    return o.reshape(B, H, Sq, Dv), lse.reshape(B, H, Sq)


def _kernel_attention_bwd(seed, q, k, v, bias, o, lse, do, *, causal, scale,
                          dropout, interpret, window=0, causal_block=0):
    """The two backward kernels over one [B, H, S, D] block, from the
    forward's saved ``o`` and ``lse``: (dQ, dK, dV)."""
    from ..kernels import flash_attention_bwd

    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    dq, dk, dv = flash_attention_bwd(
        q.reshape(B * H, Sq, D), k.reshape(B * Hkv, Sk, D),
        v.reshape(B * Hkv, Sk, D), o.reshape(B * H, Sq, D),
        lse.reshape(B * H, Sq), do.reshape(B * H, Sq, D), bias=bias,
        causal=causal, scale=scale, dropout_rate=dropout, seed=seed,
        num_heads=H, interpret=interpret, window=window,
        causal_block=causal_block)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _kernel_on_mesh(kernel, mesh, seed, blocks, out_ranks):
    """Under a mesh the step is partitioned by GSPMD, and a Mosaic kernel
    cannot be ("Mosaic kernels cannot be automatically partitioned" — the
    first thing the four-chip host said): run it per shard under
    ``shard_map``. Attention is independent per batch row and per head, so
    the batch splits over 'dp' and the heads over 'tp' — the Megatron
    layout the q/k/v projections already produce — and no collective is
    needed; an axis that does not divide its dim stays unsplit.

    ``blocks`` are ``kernel``'s operands after the seed, ``None`` where an
    optional one is absent; ``out_ranks`` the ranks of its results. A
    [B, H, ...] operand follows the batch and the heads, a [B, S] bias the
    batch, an [H] sink the heads."""
    B, H = blocks[0].shape[:2]

    def axis(name, dim):
        return name if name in mesh.axis_names \
            and dim % mesh.shape[name] == 0 else None

    b_ax, h_ax = axis("dp", B), axis("tp", H)

    def spec(rank):
        if rank == 1:
            return P(h_ax)
        return P(b_ax, None) if rank == 2 \
            else P(b_ax, h_ax, *([None] * (rank - 2)))

    present = [b for b in blocks if b is not None]

    def local(seed, *shards):
        # a shard-local seed: shards must not share dropout masks
        for ax, mix in ((b_ax, 0x9E3779B1), (h_ax, 0x85EBCA77)):
            if ax is not None:
                seed = seed ^ (jax.lax.axis_index(ax).astype(jnp.int32)
                               * jnp.int32(mix & 0x7FFFFFFF))
        shards = iter(shards)
        return kernel(seed, *[None if b is None else next(shards)
                              for b in blocks])

    # check_vma off: the kernel's scalar operands vary per shard (see
    # parallel/ring_attention.py)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), *[spec(b.ndim) for b in present]),
        out_specs=tuple(spec(r) for r in out_ranks),
        check_vma=False)(seed, *present)
