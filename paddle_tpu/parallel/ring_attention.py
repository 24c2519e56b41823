"""Ring attention: sequence/context parallelism over a mesh axis.

The reference (2019) has NO long-context story beyond LoD packing
(SURVEY §5); this is the capability-parity-PLUS item the TPU rebuild adds:
attention over sequences sharded across chips, K/V blocks rotating around
the ICI ring (`jax.lax.ppermute` lowers to collective-permute on TPU; the
same code runs on the CPU test mesh), with flash-style ONLINE softmax —
running max + denominator — so no chip ever materialises the full
[T, T] score matrix or the gathered K/V. Memory per chip is O(T_local),
enabling sequences P times longer than single-chip attention.

Layout: q/k/v are [batch, seq, heads, head_dim] sharded on `seq` over the
ring axis. Causal masking uses GLOBAL positions reconstructed from the
ring step, so results equal single-device causal attention exactly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_local", "attention_reference"]


def ring_attention_local(q, k, v, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None,
                         use_flash: Optional[bool] = None,
                         platform: Optional[str] = None):
    """The per-shard body — call inside shard_map over ``axis_name``.

    q, k, v: [B, T_local, H, D] local chunks. Returns [B, T_local, H, D].

    ``use_flash`` routes the per-block attention through the Pallas flash
    kernel (kernels/flash_attention.py) — the same kernel as
    fused_multihead_attention — combining ring steps through each block's
    log-sum-exp instead of carrying (m, l) explicitly. ``platform`` is the
    platform of the mesh this body is shard_mapped over
    (``lowering.lowering_platform(mesh=...)`` — the body cannot see the
    mesh itself). None = auto: kernel on a TPU mesh when the local block
    shapes divide its tiles, jnp math elsewhere (the CPU test mesh keeps
    the einsum path — Pallas interpret inside shard_map is slow and
    PRNG-free anyway); forcing ``use_flash=True`` off a TPU mesh runs the
    kernel in the Pallas interpreter (tests only).
    """
    if use_flash is None:
        from ..kernels import supports_shapes

        use_flash = (platform == "tpu"
                     and supports_shapes(q.shape[1], k.shape[1]))
    if use_flash:
        return _ring_attention_local_flash(q, k, v, axis_name, causal, scale,
                                           interpret=platform != "tpu")
    return _ring_attention_local_jnp(q, k, v, axis_name, causal, scale)


def _ring_attention_local_flash(q, k, v, axis_name: str, causal: bool,
                                scale: Optional[float], interpret: bool):
    """Ring body where each block product is one flash-kernel call.

    Blocks combine by log-sum-exp re-weighting: for partials (o_a, lse_a)
    and (o_b, lse_b) over disjoint key sets, lse = logaddexp and
    o = o_a*exp(lse_a-lse) + o_b*exp(lse_b-lse). The kernel honours the
    lse cotangent, so jax.grad through the whole ring is exact."""
    from ..kernels import flash_attention_with_lse

    B, Tl, H, D = q.shape
    P_ = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % P_) for i in range(P_)]

    # kernel layout is [B*H, T, D] head-major; transpose ALL of q/k/v once
    # up front and rotate k/v around the ring already head-major (ppermute
    # is layout-agnostic), so no per-step transpose copies
    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, Tl, D)

    qh, k, v = to_bh(q), to_bh(k), to_bh(v)

    def block(kb, vb, s):
        src = (my - s) % P_                      # owner of this k/v block
        o_s, lse_s = flash_attention_with_lse(
            qh, kb, vb, causal=causal, scale=scale,
            q_offset=my * Tl, k_offset=src * Tl, num_heads=H,
            interpret=interpret)
        return o_s, lse_s

    def combine(o, lse, o_s, lse_s):
        lse_new = jnp.logaddexp(lse, lse_s)
        # fully-masked-so-far rows: lse == lse_new == -inf -> weight 0
        w = jnp.where(jnp.isfinite(lse), jnp.exp(lse - lse_new), 0.0)
        w_s = jnp.where(jnp.isfinite(lse_s), jnp.exp(lse_s - lse_new), 0.0)
        o_new = o * w[..., None] + o_s * w_s[..., None]
        return o_new, lse_new

    o0, lse0 = block(k, v, 0)
    kb = jax.lax.ppermute(k, axis_name, perm)
    vb = jax.lax.ppermute(v, axis_name, perm)

    def step(carry, s):
        o, lse, kb, vb = carry
        o_s, lse_s = block(kb, vb, s)
        o, lse = combine(o, lse, o_s, lse_s)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, lse, kb, vb), None

    if P_ > 2:
        (o, lse, kb, vb), _ = jax.lax.scan(
            step, (o0, lse0, kb, vb), jnp.arange(1, P_ - 1))
    else:
        o, lse = o0, lse0
    if P_ > 1:
        o_s, lse_s = block(kb, vb, P_ - 1)     # last block: no dead permute
        o, lse = combine(o, lse, o_s, lse_s)
    return o.reshape(B, H, Tl, D).transpose(0, 2, 1, 3)


def _ring_attention_local_jnp(q, k, v, axis_name: str, causal: bool = False,
                              scale: Optional[float] = None):
    """Einsum ring body (runs anywhere, incl. the 8-device CPU test mesh)."""
    B, Tl, H, D = q.shape
    P_ = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q = q * scale

    neg = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)
    # accumulators derive from q so they inherit its varying-axes type on
    # ANY mesh (shard_map vma tracking: a fresh jnp.zeros would be
    # unvaried and mismatch the scan carry after the ppermute)
    zero_qh = q.sum(axis=-1) * 0.0                     # [B, Tl, H]
    m0 = zero_qh + neg                                 # running max
    l0 = zero_qh                                       # running denom
    o0 = q * 0.0                                       # numerator acc
    perm = [(i, (i + 1) % P_) for i in range(P_)]

    q_pos = my * Tl + jnp.arange(Tl)                   # global q positions

    def block_update(m, l, o, kb, vb, s):
        src = (my - s) % P_                            # owner of this block
        k_pos = src * Tl + jnp.arange(Tl)
        # scores: [B, Tl(q), H, Tl(k)]
        scores = jnp.einsum("bqhd,bkhd->bqhk", q, kb)
        valid = jnp.ones((Tl, Tl), bool)
        if causal:
            valid = q_pos[:, None] >= k_pos[None, :]   # [Tq, Tk] global
            scores = jnp.where(valid[None, :, None, :], scores, neg)
        blk_max = scores.max(axis=-1)                  # [B, Tq, H]
        m_new = jnp.maximum(m, blk_max)
        # fully-masked rows keep m == neg; their corr/p must be 0 or
        # exp(neg - neg)=1 would average masked-out values in
        alive = m_new > neg
        corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
        p = jnp.exp(scores - m_new[..., None])
        p = p * (valid[None, :, None, :] & alive[..., None])
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, vb)
        return m_new, l_new, o_new

    def step(carry, s):
        m, l, o, kb, vb = carry
        m, l, o = block_update(m, l, o, kb, vb, s)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (m, l, o, kb, vb), None

    # scan P-1 rotating steps, then peel the LAST block without the two
    # dead trailing ppermutes (the rotated K/V would be discarded)
    (m, l, o, kb, vb), _ = jax.lax.scan(step, (m0, l0, o0, k, v),
                                        jnp.arange(P_ - 1))
    m, l, o = block_update(m, l, o, kb, vb, P_ - 1)
    return o / jnp.maximum(l, 1e-20)[..., None]


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   use_flash: Optional[bool] = None):
    """shard_map wrapper: q/k/v [B, T, H, D] (global); T shards over
    ``seq_axis``, batch over 'dp' when the mesh has one."""
    from ..lowering import lowering_platform

    batch_axis = "dp" if "dp" in mesh.axis_names else None
    spec = P(batch_axis, seq_axis, None, None)
    platform = lowering_platform(mesh=mesh)

    if use_flash is None:
        from ..kernels import supports_shapes

        n_sp = mesh.shape[seq_axis]
        t_local = q.shape[1] // n_sp
        use_flash = (platform == "tpu"
                     and supports_shapes(t_local, t_local))
    # check_vma=False on the flash path: the kernel's scalar operands
    # (global position offsets) legitimately vary over the ring axis, which
    # the vma checker's pallas handling rejects
    fn = jax.shard_map(
        partial(ring_attention_local, axis_name=seq_axis, causal=causal,
                scale=scale, use_flash=use_flash, platform=platform),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not use_flash)
    # eager dispatches ride the ICI ring (P ppermute rotations) — the one
    # collective in the stack with no deadline until now; armed so a stuck
    # permute is dumped + raised under FLAGS_step_timeout_s (inside a jit
    # trace this wraps only host-side trace work and disarms immediately)
    from ..resilience.distributed import (block_until_ready_concrete,
                                          watchdog_section)

    from ..resilience.elastic import device_loss_classification

    # a dead ring rank surfaces here as an untyped runtime error — the
    # shared wrapper classifies it typed so the elastic path can act
    with watchdog_section("collective",
                          detail=f"ring_attention over '{seq_axis}'") \
            as tok, device_loss_classification("collective"):
        out = fn(q, k, v)
        if tok is not None:
            # async dispatch: arm through device completion (no-op when
            # called inside a jit trace; real runtime errors propagate)
            block_until_ready_concrete(out)
        return out


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None):
    """Dense single-device attention (the correctness oracle)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bqhk", q * scale, k)
    if causal:
        T = q.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, :, None, :], scores,
                           jnp.finfo(q.dtype).min)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v)
