"""Decode-step attention over a LATENT cache (Pallas TPU): the absorbed
form of multi-head latent attention.

A latent-attention layer keeps one row a token for all of its heads:
``[c | k_rope]``, the normed compression of the token (``kv_lora_rank``
wide) beside one rotary key (``qk_rope_head_dim``). Keys and values are
linear in ``c`` (``k_nope_i = c W_UK_i``, ``v_i = c W_UV_i``), so the
decode step never expands them: with ``qt_i = q_nope_i W_UK_i^T``,

    score_i[j] = (qt_i . c_j + q_rope_i . k_rope_j) * scale
    u_i        = softmax(score_i) . c          (then o_i = u_i W_UV_i)

and the up-projections stay outside this module (``ops/latent_attention``).
What is left is attention in which keys and values are ONE buffer: the key
is the whole row, the value its ``c`` part, and every head of a sequence
reads the same rows. :func:`mla_decode_attention` fetches a block of rows
once and serves ``q . k`` over the whole row and ``p . v`` over its ``c``
part from it; the heads ride the sublanes of one call, as the query heads
of a group do in ``decode_attention`` (``group=``). ``decode_attention``
itself takes two caches of one width and would fetch every row twice.

The row lies in memory as ONE array ``[B, S_max, W]`` with ``W`` the row's
``dc + dr`` numbers padded with zeros to whole 128-lane tiles
(:func:`latent_row_width`: 640 for 512 + 64). The query rides the same
lanes, ``[qt | q_rope | 0]``, so the score is one product over ``W`` and
the value the row's first ``dc`` lanes. One array is one append a token and
layer, which is what decides it: kept as ``[.., dc]`` beside a ``[.., dr]``
array stored rows-minor (nothing padded, 10% fewer bytes to hold and to
walk) a decode step paid two row-by-row append loops a layer, the second a
column write, and those loops, not the walk, were half of the step
(PERF.md section 6, PR 33).

The walk is ``decode_attention``'s: per-sequence lengths as scalar
prefetch, a sequence's blocks up to its last live one
(:func:`~.decode_attention.last_live_block`), the index maps repeating that
block after it so that nothing past a length is fetched, the tail of the
last live block masked. Operands in the cache's type, scores, softmax and
the accumulator f32. ``interpret=True`` runs the kernel on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import decode_walk_blocks, kv_tile, last_live_block
from .flash_attention import _PALLAS_SCOPE, NEG_INF, _out_sds

__all__ = ["mla_decode_attention", "mla_decode_attention_reference",
           "latent_row_width", "latent_block_rows", "latent_walk_blocks"]


def latent_row_width(latent_dim: int, rope_dim: int) -> int:
    """Lanes of a latent cache's row: ``[c | k_rope]`` padded to whole
    128-lane tiles."""
    return -(-(latent_dim + rope_dim) // 128) * 128


def latent_block_rows(s_max: int, width: int, dtype, page_size: int) -> int:
    """Rows of a latent cache one grid step carries:
    ``decode_attention.kv_tile``'s rule for ONE head whose row of
    ``width`` lanes is fetched once."""
    return kv_tile(1, s_max, width, dtype, page_size,
                   row_bytes=width * jnp.dtype(dtype).itemsize)[1]


def latent_walk_blocks(lengths, cache_shape, dtype, page_size: int):
    """``decode_attention.decode_walk_blocks`` for a latent cache
    ``cache_shape`` [B, 1, S_max, W]: ``(fetched, capacity)`` k-blocks of
    one call for sequences of ``lengths``."""
    _, _, S, W = cache_shape
    return decode_walk_blocks(
        lengths, cache_shape, dtype, page_size,
        rows=latent_block_rows(S, W, dtype, page_size))


def mla_decode_attention_reference(q, cache, lengths, latent_dim: int,
                                   scale):
    """Primitive oracle, and the op's route off the TPU. ``q`` [B, heads,
    W]: a head's ``[absorbed query | rotary query | 0]``; ``cache``
    [B, S_max, W]; ``lengths`` [B] (keys a sequence's query sees; 0: none,
    the output is 0) -> ``u`` [B, heads, latent_dim] in ``q``'s type."""
    prec = "highest" if q.dtype == jnp.float32 else "default"
    rows = cache.astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows,
                   precision=prec) * scale
    seen = jnp.arange(cache.shape[1])[None, None, :] < \
        lengths[:, None, None]
    # a sequence that sees no key scores nothing: its row is 0
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1),
                  0.0)
    u = jnp.einsum("bhk,bkc->bhc", p, rows[..., :latent_dim], precision=prec)
    return u.astype(q.dtype)


def _mla_kernel(scale, dc, len_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc):
    b, ik = pl.program_id(0), pl.program_id(1)
    num_k = pl.num_programs(1)
    block_k = c_ref.shape[1]
    length = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    # a block past the last live one was not fetched and is not scored
    @pl.when(ik <= last_live_block(length, 1, block_k, num_k))
    def _walk():
        rows = c_ref[0]                               # [block_k, W]
        s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alive = m_new > NEG_INF * 0.5
        m_safe = jnp.where(alive, m_new, 0.0)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(rows.dtype), rows[:, :dc],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_k - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@jax.named_scope(_PALLAS_SCOPE)
def mla_decode_attention(q, cache, lengths, *, latent_dim: int, scale: float,
                         page_size: int = 128, block_rows: int = None,
                         interpret: bool = False):
    """One decode step of absorbed latent attention: ``q`` [B, heads, W]
    (a sequence's heads, each ``[absorbed query | rotary query | 0]``)
    against the sequence's latent cache ``cache`` [B, S_max, W], rows
    ``[c | k_rope | 0]`` with ``c`` the first ``latent_dim`` lanes.
    ``lengths`` [B] int: the keys the query sees (its own row appended
    before the call); 0 sees none and gives 0. Returns ``u`` [B, heads,
    latent_dim] = ``softmax(q . row * scale) . c`` in ``q``'s type.
    ``page_size`` is the cache's page; ``block_rows`` overrides
    :func:`latent_block_rows` (the probes sweep it). Inference only."""
    B, H, W = q.shape
    S, dc = cache.shape[1], int(latent_dim)
    if cache.shape != (B, S, W) or not 0 < dc <= W or \
            S % min(page_size, S):
        raise ValueError(
            f"mla_decode_attention: q {q.shape} against a cache "
            f"{cache.shape} in pages of {page_size}, latent_dim {dc}")
    bk = block_rows or latent_block_rows(S, W, cache.dtype, page_size)
    nk = S // bk
    lengths = jnp.asarray(lengths).reshape(B).astype(jnp.int32)
    # the heads in whole sublane tiles of the operand type (padding rows
    # are replicas of the last head; their output is sliced away)
    tile = 8 * (4 // q.dtype.itemsize)
    R = -(-H // tile) * tile
    if R != H:
        q = jnp.concatenate(
            [q, jnp.broadcast_to(q[:, -1:], (B, R - H, W))], axis=1)

    def live(b, ik, lens):
        return jnp.minimum(ik, last_live_block(lens[b], 1, bk, nk))

    q_spec = lambda d: pl.BlockSpec((1, R, d), lambda b, ik, lens: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[q_spec(W),
                  pl.BlockSpec((1, bk, W),
                               lambda b, ik, lens: (b, live(b, ik, lens),
                                                    0))],
        out_specs=[q_spec(dc)],
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),      # running max
            pltpu.VMEM((R, 128), jnp.float32),      # running denominator
            pltpu.VMEM((R, dc), jnp.float32),       # numerator accumulator
        ],
    )
    (u,) = pl.pallas_call(
        functools.partial(_mla_kernel, float(scale), dc),
        grid_spec=grid_spec,
        out_shape=[_out_sds((B, R, dc), q.dtype, q, cache)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode_attention",
    )(lengths, q, cache)
    return u[:, :H]
