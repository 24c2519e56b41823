"""Decode-step flash attention over a paged/block KV cache (Pallas TPU).

The autoregressive-serving counterpart of ``flash_attention.py``: a short
*chunk* of query tokens per sequence (1 <= q_len <= 8) attends against that
sequence's KV cache. q_len == 1 is the classic decode step; q_len > 1 is
the chunked-prefill slice and the speculative-verify chunk (ISSUE 20),
where query row ``i`` is the token at cache position ``length - 1 + i`` and
may see exactly ``length + i`` keys (causal *within* the chunk, since the
chunk's own K rows are appended before the walk). The cache is *paged* —
logically ``[BH, S_max, D]`` where ``S_max = num_pages * page_size`` and
the kernel walks it one page (``block_k = page_size``) at a time with the
same online-softmax recurrence as the prefill kernel, masking key positions
``>= length + row`` per sequence and query row.
Pages past a sequence's length hold stale/garbage rows by design (they are
overwritten when the sequence reaches them); the length mask keeps them out
of the softmax, so cache capacity can be provisioned once and reused across
requests at different positions.

CODA (PAPERS.md, arXiv 2605.19269) motivates folding the decode-step
epilogue work into one op instead of separate ones. The fold is made one
level up, in the op rule (``ops/generation.py`` ``fused_decode_attention``):
it appends the chunk's K/V rows with :func:`paged_kv_append_rows` and then
calls :func:`flash_attention_decode` on the updated caches, so the
program-IR level sees ONE op that reads and writes the cache at the same
index (which is what lets ``analysis.liveness.safe_donation_set`` prove the
cache buffer donatable: its last read is not after its last write).
:func:`flash_attention_decode` itself only READS the caches and returns the
attention output; it neither appends nor returns them. The append helpers
below are plain XLA updates of the donated buffer, one sequence at a time,
and they take the slot mask: a mask gates the rows that are written, never
the cache (see :func:`paged_kv_append`).

Design notes
- q rides in ``[BH, 8, D]`` sublane tiles (Mosaic needs the second-to-last
  dim divisible by 8 for f32; a 1-row tile violates that — see
  ``flash_attention._rows8``). The 8 sublane rows ARE the chunk's query
  rows: rows ``q_len..7`` are padding (replicas of the last real row) whose
  output is discarded, so the q_len=1 decode step and the q_len<=8 chunk
  use one kernel with a per-row length mask ``k_pos < length + row``.
- per-sequence lengths arrive as scalar-prefetch values so the kernel's
  mask needs no extra VMEM traffic; ``lengths[bh // num_heads]`` maps the
  fused B*H grid axis back to its batch row.
- inference-only: no custom VJP (decode never differentiates).
- interpret=True runs the same kernel on CPU for tests/CI parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, NEG_INF, _out_sds

__all__ = ["flash_attention_decode", "paged_kv_append",
           "paged_kv_append_rows", "decode_attention_reference",
           "KERNEL_ROWS"]

# query rows one kernel call serves: the chunk rides ONE f32 sublane tile
KERNEL_ROWS = 8


def _keep(mask, batch):
    """``mask`` ([B], [B, 1], any dtype; > 0 = write) as [B] bool, or None."""
    return None if mask is None else mask.reshape(batch) > 0


def paged_kv_append(cache, new, positions, mask=None, slots=None):
    """Write ``new`` rows into ``cache`` at per-sequence ``positions``.

    cache: [B, ..., S_max, D]; new: [B, ..., L, D]; positions: [B] int —
    the start row per sequence (L == prompt bucket is the prefill bulk
    write; L == 1 is one row of the decode append). One
    ``dynamic_update_slice`` per sequence, which XLA applies to a donated
    cache in place. Out-of-range starts clamp (XLA semantics), so a
    retired sequence whose position saturates keeps overwriting the last
    row instead of corrupting a neighbour.

    ``mask`` ([B], > 0 = write) gates the ROWS, never the cache: a
    sequence whose mask is 0 writes its own old rows back (they are read
    at the same clamped start), so its cache stays bit-identical and the
    masked append moves B x ... x L x D elements, like the unmasked one,
    where a ``where`` over the result would rewrite every row of every
    cache and hold the old cache alive beside the new one.

    ``slots`` ([B'] int): ``new`` holds B' <= B sequences and sequence
    ``i`` goes to the cache's row ``slots[i]`` (a prefill that carries only
    the sequences it serves); without it sequence ``i`` is row ``i``.
    """
    B = new.shape[0]
    if slots is None and B != cache.shape[0]:
        raise ValueError(f"paged_kv_append: {B} sequences of rows for a "
                         f"cache of {cache.shape[0]}, and no slots")
    positions = positions.reshape(B).astype(jnp.int32)
    new = new.astype(cache.dtype)
    keep = _keep(mask, B)
    if slots is not None:
        slots = slots.reshape(B).astype(jnp.int32)

    # one sequence at a time, with scalar starts: XLA updates the carried
    # buffer in place, where the batched forms are a gather and a scatter
    # for which the TPU compiler re-lays the whole cache
    def one(b, c):
        at = b if slots is None else slots[b]
        start = (at,) + (jnp.int32(0),) * (c.ndim - 3) + (
            positions[b], jnp.int32(0))
        n = jax.lax.dynamic_index_in_dim(new, b, 0)
        if keep is not None:
            n = jnp.where(keep[b], n,
                          jax.lax.dynamic_slice(c, start, n.shape))
        return jax.lax.dynamic_update_slice(c, n, start)

    return jax.lax.fori_loop(0, B, one, cache)


def paged_kv_append_rows(cache, new, positions, mask=None, ring=False):
    """Chunked KV write with PER-ROW clamping: row ``i`` of ``new``
    ([B, ..., C, D]) lands at ``min(positions + i, S_max - 1)`` — or, with
    ``ring`` (a windowed layer's cache, whose ``S_max`` rows are the last
    ``S_max`` positions), at ``(positions + i) % S_max``. Unlike
    :func:`paged_kv_append` (one ``dynamic_update_slice`` of the whole
    block, whose out-of-range START shifts backwards over real rows), a
    chunk whose tail crosses the cache end collapses its overflow rows
    onto the LAST row — and the last row is never inside a live length
    mask (the serving layer caps ``prompt + max_new <= S_max`` and the
    final generated token is never appended), so overflow is unreadable
    garbage, not corruption. ``mask`` ([B], > 0 = write) leaves the cache
    of a sequence whose mask is 0 bit-identical, at the cost of the rows
    and not of the cache (see :func:`paged_kv_append`).

    Two lowerings of the same result, chosen by the row count. Up to
    ``KERNEL_ROWS`` rows — the decode step and the verify chunk, the
    shapes the Pallas kernel serves — one ``dynamic_update_slice`` per
    row (a masked-out sequence writes its old row back): XLA updates the
    donated cache in place next to the kernel's custom call. Past that —
    chunked-prefill slices, which ride the primitive path anyway — ONE
    scatter (a masked-out sequence's indices go out of range, where
    ``mode="drop"`` discards them): unrolled, a 128-row chunk was 3,072
    update ops over 12 layers and its compile took minutes where its
    siblings take seconds."""
    S = cache.shape[-2]
    C = new.shape[-2]
    B = cache.shape[0]
    positions = positions.reshape(B).astype(jnp.int32)
    if C <= KERNEL_ROWS:
        for i in range(C):
            row_pos = ((positions + i) % S if ring
                       else jnp.minimum(positions + i, S - 1))
            cache = paged_kv_append(cache, new[..., i:i + 1, :], row_pos,
                                    mask)
        return cache
    if ring:
        raise NotImplementedError(
            f"a {C}-row chunk into a ring cache: only steps of up to "
            f"{KERNEL_ROWS} rows wrap")
    rows = positions[:, None] + jnp.arange(C, dtype=jnp.int32)    # [B, C]
    # every row at or past S-1 clamps onto the last cache row, where the
    # chunk's LAST row wins (what the row-by-row form does). The rows it
    # shadows go out of range, where mode="drop" discards them: indices
    # stay unique, so the result does not depend on the order a backend
    # applies a scatter in
    shadowed = (rows >= S - 1) & (jnp.arange(C) < C - 1)
    idx = jnp.where(shadowed, S, jnp.minimum(rows, S - 1))
    keep = _keep(mask, B)
    if keep is not None:
        idx = jnp.where(keep[:, None], idx, S)

    def upd(c, n, r):
        return c.at[..., r, :].set(n.astype(c.dtype), mode="drop")

    return jax.vmap(upd)(cache, new, idx)


def decode_attention_reference(q, k_cache, v_cache, lengths, scale,
                               group: int = 1):
    """Primitive oracle: masked softmax attention of a chunk of query rows
    per sequence against its cache. q: [BH, Sq, D]; caches: [BH, S, D];
    lengths: [BH] (already expanded per head) — the number of keys visible
    to query row 0; row ``i`` sees ``lengths + i`` keys (causal within the
    chunk, whose K rows were appended before the attention). Sq == 1 is
    the classic decode step. With ``group`` > 1 (grouped-query heads) BH
    counts key/value heads and row ``i`` is query head ``i % group`` at
    chunk position ``i // group``. Matches the kernel semantics exactly;
    also the op's off-TPU lowering."""
    prec = "highest" if q.dtype == jnp.float32 else "default"
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32), precision=prec) * scale
    k_pos = jnp.arange(k_cache.shape[1])[None, None, :]
    row = jnp.arange(q.shape[1])[None, :, None] // group
    s = jnp.where(k_pos < lengths[:, None, None] + row, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, v_cache.astype(jnp.float32),
                   precision=prec)
    return o.astype(q.dtype)


def _decode_kernel(scale, num_heads, group, scal_ref, q_ref, k_ref, v_ref,
                   o_ref, m_scr, l_scr, acc):
    bh, ik = pl.program_id(0), pl.program_id(1)
    num_k = pl.num_programs(1)
    block_k = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    length = scal_ref[bh // num_heads]
    q = q_ref[0]                                    # [8, D] (chunk rows)
    k = k_ref[0]                                    # [block_k, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # per-row causal length: query row i (the token at cache position
    # length - 1 + i) sees length + i keys; padding rows past the real
    # chunk see more keys, but their output is sliced away by the caller
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    if group > 1:       # grouped-query heads: `group` query heads a position
        row = row // group
    s = jnp.where(k_pos < length + row, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alive = m_new > NEG_INF * 0.5
    m_safe = jnp.where(alive, m_new, 0.0)
    corr = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe)
    l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
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
def flash_attention_decode(q, k_cache, v_cache, lengths, *,
                           scale=None, num_heads: int = 1,
                           page_size: int = 128, group: int = 1,
                           interpret: bool = False):
    """One decode/verify chunk: q [BH, Sq, D] (1 <= Sq <= 8) against paged
    caches [BH, S_max, D].

    ``lengths`` is per-BATCH ([B] int, B = BH // num_heads): the number of
    valid key rows visible to query row 0; row ``i`` sees ``lengths + i``
    keys (causal within the chunk — the chunk's K rows are appended to the
    cache before the walk). Sq == 1 is the classic decode step; Sq > 1 is
    the chunked-prefill / speculative-verify shape riding the same 8-row
    sublane tile (rows past Sq are padding, sliced off the output).
    ``page_size`` is the kernel's k-block — the cache page granularity;
    ``S_max`` must divide into whole pages
    (``flash_attention.classify_shapes`` refuses otherwise). Returns
    o [BH, Sq, D]. Inference-only (no VJP).

    ``group`` > 1 is grouped-query attention: BH counts KEY/VALUE heads
    (``num_heads`` of them a sequence) and the ``group`` query heads that
    share one ride the sublane rows beside each other — row ``i`` is query
    head ``i % group`` at chunk position ``i // group`` — so a cache page
    is read once for all of them.
    """
    BH, Sq, D = q.shape
    Sk = k_cache.shape[1]
    if Sq % group or not 1 <= Sq // group <= KERNEL_ROWS:
        raise ValueError(
            f"flash_attention_decode is the q_len<={KERNEL_ROWS} chunk "
            f"path (one sublane tile), got q_len={Sq // group} "
            f"(x {group} grouped heads); use flash_attention "
            f"for prefill/full-sequence shapes")
    bk = min(page_size, Sk)
    if Sk % bk:
        raise ValueError(
            f"decode cache length S_max={Sk} must divide into whole pages "
            f"of page_size={bk}")
    scale = float(scale if scale is not None else D ** -0.5)
    lengths = jnp.asarray(lengths).reshape(-1).astype(jnp.int32)
    if lengths.shape[0] * num_heads != BH:
        raise ValueError(
            f"lengths has {lengths.shape[0]} rows but q has BH={BH} with "
            f"num_heads={num_heads} (expected {BH // num_heads})")
    # pad the chunk to whole sublane tiles: [BH, Sq, D] -> [BH, R, D]
    # (replicas of the last real row; their output is sliced away). A
    # packed 16-bit type tiles 16 rows.
    tile = 8 * (4 // q.dtype.itemsize)
    R = -(-Sq // tile) * tile
    if Sq == R:
        q8 = q
    else:
        q8 = jnp.concatenate(
            [q, jnp.broadcast_to(q[:, -1:, :], (BH, R - Sq, D))], axis=1)
    nk = Sk // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, R, D), lambda bh, ik, s: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, s: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, s: (bh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, R, D), lambda bh, ik, s: (bh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),     # running max
            pltpu.VMEM((R, 128), jnp.float32),     # running denom
            pltpu.VMEM((R, D), jnp.float32),       # numerator acc
        ],
    )
    (o8,) = pl.pallas_call(
        functools.partial(_decode_kernel, scale, int(num_heads),
                          int(group)),
        grid_spec=grid_spec,
        out_shape=[_out_sds((BH, R, D), q.dtype, q, k_cache, v_cache)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q8, k_cache, v_cache)
    return o8[:, :Sq, :]
