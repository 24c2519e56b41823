"""Decode-step flash attention over a paged/block KV cache (Pallas TPU).

The autoregressive-serving counterpart of ``flash_attention.py``: a short
*chunk* of query tokens per sequence (1 <= q_len <= 8) attends against that
sequence's KV cache. q_len == 1 is the classic decode step; q_len > 1 is
the chunked-prefill slice and the speculative-verify chunk (ISSUE 20),
where query row ``i`` is the token at cache position ``length - 1 + i`` and
may see exactly ``length + i`` keys (causal *within* the chunk, since the
chunk's own K rows are appended before the walk). A chunk may also be a
*block* of block-diffusion generation (``whole_chunk``, PR 41): its rows see
one another in both directions, every row ``length + q_len - 1`` keys, and
what a forward of it yields is 0 to q_len tokens, not one. The cache is
*paged* —
logically ``[BH, S_max, D]`` where ``S_max = num_pages * page_size`` — and
``page_size`` is the CACHE's page: the unit of the prefix cache, of
``S_max``'s divisibility, of ``classify_shapes``. What a grid step carries
is the kernel's own (:func:`kv_tile`): whole pages of as many of a
sequence's heads as make the step worth its fixed cost, since a sequence's
heads share its length. The kernel walks a sequence's cache k-block by
k-block with the same online-softmax recurrence as the prefill kernel, up
to the sequence's LAST LIVE block, and its grid has a step only where a
block is fetched (PR 53): the steps are listed in a table built from the
lengths beside the call (:func:`walk_steps`: the live ``(sequence, group
of heads, k-block)`` triples, a sequence's blocks in ascending order),
the table is scalar-prefetched and its count is the grid's bound, a traced
value. (A grid step costs a third of a microsecond even where it fetches
nothing and skips its body, so a grid sized by the cache, ``num_k`` steps
a sequence, paid more for its dead steps than for its live ones wherever
the caches are mostly empty: four steps in five on GPT-2's saturated mix.)
Inside the last live block key positions ``>= length + row`` are masked
per sequence and query row (``>= length + q_len - 1`` for every row of a
whole chunk: the walk is the same, it ends at the last row's block). Pages
past a sequence's length hold stale/garbage rows by design (they are
overwritten when the sequence reaches them): they are never fetched, and
the length mask keeps the tail of the last live block out of the softmax,
so cache capacity can be provisioned once and reused across requests at
different positions at the cost of the keys they hold.

CODA (PAPERS.md, arXiv 2605.19269) motivates folding the decode-step
epilogue work into one op instead of separate ones. The fold is made one
level up, in the op rule (``ops/generation.py`` ``fused_decode_attention``):
it appends the chunk's K/V rows (:func:`paged_kv_append_rows`, or
:func:`kv_append` where the cache is worked on rows-minor) and then
calls :func:`flash_attention_decode` on the updated caches, so the
program-IR level sees ONE op that reads and writes the cache at the same
index (which is what lets ``analysis.liveness.safe_donation_set`` prove the
cache buffer donatable: its last read is not after its last write).
:func:`flash_attention_decode` itself only READS the caches and returns the
attention output; it neither appends nor returns them, but for a decode
step of ONE row on a rows-minor cache (``append=``, PR 45): there the row
is a column, whoever writes it fetches and rewrites the block around it,
and that block is the walk's last live one, so the kernel merges the
column in, scores the block as merged and copies it back itself, the
caches its aliased results. The ``paged_``
append helpers below are plain XLA updates of the donated buffer, for rows
that are whole lane tiles: one scatter a cache for a step's rows of every
sequence (:func:`paged_kv_append_rows`), one update a sequence for a
prefill's bucket (:func:`paged_kv_append`); :func:`kv_append`
is a Pallas call that aliases the cache, for rows that are columns (a
chunk of rows, a ring). All
take the slot mask: a mask gates the rows that are written, never the
cache (see :func:`paged_kv_append`).

Design notes
- q rides in ``[BH, 8, D]`` sublane tiles (Mosaic needs the second-to-last
  dim divisible by 8 for f32; a 1-row tile violates that — see
  ``flash_attention._rows8``). The 8 sublane rows ARE the chunk's query
  rows: rows ``q_len..7`` are padding (replicas of the last real row) whose
  output is discarded, so the q_len=1 decode step and the q_len<=8 chunk
  use one kernel with a per-row length mask ``k_pos < length + row``
  (``row`` the chunk's last for every row of a whole chunk).
- a cache whose head dimension is not whole 128-lane tiles (GPT-2's 64)
  is read and appended to in the view ``[B, H, D, S_max]``, rows in lanes
  (:func:`rows_minor`, beside :func:`kv_tile`): that is how the runtime
  stores such an array, so the view is a bitcast, where the logical shape
  cost a layout conversion of every cache around every chunk. One kernel,
  one walk, one mask: a block's two axes in the other order, chosen from
  the shape as the call is traced. Heads of 128 and 256 lie as declared
  and are read so.
- per-sequence lengths arrive as scalar-prefetch values beside the table
  of steps made from them (:func:`walk_steps`, from
  :func:`last_live_block`): the index maps read a step's ``(sequence,
  group of heads, k-block)`` from the table, the body reads the length for
  its mask and to know a visit's last step, and neither costs VMEM
  traffic. The grid is ONE axis of as many steps as blocks are fetched;
  the ``BlockSpec`` pipeline prefetches from one step to the next, across
  sequences as inside one. The running softmax starts on a visit's block 0
  and is written out on its last live block.
- the call is traced once a configuration and set of shapes
  (``_decode_call`` is a ``jax.jit``): a program's layers share one traced
  kernel and one Mosaic body in the lowered module.
- inference-only: no custom VJP (decode never differentiates).
- interpret=True runs the same kernel on CPU for tests/CI parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, NEG_INF, _out_sds

__all__ = ["flash_attention_decode", "kv_append", "paged_kv_append",
           "paged_kv_append_rows", "decode_attention_reference",
           "decode_walk_blocks", "decode_grid_steps", "rows_minor",
           "KERNEL_ROWS", "fold_rows",
           "window_fold"]

# query rows one kernel call serves: the chunk rides ONE f32 sublane tile
KERNEL_ROWS = 8


def _keep(mask, batch):
    """``mask`` ([B], [B, 1], any dtype; > 0 = write) as [B] bool, or None."""
    return None if mask is None else mask.reshape(batch) > 0


def _keep_flags(mask, batch):
    """The same as [B] int32 for a kernel's scalar prefetch; ones for None."""
    return (jnp.ones((batch,), jnp.int32) if mask is None
            else _keep(mask, batch).astype(jnp.int32))


def paged_kv_append(cache, new, positions, mask=None, slots=None):
    """Write ``new`` rows into ``cache`` at per-sequence ``positions``.

    cache: [B, ..., S_max, D]; new: [B, ..., L, D]; positions: [B] int —
    the start row per sequence (L == prompt bucket: the prefill's bulk
    write, a few sequences of a whole bucket each; the decode step's rows
    go through :func:`paged_kv_append_rows`). One
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
    # buffer in place, and a sequence's whole bucket is one operation. Of
    # the batched forms the TPU compiler applies ONE in place: a scatter
    # whose every index names a (sequence, head, row) and whose window is
    # the row (`paged_kv_append_rows`, the decode step's). With the heads
    # inside the update window (one index a sequence, or a `vmap` over the
    # sequences) it moves them next to the lanes and re-lays the whole
    # cache into the loop and out of it; with several rows a window it
    # expands the scatter into a loop of its own, a slower one than this
    # (tools/probe_kv_append.py; PERF.md section 6, PR 48)
    def one(b, c):
        start = [jnp.int32(0)] * c.ndim
        start[0] = b if slots is None else slots[b]
        start[-2] = positions[b]
        n = jax.lax.dynamic_index_in_dim(new, b, 0)
        if keep is not None:
            n = jnp.where(keep[b], n,
                          jax.lax.dynamic_slice(c, start, n.shape))
        return jax.lax.dynamic_update_slice(c, n, start)

    return jax.lax.fori_loop(0, B, one, cache)


def _row_positions(positions, i, s_max: int, ring: bool):
    """Where row ``i`` (or rows ``i``, an array that broadcasts) of a chunk
    that starts at ``positions`` lands."""
    return ((positions + i) % s_max if ring
            else jnp.minimum(positions + i, s_max - 1))


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

    ONE scatter a cache for every row count — the decode step, the verify
    chunk, the chunked-prefill slice — with one index a (sequence, head,
    row), which XLA applies to the donated cache in place next to the
    kernel's custom call (PERF.md section 6, PR 48: a step's 128 x 8 rows
    in one operation where a loop over the sequences was three operations
    a sequence and row). What must not be written goes out of range, where
    the scatter drops it: a sequence whose mask is 0, and a row that a
    later row of the chunk overwrites (every row at or past ``S_max - 1``
    clamps onto the last cache row, where the chunk's LAST row wins), so
    the indices in range are unique and the result does not depend on the
    order a backend applies a scatter in."""
    S = cache.shape[-2]
    C = new.shape[-2]
    B = cache.shape[0]
    if ring and C > KERNEL_ROWS:
        raise NotImplementedError(
            f"a {C}-row chunk into a ring cache: only steps of up to "
            f"{KERNEL_ROWS} rows, the kernel's, wrap")
    i = jnp.arange(C, dtype=jnp.int32)
    at = _row_positions(positions.reshape(B, 1).astype(jnp.int32), i, S,
                        ring)                                     # [B, C]
    shadowed = i + S < C if ring else (at == S - 1) & (i < C - 1)
    keep = _keep(mask, B)
    if keep is not None:
        shadowed = shadowed | ~keep[:, None]
    # [N, S, D], N = B x heads: every head's row named by an index of its
    # own is the form XLA applies in place (see `paged_kv_append`)
    D, N = cache.shape[-1], cache.size // (S * cache.shape[-1])
    at = jnp.repeat(jnp.where(shadowed, S + i, at), N // B, axis=0)
    return cache.reshape(N, S, D).at[jnp.arange(N)[:, None], at].set(
        new.astype(cache.dtype).reshape(N, C, D), mode="drop",
        unique_indices=True).reshape(cache.shape)


def fold_rows(lengths, window: int):
    """``[R, window]``: the prompt position each row of a ring of
    ``window`` rows takes from a sequence of ``lengths`` [R] tokens: row
    ``r`` the LAST position ``p < length`` with ``p % window == r``, so
    that a decode step, which writes position ``p`` at row ``p % window``,
    goes on where the prompt stopped. A row no position of a short
    sequence falls on (``r >= length``) takes position ``r``, a padding
    row the ring's length mask hides, as a bucket written at row 0 leaves
    it. The rows come from two consecutive blocks of ``window`` positions:
    the one the sequence ends in, up to its last token, and the one
    before."""
    last = jnp.maximum(lengths.astype(jnp.int32), 1) - 1
    block, rem = last // window, last % window
    r = jnp.arange(window, dtype=jnp.int32)[None, :]
    ends_here = r <= rem[:, None]
    return jnp.where(ends_here, block[:, None],
                     jnp.maximum(block[:, None] - 1, 0)) * window + r


@jax.named_scope("window_fold")
def window_fold(cache, new, lengths, mask=None, slots=None):
    """A prompt past a window layer's ring, folded into it: ``cache``
    [B, H, W, D] is a ring of the last ``W`` positions, ``new`` [R, H, S,
    D] the keys or values of ``R`` whole prompts of ``lengths`` [R] tokens
    in a bucket of ``S > W`` rows. ONE gather takes each sequence's rows
    (:func:`fold_rows`) and ONE write a sequence puts them in the slot it
    names (``slots`` [R], default its own index) where its ``mask`` is set;
    every other slot stays bit-untouched (:func:`paged_kv_append`). Plain
    XLA on every device, under the scope ``window_fold`` (a device trace
    shows it in the operations' ``op_name``)."""
    R, W = new.shape[0], cache.shape[-2]
    idx = fold_rows(lengths.reshape(R), W)
    folded = jnp.take_along_axis(new, idx[:, None, :, None], axis=2)
    return paged_kv_append(cache, folded, jnp.zeros((R,), jnp.int32), mask,
                           slots)


def decode_attention_reference(q, k_cache, v_cache, lengths, scale,
                               group: int = 1, whole_chunk: bool = False,
                               sink=None):
    """Primitive oracle: masked softmax attention of a chunk of query rows
    per sequence against its cache. q: [BH, Sq, D]; caches: [BH, S, D];
    lengths: [BH] (already expanded per head) — the number of keys visible
    to query row 0; row ``i`` sees ``lengths + i`` keys (causal within the
    chunk, whose K rows were appended before the attention). Sq == 1 is
    the classic decode step. With ``group`` > 1 (grouped-query heads) BH
    counts key/value heads and row ``i`` is query head ``i % group`` at
    chunk position ``i // group``. With ``whole_chunk`` every row sees
    what the chunk's last row sees, ``lengths + Sq // group - 1`` keys: the
    chunk's rows see one another in both directions (a block-diffusion
    block). ``v_cache`` may be [BH, S, Dv] with another width than the
    keys'. ``sink`` ([BH, group] f32, a scalar a query head): one more
    column of the softmax that carries no value. Matches the kernel
    semantics exactly; also the op's off-TPU lowering."""
    prec = "highest" if q.dtype == jnp.float32 else "default"
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32), precision=prec) * scale
    k_pos = jnp.arange(k_cache.shape[1])[None, None, :]
    row = jnp.arange(q.shape[1])[None, :, None] // group
    if whole_chunk:
        row = q.shape[1] // group - 1
    s = jnp.where(k_pos < lengths[:, None, None] + row, s, NEG_INF)
    if sink is not None:        # row i is query head i % group
        col = jnp.tile(sink.astype(jnp.float32), (1, q.shape[1] // group))
        s = jnp.concatenate([s, col[:, :, None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :k_cache.shape[1]]
    o = jnp.einsum("bqk,bkd->bqd", p, v_cache.astype(jnp.float32),
                   precision=prec)
    return o.astype(q.dtype)


# K and V bytes ONE grid step carries, at most (the pipeline holds twice
# that in VMEM, 3 of the 16 MiB scoped to a kernel on a v5e). A step costs
# 0.2-0.5 us whatever it moves, a third of a microsecond's worth of HBM is
# 290 KB, so a step of 0.75-1.5 MB is bound by its bytes (740-750 GB/s on
# a full cache at either end of that range) and more rows than that only
# fetch more rows past the length: tools/probe_decode_walk.py, PERF.md
# section 6, PRs 28 and 32. Since PR 53 every step of the grid fetches a
# block, so that fixed cost is paid once a block that is read and never
# for one that is not
_STEP_BYTES = 3 << 19


def rows_minor(head_dim: int, dtype, page: int) -> bool:
    """Whether the decode step works on a cache ``[B, H, S_max, D]`` in
    the view ``[B, H, D, S_max]``: rows in lanes, the head dimension in
    sublanes. The TPU runtime stores an array whose minor dimension is not
    whole 128-lane tiles with its second-minor dimension in lanes instead
    (``f32[64,12,1024,64]{2,3,1,0:T(8,128)}``: nothing padded), so that
    view is a bitcast of the buffer as it lies, where a Mosaic call on the
    logical shape wants ``D`` minor, 64 padded to 128 lanes, and XLA
    converts every cache on the way in and out of the program: 48 copies
    and 9.86 GB of scratch a chunk at GPT-2's geometry (PERF.md section 6,
    PR 32). The append has to work in the same view, or the conversion
    moves into the loop. Read from the shape as the kernel is traced:
    a head dimension of whole lane tiles (128, 256) keeps the logical
    view, and so does one that is not whole sublane tiles of ``dtype`` or
    a ``page`` (the unit of a k-block) that is not whole lane tiles."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (head_dim % 128 != 0 and head_dim % sublanes == 0
            and page % 128 == 0)


_LANES = 128


def _with_column(block, new_ref, b, row, keep):
    """``block`` [heads, D, rows] of a rows-minor cache with row ``row`` of
    the cache (its lane ``row % rows``) replaced by sequence ``b``'s new
    column where ``keep``. The column stands in lane ``b % 128`` of
    ``new_ref``'s block [heads, D, 128]; the roll turns it to the lane its
    row has in its lane tile (Mosaic rotates 32-bit lanes only: a 16-bit
    float widens exactly), and a block of several lane tiles sees it once
    a tile, of which the mask takes the row's."""
    rows = block.shape[2]
    col = row % rows
    new = pltpu.roll(new_ref[...].astype(jnp.float32), (col - b) % _LANES,
                     2).astype(block.dtype)
    if rows > _LANES:
        new = jnp.concatenate([new] * (rows // _LANES), axis=2)
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 2)
    return jnp.where((lane == col) & keep, new, block)


def _append_kernel(pos_ref, keep_ref, new_ref, c_ref, o_ref):
    b = pl.program_id(0)
    o_ref[0] = _with_column(c_ref[0], new_ref, b, pos_ref[b],
                            keep_ref[b] > 0)


def _columns(new, dtype):
    """``new`` [B, H, C, D] as [C, H, D, B']: a row as a column, the
    sequences beside each other in whole lane tiles (one block of 128
    sequences is fetched once)."""
    return jnp.pad(new.astype(dtype).transpose(2, 1, 3, 0),
                   ((0, 0),) * 3 + ((0, -new.shape[0] % _LANES),))


@jax.named_scope(_PALLAS_SCOPE)
def kv_append(cache, new, positions, mask=None, ring=False, *,
              interpret: bool = False):
    """:func:`paged_kv_append_rows` for a cache in the rows-minor view
    (:func:`rows_minor`), where a row is a column: ``cache`` [B, H, D,
    S_max] with ``S_max`` whole lane tiles, ``new`` [B, H, C, D] as the op
    has it, ``C <= KERNEL_ROWS``; the same per-row clamp or ``ring``, the
    same ``mask``, the same bits. A column is ``H x D`` numbers in as many
    ``(8, 128)`` tiles, which XLA writes one sequence at a time at 7 us
    each (PERF.md section 6, PRs 32 and 34). Here one Pallas call a row of
    the chunk walks the sequences: the index map picks the cache's block
    ``(1, H, D, 128)`` that holds the row (``position // 128``, scalar
    prefetch), the body selects the column in by a lane mask and the block
    goes back where it came from, the cache being the call's own result
    (``input_output_aliases``), so a donated cache is updated in place. A
    sequence whose mask is 0 writes its block back as it was."""
    B, H, D, S = cache.shape
    if S % _LANES:
        raise ValueError(f"kv_append: a rows-minor cache of {S} rows is not "
                         f"whole {_LANES}-lane tiles")
    positions = positions.reshape(B).astype(jnp.int32)
    keep = _keep_flags(mask, B)
    cols = _columns(new, cache.dtype)
    c_spec = pl.BlockSpec((1, H, D, _LANES),
                          lambda b, pos, keep: (b, 0, 0, pos[b] // _LANES))
    for i in range(new.shape[2]):
        cache = pl.pallas_call(
            _append_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[pl.BlockSpec((H, D, _LANES),
                                       lambda b, pos, keep: (0, 0,
                                                             b // _LANES)),
                          c_spec],
                out_specs=c_spec),
            out_shape=_out_sds(cache.shape, cache.dtype, cache, cols),
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="kv_append",
        )(_row_positions(positions, i, S, ring), keep, cols[i], cache)
    return cache


def kv_tile(num_heads: int, s_max: int, head_dim: int, dtype,
            page_size: int, row_bytes: int = None, v_dim: int = None):
    """``(heads, rows)`` of a cache that ONE grid step of the decode kernel
    carries, from what the kernel sees when it is traced. ``page_size`` is
    the cache's page, the unit of everything outside this module; the tile
    is the kernel's own.

    The heads of a sequence share its length, so heads come first: a page
    of as many of them as ``_STEP_BYTES`` holds (a divisor of
    ``num_heads``) makes a step fuller at no row fetched past the length.
    Then rows: the fewest whole pages (a number that divides ``s_max``'s)
    that bring the step to half of ``_STEP_BYTES``, from where it is bound
    by its bytes, or else as many as fit: each further page is fetched
    whole where the length ends inside it. The head dimension in sublanes
    (:func:`rows_minor`), a row weighs what it holds: GPT-2's tile stays
    12 heads x 128 rows at 0.79 MB, where padded to 128 lanes it weighed
    1.57 (256 rows, the step the budget would also hold, took 0.229 ms a
    call on the saturated mix against 0.236 and 0.229 against 0.197 on
    sequences of one key: PR 32's sweep). ``row_bytes``: what a step
    fetches of one row of one head where that is not a K and a V row of
    ``head_dim`` (a latent cache's row is fetched once). ``v_dim``: a
    value row's width where it is not the key's (both then lie as
    declared, each padded to whole lane tiles)."""
    page = min(page_size, s_max)
    # K and V of one row of one head in VMEM: the head dimension padded to
    # whole 128-lane vregs, or, rows in lanes, as it is
    padded = lambda d: -(-d // 128) * 128
    if v_dim not in (None, head_dim):
        lanes2 = padded(head_dim) + padded(v_dim)
    else:
        lanes2 = 2 * (head_dim if rows_minor(head_dim, dtype, page)
                      else padded(head_dim))
    if row_bytes is None:
        row_bytes = lanes2 * jnp.dtype(dtype).itemsize
    heads = max(h for h in range(1, num_heads + 1) if num_heads % h == 0
                and (h == 1 or h * page * row_bytes <= _STEP_BYTES))
    pages, step = s_max // page, heads * page * row_bytes
    fits = [m for m in range(1, pages + 1) if pages % m == 0
            and (m == 1 or m * step <= _STEP_BYTES)]
    return heads, page * next(
        (m for m in fits if 2 * m * step >= _STEP_BYTES), fits[-1])


def last_live_block(lengths, q_len: int, block_k: int, num_k: int):
    """Index of the last k-block of ``block_k`` rows that any query row of
    a chunk sees: the chunk's last row, ``q_len - 1``, sees ``lengths +
    q_len - 1`` keys. Block 0 for an empty sequence; never past the cache
    (a chunk whose tail crosses its end). Works on a traced scalar (the
    kernel's index maps and its body) and on a numpy array (the host's
    count), which is what keeps the two from drifting."""
    last = (lengths + q_len - 2) // block_k
    return (jnp if isinstance(last, jax.Array) else np).clip(
        last, 0, num_k - 1)


def decode_walk_blocks(lengths, cache_shape, dtype, page_size: int,
                       q_len: int = 1, rows: int = None, v_dim: int = None):
    """``(fetched, capacity)``: the k-blocks one call of the kernel fetches
    for sequences of ``lengths`` (host integers, visible keys of query row
    0) out of those their caches ``[B, H, S_max, D]`` hold. Pure host
    arithmetic on the kernel's own tile and walk (``rows``: the rows of a
    step of another kernel that walks as this one does; ``v_dim``: the
    values' width where it is not the keys')."""
    _, H, S, D = cache_shape
    if rows is None:
        _, rows = kv_tile(H, S, D, dtype, page_size, v_dim=v_dim)
    num_k = S // rows
    live = last_live_block(np.asarray(lengths, np.int64), q_len, rows,
                           num_k) + 1
    return int(live.sum()), int(live.size * num_k)


def decode_grid_steps(lengths, cache_shape, dtype, page_size: int,
                      q_len: int = 1, v_dim: int = None):
    """The grid steps one call of the kernel runs for sequences of
    ``lengths`` (host integers, as :func:`decode_walk_blocks` takes them):
    a step a k-block that is fetched, once a group of heads where a step
    carries fewer heads than the cache has (:func:`walk_steps` counts the
    same on the traced lengths, and its count is the grid's bound)."""
    _, H, S, D = cache_shape
    heads, rows = kv_tile(H, S, D, dtype, page_size, v_dim=v_dim)
    return (H // heads) * decode_walk_blocks(
        lengths, cache_shape, dtype, page_size, q_len, rows)[0]


def walk_steps(lengths, q_len: int, block_k: int, num_k: int, groups: int):
    """``(table, steps)``: the grid of one decode call, a step a k-block
    that is fetched, from the traced ``lengths`` [B] by ``jnp`` beside the
    call. A *visit* is a sequence's group of heads, ``groups`` of them a
    sequence, in the order ``(sequence, group)``; visit ``v`` walks the
    blocks ``0..last_live_block`` of its sequence in ascending order, and
    the steps of the call are the visits' walks one after another.
    ``table[s]`` is ``v * num_k + ik`` of step ``s`` (past the call's
    ``steps`` the last step again: a grid never gets there); ``steps`` is
    their number, the grid's bound."""
    live = jnp.repeat(last_live_block(lengths, q_len, block_k, num_k) + 1,
                      groups)                               # [V] blocks
    ends = jnp.cumsum(live)
    steps = ends[-1]
    s = jnp.minimum(jnp.arange(live.shape[0] * num_k), steps - 1)
    # the visit a step belongs to: how many walks end at or before it,
    # and its block: the step less the blocks of those walks
    ended = ends[None, :] <= s[:, None]
    table = (jnp.sum(ended, axis=1) * num_k + s
             - jnp.sum(jnp.where(ended, live[None, :], 0), axis=1))
    return table.astype(jnp.int32), steps.astype(jnp.int32)


def _decode_kernel(scale, group, q_len, minor, whole, append, sink, num_k,
                   groups, step_ref, len_ref, *refs):
    sink_ref = None
    if append:      # the step's new K/V row rides the last live block
        (keep_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref, ko_hbm,
         vo_hbm, m_scr, l_scr, acc, k_buf, v_buf, sems) = refs
    elif sink:      # a scalar a query row: one more column, no value
        q_ref, k_ref, v_ref, sink_ref, o_ref, m_scr, l_scr, acc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc = refs
    # a grid step is a block that is fetched (`walk_steps`): block `ik` of
    # visit `visit`, a sequence's group of heads
    visit, ik = _visit_and_block(step_ref[pl.program_id(0)], num_k)
    b, hg = visit // groups, visit % groups
    # a K or V block is [heads, block_k, D] or, rows-minor, [heads, D,
    # block_k]: the same products, the block's axes in the other order
    rows_at, d_at = (2, 1) if minor else (1, 2)
    block_k = k_ref.shape[1 + rows_at]
    length = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        if sink:    # its score the running maximum, its exp(0) the sum
            m_scr[:] = sink_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    last = last_live_block(length, q_len, block_k, num_k)

    def walk(k_ref, v_ref):
        q = q_ref[...]                              # [heads, R, D]
        s = jax.lax.dot_general(q, k_ref[0],
                                (((2,), (d_at,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        2)
        # per-row causal length: query row i (the token at cache position
        # length - 1 + i) sees length + i keys; padding rows past the real
        # chunk see more keys, but their output is sliced away by the
        # caller. With `whole` the chunk's rows see one another in both
        # directions: every row sees what the last one does
        if whole:
            row = q_len - 1
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if group > 1:   # grouped-query heads: `group` heads a position
                row = row // group
        s = jnp.where(k_pos < length + row, s, NEG_INF)

        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alive = m_new > NEG_INF * 0.5
        m_safe = jnp.where(alive, m_new, 0.0)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = corr * l_scr[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((2,), (rows_at,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if not append:
        walk(k_ref, v_ref)      # every step of the grid is a live block
    else:
        pl.when(ik < last)(lambda: walk(k_ref, v_ref))
        heads = k_ref.shape[1]
        slot = visit % 2

        def flushes(slot, b=0, hg=0, block=0):
            """The two copies that write a slot's merged blocks to where
            they came from in the caches (at the defaults: a handle of the
            same extent, to wait on)."""
            at = (b, pl.ds(hg * heads, heads), slice(None),
                  pl.ds(pl.multiple_of(block * block_k, _LANES), block_k))
            return [pltpu.make_async_copy(buf.at[slot], cache.at[at],
                                          sems.at[i, slot])
                    for i, (buf, cache) in enumerate(((k_buf, ko_hbm),
                                                      (v_buf, vo_hbm)))]

        def drain(slot):
            for copy in flushes(slot):
                copy.wait()

        # the new row is the sequence's last visible key, row length - 1:
        # its block is the last live one. It is merged (kept as fetched
        # where the slot's mask is 0) into one of two buffers, scored from
        # there, and flushed to the cache behind the visits that follow:
        # the buffer is waited for when its turn comes again, two visits
        # on. (As a pipelined result the block went out between two visits
        # and the pipeline waited for it there: 2 us a visit, what the
        # append kernel had cost. PERF.md section 6, PR 45)
        @pl.when(ik == last)
        def _append_and_walk():
            pl.when(visit >= 2)(lambda: drain(slot))
            keep = keep_ref[b] > 0
            k_buf[slot] = _with_column(k_ref[0], kn_ref, b, length - 1, keep)
            v_buf[slot] = _with_column(v_ref[0], vn_ref, b, length - 1, keep)
            walk(k_buf.at[pl.ds(slot, 1)], v_buf.at[pl.ds(slot, 1)])
            for copy in flushes(slot, b, hg, last):
                copy.start()

    @pl.when(ik == last)        # the visit's last step
    def _finish():
        l = l_scr[:, :, :1]
        o_ref[...] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)
        if append:      # the call's last step: both buffers' flushes land
            @pl.when(visit == len_ref.shape[0] * groups - 1)
            def _drain_all():
                drain(slot)
                pl.when(visit >= 1)(lambda: drain(1 - slot))


def _visit_and_block(step, num_k: int):
    """A :func:`walk_steps` entry as ``(visit, k-block)``."""
    return step // num_k, step % num_k


@jax.named_scope(_PALLAS_SCOPE)
def _decode_call_traced(q, k_cache, v_cache, lengths, tile, *, minor, scale,
                        group, q_len, interpret, whole=False, append=None,
                        sink=None):
    """The Pallas call on ``q`` [B * H, R, D] and caches [B, H, S_max, D]
    with ``tile = (heads, rows)`` of a cache a grid step
    (:func:`kv_tile`'s choice; ``tools/probe_decode_walk.py`` sweeps it).
    With ``minor`` (:func:`rows_minor` of the cache) the call takes the
    caches as [B, H, D, S_max], a bitcast of how they lie, and a block of
    them as ``(1, heads, D, rows)``. ``append = (k_new, v_new, keep)``
    (:func:`flash_attention_decode`): the caches are the call's own results
    too (``input_output_aliases``, so a donated cache is updated in place).
    As results they stay in HBM, and the kernel itself copies one block a
    sequence and group of heads into them, the last live one with the
    column merged in, from two VMEM buffers a cache. ``sink`` [H, R, 128]
    f32: a query row's scalar in every lane, what the running maximum
    starts from. The values may be narrower or wider than the keys."""
    B, H = k_cache.shape[:2]
    _, R, D = q.shape
    Dv = v_cache.shape[3]
    hb, bk = tile
    nk, groups = k_cache.shape[2] // bk, H // hb
    if minor:
        k_cache, v_cache = k_cache.swapaxes(2, 3), v_cache.swapaxes(2, 3)
    table, steps = walk_steps(lengths, q_len, bk, nk, groups)

    def at(index):
        """An index map from ``index(sequence, group of heads, k-block)``
        of the grid step's entry of the table."""
        def index_map(s, table, *_):
            visit, ik = _visit_and_block(table[s], nk)
            return index(visit // groups, visit % groups, ik)
        return index_map

    rows_of = at(lambda b, hg, ik: (b * groups + hg, 0, 0))
    q_spec = pl.BlockSpec((hb, R, D), rows_of)
    o_spec = pl.BlockSpec((hb, R, Dv), rows_of)
    kv_block = (1, hb, D, bk) if minor else (1, hb, bk, D)
    kv_map = at(lambda b, hg, ik: (b, hg, 0, ik) if minor
                else (b, hg, ik, 0))
    kv_spec = pl.BlockSpec(kv_block, kv_map)
    v_spec = kv_spec if Dv == D else pl.BlockSpec((1, hb, bk, Dv), kv_map)
    scalars, operands = [table, lengths], [q, k_cache, v_cache]
    in_specs, out_specs = [q_spec, kv_spec, v_spec], [o_spec]
    out_shape = [_out_sds((B * H, R, Dv), q.dtype, q, k_cache, v_cache)]
    scratch = [pltpu.VMEM((hb, R, 128), jnp.float32),     # running max
               pltpu.VMEM((hb, R, 128), jnp.float32),     # running denom
               pltpu.VMEM((hb, R, Dv), jnp.float32)]      # numerator acc
    if sink is not None:
        operands.append(sink)
        in_specs.append(pl.BlockSpec((hb, R, 128),
                                     at(lambda b, hg, ik: (hg, 0, 0))))
    aliases = {}
    if append is not None:
        k_new, v_new, keep = append
        new_spec = pl.BlockSpec((hb, D, _LANES),
                                at(lambda b, hg, ik: (hg, 0, b // _LANES)))
        scalars.append(keep)
        operands += [_columns(k_new, k_cache.dtype)[0],
                     _columns(v_new, v_cache.dtype)[0]]
        in_specs += [new_spec, new_spec]
        # the caches as results stay in HBM: the kernel flushes the blocks
        # it merged itself, from two buffers a cache, so a flush has two
        # visits to land in
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        out_shape += [_out_sds(c.shape, c.dtype, c, *operands)
                      for c in (k_cache, v_cache)]
        scratch += [pltpu.VMEM((2,) + kv_block[1:], k_cache.dtype),
                    pltpu.VMEM((2,) + kv_block[1:], v_cache.dtype),
                    pltpu.SemaphoreType.DMA((2, 2))]
        # the caches follow the scalars and q among the call's operands
        aliases = {len(scalars) + 1: 1, len(scalars) + 2: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(steps,),      # as many steps as blocks are fetched
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    o, *caches = pl.pallas_call(
        functools.partial(_decode_kernel, scale, int(group), int(q_len),
                          minor, bool(whole), append is not None,
                          sink is not None, nk, groups),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the running softmax, and on the append path a flush's buffer,
        # are handed from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(*scalars, *operands)
    if append is None:
        return o
    return (o, *(c.swapaxes(2, 3) if minor else c for c in caches))


# One trace a configuration and set of shapes: the layers of a program call
# the kernel alike, the first traces it (the table, the index maps, the
# body) and the jit's cache answers the others, and the lowered module
# holds one function with the Mosaic call that every layer calls, as the
# flash forward's does (`flash_attention._fwd`, PR 50). XLA inlines the
# call sites, so the caches' aliases hold as before. The `pallas` scope
# stands inside the traced function, right around the call, where the
# kernel's one name in the HLO and the profiler wants it.
_decode_call = jax.jit(
    _decode_call_traced, static_argnums=(4,),
    static_argnames=("minor", "scale", "group", "q_len", "interpret",
                     "whole"))


def flash_attention_decode(q, k_cache, v_cache, lengths, *,
                           scale=None, num_heads: int = 1,
                           page_size: int = 128, group: int = 1,
                           interpret: bool = False,
                           whole_chunk: bool = False, append=None,
                           sink=None):
    """One decode/verify chunk: q [BH, Sq, D] (1 <= Sq <= 8) against paged
    caches [BH, S_max, D] (the values' may be [BH, S_max, Dv], another
    width than the keys': the output is then [BH, Sq, Dv], and both caches
    are read as declared).

    ``sink`` ([num_heads * group] f32, a scalar a query head, or None): the
    head's scalar joins its softmax as one more column that carries no
    value, ``p_j = exp(s_j - m) / (sum_j' exp(s_j' - m) + exp(sink -
    m))``: it seeds the running maximum and denominator.

    ``lengths`` is per-BATCH ([B] int, B = BH // num_heads): the number of
    valid key rows visible to query row 0; row ``i`` sees ``lengths + i``
    keys (causal within the chunk — the chunk's K rows are appended to the
    cache before the walk). Sq == 1 is the classic decode step; Sq > 1 is
    the chunked-prefill / speculative-verify shape riding the same 8-row
    sublane tile (rows past Sq are padding, sliced off the output).
    ``page_size`` is the CACHE's page — the unit of the prefix cache and of
    ``S_max`` (``flash_attention.classify_shapes`` refuses a cache that is
    not whole pages). What a grid step carries is the kernel's own choice
    (:func:`kv_tile`): whole pages of several of a sequence's heads, up to
    the sequence's last live block and nothing past it. Returns
    o [BH, Sq, D]. Inference-only (no VJP).

    ``group`` > 1 is grouped-query attention: BH counts KEY/VALUE heads
    (``num_heads`` of them a sequence) and the ``group`` query heads that
    share one ride the sublane rows beside each other — row ``i`` is query
    head ``i % group`` at chunk position ``i // group`` — so a cache page
    is read once for all of them.

    ``whole_chunk``: the chunk is a block whose rows see one another in
    both directions (block diffusion): every row sees ``lengths + Sq //
    group - 1`` keys, what the chunk's last row sees without it. The walk
    is the same (it ends at the last row's last block); only the mask of
    that block differs.

    ``append = (k_new, v_new, mask)``: the call also WRITES the step's new
    K/V row (``k_new``, ``v_new`` [B, num_heads, 1, D]; ``mask`` [B] or
    [B, 1], > 0 = write, or None), which is the sequence's last visible
    key, row ``lengths - 1``, and returns ``(o, k_cache, v_cache)``, the
    caches aliased to the call's operands. For a step of one row
    (Sq == group) on a rows-minor cache (:func:`rows_minor`), where a row
    is a column and its one writer has to fetch and rewrite the whole
    block ``(heads, D, rows)`` around it (:func:`kv_append`): that block is
    the last live one of the walk, fetched here anyway, so the column is
    merged into it in VMEM, it is scored as merged, and it goes back once
    a sequence and group of heads, by a copy the kernel starts itself and
    waits for two visits later (or at the call's last step). The same
    values as append-then-attend in the same products: the three results
    are that route's bit for bit.
    """
    BH, Sq, D = q.shape
    Sk, Dv = k_cache.shape[1], v_cache.shape[2]
    if Sq % group or not 1 <= Sq // group <= KERNEL_ROWS:
        raise ValueError(
            f"flash_attention_decode is the q_len<={KERNEL_ROWS} chunk "
            f"path (one sublane tile), got q_len={Sq // group} "
            f"(x {group} grouped heads); use flash_attention "
            f"for prefill/full-sequence shapes")
    if Sk % min(page_size, Sk):
        raise ValueError(
            f"decode cache length S_max={Sk} must divide into whole pages "
            f"of page_size={page_size}")
    scale = float(scale if scale is not None else D ** -0.5)
    lengths = jnp.asarray(lengths).reshape(-1).astype(jnp.int32)
    B = lengths.shape[0]
    if B * num_heads != BH:
        raise ValueError(
            f"lengths has {B} rows but q has BH={BH} with "
            f"num_heads={num_heads} (expected {BH // num_heads})")
    minor = Dv == D and rows_minor(D, k_cache.dtype, min(page_size, Sk))
    if append is not None:
        if not minor or Sq != group or whole_chunk or sink is not None:
            raise ValueError(
                f"flash_attention_decode appends one row a step to a "
                f"rows-minor cache, got q_len={Sq // group}, "
                f"whole_chunk={whole_chunk}, head_dim={D} in pages of "
                f"{page_size}; use kv_append or paged_kv_append_rows first")
        append = (*append[:2], _keep_flags(append[2], B))
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
    if sink is not None:
        # row i of a key/value head's tile is its query head i % group
        # (padding rows take any head's); the scalar in every lane, as the
        # running maximum is kept
        per_row = jnp.asarray(sink, jnp.float32).reshape(num_heads, group)[
            :, jnp.arange(R) % group]
        sink = jnp.broadcast_to(per_row[:, :, None], (num_heads, R, 128))
    # a sequence's heads beside each other: they share its length, so one
    # grid step can carry a page of each
    out = _decode_call(
        q8, k_cache.reshape(B, num_heads, Sk, D),
        v_cache.reshape(B, num_heads, Sk, Dv), lengths,
        kv_tile(num_heads, Sk, D, k_cache.dtype, page_size, v_dim=Dv),
        minor=minor, scale=scale, group=group, q_len=Sq // group,
        interpret=interpret, whole=whole_chunk, append=append, sink=sink)
    if append is None:
        return out[:, :Sq, :]
    return (out[0][:, :Sq, :],
            *(c.reshape(k_cache.shape) for c in out[1:]))
