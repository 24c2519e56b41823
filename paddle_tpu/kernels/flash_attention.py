"""Flash attention as a Pallas TPU kernel — the `jit/` + `fused/` role.

This is the TPU-native analogue of the reference's runtime-codegen fused
kernels (reference: paddle/fluid/operators/jit/kernel_base.h xbyak JIT
framework; paddle/fluid/operators/fused/fused_embedding_fc_lstm_op.cc etc.):
the one place SURVEY §7 reserves hand-written kernels because whole-graph XLA
fusion cannot produce them. The kernel computes

    O = dropout(softmax(Q K^T * scale + bias + causal_mask)) V

blockwise with the online-softmax recurrence, never materialising the
[S, S] score matrix in HBM: scores live in VMEM one (block_q, block_k)
tile at a time, accumulators persist in VMEM scratch across the innermost
grid dimension (TPU grid steps execute sequentially per core, so scratch
carries state the way the reference's xbyak kernels carry registers).

Design notes
- Layout is [B*H, S, D] (head-major): one grid axis ranges over fused
  batch*heads, blocks tile the sequence. D (head_dim) rides the lane
  dimension; 64/128 both work (64 pads lanes — bert-base's 768/12).
- The backward is the standard two-kernel flash split: dQ with the q-block
  as the outer tile, dK/dV with the k-block outer, both recomputing
  P = exp(S - lse) from the saved log-sum-exp rather than storing probs.
- The function also RETURNS lse, and its VJP accepts a cotangent for it:
  d lse_i / d S_ij = P_ij, so the lse cotangent just joins the
  `(dP - delta)` term. This is what lets ring attention combine per-block
  kernel results across ICI steps and still differentiate end-to-end.
- Dropout uses the on-core PRNG (`pltpu.prng_seed` / `prng_random_bits`),
  reseeded per (bh, q-block, k-block) so the backward kernels regenerate
  bit-identical keep masks. The PRNG has no interpret-mode lowering, so
  dropout>0 requires a real TPU; callers fall back to the primitive path
  elsewhere (ops/fused_attention.py).
- Masked-out rows (a fully-padded query) produce O=0 and lse=-inf; the
  backward guards exp(s - lse) with a finite sentinel so their grads are
  exactly zero.
- The forward's grid is chosen from the call's shape and mask
  (:func:`_forward_grid`), never from a flag or a model's name. *Which
  pairs:* a causal call visits only the (q-block, k-block) pairs some row
  sees, from the first block its window reaches to the diagonal's: with
  static offsets as a ``flat`` axis over those pairs alone, read from a
  scalar-prefetched table (no dead step); with traced offsets (ring
  attention) ``guarded``, a k axis as long as a q-block's reach whose steps
  past the last visible block repeat it (no DMA) and score nothing. A call
  with nothing to cut keeps the ``dense`` grid and the one masked step it
  always had. A tile neither the diagonal nor the window's far edge crosses
  skips the iota, compare and select. *How tall, and which way the tile
  lies:* with no dropout and whole lane tiles of queries and of value
  lanes, the score tile of a causal call of several q-blocks lies
  [keys, queries] (a query's maximum, denominator and correction are
  lane-dense [1, block_q] vectors) at the tallest of 512 / 384 / 256 rows
  that divides Sq, fits VMEM and is no taller than a window, else at 128;
  any other call takes the 128-row [queries, keys] step, whose time is its
  rows' (PERF.md, PR 50). The k-block stays the page: a row meets
  its visible k-blocks in the same order, 128 keys at a time, at every
  height, so ``o`` and ``lse`` do not depend on the grid, and the backward
  kernels (always 128 x 128) are handed the same residuals.
- The forward is traced and lowered once a program, not once a layer:
  :func:`_fwd` is a ``jax.jit`` with the configuration static, so the N
  layers that call it with one configuration and one set of shapes share
  one traced kernel and one Mosaic body in the lowered module (N call
  sites; XLA inlines them, and each instruction keeps the kernel's name).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_bwd", "supports_shapes", "classify_shapes",
           "flash_block_visits", "flash_forward_grid", "window_block_visits"]

NEG_INF = -1e30          # finite sentinel: (-inf) - (-inf) would NaN

# odd mixing constants for per-block reseeding, pre-wrapped to int32 range
# (jax int32 multiply wraps, which is exactly the mixing we want)
_SEED_MIX_BH = -1640532047   # int32(0x9E3779B1)
_SEED_MIX_Q = -2048144777    # int32(0x85EBCA77)
_SEED_MIX_K = -1028477379    # int32(0xC2B2AE3D)


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""

    causal: bool
    scale: float
    dropout: float
    block_q: int
    block_k: int
    num_heads: int       # for bias [B, Sk] indexing from the fused B*H axis
    has_bias: bool
    interpret: bool
    # 'highest' for f32 inputs (true f32 multiplies), 'default' for bf16
    # (native MXU one-pass mode)
    precision: str
    # sliding window: a query sees keys at 0 <= q_pos - k_pos < window
    # (0: no window). Rides the causal comparison.
    window: int = 0
    # grouped-query heads: `kv_group` query heads read one key/value head
    # (q is [B*Hq, ...], k and v [B*Hq/kv_group, ...]). Forward only.
    kv_group: int = 1
    # causal by blocks of `causal_block` positions (0: by row): a query
    # sees every key of its own block, in both directions, and of the
    # blocks before it. Rides the causal comparison.
    causal_block: int = 0
    # a sink: one f32 scalar a query head joins the softmax as a column
    # with no value (it seeds the running maximum and denominator).
    # Forward only.
    has_sink: bool = False
    # the call's static (q_offset, k_offset); None where either is traced.
    # Known offsets let the forward walk a host-made table of the visible
    # (q-block, k-block) pairs (:func:`_forward_grid`).
    offsets: Optional[Tuple[int, int]] = None
    # the caller named no ``block_q`` and there is no dropout: the forward
    # picks its own q-block height and tile layout from the shape
    # (:func:`_forward_tile`); ``block_q`` stays the backward kernels' block.
    auto_q: bool = False


@dataclasses.dataclass(frozen=True)
class _Grid:
    """The forward's grid for one call (:func:`_forward_grid`).

    * ``'dense'``: ``(BH, nq, nk)``, every pair fetched and scored, every
      tile of a causal call masked: a call with nothing to cut.
    * ``'guarded'``: ``(BH, nq, steps)`` where ``steps`` bounds the k-blocks
      one q-block sees; a step past its q-block's last visible block holds
      that block again (an index that repeats: no DMA) and scores nothing.
      The form for traced offsets.
    * ``'flat'``: ``(BH, steps)`` over the visible pairs alone, from the
      scalar-prefetched table ``pairs`` (``[3 * steps]``: q-blocks,
      k-blocks, flags). No dead step. The form for static offsets."""

    form: str
    # the step's layout: False a score tile of [block_q, block_k] (a query a
    # sublane row), True of [block_k, block_q] (a query a lane)
    lanes: bool
    steps: int
    num_k: int
    pairs: Optional[Tuple[int, ...]] = None
    # a tile the mask does not cross takes a step with no iota, compare and
    # select (else every causal tile takes the one masked step)
    split: bool = False


def classify_shapes(sq: int, sk: int, block_q: int = 128,
                    block_k: int = 128):
    """Classify an attention shape for the kernel layer.

    Returns ``(kind, reason)`` where ``kind`` is one of:

    * ``'prefill'`` — full-sequence shapes the blockwise kernel tiles
      (both sequence lengths divide into whole blocks);
    * ``'decode'`` — the q_len == 1 autoregressive step against a
      block/page-tiled KV cache (``decode_attention.flash_attention_decode``;
      ``block_k`` is the page size and the cache must hold whole pages);
    * ``'unsupported'`` — no kernel tiling fits; ``reason`` says exactly
      which divisibility failed so callers can refuse loudly instead of
      falling through to the dense path silently.
    """
    if sq == 1:
        bk = min(block_k, sk)
        if sk % bk == 0:
            return ("decode",
                    f"q_len=1 against a block-KV cache of {sk // bk} "
                    f"page(s) x {bk}")
        return ("unsupported",
                f"decode shape (q_len=1) but the KV cache length sk={sk} "
                f"does not divide into whole pages of page_size={bk}; pad "
                f"the cache capacity to a multiple of the page size")
    bq, bk = min(block_q, sq), min(block_k, sk)
    bad = []
    if sq % bq:
        bad.append(f"sq={sq} % block_q={bq}")
    if sk % bk:
        bad.append(f"sk={sk} % block_k={bk}")
    if bad:
        return ("unsupported",
                f"sequence lengths must divide into whole kernel blocks: "
                f"{', '.join(bad)} != 0 (pad the sequence or pick block "
                f"sizes that divide it)")
    return ("prefill", f"{sq // bq} q-block(s) x {sk // bk} k-block(s)")


def supports_shapes(sq: int, sk: int, block_q: int = 128,
                    block_k: int = 128) -> bool:
    """Whether a kernel tiling (prefill or decode) covers these shapes.
    ``classify_shapes`` carries the which-and-why."""
    return classify_shapes(sq, sk, block_q, block_k)[0] != "unsupported"


def _out_sds(shape, dtype, *like):
    """ShapeDtypeStruct for pallas outputs; under shard_map (check_vma=True)
    outputs must declare which mesh axes they vary over — the union of the
    operands'."""
    vma = set()
    for t in like:
        try:
            v = getattr(jax.typeof(t), "vma", None)
        except Exception:
            v = None
        if v:
            vma |= set(v)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _rows8(x):
    """[N, S] row vector -> [N, 8, S], replicated over the sublane dim.
    Mosaic block shapes need their second-to-last dim divisible by 8 (f32);
    a (1, block) tile of a 2-D array violates that, a (1, 8, block) tile of
    the replicated form doesn't. XLA materialises the broadcast lazily."""
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], 8, x.shape[1]))


def _cols128(x):
    """[N, S] row vector -> [N, S, 128], replicated over the lanes: a
    (1, block, 128) tile of it holds the block as a COLUMN, which a score
    tile laid [keys, queries] adds to every query's lane."""
    return jnp.broadcast_to(x[:, :, None], x.shape + (128,))


def _dropout_keep(seed, bh, iq, ik, shape, rate):
    """Deterministic per-block keep mask from the on-core PRNG."""
    mix = (seed + bh * _SEED_MIX_BH + iq * _SEED_MIX_Q + ik * _SEED_MIX_K)
    pltpu.prng_seed(mix)
    # raw bits are int32; Mosaic has no uint32->f32 cast, so mask to the
    # low 23 bits (non-negative in int32) -> uniform [0, 1)
    bits = pltpu.prng_random_bits(shape) & 0x007FFFFF
    u = bits.astype(jnp.float32) * (1.0 / (1 << 23))
    return u >= rate


def _q_seen_from(cfg: "_Cfg", q_pos):
    """The position a query stands at in the causal comparison: its own,
    or, causal by blocks, the last one of its block."""
    if cfg.causal_block:
        return (q_pos // cfg.causal_block + 1) * cfg.causal_block - 1
    return q_pos


def _visible(cfg: "_Cfg", q_pos, k_pos):
    """The causal comparison, with the window where there is one; by
    blocks of positions where the mask is causal by block (the last
    position of the query's block stands for the query)."""
    q_pos = _q_seen_from(cfg, q_pos)
    seen = q_pos >= k_pos
    if cfg.window:
        seen = seen & (q_pos - k_pos < cfg.window)
    return seen


def _window_steps(window: int, block_q: int, block_k: int, nk: int) -> int:
    """The k-blocks one q-block of a windowed layer can touch, at most,
    wherever it starts: its rows see ``block_q + window - 1`` consecutive
    key positions. ``nk`` where that is every block."""
    return min((block_q + window - 3) // block_k + 2, nk)


def _k_range(cfg: "_Cfg", q_off, k_off, iq, nk: int):
    """``(first, last)`` k-block any row of q-block ``iq`` sees under the
    causal mask and the window (``last < first``: none). ``last`` holds the
    key the q-block's last row sees last: its own position, or, causal by
    blocks of L, the end of its block of L. On traced scalars (the kernel
    and its index maps) and on host integers (:func:`_visible_pairs`)
    alike. ``cfg.block_q`` is the forward's height."""
    q_lo = q_off + iq * cfg.block_q
    first = q_lo - cfg.window + 1 - k_off if cfg.window else 0
    last = _q_seen_from(cfg, q_lo + cfg.block_q - 1) - k_off
    if not isinstance(last, jax.Array):
        return (max(first, 0) // cfg.block_k,
                min(last // cfg.block_k, nk - 1) if last >= 0 else -1)
    return (jnp.maximum(first, 0) // cfg.block_k,
            jnp.where(last >= 0,
                      jnp.minimum(jnp.maximum(last, 0) // cfg.block_k,
                                  nk - 1), -1))


def _crossed(cfg: "_Cfg", q_off, k_off, iq, kb):
    """Whether tile (q-block ``iq``, k-block ``kb``) holds a pair the mask
    hides: the diagonal or the window's far edge crosses it. Traced or
    host integers."""
    q_lo = q_off + iq * cfg.block_q
    k_lo = k_off + kb * cfg.block_k
    cut = k_lo + cfg.block_k - 1 > _q_seen_from(cfg, q_lo)
    if cfg.window:
        cut = cut | (q_lo + cfg.block_q - 1 - k_lo >= cfg.window)
    return cut


# flags of a pair in the flat form's table
_STARTS, _ENDS, _CROSSED = 1, 2, 4


def _visible_pairs(cfg: "_Cfg", nq: int, nk: int):
    """``[(q-block, k-block, flags)]`` of a causal call with static
    offsets, in the order the kernel walks them: for every q-block the
    k-blocks some row of it sees, first to last. A q-block that sees none
    still takes one step (its output is written there: zeros), on a block
    the mask hides whole. Host arithmetic on :func:`_k_range`."""
    q_off, k_off = cfg.offsets
    pairs = []
    for iq in range(nq):
        first, last = _k_range(cfg, q_off, k_off, iq, nk)
        first = min(first, nk - 1)
        for kb in range(first, max(last, first) + 1):
            flags = (_STARTS * (kb == first) | _ENDS * (kb >= last)
                     | _CROSSED * bool(last < first or _crossed(
                         cfg, q_off, k_off, iq, kb)))
            pairs.append((iq, kb, flags))
    return pairs


# The forward's own q-block: the heights tried, tallest first. A step of the
# [queries, keys] tile costs its rows at every height (0.70 / 1.09 / 2.24 us
# at 128 / 256 / 512 rows on a v5e), so only the [keys, queries] tile goes
# taller (0.69-0.76 us a 512 x 128 step: PERF.md, PR 50).
_HEIGHTS = (512, 384, 256)
_FWD_VMEM_BUDGET = 10 << 20         # of the 16 MiB a kernel may scope
_FLAT_MAX_PAIRS = 1 << 13           # three int32 a pair in SMEM


def _fwd_vmem_bytes(bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """What one forward step holds in VMEM at a q-block of ``bq`` rows:
    the double-buffered blocks, the scratch and the f32 score tiles."""
    blocks = (2 * itemsize * (bq * d + bk * (d + dv) + bq * dv)
              + 2 * 32 * bq + 2 * 4 * 128 * bk)       # lse; a key bias
    scratch = 4 * bq * (8 + 8 + dv)
    return blocks + scratch + 3 * 4 * bq * bk


def _forward_tile(cfg: "_Cfg", sq: int, d: int, dv: int, itemsize: int):
    """``(block_q, lanes)``: the forward's q-block height and whether its
    score tile lies [keys, queries]. Queries in lanes where the call is
    causal, the caller named no ``block_q``, there is no dropout (the
    backward kernels regenerate the mask of a [queries, keys] tile of
    ``cfg.block_q`` rows), and queries and value lanes are whole lane
    tiles (the output's transpose; GPT-2's and BERT's heads of 64 are
    not); a key bias rides as a column (:func:`_cols128`); then at the tallest of 512 / 384 / 256 rows that divides
    ``sq``, fits VMEM (arithmetic on the block shapes) and is no taller
    than a window, else at ``cfg.block_q`` rows. Any other call (one that
    is not causal, or of a single q-block, has nothing to cut: the kernel
    it always had) takes ``cfg.block_q`` rows in the [queries, keys]
    tile."""
    if not (cfg.causal and cfg.auto_q and sq > cfg.block_q
            and sq % 128 == 0 and dv % 128 == 0):
        return cfg.block_q, False
    for h in _HEIGHTS:
        if (sq % h == 0 and (not cfg.window or h <= cfg.window)
                and _fwd_vmem_bytes(h, cfg.block_k, d, dv, itemsize)
                <= _FWD_VMEM_BUDGET):
            return h, True
    return cfg.block_q, True


def _forward_grid(cfg: "_Cfg", sq: int, sk: int, d: int, dv: int,
                  itemsize: int, form: Optional[str] = None,
                  lanes: Optional[bool] = None):
    """``(cfg at the forward's height, its _Grid)`` for a call of these
    shapes: the tile (:func:`_forward_tile`) and which (q-block, k-block)
    pairs the forward visits. A causal call visits only the pairs some row
    sees: with static offsets the flat table of them, or the dense grid
    where that is every pair; with traced offsets (ring attention), or a
    table too long for SMEM, the guarded form. Host arithmetic. ``form``
    and ``lanes`` force one (the probe and the tests; ``lanes`` at
    ``cfg.block_q`` rows)."""
    bq, tile_lanes = ((cfg.block_q, lanes) if lanes is not None
                      else _forward_tile(cfg, sq, d, dv, itemsize))
    cfg = dataclasses.replace(cfg, block_q=bq)
    nq, nk = sq // bq, sk // cfg.block_k
    dense = _Grid("dense", tile_lanes, nk, nk)
    if not cfg.causal:
        return cfg, dense
    pairs = None if cfg.offsets is None else _visible_pairs(cfg, nq, nk)
    if form is None:
        if pairs is None or len(pairs) > _FLAT_MAX_PAIRS:
            form = "guarded"
        else:
            form = "flat" if len(pairs) < nq * nk else "dense"
    if form == "flat":
        crossed = sum(1 for p in pairs if p[2] & _CROSSED)
        return cfg, dataclasses.replace(
            dense, form="flat", steps=len(pairs),
            pairs=tuple(x for col in zip(*pairs) for x in col),
            split=0 < crossed < len(pairs))
    if form == "guarded":
        if pairs is not None:       # the most k-blocks one q-block sees
            steps = max(collections.Counter(p[0] for p in pairs).values())
        elif cfg.window:
            steps = _window_steps(cfg.window, bq, cfg.block_k, nk)
        else:
            steps = nk
        return cfg, dataclasses.replace(dense, form="guarded", steps=steps,
                                        split=True)
    return cfg, dense


def _shape_cfg(sq: int, sk: int, causal, window, causal_block, block_q,
               block_k, dropout=False, static_offsets=True) -> _Cfg:
    """The configuration of a call of this shape and mask from position 0,
    for the host functions that say what the forward will do."""
    return _Cfg(causal=bool(causal), scale=1.0, dropout=float(dropout),
                block_q=min(block_q or 128, sq), block_k=min(block_k, sk),
                num_heads=1, has_bias=False, interpret=False,
                precision="default", window=int(window),
                causal_block=int(causal_block),
                offsets=(0, 0) if static_offsets else None,
                auto_q=block_q is None and not dropout)


def flash_block_visits(sq: int, sk: int, causal: bool = True,
                       window: int = 0, causal_block: int = 0,
                       block_q: Optional[int] = None, block_k: int = 128,
                       head_dim: int = 128, v_dim: Optional[int] = None,
                       itemsize: int = 2):
    """``(visited, grid)``: the (q-block, k-block) pairs one head of the
    forward kernel fetches and scores for ``sq`` query rows over ``sk``
    keys from position 0, and the pairs of the whole grid, both in tiles
    of the call's nominal blocks (``block_q`` or 128 rows x ``block_k``
    keys): a step of a taller q-block (``block_q`` None: the height the
    forward picks for this shape, :func:`_forward_tile`) counts as its
    rows / 128 pairs, so the share means the same at every height. Host
    arithmetic on what the kernel itself walks (:func:`_forward_grid`): a
    causal call visits the pairs some row sees under the diagonal and the
    window, another call all."""
    cfg = _shape_cfg(sq, sk, causal, window, causal_block, block_q, block_k)
    nominal = cfg.block_q
    cfg, _ = _forward_grid(cfg, sq, sk, head_dim, v_dim or head_dim,
                           itemsize)
    nq, nk = sq // cfg.block_q, sk // cfg.block_k
    steps = len(_visible_pairs(cfg, nq, nk)) if causal else nq * nk
    return steps * cfg.block_q // nominal, (sq // nominal) * nk


def flash_forward_grid(sq: int, sk: int, head_dim: int,
                       v_dim: Optional[int] = None, itemsize: int = 2,
                       causal: bool = False, window: int = 0,
                       causal_block: int = 0, dropout: bool = False,
                       static_offsets: bool = True) -> str:
    """The grid the forward builds for a call of this shape and mask at the
    default blocks, as a label: ``q<rows>xk<keys>/<form>``, the q-block
    height it picks and how it walks the pairs (``dense`` all of them,
    ``flat`` the visible ones alone, ``guarded`` a k axis as long as a
    q-block's reach with its dead steps guarded). What
    ``kernel_route_total{op="<op>.grid"}`` notes."""
    cfg, grid = _forward_grid(
        _shape_cfg(sq, sk, causal, window, causal_block, None, 128,
                   dropout, static_offsets),
        sq, sk, head_dim, v_dim or head_dim, itemsize)
    return f"q{cfg.block_q}xk{cfg.block_k}/{grid.form}"


def window_block_visits(sq: int, sk: int, window: int, block_q: int = 128,
                        block_k: int = 128):
    """:func:`flash_block_visits` of a causal call at the blocks named (the
    name from when only a window cut the grid)."""
    return flash_block_visits(sq, sk, window=window, block_q=block_q,
                              block_k=block_k)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(cfg: _Cfg, grid: _Grid, scal_ref, *refs):
    """One grid step: q-block ``iq`` against k-block ``kb``. ``cfg`` is at
    the forward's height. A dense grid in the [queries, keys] layout is
    the kernel as it always was, jaxpr for jaxpr."""
    pairs_ref = sink_ref = None
    if grid.form == "flat":
        pairs_ref, refs = refs[0], refs[1:]
    if cfg.has_sink:
        sink_ref, refs = refs[0], refs[1:]
    if cfg.has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, m_scr, l_scr, acc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc = refs
        b_ref = None
    bh = pl.program_id(0)
    # live: the step scores its block (None: every step does); crossed:
    # the mask hides part of the tile (None: one step for every tile)
    live = crossed = None
    if grid.form == "flat":
        t, n = pl.program_id(1), grid.steps
        iq, kb, flags = pairs_ref[t], pairs_ref[n + t], pairs_ref[2 * n + t]
        starts = lambda: (flags & _STARTS) != 0
        ends = lambda: (flags & _ENDS) != 0
        if grid.split:
            crossed = (flags & _CROSSED) != 0
    else:
        iq, ik = pl.program_id(1), pl.program_id(2)
        num_k = pl.num_programs(2)
        starts, ends, kb = (lambda: ik == 0), (lambda: ik == num_k - 1), ik

    @pl.when(starts())
    def _init():
        if cfg.has_sink:
            # the sink's column: its score is the running maximum, its
            # exp(0) = 1 the denominator, and it adds nothing to the sum
            m_scr[:] = jnp.full_like(m_scr, sink_ref[bh % cfg.num_heads])
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    if grid.form == "guarded":
        first, last = _k_range(cfg, scal_ref[0], scal_ref[1], iq, grid.num_k)
        kb = first + ik
        # a step past the q-block's last visible block holds that block
        # again (its index map repeats it: no DMA) and scores nothing
        live = kb <= last
        crossed = _crossed(cfg, scal_ref[0], scal_ref[1], iq, kb)

    # ``lanes``: the score tile lies [keys, queries], so what a query
    # carries (maximum, denominator, correction) is a lane-dense [1, bq]
    # vector of a few registers, and a reduction over keys a plain
    # elementwise one over sublane rows; else [queries, keys] with [bq, 1]
    # columns (a register an eight rows whatever its width) and lane
    # reductions. The same numbers either way.
    lanes = grid.lanes
    qa, ka = (1, 0) if lanes else (0, 1)           # a tile's axes
    row = (lambda r: r[:1]) if lanes else (lambda r: r[:, :1])
    vec = (lambda r: r[0]) if lanes else (lambda r: r[:, 0])

    def _step(masked: bool = cfg.causal):
        q = q_ref[0]                                   # [bq, D]
        k = k_ref[0]                                   # [bk, D]
        s = jax.lax.dot_general(*((k, q) if lanes else (q, k)),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=cfg.precision)
        s = s * cfg.scale                              # f32
        if cfg.has_bias and lanes:
            s = s + b_ref[0][:, :1].astype(jnp.float32)
        elif cfg.has_bias:
            s = s + b_ref[0, 0].astype(jnp.float32)[None, :]
        if masked:
            q_pos = (scal_ref[0] + iq * cfg.block_q
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, qa))
            k_pos = (scal_ref[1] + kb * cfg.block_k
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, ka))
            s = jnp.where(_visible(cfg, q_pos, k_pos), s, NEG_INF)

        m_prev = row(m_scr)                            # [bq, 1] | [1, bq]
        m_cur = jnp.max(s, axis=ka, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        if crossed is not None and not masked and not cfg.has_bias:
            m_safe = m_new      # a row of finite scores: a finite maximum
        else:
            alive = m_new > NEG_INF * 0.5
            m_safe = jnp.where(alive, m_new, 0.0)
        corr = jnp.exp(m_prev - m_safe)            # underflows to 0 if dead
        p = jnp.exp(s - m_safe)                    # masked s -> exp(-1e30)=0
        l_new = corr * row(l_scr) + jnp.sum(p, axis=ka, keepdims=True)
        if cfg.dropout > 0.0:
            keep = _dropout_keep(scal_ref[2], bh, iq, kb, s.shape,
                                 cfg.dropout)
            p = jnp.where(keep, p / (1.0 - cfg.dropout), 0.0)
        if lanes:                                      # [Dv, bq]
            pv = jax.lax.dot_general(v_ref[0], p.astype(v_ref.dtype),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=cfg.precision)
        else:                                          # [bq, Dv]
            pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                                     precision=cfg.precision)
        acc[:] = acc[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if crossed is not None:
        both = (lambda c: c) if live is None else (
            lambda c: jnp.logical_and(live, c))
        pl.when(both(crossed))(functools.partial(_step, True))
        pl.when(both(jnp.logical_not(crossed)))(
            functools.partial(_step, False))
    elif live is not None:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(ends())
    def _finish():
        l = row(l_scr)
        o = acc[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (o.T if lanes else o).astype(o_ref.dtype)
        lse_row = jnp.where(vec(l) > 0.0,
                            vec(m_scr) + jnp.log(vec(l)), -jnp.inf)
        # row vectors are stored sublane-replicated [8, block_q]: Mosaic
        # requires block sublanes divisible by 8 (see _rows8)
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


# Device-trace names (docs/OBSERVABILITY.md "Device names"): XLA names a
# custom call after the innermost scope of its ``op_name``, and JAX wraps
# that scope in the transforms it sits under (``jvp(...)``,
# ``transpose(jvp(...))``). ``pallas_call(name=...)`` opens the innermost
# scope; this outer one takes the wrapping, so a kernel keeps ONE name in
# the HLO and the profiler however it is reached.
_PALLAS_SCOPE = "pallas"


@jax.named_scope(_PALLAS_SCOPE)
def _fwd_traced(cfg: _Cfg, q, k, v, bias, scalars, sink=None, form=None,
                lanes=None):
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    # the forward's own q-block, tile and grid, from the call's shape and
    # mask; ``cfg`` from here on is at the forward's height
    cfg, grid = _forward_grid(cfg, Sq, Sk, D, Dv, q.dtype.itemsize, form,
                              lanes)
    nq, nk = Sq // cfg.block_q, Sk // cfg.block_k
    flat = grid.form == "flat"

    # an index map is handed the grid indices, then the scalar-prefetch
    # refs: ``scalars``, the flat form's table of pairs, the sink
    if flat:
        q_block = lambda t, s, pairs, *_: pairs[t]
        k_block = lambda t, s, pairs, *_: pairs[grid.steps + t]
    else:
        q_block = lambda iq, ik, *_: iq

        def k_block(iq, ik, s, *_):
            """The k-block step ``ik`` of q-block ``iq`` holds: itself, or,
            guarded, the q-block's first visible block and on, the last
            one again past it (no DMA for an index that repeats)."""
            if grid.form == "dense":
                return ik
            first, last = _k_range(cfg, s[0], s[1], iq, nk)
            return jnp.minimum(first + ik, jnp.maximum(last, 0))

    q_map = lambda bh, *a: (bh, q_block(*a), 0)
    if cfg.kv_group == 1:
        kv_map = lambda bh, *a: (bh, k_block(*a), 0)
    else:       # bh = b * Hq + h reads b * Hkv + h // G = bh // G
        kv_map = lambda bh, *a: (bh // cfg.kv_group, k_block(*a), 0)
    in_specs = [
        pl.BlockSpec((1, cfg.block_q, D), q_map),
        pl.BlockSpec((1, cfg.block_k, D), kv_map),
        pl.BlockSpec((1, cfg.block_k, Dv), kv_map),
    ]
    args = [q, k, v]
    if cfg.has_bias:
        H = cfg.num_heads
        if grid.lanes:      # the key bias a column of the tile
            in_specs.append(pl.BlockSpec(
                (1, cfg.block_k, 128),
                lambda bh, *a: (bh // H, k_block(*a), 0)))
            args.append(_cols128(bias))
        else:
            in_specs.append(pl.BlockSpec(
                (1, 8, cfg.block_k),
                lambda bh, *a: (bh // H, 0, k_block(*a))))
            args.append(_rows8(bias))
    prefetch = [scalars]
    if flat:        # a constant of the traced function: static, like cfg
        prefetch.append(jnp.asarray(np.asarray(grid.pairs, np.int32)))
    if sink is not None:
        prefetch.append(sink)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(BH, grid.steps) if flat else (BH, nq, grid.steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, Dv), q_map),
            pl.BlockSpec((1, 8, cfg.block_q),
                         lambda bh, *a: (bh, 0, q_block(*a))),
        ],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in (
            # running max, running denominator, numerator accumulator
            [(8, cfg.block_q)] * 2 + [(Dv, cfg.block_q)] if grid.lanes
            else [(cfg.block_q, 128)] * 2 + [(cfg.block_q, Dv)])],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg, grid),
        grid_spec=grid_spec,
        out_shape=[
            _out_sds((BH, Sq, Dv), q.dtype, q, k, v),
            _out_sds((BH, 8, Sq), jnp.float32, q, k, v),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (1 if flat else 2)
            + ("arbitrary",)),
        interpret=cfg.interpret,
        name="flash_attention_fwd",
    )(*prefetch, *args)
    return o, lse[:, 0, :]


# One traced callable a (configuration, shapes): JAX's own trace cache
# answers a program's layers 2..N, and the lowered module holds one function
# with the Mosaic call that every layer calls (PERF.md, PR 50: 0.035 s a
# layer and program of trace and lowering before).
_fwd = jax.jit(_fwd_traced, static_argnums=(0,),
               static_argnames=("form", "lanes"))


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _recompute_p(cfg, scal_ref, q, k, b_ref, lse, iq, ik):
    """P = exp(S - lse) for one tile, shared by both backward kernels."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=cfg.precision) * cfg.scale
    if cfg.has_bias:
        s = s + b_ref[0, 0].astype(jnp.float32)[None, :]
    if cfg.causal:
        q_pos = (scal_ref[0] + iq * cfg.block_q
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        k_pos = (scal_ref[1] + ik * cfg.block_k
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(_visible(cfg, q_pos, k_pos), s, NEG_INF)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, -NEG_INF)  # dead rows: p=0
    return jnp.exp(s - lse_safe[:, None])


def _dq_kernel(cfg: _Cfg, scal_ref, *refs):
    if cfg.has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref, dq_ref,
         dq_acc) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_acc = refs
        b_ref = None
    bh, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    p = _recompute_p(cfg, scal_ref, q_ref[0], k_ref[0], b_ref,
                     lse_ref[0, 0], iq, ik)
    do = do_ref[0]
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                            precision=cfg.precision)
    if cfg.dropout > 0.0:
        keep = _dropout_keep(scal_ref[2], bh, iq, ik, p.shape, cfg.dropout)
        dp = jnp.where(keep, dp / (1.0 - cfg.dropout), 0.0)
    ds = p * (dp - dl_ref[0, 0].astype(jnp.float32)[:, None])
    dq_acc[:] += cfg.scale * jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
                            precision=cfg.precision)

    @pl.when(ik == num_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(cfg: _Cfg, scal_ref, *refs):
    if cfg.has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref, dk_ref,
         dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        b_ref = None
    bh, ik, iq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0]
    p = _recompute_p(cfg, scal_ref, q, k_ref[0], b_ref, lse_ref[0, 0],
                     iq, ik)
    do = do_ref[0]
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                            precision=cfg.precision)
    p_used = p
    if cfg.dropout > 0.0:
        keep = _dropout_keep(scal_ref[2], bh, iq, ik, p.shape, cfg.dropout)
        inv = 1.0 / (1.0 - cfg.dropout)
        p_used = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp * inv, 0.0)
    # dV = P_dropped^T @ dO
    dv_acc[:] += jax.lax.dot_general(
        p_used.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
                            precision=cfg.precision)
    ds = p * (dp - dl_ref[0, 0].astype(jnp.float32)[:, None])
    dk_acc[:] += cfg.scale * jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
                            precision=cfg.precision)

    @pl.when(iq == num_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@jax.named_scope(_PALLAS_SCOPE)
def _bwd(cfg: _Cfg, q, k, v, bias, scalars, do, lse, delta):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // cfg.block_q, Sk // cfg.block_k
    qspec = pl.BlockSpec((1, cfg.block_q, D),
                         lambda bh, iq, ik, s: (bh, iq, 0))
    kspec = pl.BlockSpec((1, cfg.block_k, D),
                         lambda bh, iq, ik, s: (bh, ik, 0))
    rowspec = pl.BlockSpec((1, 8, cfg.block_q),
                           lambda bh, iq, ik, s: (bh, 0, iq))
    args = [q, k, v]
    common = [qspec, kspec, kspec]
    if cfg.has_bias:
        H = cfg.num_heads
        common.append(pl.BlockSpec((1, 8, cfg.block_k),
                                   lambda bh, iq, ik, s: (bh // H, 0, ik)))
        args.append(_rows8(bias))
    common += [qspec, rowspec, rowspec]            # do, lse, delta
    args += [do, _rows8(lse), _rows8(delta)]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nq, nk),
            in_specs=common,
            out_specs=[qspec],
            scratch_shapes=[pltpu.VMEM((cfg.block_q, D), jnp.float32)],
        ),
        out_shape=[_out_sds((BH, Sq, D), q.dtype, q, k, v, do)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_bwd_dq",
    )(scalars, *args)[0]

    # k-outer grid: swap the roles of the q/k grid axes in the index maps
    qspec2 = pl.BlockSpec((1, cfg.block_q, D),
                          lambda bh, ik, iq, s: (bh, iq, 0))
    kspec2 = pl.BlockSpec((1, cfg.block_k, D),
                          lambda bh, ik, iq, s: (bh, ik, 0))
    rowspec2 = pl.BlockSpec((1, 8, cfg.block_q),
                            lambda bh, ik, iq, s: (bh, 0, iq))
    common2 = [qspec2, kspec2, kspec2]
    if cfg.has_bias:
        H = cfg.num_heads
        common2.append(pl.BlockSpec((1, 8, cfg.block_k),
                                    lambda bh, ik, iq, s: (bh // H, 0, ik)))
    common2 += [qspec2, rowspec2, rowspec2]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nk, nq),
            in_specs=common2,
            out_specs=[kspec2, kspec2],
            scratch_shapes=[pltpu.VMEM((cfg.block_k, D), jnp.float32),
                            pltpu.VMEM((cfg.block_k, D), jnp.float32)],
        ),
        out_shape=[_out_sds((BH, Sk, D), k.dtype, q, k, v, do),
                   _out_sds((BH, Sk, D), v.dtype, q, k, v, do)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_bwd_dkv",
    )(scalars, *args)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q, k, v, bias, scalars):
    return _fwd(cfg, q, k, v, bias, scalars)


def _forward_only(cfg: _Cfg, v_dim: int, head_dim: int) -> Optional[str]:
    """Why this call has no backward kernels, or None where it has."""
    if cfg.kv_group != 1:
        return "grouped-query heads: the dK/dV kernel does not sum over " \
               "a group's query heads"
    if cfg.has_sink:
        return "a sink column: the backward kernels do not carry it"
    if v_dim != head_dim:
        return f"values of {v_dim} beside keys of {head_dim}: the " \
               f"backward kernels take one width"
    return None


def _flash_fwd_rule(cfg, q, k, v, bias, scalars):
    o, lse = _fwd(cfg, q, k, v, bias, scalars)
    return (o, lse), (q, k, v, bias, scalars, o, lse)


def _flash_bwd_rule(cfg, res, cts):
    q, k, v, bias, scalars, o, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd_from_residuals(cfg, q, k, v, bias, scalars, o, lse,
                                     do, dlse)
    return dq, dk, dv, None, None


def _bwd_from_residuals(cfg, q, k, v, bias, scalars, o, lse, do, dlse=None):
    """(dQ, dK, dV) from what the forward kernel left: its output and its
    log-sum-exp. No forward call."""
    why = _forward_only(cfg, v.shape[-1], q.shape[-1])
    if why:
        raise NotImplementedError(f"flash attention backward with {why}")
    # delta_i = sum_d dO_id * O_id  = rowsum(P_dropped * dP); the lse
    # cotangent enters the same P-weighted term (d lse/dS = P), so it folds
    # in by subtraction.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    return _bwd(cfg, q, k, v, bias, scalars, do, lse, delta)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _prepare(q, k, bias, causal, scale, dropout_rate, seed, q_offset,
             k_offset, num_heads, block_q, block_k, interpret, window,
             causal_block=0):
    """The checks, the static configuration and the scalar operands that
    the forward call and a backward from saved residuals share."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    if BH % k.shape[0] or (window and not causal):
        raise ValueError(
            f"flash_attention: q has {BH} batch-heads, k {k.shape[0]}; "
            f"window={window} needs causal")
    if causal_block and (not causal or window or causal_block < 1):
        raise ValueError(
            f"flash_attention: causal_block={causal_block} needs causal "
            f"and no window (window={window})")
    bq, bk = min(block_q or 128, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(
            f"flash_attention needs seq lengths divisible by block sizes: "
            f"Sq={Sq} bq={bq} Sk={Sk} bk={bk}")
    if dropout_rate > 0.0 and interpret:
        raise NotImplementedError(
            "in-kernel dropout uses the TPU PRNG which has no interpret-"
            "mode lowering; use the primitive fallback path off-TPU")
    static = isinstance(q_offset, int) and isinstance(k_offset, int)
    cfg = _Cfg(causal=bool(causal),
               scale=float(scale if scale is not None else D ** -0.5),
               dropout=float(dropout_rate),
               block_q=bq, block_k=bk,
               num_heads=int(num_heads), has_bias=bias is not None,
               interpret=bool(interpret),
               precision=("highest" if q.dtype == jnp.float32
                          else "default"),
               window=int(window), kv_group=BH // k.shape[0],
               causal_block=int(causal_block),
               offsets=(q_offset, k_offset) if static else None,
               auto_q=block_q is None and not dropout_rate > 0.0)
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32),
                         jnp.asarray(seed, jnp.int32)])
    return cfg, bias if bias is None else bias.astype(jnp.float32), scalars


def flash_attention_with_lse(q, k, v, bias: Optional[jax.Array] = None,
                             causal: bool = False,
                             scale: Optional[float] = None,
                             dropout_rate: float = 0.0,
                             seed=0,
                             q_offset=0, k_offset=0,
                             num_heads: int = 1,
                             block_q: Optional[int] = None,
                             block_k: int = 128,
                             interpret: bool = False, window: int = 0,
                             causal_block: int = 0, sink=None):
    """Flash attention over [B*H, S, D] tensors; returns (O, lse).

    ``bias`` is an additive [B, Sk] key bias (the padding-mask encoding —
    models/bert.py builds (mask-1)*10000 exactly like this); ``num_heads``
    tells the kernel how the leading B*H axis factors so bias rows map to
    batches. ``q_offset``/``k_offset`` (may be traced scalars) shift the
    causal comparison to GLOBAL positions for ring attention. ``lse`` is the
    per-row log-sum-exp; its cotangent is honoured, so blockwise
    combinations that re-weight through lse differentiate correctly.

    ``window`` > 0 (with ``causal``) lets a query see only the last
    ``window`` positions, itself included. ``k``/``v`` may have a whole
    fraction of ``q``'s leading B*H rows (grouped-query heads, forward
    only): query head ``n`` reads key/value head ``n // group``.
    ``causal_block`` = L > 0 (with ``causal``, no window) makes the mask
    causal by blocks of L positions: key ``j`` is visible to query ``i``
    iff ``j // L <= i // L`` (block-diffusion prefill). Only the tiles on
    the diagonal differ from the row-causal ones.

    ``v`` may be [.., Sk, Dv] with ``Dv`` other than ``D`` (the output is
    then [B*H, Sq, Dv]). ``sink`` ([num_heads] f32): a head's scalar joins
    its softmax as one more column that carries no value, ``p_ij =
    exp(s_ij - m) / (sum_j' exp(s_ij' - m) + exp(sink_h - m))``; ``lse``
    counts it. Both forward only.

    A causal call visits only the (q-block, k-block) pairs some row of the
    q-block sees under the diagonal and the window
    (:func:`flash_block_visits` counts them). ``block_q`` None (the
    default) is 128 rows for the backward kernels and lets the forward pick
    its own height and tile layout from the shape (:func:`_forward_tile`;
    :func:`flash_forward_grid` names the choice); a ``block_q`` named is
    obeyed. ``o`` and ``lse`` are the 128-row grid's either way.
    """
    cfg, bias, scalars = _prepare(q, k, bias, causal, scale, dropout_rate,
                                  seed, q_offset, k_offset, num_heads,
                                  block_q, block_k, interpret, window,
                                  causal_block)
    if sink is None and v.shape[-1] == q.shape[-1]:
        return _flash(cfg, q, k, v, bias, scalars)
    if sink is not None:            # forward only, like the other width
        cfg = dataclasses.replace(cfg, has_sink=True)
        sink = jnp.asarray(sink, jnp.float32).reshape(cfg.num_heads)
    return _fwd(cfg, q, k, v, bias, scalars, sink)


def flash_attention_bwd(q, k, v, o, lse, do,
                        bias: Optional[jax.Array] = None,
                        causal: bool = False, scale: Optional[float] = None,
                        dropout_rate: float = 0.0, seed=0,
                        num_heads: int = 1, block_q: Optional[int] = None,
                        block_k: int = 128, interpret: bool = False,
                        window: int = 0, causal_block: int = 0):
    """(dQ, dK, dV) of :func:`flash_attention` for the cotangent ``do``,
    from the ``(o, lse)`` that :func:`flash_attention_with_lse` returned
    for the same operands, options and ``seed``: the two backward kernels
    alone, no forward call. What ``jax.vjp`` over the forward computes,
    for a caller that kept the residuals itself."""
    cfg, bias, scalars = _prepare(q, k, bias, causal, scale, dropout_rate,
                                  seed, 0, 0, num_heads, block_q, block_k,
                                  interpret, window, causal_block)
    return _bwd_from_residuals(cfg, q, k, v, bias, scalars, o, lse, do)


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, scale: Optional[float] = None,
                    dropout_rate: float = 0.0, seed=0,
                    num_heads: int = 1, block_q: Optional[int] = None,
                    block_k: int = 128, interpret: bool = False,
                    window: int = 0, causal_block: int = 0, sink=None):
    """Like :func:`flash_attention_with_lse` but returns only O."""
    o, _ = flash_attention_with_lse(
        q, k, v, bias=bias, causal=causal, scale=scale,
        dropout_rate=dropout_rate, seed=seed, num_heads=num_heads,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, causal_block=causal_block, sink=sink)
    return o
