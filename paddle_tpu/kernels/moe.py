"""Routed-expert kernels (Pallas TPU): the router's scores and the grouped
matmul over the experts a chip holds.

A sparse-expert layer routes every token over ALL ``num_experts`` experts
and computes, on this chip, the part of the result that the experts held
here give (``ops/moe.py``). Two kernels carry it:

* :func:`router_scores` — ``sigmoid(x @ w)``, or a softmax over the
  experts, in f32 with true f32 products. The top-k sits on these scores, and a score rounded to bf16
  flips the k-th place between near-equal experts, which changes the
  layer's output by a whole expert's worth: the router is the one matmul
  of the layer that does not run in bf16.
* :func:`grouped_matmul` — rows sorted by expert, each group padded to
  whole ``tm``-row tiles, against ``[experts_held, K, N]`` stacked weights:
  tile ``i`` multiplies by the weights of ``tile_expert[i]`` (a scalar-
  prefetch array, so the weight block's index is known before the tile's
  DMA is issued). With ``rhs2`` the kernel is the gated pair
  ``silu(x @ rhs) * (x @ rhs2)`` on the two f32 products, before one
  rounding to the output type. The row buffer is sized for the worst case
  (every assignment local: no token is ever dropped for capacity), so most
  of its tiles are empty: tiles at or past ``n_valid`` keep the block
  indices of their column sweep's last valid step (no DMA is issued for an
  unchanged block) and skip the body, and their output rows are never
  written.

Grid ``(N/tn, M/tm, K/tk)``: the column block outermost, the tiles inside
it, k innermost. The blocks (:func:`gmm_blocks`) take ``K`` whole wherever
a weight block of ``K`` rows fits its budget, which is every published
width so far; the weight block's index ``(tile_expert[i], 0, n)`` is then
the same for the consecutive tiles of one expert, and the pipeline fetches
it once: an expert's second and later tiles multiply against the block
already in VMEM. So a call streams each hit expert's weights once,
however unevenly the router deals the rows; what an expert's further
tiles cost is their own MXU passes and the row tile (fetched once a
column block). In the decode step a group is a handful of rows (``tm``
16) and the kernel is bound by the weights' bytes. In prefill a group is
hundreds of rows (``tm`` 256) and the same kernel is bound by the MXU.
Where ``K`` has to be split the f32 accumulator(s) sit in VMEM scratch and
a weight block is streamed again for every tile of its expert.
``interpret=True`` runs both kernels on the CPU for the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, _out_sds

__all__ = ["router_scores", "router_scores_reference", "grouped_matmul",
           "grouped_matmul_reference", "gmm_blocks", "gmm_vmem_bytes"]

# the default scoped limit of 16 MiB leaves the double-buffered weight
# blocks (two of them when gated: 16 MiB at ``_W_BLOCK``) no room beside
# the row block, the output block and the accumulators
_VMEM_LIMIT = 48 * 1024 * 1024
# the most bytes of one weight block: large enough that a grid step's
# fixed cost (about 0.35 us) is small beside its DMA (5 us at 4 MiB),
# small enough that the four buffers of a gated call are a third of the
# limit
_W_BLOCK = 4 * 1024 * 1024


def _aligned_divisors(dim: int):
    """The divisors of ``dim`` that are whole 128-lane multiples,
    ascending; the dim itself where there is none (a block equal to the
    array's dim needs no alignment)."""
    return [d for d in range(128, dim + 1, 128) if dim % d == 0] or [dim]


def gmm_blocks(K: int, N: int, itemsize: int = 2):
    """``(tk, tn)`` of :func:`grouped_matmul` for a ``[*, K] @ [*, K, N]``
    product, from the shapes alone: ``tk`` the largest aligned divisor of
    ``K`` whose block of 128 columns is at most ``_W_BLOCK`` bytes (``K``
    itself up to 16,384 in bf16: the weights stay resident across an
    expert's tiles only when ``K`` is whole), then ``tn`` the largest
    aligned divisor of ``N`` that keeps the block at most ``_W_BLOCK``
    (768 -> 768 or 384, not the 256 a power of two would give)."""
    fits = lambda tk, tn: tk * tn * itemsize <= _W_BLOCK
    tks, tns = _aligned_divisors(K), _aligned_divisors(N)
    tk = max([d for d in tks if fits(d, tns[0])] or tks[:1])
    tn = max([d for d in tns if fits(tk, d)] or tns[:1])
    return tk, tn


def gmm_vmem_bytes(tm: int, tk: int, tn: int, gated: bool,
                   itemsize: int = 2, out_itemsize: int = 4) -> int:
    """What one call's pipeline keeps in VMEM: the double-buffered row,
    weight (two when gated) and output blocks, and the f32 accumulator(s)
    where ``K`` is split (counted always: the upper bound)."""
    mats = 2 if gated else 1
    return (2 * tm * tk * itemsize + 2 * mats * tk * tn * itemsize
            + 2 * tm * tn * out_itemsize + mats * tm * tn * 4)


# --------------------------------------------------------------------------
# router
# --------------------------------------------------------------------------

def _score(s, score_fn: str):
    """``sigmoid``: each expert's logit alone; ``softmax``: over all the
    experts of a row (the kernel's block holds the whole row)."""
    if score_fn == "sigmoid":
        return jax.nn.sigmoid(s)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def router_scores_reference(x, w, score_fn: str = "sigmoid"):
    """``score_fn(x @ w)`` with f32 operands and true f32 products."""
    s = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return _score(s, score_fn)


def _router_kernel(score_fn, x_ref, w_ref, o_ref):
    s = jax.lax.dot_general(x_ref[...], w_ref[...],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = _score(s, score_fn)


@jax.named_scope(_PALLAS_SCOPE)
def router_scores(x, w, *, score_fn: str = "sigmoid", block_t: int = 256,
                  interpret: bool = False):
    """x [T, H], w [H, E] -> score_fn(x @ w) [T, E] f32 (``sigmoid``, or
    ``softmax`` over E). ``T`` must divide into ``block_t``-row tiles or
    be one tile of a multiple of 8 rows."""
    T, H = x.shape
    E = w.shape[1]
    bt = block_t if T % block_t == 0 else T
    return pl.pallas_call(
        functools.partial(_router_kernel, score_fn),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((H, E), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, E), lambda i: (i, 0)),
        out_shape=_out_sds((T, E), jnp.float32, x, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_router",
    )(x.astype(jnp.float32), w.astype(jnp.float32))


# --------------------------------------------------------------------------
# grouped matmul
# --------------------------------------------------------------------------

def grouped_matmul_reference(lhs, rhs, tile_expert, n_valid, *, tm,
                             rhs2=None, out_dtype=jnp.float32):
    """Primitive oracle of :func:`grouped_matmul`: the same operand types
    and f32 accumulation, one tile at a time; rows of tiles at or past
    ``n_valid`` come out as zeros (the kernel leaves them unwritten)."""
    M, K = lhs.shape
    tiles = lhs.reshape(M // tm, tm, K)

    def one(i, x):
        e = tile_expert[i]
        mm = lambda w: jnp.matmul(x, w[e], preferred_element_type=jnp.float32)
        y = mm(rhs)
        if rhs2 is not None:
            y = jax.nn.silu(y) * mm(rhs2)
        return jnp.where(i < n_valid, y, 0.0).astype(out_dtype)

    out = jax.vmap(one)(jnp.arange(M // tm), tiles)
    return out.reshape(M, -1)


def _gmm_kernel(mats, nk, te_ref, nv_ref, x_ref, *refs):
    """``mats`` weight blocks (two when gated), the output block, and one
    f32 accumulator a weight where ``nk > 1``."""
    w_refs, o_ref, accs = refs[:mats], refs[mats], refs[mats + 1:]
    i, k = pl.program_id(1), pl.program_id(2)

    def finish(ys):
        y = jax.nn.silu(ys[0]) * ys[1] if mats == 2 else ys[0]
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(i < nv_ref[0])
    def _tile():
        dots = [jax.lax.dot_general(
            x_ref[...], w[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for w in w_refs]
        if nk == 1:                 # K whole: one dot, no accumulator
            finish(dots)
            return

        @pl.when(k == 0)
        def _first():
            for acc, d in zip(accs, dots):
                acc[:] = d

        @pl.when(k > 0)
        def _add():
            for acc, d in zip(accs, dots):
                acc[:] += d

        @pl.when(k == nk - 1)
        def _finish():
            finish([acc[:] for acc in accs])


@jax.named_scope(_PALLAS_SCOPE)
def grouped_matmul(lhs, rhs, tile_expert, n_valid, *, tm: int, rhs2=None,
                   out_dtype=jnp.float32, blocks=None,
                   interpret: bool = False):
    """``lhs`` [M, K] (rows sorted by expert, groups padded to whole
    ``tm``-row tiles) times ``rhs`` [E, K, N]: tile ``i`` uses
    ``rhs[tile_expert[i]]``. ``tile_expert`` [M // tm] int32; ``n_valid``
    (int32 scalar) is the number of leading tiles that hold rows. With
    ``rhs2`` (same shape) the result is ``silu(x @ rhs) * (x @ rhs2)``.
    ``blocks``: ``(tk, tn)`` in place of :func:`gmm_blocks`' (the probe
    and the tests; the op passes none). Returns [M, N] of ``out_dtype``;
    rows of tiles past ``n_valid`` are not written (whatever the buffer
    held)."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    if K2 != K or M % tm or (rhs2 is not None and rhs2.shape != rhs.shape):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape} (tm={tm}), rhs {rhs.shape}, "
            f"rhs2 {None if rhs2 is None else rhs2.shape} do not line up")
    gated = rhs2 is not None
    itemsize = jnp.dtype(rhs.dtype).itemsize
    tk, tn = blocks or gmm_blocks(K, N, itemsize)
    held = gmm_vmem_bytes(tm, tk, tn, gated, itemsize,
                          jnp.dtype(out_dtype).itemsize)
    if K % tk or N % tn or held > _VMEM_LIMIT:
        raise ValueError(
            f"grouped_matmul: blocks ({tk}, {tn}) of a [{K}, {N}] product "
            f"at tm={tm} do not divide it or hold {held} bytes of VMEM "
            f"(limit {_VMEM_LIMIT})")
    nt, nn, nk = M // tm, N // tn, K // tk
    tile_expert = jnp.clip(tile_expert.astype(jnp.int32), 0, E - 1)
    nv = jnp.minimum(jnp.asarray(n_valid, jnp.int32).reshape(1), nt)

    # a tile past the valid ones holds every block index where its column
    # sweep's last valid step left it, so the pipeline issues no DMA for it
    def frozen(fn):
        def index(n, i, k, te, nv):
            live = i < nv[0]
            last = jnp.maximum(nv[0] - 1, 0)
            return fn(jnp.where(live, i, last), n,
                      jnp.where(live, k, nk - 1), te)
        return index

    # with nk == 1 consecutive tiles of one expert give the same index:
    # the block stays where it is
    w_spec = pl.BlockSpec((1, tk, tn),
                          frozen(lambda i, n, k, te: (te[i], k, n)))
    mats = 2 if gated else 1
    in_specs = [pl.BlockSpec((tm, tk), frozen(lambda i, n, k, te: (i, k)))
                ] + [w_spec] * mats
    acc = pltpu.VMEM((tm, tn), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nn, nt, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn),
                               frozen(lambda i, n, k, te: (i, n))),
        scratch_shapes=[acc] * (mats if nk > 1 else 0),
    )
    args = (lhs, rhs) + ((rhs2,) if gated else ())
    return pl.pallas_call(
        functools.partial(_gmm_kernel, mats, nk),
        grid_spec=grid_spec,
        out_shape=_out_sds((M, N), out_dtype, *args),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_expert_matmul",
    )(tile_expert, nv, *args)
