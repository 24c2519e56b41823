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
  ``silu(x @ rhs) * (x @ rhs2)`` on the two f32 accumulators, before one
  rounding to the output type. The row buffer is sized for the worst case
  (every assignment local: no token is ever dropped for capacity), so most
  of its tiles are empty: tiles at or past ``n_valid`` keep the block
  indices of the last valid step (no DMA is issued for an unchanged block)
  and skip the body, and their output rows are never written.

Grid ``(M/tm, N/tn, K/tk)``, k innermost with the f32 accumulator(s) in
VMEM scratch. In the decode step a group is a handful of rows (``tm`` 16)
and the kernel streams each hit expert's weights once: it is bound by the
weights' bytes. In prefill a group is hundreds of rows (``tm`` 256) and
the same kernel is bound by the MXU. ``interpret=True`` runs both kernels
on the CPU for the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, _out_sds

__all__ = ["router_scores", "router_scores_reference", "grouped_matmul",
           "grouped_matmul_reference", "gmm_blocks"]

# the two double-buffered weight blocks of the gated kernel take 8 MiB at
# (2048, 512); the default scoped limit of 16 MiB leaves them no room
# beside the row block and the accumulators
_VMEM_LIMIT = 48 * 1024 * 1024


def _block(dim: int, want: int) -> int:
    """The largest divisor of ``dim`` that is ``want`` or a power-of-two
    fraction of it down to 128 lanes; the whole dim where none divides (a
    block equal to the array's dim needs no alignment)."""
    b = want
    while b >= 128:
        if dim % b == 0:
            return b
        b //= 2
    return dim


def gmm_blocks(K: int, N: int, block_k: int = 2048, block_n: int = 512):
    """``(tk, tn)`` of :func:`grouped_matmul` for a ``[*, K] @ [*, K, N]``
    product (the benchmark's ops-and-bytes count reads the same rule)."""
    return _block(K, block_k), _block(N, block_n)


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


def _gmm_kernel(gated, te_ref, nv_ref, *refs):
    if gated:
        x_ref, w_ref, w2_ref, o_ref, acc, acc2 = refs
    else:
        x_ref, w_ref, o_ref, acc = refs
    i, k = pl.program_id(0), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(i < nv_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            if gated:
                acc2[:] = jnp.zeros_like(acc2)

        dot = lambda w: jax.lax.dot_general(
            x_ref[...], w[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] += dot(w_ref)
        if gated:
            acc2[:] += dot(w2_ref)

        @pl.when(k == nk - 1)
        def _finish():
            y = acc[:]
            if gated:
                y = jax.nn.silu(y) * acc2[:]
            o_ref[...] = y.astype(o_ref.dtype)


@jax.named_scope(_PALLAS_SCOPE)
def grouped_matmul(lhs, rhs, tile_expert, n_valid, *, tm: int, rhs2=None,
                   out_dtype=jnp.float32, block_k: int = 2048,
                   block_n: int = 512, interpret: bool = False):
    """``lhs`` [M, K] (rows sorted by expert, groups padded to whole
    ``tm``-row tiles) times ``rhs`` [E, K, N]: tile ``i`` uses
    ``rhs[tile_expert[i]]``. ``tile_expert`` [M // tm] int32; ``n_valid``
    (int32 scalar) is the number of leading tiles that hold rows. With
    ``rhs2`` (same shape) the result is ``silu(x @ rhs) * (x @ rhs2)``.
    Returns [M, N] of ``out_dtype``; rows of tiles past ``n_valid`` are
    not written (whatever the buffer held)."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    if K2 != K or M % tm or (rhs2 is not None and rhs2.shape != rhs.shape):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape} (tm={tm}), rhs {rhs.shape}, "
            f"rhs2 {None if rhs2 is None else rhs2.shape} do not line up")
    tk, tn = gmm_blocks(K, N, block_k, block_n)
    nt, nn, nk = M // tm, N // tn, K // tk
    gated = rhs2 is not None
    tile_expert = jnp.clip(tile_expert.astype(jnp.int32), 0, E - 1)
    nv = jnp.minimum(jnp.asarray(n_valid, jnp.int32).reshape(1), nt)

    # a tile past the valid ones holds every block index where the last
    # valid step left it, so the pipeline issues no DMA for it
    def frozen(fn):
        def index(i, n, k, te, nv):
            live = i < nv[0]
            last = jnp.maximum(nv[0] - 1, 0)
            return fn(jnp.where(live, i, last), jnp.where(live, n, nn - 1),
                      jnp.where(live, k, nk - 1), te)
        return index

    w_spec = pl.BlockSpec((1, tk, tn),
                          frozen(lambda i, n, k, te: (te[i], k, n)))
    in_specs = [pl.BlockSpec((tm, tk), frozen(lambda i, n, k, te: (i, k))),
                w_spec] + ([w_spec] if gated else [])
    acc = pltpu.VMEM((tm, tn), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn),
                               frozen(lambda i, n, k, te: (i, n))),
        scratch_shapes=[acc] + ([acc] if gated else []),
    )
    args = (lhs, rhs) + ((rhs2,) if gated else ())
    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated),
        grid_spec=grid_spec,
        out_shape=_out_sds((M, N), out_dtype, *args),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_expert_matmul",
    )(tile_expert, nv, *args)
