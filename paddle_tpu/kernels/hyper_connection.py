"""Manifold-constrained hyper-connections (Pallas TPU): the read and the
write of an ``n``-stream residual path, each ONE pass over the stream.

``ops/hyper_connection.py`` has the equations. A token's stream is ``n x
C`` f32 numbers (4 x 3,584: 57 KB), and every sublayer reads all of it and
rewrites all of it, so the path is bound by the stream's bytes. Written
in ``jax.numpy`` the read is three operations that each fetch the stream
(the mean square, the projection, the mix ``H_pre X``) with the
coefficients' small arithmetic between them, and the write a fourth and
fifth; here

* :func:`hc_read` fetches a tile of 128 token rows once and makes from it
  the rows' mean squares, their projections onto the ``n (n + 2)``
  coefficient directions (one true-f32 product a stream, tokens in the
  lanes: ``Proj`` [n(n+2), nC] against the tile's transpose), the
  coefficients (sigmoids, the clamp, ``exp`` and the Sinkhorn rounds on
  ``[1, 128]`` rows, a token a lane) and ``u = H_pre X``;
* :func:`hc_write` fetches the tile, the sublayer's output and the
  coefficients and writes ``H_res X + H_post^T y``.

The coefficients travel between the two as ONE lane-dense array ``[rows,
128]``: lanes ``0..n-1`` ``H_pre``, ``n..2n-1`` ``H_post``, ``2n..2n+n^2-1``
``H_res`` row-major, zeros after. Rows come in whole tiles of
:data:`ROW_TILE` (a decode step's slots, a prefill bucket's rows); the op
takes its primitive route for anything else. ``interpret=True`` runs both
on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, _out_sds

__all__ = ["hc_read", "hc_write", "hc_read_reference", "hc_write_reference",
           "supports", "ROW_TILE", "COEF_LANES"]

ROW_TILE = 128          # token rows a grid step carries (one lane tile)
COEF_LANES = 128        # lanes of a row of coefficients
# a tile of 128 rows x 14,336 f32 is 7.3 MB: the read holds two of them
# beside the projection (1.4 MB) and its outputs, 25 MB; the write two in
# and two out beside the sublayer's output, 36 MB: over the default 16 MiB
_VMEM_LIMIT = 48 * 1024 * 1024
_HIGHEST = jax.lax.Precision.HIGHEST


def supports(rows: int, n: int, C: int) -> bool:
    """Whether the kernels take ``rows`` token rows of ``n`` streams of
    ``C``: rows in whole tiles, streams in whole lane tiles, ``H_pre``
    beside ``H_post`` one sublane tile."""
    return (rows % ROW_TILE == 0 and C % 128 == 0 and 2 * n == 8
            and n * (n + 2) <= COEF_LANES)


def sinkhorn(a, iters: int, eps: float):
    """``iters`` rounds on ``a`` [..., n, n]: each row over (its sum +
    ``eps``), then each column over (its sum + ``eps``)."""
    for _ in range(int(iters)):
        a = a / (jnp.sum(a, axis=-1, keepdims=True) + eps)
        a = a / (jnp.sum(a, axis=-2, keepdims=True) + eps)
    return a


def _coefficient_scale(alpha, n: int):
    """``[a_pre] * n + [a_post] * n + [a_res] * n^2``: a coefficient's
    scalar beside its bias."""
    return jnp.repeat(alpha.astype(jnp.float32), np.array([n, n, n * n]),
                      total_repeat_length=n * (n + 2))


def hc_read_reference(x, proj, alpha, bias, *, n: int,
                      sinkhorn_iters: int = 20, eps: float = 1e-6,
                      norm_eps: float = 1e-6, clamp=(-30.0, 30.0)):
    """Primitive oracle of :func:`hc_read`, and the op's route off the
    TPU: the written equations, any number of rows and streams. Returns
    ``(u [rows, C], coef [rows, n (n + 2)]: H_pre | H_post | H_res, err)``."""
    R, nC = x.shape
    xn = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                           + norm_eps)
    z = jnp.einsum("rk,mk->rm", xn, proj, precision=_HIGHEST)
    h = z * _coefficient_scale(alpha, n) + bias
    res = sinkhorn(jnp.exp(jnp.clip(h[:, 2 * n:], clamp[0], clamp[1])
                           ).reshape(R, n, n), sinkhorn_iters, eps)
    pre = jax.nn.sigmoid(h[:, :n])
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0)))
    u = jnp.sum(pre[:, :, None] * x.reshape(R, n, nC // n), axis=1)
    coef = jnp.concatenate([pre, 2.0 * jax.nn.sigmoid(h[:, n:2 * n]),
                            res.reshape(R, n * n)], axis=1)
    return u, coef, err


def hc_write_reference(x, y, post, res, *, n: int):
    """Primitive oracle of :func:`hc_write`: ``H_res X + H_post^T y``."""
    R, nC = x.shape
    C = nC // n
    out = jnp.sum(res.reshape(R, n, n, 1) * x.reshape(R, 1, n, C), axis=2) \
        + post[:, :, None] * y[:, None, :]
    return out.reshape(R, nC)


def _read_kernel(n, C, iters, eps, norm_eps, clamp, x_ref, p_ref, ab_ref,
                 u_ref, coef_ref, err_ref, c_scr):
    T, m = ROW_TILE, n * (n + 2)
    stream = lambda i: x_ref[:, i * C:(i + 1) * C]            # [T, C]
    # the rows' mean squares (a row a sublane) and their projections (a
    # row a lane): zt[k, t] = sum_j Proj[k, j] x[t, j]
    ss = jnp.zeros((T, 1), jnp.float32)
    zt = jnp.zeros((m, T), jnp.float32)
    for i in range(n):
        xi = stream(i)
        ss += jnp.sum(xi * xi, axis=1, keepdims=True)
        zt += jax.lax.dot_general(
            p_ref[:, i * C:(i + 1) * C], xi, (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
    inv = jax.lax.rsqrt(ss / (n * C) + norm_eps)              # [T, 1]
    # the same, a row a lane
    inv_t = jnp.transpose(jnp.broadcast_to(inv, (T, T)))[:1]  # [1, T]
    h = zt * inv_t * ab_ref[0] + ab_ref[1]                    # [m, T]
    # rows 0..n-1 sigmoid, n..2n-1 twice the sigmoid (2n = 8 sublanes)
    twice = jax.lax.broadcasted_iota(jnp.int32, (2 * n, T), 0) >= n
    c_scr[...] = jnp.zeros_like(c_scr)
    c_scr[0:2 * n, :] = jax.nn.sigmoid(h[0:2 * n]) * jnp.where(twice, 2.0,
                                                               1.0)
    c_scr[2 * n:m, :] = jnp.exp(jnp.clip(h[2 * n:m], clamp[0], clamp[1]))
    # the Sinkhorn rounds, one [1, T] row an entry of H_res
    e = [[c_scr[2 * n + n * i + j:2 * n + n * i + j + 1, :]
          for j in range(n)] for i in range(n)]
    total = lambda rows: functools.reduce(lambda a, b: a + b, rows)
    for _ in range(iters):
        for i in range(n):
            s = total(e[i]) + eps
            e[i] = [v / s for v in e[i]]
        for j in range(n):
            s = total([e[i][j] for i in range(n)]) + eps
            for i in range(n):
                e[i][j] = e[i][j] / s
    err = jnp.zeros((1, T), jnp.float32)
    for i in range(n):
        err = jnp.maximum(err, jnp.abs(total(e[i]) - 1.0))
        err = jnp.maximum(err, jnp.abs(
            total([e[j][i] for j in range(n)]) - 1.0))
        for j in range(n):
            k = 2 * n + n * i + j
            c_scr[k:k + 1, :] = e[i][j]
    err_ref[...] = jnp.broadcast_to(err, err_ref.shape)
    coef = jnp.transpose(c_scr[...])                          # [T, lanes]
    coef_ref[...] = coef
    u = coef[:, 0:1] * stream(0)
    for i in range(1, n):
        u += coef[:, i:i + 1] * stream(i)
    u_ref[...] = u


def hc_read(x, proj, alpha, bias, *, n: int, sinkhorn_iters: int = 20,
            eps: float = 1e-6, norm_eps: float = 1e-6,
            clamp=(-30.0, 30.0), interpret: bool = False):
    """``x`` [rows, n C] f32 (a token's streams side by side), ``proj``
    [n (n + 2), n C], ``alpha`` [3], ``bias`` [n (n + 2)] -> ``(u [rows,
    C], coef [rows, 128], err [])``: what the sublayer reads, the rows'
    coefficients (the module's layout) and the largest ``|row or column
    sum - 1|`` of an ``H_res``. ``rows`` in whole tiles of 128."""
    return _read(x, proj, alpha, bias, n=int(n), iters=int(sinkhorn_iters),
                 eps=float(eps), norm_eps=float(norm_eps),
                 clamp=(float(clamp[0]), float(clamp[1])),
                 interpret=bool(interpret))


# One traced callable a set of shapes (as ``flash_attention._fwd``): a
# program's 16 sublayers share one traced kernel and one Mosaic body in the
# lowered module, where each call traced anew cost 0.4 s of set-up a pair.
@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "norm_eps",
                                             "clamp", "interpret"))
@jax.named_scope(_PALLAS_SCOPE)
def _read(x, proj, alpha, bias, *, n, iters, eps, norm_eps, clamp,
          interpret):
    R, nC = x.shape
    C, m = nC // n, n * (n + 2)
    if not supports(R, n, C) or nC != n * C or proj.shape != (m, nC):
        raise ValueError(
            f"hc_read: x {x.shape} as {n} streams, proj {proj.shape}: rows "
            f"in tiles of {ROW_TILE}, streams in lane tiles, 2n a sublane "
            f"tile")
    # a_pre, a_post, a_res and the biases, a coefficient a sublane
    ab = jnp.broadcast_to(
        jnp.stack([_coefficient_scale(alpha, n),
                   bias.astype(jnp.float32)])[:, :, None], (2, m, ROW_TILE))
    nt = R // ROW_TILE
    rows = lambda w: pl.BlockSpec((ROW_TILE, w), lambda t: (t, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape))
    u, coef, err = pl.pallas_call(
        functools.partial(_read_kernel, n, C, iters, eps, norm_eps, clamp),
        grid=(nt,),
        in_specs=[rows(nC), whole((m, nC)), whole((2, m, ROW_TILE))],
        out_specs=[rows(C), rows(COEF_LANES),
                   pl.BlockSpec((8, ROW_TILE), lambda t: (t, 0))],
        out_shape=[_out_sds((R, C), jnp.float32, x),
                   _out_sds((R, COEF_LANES), jnp.float32, x),
                   _out_sds((nt * 8, ROW_TILE), jnp.float32, x)],
        scratch_shapes=[pltpu.VMEM((COEF_LANES, ROW_TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hc_read",
    )(x, proj.astype(jnp.float32), ab)
    return u, coef, jnp.max(err)


def _write_kernel(n, C, x_ref, y_ref, coef_ref, o_ref):
    coef, y = coef_ref[...], y_ref[...]
    col = lambda k: coef[:, k:k + 1]                          # [T, 1]
    for i in range(n):
        acc = col(n + i) * y
        for j in range(n):
            acc += col(2 * n + n * i + j) * x_ref[:, j * C:(j + 1) * C]
        o_ref[:, i * C:(i + 1) * C] = acc


def hc_write(x, y, post, res, *, n: int, interpret: bool = False):
    """``x`` [rows, n C], the sublayer's output ``y`` [rows, C], ``post``
    [rows, n] and ``res`` [rows, n n] (all f32) -> ``H_res X + H_post^T
    y`` [rows, n C]."""
    return _write(x, y, post, res, n=int(n), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
@jax.named_scope(_PALLAS_SCOPE)
def _write(x, y, post, res, *, n, interpret):
    R, nC = x.shape
    C = nC // n
    if (not supports(R, n, C) or y.shape != (R, C)
            or post.shape != (R, n) or res.shape != (R, n * n)):
        raise ValueError(
            f"hc_write: x {x.shape} as {n} streams, y {y.shape}, post "
            f"{post.shape}, res {res.shape}")
    coef = jnp.pad(jnp.concatenate([post, res], axis=1),
                   [(0, 0), (n, COEF_LANES - n * (n + 2))])
    rows = lambda w: pl.BlockSpec((ROW_TILE, w), lambda t: (t, 0))
    return pl.pallas_call(
        functools.partial(_write_kernel, n, C),
        grid=(R // ROW_TILE,),
        in_specs=[rows(nC), rows(C), rows(COEF_LANES)],
        out_specs=rows(nC),
        out_shape=_out_sds((R, nC), jnp.float32, x, y),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hc_write",
    )(x, y, coef)
