"""GEMM-epilogue fusion as a Pallas TPU kernel family — the CODA rewrite.

The flash-attention kernel (kernels/flash_attention.py) fused softmax into
the attention matmuls because whole-graph XLA fusion cannot keep the [S, S]
score matrix out of HBM. This module applies the same move to the OTHER
matmul-shaped hot path: the ``mul``/``matmul`` → bias-add → activation →
residual-add → layer_norm chains every fc/FFN layer builds. XLA fuses the
elementwise tail *after* the matmul writes its result to HBM; the Pallas
kernel applies the whole epilogue on the f32 accumulator tile while it is
still in VMEM, so the fused chain costs one HBM round-trip instead of one
per epilogue op (CODA, PAPERS.md arXiv 2605.19269: transformer blocks as
GEMM-epilogue programs recover most of the lost MXU utilisation).

Design notes
- The GEMM view is strictly 2-D: ``[M, K] @ [K, N]`` (the ``mul`` op already
  reshapes to 2-D; the fusion pass only matches epilogues expressible in
  this view — a 1-D ``[N]`` bias, an ``[M, N]`` residual, row-wise
  layer_norm).
- Grid is ``(M/bm, N/bn, K/bk)`` with the k axis innermost ("arbitrary" —
  TPU grid steps run sequentially per core, so the f32 accumulator lives in
  VMEM scratch across k steps, flash-attention style). The epilogue runs on
  the final k step only.
- layer_norm needs the WHOLE output row to compute its row statistics, so
  it requires ``bn == N`` (one n-block). ``classify_gemm`` refuses loudly
  otherwise — callers fall back to the dense path, never a silent wrong
  tiling.
- ``interpret=True`` runs the identical kernel on CPU for parity tests.
- Accumulation is f32 with the epilogue applied in f32 before one final
  cast to the output dtype. This is *more* accurate than the unfused chain
  under bf16 (which round-trips through bf16 between ops), which is why the
  fusion pass's fidelity witness compares against a declared per-epilogue
  tolerance on the kernel route and exact bits on the dense route.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE

__all__ = ["fused_gemm", "classify_gemm", "supports_gemm",
           "fused_gemm_reference", "DEFAULT_BLOCKS", "EPILOGUE_ACTIVATIONS"]

DEFAULT_BLOCKS = (128, 128, 128)          # (block_m, block_n, block_k)
EPILOGUE_ACTIVATIONS = ("none", "relu", "gelu")

# largest bm*N f32 row-tile the layer_norm epilogue may hold in VMEM
# (one accumulator tile; v5e VMEM is 128 MiB but Mosaic wants headroom)
_LN_MAX_ROW_BYTES = 4 << 20


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static kernel configuration (hashable)."""

    block_m: int
    block_n: int
    block_k: int
    has_bias: bool
    activation: str            # 'none' | 'relu' | 'gelu'
    gelu_approximate: bool
    has_residual: bool
    layer_norm: bool
    ln_eps: float
    has_ln_scale: bool
    has_ln_bias: bool
    interpret: bool
    precision: str             # 'highest' for f32 inputs, 'default' for bf16


def classify_gemm(m: int, n: int, k: int, *, layer_norm: bool = False,
                  block_m: int = 128, block_n: int = 128,
                  block_k: int = 128) -> Tuple[str, str]:
    """Classify a fused-GEMM shape for the kernel layer.

    Returns ``(kind, reason)`` with ``kind`` one of ``'supported'`` /
    ``'unsupported'``; ``reason`` names exactly which constraint failed so
    callers can refuse loudly (``FLAGS_use_fused_gemm=always``) or fall
    back to the dense path with the why on record. Constraints are the
    real Mosaic tiling rules: whole blocks in every dim, f32 tile geometry
    (sublanes % 8, lanes % 128), and for layer_norm one n-block covering
    the full row (the row statistics need the whole row in VMEM).
    """
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if layer_norm:
        bn = n
    bad = []
    if m % bm:
        bad.append(f"m={m} % block_m={bm}")
    if n % bn:
        bad.append(f"n={n} % block_n={bn}")
    if k % bk:
        bad.append(f"k={k} % block_k={bk}")
    if bad:
        return ("unsupported",
                f"GEMM dims must divide into whole kernel blocks: "
                f"{', '.join(bad)} != 0 (pad the operand or pick block "
                f"sizes that divide it)")
    if bm % 8:
        return ("unsupported",
                f"block_m={bm} is not a multiple of 8 (f32 sublane tile)")
    if bn % 128:
        return ("unsupported",
                f"block_n={bn} is not a multiple of 128 (lane tile)")
    if bk % 128:
        return ("unsupported",
                f"block_k={bk} is not a multiple of 128 (lane tile of the "
                f"X block / sublane-aligned K of the Y block)")
    if layer_norm and bm * n * 4 > _LN_MAX_ROW_BYTES:
        return ("unsupported",
                f"layer_norm epilogue needs the whole row in VMEM: "
                f"block_m={bm} x n={n} f32 is "
                f"{bm * n * 4 >> 20} MiB > {_LN_MAX_ROW_BYTES >> 20} MiB "
                f"(shrink block_m)")
    return ("supported",
            f"{m // bm} x {n // bn} x {k // bk} blocks of "
            f"({bm}, {bn}, {bk})" + (" with whole-row layer_norm"
                                     if layer_norm else ""))


def supports_gemm(m: int, n: int, k: int, *, layer_norm: bool = False,
                  block_m: int = 128, block_n: int = 128,
                  block_k: int = 128) -> bool:
    return classify_gemm(m, n, k, layer_norm=layer_norm, block_m=block_m,
                         block_n=block_n, block_k=block_k)[0] == "supported"


def _rows8(v):
    """[N] row vector -> [8, N] sublane-replicated (Mosaic block shapes
    need sublanes % 8; a 1-D operand cannot tile)."""
    return jnp.broadcast_to(v[None, :], (8, v.shape[0]))


def _apply_activation(acc, cfg: _Cfg):
    if cfg.activation == "relu":
        return jnp.maximum(acc, 0.0)
    if cfg.activation == "gelu":
        return jax.nn.gelu(acc, approximate=cfg.gelu_approximate)
    return acc


def _kernel(cfg: _Cfg, *refs):
    idx = 0
    x_ref = refs[idx]; idx += 1
    y_ref = refs[idx]; idx += 1
    b_ref = r_ref = s_ref = lb_ref = None
    if cfg.has_bias:
        b_ref = refs[idx]; idx += 1
    if cfg.has_residual:
        r_ref = refs[idx]; idx += 1
    if cfg.has_ln_scale:
        s_ref = refs[idx]; idx += 1
    if cfg.has_ln_bias:
        lb_ref = refs[idx]; idx += 1
    o_ref, acc = refs[idx], refs[idx + 1]

    kk = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=cfg.precision)

    @pl.when(kk == num_k - 1)
    def _epilogue():
        a = acc[...]
        if cfg.has_bias:
            a = a + b_ref[0].astype(jnp.float32)[None, :]
        a = _apply_activation(a, cfg)
        if cfg.has_residual:
            a = a + r_ref[...].astype(jnp.float32)
        if cfg.layer_norm:
            # whole row in this tile by construction (bn == N)
            mean = jnp.mean(a, axis=1, keepdims=True)
            var = jnp.mean(jnp.square(a - mean), axis=1, keepdims=True)
            a = (a - mean) / jnp.sqrt(var + cfg.ln_eps)
            if cfg.has_ln_scale:
                a = a * s_ref[0].astype(jnp.float32)[None, :]
            if cfg.has_ln_bias:
                a = a + lb_ref[0].astype(jnp.float32)[None, :]
        o_ref[...] = a.astype(o_ref.dtype)


@jax.named_scope(_PALLAS_SCOPE)
def fused_gemm(x, y, bias=None, residual=None, ln_scale=None, ln_bias=None,
               activation: str = "none", gelu_approximate: bool = False,
               layer_norm: bool = False, ln_eps: float = 1e-5,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               out_dtype=None, interpret: bool = False):
    """``epilogue(x @ y)`` with the epilogue applied on the in-VMEM f32
    accumulator tile: optional bias-add (``bias`` [N]), activation
    (``relu``/``gelu``), residual-add (``residual`` [M, N]) and row-wise
    layer_norm (``ln_scale``/``ln_bias`` [N]), in that order — the order
    the fusion pass matched them in the Program IR.

    ``x`` [M, K], ``y`` [K, N]; raises ``ValueError`` with the
    ``classify_gemm`` reason on unsupported tilings (callers decide
    between loud refusal and the dense fallback *before* calling).
    """
    if x.ndim != 2 or y.ndim != 2 or x.shape[1] != y.shape[0]:
        raise ValueError(
            f"fused_gemm is strictly 2-D [M,K]@[K,N]: got x{x.shape} "
            f"y{y.shape}")
    if activation not in EPILOGUE_ACTIVATIONS:
        raise ValueError(f"unknown epilogue activation {activation!r} — "
                         f"one of {EPILOGUE_ACTIVATIONS}")
    m, k = x.shape
    n = y.shape[1]
    kind, reason = classify_gemm(m, n, k, layer_norm=layer_norm,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k)
    if kind != "supported":
        raise ValueError(f"fused_gemm has no kernel tiling for "
                         f"(m={m}, n={n}, k={k}): {reason}")
    bm, bn, bk = min(block_m, m), (n if layer_norm else min(block_n, n)), \
        min(block_k, k)
    out_dtype = out_dtype or x.dtype
    cfg = _Cfg(block_m=bm, block_n=bn, block_k=bk,
               has_bias=bias is not None,
               activation=activation,
               gelu_approximate=bool(gelu_approximate),
               has_residual=residual is not None,
               layer_norm=bool(layer_norm), ln_eps=float(ln_eps),
               has_ln_scale=ln_scale is not None,
               has_ln_bias=ln_bias is not None,
               interpret=bool(interpret),
               precision=("highest" if x.dtype == jnp.float32 else "default"))

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    args = [x, y]
    rowspec = pl.BlockSpec((8, bn), lambda i, j, kk: (0, j))
    if bias is not None:
        in_specs.append(rowspec)
        args.append(_rows8(bias))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        args.append(residual)
    if ln_scale is not None:
        in_specs.append(rowspec)
        args.append(_rows8(ln_scale))
    if ln_bias is not None:
        in_specs.append(rowspec)
        args.append(_rows8(ln_bias))

    out = pl.pallas_call(
        functools.partial(_kernel, cfg),
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="fused_gemm",
    )(*args)
    return out


def fused_gemm_reference(x, y, bias=None, residual=None, ln_scale=None,
                         ln_bias=None, activation: str = "none",
                         gelu_approximate: bool = False,
                         layer_norm: bool = False, ln_eps: float = 1e-5,
                         out_dtype=None):
    """Dense oracle with the KERNEL's numerics (f32 accumulate + epilogue,
    one final cast): what the kernel must match in parity tests. The
    *op-level* dense fallback (ops/fused_gemm.py) instead replays the
    original unfused op rules so it is bit-exact against the unfused
    program — two different fidelity contracts, both tested."""
    acc = jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=("highest" if x.dtype == jnp.float32 else "default"))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)[None, :]
    if activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc, approximate=bool(gelu_approximate))
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    if layer_norm:
        mean = jnp.mean(acc, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(acc - mean), axis=1, keepdims=True)
        acc = (acc - mean) / jnp.sqrt(var + ln_eps)
        if ln_scale is not None:
            acc = acc * ln_scale.astype(jnp.float32)[None, :]
        if ln_bias is not None:
            acc = acc + ln_bias.astype(jnp.float32)[None, :]
    return acc.astype(out_dtype or x.dtype)
