"""``fused_gemm_epilogue``: the op the epilogue-fusion pass rewrites
mul/matmul → elementwise_add → activation → residual → layer_norm chains
into (analysis/epilogue_fusion.py; CODA, PAPERS.md).

Routing mirrors fused_attention.py:

- step lowered for a TPU + supported tiling -> the Pallas fused-GEMM kernel
  (kernels/fused_gemm.py): the whole epilogue runs on the in-VMEM f32
  accumulator tile;
- anything else -> a dense replay of the ORIGINAL unfused op rules, in the
  original order, with the program's AMP policy applied per sub-op exactly
  as ``lowering._lower_op_inner`` would — bit-exact against the unfused
  program by construction (this is what makes the fusion pass's fidelity
  witness an equality check off-TPU).

``FLAGS_use_fused_gemm`` = auto|always|never picks the path; ``always``
off-TPU runs the kernel in interpret mode (slow — tests only) and raises
loudly on unsupported tilings instead of silently falling back.

Kernel block sizes resolve, in order: ``FLAGS_fused_gemm_blocks``
("m,n,k") > the autotuner's best-known config threaded into this
compile's ``LowerCtx.gemm_blocks`` (paddle_tpu.tuning, via the
executor's ``_tuned_compile_config``) > (128, 128, 128).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from .. import flags
from ..core import registry
from ..lowering import lowering_platform, note_kernel_route
from .common import IOSpec, register_op, x

__all__ = ["fused_gemm_route", "resolve_gemm_blocks"]


def resolve_gemm_blocks(ctx=None) -> Tuple[int, int, int]:
    """(block_m, block_n, block_k) for the kernel path: explicit flag wins,
    then the autotuner blocks the executor bound into this compile's
    ``LowerCtx`` (per-compile, never a shared Program attribute — the
    values traced are the values in the compile-cache key even under
    concurrent compiles), then the defaults."""
    from ..kernels.fused_gemm import DEFAULT_BLOCKS

    raw = str(flags.flag("fused_gemm_blocks")).strip()
    if raw:
        parts = [p for p in raw.replace("x", ",").split(",") if p.strip()]
        if len(parts) != 3:
            raise ValueError(
                f"FLAGS_fused_gemm_blocks must be 'm,n,k', got {raw!r}")
        return tuple(int(p) for p in parts)
    tuned = getattr(ctx, "gemm_blocks", None)
    if tuned:
        return tuple(int(b) for b in tuned)
    return DEFAULT_BLOCKS


def fused_gemm_route(m: int, n: int, k: int, *, layer_norm: bool,
                     blocks: Tuple[int, int, int],
                     alpha: float = 1.0, platform=None) -> Tuple[str, str]:
    """('pallas' | 'pallas-interpret' | 'primitive', reason) for a step
    lowered for ``platform`` (``lowering.lowering_platform``). The single
    route authority: the op lowering, the fusion pass's fidelity witness
    and its PT755 reporting must all agree on which path runs."""
    from ..kernels.fused_gemm import classify_gemm

    mode = flags.flag("use_fused_gemm")
    if mode == "never":
        return "primitive", "FLAGS_use_fused_gemm=never"
    if alpha != 1.0:
        # the kernel computes X@Y + epilogue; an alpha-scaled matmul
        # always replays the dense rules (not an 'always'-mode error —
        # there is no kernel variant to insist on)
        return "primitive", f"alpha={alpha} != 1 runs the dense replay"
    kind, reason = classify_gemm(m, n, k, layer_norm=layer_norm,
                                 block_m=blocks[0], block_n=blocks[1],
                                 block_k=blocks[2])
    if kind != "supported":
        if mode == "always":
            # loud, not a silent dense fallback: 'always' is a promise
            raise ValueError(
                f"FLAGS_use_fused_gemm=always but (m={m}, n={n}, k={k}) "
                f"has no kernel tiling: {reason}")
        return "primitive", reason
    if platform == "tpu":
        return "pallas", reason
    if mode == "always":
        return "pallas-interpret", reason
    return "primitive", f"not lowered for a TPU ({reason})"


def _amp_cast(ctx, op_type: str, ins: dict) -> dict:
    """Apply the program's AMP policy to one replayed sub-op, exactly as
    ``lowering._lower_op_inner`` does for the unfused chain."""
    policy = getattr(ctx.program, "_amp_policy", None) if ctx.program \
        else None
    if policy is None:
        return ins
    return policy.cast_ins(op_type, ins)


def _replay(ctx, op_type: str, ins: dict, attrs: dict):
    """Run one original op rule over concrete/traced values (the dense
    fallback path and the witness both go through here)."""
    opdef = registry.get_op_def(op_type)
    full = dict(opdef.attrs and {k: v.default for k, v in
                                 opdef.attrs.items()} or {})
    full.update(attrs)
    return opdef.lower(ctx, _amp_cast(ctx, op_type, ins), full)


def _base_attrs(attrs: dict) -> dict:
    if attrs["base_type"] == "mul":
        return {"x_num_col_dims": attrs["x_num_col_dims"],
                "y_num_col_dims": attrs["y_num_col_dims"]}
    return {"transpose_X": attrs["transpose_X"],
            "transpose_Y": attrs["transpose_Y"],
            "alpha": attrs["alpha"]}


def _primitive_chain(ctx, xv, yv, bias, residual, ln_scale, ln_bias, attrs):
    """The unfused chain, op rule by op rule, in the matched order —
    bit-exact against the original program (same rules, same AMP casts,
    same dtype promotions)."""
    cur = _replay(ctx, attrs["base_type"], {"X": [xv], "Y": [yv]},
                  _base_attrs(attrs))["Out"][0]
    if bias is not None:
        cur = _replay(ctx, "elementwise_add", {"X": [cur], "Y": [bias]},
                      {"axis": attrs["bias_axis"]})["Out"][0]
    act = attrs["activation"]
    if act == "relu":
        cur = _replay(ctx, "relu", {"X": [cur]}, {})["Out"][0]
    elif act == "gelu":
        cur = _replay(ctx, "gelu", {"X": [cur]},
                      {"approximate": attrs["gelu_approximate"]})["Out"][0]
    if residual is not None:
        cur = _replay(ctx, "elementwise_add", {"X": [cur], "Y": [residual]},
                      {"axis": attrs["residual_axis"]})["Out"][0]
    if attrs["layer_norm"]:
        ins = {"X": [cur], "Scale": [ln_scale], "Bias": [ln_bias]}
        cur = _replay(ctx, "layer_norm", ins,
                      {"epsilon": attrs["epsilon"],
                       "begin_norm_axis": attrs["begin_norm_axis"]})["Y"][0]
    return cur


def _gemm_2d_view(xv, yv, attrs):
    """(x2 [M,K], y2 [K,N], out_shape) — the strictly-2-D view the kernel
    computes in; mirrors the mul/matmul rules' own reshapes."""
    if attrs["base_type"] == "mul":
        xnc, ync = attrs["x_num_col_dims"], attrs["y_num_col_dims"]
        xs, ys = xv.shape, yv.shape
        x2 = xv.reshape((int(np.prod(xs[:xnc])), int(np.prod(xs[xnc:]))))
        y2 = yv.reshape((int(np.prod(ys[:ync])), int(np.prod(ys[ync:]))))
        return x2, y2, xs[:xnc] + ys[ync:]
    x2 = jnp.swapaxes(xv, -1, -2) if attrs["transpose_X"] else xv
    y2 = jnp.swapaxes(yv, -1, -2) if attrs["transpose_Y"] else yv
    return x2, y2, (x2.shape[0], y2.shape[1])


@register_op("fused_gemm_epilogue",
             inputs=[IOSpec("X"), IOSpec("Y"),
                     IOSpec("Bias", optional=True, no_grad=True),
                     IOSpec("Residual", optional=True),
                     IOSpec("LnScale", optional=True, no_grad=True),
                     IOSpec("LnBias", optional=True, no_grad=True)],
             outputs=["Out"],
             attrs={"base_type": "mul",
                    "x_num_col_dims": 1, "y_num_col_dims": 1,
                    "transpose_X": False, "transpose_Y": False, "alpha": 1.0,
                    "activation": "none", "gelu_approximate": False,
                    "bias_axis": -1, "residual_axis": -1,
                    "layer_norm": False, "epsilon": 1e-5,
                    "begin_norm_axis": -1},
             grad=None)
def _fused_gemm_epilogue(ctx, ins, attrs):
    """Out = epilogue(X [mul|matmul] Y): bias-add, relu/gelu, residual-add,
    layer_norm — folded into the GEMM on the kernel route, replayed rule by
    rule on the dense route. Only the epilogue-fusion pass emits this op
    (its matcher guarantees the attr/shape invariants); it never carries a
    backward (the pass refuses training programs), so ``grad=None``."""
    xv, yv = x(ins, "X"), x(ins, "Y")
    bias = x(ins, "Bias")
    residual = x(ins, "Residual")
    ln_scale, ln_bias = x(ins, "LnScale"), x(ins, "LnBias")

    blocks = resolve_gemm_blocks(ctx)
    x2, y2, out_shape = _gemm_2d_view(xv, yv, attrs)
    m, k = int(x2.shape[0]), int(x2.shape[1])
    n = int(y2.shape[1])
    route, _reason = fused_gemm_route(
        m, n, k, layer_norm=bool(attrs["layer_norm"]), blocks=blocks,
        alpha=float(attrs.get("alpha", 1.0)),
        platform=lowering_platform(ctx))
    note_kernel_route(ctx, "fused_gemm_epilogue", route)
    if route == "primitive":
        return {"Out": [_primitive_chain(ctx, xv, yv, bias, residual,
                                         ln_scale, ln_bias, attrs)]}

    from ..kernels.fused_gemm import fused_gemm

    policy = getattr(ctx.program, "_amp_policy", None) if ctx.program \
        else None
    if policy is not None and attrs["base_type"] in policy.white:
        cast = policy.compute_dtype
        if x2.dtype == jnp.float32:
            x2 = x2.astype(cast)
        if y2.dtype == jnp.float32:
            y2 = y2.astype(cast)
    res2 = residual.reshape((m, n)) if residual is not None else None
    # the unfused chain's output dtype: the epilogue ops are AMP-neutral,
    # so a compute-dtype GEMM output meeting f32 epilogue params promotes
    # op by op exactly as jnp's binary promotion — the kernel must hand
    # back the same dtype or the fusion pass's witness meta check
    # (rightly) refuses every AMP program on this route
    out_dt = x2.dtype
    for extra in (bias, res2, ln_scale, ln_bias):
        if extra is not None:
            out_dt = jnp.result_type(out_dt, extra.dtype)
    o = fused_gemm(
        x2, y2, bias=bias, residual=res2, ln_scale=ln_scale,
        ln_bias=ln_bias, activation=attrs["activation"],
        gelu_approximate=bool(attrs["gelu_approximate"]),
        layer_norm=bool(attrs["layer_norm"]),
        ln_eps=float(attrs["epsilon"]),
        block_m=blocks[0], block_n=blocks[1], block_k=blocks[2],
        out_dtype=out_dt, interpret=(route == "pallas-interpret"))
    return {"Out": [o.reshape(out_shape)]}
