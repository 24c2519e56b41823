"""Shared helpers for op lowering rules."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.registry import IOSpec, register_op  # re-export for op modules

__all__ = ["register_op", "IOSpec", "x", "out", "broadcast_to_x", "unary",
           "count_by_layer"]


def x(ins, slot="X", i=0):
    """Fetch the i-th value of a slot (None if absent)."""
    vals = ins.get(slot)
    if not vals:
        return None
    return vals[i] if i < len(vals) else None


def out(val, slot="Out"):
    return {slot: [val]}


def count_by_layer(phase: str, stats, layers, rows, calls) -> None:
    """What a serving dispatch fetched of an op that counts one number an
    execution (``Stats`` [..., n, 1]: one count from each of ``n`` ops of a
    kind, in layer order; a chained decode stacks its steps in front;
    ``layers`` names their layers where they are not all of them). Sums go
    on the counter ``rows``, executions on ``calls``, by layer and phase."""
    stats = stats.reshape(-1, stats.shape[-2]).astype(np.int64)
    for j in range(stats.shape[1]):
        lab = dict(layer=str(layers[j] if layers else j), phase=phase)
        rows.labels(**lab).inc(float(stats[:, j].sum()))
        calls.labels(**lab).inc(float(stats.shape[0]))


def broadcast_to_x(xv, yv, axis: int):
    """Reference elementwise broadcast rule (elementwise_op_function.h):
    Y's shape must match a contiguous span of X's dims starting at ``axis``
    (axis==-1 means align trailing dims, i.e. numpy broadcasting)."""
    if xv.shape == yv.shape:
        return yv
    if axis == -1 or axis is None:
        return yv  # numpy trailing-dim broadcasting handles it
    pad_left = axis
    pad_right = xv.ndim - axis - yv.ndim
    if pad_right < 0:
        raise ValueError(
            f"elementwise axis={axis} incompatible: x{xv.shape} y{yv.shape}"
        )
    return yv.reshape((1,) * pad_left + yv.shape + (1,) * pad_right)


def unary(op_type, fn, **kwargs):
    """Register a single-input single-output elementwise op."""

    @register_op(op_type, inputs=["X"], outputs=["Out"], **kwargs)
    def _lower(ctx, ins, attrs, _fn=fn):
        return out(_fn(x(ins)))

    return _lower
