"""The Mamba-2 mixer's recurrent part as one op: the short causal
convolution over ``[x | B | C]``, ``softplus(dt + dt_bias)``, the selective
scan with a scalar decay a head (``kernels/ssd.py``), the skip ``D x``, and
the write of both pieces of state.

``mamba2_scan`` is one rule in two forms, as ``gated_delta_rule`` is
(``ops/gdn.py``). Both read the layer's projections before the convolution
and keep two pieces of state per slot: the scan's ``[H, P, N]`` f32 state
and the convolution's tail (the last ``taps - 1`` rows that went into it).

* ``mode="scan"`` — ``R`` whole prompts of up to ``S`` rows. Each starts
  from a zero state and OVERWRITES the state of the slot it names. Rows
  past a prompt's length (``Mask`` 0; the real rows are a prefix) stand
  still: ``dt`` 0, so decay 1 and no input, and the tail is taken at the
  prompt's last real rows, not at the bucket's.
* ``mode="step"`` — one token for every slot, read from and written back
  into the state under the decode gate (``Mask`` [slots, 1]): a slot whose
  gate is 0 keeps both pieces bit for bit.

Everything here is f32: the state feeds itself through every later row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import IOSpec, register_op, x
from .gdn import short_conv, write_slots
from .. import flags
from ..lowering import lowering_platform, note_kernel_route

F32 = jnp.float32


def _route_ssd(H: int, P: int, N: int, platform) -> str:
    mode = flags.flag("use_flash_attention")
    if mode == "never":
        return "primitive"
    if platform == "tpu":       # the heads of a grid step fill whole lanes
        from ..kernels.ssd import heads_per_step
        whole = (heads_per_step(H, P) * P) % 128 == 0 and N % 128 == 0
        return "pallas" if whole else "primitive"
    return "pallas-interpret" if mode == "always" else "primitive"


@register_op(
    "mamba2_scan",
    inputs=[IOSpec("X"), IOSpec("ConvW"), IOSpec("ConvB"), IOSpec("Dt"),
            IOSpec("ALog"), IOSpec("DtBias"), IOSpec("D"), IOSpec("State"),
            IOSpec("ConvState"), IOSpec("Mask", no_grad=True),
            IOSpec("Slots", optional=True, no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True)],
    outputs=["Out", "StateOut", "ConvStateOut", "Stats"],
    attrs={"mode": "scan", "num_heads": 1, "head_dim": 64, "state_dim": 128,
           "chunk": 256},
    grad=None)
def _mamba2_scan(ctx, ins, attrs):
    """``X`` [R, S, C]: the rows of ``[x | B | C]`` before the convolution,
    ``C = H P + 2 N`` (one ``B`` and ``C`` for all heads); ``ConvW``
    [C, taps], ``ConvB`` [C]: a causal depthwise convolution (``c_t = b +
    sum_j W[:, j] m_{t-taps+1+j}``, zeros before the sequence), then SiLU.
    ``Dt`` [R, S, H]: ``dt = softplus(Dt + DtBias)``, ``a = exp(-exp(ALog)
    dt)`` (``ALog``, ``DtBias``, ``D`` [H]). Per head ``S <- a S + dt x
    B^T``, ``y = S C + D x``. ``State`` [slots, H, P, N] f32 and
    ``ConvState`` [slots, taps - 1, C] f32 are the per-slot state; builders
    point ``StateOut`` / ``ConvStateOut`` back at them.

    ``mode="scan"``: ``Mask`` [R, S] (1 on a prompt's rows, which come
    first); sequence ``i`` writes slot ``Slots[i]`` (default ``i``) where
    ``SlotMask[i]`` > 0. ``mode="step"``: ``S`` = 1, ``R`` = slots, ``Mask``
    [slots, 1] the decode gate. ``Out`` [R, S, H P] f32: ``y_t`` of every
    row (of padding rows too: finite, meaningless). ``Stats`` [1] int32:
    the rows the scan advanced (the serving layer counts them)."""
    from ..kernels.ssd import (ssd_chunk_scan, ssd_decode_step,
                               ssd_scan_reference, ssd_step_reference)

    mixed, w = x(ins, "X").astype(F32), x(ins, "ConvW").astype(F32)
    dt, skip = x(ins, "Dt").astype(F32), x(ins, "D").astype(F32)
    state, tail = x(ins, "State"), x(ins, "ConvState")
    mask = x(ins, "Mask").astype(F32)
    H, P, N = (int(attrs[k]) for k in ("num_heads", "head_dim", "state_dim"))
    step = str(attrs["mode"]) == "step"
    R, S, C = mixed.shape
    taps = w.shape[1]
    if (C != H * P + 2 * N or dt.shape != (R, S, H) or (step and S != 1)
            or state.shape[1:] != (H, P, N)
            or tail.shape[1:] != (taps - 1, C)):
        raise ValueError(
            f"mamba2_scan ({attrs['mode']}): X {mixed.shape}, Dt {dt.shape}, "
            f"ConvW {w.shape}, State {state.shape}, ConvState {tail.shape} "
            f"for {H} heads of {P} over a state of {N}")
    route = _route_ssd(H, P, N, lowering_platform(ctx))
    note_kernel_route(ctx, "mamba2_scan", route)
    interpret = route == "pallas-interpret"

    with jax.named_scope("ssd_conv"):
        conv, new_tail = short_conv(mixed, w, tail, mask, step)
        conv = jax.nn.silu(conv + x(ins, "ConvB").astype(F32))
    xs, b, c = jnp.split(conv, [H * P, H * P + N], axis=-1)
    xs = xs.reshape(R, S, H, P)
    live = mask.reshape(R, S, 1)
    dt = jax.nn.softplus(dt + x(ins, "DtBias").astype(F32)) * live
    g = -jnp.exp(x(ins, "ALog").astype(F32)) * dt                # [R, S, H]
    u = xs * dt[..., None]

    if step:
        args = (state, u[:, 0], jnp.exp(g[:, 0]), b[:, 0], c[:, 0])
        if route == "primitive":
            y, state2 = ssd_step_reference(*args)
        else:
            y, state2 = ssd_decode_step(*args, interpret=interpret)
        y = y[:, None]
        tail2 = jnp.where(mask.reshape(R, 1, 1) > 0, new_tail, tail)
        advanced = jnp.sum(mask > 0)
    else:
        zero = jnp.zeros((R, H, P, N), F32)
        if route == "primitive":
            y, final = ssd_scan_reference(u, g, b, c, zero)
        else:
            y, final = ssd_chunk_scan(u, g, b, c, zero,
                                      chunk=int(attrs["chunk"]),
                                      interpret=interpret)
        state2, tail2, live = write_slots(
            state, tail, final, new_tail, x(ins, "Slots"),
            x(ins, "SlotMask"), live)
        advanced = jnp.sum(live > 0)
    out = (y + skip[:, None] * xs).reshape(R, S, H * P)
    return {"Out": [out], "StateOut": [state2.astype(state.dtype)],
            "ConvStateOut": [tail2.astype(tail.dtype)],
            "Stats": [advanced.astype(jnp.int32).reshape(1)]}
