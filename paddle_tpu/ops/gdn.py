"""Recurrent-layer ops: the gated delta rule of a Gated DeltaNet mixer
(with the short causal convolution in front of it), and RMS norm.

``gated_delta_rule`` is one rule in two forms. Both read the layer's
projections before the convolution and keep two pieces of state per slot:
the rule's ``[Hv, Dk, Dv]`` f32 state and the convolution's tail (the
last ``taps - 1`` rows that went into it).

* ``mode="scan"`` — ``R`` whole prompts of up to ``S`` rows. Each starts
  from a zero state and OVERWRITES the state of the slot it names. Rows
  past a prompt's length (``Mask`` 0; the real rows are a prefix) stand
  still: decay 1, ``beta`` 0, and the tail is taken at the prompt's last
  real rows, not at the bucket's.
* ``mode="step"`` — one token for every slot, read from and written back
  into the state under the decode gate (``Mask`` [slots, 1]): a slot whose
  gate is 0 keeps both pieces bit for bit.

Everything here is f32: the rule feeds itself, so a rounding of its
operands is carried through every later row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import IOSpec, count_by_layer, register_op, x
from .. import flags
from ..lowering import lowering_platform, note_kernel_route

F32 = jnp.float32


def count_rule_stats(phase: str, stats, sums, layers, family: str) -> None:
    """What a serving dispatch's recurrent layers counted (the ``Stats`` of
    ``gated_delta_rule`` or, from ``ops/ssd.py``, ``mamba2_scan``: [...,
    layers, 1]), onto the monitor: the real rows each layer's rule
    advanced, an execution at a time, under the counters' ``family``
    (``gdn``, ``ssm``). ``layers`` names the recurrent layers."""
    from .. import monitor

    count_by_layer(
        phase, stats, layers,
        monitor.counter(
            f"{family}_tokens_total",
            f"rows of real tokens the recurrent layers' rule ({family}) "
            f"advanced, by layer and phase of the dispatch"),
        monitor.counter(
            f"{family}_calls_total",
            f"executions of the recurrent layers' op ({family})"))


def _route_gdn(Dk: int, Dv: int, platform) -> str:
    mode = flags.flag("use_flash_attention")
    if mode == "never":
        return "primitive"
    if platform == "tpu":       # a head fills whole 128-lane registers
        return "primitive" if Dk % 128 or Dv % 128 else "pallas"
    return "pallas-interpret" if mode == "always" else "primitive"


def _unit(t):
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def short_conv(mixed, w, tail, mask, step: bool):
    """The causal depthwise convolution in front of a recurrent rule, both
    forms: ``mixed`` [R, S, C] against taps ``w`` [C, taps]. A step reads
    the stored ``tail`` [R, taps - 1, C] before its row; a scan starts from
    zeros. Returns the sums (no bias, no activation) and the tail to store:
    the window's last rows for a step, for a scan the last ``taps - 1`` real
    rows by ``mask`` [R, S] (zeros before the sequence)."""
    R, S, C = mixed.shape
    taps = w.shape[1]
    before = tail.astype(F32) if step else jnp.zeros((R, taps - 1, C), F32)
    window = jnp.concatenate([before, mixed], axis=1)
    conv = sum(window[:, j:j + S] * w[:, j] for j in range(taps))
    if step:
        return conv, window[:, 1:]
    n = jnp.sum(mask, axis=1).astype(jnp.int32)                      # [R]
    at = n[:, None] + jnp.arange(taps - 1)             # into ``window``
    return conv, jnp.take_along_axis(window, at[:, :, None], axis=1)


def write_slots(state, tail, final, new_tail, slots, smask, live):
    """A scan's end: sequence ``i`` OVERWRITES the state and tail of slot
    ``slots[i]`` (default ``i``) where ``smask[i]`` > 0. Returns both and
    ``live`` with the masked sequences' rows taken out."""
    R = final.shape[0]
    idx = (jnp.arange(R) if slots is None
           else slots.reshape(R)).astype(jnp.int32)
    if smask is not None:       # a masked sequence goes out of range: dropped
        idx = jnp.where(smask.reshape(R) > 0, idx, state.shape[0])
        live = live * (smask.reshape((R,) + (1,) * (live.ndim - 1)) > 0)
    return (state.at[idx].set(final.astype(state.dtype), mode="drop"),
            tail.at[idx].set(new_tail.astype(tail.dtype), mode="drop"), live)


@register_op(
    "gated_delta_rule",
    inputs=[IOSpec("X"), IOSpec("ConvW"), IOSpec("A"), IOSpec("B"),
            IOSpec("ALog"), IOSpec("DtBias"), IOSpec("State"),
            IOSpec("ConvState"), IOSpec("Mask", no_grad=True),
            IOSpec("Slots", optional=True, no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True)],
    outputs=["Out", "StateOut", "ConvStateOut", "Stats"],
    attrs={"mode": "scan", "num_k_heads": 1, "num_v_heads": 1,
           "head_k_dim": 128, "head_v_dim": 128},
    grad=None)
def _gated_delta_rule(ctx, ins, attrs):
    """``X`` [R, S, C]: the rows of ``concat(q, k, v)`` before the
    convolution, ``C = 2 Hk Dk + Hv Dv``; ``ConvW`` [C, taps]: a causal
    depthwise convolution (``c_t = sum_j W[:, j] m_{t-taps+1+j}``, zeros
    before the sequence), then SiLU. ``A``/``B`` [R, S, Hv]:
    ``beta = sigmoid(B)``, ``g = -exp(ALog) softplus(A + DtBias)``
    (``ALog``, ``DtBias`` [Hv]). q and k are scaled to unit length over
    their ``Dk`` dims and q by ``Dk^-1/2``; value head ``n`` reads key head
    ``n // (Hv / Hk)``. ``State`` [slots, Hv, Dk, Dv] f32 and ``ConvState``
    [slots, taps - 1, C] f32 are the per-slot state; builders point
    ``StateOut`` / ``ConvStateOut`` back at them.

    ``mode="scan"``: ``Mask`` [R, S] (1 on a prompt's rows, which come
    first); sequence ``i`` writes slot ``Slots[i]`` (default ``i``) where
    ``SlotMask[i]`` > 0. ``mode="step"``: ``S`` = 1, ``R`` = slots, ``Mask``
    [slots, 1] the decode gate. ``Out`` [R, S, Hv Dv] f32: ``o_t`` of every
    row (of padding rows too: finite, meaningless). ``Stats`` [1] int32:
    the rows the rule advanced (the serving layer counts them)."""
    from ..kernels.gdn import (gdn_chunk_scan, gdn_decode_step,
                               gdn_scan_reference, gdn_step_reference)

    mixed, w = x(ins, "X").astype(F32), x(ins, "ConvW").astype(F32)
    a, b = x(ins, "A").astype(F32), x(ins, "B").astype(F32)
    state, tail = x(ins, "State"), x(ins, "ConvState")
    mask = x(ins, "Mask").astype(F32)
    Hk, Hv = int(attrs["num_k_heads"]), int(attrs["num_v_heads"])
    Dk, Dv = int(attrs["head_k_dim"]), int(attrs["head_v_dim"])
    step = str(attrs["mode"]) == "step"
    R, S, C = mixed.shape
    taps = w.shape[1]
    if (C != 2 * Hk * Dk + Hv * Dv or Hv % Hk or (step and S != 1)
            or state.shape[1:] != (Hv, Dk, Dv)
            or tail.shape[1:] != (taps - 1, C)):
        raise ValueError(
            f"gated_delta_rule ({attrs['mode']}): X {mixed.shape}, ConvW "
            f"{w.shape}, State {state.shape}, ConvState {tail.shape} for "
            f"{Hk} x {Dk} key heads and {Hv} x {Dv} value heads")
    route = _route_gdn(Dk, Dv, lowering_platform(ctx))
    note_kernel_route(ctx, "gated_delta_rule", route)
    interpret = route == "pallas-interpret"

    with jax.named_scope("gdn_conv"):
        conv, new_tail = short_conv(mixed, w, tail, mask, step)
        conv = jax.nn.silu(conv)
    q, k, v = jnp.split(conv, [Hk * Dk, 2 * Hk * Dk], axis=-1)
    heads = lambda t, n, d: t.reshape(R, S, n, d).transpose(0, 2, 1, 3)
    q = _unit(heads(q, Hk, Dk)) * Dk ** -0.5
    k = _unit(heads(k, Hk, Dk))
    v = heads(v, Hv, Dv)
    live = mask.reshape(R, S)[:, None, :]                        # [R, 1, S]
    g = -jnp.exp(x(ins, "ALog").astype(F32)) * jax.nn.softplus(
        a + x(ins, "DtBias").astype(F32))
    g = g.transpose(0, 2, 1) * live                              # [R, Hv, S]
    beta = jax.nn.sigmoid(b).transpose(0, 2, 1) * live

    if step:
        rep = Hv // Hk
        args = (state, jnp.repeat(q[:, :, 0], rep, axis=1),
                jnp.repeat(k[:, :, 0], rep, axis=1), v[:, :, 0],
                jnp.exp(g[:, :, 0]), beta[:, :, 0])
        if route == "primitive":
            o, state2 = gdn_step_reference(*args)
        else:
            o, state2 = gdn_decode_step(*args, interpret=interpret)
        o = o[:, :, None]
        tail2 = jnp.where(mask.reshape(R, 1, 1) > 0, new_tail, tail)
        advanced = jnp.sum(mask > 0)
    else:
        if route == "primitive":
            o, final = gdn_scan_reference(q, k, v, g, beta)
        else:
            o, final = gdn_chunk_scan(q, k, v, g, beta, interpret=interpret)
        state2, tail2, live = write_slots(
            state, tail, final, new_tail, x(ins, "Slots"),
            x(ins, "SlotMask"), live)
        advanced = jnp.sum(live > 0)
    out = o.transpose(0, 2, 1, 3).reshape(R, S, Hv * Dv)
    return {"Out": [out], "StateOut": [state2.astype(state.dtype)],
            "ConvStateOut": [tail2.astype(tail.dtype)],
            "Stats": [advanced.astype(jnp.int32).reshape(1)]}


@register_op(
    "rms_norm",
    inputs=[IOSpec("X"), IOSpec("Scale")],
    outputs=["Out"],
    attrs={"epsilon": 1e-6, "zero_centered": False},
    grad=None)
def _rms_norm(ctx, ins, attrs):
    """``X / sqrt(mean(X^2) + epsilon) * s`` over the last dim, in f32;
    ``s`` is ``Scale`` [D], or ``1 + Scale`` where ``zero_centered`` (a
    scale stored around zero)."""
    xv, s = x(ins, "X").astype(F32), x(ins, "Scale").astype(F32)
    if attrs.get("zero_centered"):
        s = 1.0 + s
    y = xv * jax.lax.rsqrt(jnp.mean(xv * xv, axis=-1, keepdims=True)
                           + float(attrs["epsilon"]))
    return {"Out": [y * s]}
