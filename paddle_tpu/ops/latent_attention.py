"""Multi-head latent attention: one set of weights, two routes through it,
one latent cache.

A latent-attention layer stores, a token, the row ``[c | k_rope]``: ``c``
the normed compression of the token (``kv_lora_rank`` wide), ``k_rope`` one
rotary key shared by all heads. Keys and values are linear in ``c`` through
``KVBW`` [dc, heads x (dn + dv)], read a head as ``W_UK`` [dc, dn] beside
``W_UV`` [dc, dv]: ``k_i = [c W_UK_i | k_rope]``, ``v_i = c W_UV_i``.

* ``mode="prefill"`` — the published form: keys and values of the whole
  bucket are expanded from ``c`` and the flash kernel runs over heads of
  ``dn + dr`` (causal; a prompt's real rows come first, so none of them
  sees a padding row).
* ``mode="decode"`` — the absorbed form, the same numbers: ``W_UK`` goes
  into the query (``qt_i = q_nope_i W_UK_i^T``) and ``W_UV`` onto the
  output, and attention runs over the latent rows themselves
  (``kernels.mla_decode_attention``). Nothing is expanded and no second
  copy of ``KVBW`` is kept.

Both append the step's rows to the layer's latent cache first: ``Cache``
[slots, 1, S_max, W], a row ``[c | k_rope | 0]`` padded to whole lane tiles
(``kernels/latent_attention.py`` says why one array). Builders point
``CacheOut`` back at it: the op reads and writes the cache at one index, so
the buffer donates (``ops/generation.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import IOSpec, count_by_layer, register_op, x
from .fused_attention import _route as _route_prefill
from .generation import _route_decode
from ..lowering import lowering_platform, note_kernel_route


def count_latent_stats(phase: str, stats, sums) -> None:
    """What a serving dispatch's latent-attention layers counted
    (``latent_attention`` ``Stats``, [..., layers, 1]), onto the monitor:
    the cache rows each layer's attention read, an execution at a time."""
    from .. import monitor

    count_by_layer(
        phase, stats, (),
        monitor.counter(
            "latent_attention_rows_total",
            "latent-cache rows the attention read, by layer and phase "
            "of the dispatch: in decode whole blocks up to each "
            "sequence's last live one, in prefill the bucket's rows"),
        monitor.counter(
            "latent_attention_calls_total",
            "executions of the latent attention op"))


@register_op(
    "latent_attention",
    inputs=[IOSpec("Q"), IOSpec("C"), IOSpec("KRope"), IOSpec("KVBW"),
            IOSpec("Cache"), IOSpec("Positions", no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True),
            IOSpec("Slots", optional=True, no_grad=True)],
    outputs=["Out", "CacheOut", "Stats"],
    attrs={"mode": "decode", "nope_dim": 0, "page_size": 128, "scale": 0.0},
    grad=None)
def _latent_attention(ctx, ins, attrs):
    """``Q`` [B, heads, S, dn + dr]: a head's ``[q_nope | q_rope]``, the
    rotary part turned; ``C`` [B, S, dc] and ``KRope`` [B, S, dr] (turned):
    the rows this call appends; ``nope_dim`` = dn. The softmax scale is
    the attribute ``scale``, or ``(dn + dr)^-1/2`` where it is 0 (a model
    whose positions are YaRN's multiplies that by ``m^2``,
    ``ops.moe.yarn_softmax_scale``). ``Out`` [B, heads, S, dv] in ``Q``'s
    type.

    ``mode="prefill"``: ``B`` whole prompts of up to ``S`` rows; sequence
    ``i`` writes its rows at row 0 of slot ``Slots[i]`` (default ``i``)
    where ``SlotMask[i]`` > 0; ``Positions`` is not read. ``mode="decode"``:
    ``S`` = 1, ``B`` = slots, ``Positions`` [B, 1] the length before the
    step, ``SlotMask`` [B, 1] the decode gate: a slot whose gate is 0 keeps
    its rows bit for bit, and its query sees no key (its ``Out`` row is 0).
    The step's rows go in by ONE scatter, a slot's row at its own position
    (a shut slot's index out of range, where ``mode="drop"`` discards it; a
    position at or past the cache's end onto its last row, which no length
    mask holds): the compiler updates the donated cache in place, where a
    row-by-row loop paid each slot's read, select and write as three small
    operations.

    ``Stats`` [1] int32, for the serving layer's counters: in decode the
    cache rows the kernel's walk fetches (whole blocks up to each
    sequence's last live one), in prefill the rows the flash kernel reads
    keys of (``B x S``)."""
    from ..kernels import (flash_attention, flash_forward_grid,
                           paged_kv_append)
    from ..kernels.decode_attention import last_live_block
    from ..kernels.latent_attention import (
        latent_block_rows, mla_decode_attention,
        mla_decode_attention_reference)

    q, c, kr, w = x(ins, "Q"), x(ins, "C"), x(ins, "KRope"), x(ins, "KVBW")
    cache, smask = x(ins, "Cache"), x(ins, "SlotMask")
    B, nh, S, dq = q.shape
    dc, dr, dn = c.shape[-1], kr.shape[-1], int(attrs["nope_dim"])
    dv = w.shape[1] // nh - dn
    S_max, W = cache.shape[2:]
    decode = str(attrs["mode"]) == "decode"
    if (dq != dn + dr or dv <= 0 or w.shape != (dc, nh * (dn + dv))
            or cache.shape[1] != 1 or W < dc + dr or (decode and S != 1)):
        raise ValueError(
            f"latent_attention ({attrs['mode']}): Q {q.shape} (nope_dim "
            f"{dn}), C {c.shape}, KRope {kr.shape}, KVBW {w.shape}, cache "
            f"{cache.shape}")
    page = int(attrs.get("page_size") or 128)
    scale = float(attrs.get("scale") or 0.0) or float(dq) ** -0.5
    platform = lowering_platform(ctx)
    prec = "highest" if w.dtype == jnp.float32 else "default"
    wh = w.reshape(dc, nh, dn + dv)
    # a row as the cache holds it: [c | k_rope | 0]
    lanes = lambda parts: jnp.pad(
        jnp.concatenate(parts, axis=-1),
        [(0, 0)] * (parts[0].ndim - 1) + [(0, W - dc - dr)])
    rows = lanes([c.astype(cache.dtype), kr.astype(cache.dtype)])

    if not decode:
        route = _route_prefill(S, S, 0.0, platform)
        note_kernel_route(ctx, "latent_attention", route)
        cache2 = paged_kv_append(cache, rows[:, None],
                                 jnp.zeros((B,), jnp.int32), smask,
                                 x(ins, "Slots"))
        with jax.named_scope("latent_expand"):
            kv = jnp.einsum("bsc,chd->bhsd", c.astype(w.dtype), wh,
                            precision=prec,
                            preferred_element_type=jnp.float32
                            ).astype(q.dtype)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    kr[:, None].astype(q.dtype), (B, nh, S, dr))], axis=-1)
            v = kv[..., dn:]
        flat = lambda t: t.reshape(B * nh, S, t.shape[-1])
        if route == "primitive":
            from .fused_attention import _primitive_attention
            o = _primitive_attention(ctx, flat(q), flat(k), flat(v), None,
                                     True, scale, 0.0, True)
        else:
            note_kernel_route(
                ctx, "latent_attention.grid", flash_forward_grid(
                    S, S, dq, dv, q.dtype.itemsize, causal=True))
            o = flash_attention(flat(q), flat(k), flat(v), causal=True,
                                scale=scale, num_heads=nh,
                                interpret=(route == "pallas-interpret"))
        return {"Out": [o.reshape(B, nh, S, dv).astype(q.dtype)],
                "CacheOut": [cache2],
                "Stats": [jnp.full((1,), B * S, jnp.int32)]}

    route = _route_decode(S_max, page, platform=platform)
    note_kernel_route(ctx, "latent_attention", route)
    pos = x(ins, "Positions").reshape(B).astype(jnp.int32)
    live = (jnp.ones((B,), bool) if smask is None
            else smask.reshape(B) > 0)
    at = jnp.where(live, jnp.minimum(pos, S_max - 1), S_max)
    cache2 = cache.at[jnp.arange(B), 0, at].set(rows[:, 0], mode="drop")
    lengths = jnp.where(live, jnp.minimum(pos + 1, S_max), 0)
    with jax.named_scope("latent_absorb"):
        q_lat = jnp.einsum("bhn,chn->bhc", q[:, :, 0, :dn], wh[..., :dn],
                           precision=prec,
                           preferred_element_type=jnp.float32
                           ).astype(q.dtype)
    args = (lanes([q_lat, q[:, :, 0, dn:]]), cache2[:, 0], lengths)
    if route == "primitive":
        u = mla_decode_attention_reference(*args, dc, scale)
    else:
        u = mla_decode_attention(*args, latent_dim=dc, scale=scale,
                                 page_size=page,
                                 interpret=(route == "pallas-interpret"))
    with jax.named_scope("latent_absorb"):
        o = jnp.einsum("bhc,chv->bhv", u.astype(w.dtype), wh[..., dn:],
                       precision=prec, preferred_element_type=jnp.float32)
    bk = latent_block_rows(S_max, W, cache.dtype, page)
    walked = jnp.sum(last_live_block(lengths, 1, bk, S_max // bk) + 1) * bk
    return {"Out": [o[:, :, None].astype(q.dtype)], "CacheOut": [cache2],
            "Stats": [walked.astype(jnp.int32).reshape(1)]}
