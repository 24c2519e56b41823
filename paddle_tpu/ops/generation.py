"""Generative-inference ops: fused decode attention over a paged KV cache,
bulk KV writes, last-position gathers and in-program token sampling.

These are the decode-step building blocks of ``models/gpt.py`` and the
serving layer's prefill/decode split (``serving.generate``). A decode step
is one forward of every slot; what it yields is the model's to say: one
token a slot for an autoregressive decoder (``sample_token``), or, for a
decoder that generates by diffusion over blocks, 0 to ``block_length``
tokens (``fused_decode_attention`` with ``whole_chunk`` over the block's
rows, then ``ops/block_diffusion.py``'s ``block_reveal``). Two design rules
shape them:

* **The KV append is fused into the decode attention op** (CODA, PAPERS.md
  arXiv 2605.19269: fold decode-step epilogue work into the fused kernels):
  ``fused_decode_attention`` reads AND writes the cache vars at one op
  index, so ``analysis.liveness.safe_donation_set`` proves the cache
  buffers donatable — the executor updates the multi-megabyte cache in
  place instead of copying it every token, including through
  ``run_chained``'s scan carry. A separate append-then-attend op pair
  would read the cache after its write and the liveness proof would
  (correctly) refuse the donation.
* **Sampling runs in-program** (``sample_token``; a block's reveal and
  commit likewise, ``block_reveal``): what a forward chose is a program
  state write, so a whole decode chunk runs as ONE ``run_chained``
  dispatch with no host round-trip per forward; seeded through the op-uid
  PRNG discipline, CI runs are deterministic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import IOSpec, register_op, x
from .. import flags
from ..core.types import jnp_dtype
from ..lowering import lowering_platform, note_kernel_route


def _route_decode(s_max: int, page_size: int, q_len: int = 1,
                  platform=None) -> str:
    """'pallas' | 'pallas-interpret' | 'primitive' for a decode/chunk
    shape lowered for ``platform`` (``lowering.lowering_platform``).
    ``q_len`` > 1 is the chunked-prefill / speculative-verify chunk; the
    kernel rides one 8-row sublane tile, so chunks past 8 rows take the
    primitive path (never an error — the chunk size is a scheduling
    knob, not a hardware contract; ``kernel_route_total`` says which
    programs do)."""
    from ..kernels import KERNEL_ROWS, classify_shapes

    mode = flags.flag("use_flash_attention")
    if mode == "never":
        return "primitive"
    kind, reason = classify_shapes(1, s_max, block_k=page_size)
    if kind != "decode":
        if mode == "always":
            raise ValueError(
                f"FLAGS_use_flash_attention=always but the decode shape "
                f"has no kernel tiling: {reason}")
        return "primitive"
    if q_len > KERNEL_ROWS:
        return "primitive"
    if platform == "tpu":
        return "pallas"
    return "pallas-interpret" if mode == "always" else "primitive"


@register_op(
    "fused_decode_attention",
    inputs=[IOSpec("Q"), IOSpec("KNew"), IOSpec("VNew"),
            IOSpec("CacheK"), IOSpec("CacheV"),
            IOSpec("Positions", no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True),
            IOSpec("Sink", optional=True, no_grad=True)],
    outputs=["Out", "CacheKOut", "CacheVOut"],
    attrs={"scale": 0.0, "page_size": 128, "window": 0,
           "whole_chunk": False},
    grad=None)
def _fused_decode_attention(ctx, ins, attrs):
    """One autoregressive decode/verify chunk, epilogue fused:

    1. append this chunk's K/V rows (``KNew``/``VNew`` [B, H, C, D],
       C = q_len; C == 1 is the classic decode step) into the paged
       caches ([B, H, S_max, D]) at per-sequence ``Positions`` ([B, 1]
       int — the sequence length BEFORE this chunk), with per-row
       clamping onto the last cache row: one scatter a cache for all
       sequences where the cache lies as declared
       (``kernels.paged_kv_append_rows``), the decode kernel itself or
       ``kernels.kv_append`` where it lies rows-minor, and
       ``kernel_route_total`` says which (``...append_scatter``,
       ``...append_in_kernel``, ``kv_append``);
    2. attend the C query rows against the updated cache with a
       per-sequence, per-row causal length mask (query row i sees keys
       at positions < pos + i + 1 — its own K row and everything before,
       never a later chunk row). With ``whole_chunk`` the chunk is a block
       whose rows see one another in both directions (a block-diffusion
       decode forward: C = block_length rows a sequence, which yield 0 to C
       tokens): every row sees the keys at positions < pos + C.

    ``SlotMask`` [B, 1] (optional) gates the ROWS that step 1 writes: a
    sequence whose mask is 0 writes nothing (rows-minor: its own old rows
    back), so its caches stay bit-untouched —
    the chunked-prefill and speculative-verify dispatches run a subset of
    slots while their neighbours keep decoding, and the decode chunk runs
    under the ``active`` gate. The mask never selects between an old and
    a new CACHE: after the append this rule holds no reference to the old
    one, so the donated buffer is updated in place through the scan carry
    and a masked append costs what an unmasked one does.
    ``CacheKOut``/``CacheVOut`` are the updated caches — program builders
    point them back at the cache vars, making this the one op that reads
    and writes them (the donation-proof shape, see module docstring).
    Retired sequences whose position saturates past S_max - 1 clamp onto
    the last row and their output is garbage by design — the serving
    layer discards it (the last row is never inside a live length mask).

    Grouped-query heads: ``Q`` may carry a whole multiple of the caches'
    heads; query head ``n`` reads key/value head ``n // group``, and the
    group's heads ride one kernel call beside each other, so a cache page
    is read once for all of them.

    ``window`` > 0 is a sliding-window layer: a query sees the last
    ``window`` positions, itself included. Its caches hold
    ``S_max = min(window, max_seq)`` rows as a ring — position ``p`` lives
    in row ``p % S_max`` — so once positions pass the window a new row
    overwrites the one that just left it, and every row the ring holds is
    visible: the length mask is ``min(pos + 1, S_max)``, as it is without a
    window. Keys carry their positions in themselves (rotary) or not at
    all, so the order of the ring's rows does not matter to the softmax.
    Only single-row steps wrap (a chunk's causal order is its row order).
    A prefill whose prompt is longer than the ring leaves it so
    (``kv_cache_fold``), and the steps go on from ``pos % S_max``.

    ``VNew``/``CacheV`` may be ``Dv`` wide where the keys are ``D`` (``Out``
    is then [B, Hq, C, Dv]); both caches are then read and appended to as
    declared. ``Sink`` [Hq] float32 (optional): a query head's scalar
    joins its softmax as one more column that carries no value.
    """
    from ..kernels import (decode_attention_reference, flash_attention_decode,
                           kv_append, paged_kv_append_rows, rows_minor)

    q, kn, vn = x(ins, "Q"), x(ins, "KNew"), x(ins, "VNew")
    ck, cv = x(ins, "CacheK"), x(ins, "CacheV")
    pos = x(ins, "Positions")
    smask = x(ins, "SlotMask")
    sink = x(ins, "Sink")
    B, Hq, q_len, D = q.shape
    Dv = cv.shape[3]
    if q_len < 1:
        raise ValueError(
            f"fused_decode_attention: q_len must be >= 1, got {q_len}")
    H, S = ck.shape[1], ck.shape[2]
    G = Hq // H
    window = int(attrs.get("window") or 0)
    whole = bool(attrs.get("whole_chunk"))
    if Hq % H or kn.shape[1] != H:
        raise ValueError(
            f"fused_decode_attention: {Hq} query heads over caches of {H} "
            f"heads and new rows of {kn.shape[1]}")
    if window and (S > window or q_len > 1):
        raise NotImplementedError(
            f"fused_decode_attention: a window of {window} over caches of "
            f"{S} rows in steps of {q_len}: a windowed layer's cache is a "
            f"ring of at most `window` rows, written one row a step")
    page = int(attrs.get("page_size") or 128)
    scale = attrs["scale"] or float(D) ** -0.5
    pos_b = pos.reshape(B).astype(jnp.int32)
    route = _route_decode(S, page, q_len=q_len,
                          platform=lowering_platform(ctx))
    note_kernel_route(ctx, "fused_decode_attention", route)
    if whole:
        note_kernel_route(ctx, "fused_decode_attention.whole_chunk", route)
    # the append works in the view the kernel reads (kernels.rows_minor:
    # [B, H, D, S_max] where the runtime stores the cache so), or a layout
    # conversion of every cache lands between the two, inside the scan;
    # the swaps themselves are bitcasts. There a row is a column, whose
    # writer fetches and rewrites the block around it: for a step of one
    # row that block is the decode kernel's last live one, and the kernel
    # writes the row itself; a chunk of rows (it may cross a block's edge)
    # and a ring (its new row is not its last) go through `kv_append` first
    minor = (route != "primitive" and Dv == D
             and rows_minor(D, ck.dtype, min(page, S)))
    in_kernel = (minor and q_len == 1 and not whole and not window
                 and sink is None)
    if in_kernel:
        note_kernel_route(ctx, "fused_decode_attention.append_in_kernel",
                          route)
    elif minor:
        note_kernel_route(ctx, "kv_append", route)
    else:
        note_kernel_route(ctx, "fused_decode_attention.append_scatter",
                          route)
    interpret = route == "pallas-interpret"

    def append(cache, new):
        if not minor:
            return paged_kv_append_rows(cache, new, pos_b, smask,
                                        ring=bool(window))
        return kv_append(cache.swapaxes(2, 3), new, pos_b, smask,
                         ring=bool(window), interpret=interpret
                         ).swapaxes(2, 3)

    ck2, cv2 = (ck, cv) if in_kernel else (append(ck, kn), append(cv, vn))
    lengths = jnp.minimum(pos_b + 1, S)

    # the G query heads of one key/value head beside each other, position-
    # major: row i of a group is head i % G at chunk position i // G
    q3 = q.reshape(B * H, G, q_len, D).swapaxes(1, 2).reshape(
        B * H, q_len * G, D)
    k3 = ck2.reshape(B * H, S, D)
    v3 = cv2.reshape(B * H, S, Dv)
    if route == "primitive":
        o = decode_attention_reference(
            q3, k3, v3, jnp.repeat(lengths, H, axis=0), scale, group=G,
            whole_chunk=whole,
            sink=None if sink is None else jnp.tile(sink.reshape(H, G),
                                                    (B, 1)))
    else:
        o = flash_attention_decode(
            q3, k3, v3, lengths, scale=scale, num_heads=H,
            page_size=page, group=G, interpret=interpret, whole_chunk=whole,
            append=(kn, vn, smask) if in_kernel else None, sink=sink)
        if in_kernel:
            o, ck2, cv2 = o[0], o[1].reshape(ck.shape), o[2].reshape(cv.shape)
    o = o.reshape(B * H, q_len, G, Dv).swapaxes(1, 2)
    return {"Out": [o.reshape(B, Hq, q_len, Dv)],
            "CacheKOut": [ck2], "CacheVOut": [cv2]}


@register_op(
    "kv_cache_append",
    inputs=[IOSpec("Cache"), IOSpec("New"),
            IOSpec("Positions", no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True),
            IOSpec("Slots", optional=True, no_grad=True)],
    outputs=["Out"],
    attrs={},
    grad=None)
def _kv_cache_append(ctx, ins, attrs):
    """Bulk KV write: place ``New`` [B, H, L, D] rows into ``Cache``
    [B, H, S_max, D] starting at per-sequence ``Positions`` [B, 1] (the
    prefill path writes a whole prompt, L = prompt bucket, at position 0).
    ``SlotMask`` [B, 1] (optional) gates the rows that are written: a
    sequence whose mask is 0 writes its own L old rows back, so its cache
    stays bit-untouched at the cost of L rows, not of the cache — the
    continuous-batching refill writes only the slots being prefilled
    while their neighbours keep decoding. Builders point ``Out``
    back at the cache var: the op reads and writes it at one index, so the
    buffer donates (liveness-proven in-place update). ``Slots`` [B', 1]
    (optional): ``New`` carries B' <= B sequences and sequence ``i`` is
    written into the cache's row ``Slots[i]`` (``Positions`` and
    ``SlotMask`` are then per sequence of ``New``)."""
    from ..kernels import paged_kv_append

    cache, new, pos = x(ins, "Cache"), x(ins, "New"), x(ins, "Positions")
    return {"Out": [paged_kv_append(cache, new, pos, x(ins, "SlotMask"),
                                    x(ins, "Slots"))]}


@register_op(
    "kv_cache_fold",
    inputs=[IOSpec("Cache"), IOSpec("New"),
            IOSpec("Lengths", no_grad=True),
            IOSpec("SlotMask", optional=True, no_grad=True),
            IOSpec("Slots", optional=True, no_grad=True)],
    outputs=["Out", "Stats"],
    attrs={},
    grad=None)
def _kv_cache_fold(ctx, ins, attrs):
    """A prefill past a window layer's ring: ``New`` [R, H, S, D], the keys
    or values of ``R`` whole prompts of ``Lengths`` [R, 1] tokens in a
    bucket of ``S`` rows, into ``Cache`` [B, H, W, D], rings of the last
    ``W < S`` positions. Row ``r`` of sequence ``i``'s ring, in the slot
    ``Slots[i]`` (default ``i``), takes the last position ``p <
    Lengths[i]`` with ``p % W == r`` (``kernels.fold_rows``), which is
    where ``fused_decode_attention`` with a ``window`` writes position
    ``p``: the decode step goes on from ``Lengths[i] % W`` with no special
    case. A sequence shorter than the ring lies in it from row 0, as
    ``kv_cache_append`` leaves it. One gather and one write a cache; a
    sequence whose ``SlotMask`` is 0 writes nothing, and every slot no
    sequence in use names stays bit-untouched. ``Out`` goes back to the
    cache var. ``Stats`` [2] int32: the prompt rows the sequences in use
    kept in their rings, and those they dropped (the window had passed
    them). Plain XLA on every device (``kernels.window_fold``: its
    operations carry the scope ``window_fold`` in a device trace)."""
    from ..kernels import window_fold

    cache, new = x(ins, "Cache"), x(ins, "New")
    lengths, smask = x(ins, "Lengths"), x(ins, "SlotMask")
    R, W, S = new.shape[0], cache.shape[2], new.shape[2]
    if S <= W:
        raise ValueError(f"kv_cache_fold: a bucket of {S} rows fits a ring "
                         f"of {W}; kv_cache_append writes it at row 0")
    out = window_fold(cache, new, lengths, smask, x(ins, "Slots"))
    n = lengths.reshape(R).astype(jnp.int32)
    if smask is not None:
        n = jnp.where(smask.reshape(R) > 0, n, 0)
    kept = jnp.minimum(n, W)
    return {"Out": [out],
            "Stats": [jnp.stack([kept.sum(), (n - kept).sum()])]}


@register_op(
    "slot_assign",
    inputs=[IOSpec("X"), IOSpec("Slots", no_grad=True), IOSpec("Updates"),
            IOSpec("Mask", optional=True, no_grad=True)],
    outputs=["Out"],
    attrs={},
    grad=None)
def _slot_assign(ctx, ins, attrs):
    """Per-slot state written by the rows that serve a slot: ``Out`` is
    ``X`` [B, ...] with row ``Slots[i]`` replaced by ``Updates[i]`` for
    every ``i`` whose ``Mask[i]`` > 0 (``Slots``, ``Mask``: [B', 1]; a
    masked row writes nothing). Builders point ``Out`` back at ``X``'s var,
    as with ``kv_cache_append``."""
    xv, slots, upd = x(ins, "X"), x(ins, "Slots"), x(ins, "Updates")
    mask = x(ins, "Mask")
    idx = slots.reshape(-1).astype(jnp.int32)
    if mask is not None:        # a masked row goes out of range and is dropped
        idx = jnp.where(mask.reshape(-1) > 0, idx, xv.shape[0])
    return {"Out": [xv.at[idx].set(upd.astype(xv.dtype), mode="drop")]}


@register_op(
    "spec_accept",
    inputs=[IOSpec("Sampled", no_grad=True),
            IOSpec("Drafts", no_grad=True),
            IOSpec("Start", no_grad=True)],
    outputs=["AcceptLen", "NewTok", "NewPos"],
    attrs={},
    grad=None)
def _spec_accept(ctx, ins, attrs):
    """Speculative-decoding accept rule, in-program (no host round-trip
    between verify and state commit). ``Sampled`` [B, k] int64 holds the
    target model's token at every chunk position: ``Sampled[:, i]`` is
    the token the target emits AFTER seeing the chunk's first ``i + 1``
    tokens. ``Drafts`` [B, k-1] int64 are the draft's proposals (the
    chunk tokens 1..k-1). ``Start`` [B, 1] int is the sequence length
    before the chunk.

    The longest agreeing prefix ``m = |{j : Drafts[:, :j] ==
    Sampled[:, :j]}|`` accepts ``m`` draft tokens plus the target's own
    bonus token ``Sampled[:, m]`` (the in-program fallback: at m == 0
    the dispatch still emits one token, exactly the non-speculative
    step). Outputs: ``AcceptLen`` [B, 1] = m, ``NewTok`` [B, 1] =
    ``Sampled[:, m]``, ``NewPos`` [B, 1] = ``Start + m + 1`` (the new
    sequence length: the chunk's first token plus m accepted drafts are
    now committed cache rows; rejected rows sit past the length mask and
    are overwritten by the next dispatch)."""
    s, d = x(ins, "Sampled"), x(ins, "Drafts")
    start = x(ins, "Start")
    B, k = s.shape
    if d.shape != (B, k - 1):
        raise ValueError(
            f"spec_accept: Drafts must be [B, k-1] = [{B}, {k - 1}] for "
            f"Sampled [B, k] = {tuple(s.shape)}, got {tuple(d.shape)}")
    i64 = jnp_dtype("int64")
    if k == 1:
        m = jnp.zeros((B,), jnp.int32)
    else:
        agree = (s[:, :k - 1] == d).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
    new_tok = jnp.take_along_axis(s, m[:, None].astype(jnp.int32), axis=1)
    new_pos = start.reshape(B, 1).astype(i64) + m[:, None] + 1
    return {"AcceptLen": [m[:, None].astype(i64)],
            "NewTok": [new_tok.astype(i64)],
            "NewPos": [new_pos.astype(i64)]}


@register_op(
    "sequence_gather",
    inputs=[IOSpec("X"), IOSpec("Index", no_grad=True)],
    outputs=["Out"])
def _sequence_gather(ctx, ins, attrs):
    """Per-sequence gather along axis 1: X [B, S, ...], Index [B, 1] ->
    Out [B, ...] = X[b, Index[b]]. The prefill path uses it to pull the
    last real prompt position's hidden state out of a padded batch
    (indices clamp into [0, S-1])."""
    xv, idx = x(ins, "X"), x(ins, "Index")
    B = xv.shape[0]
    i = jnp.clip(idx.reshape(B).astype(jnp.int32), 0, xv.shape[1] - 1)
    i = i.reshape((B, 1) + (1,) * (xv.ndim - 2))
    taken = jnp.take_along_axis(xv, jnp.broadcast_to(
        i, (B, 1) + xv.shape[2:]), axis=1)
    return {"Out": [taken[:, 0]]}


@register_op(
    "sample_token",
    inputs=[IOSpec("Logits", no_grad=True)],
    outputs=["Out"],
    attrs={"strategy": "greedy", "temperature": 1.0, "top_k": 0},
    needs_rng=True,
    grad=None)
def _sample_token(ctx, ins, attrs):
    """Next-token selection from ``Logits`` [B, V] -> ``Out`` [B, 1] int64.

    ``strategy='greedy'`` is pure argmax (deterministic, the CI default);
    ``'sample'`` draws from softmax(logits / temperature), optionally
    truncated to the ``top_k`` highest-probability tokens. The PRNG key is
    the executor's op-uid-folded key, so a fixed ``program.random_seed``
    reproduces the same token sequence run over run."""
    logits = x(ins, "Logits").astype(jnp.float32)
    strategy = str(attrs.get("strategy", "greedy"))
    if strategy == "greedy":
        tok = jnp.argmax(logits, axis=-1)
    elif strategy == "sample":
        temp = max(float(attrs.get("temperature", 1.0)), 1e-6)
        scaled = logits / temp
        k = int(attrs.get("top_k", 0))
        if k > 0:
            k = min(k, scaled.shape[-1])
            thresh = jax.lax.top_k(scaled, k)[0][:, -1:]
            scaled = jnp.where(scaled >= thresh, scaled, -1e30)
        tok = jax.random.categorical(ctx.rng(), scaled, axis=-1)
    else:
        raise ValueError(
            f"sample_token: unknown strategy '{strategy}' "
            f"(expected 'greedy' or 'sample')")
    return {"Out": [tok.astype(jnp_dtype("int64"))[:, None]]}
