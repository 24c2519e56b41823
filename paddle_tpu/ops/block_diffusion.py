"""Block-diffusion generation: the state of a block and its reveal.

A block-diffusion decoder (``models/sdar_moe.py``) generates ``L =
block_length`` positions at a time. A slot's block is ``L`` token ids, the
mask id ``M`` where a position is not known yet, and beside each id the
forward of the block at which it was revealed (-1: it came with the
prompt). One decode forward scores the block's ``L`` rows against the
cache and the block itself; then, per slot (``block_reveal``):

* a block with no masked position **commits**: its tokens go out
  (``Emitted``, the prompt's remainder left off), the block moves on ``L``
  rows and starts again all masked at forward 0. The K/V rows the forward
  wrote are those of the final tokens, which is what later blocks read;
* a block with masked positions is **denoised**: at every masked position
  the greedy token ``x0`` and its log-confidence ``log softmax(logits)[x0]``
  (row ``i`` scores position ``i``'s own token, no shift; ``M``'s logit
  counts as minus infinity, so ``M`` is never revealed), and the ``n_t``
  most confident masked positions take their ``x0`` (lower position first
  among equals), with ``n_t = L // T (+ 1 for the first L % T forwards)``
  clipped to what is still masked (``T = denoising_steps``): static
  low-confidence remasking. The K/V rows this forward wrote are
  overwritten by the block's next forward.

So a forward yields 0 tokens (a denoise forward) or up to ``L`` (a commit):
``EmitCount`` says how many, per slot, and the serving layer reads its
tokens from that (``serving/generate.py`` "Yield").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import IOSpec, register_op, x
from ..core.types import jnp_dtype
from ..lowering import note_kernel_route


@register_op(
    "block_seed",
    inputs=[IOSpec("PromptIds", no_grad=True),
            IOSpec("PromptLen", no_grad=True)],
    outputs=["Tokens", "RevealedAt", "Start", "Seated"],
    attrs={"block_length": 4, "mask_id": 0},
    grad=None)
def _block_seed(ctx, ins, attrs):
    """What a prefill leaves of a prompt for the decode phase.
    ``PromptIds`` [R, S] int, ``PromptLen`` [R, 1] int = P. ``Start`` [R, 1]
    = ``(P // L) * L``: the rows of whole prompt blocks, which the prefill
    seats in the cache. The ``P % L`` tokens left over open the first block
    as known tokens: ``Tokens`` [R, L] (``mask_id`` after them),
    ``RevealedAt`` [R, L] (-1 on them, 0 elsewhere). ``Seated`` [R, S] f32
    is 1 on the rows before ``Start``."""
    ids, plen = x(ins, "PromptIds"), x(ins, "PromptLen")
    L, M = int(attrs["block_length"]), int(attrs["mask_id"])
    R, S = ids.shape
    i64 = jnp_dtype("int64")
    plen = plen.reshape(R, 1).astype(jnp.int32)
    start = plen // L * L
    at = jnp.arange(L, dtype=jnp.int32)[None, :]
    known = at < plen - start
    toks = jnp.take_along_axis(ids, jnp.minimum(start + at, S - 1), axis=1)
    seated = jnp.arange(S, dtype=jnp.int32)[None, :] < start
    return {"Tokens": [jnp.where(known, toks, M).astype(i64)],
            "RevealedAt": [jnp.where(known, -1, 0).astype(i64)],
            "Start": [start.astype(i64)],
            "Seated": [seated.astype(jnp.float32)]}


@register_op(
    "block_positions",
    inputs=[IOSpec("Start", no_grad=True)],
    outputs=["Out"],
    attrs={"block_length": 4},
    grad=None)
def _block_positions(ctx, ins, attrs):
    """``Start`` [B, 1] -> [B, L]: the rows ``start .. start + L - 1``."""
    start = x(ins, "Start")
    at = jnp.arange(int(attrs["block_length"]), dtype=start.dtype)
    return {"Out": [start.reshape(-1, 1) + at[None, :]]}


def n_transfer(step, block_length: int, denoising_steps: int):
    """Positions forward ``step`` of a block reveals: ``L // T``, and one
    more on the first ``L % T`` forwards."""
    base, rem = divmod(block_length, denoising_steps)
    return base + (step < rem).astype(step.dtype)


@register_op(
    "block_reveal",
    inputs=[IOSpec("Logits", no_grad=True), IOSpec("Tokens", no_grad=True),
            IOSpec("RevealedAt", no_grad=True), IOSpec("Start", no_grad=True),
            IOSpec("Step", no_grad=True), IOSpec("Active", no_grad=True)],
    outputs=["TokensOut", "RevealedAtOut", "StartOut", "StepOut", "Emitted",
             "EmittedAt", "EmitCount"],
    attrs={"mask_id": 0, "denoising_steps": 1, "max_seq": 0},
    grad=None)
def _block_reveal(ctx, ins, attrs):
    """One forward's end, per slot (see the module docstring). ``Logits``
    [B * L, V] or [B, L, V]; ``Tokens``, ``RevealedAt`` [B, L] int;
    ``Start``, ``Step`` [B, 1] int (the block's first row; the forward's
    index inside the block); ``Active`` [B, 1] the decode gate: a shut
    slot keeps its state and emits nothing. The ``...Out`` are the new
    state (builders point them back at the state vars). ``Emitted`` [B, L]
    holds a committed block's tokens from its first answer position on,
    ``EmittedAt`` the forward each was revealed at, ``EmitCount`` [B, 1]
    how many they are (0 on a denoise forward). ``max_seq``: a block never
    starts past ``max_seq - L`` (a slot that is done keeps turning on the
    cache's last block until the host shuts its gate)."""
    toks, at = x(ins, "Tokens"), x(ins, "RevealedAt")
    start, step = x(ins, "Start"), x(ins, "Step")
    B, L = toks.shape
    M, T = int(attrs["mask_id"]), int(attrs["denoising_steps"])
    if T < 1:
        raise ValueError(f"block_reveal: denoising_steps {T} < 1")
    note_kernel_route(ctx, "block_reveal", "primitive")
    logits = x(ins, "Logits").astype(jnp.float32).reshape(B, L, -1)
    live = x(ins, "Active").reshape(B, 1) > 0
    step1 = step.reshape(B, 1)

    masked = toks == M
    commit = live & ~jnp.any(masked, axis=1, keepdims=True)
    denoise = live & ~commit

    # the greedy token and its log-confidence at every position
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    logits = jnp.where(col == M, -jnp.inf, logits)
    x0 = jnp.argmax(logits, axis=-1).astype(toks.dtype)
    conf = jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    conf = jnp.where(masked, conf, -jnp.inf)
    # a masked position's place among the masked ones, most confident
    # first, the lower position first among equals
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (j < i))
    place = jnp.sum(ahead & masked[:, None, :], axis=2)
    reveal = denoise & masked & (place < n_transfer(step1, L, T))

    # a committed block goes out from its first answer position on
    n_prompt = jnp.sum(at < 0, axis=1, keepdims=True).astype(jnp.int32)
    order = (jnp.arange(L, dtype=jnp.int32)[None, :] + n_prompt) % L
    count = jnp.where(commit, L - n_prompt, 0)
    emitted = jnp.take_along_axis(toks, order, axis=1)
    emitted_at = jnp.take_along_axis(at, order, axis=1)

    limit = max(int(attrs["max_seq"]) - L, 0)
    new_start = jnp.where(commit, jnp.minimum(start.reshape(B, 1) + L, limit),
                          start.reshape(B, 1))
    return {
        "TokensOut": [jnp.where(commit, M, jnp.where(reveal, x0, toks))],
        "RevealedAtOut": [jnp.where(commit, 0,
                                    jnp.where(reveal, step1, at))],
        "StartOut": [new_start.astype(start.dtype).reshape(start.shape)],
        "StepOut": [jnp.where(commit, 0, jnp.where(denoise, step1 + 1,
                                                   step1)
                              ).astype(step.dtype).reshape(step.shape)],
        "Emitted": [emitted], "EmittedAt": [emitted_at],
        "EmitCount": [count.astype(toks.dtype)]}
