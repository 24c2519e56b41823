"""SDAR-MoE decoder (``model_type`` ``sdar_moe``) for generative serving:
a Qwen3-MoE layer that generates by diffusion over blocks.

Every layer, on the residual stream ``x`` (f32), with ``N(x) = x /
sqrt(mean(x^2) + eps) * w`` and no bias anywhere:

    a = x + Attn(N_in(x));   y = a + MoE(N_post(a))

    Attn: grouped-query heads (query head n reads key/value head
          n // group); q and k each through an RMS norm over a head's dims
          with its own weight; rotary positions on all of a head's dims
          (rotate-half pairs); scores / sqrt(head_dim), softmax in f32
          under the mask below.
    MoE:  softmax over all experts, the ``top_k`` largest, weights
          normalised over them; the held experts' part of the routed sum.
          No shared expert. Every layer has experts.
    Head: final norm, then the untied ``lm_head``.
    Mask: with block length L, key j is visible to query i iff
          j // L <= i // L: whole earlier blocks, and the query's own
          block in both directions.

Generation (``ops/block_diffusion.py`` has the reveal rule): a prompt of P
tokens is prefilled as its ``P // L`` whole blocks in one forward under
the mask above, whose K/V go to the cache; no token is sampled. The
``P % L`` tokens left over open the slot's first block as known tokens.
A decode forward then carries, for every slot, its block of L token ids at
rows ``start .. start + L - 1`` (the mask id where a position is not known
yet): it appends the rows' K/V, attends them to the cache and to one
another (``fused_decode_attention(..., whole_chunk=True)``), and ends in
``block_reveal``. A block with masked positions reveals its most confident
ones and is run again (its K/V rows are overwritten by that next forward,
which reads the block's keys fresh); a block with none is committed: the
rows its forward wrote are the K/V of its final tokens, its tokens go out,
and the slot moves on L rows. So a forward yields 0 to L tokens a slot,
and ``denoising_steps + 1`` forwards make a block.

What is held here is ONE chip's share (``experts_held`` routed experts from
``expert_offset``; bf16 storage, bf16 matmul operands with f32 accumulation;
norms, router, softmax, confidence and the residual stream f32). The block
is written once (:func:`_block`) over an ``attend`` handle. The two phases
are this module's own over the parts of ``models/decoder.py``: the prefill
samples nothing and seeds the slot's block state, the decode forward carries
a block and yields.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

from .. import layers
from ..framework import Program, program_guard
from ..ops.moe import expert_counter
from . import decoder
from .decoder import ffn, proj, proj_out, split_heads

__all__ = ["SdarMoeConfig", "build_sdar_moe_generative"]

_P = "sdar"                          # prefix of every parameter and state var


@dataclasses.dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    intermediate_size: int = 768         # width of one expert
    num_experts: int = 128
    top_k: int = 8
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    denoising_steps: int = 2
    mask_token_id: int = 0
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    score_fn: str = "softmax"            # the router's, as ``moe_experts``
    num_shared_experts: int = 0          # what ``decoder.ffn`` reads

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} key/value heads")
        if self.block_length < 1 or self.denoising_steps < 1:
            raise ValueError(f"block_length {self.block_length}, "
                             f"denoising_steps {self.denoising_steps}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask id {self.mask_token_id} outside a "
                             f"vocabulary of {self.vocab_size}")

    @staticmethod
    def tiny(**over):
        """CI-sized: every expert held, blocks of 4 in 2 steps."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                   num_kv_heads=2, head_dim=16, intermediate_size=32,
                   num_experts=8, top_k=2)
        cfg.update(over)
        return SdarMoeConfig(**cfg)


def _block(x, i: int, cfg: SdarMoeConfig, positions, real, attend):
    """One layer on the residual stream ``x`` [B, S, H] (f32). ``positions``
    [B, S] feeds the rotary embedding; ``real`` [B, S] is 1 on the rows of
    the sequences this dispatch serves. ``attend(i, q, k, v)`` stores
    ``k``/``v`` ([B, kv_heads, S, D]) in layer ``i``'s cache and returns
    the attended context [B, heads, S, D]. Returns the new stream and the
    expert op's statistics."""
    p = f"{_P}_l{i}"
    S, H = x.shape[1], cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = lambda t, name, dim: decoder.norm(t, f"{p}_{name}", cfg, dim,
                                             zero_centered=False)
    hb = layers.cast(norm(x, "ln_in", H), cfg.dtype)
    # q and k keep the f32 accumulator on their way into a norm
    q = layers.reshape(proj_out(hb, nh * hd, f"{p}_q", cfg), [0, S, nh, hd])
    k = layers.reshape(proj_out(hb, nkv * hd, f"{p}_k", cfg),
                       [0, S, nkv, hd])
    v = split_heads(proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    rot = lambda t: layers.cast(layers.rotary_embedding(
        layers.transpose(t, [0, 2, 1, 3]), positions, theta=cfg.rope_theta,
        pairing="half"), cfg.dtype)
    ctx = attend(i, rot(norm(q, "qnorm", hd)), rot(norm(k, "knorm", hd)), v)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * hd])
    x = layers.elementwise_add(x, proj_out(ctx, H, f"{p}_out", cfg))
    h = norm(x, "ln_post", H)
    routed, _, stats = ffn(h, layers.cast(h, cfg.dtype), p, cfg, real)
    return layers.elementwise_add(x, routed), stats


def _stack_layers(x, cfg: SdarMoeConfig, positions, real, attend):
    stats = []
    for i in range(cfg.num_layers):
        x, s = _block(x, i, cfg, positions, real, attend)
        stats.append(s)
    h = decoder.norm(x, f"{_P}_lnf", cfg, cfg.hidden_size,
                     zero_centered=False)
    experts = layers.stack(stats, axis=0)
    return h, [("expert_stats", experts, expert_counter(experts))]


def _state_vars(block, cfg: SdarMoeConfig, batch_slots: int, max_seq: int):
    """Per slot: the block's ``block_length`` token ids (the mask id where
    a position is not known yet), the forward each was revealed at (-1: a
    prompt token), the block's first row, the forward's index inside the
    block and the decode gate; and one K/V cache pair per layer,
    ``[slots, kv_heads, max_seq, head_dim]`` in ``cfg.dtype``."""
    L = cfg.block_length
    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots,
                                                   tokens=L)
    at = mk(f"{_P}_gen_revealed_at", (batch_slots, L), "int64")
    step = mk(f"{_P}_gen_step", (batch_slots, 1), "int64")
    shape = (batch_slots, cfg.num_kv_heads, max_seq, cfg.head_dim)
    caches = [tuple(mk(f"{_P}_kv_{kv}_{i}", shape, cfg.dtype) for kv in "kv")
              for i in range(cfg.num_layers)]
    return tok, at, pos, step, active, caches, sv


def _build_prefill(cfg, B, R, S, max_seq, page_size, startup):
    """The full-sequence phase for one prompt bucket: ``R`` sequences a
    dispatch, each naming its slot (``decoder.prefill_feeds``), under
    the block-causal mask. The bucket's K/V go to the slot's cache at row
    0, of which the rows of whole prompt blocks are kept (the decode phase
    writes every later row before it reads it); the slot's block state is
    seeded with the prompt's remainder. No token is sampled."""
    main = Program()
    L = cfg.block_length
    with program_guard(main, startup):
        ids, pos_ids, pmask, plen, smask, slots = decoder.prefill_feeds(R, S)
        tok, at, pos, step, active, caches, sv = _state_vars(
            main.global_block, cfg, B, max_seq)
        bias = layers.unsqueeze(
            layers.scale(pmask, scale=10000.0, bias=-10000.0), [1, 2])
        zero_pos = layers.fill_constant([R, 1], "int64", 0)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v):
            for cache, new in zip(caches[i], (k, v)):
                layers.kv_cache_append(cache, new, zero_pos, slot_mask=smask,
                                       slots=slots)
            return layers.fused_multihead_attention(
                q, k, v, bias_qk=bias, causal=True, causal_block=L,
                scale=scale, is_test=True)

        first, first_at, start, seated = layers.block_seed(
            ids, plen, L, cfg.mask_token_id)
        real = layers.elementwise_mul(seated, smask, axis=0)
        _, stats = _stack_layers(
            decoder.embed(ids, cfg, f"{_P}_word_emb"), cfg, pos_ids, real,
            attend)
        for var, new in ((tok, first), (at, first_at), (pos, start),
                         (step, zero_pos),
                         (active, layers.fill_constant([R, 1], "float32",
                                                       1.0))):
            layers.slot_assign(var, slots, new, smask)
    return decoder.counted({"main": main, "state_vars": sv, "rows": R,
                            "feeds": decoder.PREFILL_FEEDS}, stats)


def _build_decode(cfg, B, max_seq, page_size, startup):
    """The per-forward phase: no feeds, everything is persistable state.
    One forward of every slot's block, then the reveal (module
    docstring). The head is this phase's alone, so its initialiser goes
    to the shared ``startup`` from here."""
    main = Program()
    L = cfg.block_length
    with program_guard(main, startup):
        tok, at, pos, step, active, caches, sv = _state_vars(
            main.global_block, cfg, B, max_seq)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v):
            ck, cv = caches[i]
            return layers.fused_decode_attention(
                q, k, v, ck, cv, pos, scale=scale, page_size=page_size,
                slot_mask=active, whole_chunk=True)

        rows = layers.block_positions(pos, L)
        real = layers.expand(active, [1, L])
        h, stats = _stack_layers(
            decoder.embed(tok, cfg, f"{_P}_word_emb"), cfg, rows, real,
            attend)
        logits = decoder.untied_head(
            layers.reshape(h, [-1, cfg.hidden_size]), cfg, f"{_P}_lm_head")
        emitted, emitted_at, count = layers.block_reveal(
            logits, tok, at, pos, step, active, cfg.mask_token_id,
            cfg.denoising_steps, max_seq)
    return decoder.counted(decoder.decode_net(
        main, caches, sv, {c.name: "full" for pair in caches for c in pair},
        active, logits=logits,
        **{"yield": {"tokens": emitted, "count": count,
                     "revealed_at": emitted_at}}), stats)


def build_sdar_moe_generative(cfg: SdarMoeConfig = None,
                              batch_slots: int = 4, max_seq: int = 64,
                              page_size: int = 8, prompt_buckets=(16,),
                              prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``), with ``block_length`` and
    the decode net's ``yield`` (how its tokens come out: ``tokens`` [slots,
    L], ``count`` [slots, 1] and ``revealed_at`` [slots, L] a forward)
    beside: the engine reads a dispatch's tokens from those, and a prefill
    streams none. Greedy only. ``prefill_rows``: the sequences a prefill
    dispatch carries, each naming its slot (default: one per slot)."""
    cfg = cfg or SdarMoeConfig.tiny()
    L = cfg.block_length
    if max_seq % page_size or max_seq % L or any(int(b) % L
                                                 for b in prompt_buckets):
        raise ValueError(
            f"max_seq {max_seq} must be whole pages of {page_size} and, as "
            f"every prompt bucket of {tuple(prompt_buckets)}, whole blocks "
            f"of {L}")
    net = decoder.build_generative(
        cfg, functools.partial(_build_prefill, cfg),
        functools.partial(_build_decode, cfg), batch_slots, max_seq,
        page_size, prompt_buckets, prefill_rows)
    net["block_length"] = L
    return net
