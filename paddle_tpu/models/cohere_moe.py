"""Cohere2-MoE decoder (``model_type`` ``cohere2_moe``) for generative
serving, as the share of the model that ONE chip of an expert-parallel
deployment holds.

The layer, for a row ``x`` (one norm per layer, attention and feed-forward
side by side on it):

    h = LN(x)                      mean-subtracting, scale, no bias, f32
    y = x + Attn(h) + FFN(h)
    Attn: grouped-query heads (query head n reads key/value head
          n // group), no biases. ``sliding_attention`` layers: rotary
          positions on q and k (interleaved pairs) and a window — key j is
          visible to query i iff 0 <= i - j < window. ``full_attention``
          layers: no positional signal at all, causal mask.
    FFN:  s = sigmoid(h Wr); the top_k largest of s, weights normalised
          over them; sum_e w_e E_e(h) + mean_t S_t(h), every expert a
          gated feed-forward (silu(h Wg) * (h Wu)) Wd.
    Head: final norm, then logit_scale x the tied embedding.

What is held here: ``experts_held`` of the ``num_experts`` routed experts
from ``expert_offset`` (``layers.moe_experts`` routes over all of them and
computes its own experts' part; nothing stands in for the absent ones),
attention and the shared experts whole, and whatever slice of the
vocabulary ``vocab_size`` says. Weights, embedding and KV caches are
stored in ``dtype`` (bf16): matmuls take operands of that type and
accumulate in f32; norms, the router, softmax and the residual stream are
f32.

The block is written once (:func:`_block`) as a function of the query
length, the layer's type and a cache handle; the two phases are
``models/decoder.py``'s, which hands the block a bulk write and
full-sequence attention, or a fused append-and-attend over the cache. The
state table has two kinds of cache in it: a sliding layer keeps
``min(sliding_window, max_seq)`` rows as a ring, a full layer ``max_seq``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from .. import layers
from ..framework import default_main_program
from ..ops.moe import expert_counter
from ..param_attr import ParamAttr
from . import decoder
from .decoder import ffn, proj, proj_out, split_heads

__all__ = ["CohereMoeConfig", "build_cohere_moe_generative"]

SLIDING, FULL = "sliding_attention", "full_attention"
_P = "cmoe"                          # prefix of every parameter and state var


@dataclasses.dataclass
class CohereMoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 4096        # width of one expert
    num_experts: int = 128
    top_k: int = 8
    num_shared_experts: int = 4
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    sliding_window: int = 4096
    layer_types: Optional[Tuple[str, ...]] = None   # None: 3 sliding, 1 full
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    score_fn: str = "sigmoid"            # the router's, as ``moe_experts``

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i % 4 == 3 else SLIDING
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for "
                             f"{self.num_layers} layers")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} key/value heads")

    @staticmethod
    def tiny(**over):
        """CI-sized: both layer types, 2 of 16 experts held."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
                   num_kv_heads=2, head_dim=16, intermediate_size=32,
                   num_experts=16, top_k=4, num_shared_experts=2,
                   experts_held=2, sliding_window=16)
        cfg.update(over)
        return CohereMoeConfig(**cfg)

    def cache_rows(self, layer: int, max_seq: int) -> int:
        if self.layer_types[layer] == SLIDING:
            return min(self.sliding_window, max_seq)
        return max_seq


def _ln(x, name: str, cfg: CohereMoeConfig, axis: int = 2):
    return layers.layer_norm(x, shift=False, begin_norm_axis=axis,
                             epsilon=cfg.layer_norm_eps,
                             param_attr=ParamAttr(name=f"{name}_scale"))


def _block(x, i: int, cfg: CohereMoeConfig, positions, real, attend):
    """One layer on the residual stream ``x`` [B, S, H] (f32); S is the
    query length. ``positions`` [B, S] feeds the rotary embedding of a
    sliding layer; ``real`` [B, S] is 1 on the tokens of the sequences this
    dispatch serves and 0 on padding and on the other slots' rows. ``attend(i, q, k, v, window)`` is the phase's cache
    handle: it stores ``k``/``v`` ([B, kv_heads, S, D]) in layer ``i``'s
    cache and returns the attended context [B, heads, S, D]. Returns the
    new stream and the expert op's int32 statistics."""
    p = f"{_P}_l{i}"
    S = x.shape[1]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = _ln(x, f"{p}_ln", cfg)
    hb = layers.cast(h, cfg.dtype)

    q = split_heads(proj(hb, nh * hd, f"{p}_q", cfg), S, nh, hd)
    k = split_heads(proj(hb, nkv * hd, f"{p}_k", cfg), S, nkv, hd)
    v = split_heads(proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    sliding = cfg.layer_types[i] == SLIDING
    if sliding:
        q = layers.rotary_embedding(q, positions, theta=cfg.rope_theta)
        k = layers.rotary_embedding(k, positions, theta=cfg.rope_theta)
    ctx = attend(i, q, k, v, cfg.sliding_window if sliding else 0)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * hd])
    att = proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)

    routed, shared, stats = ffn(h, hb, p, cfg, real)
    x = layers.elementwise_add(layers.elementwise_add(x, att),
                               layers.elementwise_add(routed, shared))
    return x, stats


def _stack_layers(x, cfg: CohereMoeConfig, positions, real, attend):
    stats = []
    for i in range(cfg.num_layers):
        x, s = _block(x, i, cfg, positions, real, attend)
        stats.append(s)
    h, experts = _ln(x, f"{_P}_lnf", cfg), layers.stack(stats, axis=0)
    return h, [
        ("expert_stats", experts, expert_counter(experts))]


def _state_vars(block, cfg: CohereMoeConfig, batch_slots: int, max_seq: int):
    """Current token, position and decode gate per slot
    (``decoder.state_table``), and one K/V cache pair per layer:
    ``[slots, kv_heads, rows, head_dim]`` in ``cfg.dtype`` with ``rows`` by
    the layer's type."""
    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots)
    kinds, caches = {}, []
    for i in range(cfg.num_layers):
        shape = (batch_slots, cfg.num_kv_heads, cfg.cache_rows(i, max_seq),
                 cfg.head_dim)
        pair = tuple(mk(f"{_P}_kv_{kv}_{i}", shape, cfg.dtype)
                     for kv in "kv")
        caches.append(pair)
        kind = "window" if cfg.layer_types[i] == SLIDING else "full"
        kinds.update({c.name: kind for c in pair})
    return tok, pos, active, caches, sv, kinds


def _embed(ids, cfg: CohereMoeConfig):
    return decoder.embed(ids, cfg, f"{_P}_word_emb")


def _head(h2d, cfg: CohereMoeConfig):
    """The tied head: ``logit_scale`` x the embedding's held rows."""
    return decoder.logits(
        h2d, cfg, default_main_program().global_block.var(f"{_P}_word_emb"),
        cfg.logit_scale)


def _prefill_handle(cfg, caches, pmask, plen, smask, slots, page_size):
    return decoder.bulk_attend(caches, pmask, smask, slots,
                               1.0 / math.sqrt(cfg.head_dim), plen=plen)


def _decode_handle(cfg, caches, pos, active, page_size):
    return decoder.step_attend(caches, pos, active,
                               1.0 / math.sqrt(cfg.head_dim), page_size)


def build_cohere_moe_generative(cfg: CohereMoeConfig = None,
                                batch_slots: int = 4, max_seq: int = 64,
                                page_size: int = 8, prompt_buckets=(16,),
                                strategy: str = "greedy",
                                temperature: float = 1.0, top_k: int = 0,
                                prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). A prefill dispatch carries
    ``prefill_rows`` sequences (default: one per slot), each naming its
    slot. A bucket past a sliding layer's window is folded into its ring
    (``decoder.bulk_attend``)."""
    cfg = cfg or CohereMoeConfig.tiny()
    rows = min(cfg.cache_rows(i, max_seq) for i in range(cfg.num_layers))
    if rows % page_size:
        raise ValueError(f"caches of {rows} rows in pages of {page_size}")
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          _prefill_handle, _decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
