"""GLM-4.7-Flash decoder (``model_type`` ``glm4_moe_lite``) for generative
serving, as the share of the model that ONE chip of an expert-parallel
deployment holds.

Every layer, on the residual stream ``x`` (f32), with
``N(x) = x / sqrt(mean(x^2) + eps) * w``:

    h = x + Attn(N_in(x));   y = h + FFN_i(N_post(h))

    Attn: multi-head latent attention. The query goes through a low-rank
          bottleneck with its own norm (``q = N(h W_qa) W_qb``), a head's
          ``[q_nope | q_rope]``; ``[c_raw | k_r] = h W_kva``, ``c =
          N(c_raw)``; rotary positions on ``q_rope`` and on ``k_r``
          (interleaved pairs, the whole rotary part), one rotary key for
          all heads. A token's cache row is ``[c | k_rope]``. Keys and
          values are linear in ``c`` through ``W_kvb``; the prefill
          expands them and runs the flash kernel, the decode step absorbs
          ``W_kvb`` into the query and the output and attends over the
          latent rows (``layers.latent_attention``: two routes through one
          set of weights). ``o_proj`` over the joined heads.
    FFN:  the first ``first_k_dense`` layers a dense gated feed-forward of
          ``dense_intermediate_size``; the others ``s = sigmoid(h2 Wr)``,
          the ``top_k`` experts with the largest ``s + b`` (a stored
          bias that enters the choice and not the weights), weights
          ``route_scale * s / sum s`` over the chosen, the held experts'
          part of the routed sum plus one shared expert, added.
    Head: final norm, then the untied ``lm_head``.

What is held here is ONE chip's share: ``experts_held`` routed experts
from ``expert_offset``, attention, the dense layers and the shared expert
whole, a slice of the vocabulary; bf16 storage, bf16 matmul operands with
f32 accumulation; norms, router, softmax and the residual stream f32. The
block is written once (:func:`_block`); the two phases are
``models/decoder.py``'s. The state table holds one kind of state,
``latent``: per layer ONE array of ``max_seq`` rows ``[c | k_rope | 0]`` of
one "head", which follow the sequence as a ``full`` layer's do. The
multi-token prediction module is not built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .. import layers
from ..layer_helper import LayerHelper
from ..ops.latent_attention import count_latent_stats
from ..ops.moe import expert_counter
from . import decoder
from .decoder import attr, ffn, gated_mlp, proj, proj_out

__all__ = ["Glm4MoeLiteConfig", "build_glm4_moe_lite_generative"]

_P = "glm"                           # prefix of every parameter and state var


@dataclasses.dataclass
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1000000.0
    intermediate_size: int = 1536        # width of one routed expert
    dense_intermediate_size: int = 10240
    first_k_dense: int = 1
    num_experts: int = 64
    top_k: int = 4
    num_shared_experts: int = 1
    route_scale: float = 1.8
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    score_fn: str = "sigmoid"
    select_bias: bool = True             # ``noaux_tc``

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(f"{self.first_k_dense} dense layers of "
                             f"{self.num_layers}")

    @staticmethod
    def tiny(**over):
        """CI-sized: one dense layer and two with experts, 4 of 16 held;
        a head's key as wide as its value, as published."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=3, num_heads=4,
                   q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
                   qk_rope_head_dim=8, v_head_dim=32, intermediate_size=32,
                   dense_intermediate_size=96, num_experts=16, top_k=4,
                   experts_held=4)
        cfg.update(over)
        return Glm4MoeLiteConfig(**cfg)


def _norm(x, name: str, cfg: Glm4MoeLiteConfig, dim: int):
    return decoder.norm(x, name, cfg, dim, zero_centered=False)


def _attention(hb, p: str, S: int, cfg: Glm4MoeLiteConfig, positions,
               attend, i: int):
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc = cfg.kv_lora_rank
    cq = _norm(proj_out(hb, cfg.q_lora_rank, f"{p}_q_a", cfg),
               f"{p}_q_a_norm", cfg, cfg.q_lora_rank)
    q = layers.reshape(
        proj(layers.cast(cq, cfg.dtype), nh * (dn + dr), f"{p}_q_b", cfg),
        [0, S, nh, dn + dr])
    q_nope, q_rope = layers.split(layers.transpose(q, [0, 2, 1, 3]),
                                  [dn, dr], dim=3)
    rot = lambda t: layers.rotary_embedding(t, positions,
                                            theta=cfg.rope_theta)
    q = layers.concat([q_nope, rot(q_rope)], axis=3)      # [B, nh, S, dn+dr]
    c_raw, k_r = layers.split(proj_out(hb, dc + dr, f"{p}_kv_a", cfg),
                              [dc, dr], dim=2)
    c = layers.cast(_norm(c_raw, f"{p}_kv_a_norm", cfg, dc), cfg.dtype)
    k_rope = layers.squeeze(
        rot(layers.unsqueeze(layers.cast(k_r, cfg.dtype), [1])), [1])
    w_kvb = LayerHelper("glm4_moe_lite").create_parameter(
        attr(f"{p}_kv_b_w", cfg), [dc, nh * (dn + cfg.v_head_dim)],
        cfg.dtype)
    ctx, stats = attend(i, q, c, k_rope, w_kvb)          # [B, nh, S, dv]
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * cfg.v_head_dim])
    return proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg), stats


def _block(x, i: int, cfg: Glm4MoeLiteConfig, positions, real, attend):
    """One layer on the residual stream ``x`` [B, S, H] (f32). ``real``
    [B, S] is 1 on the tokens of the sequences this dispatch serves.
    ``attend(i, q, c, k_rope, w_kvb)`` appends the rows to layer ``i``'s
    latent cache and returns the attended context and the op's statistics.
    Returns the new stream, the expert op's statistics (None on a dense
    layer) and the attention's."""
    p = f"{_P}_l{i}"
    S, H = x.shape[1], cfg.hidden_size
    hb = layers.cast(_norm(x, f"{p}_ln_in", cfg, H), cfg.dtype)
    att, walked = _attention(hb, p, S, cfg, positions, attend, i)
    x = layers.elementwise_add(x, att)
    h = _norm(x, f"{p}_ln_post", cfg, H)
    hb = layers.cast(h, cfg.dtype)
    if i < cfg.first_k_dense:
        return (layers.elementwise_add(x, gated_mlp(
            hb, cfg.dense_intermediate_size, f"{p}_mlp", cfg)), None, walked)
    routed, shared, stats = ffn(h, hb, p, cfg, real, join="sum")
    x = layers.elementwise_add(x, layers.elementwise_add(routed, shared))
    return x, stats, walked


def _stack_layers(x, cfg: Glm4MoeLiteConfig, positions, real, attend):
    experts, walks, moe = [], [], []
    for i in range(cfg.num_layers):
        x, s, w = _block(x, i, cfg, positions, real, attend)
        walks.append(w)
        if s is not None:
            experts.append(s)
            moe.append(i)
    h, stats = _norm(x, f"{_P}_lnf", cfg, cfg.hidden_size), []
    if experts:
        experts = layers.stack(experts, axis=0)
        stats.append(("expert_stats", experts,
                      expert_counter(experts, moe)))
    stats.append(("latent_stats", layers.stack(walks, axis=0),
                  count_latent_stats))
    return h, stats


def _embed(ids, cfg: Glm4MoeLiteConfig):
    return decoder.embed(ids, cfg, f"{_P}_word_emb")


def _head(h2d, cfg: Glm4MoeLiteConfig):
    return decoder.untied_head(h2d, cfg, f"{_P}_lm_head")


def _state_vars(block, cfg: Glm4MoeLiteConfig, batch_slots: int,
                max_seq: int):
    """Current token, position and decode gate per slot, and each layer's
    latent cache ``[slots, 1, max_seq, W]`` in ``cfg.dtype``, kind
    ``latent``: a row is ``[c (kv_lora_rank) | k_rope (qk_rope_head_dim) |
    0]``, ``W`` whole lane tiles (``kernels.latent_row_width``)."""
    from ..kernels.latent_attention import latent_row_width

    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots)
    width = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    caches = [(mk(f"{_P}_lat_{i}", (batch_slots, 1, max_seq, width),
                  cfg.dtype),) for i in range(cfg.num_layers)]
    return (tok, pos, active, caches, sv,
            {c.name: "latent" for c, in caches})


def _prefill_handle(cfg, caches, pmask, plen, smask, slots, page_size):
    """A layer writes the bucket's latent rows at row 0 of the slot's cache
    and attends over keys and values expanded from them."""
    def attend(i, q, c, k_rope, w_kvb):
        return layers.latent_attention(
            q, c, k_rope, w_kvb, *caches[i], plen, cfg.qk_nope_head_dim,
            mode="prefill", page_size=page_size, slot_mask=smask,
            slots=slots)
    return attend


def _decode_handle(cfg, caches, pos, active, page_size):
    def attend(i, q, c, k_rope, w_kvb):
        return layers.latent_attention(
            q, c, k_rope, w_kvb, *caches[i], pos, cfg.qk_nope_head_dim,
            page_size=page_size, slot_mask=active)
    return attend


def build_glm4_moe_lite_generative(cfg: Glm4MoeLiteConfig = None,
                                   batch_slots: int = 4, max_seq: int = 64,
                                   page_size: int = 8, prompt_buckets=(16,),
                                   strategy: str = "greedy",
                                   temperature: float = 1.0, top_k: int = 0,
                                   prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). ``prefill_rows``: the sequences a
    prefill dispatch carries, each naming its slot (default: one per
    slot)."""
    cfg = cfg or Glm4MoeLiteConfig.tiny()
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          _prefill_handle, _decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
