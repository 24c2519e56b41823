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
block is written once (:func:`_block`); the two phases, the attention
(``decoder.latent_attention``, shared with ``models/xing4.py``), its state
and its two cache handles are ``models/decoder.py``'s. The state table
holds one kind of state, ``latent``: per layer ONE array of ``max_seq``
rows ``[c | k_rope | 0]`` of one "head", which follow the sequence as a
``full`` layer's do. The multi-token prediction module is not built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .. import layers
from ..ops.latent_attention import count_latent_stats
from ..ops.moe import expert_counter
from . import decoder
from .decoder import ffn, gated_mlp

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
    att, walked = decoder.latent_attention(hb, p, S, cfg, positions, attend,
                                           i)
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
    return decoder.latent_state(block, cfg, _P, batch_slots, max_seq)


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
                          decoder.latent_prefill_handle,
                          decoder.latent_decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
