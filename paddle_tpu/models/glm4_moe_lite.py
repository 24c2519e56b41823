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

What is held here is what ``models/cohere_moe.py`` holds of its model:
``experts_held`` routed experts from ``expert_offset``, attention, the
dense layers and the shared expert whole, a slice of the vocabulary; bf16
storage, bf16 matmul operands with f32 accumulation; norms, router,
softmax and the residual stream f32. The feed-forward, the embedding, the
head, the state table's maker and the phase's commits are that module's,
by import. The state table holds one kind of state, ``latent``: per layer
ONE array of ``max_seq`` rows ``[c | k_rope | 0]`` of one "head", which
follow the sequence as a ``full`` layer's do. The multi-token prediction
module is not built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .. import layers
from ..framework import Program, program_guard
from ..initializer import Constant
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .cohere_moe import (PREFILL_FEEDS, _attr, _commit_decode,
                         _commit_prefill, _embed, _ffn, _gated_mlp,
                         _generative, _logits, _prefill_feeds, _proj,
                         _proj_out, _state_table)

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
    w = LayerHelper("glm4_moe_lite").create_parameter(
        ParamAttr(name=f"{name}_scale", initializer=Constant(1.0)), [dim],
        "float32")
    return layers.rms_norm(x, w, epsilon=cfg.rms_norm_eps)


def _attention(hb, p: str, S: int, cfg: Glm4MoeLiteConfig, positions,
               attend, i: int):
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc = cfg.kv_lora_rank
    cq = _norm(_proj_out(hb, cfg.q_lora_rank, f"{p}_q_a", cfg),
               f"{p}_q_a_norm", cfg, cfg.q_lora_rank)
    q = layers.reshape(
        _proj(layers.cast(cq, cfg.dtype), nh * (dn + dr), f"{p}_q_b", cfg),
        [0, S, nh, dn + dr])
    q_nope, q_rope = layers.split(layers.transpose(q, [0, 2, 1, 3]),
                                  [dn, dr], dim=3)
    rot = lambda t: layers.rotary_embedding(t, positions,
                                            theta=cfg.rope_theta)
    q = layers.concat([q_nope, rot(q_rope)], axis=3)      # [B, nh, S, dn+dr]
    c_raw, k_r = layers.split(_proj_out(hb, dc + dr, f"{p}_kv_a", cfg),
                              [dc, dr], dim=2)
    c = layers.cast(_norm(c_raw, f"{p}_kv_a_norm", cfg, dc), cfg.dtype)
    k_rope = layers.squeeze(
        rot(layers.unsqueeze(layers.cast(k_r, cfg.dtype), [1])), [1])
    w_kvb = LayerHelper("glm4_moe_lite").create_parameter(
        _attr(f"{p}_kv_b_w", cfg), [dc, nh * (dn + cfg.v_head_dim)],
        cfg.dtype)
    ctx, stats = attend(i, q, c, k_rope, w_kvb)          # [B, nh, S, dv]
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * cfg.v_head_dim])
    return _proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg), stats


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
        return (layers.elementwise_add(x, _gated_mlp(
            hb, cfg.dense_intermediate_size, f"{p}_mlp", cfg)), None, walked)
    routed, shared, stats = _ffn(h, hb, p, cfg, real, join="sum")
    x = layers.elementwise_add(x, layers.elementwise_add(routed, shared))
    return x, stats, walked


def _stack_layers(x, cfg: Glm4MoeLiteConfig, positions, real, attend):
    experts, walks = [], []
    for i in range(cfg.num_layers):
        x, s, w = _block(x, i, cfg, positions, real, attend)
        walks.append(w)
        if s is not None:
            experts.append(s)
    return (_norm(x, f"{_P}_lnf", cfg, cfg.hidden_size),
            layers.stack(experts, axis=0) if experts else None,
            layers.stack(walks, axis=0))


def _head(h2d, cfg: Glm4MoeLiteConfig):
    w = LayerHelper("glm4_moe_lite").create_parameter(
        _attr(f"{_P}_lm_head", cfg), [cfg.vocab_size, cfg.hidden_size],
        cfg.dtype)
    return _logits(h2d, cfg, w)


def _state_vars(block, cfg: Glm4MoeLiteConfig, batch_slots: int,
                max_seq: int):
    """Current token, position and decode gate per slot, and each layer's
    latent cache ``[slots, 1, max_seq, W]`` in ``cfg.dtype``, kind
    ``latent``: a row is ``[c (kv_lora_rank) | k_rope (qk_rope_head_dim) |
    0]``, ``W`` whole lane tiles (``kernels.latent_row_width``)."""
    from ..kernels.latent_attention import latent_row_width

    mk, sv, tok, pos, active = _state_table(block, _P, batch_slots)
    width = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    caches = [mk(f"{_P}_lat_{i}", (batch_slots, 1, max_seq, width),
                 cfg.dtype) for i in range(cfg.num_layers)]
    return tok, pos, active, caches, sv, {c.name: "latent" for c in caches}


def _outputs(cfg, experts, walks):
    """The statistics a phase fetches, and which layers they are of."""
    moe = list(range(cfg.first_k_dense, cfg.num_layers))
    return {"expert_stats": experts, "expert_layers": moe,
            "latent_stats": walks}


def _build_prefill(cfg, B, R, S, max_seq, page_size, sample, startup):
    """The full-sequence phase for one prompt bucket: ``R`` sequences a
    dispatch, each naming its slot (``cohere_moe._prefill_feeds``); a
    layer writes the bucket's latent rows at row 0 of the slot's cache and
    attends over keys and values expanded from them."""
    main = Program()
    with program_guard(main, startup):
        ids, pos_ids, pmask, plen, smask, slots = _prefill_feeds(R, S)
        tok, pos, active, caches, sv, _ = _state_vars(
            main.global_block, cfg, B, max_seq)

        def attend(i, q, c, k_rope, w_kvb):
            return layers.latent_attention(
                q, c, k_rope, w_kvb, caches[i], plen,
                cfg.qk_nope_head_dim, mode="prefill", page_size=page_size,
                slot_mask=smask, slots=slots)

        real = layers.elementwise_mul(pmask, smask, axis=0)
        h, experts, walks = _stack_layers(
            _embed(ids, cfg, f"{_P}_word_emb"), cfg, pos_ids, real, attend)
        one = layers.fill_constant([R, 1], "int64", 1)
        last_h = layers.sequence_gather(h, layers.elementwise_sub(plen, one))
        logits = _head(last_h, cfg)
        first_tok = layers.sample_token(logits, **sample)
        _commit_prefill(tok, pos, active, slots, first_tok, plen, smask)
    return {"main": main, "first_token": first_tok, "state_vars": sv,
            "last_logits": logits, "rows": R, "feeds": PREFILL_FEEDS,
            **_outputs(cfg, experts, walks)}


def _build_decode(cfg, B, max_seq, page_size, sample):
    """The per-token phase: no feeds, everything is persistable state."""
    main = Program()
    with program_guard(main, Program()):
        tok, pos, active, caches, sv, kinds = _state_vars(
            main.global_block, cfg, B, max_seq)

        def attend(i, q, c, k_rope, w_kvb):
            return layers.latent_attention(
                q, c, k_rope, w_kvb, caches[i], pos, cfg.qk_nope_head_dim,
                page_size=page_size, slot_mask=active)

        x = layers.unsqueeze(_embed(tok, cfg, f"{_P}_word_emb"), [1])
        h, experts, walks = _stack_layers(x, cfg, pos, active, attend)
        logits = _head(layers.reshape(h, [0, cfg.hidden_size]), cfg)
        next_tok = layers.sample_token(logits, **sample)
        _commit_decode(tok, pos, active, next_tok, max_seq)
    return {"main": main, "next_token": next_tok, "state_vars": sv,
            "logits": logits, "cache_kinds": kinds,
            "cache_vars": [(c.name,) for c in caches],
            "active_var": active.name, **_outputs(cfg, experts, walks)}


def build_glm4_moe_lite_generative(cfg: Glm4MoeLiteConfig = None,
                                   batch_slots: int = 4, max_seq: int = 64,
                                   page_size: int = 8, prompt_buckets=(16,),
                                   strategy: str = "greedy",
                                   temperature: float = 1.0, top_k: int = 0,
                                   prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs, as
    ``build_cohere_moe_generative`` returns it. ``prefill_rows``: the
    sequences a prefill dispatch carries, each naming its slot (default:
    one per slot). No chunk or verify program: a prompt has to fit a
    bucket, and a bucket the cache."""
    cfg = cfg or Glm4MoeLiteConfig.tiny()
    prompt_buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
    if not prompt_buckets or prompt_buckets[-1] > max_seq:
        raise ValueError(f"prompt buckets {prompt_buckets} for a cache of "
                         f"{max_seq} rows")
    if max_seq % page_size:
        raise ValueError(f"max_seq {max_seq} must be a whole number of "
                         f"pages of page_size {page_size}")
    rows = int(prefill_rows or batch_slots)
    if not 1 <= rows <= batch_slots:
        raise ValueError(f"prefill_rows {rows} for {batch_slots} slots")
    sample = dict(strategy=strategy, temperature=temperature, top_k=top_k)
    startup = Program()
    prefill = {S: _build_prefill(cfg, batch_slots, rows, S, max_seq,
                                 page_size, sample, startup)
               for S in prompt_buckets}
    decode = _build_decode(cfg, batch_slots, max_seq, page_size, sample)
    return _generative(cfg, startup, prefill, decode, batch_slots, max_seq,
                       page_size, strategy)
