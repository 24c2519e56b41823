"""Qwen3-Next decoder (``model_type`` ``qwen3_next``) for generative
serving, as the share of the model that ONE chip of an expert-parallel
deployment holds.

Every layer, on the residual stream ``x`` (f32), with
``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``:

    h = x + Mixer_i(N_in(x));   y = h + MoE(N_post(h))

Layer ``i`` is *full attention* when ``(i + 1) % full_attention_interval
== 0`` and *linear attention* otherwise (three in four).

    Gated attention: ``q_proj`` gives, per head, a query and a gate side
          by side; q and k pass an RMS norm over the head's dims, then
          rotary positions on the first ``partial_rotary_factor`` of them
          (rotate-half pairs); grouped-query causal attention;
          ``o_proj(attn * sigmoid(gate))``. State: a K/V cache.
    Gated DeltaNet: ``in_proj_qkvz`` (columns ``[q | k | v | z]``) and
          ``in_proj_ba`` (``[b | a]``); a causal depthwise convolution over
          ``concat(q, k, v)``, SiLU, and the gated delta rule
          (``layers.gated_delta_rule``); per head
          ``w * rmsnorm(o) * silu(z)``, heads joined, ``out_proj``. State:
          the rule's ``[value heads, key dim, value dim]`` f32 matrix and
          the convolution's last ``taps - 1`` input rows. Fixed size: it
          does not grow with the sequence.
    MoE:  softmax over all experts, the ``top_k`` largest, weights
          normalised over them; the held experts' part of the routed sum
          plus ``sigmoid(h . w_s)`` times one shared expert.
    Head: final norm, then the untied ``lm_head``.

What is held here is what ``models/cohere_moe.py`` holds of its model:
``experts_held`` routed experts from ``expert_offset``, the mixers and the
shared expert whole, a slice of the vocabulary; bf16 storage, bf16 matmul
operands with f32 accumulation; norms, router, gates, the rule and the
residual stream f32. The feed-forward, the embedding, the head, the state
table's maker and the phase's commits are that module's, by import.

The block is written once (:func:`_block`) for both phases; a phase hands
it a ``mix`` handle with ``attend`` (as in ``cohere_moe``) and ``recur``
(the rule over a whole prompt, which overwrites the named slots' state, or
one step under the decode gate). The state table holds two kinds of state:
``full`` (a K/V pair of ``max_seq`` rows) and ``recurrent``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from .. import layers
from ..framework import Program, program_guard
from ..initializer import Constant, Uniform
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .cohere_moe import (PREFILL_FEEDS, _attr, _commit_decode,
                         _commit_prefill, _embed, _ffn, _generative, _logits,
                         _prefill_feeds, _proj, _proj_out, _split_heads,
                         _state_table)

__all__ = ["Qwen3NextConfig", "build_qwen3_next_generative"]

FULL, LINEAR = "full_attention", "linear_attention"
_P = "qn"                            # prefix of every parameter and state var


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    intermediate_size: int = 512         # width of one routed expert
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    top_k: int = 10
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    score_fn: str = "softmax"
    num_shared_experts: int = 1          # one, behind a learned gate

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.num_heads % self.num_kv_heads or \
                self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads, {self.linear_num_value_heads} value "
                f"heads over {self.linear_num_key_heads} key heads")
        if self.shared_expert_intermediate_size != self.intermediate_size:
            raise ValueError("the shared expert is built at the routed "
                             "experts' width")

    @staticmethod
    def tiny(**over):
        """CI-sized: one period (three linear layers, one full), 4 of 16
        experts held."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
                   num_kv_heads=2, head_dim=16, linear_num_key_heads=2,
                   linear_num_value_heads=4, linear_key_head_dim=16,
                   linear_value_head_dim=16, intermediate_size=32,
                   shared_expert_intermediate_size=32, num_experts=16,
                   top_k=4, experts_held=4)
        cfg.update(over)
        return Qwen3NextConfig(**cfg)

    def layer_type(self, i: int) -> str:
        return FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)


def _f32_param(name: str, shape, init):
    return LayerHelper("qwen3_next").create_parameter(
        ParamAttr(name=name, initializer=init), list(shape), "float32")


def _norm(x, name: str, cfg: Qwen3NextConfig, dim: int, zero_centered=True):
    """RMS norm over ``x``'s last axis (of size ``dim``) with a scale
    stored around zero (``1 + w``), or a plain one."""
    init = Constant(0.0 if zero_centered else 1.0)
    return layers.rms_norm(x, _f32_param(f"{name}_scale", [dim], init),
                           epsilon=cfg.rms_norm_eps,
                           zero_centered=zero_centered)


def _attention(hb, p: str, S: int, cfg: Qwen3NextConfig, positions, attend,
               i: int):
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qg = layers.reshape(_proj_out(hb, nh * 2 * hd, f"{p}_q", cfg),
                        [0, S, nh, 2 * hd])
    q, gate = layers.split(qg, 2, dim=3)
    k = layers.reshape(_proj_out(hb, nkv * hd, f"{p}_k", cfg),
                       [0, S, nkv, hd])
    v = _split_heads(_proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    rot = lambda t: layers.cast(layers.rotary_embedding(
        layers.transpose(t, [0, 2, 1, 3]), positions, theta=cfg.rope_theta,
        rotary_dim=cfg.rotary_dim, pairing="half"), cfg.dtype)
    q = rot(_norm(q, f"{p}_qnorm", cfg, hd))
    k = rot(_norm(k, f"{p}_knorm", cfg, hd))
    ctx = attend(i, q, k, v)                                  # [B, nh, S, hd]
    ctx = layers.cast(layers.transpose(ctx, [0, 2, 1, 3]), "float32")
    ctx = layers.elementwise_mul(ctx, layers.sigmoid(gate))
    ctx = layers.cast(layers.reshape(ctx, [0, S, nh * hd]), cfg.dtype)
    return _proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)


def _delta_net(hb, p: str, S: int, cfg: Qwen3NextConfig, recur, i: int):
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    C, vd = cfg.conv_channels, Hv * Dv
    qkvz = _proj_out(hb, C + vd, f"{p}_qkvz", cfg)
    mixed, z = layers.split(qkvz, [C, vd], dim=2)
    b, a = layers.split(_proj_out(hb, 2 * Hv, f"{p}_ba", cfg), 2, dim=2)
    helper = LayerHelper("qwen3_next")
    conv_w = helper.create_parameter(
        _attr(f"{p}_conv_w", cfg), [C, cfg.linear_conv_kernel_dim], cfg.dtype)
    # decay rates from 0.25 to 2 a unit of softplus, and a softplus centred
    # on 0.02 to 0.12: exp(g) from 0.8 to 0.995 a token, by head
    a_log = _f32_param(f"{p}_a_log", [Hv],
                       Uniform(math.log(0.25), math.log(2.0)))
    dt_bias = _f32_param(f"{p}_dt_bias", [Hv], Uniform(-4.0, -2.0))
    o, stats = recur(i, mixed, conv_w, a, b, a_log, dt_bias)  # [B, S, Hv Dv]
    o = _norm(layers.reshape(o, [0, S, Hv, Dv]), f"{p}_gnorm", cfg, Dv,
              zero_centered=False)
    o = layers.elementwise_mul(
        o, layers.swish(layers.reshape(z, [0, S, Hv, Dv])))
    o = layers.cast(layers.reshape(o, [0, S, vd]), cfg.dtype)
    return _proj_out(o, cfg.hidden_size, f"{p}_out", cfg), stats


def _block(x, i: int, cfg: Qwen3NextConfig, positions, real, mix):
    """One layer on the residual stream ``x`` [B, S, H] (f32). ``real``
    [B, S] is 1 on the tokens of the sequences this dispatch serves.
    ``mix.attend(i, q, k, v)`` stores ``k``/``v`` in layer ``i``'s cache
    and returns the attended context; ``mix.recur(i, mixed, conv_w, a, b,
    a_log, dt_bias)`` runs the rule on layer ``i``'s state. Returns the new
    stream, the expert op's statistics and the rule's (None on a full
    layer)."""
    p = f"{_P}_l{i}"
    S, H = x.shape[1], cfg.hidden_size
    hb = layers.cast(_norm(x, f"{p}_ln_in", cfg, H), cfg.dtype)
    if cfg.layer_type(i) == FULL:
        att, rule = _attention(hb, p, S, cfg, positions, mix.attend, i), None
    else:
        att, rule = _delta_net(hb, p, S, cfg, mix.recur, i)
    x = layers.elementwise_add(x, att)
    h = _norm(x, f"{p}_ln_post", cfg, H)
    hb = layers.cast(h, cfg.dtype)
    routed, shared, stats = _ffn(h, hb, p, cfg, real, join="gated")
    x = layers.elementwise_add(x, layers.elementwise_add(routed, shared))
    return x, stats, rule


def _stack_layers(x, cfg: Qwen3NextConfig, positions, real, mix):
    stats, rules = [], []
    for i in range(cfg.num_layers):
        x, s, r = _block(x, i, cfg, positions, real, mix)
        stats.append(s)
        if r is not None:
            rules.append(r)
    return (_norm(x, f"{_P}_lnf", cfg, cfg.hidden_size),
            layers.stack(stats, axis=0),
            layers.stack(rules, axis=0) if rules else None)


def _head(h2d, cfg: Qwen3NextConfig):
    w = LayerHelper("qwen3_next").create_parameter(
        _attr(f"{_P}_lm_head", cfg), [cfg.vocab_size, cfg.hidden_size],
        cfg.dtype)
    return _logits(h2d, cfg, w)


def _state_vars(block, cfg: Qwen3NextConfig, batch_slots: int, max_seq: int):
    """Current token, position and decode gate per slot, and each layer's
    state by kind: ``full`` a K/V pair ``[slots, kv_heads, max_seq,
    head_dim]`` in ``cfg.dtype``; ``recurrent`` the rule's ``[slots, value
    heads, key dim, value dim]`` and the convolution's tail ``[slots, taps
    - 1, channels]``, both f32."""
    mk, sv, tok, pos, active = _state_table(block, _P, batch_slots)
    kinds, layer_state = {}, []
    for i in range(cfg.num_layers):
        if cfg.layer_type(i) == FULL:
            shape = (batch_slots, cfg.num_kv_heads, max_seq, cfg.head_dim)
            pair = tuple(mk(f"{_P}_kv_{kv}_{i}", shape, cfg.dtype)
                         for kv in "kv")
            kind = "full"
        else:
            pair = (mk(f"{_P}_rule_{i}",
                       (batch_slots, cfg.linear_num_value_heads,
                        cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                       "float32"),
                    mk(f"{_P}_conv_{i}",
                       (batch_slots, cfg.linear_conv_kernel_dim - 1,
                        cfg.conv_channels), "float32"))
            kind = "recurrent"
        layer_state.append(pair)
        kinds.update({v.name: kind for v in pair})
    return tok, pos, active, layer_state, sv, kinds


@dataclasses.dataclass
class _Mix:
    attend: object
    recur: object


def _rule(cfg: Qwen3NextConfig, state, mask, mode, **slots):
    def recur(i, mixed, conv_w, a, b, a_log, dt_bias):
        return layers.gated_delta_rule(
            mixed, conv_w, a, b, a_log, dt_bias, *state[i], mask,
            cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim, mode=mode,
            **slots)
    return recur


def _build_prefill(cfg, B, R, S, max_seq, sample, startup):
    """The full-sequence phase for one prompt bucket: ``R`` sequences a
    dispatch, each naming its slot (``cohere_moe._prefill_feeds``). A full
    layer writes the bucket into the slot's cache at row 0; a linear layer
    scans the prompt from a zero state and overwrites the slot's."""
    main = Program()
    with program_guard(main, startup):
        ids, pos_ids, pmask, plen, smask, slots = _prefill_feeds(R, S)
        tok, pos, active, state, sv, _ = _state_vars(
            main.global_block, cfg, B, max_seq)
        bias = layers.unsqueeze(
            layers.scale(pmask, scale=10000.0, bias=-10000.0), [1, 2])
        zero_pos = layers.fill_constant([R, 1], "int64", 0)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v):
            for cache, new in zip(state[i], (k, v)):
                layers.kv_cache_append(cache, new, zero_pos, slot_mask=smask,
                                       slots=slots)
            return layers.fused_multihead_attention(
                q, k, v, bias_qk=bias, causal=True, scale=scale,
                is_test=True)

        mix = _Mix(attend, _rule(cfg, state, pmask, "scan", slots=slots,
                                 slot_mask=smask))
        real = layers.elementwise_mul(pmask, smask, axis=0)
        h, stats, rules = _stack_layers(
            _embed(ids, cfg, f"{_P}_word_emb"), cfg, pos_ids, real, mix)
        one = layers.fill_constant([R, 1], "int64", 1)
        last_h = layers.sequence_gather(h, layers.elementwise_sub(plen, one))
        logits = _head(last_h, cfg)
        first_tok = layers.sample_token(logits, **sample)
        _commit_prefill(tok, pos, active, slots, first_tok, plen, smask)
    return {"main": main, "first_token": first_tok, "state_vars": sv,
            "last_logits": logits, "expert_stats": stats,
            "rule_stats": rules, "rows": R, "feeds": PREFILL_FEEDS}


def _build_decode(cfg, B, max_seq, page_size, sample):
    """The per-token phase: no feeds, everything is persistable state."""
    main = Program()
    with program_guard(main, Program()):
        tok, pos, active, state, sv, kinds = _state_vars(
            main.global_block, cfg, B, max_seq)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v):
            ck, cv = state[i]
            return layers.fused_decode_attention(
                q, k, v, ck, cv, pos, scale=scale, page_size=page_size,
                slot_mask=active)

        mix = _Mix(attend, _rule(cfg, state, active, "step"))
        x = layers.unsqueeze(_embed(tok, cfg, f"{_P}_word_emb"), [1])
        h, stats, rules = _stack_layers(x, cfg, pos, active, mix)
        logits = _head(layers.reshape(h, [0, cfg.hidden_size]), cfg)
        next_tok = layers.sample_token(logits, **sample)
        _commit_decode(tok, pos, active, next_tok, max_seq)
    return {"main": main, "next_token": next_tok, "state_vars": sv,
            "logits": logits, "expert_stats": stats, "rule_stats": rules,
            "rule_layers": [i for i in range(cfg.num_layers)
                            if cfg.layer_type(i) == LINEAR],
            "rule_family": "gdn",
            "cache_kinds": kinds,
            "cache_vars": [tuple(v.name for v in pair) for pair in state],
            "active_var": active.name}


def build_qwen3_next_generative(cfg: Qwen3NextConfig = None,
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
    cfg = cfg or Qwen3NextConfig.tiny()
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
    prefill = {S: _build_prefill(cfg, batch_slots, rows, S, max_seq, sample,
                                 startup) for S in prompt_buckets}
    decode = _build_decode(cfg, batch_slots, max_seq, page_size, sample)
    return _generative(cfg, startup, prefill, decode, batch_slots, max_seq,
                       page_size, strategy)
