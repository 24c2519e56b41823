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
          ``oproj(attn * sigmoid(gate))``. State: a K/V cache.
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

What is held here is ONE chip's share: ``experts_held`` routed experts
from ``expert_offset``, the mixers and the shared expert whole, a slice of
the vocabulary; bf16 storage, bf16 matmul operands with f32 accumulation;
norms, router, gates, the rule and the residual stream f32.

The block is written once (:func:`_block`) for both phases, which are
``models/decoder.py``'s; a phase hands it a ``mix`` handle with ``attend``
(a K/V cache pair's) and ``recur`` (the rule over a whole prompt, which
overwrites the named slots' state, or one step under the decode gate). The
state table holds two kinds of state: ``full`` (a K/V pair of ``max_seq``
rows) and ``recurrent``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

from .. import layers
from ..initializer import Uniform
from ..layer_helper import LayerHelper
from ..ops.gdn import count_rule_stats
from ..ops.moe import expert_counter
from . import decoder
from .decoder import (Mix, attr, f32_param, ffn, norm, proj, proj_out,
                      split_heads)

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


def _attention(hb, p: str, S: int, cfg: Qwen3NextConfig, positions, attend,
               i: int):
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qg = layers.reshape(proj_out(hb, nh * 2 * hd, f"{p}_q", cfg),
                        [0, S, nh, 2 * hd])
    q, gate = layers.split(qg, 2, dim=3)
    k = layers.reshape(proj_out(hb, nkv * hd, f"{p}_k", cfg),
                       [0, S, nkv, hd])
    v = split_heads(proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    rot = lambda t: layers.cast(layers.rotary_embedding(
        layers.transpose(t, [0, 2, 1, 3]), positions, theta=cfg.rope_theta,
        rotary_dim=cfg.rotary_dim, pairing="half"), cfg.dtype)
    q = rot(norm(q, f"{p}_qnorm", cfg, hd))
    k = rot(norm(k, f"{p}_knorm", cfg, hd))
    ctx = attend(i, q, k, v)                                  # [B, nh, S, hd]
    ctx = layers.cast(layers.transpose(ctx, [0, 2, 1, 3]), "float32")
    ctx = layers.elementwise_mul(ctx, layers.sigmoid(gate))
    ctx = layers.cast(layers.reshape(ctx, [0, S, nh * hd]), cfg.dtype)
    return proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)


def _delta_net(hb, p: str, S: int, cfg: Qwen3NextConfig, recur, i: int):
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    C, vd = cfg.conv_channels, Hv * Dv
    qkvz = proj_out(hb, C + vd, f"{p}_qkvz", cfg)
    mixed, z = layers.split(qkvz, [C, vd], dim=2)
    b, a = layers.split(proj_out(hb, 2 * Hv, f"{p}_ba", cfg), 2, dim=2)
    helper = LayerHelper("qwen3_next")
    conv_w = helper.create_parameter(
        attr(f"{p}_conv_w", cfg), [C, cfg.linear_conv_kernel_dim], cfg.dtype)
    # decay rates from 0.25 to 2 a unit of softplus, and a softplus centred
    # on 0.02 to 0.12: exp(g) from 0.8 to 0.995 a token, by head
    a_log = f32_param(f"{p}_a_log", [Hv],
                       Uniform(math.log(0.25), math.log(2.0)))
    dt_bias = f32_param(f"{p}_dt_bias", [Hv], Uniform(-4.0, -2.0))
    o, stats = recur(i, mixed, conv_w, a, b, a_log, dt_bias)  # [B, S, Hv Dv]
    o = norm(layers.reshape(o, [0, S, Hv, Dv]), f"{p}_gnorm", cfg, Dv,
              zero_centered=False)
    o = layers.elementwise_mul(
        o, layers.swish(layers.reshape(z, [0, S, Hv, Dv])))
    o = layers.cast(layers.reshape(o, [0, S, vd]), cfg.dtype)
    return proj_out(o, cfg.hidden_size, f"{p}_out", cfg), stats


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
    hb = layers.cast(norm(x, f"{p}_ln_in", cfg, H), cfg.dtype)
    if cfg.layer_type(i) == FULL:
        att, rule = _attention(hb, p, S, cfg, positions, mix.attend, i), None
    else:
        att, rule = _delta_net(hb, p, S, cfg, mix.recur, i)
    x = layers.elementwise_add(x, att)
    h = norm(x, f"{p}_ln_post", cfg, H)
    hb = layers.cast(h, cfg.dtype)
    routed, shared, stats = ffn(h, hb, p, cfg, real, join="gated")
    x = layers.elementwise_add(x, layers.elementwise_add(routed, shared))
    return x, stats, rule


def _stack_layers(x, cfg: Qwen3NextConfig, positions, real, mix):
    stats, rules, linear = [], [], []
    for i in range(cfg.num_layers):
        x, s, r = _block(x, i, cfg, positions, real, mix)
        stats.append(s)
        if r is not None:
            rules.append(r)
            linear.append(i)
    h = norm(x, f"{_P}_lnf", cfg, cfg.hidden_size)
    experts = layers.stack(stats, axis=0)
    return h, [
        ("expert_stats", experts, expert_counter(experts)),
        ("rule_stats", layers.stack(rules, axis=0) if rules else None,
         functools.partial(count_rule_stats, layers=linear, family="gdn"))]


def _embed(ids, cfg: Qwen3NextConfig):
    return decoder.embed(ids, cfg, f"{_P}_word_emb")


def _head(h2d, cfg: Qwen3NextConfig):
    return decoder.untied_head(h2d, cfg, f"{_P}_lm_head")


def _state_vars(block, cfg: Qwen3NextConfig, batch_slots: int, max_seq: int):
    """Current token, position and decode gate per slot, and each layer's
    state by kind: ``full`` a K/V pair ``[slots, kv_heads, max_seq,
    head_dim]`` in ``cfg.dtype``; ``recurrent`` the rule's ``[slots, value
    heads, key dim, value dim]`` and the convolution's tail ``[slots, taps
    - 1, channels]``, both f32."""
    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots)
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


def _rule(cfg: Qwen3NextConfig, state, mask, mode, **slots):
    def recur(i, mixed, conv_w, a, b, a_log, dt_bias):
        return layers.gated_delta_rule(
            mixed, conv_w, a, b, a_log, dt_bias, *state[i], mask,
            cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim, mode=mode,
            **slots)
    return recur


def _prefill_handle(cfg, state, pmask, plen, smask, slots, page_size):
    """A full layer writes the bucket into the slot's cache at row 0; a
    linear layer scans the prompt from a zero state and overwrites the
    slot's."""
    return Mix(decoder.bulk_attend(state, pmask, smask, slots,
                                   1.0 / math.sqrt(cfg.head_dim)),
               _rule(cfg, state, pmask, "scan", slots=slots,
                     slot_mask=smask))


def _decode_handle(cfg, state, pos, active, page_size):
    return Mix(decoder.step_attend(state, pos, active,
                                   1.0 / math.sqrt(cfg.head_dim), page_size),
               _rule(cfg, state, active, "step"))


def build_qwen3_next_generative(cfg: Qwen3NextConfig = None,
                                batch_slots: int = 4, max_seq: int = 64,
                                page_size: int = 8, prompt_buckets=(16,),
                                strategy: str = "greedy",
                                temperature: float = 1.0, top_k: int = 0,
                                prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). ``prefill_rows``: the sequences a
    prefill dispatch carries, each naming its slot (default: one per
    slot)."""
    cfg = cfg or Qwen3NextConfig.tiny()
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          _prefill_handle, _decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
