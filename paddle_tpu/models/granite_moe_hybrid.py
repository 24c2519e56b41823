"""Granite-4.0-H decoder (``model_type`` ``granitemoehybrid``) for
generative serving, as the share of the model that ONE chip of an
expert-parallel deployment holds.

On the residual stream ``x`` (f32), with ``N(v) = v / sqrt(mean(v^2) + eps)
* w`` and four published scalars (``embedding_multiplier``,
``residual_multiplier`` r, ``attention_multiplier``, ``logits_scaling``):

    x0 = embedding_multiplier * E[ids]
    h = x + r Mixer_i(N_in(x));   y = h + r (MoE(N_post(h)) + Shared(N_post(h)))
    logits = N_f(x_L) E^T / logits_scaling          (the head is the embedding)

Layer ``i`` is what ``layer_types[i]`` says: ``mamba`` (nine in ten) or
``attention``.

    Mamba-2: ``in_proj`` gives ``[z | xBC | dt]``; a causal depthwise
          convolution with a bias over ``xBC``, SiLU, ``xBC -> x [heads,
          head dim] | B | C`` (one ``B`` and ``C`` for all heads);
          ``dt = softplus(dt + dt_bias)``, a head's decay ``exp(-exp(A_log)
          dt)``, its state ``S <- a S + dt x B^T``, ``y = S C + D x``
          (``layers.mamba2_scan``); ``N_g(y * silu(z))`` over all the
          heads' columns at once, ``out_proj``. State: the scan's ``[heads,
          head dim, state dim]`` f32 and the convolution's last ``taps -
          1`` input rows. Fixed size: it does not grow with the sequence.
    Attention: grouped-query causal attention with NO positional signal,
          scores scaled by ``attention_multiplier`` (not ``head_dim^-1/2``),
          no biases. State: a K/V cache.
    MoE:  softmax over all experts, the ``top_k`` largest, weights
          normalised over them; the held experts' part of the routed sum
          plus one shared expert of its own width, added.

What is held here is ONE chip's share: ``experts_held`` routed experts
from ``expert_offset``, the mixers and the shared expert whole, a slice of
the vocabulary; bf16 storage, bf16 matmul operands with f32 accumulation;
norms, router, ``dt``, decay, the scan and the residual stream f32.

The block is written once (:func:`_block`) for both phases, which are
``models/decoder.py``'s; a phase hands it a ``mix`` handle with ``attend``
(a K/V cache pair's) and ``recur`` (the scan).
The state table holds two kinds of state: ``full`` (a K/V pair of
``max_seq`` rows) and ``recurrent``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

from .. import layers
from ..framework import default_main_program
from ..initializer import Constant, Uniform
from ..layer_helper import LayerHelper
from ..ops.gdn import count_rule_stats
from ..ops.moe import expert_counter
from . import decoder
from .decoder import (Mix, attr, f32_param, ffn, proj, proj_out,
                      split_heads)

__all__ = ["GraniteMoeHybridConfig", "build_granite_moe_hybrid_generative"]

MAMBA, ATTENTION = "mamba", "attention"
_P = "gmh"                           # prefix of every parameter and state var


@dataclasses.dataclass
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None   # None: attention at 5 of 10
    num_heads: int = 32
    num_kv_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    intermediate_size: int = 768         # width of one routed expert
    shared_intermediate_size: int = 1536
    num_experts: int = 72
    top_k: int = 10
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    embedding_range: Optional[float] = None   # None: initializer_range
    dtype: str = "bfloat16"
    score_fn: str = "softmax"
    num_shared_experts: int = 1          # one, added

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.embedding_range is None:
            self.embedding_range = self.initializer_range
        if self.layer_types is None:
            self.layer_types = tuple(
                ATTENTION if i % 10 == 5 else MAMBA
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types} for "
                             f"{self.num_layers} layers")
        if self.hidden_size % self.num_heads or \
                self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads in a stream of {self.hidden_size}")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not "
                f"{self.mamba_expand} x {self.hidden_size}")
        if self.mamba_n_groups != 1:
            raise ValueError("the scan is built for one B and C a token "
                             "(mamba_n_groups 1)")

    @staticmethod
    def tiny(**over):
        """CI-sized: five layers with one of attention, 4 of 16 experts
        held, a shared expert twice a routed one's width."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=5,
                   layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA),
                   num_heads=4, num_kv_heads=2, mamba_n_heads=8,
                   mamba_d_head=16, mamba_d_state=32, mamba_chunk_size=16,
                   intermediate_size=32, shared_intermediate_size=64,
                   num_experts=16, top_k=4, experts_held=4)
        cfg.update(over)
        return GraniteMoeHybridConfig(**cfg)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state


def _norm(x, name: str, cfg: GraniteMoeHybridConfig, dim: int):
    return decoder.norm(x, name, cfg, dim, zero_centered=False)


def _attention(hb, p: str, S: int, cfg: GraniteMoeHybridConfig, attend,
               i: int):
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = split_heads(proj(hb, nh * hd, f"{p}_q", cfg), S, nh, hd)
    k = split_heads(proj(hb, nkv * hd, f"{p}_k", cfg), S, nkv, hd)
    v = split_heads(proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    ctx = attend(i, q, k, v)                                  # [B, nh, S, hd]
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * hd])
    return proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)


def _mamba(hb, p: str, S: int, cfg: GraniteMoeHybridConfig, recur, i: int):
    H, C, di = cfg.mamba_n_heads, cfg.conv_channels, cfg.d_inner
    z, xbc, dt = layers.split(proj_out(hb, di + C + H, f"{p}_in", cfg),
                              [di, C, H], dim=2)
    conv_w = LayerHelper("granite_moe_hybrid").create_parameter(
        attr(f"{p}_conv_w", cfg), [C, cfg.mamba_d_conv], cfg.dtype)
    conv_b = f32_param(f"{p}_conv_b", [C], Constant(0.0))
    # Mamba-2's own start: A from 1 to 16, dt from 0.001 to 0.1 (the
    # softplus of dt_bias), the skip at 1
    a_log = f32_param(f"{p}_a_log", [H], Uniform(0.0, math.log(16.0)))
    dt_bias = f32_param(f"{p}_dt_bias", [H], Uniform(-6.9, -2.25))
    skip = f32_param(f"{p}_d", [H], Constant(1.0))
    y, stats = recur(i, xbc, conv_w, conv_b, dt, a_log, dt_bias, skip)
    y = _norm(layers.elementwise_mul(y, layers.swish(z)), f"{p}_gnorm", cfg,
              di)
    return proj_out(layers.cast(y, cfg.dtype), cfg.hidden_size,
                     f"{p}_out", cfg), stats


def _block(x, i: int, cfg: GraniteMoeHybridConfig, real, mix):
    """One layer on the residual stream ``x`` [B, S, H] (f32). ``real``
    [B, S] is 1 on the tokens of the sequences this dispatch serves.
    ``mix.attend(i, q, k, v)`` stores ``k``/``v`` in layer ``i``'s cache
    and returns the attended context; ``mix.recur(i, xbc, conv_w, conv_b,
    dt, a_log, dt_bias, d)`` runs the scan on layer ``i``'s state. Returns
    the new stream, the expert op's statistics and the scan's (None on an
    attention layer)."""
    p = f"{_P}_l{i}"
    S, H, r = x.shape[1], cfg.hidden_size, cfg.residual_multiplier
    hb = layers.cast(_norm(x, f"{p}_ln_in", cfg, H), cfg.dtype)
    if cfg.layer_types[i] == ATTENTION:
        mixed, scan = _attention(hb, p, S, cfg, mix.attend, i), None
    else:
        mixed, scan = _mamba(hb, p, S, cfg, mix.recur, i)
    x = layers.elementwise_add(x, layers.scale(mixed, scale=r))
    h = _norm(x, f"{p}_ln_post", cfg, H)
    routed, shared, stats = ffn(h, layers.cast(h, cfg.dtype), p, cfg, real,
                                 join="sum")
    x = layers.elementwise_add(
        x, layers.scale(layers.elementwise_add(routed, shared), scale=r))
    return x, stats, scan


def _embed(ids, cfg: GraniteMoeHybridConfig):
    """``embedding_multiplier`` times the embedding's rows. The embedding is
    the head too, so it is drawn at a range of its own: at the matrices'
    range the multiplier makes every token's best successor itself."""
    own = dataclasses.replace(cfg, initializer_range=cfg.embedding_range)
    return layers.scale(decoder.embed(ids, own, f"{_P}_word_emb"),
                        scale=float(cfg.embedding_multiplier))


def _stack_layers(x, cfg: GraniteMoeHybridConfig, positions, real, mix):
    """``positions`` go unread: neither kind of layer has a positional
    signal."""
    stats, scans, mamba = [], [], []
    for i in range(cfg.num_layers):
        x, s, r = _block(x, i, cfg, real, mix)
        stats.append(s)
        if r is not None:
            scans.append(r)
            mamba.append(i)
    h = _norm(x, f"{_P}_lnf", cfg, cfg.hidden_size)
    experts = layers.stack(stats, axis=0)
    return h, [
        ("expert_stats", experts, expert_counter(experts)),
        ("rule_stats", layers.stack(scans, axis=0) if scans else None,
         functools.partial(count_rule_stats, layers=mamba, family="ssm"))]


def _head(h2d, cfg: GraniteMoeHybridConfig):
    """The tied head: the embedding's held rows, logits over the slice."""
    return decoder.logits(
        h2d, cfg, default_main_program().global_block.var(f"{_P}_word_emb"),
        1.0 / cfg.logits_scaling)


def _state_vars(block, cfg: GraniteMoeHybridConfig, batch_slots: int,
                max_seq: int):
    """Current token, position and decode gate per slot, and each layer's
    state by kind: ``full`` a K/V pair ``[slots, kv_heads, max_seq,
    head_dim]`` in ``cfg.dtype``; ``recurrent`` the scan's ``[slots, heads,
    head dim, state dim]`` and the convolution's tail ``[slots, taps - 1,
    channels]``, both f32."""
    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots)
    kinds, layer_state = {}, []
    for i in range(cfg.num_layers):
        if cfg.layer_types[i] == ATTENTION:
            shape = (batch_slots, cfg.num_kv_heads, max_seq, cfg.head_dim)
            pair = tuple(mk(f"{_P}_kv_{kv}_{i}", shape, cfg.dtype)
                         for kv in "kv")
            kind = "full"
        else:
            pair = (mk(f"{_P}_ssm_{i}",
                       (batch_slots, cfg.mamba_n_heads, cfg.mamba_d_head,
                        cfg.mamba_d_state), "float32"),
                    mk(f"{_P}_conv_{i}",
                       (batch_slots, cfg.mamba_d_conv - 1,
                        cfg.conv_channels), "float32"))
            kind = "recurrent"
        layer_state.append(pair)
        kinds.update({v.name: kind for v in pair})
    return tok, pos, active, layer_state, sv, kinds


def _scan(cfg: GraniteMoeHybridConfig, state, mask, mode, **slots):
    def recur(i, xbc, conv_w, conv_b, dt, a_log, dt_bias, d):
        return layers.mamba2_scan(
            xbc, conv_w, conv_b, dt, a_log, dt_bias, d, *state[i], mask,
            cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            mode=mode, chunk=cfg.mamba_chunk_size, **slots)
    return recur


def _prefill_handle(cfg, state, pmask, plen, smask, slots, page_size):
    """An attention layer writes the bucket into the slot's cache at row 0;
    a Mamba-2 layer scans the prompt from a zero state and overwrites the
    slot's."""
    return Mix(decoder.bulk_attend(state, pmask, smask, slots,
                                   cfg.attention_multiplier),
               _scan(cfg, state, pmask, "scan", slots=slots,
                     slot_mask=smask))


def _decode_handle(cfg, state, pos, active, page_size):
    return Mix(decoder.step_attend(state, pos, active,
                                   cfg.attention_multiplier, page_size),
               _scan(cfg, state, active, "step"))


def build_granite_moe_hybrid_generative(
        cfg: GraniteMoeHybridConfig = None, batch_slots: int = 4,
        max_seq: int = 64, page_size: int = 8, prompt_buckets=(16,),
        strategy: str = "greedy", temperature: float = 1.0, top_k: int = 0,
        prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). ``prefill_rows``: the sequences a
    prefill dispatch carries, each naming its slot (default: one per
    slot)."""
    cfg = cfg or GraniteMoeHybridConfig.tiny()
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          _prefill_handle, _decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
