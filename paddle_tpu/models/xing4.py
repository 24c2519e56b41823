"""Xing4.0-29B-A4B decoder (``model_type`` ``xing4_0``) for generative
serving, as the share of the model that ONE chip of an expert-parallel
deployment holds.

The residual path is ``hc_mult`` streams: a token is ``X`` in R^{n x C}
(f32; ``C`` the hidden size). The embedding row is copied into the ``n``
streams; every sublayer reads a mix of them and writes a mix back through
manifold-constrained hyper-connections (``layers.hyper_connection_read`` /
``_write``, ``ops/hyper_connection.py`` has the equations), with its own
coefficients' parameters, per token:

    u = H_pre X;  y = Attn(N_in(u));    X <- H_res X + H_post^T y
    u = H_pre X;  y = FFN_i(N_post(u)); X <- H_res X + H_post^T y

and after the last layer the streams are summed, then the final norm and
the untied ``lm_head``.

    Attn: multi-head latent attention, ``models/glm4_moe_lite.py``'s at
          other numbers (``decoder.latent_attention``: one code for both),
          with YaRN positions (``rope_scaling``: the frequencies at every
          position, and the softmax scale times ``m^2``).
    FFN:  the first ``first_k_dense`` layers a dense gated feed-forward of
          ``dense_intermediate_size``; the others ``s = sigmoid(h Wr)``,
          the ``top_k`` experts with the largest ``s + b``, weights
          ``route_scale * s / sum s`` over the chosen, the held experts'
          part of the routed sum plus one shared expert, added
          (``decoder.ffn``).

What is held here is ONE chip's share: ``experts_held`` routed experts
from ``expert_offset``, attention, the dense layers and the shared expert
whole, a slice of the vocabulary; bf16 storage, bf16 matmul operands with
f32 accumulation; norms, router, softmax, the ``n`` streams and every
hyper-connection coefficient f32. The block is written once
(:func:`_block`); the two phases, the state table (one kind of state,
``latent``) and the attention's cache handles are ``models/decoder.py``'s.
The multi-token prediction module is not built.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .. import layers
from ..initializer import (Constant, NumpyArrayInitializer, TruncatedNormal)
from ..ops.hyper_connection import count_hc_stats
from ..ops.latent_attention import count_latent_stats
from ..ops.moe import expert_counter
from . import decoder
from .decoder import f32_param, ffn, gated_mlp

__all__ = ["Xing4Config", "build_xing4_generative"]

_P = "xing"                          # prefix of every parameter and state var


@dataclasses.dataclass
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    # a ``rope_scaling`` of type ``yarn`` (None: plain positions)
    rope_scaling: Optional[dict] = dataclasses.field(default_factory=lambda: {
        "type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    hc_mult: int = 4                     # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    intermediate_size: int = 1024        # width of one routed expert
    dense_intermediate_size: int = 9216
    first_k_dense: int = 2
    num_experts: int = 64
    top_k: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.0
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
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
        """CI-sized: one dense layer and two with experts, 4 of 16 held,
        four streams, YaRN over 32 original positions."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=3, num_heads=4,
                   q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32,
                   dense_intermediate_size=96, first_k_dense=1,
                   num_experts=16, top_k=4, experts_held=4,
                   rope_scaling={"type": "yarn", "factor": 8,
                                 "original_max_position_embeddings": 32,
                                 "beta_fast": 4, "beta_slow": 1, "mscale": 1,
                                 "mscale_all_dim": 1})
        cfg.update(over)
        return Xing4Config(**cfg)


def _norm(x, name: str, cfg: Xing4Config, dim: int):
    return decoder.norm(x, name, cfg, dim, zero_centered=False)


def _hc_read(X, name: str, cfg: Xing4Config):
    """The hyper-connection's read for the sublayer ``name``: ``(u, H_post,
    H_res, stats)``. Its parameters: ``_proj`` [n (n + 2), n C] (rows
    ``[P_pre^T | P_post^T | P_res^T]``), ``_alpha`` [3], ``_bias``
    [n (n + 2)]; at start-up the projection is drawn, the scalars are 1
    and ``b_res`` is 4 times the identity (a stream mostly keeps to
    itself)."""
    n, C = cfg.hc_mult, cfg.hidden_size
    m = n * (n + 2)
    bias = np.zeros(m, np.float32)
    bias[2 * n:] = 4.0 * np.eye(n, dtype=np.float32).ravel()
    return layers.hyper_connection_read(
        X,
        f32_param(f"{name}_proj", [m, n * C],
                  TruncatedNormal(0.0, cfg.initializer_range)),
        f32_param(f"{name}_alpha", [3], Constant(1.0)),
        f32_param(f"{name}_bias", [m], NumpyArrayInitializer(bias)),
        sinkhorn_iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        norm_eps=cfg.rms_norm_eps, clamp_min=cfg.hc_res_clamp[0],
        clamp_max=cfg.hc_res_clamp[1])


def _block(X, i: int, cfg: Xing4Config, positions, real, attend):
    """One layer on the streams ``X`` [B, S, n, C] (f32). ``real`` [B, S]
    is 1 on the tokens of the sequences this dispatch serves.
    ``attend(i, q, c, k_rope, w_kvb)`` appends the rows to layer ``i``'s
    latent cache and returns the attended context and the op's statistics.
    Returns the new streams, the expert op's statistics (None on a dense
    layer), the attention's and the two hyper-connections'."""
    p = f"{_P}_l{i}"
    S, H = X.shape[1], cfg.hidden_size
    u, post, res, mixed_a = _hc_read(X, f"{p}_hc_attn", cfg)
    hb = layers.cast(_norm(u, f"{p}_ln_in", cfg, H), cfg.dtype)
    att, walked = decoder.latent_attention(hb, p, S, cfg, positions, attend,
                                           i)
    X = layers.hyper_connection_write(X, att, post, res)
    u, post, res, mixed_f = _hc_read(X, f"{p}_hc_ffn", cfg)
    h = _norm(u, f"{p}_ln_post", cfg, H)
    hb = layers.cast(h, cfg.dtype)
    if i < cfg.first_k_dense:
        y, stats = gated_mlp(hb, cfg.dense_intermediate_size, f"{p}_mlp",
                             cfg), None
    else:
        routed, shared, stats = ffn(h, hb, p, cfg, real, join="sum")
        y = layers.elementwise_add(routed, shared)
    X = layers.hyper_connection_write(X, y, post, res)
    return X, stats, walked, [mixed_a, mixed_f]


def _stack_layers(x, cfg: Xing4Config, positions, real, attend):
    """``x`` [B, S, C] the embedding rows: copied into the streams, the
    layers, the streams summed, the final norm."""
    X = layers.stack([x] * cfg.hc_mult, axis=2)
    experts, walks, moe, mixed = [], [], [], []
    for i in range(cfg.num_layers):
        X, s, w, hc = _block(X, i, cfg, positions, real, attend)
        walks.append(w)
        mixed += hc
        if s is not None:
            experts.append(s)
            moe.append(i)
    h = _norm(layers.reduce_sum(X, dim=2), f"{_P}_lnf", cfg,
              cfg.hidden_size)
    stats = []
    if experts:
        experts = layers.stack(experts, axis=0)
        stats.append(("expert_stats", experts,
                      expert_counter(experts, moe)))
    stats.append(("latent_stats", layers.stack(walks, axis=0),
                  count_latent_stats))
    stats.append(("hc_stats", layers.stack(mixed, axis=0), count_hc_stats))
    return h, stats


def _embed(ids, cfg: Xing4Config):
    return decoder.embed(ids, cfg, f"{_P}_word_emb")


def _head(h2d, cfg: Xing4Config):
    return decoder.untied_head(h2d, cfg, f"{_P}_lm_head")


def _state_vars(block, cfg: Xing4Config, batch_slots: int, max_seq: int):
    return decoder.latent_state(block, cfg, _P, batch_slots, max_seq)


def build_xing4_generative(cfg: Xing4Config = None, batch_slots: int = 4,
                           max_seq: int = 64, page_size: int = 8,
                           prompt_buckets=(16,), strategy: str = "greedy",
                           temperature: float = 1.0, top_k: int = 0,
                           prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). ``prefill_rows``: the sequences a
    prefill dispatch carries, each naming its slot (default: one per
    slot)."""
    cfg = cfg or Xing4Config.tiny()
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          decoder.latent_prefill_handle,
                          decoder.latent_decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
