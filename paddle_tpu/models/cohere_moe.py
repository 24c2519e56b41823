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
length, the layer's type and a cache handle; the prefill and decode
programs differ only in their feeds, in the handle (bulk write and
full-sequence attention, or fused append-and-attend over the cache) and in
how they commit the sampled token. The state table has two kinds of cache
in it: a sliding layer keeps ``min(sliding_window, max_seq)`` rows as a
ring, a full layer ``max_seq``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from .. import layers
from ..framework import Program, program_guard
from ..initializer import TruncatedNormal, Uniform
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .gpt import _merge_state

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


# ``cfg`` below is this module's configuration or another sparse-expert
# decoder's (``models/qwen3_next.py``): what a helper reads of it is
# ``initializer_range`` and ``dtype``, and for the feed-forward
# ``hidden_size``, ``intermediate_size`` (an expert's width), ``num_experts``,
# ``experts_held``, ``expert_offset``, ``top_k``, ``num_shared_experts``,
# ``score_fn`` and, where it has them, ``select_bias``, ``route_scale`` and
# ``shared_intermediate_size`` (the shared experts' width in all, where it is
# not ``num_shared_experts`` routed widths).

def _attr(name: str, cfg):
    return ParamAttr(name=name,
                     initializer=TruncatedNormal(0.0, cfg.initializer_range))


def _ln(x, name: str, cfg: CohereMoeConfig, axis: int = 2):
    return layers.layer_norm(x, shift=False, begin_norm_axis=axis,
                             epsilon=cfg.layer_norm_eps,
                             param_attr=ParamAttr(name=f"{name}_scale"))


def _proj(x, size: int, name: str, cfg, act=None):
    return layers.fc(x, size, num_flatten_dims=2, act=act, bias_attr=False,
                     param_attr=_attr(f"{name}_w", cfg))


def _proj_out(x, size: int, name: str, cfg):
    """A projection back onto the residual stream: the f32 accumulator is
    kept, where ``fc`` would round it to the operands' type on its way to
    an f32 sum (every rounding upstream of a router moves its k-th place)."""
    w = LayerHelper("cohere_moe").create_parameter(
        _attr(f"{name}_w", cfg), [x.shape[-1], size], cfg.dtype)
    return layers.matmul(x, w, out_dtype="float32")


def _split_heads(t, seq_len: int, heads: int, head_dim: int):
    """[B, S, heads * D] -> [B, heads, S, D]."""
    t = layers.reshape(t, [0, seq_len, heads, head_dim])
    return layers.transpose(t, [0, 2, 1, 3])


def _expert_weights(name: str, cfg):
    """Router over all experts; gate, up and down of the held ones,
    stacked."""
    helper = LayerHelper("cohere_moe")
    H, F, Eh = cfg.hidden_size, cfg.intermediate_size, cfg.experts_held
    mk = lambda n, shape: helper.create_parameter(
        _attr(f"{name}_{n}_w", cfg), shape, cfg.dtype)
    return (mk("router", [H, cfg.num_experts]), mk("gate", [Eh, H, F]),
            mk("up", [Eh, H, F]), mk("down", [Eh, F, H]))


def _gated_mlp(hb, width: int, name: str, cfg):
    """``(silu(hb Wg) * (hb Wu)) Wd`` of ``width`` back onto the residual
    stream (f32): a dense feed-forward, or shared experts side by side."""
    gate = _proj(hb, width, f"{name}_gate", cfg, act="silu")
    up = _proj(hb, width, f"{name}_up", cfg)
    return _proj_out(layers.elementwise_mul(gate, up), cfg.hidden_size,
                     f"{name}_down", cfg)


def _ffn(h, hb, p: str, cfg, real=None, join: str = "mean"):
    """The feed-forward of one layer on the normed rows ``h`` (f32, what
    the router reads) and ``hb`` (the same in ``cfg.dtype``, what the
    matmuls read); ``real`` [B, S] marks the rows that are tokens of a
    sequence this dispatch serves (the rest are routed nowhere). Returns the held experts' part of the routed sum, the
    shared experts' part (both f32) and the expert op's statistics. How
    the shared experts join the routed sum is the model's (``join``):
    their ``mean``; the one expert behind a learned sigmoid gate
    (``gated``); or their plain ``sum``; a model with none
    (``num_shared_experts`` 0) gets None for their part. A configuration with
    ``select_bias`` chooses its experts by score plus a stored bias, and
    one with ``route_scale`` scales the routed weights
    (``layers.moe_experts``)."""
    bias = None
    if getattr(cfg, "select_bias", False):
        bias = LayerHelper("cohere_moe").create_parameter(
            ParamAttr(name=f"{p}_router_bias",
                      initializer=Uniform(-0.1, 0.1)),
            [cfg.num_experts], "float32")
    routed, stats = layers.moe_experts(
        h, *_expert_weights(p, cfg), num_experts=cfg.num_experts,
        top_k=cfg.top_k, expert_offset=cfg.expert_offset, token_mask=real,
        score_fn=cfg.score_fn, select_bias=bias,
        route_scale=getattr(cfg, "route_scale", 1.0))
    # the shared experts side by side: columns t*F..(t+1)*F of gate and up,
    # and the same rows of down, are shared expert t, so one product with
    # the stacked down matrix is their sum
    ns = cfg.num_shared_experts
    if not ns:
        return routed, None, stats
    width = (getattr(cfg, "shared_intermediate_size", None)
             or ns * cfg.intermediate_size)
    shared = _gated_mlp(hb, width, f"{p}_shared", cfg)
    if join != "sum":
        shared = layers.scale(shared, scale=1.0 / ns)
    if join == "gated":
        shared = layers.elementwise_mul(shared, layers.sigmoid(
            _proj_out(hb, 1, f"{p}_shared_mix", cfg)))
    return routed, shared, stats


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

    q = _split_heads(_proj(hb, nh * hd, f"{p}_q", cfg), S, nh, hd)
    k = _split_heads(_proj(hb, nkv * hd, f"{p}_k", cfg), S, nkv, hd)
    v = _split_heads(_proj(hb, nkv * hd, f"{p}_v", cfg), S, nkv, hd)
    sliding = cfg.layer_types[i] == SLIDING
    if sliding:
        q = layers.rotary_embedding(q, positions, theta=cfg.rope_theta)
        k = layers.rotary_embedding(k, positions, theta=cfg.rope_theta)
    ctx = attend(i, q, k, v, cfg.sliding_window if sliding else 0)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * hd])
    att = _proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)

    routed, shared, stats = _ffn(h, hb, p, cfg, real)
    x = layers.elementwise_add(layers.elementwise_add(x, att),
                               layers.elementwise_add(routed, shared))
    return x, stats


def _stack_layers(x, cfg: CohereMoeConfig, positions, real, attend):
    stats = []
    for i in range(cfg.num_layers):
        x, s = _block(x, i, cfg, positions, real, attend)
        stats.append(s)
    return _ln(x, f"{_P}_lnf", cfg), layers.stack(stats, axis=0)


def _embed(ids, cfg, name: str = f"{_P}_word_emb"):
    emb = layers.embedding(ids, (cfg.vocab_size, cfg.hidden_size),
                           dtype=cfg.dtype, param_attr=_attr(name, cfg))
    return layers.cast(emb, "float32")


def _logits(h2d, cfg, weight, logit_scale: float = 1.0):
    """[B, H] f32 rows -> f32 logits over the held vocabulary through
    ``weight`` [V, H] (bf16 operands, the f32 accumulator kept: a logit
    rounded to bf16 moves by more than the gap between near-best tokens)."""
    out = layers.matmul(layers.cast(h2d, cfg.dtype), weight,
                        transpose_y=True, out_dtype="float32")
    if logit_scale != 1.0:
        out = layers.scale(out, scale=float(logit_scale))
    return out


def _state_table(block, prefix: str, batch_slots: int, tokens: int = 1):
    """``(mk, sv, tok, pos, active)``: ``mk(name, shape, dtype)`` makes a
    persistable state var and enters it in ``sv`` (name -> (shape,
    dtype)); the current token (``tokens`` of them where a step carries a
    block), position and decode gate per slot are in it already."""
    sv = {}

    def mk(name, shape, dtype):
        block.create_var(name=name, shape=tuple(shape), dtype=dtype,
                         persistable=True, stop_gradient=True)
        sv[name] = (tuple(shape), dtype)
        return block.var(name)

    return (mk, sv,
            mk(f"{prefix}_gen_tokens", (batch_slots, tokens), "int64"),
            mk(f"{prefix}_gen_pos", (batch_slots, 1), "int64"),
            mk(f"{prefix}_gen_active", (batch_slots, 1), "float32"))


def _state_vars(block, cfg: CohereMoeConfig, batch_slots: int, max_seq: int):
    """Current token, position and decode gate per slot, and one K/V cache
    pair per layer: ``[slots, kv_heads, rows, head_dim]`` in ``cfg.dtype``
    with ``rows`` by the layer's type (see ``models/gpt.py:_state_vars``
    for what the executor does with them)."""
    mk, sv, tok, pos, active = _state_table(block, _P, batch_slots)
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


PREFILL_FEEDS = ("prompt_ids", "prompt_pos", "prompt_mask", "prompt_len",
                 "slot_mask", "slot_ids")


def _prefill_feeds(R: int, S: int):
    """The feeds of a prefill that carries ``R`` sequences of up to ``S``
    rows, each naming its slot, in the order of ``PREFILL_FEEDS``: those of
    ``models/gpt.py:build_gpt_prefill`` with ``R`` rows (``slot_mask`` 1
    on the rows in use), and ``slot_ids`` [R, 1] int64."""
    shapes = ([R, S], [R, S], [R, S], [R, 1], [R, 1], [R, 1])
    types = ("int64", "int64", "float32", "int64", "float32", "int64")
    return [layers.data(n, shape=shape, dtype=dt, append_batch_size=False)
            for n, shape, dt in zip(PREFILL_FEEDS, shapes, types)]


def _commit_prefill(tok, pos, active, slots, first_tok, plen, smask):
    """Each row in use commits its slot's first token and position and
    opens its decode gate."""
    layers.slot_assign(tok, slots, first_tok, smask)
    layers.slot_assign(pos, slots, plen, smask)
    layers.slot_assign(
        active, slots,
        layers.fill_constant([slots.shape[0], 1], "float32", 1.0), smask)


def _commit_decode(tok, pos, active, next_tok, max_seq: int):
    """The slots whose gate is open take the sampled token and move on one
    position (never past the cache)."""
    B = tok.shape[0]
    one = layers.fill_constant([B, 1], "int64", 1)
    act_i64 = layers.cast(active, "int64")
    inv = layers.elementwise_sub(one, act_i64)
    layers.assign(_merge_state(next_tok, tok, act_i64, inv), output=tok)
    new_pos = layers.elementwise_min(
        layers.elementwise_add(pos, one),
        layers.fill_constant([B, 1], "int64", max_seq))
    layers.assign(_merge_state(new_pos, pos, act_i64, inv), output=pos)


def _build_prefill(cfg, B, R, S, max_seq, sample, startup):
    """The full-sequence phase for one prompt bucket. A dispatch carries
    ``R`` <= ``B`` sequences, each with the slot it is for, and costs
    ``R x S`` tokens whichever they are (:func:`_prefill_feeds`)."""
    main = Program()
    with program_guard(main, startup):
        ids, pos_ids, pmask, plen, smask, slots = _prefill_feeds(R, S)
        tok, pos, active, caches, sv, _ = _state_vars(
            main.global_block, cfg, B, max_seq)
        bias = layers.unsqueeze(
            layers.scale(pmask, scale=10000.0, bias=-10000.0), [1, 2])
        zero_pos = layers.fill_constant([R, 1], "int64", 0)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v, window):
            for cache, new in zip(caches[i], (k, v)):
                layers.kv_cache_append(cache, new, zero_pos, slot_mask=smask,
                                       slots=slots)
            return layers.fused_multihead_attention(
                q, k, v, bias_qk=bias, causal=True, scale=scale,
                is_test=True, window=window if window < S else 0)

        real = layers.elementwise_mul(pmask, smask, axis=0)
        h, stats = _stack_layers(_embed(ids, cfg), cfg, pos_ids, real,
                                 attend)
        one = layers.fill_constant([R, 1], "int64", 1)
        last_h = layers.sequence_gather(h, layers.elementwise_sub(plen, one))
        logits = _logits(last_h, cfg, main.global_block.var(f"{_P}_word_emb"),
                         cfg.logit_scale)
        first_tok = layers.sample_token(logits, **sample)
        _commit_prefill(tok, pos, active, slots, first_tok, plen, smask)
    return {"main": main, "first_token": first_tok, "state_vars": sv,
            "last_logits": logits, "expert_stats": stats, "rows": R,
            "feeds": PREFILL_FEEDS}


def _build_decode(cfg, B, max_seq, page_size, sample):
    """The per-token phase: no feeds, everything is persistable state (see
    ``models/gpt.py:build_gpt_decode``)."""
    main = Program()
    with program_guard(main, Program()):
        tok, pos, active, caches, sv, kinds = _state_vars(
            main.global_block, cfg, B, max_seq)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(i, q, k, v, window):
            ck, cv = caches[i]
            return layers.fused_decode_attention(
                q, k, v, ck, cv, pos, scale=scale, page_size=page_size,
                slot_mask=active, window=window)

        x = layers.unsqueeze(_embed(tok, cfg), [1])
        h, stats = _stack_layers(x, cfg, pos, active, attend)
        logits = _logits(layers.reshape(h, [0, cfg.hidden_size]), cfg,
                         main.global_block.var(f"{_P}_word_emb"),
                         cfg.logit_scale)
        next_tok = layers.sample_token(logits, **sample)
        _commit_decode(tok, pos, active, next_tok, max_seq)
    return {"main": main, "next_token": next_tok, "state_vars": sv,
            "logits": logits, "expert_stats": stats,
            "cache_kinds": kinds,
            "cache_vars": [(k.name, v.name) for k, v in caches],
            "active_var": active.name}


def build_cohere_moe_generative(cfg: CohereMoeConfig = None,
                                batch_slots: int = 4, max_seq: int = 64,
                                page_size: int = 8, prompt_buckets=(16,),
                                strategy: str = "greedy",
                                temperature: float = 1.0, top_k: int = 0,
                                prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs, as ``build_gpt_generative``
    returns it: one prefill program per prompt bucket and one decode
    program over shared weights, one startup program, the state-var table
    and the geometry. A prefill dispatch carries ``prefill_rows``
    sequences (default: one per slot), each naming its slot, so a refill
    of two slots does not pay for all of them. No chunk or verify program:
    a prompt has to fit a bucket, and a bucket a sliding layer's cache."""
    cfg = cfg or CohereMoeConfig.tiny()
    prompt_buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
    if not prompt_buckets:
        raise ValueError("need at least one prompt bucket")
    if max_seq % page_size:
        raise ValueError(f"max_seq {max_seq} must be a whole number of "
                         f"pages of page_size {page_size}")
    rows = min(cfg.cache_rows(i, max_seq) for i in range(cfg.num_layers))
    if prompt_buckets[-1] > rows or rows % page_size:
        raise ValueError(
            f"prompt bucket {prompt_buckets[-1]} against caches of {rows} "
            f"rows in pages of {page_size}: prefill writes a whole bucket "
            f"into every layer's cache at row 0, so a prompt past a "
            f"sliding layer's window cannot be admitted yet")
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


def _generative(cfg, startup, prefill, decode, batch_slots, max_seq,
                page_size, strategy):
    """The dict ``serving.GenerativeEngine`` takes, from a builder's
    programs (no chunk or verify program: ``spec_k`` 0)."""
    return {"config": cfg, "startup": startup, "prefill": prefill,
            "decode": decode, "state_vars": decode["state_vars"],
            "cache_vars": decode["cache_vars"],
            "cache_kinds": decode["cache_kinds"],
            "active_var": decode["active_var"],
            "batch_slots": batch_slots, "max_seq": max_seq,
            "page_size": page_size, "prompt_buckets": tuple(sorted(prefill)),
            "spec_k": 0, "strategy": strategy}
