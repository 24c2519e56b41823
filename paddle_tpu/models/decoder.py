"""What a decoder builder for ``serving.GenerativeEngine`` is made of, once.

A builder (``models/gpt.py``, ``cohere_moe.py``, ``qwen3_next.py``,
``glm4_moe_lite.py``, ``sdar_moe.py``, ``granite_moe_hybrid.py``,
``mimo_v2_flash.py``, ``xing4.py``) writes its
configuration, its block, its state table and the cache handles of its two
phases; the rest is here:

* the parts of a block: projections, the routed feed-forward, the RMS norm,
  the embedding and the head's matmul;
* the state table's maker, the prefill's feeds and the two commits;
* the handles of a layer with a K/V cache pair (:func:`bulk_attend`,
  :func:`step_attend`);
* a latent-attention layer, its state and its two handles
  (:func:`latent_attention`, :func:`latent_state`,
  :func:`latent_prefill_handle`, :func:`latent_decode_handle`), which
  ``glm4_moe_lite.py`` and ``xing4.py`` share;
* the two phases as functions of what differs (:class:`Parts`,
  :func:`prefill_phase`, :func:`decode_phase`) and :func:`build_generative`
  (:func:`build_from_parts` over those two phases): validation, the shared
  startup program, a prefill net a bucket, the decode net, and the dict the
  engine takes (:func:`generative`; docs/SERVING.md "What a builder hands
  the engine").

``cfg`` below is any builder's configuration. What a helper reads of it is
``initializer_range`` and ``dtype``; :func:`norm` ``rms_norm_eps``; and the
feed-forward ``hidden_size``, ``intermediate_size`` (an expert's width),
``num_experts``, ``experts_held``, ``expert_offset``, ``top_k``,
``num_shared_experts``, ``score_fn`` and, where it has them,
``select_bias``, ``route_scale`` and ``shared_intermediate_size`` (the
shared experts' width in all, where it is not ``num_shared_experts`` routed
widths). The phases read ``hidden_size``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

from .. import layers
from ..framework import Program, program_guard
from ..initializer import Constant, TruncatedNormal, Uniform
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["PREFILL_FEEDS", "Mix", "Parts", "attr", "build_from_parts",
           "build_generative",
           "bulk_attend", "check_pages", "commit_decode", "commit_prefill",
           "counted", "decode_net", "decode_phase", "embed",
           "expert_weights", "f32_param", "ffn", "gated_mlp", "generative",
           "latent_attention", "latent_decode_handle",
           "latent_prefill_handle", "latent_softmax_scale", "latent_state",
           "logits", "merge_state", "norm", "prefill_feeds", "prefill_phase",
           "prefill_rows", "proj", "proj_out", "split_heads", "state_table",
           "step_attend", "untied_head"]


# -- the parts of a block ----------------------------------------------------
def attr(name: str, cfg):
    return ParamAttr(name=name,
                     initializer=TruncatedNormal(0.0, cfg.initializer_range))


def f32_param(name: str, shape, init):
    return LayerHelper("decoder").create_parameter(
        ParamAttr(name=name, initializer=init), list(shape), "float32")


def norm(x, name: str, cfg, dim: int, zero_centered=True):
    """RMS norm over ``x``'s last axis (of size ``dim``) with a scale
    stored around zero (``1 + w``), or a plain one."""
    init = Constant(0.0 if zero_centered else 1.0)
    return layers.rms_norm(x, f32_param(f"{name}_scale", [dim], init),
                           epsilon=cfg.rms_norm_eps,
                           zero_centered=zero_centered)


def proj(x, size: int, name: str, cfg, act=None):
    return layers.fc(x, size, num_flatten_dims=2, act=act, bias_attr=False,
                     param_attr=attr(f"{name}_w", cfg))


def proj_out(x, size: int, name: str, cfg):
    """A projection back onto the residual stream: the f32 accumulator is
    kept, where ``fc`` would round it to the operands' type on its way to
    an f32 sum (every rounding upstream of a router moves its k-th place)."""
    w = LayerHelper("decoder").create_parameter(
        attr(f"{name}_w", cfg), [x.shape[-1], size], cfg.dtype)
    return layers.matmul(x, w, out_dtype="float32")


def split_heads(t, seq_len: int, heads: int, head_dim: int):
    """[B, S, heads * D] -> [B, heads, S, D]."""
    t = layers.reshape(t, [0, seq_len, heads, head_dim])
    return layers.transpose(t, [0, 2, 1, 3])


def expert_weights(name: str, cfg):
    """Router over all experts; gate, up and down of the held ones,
    stacked."""
    helper = LayerHelper("decoder")
    H, F, Eh = cfg.hidden_size, cfg.intermediate_size, cfg.experts_held
    mk = lambda n, shape: helper.create_parameter(
        attr(f"{name}_{n}_w", cfg), shape, cfg.dtype)
    return (mk("router", [H, cfg.num_experts]), mk("gate", [Eh, H, F]),
            mk("up", [Eh, H, F]), mk("down", [Eh, F, H]))


def gated_mlp(hb, width: int, name: str, cfg):
    """``(silu(hb Wg) * (hb Wu)) Wd`` of ``width`` back onto the residual
    stream (f32): a dense feed-forward, or shared experts side by side."""
    gate = proj(hb, width, f"{name}_gate", cfg, act="silu")
    up = proj(hb, width, f"{name}_up", cfg)
    return proj_out(layers.elementwise_mul(gate, up), cfg.hidden_size,
                    f"{name}_down", cfg)


def ffn(h, hb, p: str, cfg, real=None, join: str = "mean"):
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
        bias = LayerHelper("decoder").create_parameter(
            ParamAttr(name=f"{p}_router_bias",
                      initializer=Uniform(-0.1, 0.1)),
            [cfg.num_experts], "float32")
    routed, stats = layers.moe_experts(
        h, *expert_weights(p, cfg), num_experts=cfg.num_experts,
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
    shared = gated_mlp(hb, width, f"{p}_shared", cfg)
    if join != "sum":
        shared = layers.scale(shared, scale=1.0 / ns)
    if join == "gated":
        shared = layers.elementwise_mul(shared, layers.sigmoid(
            proj_out(hb, 1, f"{p}_shared_mix", cfg)))
    return routed, shared, stats


def embed(ids, cfg, name: str):
    emb = layers.embedding(ids, (cfg.vocab_size, cfg.hidden_size),
                           dtype=cfg.dtype, param_attr=attr(name, cfg))
    return layers.cast(emb, "float32")


def logits(h2d, cfg, weight, logit_scale: float = 1.0):
    """[B, H] f32 rows -> f32 logits over the held vocabulary through
    ``weight`` [V, H] (bf16 operands, the f32 accumulator kept: a logit
    rounded to bf16 moves by more than the gap between near-best tokens)."""
    out = layers.matmul(layers.cast(h2d, cfg.dtype), weight,
                        transpose_y=True, out_dtype="float32")
    if logit_scale != 1.0:
        out = layers.scale(out, scale=float(logit_scale))
    return out


def untied_head(h2d, cfg, name: str):
    """Logits through a head of the model's own, ``name`` [V, H]."""
    w = LayerHelper("decoder").create_parameter(
        attr(name, cfg), [cfg.vocab_size, cfg.hidden_size], cfg.dtype)
    return logits(h2d, cfg, w)


# -- state, feeds and commits --------------------------------------------------
def state_table(block, prefix: str, batch_slots: int, tokens: int = 1):
    """``(mk, sv, tok, pos, active)``: ``mk(name, shape, dtype)`` makes a
    persistable state var and enters it in ``sv`` (name -> (shape,
    dtype)); the current token (``tokens`` of them where a step carries a
    block), position and decode gate per slot are in it already.
    Persistable: the executor threads them step to step, and the liveness
    pass proves them donatable (each is read and written by ops that never
    observe a pre-write value after the write). The gate ``active`` [B, 1]
    float32 is 1 while a slot is mid-stream (set in-program when a prefill
    commits the slot, zeroed host-side on retire and reset): the decode
    program gates its cache writes and state merges on it, so retired slots
    neither advance nor write while their neighbours decode."""
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


PREFILL_FEEDS = ("prompt_ids", "prompt_pos", "prompt_mask", "prompt_len",
                 "slot_mask", "slot_ids")


def prefill_feeds(R: int, S: int):
    """The feeds of a prefill that carries ``R`` sequences of up to ``S``
    rows, each naming its slot, in the order of ``PREFILL_FEEDS``:

    * ``prompt_ids``  [R, S] int64: padded prompt tokens;
    * ``prompt_pos``  [R, S] int64: position ids (0..S-1);
    * ``prompt_mask`` [R, S] float32: 1 on real tokens, 0 on pads;
    * ``prompt_len``  [R, 1] int64: real prompt length per row;
    * ``slot_mask``   [R, 1] float32: 1 on the rows in use; a row whose
      mask is 0 writes nothing, whatever its ``slot_ids``;
    * ``slot_ids``    [R, 1] int64: the slot each row (re)fills: its state
      goes to that slot's rows and its first token, position and decode
      gate to that slot's. Slots no row names pass through untouched."""
    shapes = ([R, S], [R, S], [R, S], [R, 1], [R, 1], [R, 1])
    types = ("int64", "int64", "float32", "int64", "float32", "int64")
    return [layers.data(n, shape=shape, dtype=dt, append_batch_size=False)
            for n, shape, dt in zip(PREFILL_FEEDS, shapes, types)]


def check_pages(max_seq: int, page_size: int) -> None:
    if max_seq % page_size:
        raise ValueError(f"max_seq {max_seq} must be a whole number of "
                         f"pages of page_size {page_size}")


def prefill_rows(rows, batch_slots: int, default: int) -> int:
    """Sequences a prefill dispatch carries: ``rows``, or the builder's
    ``default`` where the caller names none; 1 to ``batch_slots``."""
    rows = int(rows or default)
    if not 1 <= rows <= batch_slots:
        raise ValueError(f"prefill_rows {rows} for {batch_slots} slots: a "
                         f"dispatch carries 1 to {batch_slots} prefill rows")
    return rows


def merge_state(new, old, mask_i64, inv_mask_i64):
    """masked select: new where the slot mask is set, old elsewhere; the
    reads of ``old`` precede the caller's write-back, keeping the state
    var donation-safe."""
    return layers.elementwise_add(layers.elementwise_mul(new, mask_i64),
                                  layers.elementwise_mul(old, inv_mask_i64))


def commit_prefill(tok, pos, active, slots, first_tok, plen, smask):
    """Each row in use commits its slot's first token and position and
    opens its decode gate."""
    layers.slot_assign(tok, slots, first_tok, smask)
    layers.slot_assign(pos, slots, plen, smask)
    layers.slot_assign(
        active, slots,
        layers.fill_constant([slots.shape[0], 1], "float32", 1.0), smask)


def commit_decode(tok, pos, active, next_tok, max_seq: int):
    """The slots whose gate is open take the sampled token and move on one
    position (never past the cache: a position would otherwise saturate at
    ``max_seq`` overwriting the last cache row; with the gate it freezes)."""
    B = tok.shape[0]
    one = layers.fill_constant([B, 1], "int64", 1)
    act_i64 = layers.cast(active, "int64")
    inv = layers.elementwise_sub(one, act_i64)
    layers.assign(merge_state(next_tok, tok, act_i64, inv), output=tok)
    new_pos = layers.elementwise_min(
        layers.elementwise_add(pos, one),
        layers.fill_constant([B, 1], "int64", max_seq))
    layers.assign(merge_state(new_pos, pos, act_i64, inv), output=pos)


# -- the handles of a layer with a K/V cache pair ------------------------------
@dataclasses.dataclass
class Mix:
    """A phase's handle for a model whose layers are of two kinds."""
    attend: object
    recur: object


def _as_wide_as(t, cache):
    """``t`` [.., D] with zeros after its last axis up to the cache's row
    width: a key cache may keep its rows in whole lane tiles (192 numbers
    in 256), and a zero adds nothing to a query's product with a key."""
    extra = cache.shape[-1] - t.shape[-1]
    if not extra:
        return t
    return layers.pad(t, [0, 0] * (len(t.shape) - 1) + [0, extra])


def bulk_attend(caches, pmask, smask, slots, scale: float, plen=None,
                folded=None):
    """A prefill's handle ``attend(i, q, k, v, window=0, sink=None)``: each
    row's whole prompt ``k``/``v`` ([R, kv_heads, S, D]; the values may be
    another width) goes to layer ``i``'s cache pair in the slot the row
    names (slots that no row in use names keep their pages), and the
    context is full-sequence causal attention under the key-padding bias of
    ``pmask``, with the layer's ``sink`` [heads] where it has one. A bucket
    that fits the cache's rows is written at row 0. A window layer's cache
    is a ring of ``window`` rows: a bucket longer than that is folded into
    it by each sequence's own length ``plen`` [R, 1]
    (``layers.kv_cache_fold``), so that the decode step's wrap goes on
    from it, and the attention runs over the whole bucket under the window
    mask. ``folded``: a list that takes each fold's statistics."""
    # additive key-padding bias [R,1,1,S]: (mask-1)*10000, bert idiom
    bias = layers.unsqueeze(
        layers.scale(pmask, scale=10000.0, bias=-10000.0), [1, 2])
    zero_pos = layers.fill_constant([pmask.shape[0], 1], "int64", 0)
    S = pmask.shape[1]

    def attend(i, q, k, v, window=0, sink=None):
        for cache, new in zip(caches[i], (k, v)):
            new = _as_wide_as(new, cache)
            if S <= cache.shape[2]:
                layers.kv_cache_append(cache, new, zero_pos, slot_mask=smask,
                                       slots=slots)
                continue
            if not window or plen is None:
                raise ValueError(
                    f"a bucket of {S} rows for layer {i}'s cache of "
                    f"{cache.shape[2]}: only a window layer's ring takes a "
                    f"longer prompt, by its length")
            _, stats = layers.kv_cache_fold(cache, new, plen,
                                            slot_mask=smask, slots=slots)
            if folded is not None:
                folded.append(stats)
        return layers.fused_multihead_attention(
            q, k, v, bias_qk=bias, causal=True, scale=scale, is_test=True,
            window=window if window < S else 0, sink=sink)

    return attend


def step_attend(caches, at, mask, scale: float, page_size: int):
    """A step's handle ``attend(i, q, k, v, window=0, sink=None)``: append
    and attend in ONE op, the caches' only read and write site, which is
    what keeps them donation-provable; the rows go in at ``at`` [B, 1] and
    ``mask`` [B, 1] keeps every other slot's pages bit-untouched. Where the
    key cache's rows are wider than a key, query and key ride zero-padded
    (``scale`` is the model's, not the padded width's)."""
    def attend(i, q, k, v, window=0, sink=None):
        ck, cv = caches[i]
        return layers.fused_decode_attention(
            _as_wide_as(q, ck), _as_wide_as(k, ck), v, ck, cv, at,
            scale=scale, page_size=page_size, slot_mask=mask, window=window,
            sink=sink)

    return attend


# -- a latent-attention layer ---------------------------------------------------
def latent_softmax_scale(cfg):
    """The softmax scale of ``cfg``'s latent attention where it is not the
    op's default ``(dn + dr)^-1/2``: under YaRN positions
    (``cfg.rope_scaling``) that times ``m(mscale_all_dim)^2``. None: the
    default."""
    yarn = getattr(cfg, "rope_scaling", None)
    if not yarn or not yarn.get("mscale_all_dim"):
        return None
    from ..ops.moe import yarn_softmax_scale

    return yarn_softmax_scale(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                              float(yarn["factor"]),
                              float(yarn["mscale_all_dim"]))


def latent_attention(hb, p: str, S: int, cfg, positions, attend, i: int):
    """Multi-head latent attention of layer ``i`` on the normed rows ``hb``
    [B, S, H] (``cfg.dtype``), parameters under ``p``: the query through
    its low-rank bottleneck and norm, a head's ``[q_nope | q_rope]``;
    ``[c_raw | k_r] = hb W_kva``, ``c = N(c_raw)``; rotary positions
    (``cfg.rope_theta``; YaRN's where ``cfg.rope_scaling`` says so) on
    ``q_rope`` and on the one rotary key; ``attend(i, q, c, k_rope,
    w_kvb)`` appends the rows to the layer's latent cache and attends;
    ``o_proj`` over the joined heads. Returns the f32 rows for the
    residual path and the attention op's statistics. What it reads of
    ``cfg``: ``num_heads``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``."""
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc = cfg.kv_lora_rank
    plain = functools.partial(norm, cfg=cfg, zero_centered=False)
    cq = plain(proj_out(hb, cfg.q_lora_rank, f"{p}_q_a", cfg),
               f"{p}_q_a_norm", dim=cfg.q_lora_rank)
    q = layers.reshape(
        proj(layers.cast(cq, cfg.dtype), nh * (dn + dr), f"{p}_q_b", cfg),
        [0, S, nh, dn + dr])
    q_nope, q_rope = layers.split(layers.transpose(q, [0, 2, 1, 3]),
                                  [dn, dr], dim=3)
    rot = lambda t: layers.rotary_embedding(
        t, positions, theta=cfg.rope_theta,
        yarn=getattr(cfg, "rope_scaling", None))
    q = layers.concat([q_nope, rot(q_rope)], axis=3)      # [B, nh, S, dn+dr]
    c_raw, k_r = layers.split(proj_out(hb, dc + dr, f"{p}_kv_a", cfg),
                              [dc, dr], dim=2)
    c = layers.cast(plain(c_raw, f"{p}_kv_a_norm", dim=dc), cfg.dtype)
    k_rope = layers.squeeze(
        rot(layers.unsqueeze(layers.cast(k_r, cfg.dtype), [1])), [1])
    w_kvb = LayerHelper("decoder").create_parameter(
        attr(f"{p}_kv_b_w", cfg), [dc, nh * (dn + cfg.v_head_dim)],
        cfg.dtype)
    ctx, stats = attend(i, q, c, k_rope, w_kvb)          # [B, nh, S, dv]
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, nh * cfg.v_head_dim])
    return proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg), stats


def latent_state(block, cfg, prefix: str, batch_slots: int, max_seq: int):
    """A builder's ``Parts.state`` for latent-attention layers alone:
    current token, position and decode gate per slot, and each layer's
    latent cache ``[slots, 1, max_seq, W]`` in ``cfg.dtype``, kind
    ``latent``: a row is ``[c (kv_lora_rank) | k_rope (qk_rope_head_dim) |
    0]``, ``W`` whole lane tiles (``kernels.latent_row_width``)."""
    from ..kernels.latent_attention import latent_row_width

    mk, sv, tok, pos, active = state_table(block, prefix, batch_slots)
    width = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    caches = [(mk(f"{prefix}_lat_{i}", (batch_slots, 1, max_seq, width),
                  cfg.dtype),) for i in range(cfg.num_layers)]
    return (tok, pos, active, caches, sv,
            {c.name: "latent" for c, in caches})


def latent_prefill_handle(cfg, caches, pmask, plen, smask, slots, page_size):
    """A layer writes the bucket's latent rows at row 0 of the slot's cache
    and attends over keys and values expanded from them."""
    def attend(i, q, c, k_rope, w_kvb):
        return layers.latent_attention(
            q, c, k_rope, w_kvb, *caches[i], plen, cfg.qk_nope_head_dim,
            mode="prefill", page_size=page_size, slot_mask=smask,
            slots=slots, scale=latent_softmax_scale(cfg))
    return attend


def latent_decode_handle(cfg, caches, pos, active, page_size):
    def attend(i, q, c, k_rope, w_kvb):
        return layers.latent_attention(
            q, c, k_rope, w_kvb, *caches[i], pos, cfg.qk_nope_head_dim,
            page_size=page_size, slot_mask=active,
            scale=latent_softmax_scale(cfg))
    return attend


# -- the phases ----------------------------------------------------------------
@dataclasses.dataclass
class Parts:
    """What differs between decoders whose phases are the scaffold's: the
    configuration and the builder's functions of it."""

    cfg: object
    # (block, cfg, batch_slots, max_seq) -> (tok, pos, active, per-layer
    # state (a tuple of vars a layer), state_vars, cache_kinds)
    state: Callable
    embed: Callable            # (ids, cfg) -> f32 rows [..., H]
    # (x, cfg, positions, real, handle) -> (h, [(net key, stats var or None,
    # what counts it)]): the final norm's output and what the layers counted
    stack: Callable
    head: Callable             # ([B, H] f32 rows, cfg) -> f32 logits
    # (cfg, per-layer state, pmask, plen, smask, slots, page_size) -> handle
    prefill_handle: Callable
    # (cfg, per-layer state, pos, active, page_size) -> handle
    decode_handle: Callable


def counted(net: dict, stats) -> dict:
    """Enters what a phase's layers counted in its net: each stack of
    statistics under its key, and ``counted``, the pairs (variable, what
    counts it: ``count(phase, fetched array, sums)``) the engine fetches
    beside the tokens and hands over."""
    stats = [s for s in stats if s[1] is not None]
    net.update({key: var for key, var, _ in stats})
    net["counted"] = [(var, count) for _, var, count in stats]
    return net


def prefill_phase(parts: Parts, sample: dict, B, R, S, max_seq, page_size,
                  startup):
    """The full-sequence phase for one prompt bucket. A dispatch carries
    ``R`` <= ``B`` sequences, each with the slot it is for, and costs
    ``R x S`` tokens whichever they are (:func:`prefill_feeds`): every
    layer's state for the whole prompt goes to the slot, the first
    generated token is sampled from the last real position, and the slot's
    generation state is committed."""
    cfg, main = parts.cfg, Program()
    with program_guard(main, startup):
        ids, pos_ids, pmask, plen, smask, slots = prefill_feeds(R, S)
        tok, pos, active, state, sv, _ = parts.state(main.global_block, cfg,
                                                     B, max_seq)
        handle = parts.prefill_handle(cfg, state, pmask, plen, smask, slots,
                                      page_size)
        real = layers.elementwise_mul(pmask, smask, axis=0)
        h, stats = parts.stack(parts.embed(ids, cfg), cfg, pos_ids, real,
                               handle)
        one = layers.fill_constant([R, 1], "int64", 1)
        last_h = layers.sequence_gather(h, layers.elementwise_sub(plen, one))
        last_logits = parts.head(last_h, cfg)
        first_tok = layers.sample_token(last_logits, **sample)
        commit_prefill(tok, pos, active, slots, first_tok, plen, smask)
    return counted({"main": main, "first_token": first_tok, "state_vars": sv,
                    "last_logits": last_logits, "rows": R,
                    "feeds": PREFILL_FEEDS}, stats)


def decode_net(main, state, sv, kinds, active, **outs) -> dict:
    """A decode net: its outputs and how the engine finds the state."""
    return {"main": main, "state_vars": sv, "cache_kinds": kinds,
            "cache_vars": [tuple(v.name for v in s) for s in state],
            "active_var": active.name, **outs}


def decode_phase(parts: Parts, sample: dict, B, max_seq, page_size, startup):
    """The per-token phase: no feeds, everything (current token, position,
    caches) is persistable state, so ``run_chained`` scans whole decode
    chunks with the caches donated through the carry, and sampling happens
    in-program. Sequences at different positions batch together: the
    position is data, not shape."""
    cfg, main = parts.cfg, Program()
    with program_guard(main, startup):
        tok, pos, active, state, sv, kinds = parts.state(
            main.global_block, cfg, B, max_seq)
        handle = parts.decode_handle(cfg, state, pos, active, page_size)
        # lookup_table squeezes the trailing ids dim ([B,1] -> [B,H]);
        # restore the length-1 sequence axis the layer stack expects
        x = layers.unsqueeze(parts.embed(tok, cfg), [1])
        h, stats = parts.stack(x, cfg, pos, active, handle)
        step_logits = parts.head(layers.reshape(h, [0, cfg.hidden_size]), cfg)
        next_tok = layers.sample_token(step_logits, **sample)
        commit_decode(tok, pos, active, next_tok, max_seq)
    return counted(decode_net(main, state, sv, kinds, active,
                              next_token=next_tok, logits=step_logits), stats)


def build_generative(cfg, prefill, decode, batch_slots: int, max_seq: int,
                     page_size: int, prompt_buckets, rows,
                     strategy: str = "greedy"):
    """What ``serving.GenerativeEngine`` needs: one prefill program per
    prompt bucket and one decode program over shared weights, one startup
    program (parameters only: generation state is reset host-side by the
    engine), the state-var table and the geometry. ``prefill(B, R, S,
    max_seq, page_size, startup)`` and ``decode(B, max_seq, page_size,
    startup)`` build a phase's net (:func:`prefill_phase` and
    :func:`decode_phase` over a builder's :class:`Parts` and its sampling
    arguments, or the builder's own). A prefill dispatch carries ``rows``
    sequences (default: one per slot), each naming its slot, so a refill of
    two slots does not pay for all of them. No chunk or verify program: a
    prompt has to fit a bucket, and a bucket the cache."""
    prompt_buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
    if not prompt_buckets or prompt_buckets[-1] > max_seq:
        raise ValueError(f"prompt buckets {prompt_buckets} for a cache of "
                         f"{max_seq} rows")
    check_pages(max_seq, page_size)
    rows = prefill_rows(rows, batch_slots, batch_slots)
    startup = Program()
    nets = {S: prefill(batch_slots, rows, S, max_seq, page_size, startup)
            for S in prompt_buckets}
    return generative(cfg, startup, nets,
                      decode(batch_slots, max_seq, page_size, startup),
                      batch_slots, max_seq, page_size, strategy)


def build_from_parts(parts: Parts, batch_slots: int, max_seq: int,
                     page_size: int, prompt_buckets, rows, strategy: str,
                     temperature: float, top_k: int):
    """:func:`build_generative` with the scaffold's two phases over a
    builder's ``parts``, sampling as the arguments say."""
    sample = dict(strategy=strategy, temperature=temperature, top_k=top_k)
    return build_generative(
        parts.cfg, functools.partial(prefill_phase, parts, sample),
        functools.partial(decode_phase, parts, sample), batch_slots, max_seq,
        page_size, prompt_buckets, rows, strategy)


def generative(cfg, startup, prefill, decode, batch_slots, max_seq,
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
