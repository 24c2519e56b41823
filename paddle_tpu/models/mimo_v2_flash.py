"""MiMo-V2-Flash decoder (``model_type`` ``mimo_v2_flash``) for generative
serving, as the share of the model that ONE chip of an expert-parallel
deployment holds.

Every layer, on the residual stream ``x`` (f32), with
``N(x) = x / sqrt(mean(x^2) + eps) * w``:

    h = x + Attn_t(N_in(x));   y = h + FFN_i(N_post(h))

    t = layer_pattern[i]: 0 a full layer, 1 a window layer. The two kinds
          have their own head counts, head widths and rotary base.
    Attn: q = h Wq [heads x qk]; k = h Wk [kv_t x qk]; v = h Wv [kv_t x
          v_dim], no biases; query head n reads key/value head n // group.
          Rotary positions on the first ``rotary_dim`` dims of a head of q
          and of k, as rotate-half pairs; ``v`` times ``value_scale``.
          Scores ``q . k / sqrt(qk)``, causal; a window layer's query sees
          the last ``sliding_window`` positions, itself included. A window
          layer (``swa_sink``) adds one learned scalar a head to its
          softmax as a column with no value (a full layer too, with
          ``full_sink``). ``o_proj`` over the joined heads' ``v_dim``.
    FFN:  layers with ``moe_layer_freq[i] == 0`` a dense gated
          feed-forward of ``dense_intermediate_size``; the others ``s =
          sigmoid(h2 Wr)``, the ``top_k`` experts with the largest ``s + b``
          (a stored bias that enters the choice and not the weights),
          weights ``s / sum s`` over the chosen, the held experts' part of
          the routed sum; no shared expert.
    Head: final norm, then the untied ``lm_head``.

What is held here is ONE chip's share: ``experts_held`` routed experts
from ``expert_offset``, attention and the dense layers whole, a slice of
the vocabulary; bf16 storage, bf16 matmul operands with f32 accumulation;
norms, router, softmax, sinks and the residual stream f32. The block is
written once (:func:`_block`); the two phases are ``models/decoder.py``'s.
The state table holds two kinds of K/V cache, and a pair's shape follows
its layer's kind: a ``full`` layer ``[slots, kv_heads, max_seq, .]``, a
``window`` layer ``[slots, swa_kv_heads, sliding_window, .]`` as a ring
that a prefill folds a longer prompt into and the decode step wraps. Keys
and values have their own widths; a key cache's rows are kept in
``key_cache_dim`` numbers (whole 128-lane tiles where a key is wider than
one: the runtime stores any other width with its rows in lanes, and a
kernel that reads rows as declared pays a conversion of the whole cache a
call). The multi-token prediction layers are not built.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

from .. import layers
from ..initializer import TruncatedNormal
from ..ops.moe import expert_counter
from . import decoder
from .decoder import ffn, gated_mlp, proj, proj_out, split_heads

__all__ = ["MimoV2FlashConfig", "build_mimo_v2_flash_generative",
           "count_fold_stats"]

_P = "mimo"                          # prefix of every parameter and state var
FULL, WINDOW = 0, 1                  # ``hybrid_layer_pattern``'s two values


@dataclasses.dataclass
class MimoV2FlashConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_layers: int = 48
    # full layers / window layers
    num_heads: int = 64
    num_kv_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_heads: int = 64
    swa_num_kv_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    sliding_window: int = 128
    value_scale: float = 0.707
    swa_sink: bool = True
    full_sink: bool = False
    layer_pattern: Optional[Tuple[int, ...]] = None  # None: 0, then 1x5, 0
    moe_layer_freq: Optional[Tuple[int, ...]] = None  # None: 0, then ones
    intermediate_size: int = 2048        # width of one routed expert
    dense_intermediate_size: int = 16384
    num_experts: int = 256
    top_k: int = 8
    num_shared_experts: int = 0
    experts_held: Optional[int] = None   # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    sink_init_range: float = 0.02
    dtype: str = "bfloat16"
    score_fn: str = "sigmoid"
    select_bias: bool = True             # ``noaux_tc``
    key_cache_dim: Optional[int] = None  # None: whole lane tiles past 128

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.layer_pattern is None:
            self.layer_pattern = tuple(
                FULL if i == 0 or i % 6 == 5 else WINDOW
                for i in range(self.num_layers))
        if self.moe_layer_freq is None:
            self.moe_layer_freq = tuple(
                int(i > 0) for i in range(self.num_layers))
        self.layer_pattern = tuple(int(t) for t in self.layer_pattern)
        self.moe_layer_freq = tuple(int(t) for t in self.moe_layer_freq)
        for what in (self.layer_pattern, self.moe_layer_freq):
            if len(what) != self.num_layers or set(what) - {0, 1}:
                raise ValueError(f"{what} for {self.num_layers} layers")
        for kind in (FULL, WINDOW):
            a = self.attention(kind)
            if a.heads % a.kv_heads:
                raise ValueError(f"{a.heads} query heads do not divide "
                                 f"over {a.kv_heads} key/value heads")

    @staticmethod
    def tiny(**over):
        """CI-sized: a dense full layer, two window layers and a full one
        with experts, 4 of 16 held; keys wider than values, the two kinds'
        head counts apart, a window of 8."""
        cfg = dict(vocab_size=128, hidden_size=64, num_layers=4,
                   num_heads=4, num_kv_heads=1, head_dim=24, v_head_dim=16,
                   swa_num_heads=4, swa_num_kv_heads=2, swa_head_dim=24,
                   swa_v_head_dim=16, sliding_window=8,
                   layer_pattern=(0, 1, 1, 0), moe_layer_freq=(0, 1, 1, 1),
                   intermediate_size=32, dense_intermediate_size=96,
                   num_experts=16, top_k=4, experts_held=4,
                   sink_init_range=1.0)
        cfg.update(over)
        return MimoV2FlashConfig(**cfg)

    def attention(self, kind: int) -> "_Attention":
        """The sizes of a layer of ``kind``'s attention."""
        if kind == WINDOW:
            return _Attention(self.swa_num_heads, self.swa_num_kv_heads,
                              self.swa_head_dim, self.swa_v_head_dim,
                              self.swa_rope_theta, self.sliding_window,
                              self.swa_sink)
        return _Attention(self.num_heads, self.num_kv_heads, self.head_dim,
                          self.v_head_dim, self.rope_theta, 0,
                          self.full_sink)

    def rotary_dim(self, head_dim: int) -> int:
        return int(self.partial_rotary_factor * head_dim)

    def cache_shapes(self, layer: int, batch_slots: int, max_seq: int):
        """The shapes of layer ``layer``'s key and value caches."""
        a = self.attention(self.layer_pattern[layer])
        rows = min(a.window, max_seq) if a.window else max_seq
        kd = self.key_cache_dim or (
            a.qk if a.qk <= 128 else -(-a.qk // 128) * 128)
        if kd < a.qk:
            raise ValueError(f"key_cache_dim {kd} under a key of {a.qk}")
        return ((batch_slots, a.kv_heads, rows, kd),
                (batch_slots, a.kv_heads, rows, a.v))


@dataclasses.dataclass(frozen=True)
class _Attention:
    heads: int
    kv_heads: int
    qk: int
    v: int
    theta: float
    window: int
    sink: bool


def _norm(x, name: str, cfg: MimoV2FlashConfig):
    return decoder.norm(x, name, cfg, cfg.hidden_size, zero_centered=False)


def _attention(hb, p: str, i: int, S: int, cfg: MimoV2FlashConfig, positions,
               attend):
    a = cfg.attention(cfg.layer_pattern[i])
    q = split_heads(proj(hb, a.heads * a.qk, f"{p}_q", cfg), S, a.heads,
                    a.qk)
    k = split_heads(proj(hb, a.kv_heads * a.qk, f"{p}_k", cfg), S,
                    a.kv_heads, a.qk)
    v = split_heads(proj(hb, a.kv_heads * a.v, f"{p}_v", cfg), S,
                    a.kv_heads, a.v)
    rot = lambda t: layers.rotary_embedding(
        t, positions, theta=a.theta, rotary_dim=cfg.rotary_dim(a.qk),
        pairing="half")
    v = layers.scale(v, scale=float(cfg.value_scale))
    sink = None
    if a.sink:
        sink = decoder.f32_param(
            f"{p}_sink", [a.heads],
            TruncatedNormal(0.0, cfg.sink_init_range))
    ctx = attend(i, rot(q), rot(k), v, a.window, sink)   # [B, heads, S, v]
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [0, S, a.heads * a.v])
    return proj_out(ctx, cfg.hidden_size, f"{p}_out", cfg)


def _block(x, i: int, cfg: MimoV2FlashConfig, positions, real, attend):
    """One layer on the residual stream ``x`` [B, S, H] (f32). ``real``
    [B, S] is 1 on the tokens of the sequences this dispatch serves.
    ``attend(i, q, k, v, window, sink)`` stores ``k``/``v`` in layer
    ``i``'s cache pair and returns the attended context [B, heads, S,
    v_dim]. Returns the new stream and the expert op's statistics (None on
    a dense layer)."""
    p = f"{_P}_l{i}"
    S = x.shape[1]
    hb = layers.cast(_norm(x, f"{p}_ln_in", cfg), cfg.dtype)
    x = layers.elementwise_add(
        x, _attention(hb, p, i, S, cfg, positions, attend))
    h = _norm(x, f"{p}_ln_post", cfg)
    hb = layers.cast(h, cfg.dtype)
    if not cfg.moe_layer_freq[i]:
        return layers.elementwise_add(x, gated_mlp(
            hb, cfg.dense_intermediate_size, f"{p}_mlp", cfg)), None
    routed, _, stats = ffn(h, hb, p, cfg, real)
    return layers.elementwise_add(x, routed), stats


def count_fold_stats(phase: str, stats, sums) -> None:
    """What a prefill's folds counted (``kv_cache_fold`` ``Stats``,
    [folds, 2]: prompt rows kept in a ring and rows the window had
    passed), onto the monitor."""
    from .. import monitor

    stats = np.asarray(stats).reshape(-1, 2).astype(np.int64)
    rows = monitor.counter(
        "serving_prefill_window_rows_total",
        "prompt rows a window layer's cache took of the prompts a prefill "
        "seated, a cache (K or V) and layer at a time: what=kept the rows "
        "its ring holds, what=dropped the rows the window had passed "
        "before the prompt ended")
    rows.labels(what="kept").inc(float(stats[:, 0].sum()))
    rows.labels(what="dropped").inc(float(stats[:, 1].sum()))


def _flash_counter(cfg: MimoV2FlashConfig, rows: int, bucket: int):
    """What counts a prefill dispatch's flash-forward blocks, by the kind
    of its layers: the (q-block, k-block) pairs the kernel fetched and
    scored and those the diagonal or a window let it pass over, in tiles of
    128 x 128 whatever height its q-blocks take, from the kernel's own walk
    (``kernels.flash_block_visits``) for ``rows`` sequences of ``bucket``
    positions."""
    from ..kernels import flash_block_visits

    per_call = {}
    for kind, name in ((FULL, "full"), (WINDOW, "window")):
        a = cfg.attention(kind)
        n = sum(1 for t in cfg.layer_pattern if t == kind)
        window = a.window if a.window < bucket else 0
        seen, grid = flash_block_visits(
            bucket, bucket, window=window, head_dim=a.qk, v_dim=a.v,
            itemsize=4 if cfg.dtype == "float32" else 2)
        per_call[name] = (n, rows * a.heads * seen,
                          rows * a.heads * (grid - seen))

    def count(phase: str, stats, sums) -> None:
        from .. import monitor

        blocks = monitor.counter(
            "flash_attention_blocks_total",
            "(q-block, k-block) pairs of the flash forward's grid over a "
            "prefill's sequences and heads, by the kind of the layer's "
            "cache: what=visited the pairs it fetched and scored, "
            "what=skipped those the diagonal or a window hid from a whole "
            "q-block")
        calls = monitor.counter(
            "flash_attention_calls_total",
            "calls of the flash forward in prefill dispatches, by the kind "
            "of the layer's cache")
        for name, (n, seen, passed) in per_call.items():
            blocks.labels(kind=name, what="visited").inc(float(n * seen))
            blocks.labels(kind=name, what="skipped").inc(float(n * passed))
            calls.labels(kind=name).inc(float(n))
        count_fold_stats(phase, stats, sums)

    return count


@dataclasses.dataclass
class _Handle:
    """A phase's cache handle and, in a prefill, what its folds counted."""
    attend: object
    folded: Optional[list] = None
    rows: int = 0
    bucket: int = 0


def _stack_layers(x, cfg: MimoV2FlashConfig, positions, real, handle):
    experts, moe = [], []
    for i in range(cfg.num_layers):
        x, s = _block(x, i, cfg, positions, real, handle.attend)
        if s is not None:
            experts.append(s)
            moe.append(i)
    h, stats = _norm(x, f"{_P}_lnf", cfg), []
    if experts:
        experts = layers.stack(experts, axis=0)
        stats.append(("expert_stats", experts, expert_counter(experts, moe)))
    if handle.folded is not None:
        # a prefill: its flash calls, and the rows its folds kept (a
        # bucket inside the window folds nothing and counts no row)
        folds = (layers.stack(handle.folded, axis=0) if handle.folded
                 else layers.fill_constant([1, 2], "int32", 0))
        stats.append(("fold_stats", folds,
                      _flash_counter(cfg, handle.rows, handle.bucket)))
    return h, stats


def _embed(ids, cfg: MimoV2FlashConfig):
    return decoder.embed(ids, cfg, f"{_P}_word_emb")


def _head(h2d, cfg: MimoV2FlashConfig):
    return decoder.untied_head(h2d, cfg, f"{_P}_lm_head")


def _state_vars(block, cfg: MimoV2FlashConfig, batch_slots: int,
                max_seq: int):
    """Current token, position and decode gate per slot
    (``decoder.state_table``), and one K/V cache pair per layer in
    ``cfg.dtype``, by the layer's kind (``cfg.cache_shapes``): ``full``
    ``[slots, kv_heads, max_seq, .]`` or ``window`` ``[slots,
    swa_kv_heads, sliding_window, .]``, keys and values each their own
    width."""
    mk, sv, tok, pos, active = decoder.state_table(block, _P, batch_slots)
    kinds, caches = {}, []
    for i in range(cfg.num_layers):
        pair = tuple(
            mk(f"{_P}_kv_{kv}_{i}", shape, cfg.dtype) for kv, shape in
            zip("kv", cfg.cache_shapes(i, batch_slots, max_seq)))
        caches.append(pair)
        kind = "window" if cfg.layer_pattern[i] == WINDOW else "full"
        kinds.update({c.name: kind for c in pair})
    return tok, pos, active, caches, sv, kinds


def _scaled(attend, cfg: MimoV2FlashConfig):
    """``attend`` handles by the kind of the layer: the softmax scale is
    the layer's own key width's (one handle where the two kinds' keys are
    as wide, as published)."""
    by_scale = {}
    for kind in (FULL, WINDOW):
        scale = 1.0 / math.sqrt(cfg.attention(kind).qk)
        if scale not in by_scale:
            by_scale[scale] = attend(scale)
    return lambda i, *a: by_scale[1.0 / math.sqrt(
        cfg.attention(cfg.layer_pattern[i]).qk)](i, *a)


def _prefill_handle(cfg, caches, pmask, plen, smask, slots, page_size):
    folded = []
    return _Handle(
        _scaled(lambda scale: decoder.bulk_attend(
            caches, pmask, smask, slots, scale, plen=plen, folded=folded),
            cfg),
        folded, pmask.shape[0], pmask.shape[1])


def _decode_handle(cfg, caches, pos, active, page_size):
    return _Handle(_scaled(lambda scale: decoder.step_attend(
        caches, pos, active, scale, page_size), cfg))


def build_mimo_v2_flash_generative(cfg: MimoV2FlashConfig = None,
                                   batch_slots: int = 4, max_seq: int = 64,
                                   page_size: int = 8, prompt_buckets=(16,),
                                   strategy: str = "greedy",
                                   temperature: float = 1.0, top_k: int = 0,
                                   prefill_rows: int = None):
    """What ``serving.GenerativeEngine`` needs
    (``decoder.build_generative``). ``prefill_rows``: the sequences a
    prefill dispatch carries, each naming its slot (default: one per
    slot). A bucket past the window is folded into the window layers'
    rings."""
    cfg = cfg or MimoV2FlashConfig.tiny()
    ring = min(cfg.sliding_window, max_seq)
    if ring % min(page_size, ring):
        raise ValueError(f"rings of {ring} rows in pages of {page_size}")
    parts = decoder.Parts(cfg, _state_vars, _embed, _stack_layers, _head,
                          _prefill_handle, _decode_handle)
    return decoder.build_from_parts(parts, batch_slots, max_seq, page_size,
                                    prompt_buckets, prefill_rows, strategy,
                                    temperature, top_k)
