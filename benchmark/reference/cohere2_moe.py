"""Plain reference for the ``cohere2_moe`` decoder (Command A+; source and
assumptions in ``configs/command-a-plus-ep8-serve.json``), as the share of
it that one chip of an expert-parallel deployment holds. One full forward
pass over a whole sequence in f32 with every product at HIGHEST: no cache,
no kernels, no batching, nothing of the program imported. Parameter names
are the scope's (``cmoe_*``).

For a row ``x`` (``h = LN(x)``, mean-subtracting, scale and no bias):

    y    = x + Attn(h) + FFN(h)
    Attn : q = h Wq, k = h Wk, v = h Wv, no biases; query head n reads
           key/value head n // group; scale head_dim^-1/2. Sliding layers:
           rotary positions on q and k (interleaved pairs, all dims) and
           key j visible to query i iff 0 <= i - j < window. Full layers:
           no positional signal, causal mask.
    FFN  : s = sigmoid(h Wr); I = the top_k largest of s;
           w_e = s_e / sum_{j in I} s_j;
           sum_{e in I, e held} w_e E_e(h) + mean_t S_t(h)
           with E(h) = (silu(h Wg) * (h Wu)) Wd.
    Head : final norm, logit_scale x the tied embedding.

The share: the routed sum runs over the ``num_experts`` experts held from
``expert_offset`` of the ``num_experts_total`` the router scores; what the
absent experts would add is left out. The shared experts are stored side
by side: columns ``t*F..(t+1)*F`` of ``shared_gate_w`` / ``shared_up_w``
and the same rows of ``shared_down_w`` are shared expert ``t``.

Weights are stored in the configuration's storage type (bf16) and upcast
here a block at a time (one expert, one head group, one slab of the
vocabulary), so that a 1,024-row pass fits beside 9.5 GB of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import HIGHEST, layer_norm, rounder, seed_key

SLIDING = "sliding_attention"
P = "cmoe"
F32 = jnp.float32


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, and ``deployment``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "num_experts", "num_experts_per_tok",
            "num_shared_experts", "sliding_window", "rope_theta",
            "layer_norm_eps", "logit_scale", "initializer_range")
    m = {k: cfg[k] for k in keys}
    m["layer_types"] = list(cfg["layer_types"][:cfg["num_hidden_layers"]])
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["storage"] = cfg["storage_dtype"]
    return m


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype); kinds as ``common.make_weights``."""
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    Eh, E, ns = (cfg["num_experts"], cfg["num_experts_total"],
                 cfg["num_shared_experts"])
    n, st = f"normal:{cfg['initializer_range']}", cfg["storage"]
    spec = {f"{P}_word_emb": ((V, H), n, st),
            f"{P}_lnf_scale": ((H,), "ones", "float32")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}_l{i}"
        spec[f"{p}_ln_scale"] = ((H,), "ones", "float32")
        for name, shape in (
                ("q", (H, nh * hd)), ("k", (H, nkv * hd)),
                ("v", (H, nkv * hd)), ("out", (nh * hd, H)),
                ("router", (H, E)), ("gate", (Eh, H, F)), ("up", (Eh, H, F)),
                ("down", (Eh, F, H)), ("shared_gate", (H, ns * F)),
                ("shared_up", (H, ns * F)), ("shared_down", (ns * F, H))):
            spec[f"{p}_{name}_w"] = (shape, n, st)
    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, shape, kind, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
    return (z * float(kind.split(":", 1)[1])).astype(dtype)


def make_weights(spec: dict, seed: int):
    """Yields ``(name, array)`` a tensor at a time, each in its storage
    type, made on the device from the seed: all of them at once in f32
    would be twice the chip. Names are folded in by sorted position."""
    key = seed_key(seed)
    for i, name in enumerate(sorted(spec)):
        shape, kind, dtype = spec[name]
        yield name, _make(jax.random.fold_in(key, i), tuple(shape), kind,
                          dtype)


def rotary(x, pos, theta):
    """x [heads, T, D]: pair i = dims (2i, 2i+1) turns by
    pos * theta^(-2i/D)."""
    D = x.shape[-1]
    ang = pos[:, None].astype(F32) * theta ** (
        -jnp.arange(0, D, 2, dtype=F32) / D)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (D // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def route(h, wr, top_k):
    """scores, the top_k largest (lower index first among equals), and
    their weights normalised over the chosen ones; all f32, unrounded."""
    s = jax.nn.sigmoid(jnp.matmul(h, wr.astype(F32), precision=HIGHEST))
    vals, idx = jax.lax.top_k(s, top_k)
    return idx, vals / jnp.sum(vals, axis=-1, keepdims=True)


def routed_part(h, params, p, cfg, mm):
    """sum over the held experts (as many as the stacked weights hold,
    from ``expert_offset``) of w_e E_e(h), one expert at a time."""
    first = cfg["expert_offset"]
    idx, w = route(h, params[f"{p}_router_w"], cfg["num_experts_per_tok"])
    held = params[f"{p}_gate_w"].shape[0]

    def one(acc, e):
        g, u, d = (params[f"{p}_{n}_w"][e].astype(F32)
                   for n in ("gate", "up", "down"))
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)
        return acc + share[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return acc


def shared_part(h, params, p, cfg, mm):
    ns, F = cfg["num_shared_experts"], cfg["intermediate_size"]
    out = jnp.zeros_like(h)
    for t in range(ns):
        cols = slice(t * F, (t + 1) * F)
        g = params[f"{p}_shared_gate_w"][:, cols].astype(F32)
        u = params[f"{p}_shared_up_w"][:, cols].astype(F32)
        d = params[f"{p}_shared_down_w"][cols].astype(F32)
        out = out + mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)
    return out / ns


def attention(h, params, p, cfg, sliding, mm, rnd):
    T = h.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    G = nh // nkv
    pos = jnp.arange(T)
    heads = lambda t, n: t.reshape(T, n, hd).transpose(1, 0, 2)
    q = heads(mm(h, params[f"{p}_q_w"].astype(F32)), nh)
    k = heads(mm(h, params[f"{p}_k_w"].astype(F32)), nkv)
    v = heads(mm(h, params[f"{p}_v_w"].astype(F32)), nkv)
    d = pos[:, None] - pos[None, :]
    seen = d >= 0
    if sliding:
        q = rotary(q, pos, cfg["rope_theta"])
        k = rotary(k, pos, cfg["rope_theta"])
        seen = seen & (d < cfg["sliding_window"])

    def group(args):                       # the G query heads of one k/v head
        qg, kh, vh = args
        s = jnp.einsum("gqd,kd->gqk", rnd(qg), rnd(kh),
                       precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", rnd(a), rnd(vh), precision=HIGHEST)

    c = jax.lax.map(group, (q.reshape(nkv, G, T, hd), k, v))
    c = c.reshape(nh, T, hd).transpose(1, 0, 2).reshape(T, nh * hd)
    return mm(c, params[f"{p}_out_w"].astype(F32))


def layer(x, params, i, cfg, mm, rnd):
    p = f"{P}_l{i}"
    h = layer_norm(x, params[f"{p}_ln_scale"], 0.0, cfg["layer_norm_eps"])
    sliding = cfg["layer_types"][i] == SLIDING
    return (x + attention(h, params, p, cfg, sliding, mm, rnd)
            + routed_part(h, params, p, cfg, mm)
            + shared_part(h, params, p, cfg, mm))


def logits(params, ids, cfg, precision="f32", vocab_block=8192):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right. ``precision`` rounds every matmul operand but
    the router's (which the configuration states as f32)."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    x = params[f"{P}_word_emb"][ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, params, i, cfg, mm, rnd)
    x = layer_norm(x, params[f"{P}_lnf_scale"], 0.0, cfg["layer_norm_eps"])
    emb = params[f"{P}_word_emb"]
    V = emb.shape[0]
    vb = vocab_block if V % vocab_block == 0 else V
    slabs = jax.lax.map(lambda e: mm(x, e.astype(F32).T),
                        emb.reshape(V // vb, vb, -1))
    return cfg["logit_scale"] * slabs.transpose(1, 0, 2).reshape(-1, V)


def gaps_fn(cfg, control: str = ""):
    """As ``reference.gpt2.gaps_fn``: a jitted ``(params, ids[T], nxt[T])
    -> (served gaps[T], control's gaps[T])``: at row t, how far the
    reference's logit of ``nxt[t]`` (or of the control's own first choice)
    lies below the reference's best."""

    @jax.jit
    def fn(params, ids, nxt):
        ref = logits(params, ids, cfg)
        best = jnp.max(ref, axis=-1)
        below = lambda tok: best - jnp.take_along_axis(
            ref, tok[:, None], axis=-1)[:, 0]
        served = below(nxt)
        if not control:
            return served, served
        return served, below(jnp.argmax(logits(params, ids, cfg, control),
                                        axis=-1))

    return fn
