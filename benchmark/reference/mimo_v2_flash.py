"""Plain reference for the ``mimo_v2_flash`` decoder (MiMo-V2-Flash; source
and assumptions in ``configs/mimo-v2-flash-ep16-serve.json``), as the share
of it that one chip of an expert-parallel deployment holds. One full
forward pass over a whole sequence in f32 with every product at HIGHEST:
every position against every earlier one under the layer's mask, no cache,
no ring, no kernels, no batching, nothing of the program imported.
Parameter names are the scope's (``mimo_*``).

``N(x) = x / sqrt(mean(x^2) + eps) * w``. Every layer: ``h = x +
Attn_t(N_in(x))``, ``y = h + FFN_i(N_post(h))``, ``t =
hybrid_layer_pattern[i]`` (0 full, 1 window). Head: final ``N``, then the
untied ``lm_head``.

    Attn: ``q = h Wq`` [heads x qk], ``k = h Wk`` [kv_t x qk], ``v = h Wv``
        [kv_t x vd], the counts and widths of the layer's kind
        (``num_attention_heads`` / ``num_key_value_heads`` / ``head_dim`` /
        ``v_head_dim``, or their ``swa_`` namesakes), no biases; query head
        n reads key/value head ``n // group``. Rotary on the first
        ``int(partial_rotary_factor x qk)`` dims of q and k as rotate-half
        pairs ``(j, j + rot/2)``, base ``rope_theta`` (full) or
        ``swa_rope_theta`` (window); ``v <- attention_value_scale x v``.
        ``s_ij = q_i . k_j / sqrt(qk)``, ``j <= i``; a window layer also
        ``i - j < sliding_window``. Where the kind has a sink
        (``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``)
        one scalar ``b_h`` a head is one more column of the softmax, with
        no value: ``p_ij = exp(s_ij - m) / (sum_j' exp(s_ij' - m) +
        exp(b_h - m))``. The heads' ``vd`` joined, ``W_o``.
    FFN: ``moe_layer_freq[i] == 0``: ``(silu(h Wg) * (h Wu)) Wd`` of
        ``intermediate_size``. Else ``s = sigmoid(h Wr)``; I = the top_k
        largest of ``s + b`` (``e_score_correction_bias``; ``n_group`` 1, so
        no group limit); ``w_e = s_e / (sum_{j in I} s_j + 1e-20)``
        (``norm_topk_prob``; ``routed_scaling_factor`` null: no scale);
        ``sum_{e in I, e held} w_e E_e(h)``, every expert the same gated
        form at ``moe_intermediate_size``; no shared expert.

Departures from the published model, each in the file's ``assumed``: the
multi-token-prediction layers are not built; ``attention_chunk_size`` is
unused (the pattern selects the window).

The share: the routed sum runs over the ``n_routed_experts`` experts held
from ``expert_offset`` of the ``num_experts_total`` the router scores.
Weights are stored in the configuration's storage type (bf16) and upcast
here a tensor at a time; the attention runs a key/value head's group of
query heads at a time, the experts one at a time, so that a pass of 4,096
positions fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import HIGHEST, rounder, seed_key

P = "mimo"
F32 = jnp.float32
WINDOW = 1


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, and ``deployment``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "rope_theta", "swa_rope_theta", "partial_rotary_factor",
            "sliding_window", "attention_value_scale",
            "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
            "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "layernorm_epsilon",
            "initializer_range", "sink_init_range")
    m = {k: cfg[k] for k in keys}
    # the published lists stay whole in the file: the model's are their
    # entries at the layers held here
    held = cfg["deployment"]["layers_held"]
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(f"layers_held {held} for "
                         f"{cfg['num_hidden_layers']} layers")
    m["hybrid_layer_pattern"] = [cfg["hybrid_layer_pattern"][i] for i in held]
    m["moe_layer_freq"] = [cfg["moe_layer_freq"][i] for i in held]
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["storage"] = cfg["storage_dtype"]
    return m


def kind_of(cfg: dict, i: int) -> dict:
    """Layer ``i``'s attention sizes, by its kind."""
    pre = "swa_" if cfg["hybrid_layer_pattern"][i] == WINDOW else ""
    window = pre == "swa_"
    return {"heads": cfg[pre + "num_attention_heads"],
            "kv": cfg[pre + "num_key_value_heads"],
            "qk": cfg[pre + "head_dim"], "vd": cfg[pre + "v_head_dim"],
            "theta": cfg["swa_rope_theta" if window else "rope_theta"],
            "window": cfg["sliding_window"] if window else 0,
            "sink": cfg["add_swa_attention_sink_bias" if window
                        else "add_full_attention_sink_bias"]}


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype). Kinds: ``normal:<std>`` (truncated at
    two), ``uniform:<lo>:<hi>``. Norm scales are drawn around 1; the
    router's selection bias in -0.1..0.1, wide enough to change some
    selections; a sink at the stated range (``assumed`` in the file)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    Eh, E = cfg["n_routed_experts"], cfg["num_experts_total"]
    n, st = f"normal:{cfg['initializer_range']}", cfg["storage"]
    around1 = "uniform:0.9:1.1"
    spec = {f"{P}_word_emb": ((V, H), n, st),
            f"{P}_lm_head": ((V, H), n, st),
            f"{P}_lnf_scale": ((H,), around1, "float32")}
    for i in range(cfg["num_hidden_layers"]):
        p, a = f"{P}_l{i}", kind_of(cfg, i)
        for name in ("ln_in", "ln_post"):
            spec[f"{p}_{name}_scale"] = ((H,), around1, "float32")
        mats = (("q", (H, a["heads"] * a["qk"])),
                ("k", (H, a["kv"] * a["qk"])), ("v", (H, a["kv"] * a["vd"])),
                ("out", (a["heads"] * a["vd"], H)))
        if a["sink"]:
            spec[f"{p}_sink"] = ((a["heads"],),
                                 f"normal:{cfg['sink_init_range']}",
                                 "float32")
        if not cfg["moe_layer_freq"][i]:
            mats += (("mlp_gate", (H, Fd)), ("mlp_up", (H, Fd)),
                     ("mlp_down", (Fd, H)))
        else:
            mats += (("router", (H, E)), ("gate", (Eh, H, F)),
                     ("up", (Eh, H, F)), ("down", (Eh, F, H)))
            spec[f"{p}_router_bias"] = ((E,), "uniform:-0.1:0.1", "float32")
        for name, shape in mats:
            spec[f"{p}_{name}_w"] = (shape, n, st)
    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, shape, kind, dtype):
    what, *args = kind.split(":")
    if what == "uniform":
        lo, hi = map(float, args)
        return jax.random.uniform(key, shape, F32, lo, hi).astype(dtype)
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
    return (z * float(args[0])).astype(dtype)


def make_weights(spec: dict, seed: int):
    """Yields ``(name, array)`` a tensor at a time, each in its storage
    type, made on the device from the seed. Names are folded in by sorted
    position."""
    key = seed_key(seed)
    for i, name in enumerate(sorted(spec)):
        shape, kind, dtype = spec[name]
        yield name, _make(jax.random.fold_in(key, i), tuple(shape), kind,
                          dtype)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, pos, theta, rot):
    """x [heads, T, D]: dims ``j`` and ``j + rot/2`` (j < rot/2) turn by
    ``pos * theta^(-2j/rot)``; dims from ``rot`` on are left alone."""
    half = rot // 2
    ang = pos[:, None].astype(F32) * theta ** (
        -jnp.arange(0, rot, 2, dtype=F32) / rot)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def attention(h, params, p, cfg, a, mm, rnd):
    """``a``: the layer's sizes (:func:`kind_of`)."""
    T = h.shape[0]
    nh, nkv, qk, vd = a["heads"], a["kv"], a["qk"], a["vd"]
    G = nh // nkv
    pos = jnp.arange(T)
    heads = lambda t, n, d: t.reshape(T, n, d).transpose(1, 0, 2)
    rot = int(cfg["partial_rotary_factor"] * qk)
    q = rotary(heads(mm(h, params[f"{p}_q_w"].astype(F32)), nh, qk), pos,
               a["theta"], rot)
    k = rotary(heads(mm(h, params[f"{p}_k_w"].astype(F32)), nkv, qk), pos,
               a["theta"], rot)
    v = cfg["attention_value_scale"] * heads(
        mm(h, params[f"{p}_v_w"].astype(F32)), nkv, vd)
    d = pos[:, None] - pos[None, :]
    seen = d >= 0
    if a["window"]:
        seen = seen & (d < a["window"])
    sink = (params[f"{p}_sink"].astype(F32) if a["sink"]
            else jnp.full((nh,), -jnp.inf, F32))    # exp(-inf) = 0: no column

    def group(args):                       # the G query heads of one k/v head
        qg, kh, vh, b = args
        s = jnp.einsum("gqd,kd->gqk", rnd(qg), rnd(kh),
                       precision=HIGHEST) * qk ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        # the softmax with the sink's column written out: it joins the
        # maximum and the denominator and carries no value
        m = jnp.maximum(jnp.max(s, axis=-1), b[:, None])
        e = jnp.exp(s - m[..., None])
        prob = e / (jnp.sum(e, axis=-1) + jnp.exp(b[:, None] - m))[..., None]
        return jnp.einsum("gqk,kd->gqd", rnd(prob), rnd(vh),
                          precision=HIGHEST)

    c = jax.lax.map(group, (q.reshape(nkv, G, T, qk), k, v,
                            sink.reshape(nkv, G)))
    c = c.reshape(nh, T, vd).transpose(1, 0, 2).reshape(T, nh * vd)
    return mm(c, params[f"{p}_out_w"].astype(F32))


def gated_mlp(h, params, name, mm):
    g, u, d = (params[f"{name}_{n}_w"].astype(F32)
               for n in ("gate", "up", "down"))
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def route(h, wr, bias, top_k):
    """sigmoid scores; the top_k largest of score + bias (lower index
    first among equals); the unbiased scores of the chosen, normalised
    over them; f32, unrounded."""
    s = jax.nn.sigmoid(jnp.matmul(h, wr.astype(F32), precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias, top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    return idx, vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)


def routed_part(h, params, p, cfg, mm):
    """sum over the held experts (as many as the stacked weights hold,
    from ``expert_offset``) of w_e E_e(h): every row through every held
    expert, weighted 0 where the row did not choose it."""
    first = cfg["expert_offset"]
    idx, w = route(h, params[f"{p}_router_w"], params[f"{p}_router_bias"],
                   cfg["num_experts_per_tok"])

    def one(acc, e):
        g, u, d = (params[f"{p}_{n}_w"][e].astype(F32)
                   for n in ("gate", "up", "down"))
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + share[:, None] * mm(
            jax.nn.silu(mm(h, g)) * mm(h, u), d), None

    held = params[f"{p}_gate_w"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))[0]


def layer(x, params, i, cfg, mm, rnd):
    p = f"{P}_l{i}"
    eps = cfg["layernorm_epsilon"]
    x = x + attention(rms(x, params[f"{p}_ln_in_scale"], eps), params, p,
                      cfg, kind_of(cfg, i), mm, rnd)
    h = rms(x, params[f"{p}_ln_post_scale"], eps)
    if not cfg["moe_layer_freq"][i]:
        return x + gated_mlp(h, params, f"{p}_mlp", mm)
    return x + routed_part(h, params, p, cfg, mm)


def logits(params, ids, cfg, precision="f32"):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right. ``precision`` rounds every matmul operand but
    the router's (which the configuration states as f32)."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    x = params[f"{P}_word_emb"][ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, params, i, cfg, mm, rnd)
    x = rms(x, params[f"{P}_lnf_scale"], cfg["layernorm_epsilon"])
    return mm(x, params[f"{P}_lm_head"].astype(F32).T)


def gaps_fn(cfg, control: str = ""):
    """As ``reference.gpt2.gaps_fn``: a jitted ``(params, ids[T], nxt[T])
    -> (served gaps[T], control's gaps[T])``: at row t, how far the
    reference's logit of ``nxt[t]`` (or of the control's own first choice)
    lies below the reference's best. ``control``: a precision of
    ``common.rounder`` for the matmul operands."""

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
