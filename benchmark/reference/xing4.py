"""Plain reference for the ``xing4_0`` decoder (Xing4.0-29B-A4B; source
and assumptions in ``configs/xing4.0-29b-a4b-ep8-serve.json``), as the
share of it that one chip of an expert-parallel deployment holds. One full
forward pass over a whole sequence in f32 with every product at HIGHEST:
the published, un-absorbed attention at every position, the Sinkhorn
rounds as written, no cache, no kernels, no batching, nothing of the
program imported. Parameter names are the scope's (``xing_*``).

``N(x) = x / sqrt(mean(x^2) + eps) * w``. A token's residual path is
``X`` in R^{n x C} (``n`` = ``hc_mult``): the embedding row copied ``n``
times. Every sublayer ``F`` (attention, then the feed-forward) of every
layer, with its own ``proj`` [n (n + 2), n C] (rows ``[P_pre^T | P_post^T
| P_res^T]``, the last row-major), ``alpha`` [3] and ``bias`` [n (n + 2)]:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
    H~pre = a_pre (x~ P_pre) + b_pre;  H~post = a_post (x~ P_post) + b_post
    H~res = a_res mat(x~ P_res) + b_res
    H_pre = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
    H_res = SK(exp(clamp(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)))
    u = H_pre X;   X <- H_res X + H_post^T F(N(u))

``SK``: ``hc_sinkhorn_iters`` rounds of "each row over (its sum +
``hc_eps``), then each column over (its sum + ``hc_eps``)". After the
last layer: the sum of the streams, the final ``N``, the untied
``lm_head``.

    Attn: ``c_q = N(h W_qa)``; ``q = c_q W_qb``, a head's ``[q_nope (dn) |
        q_rope (dr)]``; ``[c_raw (dc) | k_r (dr)] = h W_kva``; ``c =
        N(c_raw)``; rotary on all ``dr`` dims of ``q_rope`` and of ``k_r``
        in interleaved pairs ``(2j, 2j+1)``, one rotary key for all heads,
        with YaRN's frequencies at every position (``rope_scaling``:
        ``inv_freq_j = (1 - r_j) f_j / factor + r_j f_j``, ``f_j =
        theta^(-2j/dr)``, ``r_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``,
        ``lo`` / ``hi`` the floor / ceil of ``dr ln(L / (2 pi beta)) /
        (2 ln theta)`` at ``beta_fast`` / ``beta_slow``, clipped to 0 ..
        ``dr - 1``; cos and sin times ``m(mscale) / m(mscale_all_dim)``,
        ``m(s) = 0.1 s ln(factor) + 1``); ``[k_nope_i (dn) | v_i (dv)] =
        c W_kvb`` a head; ``k_i = [k_nope_i | k_rope]``; causal softmax of
        ``q_i . k_i (dn + dr)^-1/2 m(mscale_all_dim)^2`` over ``v_i``; the
        heads joined, ``W_o``.
    FFN: layers below ``first_k_dense_replace``: ``(silu(h Wg) * (h Wu))
        Wd`` of ``intermediate_size``. The others: ``s = sigmoid(h Wr)``;
        I = the top_k largest of ``s + b`` (``e_score_correction_bias``;
        one group, so no group limit); ``w_e = routed_scaling_factor * s_e
        / (sum_{j in I} s_j + 1e-20)``; ``sum_{e in I, e held} w_e E_e(h) +
        E_shared(h)``, every expert the same gated form at
        ``moe_intermediate_size``.

The share: the routed sum runs over the ``n_routed_experts`` experts held
from ``expert_offset`` of the ``num_experts_total`` the router scores.
Weights are stored in the configuration's storage type (bf16; the
hyper-connections' and the norms' f32) and upcast here a tensor at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST, rounder, seed_key

P = "xing"
F32 = jnp.float32


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, and ``deployment``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
            "initializer_range", "rope_scaling", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")
    m = {k: cfg[k] for k in keys}
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["storage"] = cfg["storage_dtype"]
    return m


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype). Kinds: ``normal:<std>`` (truncated at
    two), ``uniform:<lo>:<hi>``, ``hc_bias:<n>`` (``[b_pre | b_post |
    b_res]``: uniform in -1..1, ``b_res`` plus 4 times the identity). Norm
    scales are drawn around 1; the router's selection bias in -0.1..0.1,
    wide enough to change some selections; a hyper-connection's scalars
    ``a_*`` in 0.5..1.5, so that its per-token part is as large as its
    bias (``assumed`` in the file)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, dc = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Eh, E = cfg["n_routed_experts"], cfg["num_experts_total"]
    ns, hc = cfg["n_shared_experts"], cfg["hc_mult"]
    n, st = f"normal:{cfg['initializer_range']}", cfg["storage"]
    around1 = "uniform:0.9:1.1"
    spec = {f"{P}_word_emb": ((V, H), n, st),
            f"{P}_lm_head": ((V, H), n, st),
            f"{P}_lnf_scale": ((H,), around1, "float32")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}_l{i}"
        for name, dim in (("ln_in", H), ("ln_post", H), ("q_a_norm", rq),
                          ("kv_a_norm", dc)):
            spec[f"{p}_{name}_scale"] = ((dim,), around1, "float32")
        for sub in ("attn", "ffn"):
            q = f"{p}_hc_{sub}"
            spec[f"{q}_proj"] = ((hc * (hc + 2), hc * H), n, "float32")
            spec[f"{q}_alpha"] = ((3,), "uniform:0.5:1.5", "float32")
            spec[f"{q}_bias"] = ((hc * (hc + 2),), f"hc_bias:{hc}",
                                 "float32")
        mats = (("q_a", (H, rq)), ("q_b", (rq, nh * (dn + dr))),
                ("kv_a", (H, dc + dr)), ("kv_b", (dc, nh * (dn + dv))),
                ("out", (nh * dv, H)))
        if i < cfg["first_k_dense_replace"]:
            mats += (("mlp_gate", (H, Fd)), ("mlp_up", (H, Fd)),
                     ("mlp_down", (Fd, H)))
        else:
            mats += (("router", (H, E)), ("gate", (Eh, H, F)),
                     ("up", (Eh, H, F)), ("down", (Eh, F, H)),
                     ("shared_gate", (H, ns * F)), ("shared_up", (H, ns * F)),
                     ("shared_down", (ns * F, H)))
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
    if what == "hc_bias":
        n = int(args[0])
        eye = jnp.concatenate([jnp.zeros(2 * n, F32),
                               4.0 * jnp.eye(n, dtype=F32).ravel()])
        return (jax.random.uniform(key, shape, F32, -1.0, 1.0)
                + eye).astype(dtype)
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


def yarn_m(factor, s):
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(D, theta, yarn):
    """The ``D / 2`` rotary frequencies: ``theta^(-2j/D)``, or YaRN's."""
    j = np.arange(D // 2, dtype=np.float64)
    plain = float(theta) ** (-2.0 * j / D)
    if not yarn:
        return plain.astype(np.float32)
    pair = lambda turns: D * math.log(
        yarn["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = max(math.floor(pair(yarn["beta_fast"])), 0)
    hi = min(math.ceil(pair(yarn["beta_slow"])), D - 1)
    if lo == hi:
        hi += 0.001
    r = 1.0 - np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return ((1.0 - r) * plain / yarn["factor"] + r * plain).astype(
        np.float32)


def rotary(x, pos, theta, yarn=None):
    """x [..., T, D]: dims ``2j`` and ``2j + 1`` turn by ``pos *
    inv_freq_j``; under YaRN cos and sin times ``m(mscale) /
    m(mscale_all_dim)``."""
    D = x.shape[-1]
    ang = pos[:, None].astype(F32) * jnp.asarray(inv_freq(D, theta, yarn))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn:
        m = yarn_m(yarn["factor"], yarn.get("mscale", 1)) / yarn_m(
            yarn["factor"], yarn.get("mscale_all_dim", 0))
        cos, sin = cos * F32(m), sin * F32(m)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(h, params, p, cfg, mm, rnd, cache_dtype=F32):
    """``cache_dtype``: the type a token's ``[c | k_rope]`` row is kept in
    between its writing and its reading (the configuration states the
    storage type, which the served path's comparison already carries;
    anything narrower is a control)."""
    T = h.shape[0]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    dc, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    w = lambda name: params[f"{p}_{name}"].astype(F32)
    pos, yarn = jnp.arange(T), cfg.get("rope_scaling")
    scale = (dn + dr) ** -0.5
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_m(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    cq = rms(mm(h, w("q_a_w")), w("q_a_norm_scale"), eps)
    q = mm(cq, w("q_b_w")).reshape(T, nh, dn + dr).transpose(1, 0, 2)
    q = jnp.concatenate(
        [q[..., :dn], rotary(q[..., dn:], pos, cfg["rope_theta"], yarn)],
        axis=-1)
    kva = mm(h, w("kv_a_w"))
    kept = lambda t: t.astype(cache_dtype).astype(F32)
    c = kept(rms(kva[:, :dc], w("kv_a_norm_scale"), eps))
    k_rope = kept(rotary(kva[:, dc:], pos, cfg["rope_theta"], yarn))
    kv = mm(c, w("kv_b_w")).reshape(T, nh, dn + dv).transpose(1, 0, 2)
    seen = pos[:, None] >= pos[None, :]

    def head(n):                                   # one head
        k = jnp.concatenate([kv[n, :, :dn], k_rope], axis=-1)
        s = jnp.matmul(rnd(q[n]), rnd(k).T, precision=HIGHEST) * scale
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(rnd(a), rnd(kv[n, :, dn:]), precision=HIGHEST)

    o = jax.lax.map(head, jnp.arange(nh))                      # [nh, T, dv]
    return mm(o.transpose(1, 0, 2).reshape(T, nh * dv), w("out_w"))


def gated_mlp(h, params, name, mm):
    g, u, d = (params[f"{name}_{n}_w"].astype(F32)
               for n in ("gate", "up", "down"))
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def route(h, wr, bias, top_k, scale):
    """sigmoid scores; the top_k largest of score + bias (lower index
    first among equals); the unbiased scores of the chosen, normalised
    over them and scaled; f32, unrounded."""
    s = jax.nn.sigmoid(jnp.matmul(h, wr.astype(F32), precision=HIGHEST))
    _, idx = jax.lax.top_k(s + bias, top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * vals / (jnp.sum(vals, axis=-1, keepdims=True)
                                + 1e-20)


def routed_part(h, params, p, cfg, mm):
    """sum over the held experts (as many as the stacked weights hold,
    from ``expert_offset``) of w_e E_e(h): every row through every held
    expert, weighted 0 where the row did not choose it."""
    first = cfg["expert_offset"]
    idx, w = route(h, params[f"{p}_router_w"], params[f"{p}_router_bias"],
                   cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])

    def one(acc, e):
        g, u, d = (params[f"{p}_{n}_w"][e].astype(F32)
                   for n in ("gate", "up", "down"))
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + share[:, None] * mm(
            jax.nn.silu(mm(h, g)) * mm(h, u), d), None

    held = params[f"{p}_gate_w"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))[0]


def sinkhorn(a, iters, eps):
    """[T, n, n]: ``iters`` rounds of each row over (its sum + eps), then
    each column over (its sum + eps)."""
    for _ in range(iters):
        a = a / (jnp.sum(a, axis=2, keepdims=True) + eps)
        a = a / (jnp.sum(a, axis=1, keepdims=True) + eps)
    return a


def hyper_connection(X, params, name, cfg, per_token=True):
    """``X`` [T, n, C] -> ``(u [T, C], H_post [T, n], H_res [T, n, n])``.
    ``per_token=False`` drops ``a_* (x~ P_*)`` (a control: the coefficients
    are then the biases' alone, the same for every token)."""
    T, n, C = X.shape
    flat = X.reshape(T, n * C)
    xn = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1,
                                       keepdims=True) + cfg["rms_norm_eps"])
    z = jnp.matmul(xn, params[f"{name}_proj"].astype(F32).T,
                   precision=HIGHEST)
    a, b = params[f"{name}_alpha"], params[f"{name}_bias"]
    z = z if per_token else jnp.zeros_like(z)
    pre = a[0] * z[:, :n] + b[:n]
    post = a[1] * z[:, n:2 * n] + b[n:2 * n]
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    res = sinkhorn(jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                                    cfg["mhc_h_res_clamp_max"])),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    h_pre = jax.nn.sigmoid(pre)
    u = jnp.sum(h_pre[:, :, None] * X, axis=1)
    return u, 2.0 * jax.nn.sigmoid(post), res


def mixed_back(X, y, post, res):
    """``H_res X + H_post^T y``."""
    return jnp.sum(res[:, :, :, None] * X[:, None, :, :], axis=2) \
        + post[:, :, None] * y[:, None, :]


def feed_forward(h, params, p, i, cfg, mm):
    if i < cfg["first_k_dense_replace"]:
        return gated_mlp(h, params, f"{p}_mlp", mm)
    return routed_part(h, params, p, cfg, mm) \
        + gated_mlp(h, params, f"{p}_shared", mm)


def layer(X, params, i, cfg, mm, rnd, cache_dtype=F32, stream_dtype=F32,
          per_token=True):
    """One layer on the streams ``X`` [T, n, C]. ``stream_dtype``: the type
    the streams are kept in between sublayers (f32 as the configuration
    states; anything narrower is a control)."""
    p = f"{P}_l{i}"
    eps = cfg["rms_norm_eps"]
    kept = lambda t: t.astype(stream_dtype).astype(F32)
    u, post, res = hyper_connection(X, params, f"{p}_hc_attn", cfg,
                                    per_token)
    y = attention(rms(u, params[f"{p}_ln_in_scale"], eps), params, p, cfg,
                  mm, rnd, cache_dtype)
    X = kept(mixed_back(X, y, post, res))
    u, post, res = hyper_connection(X, params, f"{p}_hc_ffn", cfg, per_token)
    y = feed_forward(rms(u, params[f"{p}_ln_post_scale"], eps), params, p, i,
                     cfg, mm)
    return kept(mixed_back(X, y, post, res))


def logits(params, ids, cfg, precision="f32", cache_dtype=F32,
           stream_dtype=F32, per_token=True):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right. ``precision`` rounds every matmul operand but
    the router's and the hyper-connections' (which the configuration
    states as f32)."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    x = params[f"{P}_word_emb"][ids].astype(F32)
    X = jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
    for i in range(cfg["num_hidden_layers"]):
        X = layer(X, params, i, cfg, mm, rnd, cache_dtype, stream_dtype,
                  per_token)
    x = rms(jnp.sum(X, axis=1), params[f"{P}_lnf_scale"],
            cfg["rms_norm_eps"])
    return mm(x, params[f"{P}_lm_head"].astype(F32).T)


def gaps_fn(cfg, control: str = ""):
    """As ``reference.gpt2.gaps_fn``: a jitted ``(params, ids[T], nxt[T])
    -> (served gaps[T], control's gaps[T])``: at row t, how far the
    reference's logit of ``nxt[t]`` (or of the control's own first choice)
    lies below the reference's best. ``control``: a precision of
    ``common.rounder`` for the matmul operands, ``cache:fp8`` for the
    latent cache's rows kept in fp8 e4m3, ``stream:bf16`` for the residual
    streams kept in bf16 between sublayers, or ``hc:fixed`` for
    hyper-connections without their per-token part."""

    @jax.jit
    def fn(params, ids, nxt):
        ref = logits(params, ids, cfg)
        best = jnp.max(ref, axis=-1)
        below = lambda tok: best - jnp.take_along_axis(
            ref, tok[:, None], axis=-1)[:, 0]
        served = below(nxt)
        if not control:
            return served, served
        if control == "cache:fp8":
            low = logits(params, ids, cfg, cache_dtype=jnp.float8_e4m3fn)
        elif control == "stream:bf16":
            low = logits(params, ids, cfg, stream_dtype=jnp.bfloat16)
        elif control == "hc:fixed":
            low = logits(params, ids, cfg, per_token=False)
        else:
            low = logits(params, ids, cfg, control)
        return served, below(jnp.argmax(low, axis=-1))

    return fn
