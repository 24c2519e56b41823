"""Plain reference for the ``qwen3_next`` decoder (Qwen3-Next-80B-A3B;
source and assumptions in ``configs/qwen3-next-ep2-serve.json``), as the
share of it that one chip of an expert-parallel deployment holds. One full
forward pass over a whole sequence in f32 with every product at HIGHEST:
no cache, no kernels, no chunks, no batching, nothing of the program
imported. Parameter names are the scope's (``qn_*``).

``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``. Every layer:
``h = x + Mixer_i(N_in(x))``, ``y = h + MoE(N_post(h))``; layer ``i`` is
full attention when ``(i + 1) % full_attention_interval == 0``, else
linear attention. Head: final ``N``, then the untied ``lm_head``.

    Gated attention: ``q_w`` gives [heads, 2 x head_dim] a token, the
        first head_dim of each head the query, the rest its gate;
        ``q = N_q(query)``, ``k = N_k(h k_w)`` (over a head's dims),
        ``v = h v_w``; query head n reads key/value head n // group; rotary
        on the first ``partial_rotary_factor x head_dim`` dims in
        rotate-half pairs ``(j, j + rot/2)``; causal softmax, scale
        head_dim^-1/2; ``(attn * sigmoid(gate)) out_w``.
    Gated DeltaNet: ``qkvz_w`` columns are ``[q | k | v | z]`` and
        ``ba_w`` columns ``[b | a]`` (plain concatenation, see the file's
        ``assumed``); over the channels of ``m = concat(q, k, v)``:
        ``c_t = silu(sum_j W[:, j] m_{t-taps+1+j})``, zeros before the
        sequence; ``beta = sigmoid(b)``, ``g = -exp(a_log) softplus(a +
        dt_bias)``; q and k times ``rsqrt(sum of squares + 1e-6)`` over a
        head's dims, q times key_dim^-1/2; value head n reads key head
        n // (value heads / key heads). Per value head, ``S_0 = 0``:

            S <- exp(g_t) S;  r = S^T k_t;  u = beta_t (v_t - r)
            S <- S + k_t u^T;  o_t = S^T q_t

        then ``w * (o_t / sqrt(mean(o_t^2) + eps)) * silu(z_t)`` per head
        (plain ``w``), heads joined, ``out_w``. A token at a time.
    MoE: ``p = softmax(h Wr)`` over all experts; I = the top_k largest;
        ``w_e = p_e / sum_{j in I} p_j``; ``sum_{e in I, e held} w_e
        E_e(h) + sigmoid(h . w_s) E_shared(h)`` with
        ``E(h) = (silu(h Wg) * (h Wu)) Wd``.

The share: the routed sum runs over the ``num_experts`` experts held from
``expert_offset`` of the ``num_experts_total`` the router scores. An
expert is given the rows that chose it, gathered (up to an eighth of the
sequence; past that, every row, weighted 0 where it did not choose it:
the same sum either way). Weights are stored in the configuration's
storage type (bf16) and upcast here a block at a time, so that a
4,096-row pass fits beside 7.4 GB of them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, rounder, seed_key

P = "qn"
F32 = jnp.float32


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, and ``deployment``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "full_attention_interval",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "moe_intermediate_size",
            "shared_expert_intermediate_size", "num_experts",
            "num_experts_per_tok", "rms_norm_eps", "initializer_range")
    m = {k: cfg[k] for k in keys}
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["storage"] = cfg["storage_dtype"]
    return m


def is_full(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def conv_channels(cfg: dict) -> int:
    return (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
            + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype). Kinds: ``normal:<std>`` (truncated at
    two), ``uniform:<lo>:<hi>``. Norm scales are drawn around their neutral
    value; ``a_log`` and ``dt_bias`` so that a token's decay ``exp(g)``
    lies between about 0.8 and 0.995, by head (``assumed`` in the file)."""
    H, F, V = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    Hv, Dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    C, taps = conv_channels(cfg), cfg["linear_conv_kernel_dim"]
    Eh, E = cfg["num_experts"], cfg["num_experts_total"]
    n, st = f"normal:{cfg['initializer_range']}", cfg["storage"]
    around0, around1 = "uniform:-0.1:0.1", "uniform:0.9:1.1"
    spec = {f"{P}_word_emb": ((V, H), n, st),
            f"{P}_lm_head": ((V, H), n, st),
            f"{P}_lnf_scale": ((H,), around0, "float32")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}_l{i}"
        spec[f"{p}_ln_in_scale"] = ((H,), around0, "float32")
        spec[f"{p}_ln_post_scale"] = ((H,), around0, "float32")
        if is_full(cfg, i):
            mats = (("q", (H, nh * 2 * hd)), ("k", (H, nkv * hd)),
                    ("v", (H, nkv * hd)), ("out", (nh * hd, H)))
            spec[f"{p}_qnorm_scale"] = ((hd,), around0, "float32")
            spec[f"{p}_knorm_scale"] = ((hd,), around0, "float32")
        else:
            mats = (("qkvz", (H, C + Hv * Dv)), ("ba", (H, 2 * Hv)),
                    ("conv", (C, taps)), ("out", (Hv * Dv, H)))
            spec[f"{p}_gnorm_scale"] = ((Dv,), around1, "float32")
            spec[f"{p}_a_log"] = (
                (Hv,), f"uniform:{math.log(0.25)}:{math.log(2.0)}", "float32")
            spec[f"{p}_dt_bias"] = ((Hv,), "uniform:-4.0:-2.0", "float32")
        mats += (("router", (H, E)), ("gate", (Eh, H, F)), ("up", (Eh, H, F)),
                 ("down", (Eh, F, H)), ("shared_gate", (H, F)),
                 ("shared_up", (H, F)), ("shared_down", (F, H)),
                 ("shared_mix", (H, 1)))
        for name, shape in mats:
            # the short convolution's four taps sum to a channel's gain:
            # drawn at 0.5 so that the rule sees values of order 1
            kind = "normal:0.5" if name == "conv" else n
            spec[f"{p}_{name}_w"] = (shape, kind, st)
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


def rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y * (1.0 + w if zero_centered else w)


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


def attention(h, params, p, cfg, mm, rnd):
    T = h.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    G, eps = nh // nkv, cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    heads = lambda t: t.transpose(1, 0, 2)
    qg = mm(h, params[f"{p}_q_w"].astype(F32)).reshape(T, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm(h, params[f"{p}_k_w"].astype(F32)).reshape(T, nkv, hd)
    v = heads(mm(h, params[f"{p}_v_w"].astype(F32)).reshape(T, nkv, hd))
    rot = int(hd * cfg["partial_rotary_factor"])
    q = rotary(heads(rms(q, params[f"{p}_qnorm_scale"], eps)), pos,
               cfg["rope_theta"], rot)
    k = rotary(heads(rms(k, params[f"{p}_knorm_scale"], eps)), pos,
               cfg["rope_theta"], rot)
    seen = pos[:, None] >= pos[None, :]

    def head(n):                                   # one query head
        s = jnp.matmul(rnd(q[n]), rnd(k[n // G]).T,
                       precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(rnd(a), rnd(v[n // G]), precision=HIGHEST)

    c = jax.lax.map(head, jnp.arange(nh))                      # [nh, T, hd]
    c = c.transpose(1, 0, 2) * jax.nn.sigmoid(gate)
    return mm(c.reshape(T, nh * hd), params[f"{p}_out_w"].astype(F32))


def delta_rule(q, k, v, g, beta, state_dtype=F32):
    """q, k [T, Hv, Dk], v [T, Hv, Dv], g, beta [T, Hv] -> o [T, Hv, Dv]:
    the recurrence above, a token at a time. ``state_dtype``: the type the
    state is kept in between tokens (f32 as the configuration states;
    anything else is a control)."""
    def one(S, t):
        qt, kt, vt, gt, bt = t
        S = S.astype(F32) * jnp.exp(gt)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        u = bt[:, None] * (vt - r)
        S = S + kt[:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)
        return S.astype(state_dtype), o

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), state_dtype)
    return jax.lax.scan(one, S0, (q, k, v, g, beta))[1]


def delta_net(h, params, p, cfg, mm, state_dtype=F32):
    T = h.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    C, taps = conv_channels(cfg), cfg["linear_conv_kernel_dim"]
    qkvz = mm(h, params[f"{p}_qkvz_w"].astype(F32))
    m, z = qkvz[:, :C], qkvz[:, C:]
    ba = mm(h, params[f"{p}_ba_w"].astype(F32))
    b, a = ba[:, :Hv], ba[:, Hv:]
    w = params[f"{p}_conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((taps - 1, C), F32), m])
    c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(taps)))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = unit(c[:, :Hk * Dk].reshape(T, Hk, Dk)) * Dk ** -0.5
    k = unit(c[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk))
    v = c[:, 2 * Hk * Dk:].reshape(T, Hv, Dv)
    q, k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))
    g = -jnp.exp(params[f"{p}_a_log"]) * jax.nn.softplus(
        a + params[f"{p}_dt_bias"])
    o = delta_rule(q, k, v, g, jax.nn.sigmoid(b), state_dtype)
    o = rms(o, params[f"{p}_gnorm_scale"], cfg["rms_norm_eps"],
            zero_centered=False) * jax.nn.silu(z.reshape(T, Hv, Dv))
    return mm(o.reshape(T, Hv * Dv), params[f"{p}_out_w"].astype(F32))


def route(h, wr, top_k):
    """softmax scores, the top_k largest (lower index first among equals),
    and their weights normalised over the chosen ones; f32, unrounded."""
    s = jax.nn.softmax(jnp.matmul(h, wr.astype(F32), precision=HIGHEST),
                       axis=-1)
    vals, idx = jax.lax.top_k(s, top_k)
    return idx, vals / jnp.sum(vals, axis=-1, keepdims=True)


def routed_part(h, params, p, cfg, mm):
    """sum over the held experts (as many as the stacked weights hold,
    from ``expert_offset``) of w_e E_e(h), one expert at a time on the
    rows that chose it."""
    T = h.shape[0]
    first = cfg["expert_offset"]
    idx, w = route(h, params[f"{p}_router_w"], cfg["num_experts_per_tok"])
    held = params[f"{p}_gate_w"].shape[0]
    cap = min(T, max(8, T // 8))

    def expert(e, rows):
        g, u, d = (params[f"{p}_{n}_w"][e].astype(F32)
                   for n in ("gate", "up", "down"))
        return mm(jax.nn.silu(mm(rows, g)) * mm(rows, u), d)

    share = lambda e: jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)

    def gathered(acc, e):
        chose = jnp.any(idx == first + e, axis=-1)
        (at,) = jnp.nonzero(chose, size=cap, fill_value=T)   # T: no row
        rows = h.at[at].get(mode="fill", fill_value=0.0)
        wt = share(e).at[at].get(mode="fill", fill_value=0.0)
        return acc.at[at].add(wt[:, None] * expert(e, rows),
                              mode="drop"), None

    def every(acc, e):
        return acc + share(e)[:, None] * expert(e, h), None

    local = idx[:, :, None] == first + jnp.arange(held)
    fits = jnp.max(jnp.sum(local, axis=(0, 1))) <= cap
    over = lambda body: lambda: jax.lax.scan(
        body, jnp.zeros_like(h), jnp.arange(held))[0]
    return jax.lax.cond(fits, over(gathered), over(every))


def shared_part(h, params, p, mm):
    g, u, d = (params[f"{p}_shared_{n}_w"].astype(F32)
               for n in ("gate", "up", "down"))
    mix = jax.nn.sigmoid(mm(h, params[f"{p}_shared_mix_w"].astype(F32)))
    return mix * mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def moe(h, params, p, cfg, mm):
    return routed_part(h, params, p, cfg, mm) + shared_part(h, params, p, mm)


def layer(x, params, i, cfg, mm, rnd, state_dtype=F32):
    p = f"{P}_l{i}"
    eps = cfg["rms_norm_eps"]
    h = rms(x, params[f"{p}_ln_in_scale"], eps)
    if is_full(cfg, i):
        x = x + attention(h, params, p, cfg, mm, rnd)
    else:
        x = x + delta_net(h, params, p, cfg, mm, state_dtype)
    return x + moe(rms(x, params[f"{p}_ln_post_scale"], eps), params, p, cfg,
                   mm)


def logits(params, ids, cfg, precision="f32", vocab_block=9496,
           state_dtype=F32):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right. ``precision`` rounds every matmul operand but
    the router's and the rule's (which the configuration states as f32)."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    x = params[f"{P}_word_emb"][ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, params, i, cfg, mm, rnd, state_dtype)
    x = rms(x, params[f"{P}_lnf_scale"], cfg["rms_norm_eps"])
    head = params[f"{P}_lm_head"]
    V = head.shape[0]
    vb = vocab_block if V % vocab_block == 0 else V
    slabs = jax.lax.map(lambda e: mm(x, e.astype(F32).T),
                        head.reshape(V // vb, vb, -1))
    return slabs.transpose(1, 0, 2).reshape(-1, V)


def gaps_fn(cfg, control: str = ""):
    """As ``reference.gpt2.gaps_fn``: a jitted ``(params, ids[T], nxt[T])
    -> (served gaps[T], control's gaps[T])``: at row t, how far the
    reference's logit of ``nxt[t]`` (or of the control's own first choice)
    lies below the reference's best. ``control``: a precision of
    ``common.rounder`` for the matmul operands, or ``state:bf16`` for the
    recurrent state kept in bf16 between tokens."""

    @jax.jit
    def fn(params, ids, nxt):
        ref = logits(params, ids, cfg)
        best = jnp.max(ref, axis=-1)
        below = lambda tok: best - jnp.take_along_axis(
            ref, tok[:, None], axis=-1)[:, 0]
        served = below(nxt)
        if not control:
            return served, served
        if control == "state:bf16":
            low = logits(params, ids, cfg, state_dtype=jnp.bfloat16)
        else:
            low = logits(params, ids, cfg, control)
        return served, below(jnp.argmax(low, axis=-1))

    return fn
