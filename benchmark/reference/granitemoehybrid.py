"""Plain reference for the ``granitemoehybrid`` decoder (Granite-4.0-H-Small;
source and assumptions in ``configs/granite-4.0-h-small-ep2-serve.json``),
as the share of it that one chip of an expert-parallel deployment holds.
One full forward pass over a whole sequence in f32 with every product at
HIGHEST: no cache, no kernels, no chunks, no batching, nothing of the
program imported. Parameter names are the scope's (``gmh_*``).

``N(v) = v / sqrt(mean(v^2) + eps) * w``. With the published scalars
``embedding_multiplier`` (12), ``residual_multiplier`` r (0.22),
``attention_multiplier`` (1/128) and ``logits_scaling`` (16):

    x0 = 12 E[ids]
    h = x + r Mixer_i(N_in(x));   y = h + r (MoE(N_post(h)) + Shared(N_post(h)))
    logits = N_f(x_L) E^T / 16                  (the head is the embedding)

Layer ``i`` is ``layer_types[i]``: ``mamba`` or ``attention``.

    Mamba-2: ``in_w`` columns are ``[z | xBC | dt]`` (8192 | 8448 | 128);
        over the channels of ``m = xBC``: ``c_t = silu(b + sum_j W[:, j]
        m_{t-taps+1+j})``, zeros before the sequence; ``c -> x [heads, head
        dim] | B | C`` (``mamba_n_groups`` 1: one B and C for all heads);
        ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``; per
        head, ``S_0 = 0`` in ``R^{head dim x state dim}``:

            S <- a_t S + dt_t x_t B_t^T;   y_t = S C_t + D x_t

        a token at a time; heads joined, ``N_g(y_t * silu(z_t))`` (one norm
        over all 8,192 columns, after the gate), ``out_w``.
    Attention: ``q_w``, ``k_w``, ``v_w`` without bias, query head n reads
        key/value head n // group, NO positional encoding, causal softmax
        of scores times ``attention_multiplier``, ``out_w``.
    MoE: ``l = h Wr`` over all experts; I = the top_k largest; ``w =
        softmax(l_I)`` over the chosen ones; ``sum_{e in I, e held} w_e
        E_e(h)`` with ``E(h) = (silu(h Wg) * (h Wu)) Wd``.
    Shared: one expert of the same form at its own width, added.

Departures from the published code (``modeling_granitemoehybrid.py``), none
of which changes a number: the experts' ``input_linear`` is stored as its
two halves (``gate_w``, ``up_w``) and the shared expert's likewise; the
router's logits are f32; the scan is the recurrence itself and not its
chunked dual form; the time step is not clamped (``time_step_limit`` is
``(0, inf)`` by default).

The share: the routed sum runs over the ``num_local_experts`` experts held
from ``expert_offset`` of the ``num_experts_total`` the router scores. An
expert is given the rows that chose it, gathered (up to a quarter of the
sequence; past that, every row, weighted 0 where it did not choose it: the
same sum either way). Weights are stored in the configuration's storage
type (bf16) and upcast here a block at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, rounder
from .qwen3_next import make_weights  # noqa: F401  (a tensor at a time)

P = "gmh"
F32 = jnp.float32
ATTENTION = "attention"             # every other layer type is "mamba"


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, and ``deployment``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_n_groups", "mamba_chunk_size", "intermediate_size",
            "shared_intermediate_size", "num_local_experts",
            "num_experts_per_tok", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling",
            "rms_norm_eps", "initializer_range")
    m = {k: cfg[k] for k in keys}
    # the file keeps the published list whole; the layers held are its first
    m["layer_types"] = list(cfg["layer_types"][:cfg["num_hidden_layers"]])
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["embedding_range"] = cfg["embedding_initializer_range"]
    m["storage"] = cfg["storage_dtype"]
    return m


def conv_channels(cfg: dict) -> int:
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype). Kinds: ``normal:<std>`` (truncated at
    two), ``uniform:<lo>:<hi>``. Norm scales and the skip ``D`` are drawn
    around 1; ``a_log`` so that ``A`` lies in 1..16 and ``dt_bias`` so that
    its softplus lies in 0.001..0.1, Mamba-2's own start; the tied embedding
    at a range of its own, so that a token's best successor is not itself
    (all under ``assumed`` in the file)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = H // nh
    Hm = cfg["mamba_n_heads"]
    di, C, taps = Hm * cfg["mamba_d_head"], conv_channels(cfg), \
        cfg["mamba_d_conv"]
    Eh, E = cfg["num_local_experts"], cfg["num_experts_total"]
    n, st = f"normal:{cfg['initializer_range']}", cfg["storage"]
    around1 = "uniform:0.9:1.1"
    spec = {f"{P}_word_emb": ((V, H), f"normal:{cfg['embedding_range']}", st),
            f"{P}_lnf_scale": ((H,), around1, "float32")}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"{P}_l{i}"
        spec[f"{p}_ln_in_scale"] = ((H,), around1, "float32")
        spec[f"{p}_ln_post_scale"] = ((H,), around1, "float32")
        if kind == ATTENTION:
            mats = (("q", (H, nh * hd)), ("k", (H, nkv * hd)),
                    ("v", (H, nkv * hd)), ("out", (nh * hd, H)))
        else:
            mats = (("in", (H, di + C + Hm)), ("conv", (C, taps)),
                    ("out", (di, H)))
            spec[f"{p}_conv_b"] = ((C,), "uniform:-0.1:0.1", "float32")
            spec[f"{p}_gnorm_scale"] = ((di,), around1, "float32")
            spec[f"{p}_a_log"] = ((Hm,), f"uniform:0.0:{math.log(16.0)}",
                                  "float32")
            spec[f"{p}_dt_bias"] = ((Hm,), "uniform:-6.9:-2.25", "float32")
            spec[f"{p}_d"] = ((Hm,), around1, "float32")
        mats += (("router", (H, E)), ("gate", (Eh, H, F)), ("up", (Eh, H, F)),
                 ("down", (Eh, F, H)), ("shared_gate", (H, Fs)),
                 ("shared_up", (H, Fs)), ("shared_down", (Fs, H)))
        for name, shape in mats:
            # the short convolution's four taps sum to a channel's gain:
            # drawn at 0.5 so that the scan sees values of order 1
            std = "normal:0.5" if name == "conv" else n
            spec[f"{p}_{name}_w"] = (shape, std, st)
    return spec


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def attention(h, params, p, cfg, mm, rnd):
    T, H = h.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, G = H // nh, nh // nkv
    heads = lambda t, n: t.reshape(T, n, hd).transpose(1, 0, 2)
    q = heads(mm(h, params[f"{p}_q_w"].astype(F32)), nh)
    k = heads(mm(h, params[f"{p}_k_w"].astype(F32)), nkv)
    v = heads(mm(h, params[f"{p}_v_w"].astype(F32)), nkv)
    pos = jnp.arange(T)
    seen = pos[:, None] >= pos[None, :]

    def head(n):                                   # one query head
        s = jnp.matmul(rnd(q[n]), rnd(k[n // G]).T,
                       precision=HIGHEST) * cfg["attention_multiplier"]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(rnd(a), rnd(v[n // G]), precision=HIGHEST)

    c = jax.lax.map(head, jnp.arange(nh))                      # [nh, T, hd]
    return mm(c.transpose(1, 0, 2).reshape(T, nh * hd),
              params[f"{p}_out_w"].astype(F32))


def selective_scan(x, dt, a, b, c, state_dtype=F32):
    """x [T, H, P], dt, a [T, H], b, c [T, N] -> y [T, H, P]: the recurrence
    above, a token at a time. ``state_dtype``: the type the state is kept in
    between tokens (f32 as the configuration states; anything else is a
    control)."""
    def one(S, t):
        xt, dtt, at, bt, ct = t
        S = (S.astype(F32) * at[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        y = jnp.einsum("hpn,n->hp", S, ct, precision=HIGHEST)
        return S.astype(state_dtype), y

    S0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), state_dtype)
    return jax.lax.scan(one, S0, (x, dt, a, b, c))[1]


def mamba(h, params, p, cfg, mm, state_dtype=F32):
    T = h.shape[0]
    Hm, Pd, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di, C, taps = Hm * Pd, conv_channels(cfg), cfg["mamba_d_conv"]
    zxd = mm(h, params[f"{p}_in_w"].astype(F32))
    z, m, dt = zxd[:, :di], zxd[:, di:di + C], zxd[:, di + C:]
    w = params[f"{p}_conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((taps - 1, C), F32), m])
    conv = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(taps))
                       + params[f"{p}_conv_b"])
    x = conv[:, :di].reshape(T, Hm, Pd)
    b, c = conv[:, di:di + N], conv[:, di + N:]
    dt = jax.nn.softplus(dt + params[f"{p}_dt_bias"])
    a = jnp.exp(-jnp.exp(params[f"{p}_a_log"]) * dt)
    y = selective_scan(x, dt, a, b, c, state_dtype) \
        + params[f"{p}_d"][:, None] * x
    y = rms(y.reshape(T, di) * jax.nn.silu(z), params[f"{p}_gnorm_scale"],
            cfg["rms_norm_eps"])
    return mm(y, params[f"{p}_out_w"].astype(F32))


def route(h, wr, top_k):
    """The top_k largest router logits (lower index first among equals)
    and a softmax over just those; f32, unrounded."""
    vals, idx = jax.lax.top_k(
        jnp.matmul(h, wr.astype(F32), precision=HIGHEST), top_k)
    return idx, jax.nn.softmax(vals, axis=-1)


def routed_part(h, params, p, cfg, mm):
    """sum over the held experts (as many as the stacked weights hold,
    from ``expert_offset``) of w_e E_e(h), one expert at a time on the
    rows that chose it."""
    T = h.shape[0]
    first = cfg["expert_offset"]
    idx, w = route(h, params[f"{p}_router_w"], cfg["num_experts_per_tok"])
    held = params[f"{p}_gate_w"].shape[0]
    cap = min(T, max(8, T // 4))

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
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def layer(x, params, i, cfg, mm, rnd, state_dtype=F32):
    p = f"{P}_l{i}"
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms(x, params[f"{p}_ln_in_scale"], eps)
    if cfg["layer_types"][i] == ATTENTION:
        x = x + r * attention(h, params, p, cfg, mm, rnd)
    else:
        x = x + r * mamba(h, params, p, cfg, mm, state_dtype)
    h = rms(x, params[f"{p}_ln_post_scale"], eps)
    return x + r * (routed_part(h, params, p, cfg, mm)
                    + shared_part(h, params, p, mm))


def logits(params, ids, cfg, precision="f32", vocab_block=6272,
           state_dtype=F32):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right. ``precision`` rounds every matmul operand but
    the router's and the scan's (which the configuration states as f32)."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    emb = params[f"{P}_word_emb"]
    x = cfg["embedding_multiplier"] * emb[ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, params, i, cfg, mm, rnd, state_dtype)
    x = rms(x, params[f"{P}_lnf_scale"], cfg["rms_norm_eps"])
    V = emb.shape[0]
    vb = vocab_block if V % vocab_block == 0 else V
    slabs = jax.lax.map(lambda e: mm(x, e.astype(F32).T),
                        emb.reshape(V // vb, vb, -1))
    return slabs.transpose(1, 0, 2).reshape(-1, V) / cfg["logits_scaling"]


def gaps_fn(cfg, control: str = ""):
    """As ``reference.gpt2.gaps_fn``: a jitted ``(params, ids[T], nxt[T])
    -> (served gaps[T], control's gaps[T])``: at row t, how far the
    reference's logit of ``nxt[t]`` (or of the control's own first choice)
    lies below the reference's best. ``control``: a precision of
    ``common.rounder`` for the matmul operands, or ``state:bf16`` for the
    scan's state kept in bf16 between tokens."""

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
