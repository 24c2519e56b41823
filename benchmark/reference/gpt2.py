"""Plain reference for GPT-2 (Radford et al. 2019): pre-LN causal decoder,
learned positions, exact gelu, logits through the tied word embedding. One
full forward pass over a whole sequence in f32 with every product at
HIGHEST: no cache, no kernels, no batching. Parameter names are the
scope's (``gpt_*``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, gelu, layer_norm, rounder

LN_EPS = 1e-5


def param_spec(cfg: dict) -> dict:
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = f"normal:{cfg['initializer_range']}"
    spec = {"gpt_word_emb": ((V, H), n),
            "gpt_pos_emb": ((cfg["max_position"], H), n),
            "gpt_lnf_scale": ((H,), "ones"), "gpt_lnf_bias": ((H,), "zeros")}
    for i in range(cfg["num_layers"]):
        p = f"gpt_l{i}"
        for ln in ("ln1", "ln2"):
            spec[f"{p}_{ln}_scale"] = ((H,), "ones")
            spec[f"{p}_{ln}_bias"] = ((H,), "zeros")
        for m in ("q", "k", "v", "out"):
            spec[f"{p}_{m}_w"] = ((H, H), n)
            spec[f"{p}_{m}_b"] = ((H,), "zeros")
        spec[f"{p}_ffn1_w"] = ((H, F), n)
        spec[f"{p}_ffn1_b"] = ((F,), "zeros")
        spec[f"{p}_ffn2_w"] = ((F, H), n)
        spec[f"{p}_ffn2_b"] = ((H,), "zeros")
    return spec


def logits(params, ids, cfg, precision="f32"):
    """``ids`` [T] int -> logits [T, V]: row t scores the token after
    ``ids[:t + 1]``. Padding after the real tokens is harmless, since no
    row looks to its right."""
    rnd = rounder(precision)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    T = ids.shape[0]
    nh = cfg["num_heads"]
    hd = cfg["hidden_size"] // nh
    x = params["gpt_word_emb"][ids] + params["gpt_pos_emb"][jnp.arange(T)]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["num_layers"]):
        p = f"gpt_l{i}"
        h = layer_norm(x, params[f"{p}_ln1_scale"], params[f"{p}_ln1_bias"],
                       LN_EPS)
        heads = lambda t: t.reshape(T, nh, hd).transpose(1, 0, 2)
        q, k, v = (heads(mm(h, params[f"{p}_{m}_w"]) + params[f"{p}_{m}_b"])
                   for m in ("q", "k", "v"))
        s = jnp.einsum("hqd,hkd->hqk", rnd(q), rnd(k),
                       precision=HIGHEST) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        c = jnp.einsum("hqk,hkd->hqd", rnd(a), rnd(v), precision=HIGHEST)
        c = c.transpose(1, 0, 2).reshape(T, nh * hd)
        x = x + mm(c, params[f"{p}_out_w"]) + params[f"{p}_out_b"]
        h = layer_norm(x, params[f"{p}_ln2_scale"], params[f"{p}_ln2_bias"],
                       LN_EPS)
        h = gelu(mm(h, params[f"{p}_ffn1_w"]) + params[f"{p}_ffn1_b"])
        x = x + mm(h, params[f"{p}_ffn2_w"]) + params[f"{p}_ffn2_b"]
    x = layer_norm(x, params["gpt_lnf_scale"], params["gpt_lnf_bias"], LN_EPS)
    return jnp.matmul(rnd(x), rnd(params["gpt_word_emb"]).T,
                      precision=HIGHEST)


def gaps_fn(cfg, control: str = ""):
    """A jitted ``(params, ids[T], nxt[T]) -> gaps[T]`` (and, with
    ``control``, the control's gaps too): at row t, how far the reference's
    logit of ``nxt[t]`` lies below the reference's best logit. ``control``
    names the lower precision whose own first choice is scored the same
    way, at every row."""

    @jax.jit
    def fn(params, ids, nxt):
        ref = logits(params, ids, cfg)
        best = jnp.max(ref, axis=-1)
        served = best - jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
        if not control:
            return served, served
        low = jnp.argmax(logits(params, ids, cfg, control), axis=-1)
        ctl = best - jnp.take_along_axis(ref, low[:, None], axis=-1)[:, 0]
        return served, ctl

    return fn
