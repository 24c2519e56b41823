"""Plain reference for BERT pretraining (Devlin et al. 2019): post-LN
encoder, learned positions, masked-LM head tied to the word embedding (no
output bias, as the program has none) plus next-sentence head, Adam with a
constant rate. f32 throughout, every product at HIGHEST. No kernels, no
dropout (the configuration's rates are 0, see its file), rows in blocks so
that the activations of 32 x 512 in f32 fit beside nothing else.

Parameter names are the scope's: the runner plants ``make_weights(
param_spec(cfg), seed)`` under these names and the reference reads the same
dict.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import HIGHEST, gelu, layer_norm, rounder

LN_EPS = 1e-5
ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}


def _ln_names(cfg):
    """The program names its layer norms by creation order: the embedding's,
    two per encoder layer (after attention, after the FFN), the MLM head's."""
    names = {"emb": "layer_norm_0"}
    for i in range(cfg["num_layers"]):
        names[f"l{i}_att"] = f"layer_norm_{1 + 2 * i}"
        names[f"l{i}_ffn"] = f"layer_norm_{2 + 2 * i}"
    names["mlm"] = f"layer_norm_{1 + 2 * cfg['num_layers']}"
    return names


def param_spec(cfg: dict) -> dict:
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = f"normal:{cfg['initializer_range']}"
    spec = {"word_embedding": ((V, H), n),
            "pos_embedding": ((cfg["max_position"], H), n),
            "sent_embedding": ((cfg["type_vocab_size"], H), n),
            "mlm_trans_w": ((H, H), n), "mlm_trans_b": ((H,), "zeros"),
            "pooler_w": ((H, H), n), "pooler_b": ((H,), "zeros"),
            "nsp_w": ((H, 2), n), "nsp_b": ((2,), "zeros")}
    for ln in _ln_names(cfg).values():
        spec[f"{ln}.w_0"] = ((H,), "ones")
        spec[f"{ln}.b_0"] = ((H,), "zeros")
    for i in range(cfg["num_layers"]):
        p = f"layer{i}"
        for m in ("q", "k", "v", "out"):
            spec[f"{p}_att_{m}_w"] = ((H, H), n)
            spec[f"{p}_att_{m}_b"] = ((H,), "zeros")
        spec[f"{p}_ffn1_w"] = ((H, F), n)
        spec[f"{p}_ffn1_b"] = ((F,), "zeros")
        spec[f"{p}_ffn2_w"] = ((F, H), n)
        spec[f"{p}_ffn2_b"] = ((H,), "zeros")
    return spec


def _loss_sums(params, feed, cfg, rnd):
    """(sum of masked-LM cross entropies, sum of next-sentence cross
    entropies) over the rows of ``feed``; the caller divides by the whole
    batch's counts, so rows can come in blocks."""
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    ln = _ln_names(cfg)
    norm = lambda x, k: layer_norm(x, params[f"{ln[k]}.w_0"],
                                   params[f"{ln[k]}.b_0"], LN_EPS)
    nh = cfg["num_heads"]
    hd = cfg["hidden_size"] // nh
    B, S = feed["src_ids"].shape
    x = (params["word_embedding"][feed["src_ids"]]
         + params["pos_embedding"][feed["pos_ids"]]
         + params["sent_embedding"][feed["sent_ids"]])
    x = norm(x, "emb")
    bias = ((feed["input_mask"] - 1.0) * 10000.0)[:, None, None, :]

    def layer(x, i):
        p = f"layer{i}_att"
        heads = lambda t: t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
        q, k, v = (heads(mm(x, params[f"{p}_{m}_w"]) + params[f"{p}_{m}_b"])
                   for m in ("q", "k", "v"))
        s = jnp.einsum("bhqd,bhkd->bhqk", rnd(q), rnd(k),
                       precision=HIGHEST) / math.sqrt(hd) + bias
        a = jax.nn.softmax(s, axis=-1)
        c = jnp.einsum("bhqk,bhkd->bhqd", rnd(a), rnd(v), precision=HIGHEST)
        c = c.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        x = norm(x + mm(c, params[f"{p}_out_w"]) + params[f"{p}_out_b"],
                 f"l{i}_att")
        f = f"layer{i}"
        h = gelu(mm(x, params[f"{f}_ffn1_w"]) + params[f"{f}_ffn1_b"])
        h = mm(h, params[f"{f}_ffn2_w"]) + params[f"{f}_ffn2_b"]
        return norm(x + h, f"l{i}_ffn")

    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(functools.partial(layer, i=i))(x)

    h = gelu(mm(x, params["mlm_trans_w"]) + params["mlm_trans_b"])
    h = norm(h, "mlm")
    labels = feed["mask_label"]
    logits = jnp.matmul(rnd(h), rnd(params["word_embedding"]).T,
                        precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mlm_sum = -jnp.sum(jnp.where(labels >= 0, picked, 0.0))

    pooled = jnp.tanh(mm(x[:, 0, :], params["pooler_w"]) + params["pooler_b"])
    nsp_logp = jax.nn.log_softmax(
        mm(pooled, params["nsp_w"]) + params["nsp_b"], axis=-1)
    nsp_sum = -jnp.sum(jnp.take_along_axis(
        nsp_logp, feed["next_sent_label"], axis=-1))
    return mlm_sum, nsp_sum


def loss_and_grads(params, feed, cfg, precision="f32", row_block=8):
    """Loss of the whole batch and its gradient, rows in blocks of
    ``row_block`` (sums are linear in the rows, so blocks add up exactly
    as the whole would, to rounding)."""
    rnd = rounder(precision)
    B = feed["src_ids"].shape[0]
    row_block = min(row_block, B)
    if B % row_block:
        raise ValueError(f"batch {B} is not a multiple of {row_block}")
    n_masked = jnp.maximum(
        jnp.sum((feed["mask_label"] >= 0).astype(jnp.float32)), 1.0)

    def block_loss(p, blk):
        mlm_sum, nsp_sum = _loss_sums(p, blk, cfg, rnd)
        return mlm_sum / n_masked + nsp_sum / B

    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((B // row_block, row_block) + a.shape[1:]), feed)

    def body(carry, blk):
        loss, grads = carry
        l, g = jax.value_and_grad(block_loss)(params, blk)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
    return loss, grads


def adam_step(params, grads, m1, m2, step, lr):
    """Adam as the 2015 paper has it (epsilon outside the root), ``step``
    counted from 1."""
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["epsilon"]
    lr_t = lr * jnp.sqrt(1.0 - b2 ** step) / (1.0 - b1 ** step)
    tm = jax.tree_util.tree_map
    m1 = tm(lambda m, g: b1 * m + (1 - b1) * g, m1, grads)
    m2 = tm(lambda m, g: b2 * m + (1 - b2) * g * g, m2, grads)
    params = tm(lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + eps),
                params, m1, m2)
    return params, m1, m2


def follow(params, feeds, cfg, lr, precision="f32", row_block=8):
    """Drive ``len(feeds)`` training steps from ``params``. Returns each
    step's loss, the first gradient (on the device) with its norm by leaf,
    and the norm of each leaf's change over all the steps."""
    @jax.jit
    def step(p, m1, m2, feed, t):
        loss, g = loss_and_grads(p, feed, cfg, precision, row_block)
        p, m1, m2 = adam_step(p, g, m1, m2, t, lr)
        return p, m1, m2, loss, g

    p = params
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, feed in enumerate(feeds, 1):
        feed = {k: jnp.asarray(v) for k, v in feed.items()}
        p, m1, m2, loss, g = step(p, m1, m2, feed, jnp.float32(t))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g
        del g
    norms = jax.jit(lambda d: {k: jnp.linalg.norm(v) for k, v in d.items()})
    delta = jax.jit(lambda a, b: {k: jnp.linalg.norm(a[k] - b[k])
                                  for k in a})(p, params)
    return {"losses": losses, "first_grad": first_grad,
            "grad_norm": {k: float(v)
                          for k, v in norms(first_grad).items()},
            "delta_norm": {k: float(v) for k, v in delta.items()}}
