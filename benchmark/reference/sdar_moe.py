"""Plain reference for the ``sdar_moe`` decoder (SDAR-30B-A3B-Chat; source
and assumptions in ``configs/sdar-30b-a3b-serve.json``): a Qwen3-MoE layer
and generation by diffusion over blocks. Full forward passes over whole
sequences in f32 with every product at HIGHEST: no kernels, no batching,
nothing of the program imported. Parameter names are the scope's
(``sdar_*``).

``N(x) = x / sqrt(mean(x^2) + eps) * w``, no bias anywhere. Every layer:
``a = x + Attn(N_in(x))``, ``y = a + MoE(N_post(a))``. Head: final ``N``,
then the untied ``lm_head``.

    Attn: ``q = N_q(h q_w)``, ``k = N_k(h k_w)`` (over a head's dims, each
        with its own weight), ``v = h v_w``; rotary on all of a head's dims
        in rotate-half pairs ``(j, j + D/2)``; query head n reads key/value
        head n // group; scores times head_dim^-1/2; softmax under the
        mask: with block length L, key j is visible to query i iff
        ``j // L <= i // L``.
    MoE: ``p = softmax(h Wr)`` over all experts; I = the top_k largest;
        ``w_e = p_e / sum_{j in I} p_j``; ``sum_{e in I} w_e E_e(h)`` with
        ``E(h) = (silu(h Wg) * (h Wu)) Wd``. No shared expert.

Generation (:func:`generate`; ``L`` = ``block_length``, ``T`` =
``denoising_steps``, ``M`` = ``mask_token_id``): positions 0, 1, 2, ...; the
sequence is ``ceil((P + G) / L)`` blocks. The first ``P // L`` blocks are
the prompt's; the ``P % L`` tokens left over open the next block as known
tokens. For each further block, starting with every unknown position =
``M``, for t = 0 .. T: if no position is ``M`` the block is final ("commit":
what a cache would keep is the K/V of these tokens; here nothing is kept
and every forward runs the whole sequence again); else one forward over
``[prompt; earlier blocks; the block]`` and, at every masked position, the
greedy token ``x0`` (``M`` itself excluded: its logit counts as minus
infinity) and its log-confidence ``log softmax(logits)[x0]`` (row i scores
position i's own token: no shift); the ``n_t`` masked positions of largest
confidence (lower position first among equals) take their ``x0``, ``n_t =
L // T (+ 1 for t < L % T)`` clipped to what is still masked. The answer is
the first G tokens after the prompt.

An expert is given the rows that chose it, gathered (up to an eighth of
the sequence; past that, every row, weighted 0 where it did not choose it:
the same sum either way). Weights are stored in the configuration's
storage type (bf16) and upcast here a block at a time, so that a 2,048-row
pass fits beside 8.7 GB of them.

Note for the comparison on the chip (:func:`block_check_fn`): under the
block mask the rows before a block do not depend on the block's state, so
their keys and values are computed once per request, by one full pass over
the final sequence (:func:`keys_values`), and each state of a block is then
run as the block's L rows against them (:func:`block_logits`): the same
numbers as a full pass over ``[rows before; the block's state]``, which
``benchmark/tests/test_blocks_metrics.py`` holds it to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST, rounder, seed_key

P = "sdar"
F32 = jnp.float32


def model_config(cfg: dict) -> dict:
    """The sizes the family and this reference read, from the keys of the
    configuration's file (the model's published ``config.json`` keys at
    its top level, ``deployment`` and ``block_diffusion``)."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "rms_norm_eps", "initializer_range")
    m = {k: cfg[k] for k in keys}
    m["num_experts_total"] = cfg["deployment"]["num_experts_total"]
    m["expert_offset"] = cfg["deployment"]["expert_offset"]
    m["storage"] = cfg["storage_dtype"]
    for k in ("block_length", "denoising_steps", "mask_token_id"):
        m[k] = cfg["block_diffusion"][k]
    return m


def param_spec(cfg: dict) -> dict:
    """name -> (shape, kind, dtype). Kinds: ``normal:<std>`` (truncated at
    two), ``uniform:<lo>:<hi>`` (norm scales, around their neutral 1)."""
    H, F, V = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    Eh, E = cfg["num_experts"], cfg["num_experts_total"]
    n, st, around1 = (f"normal:{cfg['initializer_range']}", cfg["storage"],
                      "uniform:0.9:1.1")
    spec = {f"{P}_word_emb": ((V, H), n, st),
            f"{P}_lm_head": ((V, H), n, st),
            f"{P}_lnf_scale": ((H,), around1, "float32")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}_l{i}"
        for name, dim in (("ln_in", H), ("ln_post", H), ("qnorm", hd),
                          ("knorm", hd)):
            spec[f"{p}_{name}_scale"] = ((dim,), around1, "float32")
        for name, shape in (
                ("q", (H, nh * hd)), ("k", (H, nkv * hd)),
                ("v", (H, nkv * hd)), ("out", (nh * hd, H)),
                ("router", (H, E)), ("gate", (Eh, H, F)), ("up", (Eh, H, F)),
                ("down", (Eh, F, H))):
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


# -- the layer ----------------------------------------------------------------

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, pos, theta):
    """x [heads, T, D]: dims ``j`` and ``j + D/2`` turn by
    ``pos * theta^(-2j/D)``."""
    D = x.shape[-1]
    ang = pos[:, None].astype(F32) * theta ** (
        -jnp.arange(0, D, 2, dtype=F32) / D)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(h, params, p, cfg, pos, mm):
    """The rows' queries [heads, T, D], keys and values [kv heads, T, D],
    normed and turned to their positions ``pos`` [T]."""
    T = h.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads = lambda t, n: t.reshape(T, n, hd).transpose(1, 0, 2)
    proj = lambda name, n: mm(h, params[f"{p}_{name}_w"].astype(F32)
                              ).reshape(T, n, hd)
    q = rotary(heads(rms(proj("q", nh), params[f"{p}_qnorm_scale"], eps),
                     nh), pos, theta)
    k = rotary(heads(rms(proj("k", nkv), params[f"{p}_knorm_scale"], eps),
                     nkv), pos, theta)
    return q, k, heads(proj("v", nkv), nkv)


def attend(q, k, v, seen, rnd):
    """q [heads, Tq, D] over k, v [kv heads, Tk, D] under ``seen``
    [Tq, Tk], a query head at a time -> [Tq, heads x D]."""
    nh, Tq, hd = q.shape
    G = nh // k.shape[0]

    def head(n):
        s = jnp.matmul(rnd(q[n]), rnd(k[n // G]).T,
                       precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(rnd(a), rnd(v[n // G]), precision=HIGHEST)

    c = jax.lax.map(head, jnp.arange(nh))
    return c.transpose(1, 0, 2).reshape(Tq, nh * hd)


def route(h, wr, top_k):
    """softmax scores, the top_k largest (lower index first among equals),
    and their weights normalised over the chosen ones; f32, unrounded."""
    s = jax.nn.softmax(jnp.matmul(h, wr.astype(F32), precision=HIGHEST),
                       axis=-1)
    vals, idx = jax.lax.top_k(s, top_k)
    return idx, vals / jnp.sum(vals, axis=-1, keepdims=True)


def moe(h, params, p, cfg, mm):
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


def layer(x, params, i, cfg, pos, mm, rnd, seen, before=None):
    """One layer on the rows ``x`` [T, H] at positions ``pos``. ``seen``
    [T, keys]: what each row may see of the keys, which are the rows' own
    or, with ``before`` = (k, v) [kv heads, Tb, D], those followed by the
    rows' own. Returns the new rows and the rows' own (k, v)."""
    p = f"{P}_l{i}"
    eps = cfg["rms_norm_eps"]
    q, k, v = qkv(rms(x, params[f"{p}_ln_in_scale"], eps), params, p, cfg,
                  pos, mm)
    keys, vals = (k, v) if before is None else (
        jnp.concatenate([before[0], k], axis=1),
        jnp.concatenate([before[1], v], axis=1))
    x = x + mm(attend(q, keys, vals, seen, rnd),
               params[f"{p}_out_w"].astype(F32))
    return x + moe(rms(x, params[f"{p}_ln_post_scale"], eps), params, p, cfg,
                   mm), (k, v)


def head(x, params, cfg, mm, vocab_block=9496):
    x = rms(x, params[f"{P}_lnf_scale"], cfg["rms_norm_eps"])
    w = params[f"{P}_lm_head"]
    V = w.shape[0]
    vb = vocab_block if V % vocab_block == 0 else V
    slabs = jax.lax.map(lambda e: mm(x, e.astype(F32).T),
                        w.reshape(V // vb, vb, -1))
    return slabs.transpose(1, 0, 2).reshape(-1, V)


def _ops(precision):
    rnd = rounder(precision)
    return rnd, lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def block_mask(T: int, L: int):
    at = jnp.arange(T) // L
    return at[None, :] <= at[:, None]


def logits(params, ids, cfg, precision="f32"):
    """``ids`` [T] int -> logits [T, V] under the block mask: row t scores
    position t's own token. Padding after the real tokens is harmless to
    the rows of every block that holds none of it. ``precision`` rounds
    every matmul operand but the router's."""
    rnd, mm = _ops(precision)
    T = ids.shape[0]
    pos, seen = jnp.arange(T), block_mask(T, cfg["block_length"])
    x = params[f"{P}_word_emb"][ids].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x, _ = layer(x, params, i, cfg, pos, mm, rnd, seen)
    return head(x, params, cfg, mm)


# -- generation -----------------------------------------------------------------

def n_transfer(t: int, L: int, T: int) -> int:
    return L // T + (1 if t < L % T else 0)


def reveal(lg, masked, n: int, M: int):
    """From a block's logits ``lg`` [L, V] (numpy) and its masked positions:
    ``(x0 [L], confidence [L], chosen)``: the greedy token and its
    log-confidence everywhere (``M`` excluded), and the ``n`` masked
    positions of largest confidence, the lower position first among
    equals."""
    lg = np.array(lg, np.float32)
    lg[:, M] = -np.inf
    x0 = lg.argmax(axis=-1)
    top = lg.max(axis=-1)
    conf = top - (top + np.log(np.exp(lg - top[:, None]).sum(axis=-1)))
    order = sorted(np.nonzero(masked)[0], key=lambda i: (-conf[i], i))
    return x0, conf, order[:n]


def generate(params, prompt, max_new: int, cfg, logits_fn=None):
    """The generation loop above for one request. Returns ``(tokens
    [max_new], revealed_at [max_new], forwards)``: the answer, the forward
    of its block at which each token was revealed, and per forward ``(first
    row, state [L], logits [L, V] or None on a commit)``."""
    L, T, M = (cfg["block_length"], cfg["denoising_steps"],
               cfg["mask_token_id"])
    logits_fn = logits_fn or jax.jit(
        lambda ids: logits(params, ids, cfg))
    prompt = np.asarray(prompt, np.int64)
    Pn = len(prompt)
    total = -(-(Pn + max_new) // L) * L
    seq = np.full(total, M, np.int64)
    seq[:Pn] = prompt
    at = np.full(total, -1, np.int64)
    forwards = []
    for start in range(Pn // L * L, total, L):
        blk = slice(start, start + L)
        for t in range(T + 1):
            masked = seq[blk] == M
            if not masked.any():
                forwards.append((start, seq[blk].copy(), None))
                break
            # the rows after the block are all M still, and no row of the
            # block sees them
            lg = np.asarray(logits_fn(jnp.asarray(seq)))[blk]
            forwards.append((start, seq[blk].copy(), lg))
            x0, _, chosen = reveal(lg, masked, n_transfer(t, L, T), M)
            for i in chosen:
                seq[start + i], at[start + i] = x0[i], t
    return seq[Pn:Pn + max_new], at[Pn:Pn + max_new], forwards


# -- the comparison on the chip ------------------------------------------------

def keys_values(params, ids, cfg, precision="f32"):
    """One full pass over ``ids`` [T] under the block mask; every layer's
    keys and values, [layers, 2, kv heads, T, D]. No head."""
    rnd, mm = _ops(precision)
    T = ids.shape[0]
    pos, seen = jnp.arange(T), block_mask(T, cfg["block_length"])
    x = params[f"{P}_word_emb"][ids].astype(F32)
    kvs = []
    for i in range(cfg["num_hidden_layers"]):
        x, kv = layer(x, params, i, cfg, pos, mm, rnd, seen)
        kvs.append(jnp.stack(kv))
    return jnp.stack(kvs)


def block_logits(params, kvs, start, state, cfg, precision="f32"):
    """The logits [L, V] of a block whose rows hold ``state`` [L] and start
    at row ``start`` (traced), against ``kvs`` (:func:`keys_values` of the
    final sequence) for the rows before it and the block itself."""
    rnd, mm = _ops(precision)
    L, Tb = state.shape[0], kvs.shape[3]
    pos = start + jnp.arange(L)
    seen = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(Tb)[None, :] < start, (L, Tb)),
         jnp.ones((L, L), bool)], axis=1)
    x = params[f"{P}_word_emb"][state].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x, _ = layer(x, params, i, cfg, pos, mm, rnd, seen,
                     before=(kvs[i, 0], kvs[i, 1]))
    return head(x, params, cfg, mm)


def block_states(seq, prompt_len: int, revealed_at, block: int, L: int,
                 M: int):
    """The states of ``block`` (its first row ``block x L``) that can be
    rebuilt from a served answer (``seq``: the prompt and the answer;
    ``revealed_at``: the forward of its block each answer token was
    revealed at), one per denoise forward ``t``: ``(t, state [L], revealed
    [L] bool)``, the block before forward ``t`` (the positions revealed at
    or after ``t`` masked) and the positions forward ``t`` revealed. A
    block that ends past the answer gives its forward 0 alone: what became
    of its unserved positions is not known."""
    seq = np.asarray(seq, np.int64)
    n, never = len(seq), np.iinfo(np.int64).max
    at = np.concatenate([np.full(prompt_len, -1, np.int64),
                         np.asarray(revealed_at, np.int64)])
    rows = np.arange(block * L, (block + 1) * L)
    have = rows < n
    tok = np.where(have, seq[np.minimum(rows, n - 1)], M)
    when = np.where(have, at[np.minimum(rows, n - 1)], never)
    last = int(when[have].max()) if have.all() else 0
    return [(t, np.where(when >= t, M, tok), have & (when == t))
            for t in range(last + 1)]


def block_check_fn(cfg, control: str = ""):
    """A jitted ``(params, kvs, low_kvs, start, state [L], tokens [L],
    revealed [L] bool, n) -> (logit gap, confidence gap, the control's
    two)`` for one
    denoise forward of one block: over the positions the forward revealed,
    the widest gap by which a revealed token's reference logit lies below
    the reference's best at its position, and the widest by which a
    revealed position's reference log-confidence lies below the ``n``-th
    best masked position's (0 where the reference would reveal the same
    positions). The control's are those of what the reference computed at
    ``control`` would reveal of the same state: its own ``n`` positions
    and greedy tokens, scored the same way."""
    M = cfg["mask_token_id"]

    def scores(lg):
        lg = jnp.where(jnp.arange(lg.shape[-1]) == M, -jnp.inf, lg)
        return lg, jnp.max(lg, axis=-1) - jax.nn.logsumexp(lg, axis=-1)

    def chosen(conf, masked, n):
        """The ``n`` most confident masked positions, [L] bool."""
        c = jnp.where(masked, conf, -jnp.inf)
        i, j = jnp.arange(c.shape[0])[:, None], jnp.arange(c.shape[0])[None]
        ahead = (c[None, :] > c[:, None]) | ((c[None, :] == c[:, None])
                                             & (j < i))
        return masked & (jnp.sum(ahead & masked[None, :], axis=1) < n)

    def gaps(ref, conf, masked, n, tokens, revealed):
        best = jnp.max(ref, axis=-1)
        mine = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
        nth = jnp.sort(jnp.where(masked, conf, -jnp.inf))[::-1][n - 1]
        none = -jnp.inf
        return (jnp.max(jnp.where(revealed, best - mine, none)),
                jnp.max(jnp.where(revealed, nth - conf, none)))

    @jax.jit
    def fn(params, kvs, low_kvs, start, state, tokens, revealed, n):
        masked = state == M
        ref, conf = scores(block_logits(params, kvs, start, state, cfg))
        served = gaps(ref, conf, masked, n, tokens, revealed)
        if not control:
            return served + served
        low, low_conf = scores(block_logits(params, low_kvs, start, state,
                                            cfg, control))
        theirs = chosen(low_conf, masked, n)
        return served + gaps(ref, conf, masked, n,
                             jnp.argmax(low, axis=-1), theirs)

    return fn
