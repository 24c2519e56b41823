"""Sparse-expert and rotary ops: a routed-expert layer that is told which
experts it holds, and rotary position embedding.

``moe_experts`` is what expert parallelism asks of one chip: it routes
every token over ALL ``num_experts`` experts (scores by ``score_fn``: a
sigmoid of each logit, or a softmax over all of them; the ``top_k``
largest, weights normalised over the chosen ones — all in f32) and
computes the part of ``sum_e w_e E_e(x)`` that the ``experts_held``
experts from ``expert_offset`` on contribute, each a gated feed-forward
``(silu(x Wg) * (x Wu)) Wd`` with bf16 operands and f32 accumulation. With
all experts held it is the whole layer. What the absent experts would add
is left out, and nothing stands in for the exchange that would fetch it.
No token is dropped for capacity: the row buffer holds the worst case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import IOSpec, register_op, x
from .. import flags
from ..lowering import lowering_platform, note_kernel_route

# rows of one grouped-matmul tile: 16 (one packed bf16 sublane tile) while
# a held expert expects a handful of rows, as in a decode step, where the
# kernel streams weights; 256 once it expects a few hundred, as in a large
# prefill, where a tile has to keep the MXU busy for the weights it loads;
# between the two, the power of two that holds the rows an expert expects
# under even routing, so that an expert is one or two tiles and each row
# tile meets the expert's weights in one MXU pass, not one for every 16 of
# its rows (the weights themselves cross HBM once a call whatever the
# tile: the kernel keeps a block resident over an expert's tiles)
_TM_SMALL, _TM_LARGE = 16, 256


def _tile_rows(expected: int) -> int:
    """Rows of a tile for an expert that expects ``expected`` rows."""
    tm = _TM_SMALL
    while tm < min(expected, _TM_LARGE):
        tm *= 2
    return tm


def expert_tile_rows(tokens: int, top_k: int, num_experts: int) -> int:
    """Rows of a tile in a ``moe_experts`` op over ``tokens`` rows: what
    the grouped route tiles by, and what the serving layer counts an
    execution's live tiles by (``ceil(assignments / rows)`` a hit expert)."""
    return _tile_rows(tokens * top_k // num_experts)


def program_tile_rows(stats_var) -> list:
    """Rows of a grouped-matmul tile in each ``moe_experts`` op of the
    program that stacks their ``Stats`` into ``stats_var``, in program order
    (which is the order they are stacked in): the op's own rule over its
    static shapes."""
    block = stats_var.block
    rows = [expert_tile_rows(
        int(np.prod(block.var(op.input("X")[0]).shape[:-1])),
        int(op.attr("top_k")), int(op.attr("num_experts")))
        for op in block.ops if op.type == "moe_experts"]
    if len(rows) != stats_var.shape[0]:
        raise ValueError(
            f"{stats_var.name} stacks {stats_var.shape[0]} layers' counts, "
            f"the program has {len(rows)} moe_experts ops")
    return rows


def expert_counter(stats_var, layers=()):
    """What counts the ``Stats`` a program stacks into ``stats_var``, for a
    net's ``counted``: :func:`count_expert_stats` with the ops' layers
    (where they are not all of them) and the program's tile rows bound."""
    return functools.partial(count_expert_stats, layers=layers,
                             tile_rows=program_tile_rows(stats_var))


def count_expert_stats(phase: str, stats, sums, layers, tile_rows) -> dict:
    """What a serving dispatch's expert ops counted (``moe_experts``
    ``Stats``, [..., layers, experts_held + 2]; a chained decode stacks its
    steps in front), onto the monitor: per layer and execution the
    assignments each held expert received, all assignments made, and local
    assignments that found no row. ``layers`` names the ops' layers where
    they are not all of them; ``tile_rows`` is a layer's rows of a
    grouped-matmul tile (:func:`program_tile_rows`), by which its live
    tiles are counted. Returns what this one dispatch added to
    ``moe_expert_tokens_total``, ``moe_experts_hit_total`` and
    ``moe_expert_calls_total`` (the engine puts it on the dispatch's settle
    span). ``sums`` (a ``collections.Counter`` the engine was
    built with) holds the two running sums behind
    ``moe_local_assignment_share``."""
    from .. import monitor

    stats = stats.reshape((-1,) + stats.shape[-2:]).astype(np.int64)
    load, made, dropped = stats[..., :-2], stats[..., -2], stats[..., -1]
    tokens = monitor.counter(
        "moe_expert_tokens_total",
        "token assignments the held experts received, by layer and "
        "phase of the dispatch")
    hit = monitor.counter(
        "moe_experts_hit_total",
        "held experts that received at least one token, summed over "
        "the expert op's executions")
    calls = monitor.counter(
        "moe_expert_calls_total", "executions of the expert op")
    tiles = monitor.counter(
        "moe_expert_tiles_total",
        "row tiles of the grouped expert matmul that held rows "
        "(ceil(assignments / tile rows) a hit expert), summed over the "
        "expert op's executions: over moe_experts_hit_total, the tiles "
        "that rode one fetch of an expert's weights")
    for j in range(stats.shape[1]):
        lab = dict(layer=str(layers[j] if layers else j), phase=phase)
        tokens.labels(**lab).inc(float(load[:, j].sum()))
        hit.labels(**lab).inc(float((load[:, j] > 0).sum()))
        tiles.labels(**lab).inc(float(
            (-(-load[:, j] // tile_rows[j])).sum()))
        calls.labels(**lab).inc(float(stats.shape[0]))
    mean = load.mean(axis=-1)
    skew = monitor.histogram(
        "moe_expert_load_max_over_mean",
        "per execution of the expert op, the busiest held expert's "
        "assignments over the mean of the held experts' (1 = even)")
    for v in (load.max(axis=-1)[mean > 0] / mean[mean > 0]).ravel():
        skew.observe(float(v))
    held = monitor.histogram(
        "moe_held_assignments_per_step",
        "per execution of the expert op (one step of one layer), the "
        "assignments that fell on the experts held here: the load a "
        "seed's router deals this share of the deployment").labels(
        phase=phase)
    for v in load.sum(axis=-1).ravel():
        held.observe(float(v))
    sums["moe_local"] += int(load.sum())
    sums["moe_made"] += int(made.sum())
    monitor.gauge(
        "moe_local_assignment_share",
        "share of all token-to-expert assignments that fell on experts "
        "held here, since the engine was built (experts_held / "
        "num_experts under even routing)"
    ).set(sums["moe_local"] / max(sums["moe_made"], 1))
    monitor.counter(
        "moe_dropped_assignments_total",
        "local assignments the expert op found no buffer row for; the "
        "buffer holds the worst case, so anything but 0 is a bug"
    ).inc(float(dropped.sum()))
    # this ONE dispatch's share of the three counters above, for its span
    return {"moe_expert_tokens": int(load.sum()),
            "moe_experts_hit": int((load > 0).sum()),
            "moe_expert_calls": int(stats.shape[0] * stats.shape[1])}


def _route_moe(T: int, H: int, platform) -> str:
    mode = flags.flag("use_flash_attention")
    if mode == "never" or T % 8 or H % 128:
        return "primitive"
    if platform == "tpu":
        return "pallas"
    return "pallas-interpret" if mode == "always" else "primitive"


def route_tokens(scores, top_k: int, select_bias=None, scale: float = 1.0):
    """scores [T, E] f32 -> (experts [T, k] int32, weights [T, k] f32):
    the ``top_k`` largest scores of each token (the lower index first among
    equal scores) and the scores normalised over the chosen ones. With
    ``select_bias`` [E] the experts are the ``top_k`` largest of ``score +
    bias`` and the weights still the scores themselves, normalised over
    the chosen; ``scale`` multiplies the weights."""
    if select_bias is None:
        vals, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + select_bias.astype(scores.dtype),
                               top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    weights = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return idx.astype(jnp.int32), weights


def _dense_held(xb, wg, wu, wd, combine):
    """Every held expert over every token, weighted by ``combine`` [T, Eh]
    (0 where the token did not choose the expert): the primitive route."""
    def one(args):
        g, u, d, c = args
        mm = lambda a, b: jnp.matmul(a, b,
                                     preferred_element_type=jnp.float32)
        h = (jax.nn.silu(mm(xb, g)) * mm(xb, u)).astype(xb.dtype)
        return mm(h, d) * c[:, None]

    return jnp.sum(jax.lax.map(one, (wg, wu, wd, combine.T)), axis=0)


def _grouped_held(xb, wg, wu, wd, le, local, weights, counts, num_experts,
                  interpret):
    """The Pallas route: local assignments sorted by expert into whole
    tiles, two grouped matmuls, and each token's rows gathered back."""
    from ..kernels.moe import grouped_matmul

    T, k = le.shape
    Eh = wg.shape[0]
    A = T * k
    # rows a held expert expects under even routing
    tm = expert_tile_rows(T, k, num_experts)
    M = -(-A // tm) * tm + Eh * tm           # every assignment local
    flat_e = jnp.where(local, le, Eh).reshape(A)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    rank_sorted = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32))
    padded = -(-counts // tm) * tm
    pend = jnp.cumsum(padded)
    pstart, ustart = pend - padded, jnp.cumsum(counts) - counts
    tile_expert = jnp.minimum(jnp.searchsorted(
        pend, jnp.arange(M // tm, dtype=jnp.int32) * tm, side="right"),
        Eh - 1).astype(jnp.int32)
    n_valid = (pend[-1] // tm).astype(jnp.int32)

    rows = jnp.arange(M, dtype=jnp.int32)
    e_r = jnp.repeat(tile_expert, tm)
    rank = rows - pstart[e_r]
    valid = (rank < counts[e_r]) & (rows < pend[-1])
    src = order[jnp.clip(ustart[e_r] + rank, 0, A - 1)]
    x_rows = jnp.where(valid[:, None], xb[src // k], 0)
    h = grouped_matmul(x_rows, wg, tile_expert, n_valid, tm=tm, rhs2=wu,
                       out_dtype=xb.dtype, interpret=interpret)
    y = grouped_matmul(h, wd, tile_expert, n_valid, tm=tm,
                       out_dtype=jnp.float32, interpret=interpret)

    # each token gathers the rows of its own local assignments
    e_a = jnp.minimum(flat_e, Eh - 1)
    pos = (pstart[e_a] + rank_sorted - ustart[e_a]).reshape(T, k)
    out = jnp.zeros((T, y.shape[1]), jnp.float32)
    for j in range(k):
        row = jnp.where(local[:, j], pos[:, j], 0)
        out = out + jnp.where(local[:, j, None],
                              weights[:, j, None] * y[row], 0.0)
    return out, jnp.sum(local) - jnp.sum(valid)


@register_op(
    "moe_experts",
    inputs=[IOSpec("X"), IOSpec("RouterW"), IOSpec("GateW"), IOSpec("UpW"),
            IOSpec("DownW"), IOSpec("TokenMask", optional=True, no_grad=True),
            IOSpec("SelectBias", optional=True, no_grad=True)],
    outputs=["Out", "Stats"],
    attrs={"num_experts": 0, "top_k": 1, "expert_offset": 0,
           "score_fn": "sigmoid", "route_scale": 1.0},
    grad=None)
def _moe_experts(ctx, ins, attrs):
    """``X`` [..., H] (f32: the router reads it unrounded); ``RouterW``
    [H, num_experts]; ``GateW``/``UpW`` [experts_held, H, F] and ``DownW``
    [experts_held, F, H] hold experts ``expert_offset ..
    expert_offset + experts_held - 1``. ``score_fn``: ``sigmoid`` scores
    each expert alone, ``softmax`` all ``num_experts`` against each other;
    either way the chosen scores are divided by their sum, and multiplied
    by ``route_scale``. ``SelectBias`` (optional, [num_experts] f32): the
    ``top_k`` are taken of ``score + bias``; the weights are the unbiased
    scores of the chosen (a bias that balances load without entering the
    output). ``Out`` [..., H] f32: the held experts' part of the routed
    sum. ``TokenMask`` (optional, ``X``'s
    leading shape, > 0 = a real token): padding, and the rows of sequences
    that a dispatch does not serve, are routed nowhere; they cost the
    experts nothing and their ``Out`` rows are 0 (identical padding rows
    would otherwise all land on the same ``top_k`` experts, a load that
    depends on nothing but the weights). ``Stats`` [experts_held + 2]
    int32: assignments each held expert received, all assignments made
    (``real tokens * top_k``), and local assignments that found no row in
    the buffer (0 by construction; the serving layer counts it)."""
    from ..kernels.moe import router_scores, router_scores_reference

    xv, wr = x(ins, "X"), x(ins, "RouterW")
    wg, wu, wd = x(ins, "GateW"), x(ins, "UpW"), x(ins, "DownW")
    E, k = int(attrs["num_experts"]), int(attrs["top_k"])
    off, Eh = int(attrs["expert_offset"]), wg.shape[0]
    score_fn = str(attrs.get("score_fn", "sigmoid"))
    if (wr.shape[-1] != E or not 0 < k <= E or not 0 <= off <= E - Eh
            or score_fn not in ("sigmoid", "softmax")):
        raise ValueError(
            f"moe_experts: router {wr.shape} for num_experts={E}, top_k={k}, "
            f"{Eh} experts held from {off}, score_fn={score_fn!r}")
    lead, H = xv.shape[:-1], xv.shape[-1]
    x2 = xv.reshape(-1, H)
    T = x2.shape[0]
    route = _route_moe(T, H, lowering_platform(ctx))
    note_kernel_route(ctx, "moe_experts", route)
    interpret = route == "pallas-interpret"
    with jax.named_scope("moe_router"):
        if route == "primitive":
            scores = router_scores_reference(x2, wr, score_fn)
        else:
            scores = router_scores(x2, wr, score_fn=score_fn,
                                   interpret=interpret)
        experts, weights = route_tokens(
            scores, k, x(ins, "SelectBias"),
            float(attrs.get("route_scale", 1.0)))
    local = (experts >= off) & (experts < off + Eh)
    made = jnp.int32(T * k)
    mask = x(ins, "TokenMask")
    if mask is not None:
        real = mask.reshape(T) > 0
        local = local & real[:, None]
        made = jnp.sum(real).astype(jnp.int32) * k
    le = experts - off
    hit = local[:, :, None] & (le[:, :, None] == jnp.arange(Eh))
    counts = jnp.sum(hit, axis=(0, 1)).astype(jnp.int32)
    xb = x2.astype(wg.dtype)
    if route == "primitive":
        combine = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
        out = _dense_held(xb, wg, wu, wd, combine)
        dropped = jnp.int32(0)
    else:
        out, dropped = _grouped_held(xb, wg, wu, wd, le, local, weights,
                                     counts, E, interpret)
    stats = jnp.concatenate([counts, jnp.stack([
        made, dropped.astype(jnp.int32)])])
    return {"Out": [out.reshape(lead + (H,))], "Stats": [stats]}


_YARN = ("factor", "original_max_position", "beta_fast", "beta_slow",
         "mscale", "mscale_all_dim")


def yarn_attrs(attrs) -> dict:
    """The YaRN attribute set of a ``rotary_embedding`` op (its
    ``yarn_*`` attributes without the prefix), or {} where the op has none
    (``yarn_factor`` 0)."""
    if not float(attrs.get("yarn_factor", 0.0) or 0.0):
        return {}
    return {k: float(attrs[f"yarn_{k}"]) for k in _YARN}


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(s) = 0.1 s ln(factor) + 1`` (1 at a factor of 1 or below)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * np.log(factor) + 1.0


def yarn_cos_scale(factor, mscale, mscale_all_dim, **_) -> float:
    """What YaRN multiplies cos and sin by: ``m(mscale) /
    m(mscale_all_dim)``."""
    return float(yarn_mscale(factor, mscale)
                 / yarn_mscale(factor, mscale_all_dim))


def yarn_softmax_scale(head_dim: int, factor: float,
                       mscale_all_dim: float) -> float:
    """The attention's softmax scale under YaRN: ``head_dim^-1/2 x
    m(mscale_all_dim)^2`` (the plain one where ``mscale_all_dim`` is 0)."""
    m = yarn_mscale(factor, mscale_all_dim) if mscale_all_dim else 1.0
    return float(head_dim ** -0.5 * m * m)


def yarn_inv_freq(theta: float, rot: int, factor, original_max_position,
                  beta_fast, beta_slow, **_) -> np.ndarray:
    """YaRN's ``rot / 2`` frequencies (f32): pair ``j`` turns by ``pos x
    ((1 - r_j) f_j / factor + r_j f_j)`` with ``f_j = theta^(-2j/rot)``
    and ``r_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``, ``lo`` / ``hi`` the
    pairs whose wavelength turns ``beta_fast`` / ``beta_slow`` times in
    ``original_max_position`` positions (floor / ceil, clipped to 0 ..
    ``rot - 1`` as the latent-attention family's code clips them)."""
    def pair(turns):
        return rot * np.log(original_max_position / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))

    lo = max(int(np.floor(pair(beta_fast))), 0)
    hi = min(int(np.ceil(pair(beta_slow))), rot - 1)
    j = np.arange(rot // 2, dtype=np.float64)
    keep = 1.0 - np.clip((j - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    plain = theta ** (-2.0 * j / rot)
    return ((1.0 - keep) * plain / factor + keep * plain).astype(np.float32)


@register_op(
    "rotary_embedding",
    inputs=[IOSpec("X"), IOSpec("Positions", no_grad=True)],
    outputs=["Out"],
    attrs={"theta": 10000.0, "rotary_dim": 0, "pairing": "interleaved",
           "yarn_factor": 0.0, "yarn_original_max_position": 0,
           "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
           "yarn_mscale": 1.0, "yarn_mscale_all_dim": 0.0},
    grad=None)
def _rotary_embedding(ctx, ins, attrs):
    """Rotary positions: ``X`` [B, heads, S, D], ``Positions`` [B, S] int.
    The first ``rotary_dim`` dims of a head turn (0: all ``D``), the rest
    carry no position. ``pairing`` ``interleaved`` (the GPT-J layout):
    pair ``i`` = dims ``(2i, 2i+1)``; ``half`` (rotate-half, the NeoX
    layout): pair ``i`` = dims ``(i, i + rotary_dim/2)``. Pair ``i`` turns
    by ``pos * theta^(-2i/rotary_dim)``. The pair swap is a product with a
    constant signed permutation (exact in any float type) and not a lane
    shuffle; angles, sines and the blend are f32.

    ``yarn_factor`` > 0 (with ``yarn_original_max_position``, the two
    betas and the two ``mscale``) makes the frequencies YaRN's, at every
    position (:func:`yarn_inv_freq`), and scales cos and sin by
    :func:`yarn_cos_scale`; 0 (the default) leaves the plain frequencies
    and the op's output bit for bit what it was."""
    xv, pos = x(ins, "X"), x(ins, "Positions")
    B, _, S, D = xv.shape
    rot = int(attrs.get("rotary_dim", 0)) or D
    half = str(attrs.get("pairing", "interleaved")) == "half"
    if rot % 2 or rot > D:
        raise ValueError(f"rotary_embedding: rotary_dim {rot} of {D} dims")
    with jax.named_scope("rotary"):
        yarn = yarn_attrs(attrs)
        if yarn:
            inv = jnp.asarray(yarn_inv_freq(float(attrs["theta"]), rot,
                                            **yarn), jnp.float32)
        else:
            inv = float(attrs["theta"]) ** (
                -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        ang = pos.reshape(B, 1, S, 1).astype(jnp.float32) * inv
        if half:
            spread = lambda t: jnp.concatenate([t, t], axis=-1)
            first = jnp.arange(rot // 2)
            second = first + rot // 2
        else:
            spread = lambda t: jnp.repeat(t, 2, axis=-1)
            first = jnp.arange(0, rot, 2)
            second = first + 1
        cos, sin = spread(jnp.cos(ang)), spread(jnp.sin(ang))
        m = yarn_cos_scale(**yarn) if yarn else 1.0
        if m != 1.0:
            cos, sin = cos * np.float32(m), sin * np.float32(m)
        if rot < D:
            still = [(0, 0)] * 3 + [(0, D - rot)]
            cos = jnp.pad(cos, still, constant_values=1.0)
            sin = jnp.pad(sin, still)
        # (x @ swap)[first] = -x[second], (x @ swap)[second] = x[first]
        swap = jnp.zeros((D, D), xv.dtype).at[second, first].set(-1).at[
            first, second].set(1)
        turned = jnp.matmul(xv, swap, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
        out = xv.astype(jnp.float32) * cos + turned * sin
    return {"Out": [out.astype(xv.dtype)]}
