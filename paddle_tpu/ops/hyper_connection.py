"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a residual path of ``n`` streams
that every sublayer reads a mix of and writes a mix back into.

The stream of a token is ``X`` in R^{n x C}, f32. A sublayer ``F`` with
its own parameters ``Proj`` [n(n + 2), nC] (rows ``[P_pre^T (n) | P_post^T
(n) | P_res^T (n^2, row-major)]``: a coefficient's direction is a row, so
that the kernel's product has the tokens in its lanes), ``Alpha`` [3]
(``a_pre, a_post, a_res``) and ``Bias`` [n(n + 2)] (``[b_pre | b_post |
b_res]``):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)            in R^{nC}
    H~pre  = a_pre (x~ P_pre) + b_pre                            in R^n
    H~post = a_post (x~ P_post) + b_post                         in R^n
    H~res  = a_res mat(x~ P_res) + b_res                         in R^{n x n}
    H_pre  = sigmoid(H~pre),  H_post = 2 sigmoid(H~post)
    H_res  = SK(exp(clamp(H~res, clamp_min, clamp_max)))
    u      = H_pre X                     (what the sublayer reads, R^C)
    X'     = H_res X + H_post^T F(u)     (what it writes back)

``SK`` is ``sinkhorn_iters`` rounds of "divide each row by (its sum +
eps), then each column by (its sum + eps)", which leaves ``H_res`` doubly
stochastic to rounding: the mix of the streams neither grows nor shrinks
them. All coefficients are per token. ``hyper_connection_read`` computes
the coefficients and ``u``; ``hyper_connection_write`` takes them with the
sublayer's output. Everything is f32 and the projections are true f32
products (``HIGHEST``): a coefficient moves all ``C`` numbers of a stream.

Two routes (``kernel_route_total{op="hyper_connection_read" |
"hyper_connection_write"}``): ``pallas`` on a TPU, where each op is ONE
kernel that reads the stream once (``kernels/hyper_connection.py``), and
``primitive``, the equations in ``jax.numpy``
(``kernels.hyper_connection.hc_read_reference`` / ``hc_write_reference``)
under the named scopes ``hc_read`` / ``hc_write``, which is every other
device's route and the kernels' test oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import IOSpec, register_op, x
from .. import flags
from ..lowering import lowering_platform, note_kernel_route


def count_hc_stats(phase: str, stats, sums) -> None:
    """What a serving dispatch's hyper-connection reads counted
    (``hyper_connection_read`` ``Stats`` [..., sublayers, 2]: token rows
    mixed, and the largest ``|row or column sum - 1|`` of an ``H_res``),
    onto the monitor. The counters carry a call's own rows as the label
    ``call_rows`` (a decode step's slots, a prefill's bucket): whether a
    call's streams can stay on the chip between its read and its write
    goes by their size."""
    from .. import monitor

    stats = np.asarray(stats, np.float64).reshape(-1, 2)
    rows = monitor.counter(
        "hyper_connection_rows_total",
        "token rows a hyper-connection mixed (one read and one write a "
        "sublayer), by phase of the dispatch and rows of the call")
    calls = monitor.counter(
        "hyper_connection_calls_total",
        "executions of the hyper-connection op pair (a sublayer of a "
        "step), by phase of the dispatch and rows of the call")
    for n in np.unique(stats[:, 0]):
        lab = dict(phase=phase, call_rows=str(int(n)))
        count = float((stats[:, 0] == n).sum())
        rows.labels(**lab).inc(count * float(n))
        calls.labels(**lab).inc(count)
    monitor.gauge(
        "hyper_connection_res_sum_err_max",
        "the largest |row or column sum - 1| of a token's H_res in the "
        "last dispatch: what the Sinkhorn rounds leave").set(
        float(stats[:, 1].max()))


def _route(ctx, rows: int, n: int, C: int) -> str:
    from ..kernels.hyper_connection import supports

    mode = flags.flag("use_flash_attention")
    if mode == "never" or not supports(rows, n, C):
        return "primitive"
    if lowering_platform(ctx) == "tpu":
        return "pallas"
    return "pallas-interpret" if mode == "always" else "primitive"


@register_op(
    "hyper_connection_read",
    inputs=[IOSpec("X"), IOSpec("Proj"), IOSpec("Alpha"), IOSpec("Bias")],
    outputs=["Out", "HPost", "HRes", "Stats"],
    attrs={"sinkhorn_iters": 20, "eps": 1e-6, "norm_eps": 1e-6,
           "clamp_min": -30.0, "clamp_max": 30.0},
    grad=None)
def _hyper_connection_read(ctx, ins, attrs):
    """``X`` [B, S, n, C] f32 -> ``Out`` [B, S, C] (``u``), ``HPost``
    [B, S, n], ``HRes`` [B, S, n, n], all f32, and ``Stats`` [2] f32:
    the token rows mixed (``B x S``) and the largest ``|row or column sum
    - 1|`` among their ``H_res``."""
    xv, proj = x(ins, "X"), x(ins, "Proj")
    alpha, bias = x(ins, "Alpha"), x(ins, "Bias")
    B, S, n, C = xv.shape
    m = n * (n + 2)
    if (xv.dtype != jnp.float32 or proj.shape != (m, n * C)
            or alpha.shape != (3,) or bias.shape != (m,)):
        raise ValueError(
            f"hyper_connection_read: X {xv.shape} {xv.dtype}, Proj "
            f"{proj.shape}, Alpha {alpha.shape}, Bias {bias.shape}")
    route = _route(ctx, B * S, n, C)
    note_kernel_route(ctx, "hyper_connection_read", route)
    kw = dict(n=n, sinkhorn_iters=int(attrs["sinkhorn_iters"]),
              eps=float(attrs["eps"]), norm_eps=float(attrs["norm_eps"]),
              clamp=(float(attrs["clamp_min"]), float(attrs["clamp_max"])))
    from ..kernels.hyper_connection import hc_read, hc_read_reference

    flat = xv.reshape(B * S, n * C)
    if route == "primitive":
        with jax.named_scope("hc_read"):
            u, coef, err = hc_read_reference(flat, proj, alpha, bias, **kw)
    else:
        u, coef, err = hc_read(flat, proj, alpha, bias, **kw,
                               interpret=(route == "pallas-interpret"))
    u = u.reshape(B, S, C)
    post = coef[:, n:2 * n].reshape(B, S, n)
    res = coef[:, 2 * n:m].reshape(B, S, n, n)
    stats = jnp.stack([jnp.float32(B * S), err.astype(jnp.float32)])
    return {"Out": [u], "HPost": [post], "HRes": [res], "Stats": [stats]}


@register_op(
    "hyper_connection_write",
    inputs=[IOSpec("X"), IOSpec("Y"), IOSpec("HPost"), IOSpec("HRes")],
    outputs=["Out"], grad=None)
def _hyper_connection_write(ctx, ins, attrs):
    """``X`` [B, S, n, C], the sublayer's output ``Y`` [B, S, C], ``HPost``
    [B, S, n], ``HRes`` [B, S, n, n] (all f32) -> ``Out`` [B, S, n, C] =
    ``H_res X + H_post^T Y``."""
    xv, y = x(ins, "X"), x(ins, "Y")
    post, res = x(ins, "HPost"), x(ins, "HRes")
    B, S, n, C = xv.shape
    if (y.shape != (B, S, C) or post.shape != (B, S, n)
            or res.shape != (B, S, n, n)
            or {t.dtype for t in (xv, y, post, res)} != {jnp.dtype("float32")}):
        raise ValueError(
            f"hyper_connection_write: X {xv.shape} {xv.dtype}, Y {y.shape} "
            f"{y.dtype}, HPost {post.shape}, HRes {res.shape}")
    route = _route(ctx, B * S, n, C)
    note_kernel_route(ctx, "hyper_connection_write", route)
    from ..kernels.hyper_connection import hc_write, hc_write_reference

    args = (xv.reshape(B * S, n * C), y.reshape(B * S, C),
            post.reshape(B * S, n), res.reshape(B * S, n * n))
    if route == "primitive":
        with jax.named_scope("hc_write"):
            out = hc_write_reference(*args, n=n)
    else:
        out = hc_write(*args, n=n, interpret=(route == "pallas-interpret"))
    return {"Out": [out.reshape(B, S, n, C)]}
