"""The hyper-connection op pair (``ops/hyper_connection.py``,
``kernels/hyper_connection.py``): manifold-constrained hyper-connections
against their written equations in float64 numpy, on both routes.

Tolerances, and why. The op is f32 throughout and its projections are true
f32 products, so against float64 it differs by f32 rounding alone: the
projection of 1,024 numbers of unit size reads 1e-6 to 4e-6, the
coefficients after 20 Sinkhorn rounds the same, and the mixes (sums of 4
products of order 1) 1e-6; 2e-5 holds every number here with five times of
room. A projection at bf16 operands (what the matrix unit does to an f32
product unless told otherwise) is off by 2e-3 to 1e-2, five hundred times
the tolerance.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers, monitor
from paddle_tpu.kernels import hyper_connection as hc
from paddle_tpu.ops.hyper_connection import count_hc_stats

TOL = 2e-5


def _formula(x, proj, alpha, bias, y=None, iters=20, eps=1e-6,
             norm_eps=1e-6, clamp=(-30.0, 30.0)):
    """float64, the equations as written: ``x`` [R, n, C]. Returns ``u``,
    ``H_post``, ``H_res`` and, with ``y``, the written-back streams."""
    x = x.astype(np.float64)
    R, n, C = x.shape
    flat = x.reshape(R, n * C)
    xn = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + norm_eps)
    z = xn @ proj.astype(np.float64).T
    pre = alpha[0] * z[:, :n] + bias[:n]
    post = alpha[1] * z[:, n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * z[:, 2 * n:] + bias[2 * n:]).reshape(R, n, n)
    sig = lambda t: 1.0 / (1.0 + np.exp(-t))
    a = np.exp(np.clip(res, *clamp))
    for _ in range(iters):
        a = a / (a.sum(2, keepdims=True) + eps)
        a = a / (a.sum(1, keepdims=True) + eps)
    u = np.einsum("rn,rnc->rc", sig(pre), x)
    out = None
    if y is not None:
        out = np.einsum("rmn,rnc->rmc", a, x) \
            + (2 * sig(post))[:, :, None] * y.astype(np.float64)[:, None, :]
    return u, 2 * sig(post), a, out


def _draw(R, n, C, seed=0, spread=1.0):
    rng = np.random.default_rng(seed)
    m = n * (n + 2)
    bias = rng.uniform(-1, 1, m)
    bias[2 * n:] += 4 * np.eye(n).ravel()
    f = lambda a: np.asarray(a, np.float32)
    return (f(rng.normal(size=(R, n, C))),
            f(rng.normal(size=(m, n * C)) * spread / np.sqrt(n * C)),
            f(rng.uniform(0.5, 1.5, 3)), f(bias), f(rng.normal(size=(R, C))))


def _run(x, proj, alpha, bias, y, flash="auto", batch=1, **attrs):
    """The op pair through a program: ``x`` [R, n, C] as [batch, R / batch,
    n, C]."""
    R, n, C = x.shape
    S = R // batch
    feed = dict(x=x.reshape(batch, S, n, C), proj=proj, alpha=alpha,
                bias=bias, y=y.reshape(batch, S, C))
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            v = {k: layers.data(k, shape=list(a.shape), dtype="float32",
                                append_batch_size=False)
                 for k, a in feed.items()}
            u, post, res, stats = layers.hyper_connection_read(
                v["x"], v["proj"], v["alpha"], v["bias"], **attrs)
            out = layers.hyper_connection_write(v["x"], v["y"], post, res)
        exe = fluid.Executor(fluid.CPUPlace())
        got = exe.run(main, feed=feed, fetch_list=[u, post, res, out, stats])
        routes = {r["op"]: r["route"] for r in monitor.kernel_routes()
                  if r["op"].startswith("hyper_connection")} \
            if hasattr(monitor, "kernel_routes") else {}
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    u, post, res, out, stats = got
    return (u.reshape(R, C), post.reshape(R, n), res.reshape(R, n, n),
            out.reshape(R, n, C), stats, routes)


@pytest.mark.parametrize("flash,R,batch", [("auto", 37, 1), ("auto", 48, 2),
                                           ("always", 256, 2)])
def test_the_op_pair_is_the_written_equations(flash, R, batch):
    """Four streams of 256 on the primitive route (any number of rows) and
    through the kernels in interpret mode (two tiles of 128 rows), against
    float64: the read's mix, both coefficient sets and the write."""
    x, proj, alpha, bias, y = _draw(R, 4, 256, seed=R)
    u, post, res, out, stats, _ = _run(x, proj, alpha, bias, y, flash, batch)
    wu, wpost, wres, wout = _formula(x, proj, alpha, bias, y)
    for got, want in ((u, wu), (post, wpost), (res, wres), (out, wout)):
        np.testing.assert_allclose(got, want, atol=TOL)
    # every coefficient is per token: no two rows share an H_res
    assert np.abs(res[0] - res[1]).max() > 1e-3
    assert stats[0] == R
    err = max(np.abs(wres.sum(1) - 1).max(), np.abs(wres.sum(2) - 1).max())
    assert stats[1] == pytest.approx(err, abs=TOL)
    # the projection at bf16 operands, the control the tolerance must catch
    import ml_dtypes
    low = lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float32)
    flat = x.reshape(R, -1)
    xn = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
    z16 = low(xn).astype(np.float64) @ low(proj).astype(np.float64).T
    z = xn.astype(np.float64) @ proj.astype(np.float64).T
    assert np.abs(z16 - z).max() > 50 * TOL


def test_twenty_rounds_leave_h_res_doubly_stochastic():
    """Logits within +-1 of each other: rows and columns sum to 1 within
    1e-5 after the 20 rounds, and not after 2. (With a wider spread the
    odd token is left at 1e-3, and with 4 times
    the identity on the biases, as the benchmark draws them, a matrix is
    near a permutation, where the rounds converge slowly: 20 leave 1e-2,
    which is what ``hyper_connection_res_sum_err_max`` is there to say; the
    reference does the same 20 rounds.)"""
    x, proj, alpha, bias, y = _draw(64, 4, 128, seed=3, spread=0.3)
    near_identity = _run(x, proj, alpha, bias, y)[4][1]
    bias[8:] = 0.3 * (bias[8:] - 4 * np.eye(4, dtype=np.float32).ravel())
    _, _, res, _, stats, _ = _run(x, proj, alpha, bias, y)
    assert np.abs(res.sum(1) - 1).max() < 1e-5
    assert np.abs(res.sum(2) - 1).max() < 1e-5
    assert stats[1] < 1e-5 and (res > 0).all()
    _, _, two, _, stats2, _ = _run(x, proj, alpha, bias, y, sinkhorn_iters=2)
    assert stats2[1] > 1e-4 > stats[1] and near_identity > 1e-3
    # the write neither grows nor shrinks the streams' sum beyond H_post y
    _, post, _, out, _, _ = _run(x, proj, alpha, bias, np.zeros_like(y))
    np.testing.assert_allclose(out.sum(1), x.sum(1), atol=1e-4)


def test_the_clamp_is_live():
    """A logit of +-100 would overflow ``exp`` in f32 (e^89): clamped at
    +-30 every number stays finite and is the clamped formula's; a tighter
    clamp gives other numbers."""
    x, proj, alpha, bias, y = _draw(16, 4, 128, seed=4)
    bias[8:] = np.array([100, -100, 0, 0, -100, 100, 0, 0, 0, 0, 50, -50,
                         0, 0, -50, 50], np.float32)
    u, post, res, out, _, _ = _run(x, proj, alpha, bias, y)
    assert np.isfinite(res).all() and np.isfinite(out).all()
    _, _, wres, wout = _formula(x, proj, alpha, bias, y)
    np.testing.assert_allclose(res, wres, atol=TOL)
    np.testing.assert_allclose(out, wout, atol=2e-4)
    _, _, tight, _, _, _ = _run(x, proj, alpha, bias, y, clamp_min=-2.0,
                                clamp_max=2.0)
    assert np.abs(tight - res).max() > 0.1
    _, _, want, _ = _formula(x, proj, alpha, bias, y, clamp=(-2.0, 2.0))
    np.testing.assert_allclose(tight, want, atol=TOL)


def test_one_stream_with_fixed_coefficients_is_the_plain_residual():
    """``n`` = 1, no per-token part (a zero projection), ``b_pre`` large
    and ``b_post`` 0: ``H_pre`` = 1, ``H_post`` = 1, ``H_res`` = 1, so the
    sublayer reads ``x`` and the write is ``x + y``."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(24, 1, 128)).astype(np.float32)
    y = rng.normal(size=(24, 128)).astype(np.float32)
    proj = np.zeros((3, 128), np.float32)
    alpha = np.ones(3, np.float32)
    bias = np.array([40.0, 0.0, 0.7], np.float32)
    u, post, res, out, _, _ = _run(x, proj, alpha, bias, y)
    np.testing.assert_allclose(u, x[:, 0], atol=1e-6)
    np.testing.assert_allclose(out[:, 0], x[:, 0] + y, atol=1e-5)
    np.testing.assert_allclose(post, 1.0, atol=1e-7)
    np.testing.assert_allclose(res, 1.0, atol=2e-6)


@pytest.mark.parametrize("R,C", [(128, 128), (384, 512)])
def test_the_kernels_are_their_oracles(R, C):
    """``hc_read`` / ``hc_write`` in interpret mode against
    ``hc_read_reference`` / ``hc_write_reference``: the mix, every lane of
    the coefficients (zeros past the 24) and the Sinkhorn error."""
    import jax.numpy as jnp

    x, proj, alpha, bias, y = _draw(R, 4, C, seed=C, spread=2.0)
    args = [jnp.asarray(a) for a in (x.reshape(R, 4 * C), proj, alpha, bias)]
    u, coef, err = hc.hc_read(*args, n=4, interpret=True)
    wu, wcoef, werr = hc.hc_read_reference(*args, n=4)
    np.testing.assert_allclose(u, wu, atol=TOL)
    np.testing.assert_allclose(coef[:, :24], wcoef, atol=TOL)
    assert coef.shape == (R, hc.COEF_LANES) and not np.asarray(
        coef[:, 24:]).any()
    assert float(err) == pytest.approx(float(werr), abs=TOL)
    post, res = wcoef[:, 4:8], wcoef[:, 8:]
    out = hc.hc_write(args[0], jnp.asarray(y), post, res, n=4,
                      interpret=True)
    np.testing.assert_allclose(
        out, hc.hc_write_reference(args[0], jnp.asarray(y), post, res, n=4),
        atol=TOL)
    assert hc.supports(R, 4, C) and not hc.supports(R + 8, 4, C)
    assert not hc.supports(R, 2, C) and not hc.supports(R, 4, C + 64)
    with pytest.raises(ValueError, match="hc_read"):
        hc.hc_read(args[0][:100], *args[1:], n=4, interpret=True)


def test_what_a_dispatch_counted_reaches_the_monitor():
    """``count_hc_stats`` on a chained decode's stack of statistics ([steps,
    sublayers, 2]): rows and calls by phase and by the call's rows, the
    error as a gauge."""
    value = lambda name, **lab: sum(
        v["value"] for v in monitor.get_registry().to_dict().get(
            name, {"values": []})["values"]
        if all(v["labels"].get(k) == w for k, w in lab.items()))
    lab = dict(phase="decode", call_rows="256")
    before = {k: value(k, **lab) for k in (
        "hyper_connection_rows_total", "hyper_connection_calls_total")}
    stats = np.zeros((4, 6, 2), np.float32)
    stats[..., 0] = 256.0
    stats[2, 3, 1] = 3e-4
    assert count_hc_stats("decode", stats, None) is None
    assert value("hyper_connection_rows_total", **lab) \
        - before["hyper_connection_rows_total"] == 4 * 6 * 256
    assert value("hyper_connection_calls_total", **lab) \
        - before["hyper_connection_calls_total"] == 24
    assert value("hyper_connection_res_sum_err_max") == pytest.approx(3e-4)
    count_hc_stats("prefill", np.array([[768.0, 0.0], [768.0, 1e-3]]), None)
    assert value("hyper_connection_rows_total", phase="prefill",
                 call_rows="768") >= 1536


def test_shapes_and_types_are_checked():
    x, proj, alpha, bias, y = _draw(8, 4, 128)
    with pytest.raises(Exception, match="hyper_connection_read"):
        _run(x, proj[:, :100], alpha, bias, y)
    with pytest.raises(Exception, match="hyper_connection_read"):
        _run(x, proj, alpha[:2], bias, y)
