"""The Mamba-2 selective scan that the granitemoehybrid decoder brought: the
chunked scan kernel, the decode-step kernel and the plain token loop they
must agree with, and the ``mamba2_scan`` op with the causal convolution,
its bias, the skip and the per-slot state. Kernels run interpreted on the
CPU (``FLAGS_use_flash_attention=always``).

Tolerance: the kernels compute in f32 what the token loop computes in f32,
in another order (a chunk's dual form against single steps). An output is
a sum of 128 products of order 10 that largely cancel, so f32 leaves 1e-7 x
10 x 128 = 1e-4 on it: 2e-4 and two parts in 100,000 (bf16 operands would
leave 4e-2).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers
from paddle_tpu.kernels.ssd import (ssd_chunk_scan, ssd_decode_step,
                                    ssd_scan_reference, ssd_step_reference)

TOL = dict(rtol=2e-5, atol=2e-4)


def _scan_inputs(rng, R, S, H, P, N, lens):
    """u, g, b, c and a start state for ``R`` sequences of ``lens`` real
    rows in ``S`` (rows past a length stand still: no input, decay 1).
    Decays from 0.2 to 0.999 a token, by head and row."""
    live = (np.arange(S)[None] < np.asarray(lens)[:, None])[..., None]
    u = rng.normal(size=(R, S, H, P)) * 0.3 * live[..., None]
    g = -rng.uniform(0.001, 1.6, size=(R, S, H)) * live
    b, c = rng.normal(size=(2, R, S, N))
    s0 = rng.normal(size=(R, H, P, N))
    return tuple(jnp.asarray(t, jnp.float32) for t in (u, g, b, c, s0))


# -- one rule, three forms -------------------------------------------------------

@pytest.mark.parametrize("case", ["whole_chunks", "ragged", "padded",
                                  "short", "heads_of_128", "odd_heads"])
def test_scan_kernel_equals_the_token_loop(case):
    S, lens, H, P, chunk = {
        "whole_chunks": (256, (256, 256), 4, 64, 128),
        "ragged": (150, (150, 150), 4, 64, 128),
        "padded": (192, (67, 130), 2, 64, 128),
        "short": (24, (24, 5), 2, 64, 256),
        "heads_of_128": (96, (96, 50), 2, 128, 256),
        "odd_heads": (40, (40, 33), 3, 16, 16)}[case]
    args = _scan_inputs(np.random.default_rng(1), 2, S, H, P, 128, lens)
    y1, s1 = ssd_scan_reference(*args)
    y2, s2 = ssd_chunk_scan(*args, chunk=chunk, interpret=True)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(y2[r, :n], y1[r, :n], **TOL)
    np.testing.assert_allclose(s2, s1, **TOL)
    assert float(jnp.abs(s1).max()) > 0.1


@pytest.mark.parametrize("cut", [100, 131, 255])
def test_scan_kernel_continues_itself_from_a_carried_state(cut):
    """Rows 0..cut, then the rest from the state the first call left, is the
    scan over all rows: at cuts that are no multiple of the chunk."""
    u, g, b, c, s0 = _scan_inputs(np.random.default_rng(2), 2, 300, 4, 64,
                                  128, (300, 300))
    run = lambda lo, hi, s: ssd_chunk_scan(
        u[:, lo:hi], g[:, lo:hi], b[:, lo:hi], c[:, lo:hi], s, chunk=128,
        interpret=True)
    y, s = run(0, 300, s0)
    y_a, mid = run(0, cut, s0)
    y_b, end = run(cut, 300, mid)
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], axis=1), y,
                               **TOL)
    np.testing.assert_allclose(end, s, **TOL)


def test_padding_rows_leave_the_state_where_the_last_real_row_put_it():
    u, g, b, c, s0 = _scan_inputs(np.random.default_rng(3), 1, 160, 2, 64,
                                  128, (41,))
    _, padded = ssd_chunk_scan(u, g, b, c, s0, chunk=128, interpret=True)
    _, exact = ssd_scan_reference(u[:, :41], g[:, :41], b[:, :41], c[:, :41],
                                  s0)
    np.testing.assert_allclose(padded, exact, **TOL)
    # and a sequence of padding only gets its start state back, bit for bit
    z = jnp.zeros_like
    _, still = ssd_chunk_scan(z(u), z(g), b, c, s0, chunk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(still), np.asarray(s0))


@pytest.mark.parametrize("H,P", [(32, 64), (8, 64), (3, 16)])
def test_step_kernel_continues_what_the_scan_left(H, P):
    """A scan over the first 70 rows, then 10 single steps through the step
    kernel, is the token loop over 80 rows; a slot whose gate is shut
    (decay 1, no input) keeps its state bit for bit."""
    R, S, N = 3, 80, 128
    u, g, b, c, s0 = _scan_inputs(np.random.default_rng(4), R, S, H, P, N,
                                  (S,) * R)
    want_y, want_s = ssd_scan_reference(u, g, b, c, s0)
    _, state = ssd_chunk_scan(u[:, :70], g[:, :70], b[:, :70], c[:, :70],
                              s0, chunk=128, interpret=True)
    shut = jnp.asarray([1.0, 1.0, 0.0])            # sequence 2 stands still
    frozen = np.asarray(state[2]).copy()
    for t in range(70, 80):
        args = (state, u[:, t] * shut[:, None, None],
                jnp.exp(g[:, t] * shut[:, None]), b[:, t], c[:, t])
        y_ref, _ = ssd_step_reference(*args)
        y, state = ssd_decode_step(*args, interpret=True)
        np.testing.assert_allclose(y, y_ref, **TOL)
        np.testing.assert_allclose(y[:2], want_y[:2, t], **TOL)
    np.testing.assert_allclose(state[:2], want_s[:2], **TOL)
    np.testing.assert_array_equal(np.asarray(state[2]), frozen)


# -- the op: convolution, bias, skip, tail, slots ----------------------------------

def _run(build, feed, flash="auto"):
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            fetches = build()
        exe = fluid.Executor(fluid.CPUPlace())
        return exe.run(main, feed=feed, fetch_list=list(fetches))
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})


def _data(name, a):
    return layers.data(name, shape=list(a.shape), dtype=str(a.dtype),
                       append_batch_size=False)


def _naive_mixer(x, w, bias, dt, a_log, dt_bias, d, H, P, N):
    """One sequence [T, C] through convolution, SiLU and the scan, in
    float64 numpy, a token at a time. Returns (y [T, H P], state, tail)."""
    T, C = x.shape
    taps = w.shape[1]
    padded = np.concatenate([np.zeros((taps - 1, C)), x])
    c = sum(padded[j:j + T] * w[:, j] for j in range(taps)) + bias
    c = c / (1 + np.exp(-c))
    xs = c[:, :H * P].reshape(T, H, P)
    b, cc = c[:, H * P:H * P + N], c[:, H * P + N:]
    dt = np.log1p(np.exp(dt + dt_bias))
    a = np.exp(-np.exp(a_log) * dt)
    S = np.zeros((H, P, N))
    out = []
    for t in range(T):
        S = (S * a[t][:, None, None]
             + (dt[t][:, None] * xs[t])[:, :, None] * b[t])
        out.append((S @ cc[t] + d[:, None] * xs[t]).reshape(-1))
    return np.stack(out), S, padded[T:T + taps - 1]


@pytest.mark.parametrize("flash", ["auto", "always"])
def test_the_op_scans_prompts_into_named_slots_then_steps(flash):
    """Two prompts of 21 and 9 rows in a bucket of 32 go to slots 3 and 1
    of 4 (a third row of the dispatch is masked out); then two decode
    steps with slots 0 and 2 idle. Outputs, states and tails against the
    naive loop; slots nobody named keep what they held."""
    rng = np.random.default_rng(7)
    H, P, N, taps, B, R, S = 4, 64, 128, 4, 4, 3, 32
    C = H * P + 2 * N
    lens = [21, 9, 30]
    f32 = lambda t: t.astype(np.float32)
    x = f32(rng.normal(size=(R, S + 2, C)))
    dt = f32(rng.normal(size=(R, S + 2, H)))
    w = f32(rng.normal(size=(C, taps)) * 0.5)
    bias = f32(rng.normal(size=(C,)) * 0.2)
    a_log = f32(np.log(rng.uniform(1, 16, H)))
    dt_bias = f32(rng.uniform(-4, -1, H))
    d = f32(rng.uniform(0.5, 1.5, H))
    state0 = f32(rng.normal(size=(B, H, P, N)))
    tail0 = f32(rng.normal(size=(B, taps - 1, C)))
    mask = f32(np.arange(S)[None] < np.array(lens)[:, None])
    slots = np.array([[3], [1], [2]], np.int64)
    smask = np.array([[1.0], [1.0], [0.0]], np.float32)
    dims = dict(num_heads=H, head_dim=P, state_dim=N, chunk=16)
    of_slot = {3: 0, 1: 1}       # slot 3 continues sequence 0, slot 1 seq. 1
    step_x = np.zeros((2, B, 1, C), np.float32)
    step_dt = np.zeros((2, B, 1, H), np.float32)
    for slot, r in of_slot.items():
        for t in range(2):
            step_x[t, slot, 0] = x[r, lens[r] + t]
            step_dt[t, slot, 0] = dt[r, lens[r] + t]
    gate = np.array([[0.0], [1.0], [0.0], [1.0]], np.float32)

    def build():
        st, tl = _data("state", state0), _data("tail", tail0)
        cw, cb = _data("w", w), _data("bias", bias)
        al, db, dd = _data("a_log", a_log), _data("dt_bias", dt_bias), \
            _data("d", d)
        o, n = layers.mamba2_scan(
            _data("x", x[:, :S]), cw, cb, _data("dt", dt[:, :S]), al, db, dd,
            st, tl, _data("mask", mask), slots=_data("slots", slots),
            slot_mask=_data("smask", smask), **dims)
        outs = [o, n]
        for t in range(2):
            o, n = layers.mamba2_scan(
                _data(f"x{t}", step_x[t]), cw, cb, _data(f"dt{t}",
                                                         step_dt[t]),
                al, db, dd, st, tl, _data(f"gate{t}", gate), mode="step",
                **dims)
            outs += [o, n]
        return outs + [st, tl]

    feed = dict(state=state0, tail=tail0, w=w, bias=bias, x=x[:, :S],
                dt=dt[:, :S], a_log=a_log, dt_bias=dt_bias, d=d, mask=mask,
                slots=slots, smask=smask)
    for t in range(2):
        feed.update({f"x{t}": step_x[t], f"dt{t}": step_dt[t],
                     f"gate{t}": gate})
    o, n, o0, n0, o1, n1, state, tail = _run(build, feed, flash)
    assert int(n[0]) == 21 + 9 and int(n0[0]) == int(n1[0]) == 2
    for slot, r in of_slot.items():
        L = lens[r]
        want_o, want_s, want_tail = _naive_mixer(
            x[r, :L + 2].astype(np.float64), w, bias, dt[r, :L + 2], a_log,
            dt_bias, d, H, P, N)
        np.testing.assert_allclose(o[r, :L], want_o[:L], **TOL)
        np.testing.assert_allclose(o0[slot, 0], want_o[L], **TOL)
        np.testing.assert_allclose(o1[slot, 0], want_o[L + 1], **TOL)
        np.testing.assert_allclose(state[slot], want_s, **TOL)
        np.testing.assert_allclose(tail[slot], want_tail, atol=1e-6)
    for slot in (0, 2):                 # unnamed, masked out, and idle
        np.testing.assert_array_equal(state[slot], state0[slot])
        np.testing.assert_array_equal(tail[slot], tail0[slot])


def test_the_op_refuses_shapes_that_are_not_the_mixers():
    H, P, N = 2, 16, 32
    C = H * P + N                       # one of B and C is missing
    z = lambda *s: np.zeros(s, np.float32)
    feed = dict(x=z(1, 8, C), w=z(C, 4), b=z(C), dt=z(1, 8, H), al=z(H),
                db=z(H), d=z(H), st=z(2, H, P, N), tl=z(2, 3, C), m=z(1, 8))
    with pytest.raises(Exception, match="mamba2_scan .scan.: X"):
        _run(lambda: layers.mamba2_scan(
            *(_data(k, v) for k, v in feed.items()), num_heads=H, head_dim=P,
            state_dim=N), feed)
