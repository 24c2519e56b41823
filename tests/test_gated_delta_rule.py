"""The ops the qwen3_next decoder brought: the gated delta rule (chunked
scan kernel, decode-step kernel, and the plain token loop they must agree
with), its op with the causal convolution and the per-slot state, partial
rotate-half rotary, RMS norm, and the two attention kernels at head size
256 with 8 query heads a key/value head. Kernels run interpreted on the
CPU (``FLAGS_use_flash_attention=always``).

Tolerances: the kernels compute in f32 what the token loop computes in
f32, in another order (a chunk's triangular system against 64 single
steps): 2e-5 on outputs of order 0.1 to 1.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers
from paddle_tpu.kernels.gdn import (gdn_chunk_scan, gdn_decode_step,
                                    gdn_scan_reference, gdn_step_reference)

TOL = 2e-5


def _unit(t):
    return t / np.linalg.norm(t, axis=-1, keepdims=True)


def _rule_inputs(rng, R, Hk, Hv, S, Dk, Dv, lens, lean=0.0):
    """q, k, v, g, beta for ``R`` sequences of ``lens`` real rows in ``S``
    (rows past a length stand still); ``lean`` pulls every key towards one
    direction, which is what makes the triangular system stiff."""
    q = _unit(rng.normal(size=(R, Hk, S, Dk))) * Dk ** -0.5
    k = _unit(rng.normal(size=(R, Hk, S, Dk)) + lean)
    v = rng.normal(size=(R, Hv, S, Dv))
    g = -np.exp(rng.normal(size=(R, Hv, S))) * 0.3
    beta = rng.uniform(0.05, 0.99, size=(R, Hv, S))
    live = (np.arange(S)[None] < np.asarray(lens)[:, None])[:, None]
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return f32(q), f32(k), f32(v), f32(g * live), f32(beta * live)


# -- (b) one rule, three forms ------------------------------------------------

@pytest.mark.parametrize("case", ["whole_chunks", "ragged", "padded",
                                  "short", "keys_lean_one_way"])
def test_scan_kernel_equals_the_token_loop(case):
    S, lens, lean = {"whole_chunks": (128, (128, 128), 0.0),
                     "ragged": (150, (150, 150), 0.0),
                     "padded": (192, (67, 130), 0.0),
                     "short": (24, (24, 5), 0.0),
                     "keys_lean_one_way": (128, (128, 90), 2.0)}[case]
    args = _rule_inputs(np.random.default_rng(1), 2, 2, 4, S, 128, 128,
                        lens, lean)
    o1, s1 = gdn_scan_reference(*args)
    o2, s2 = gdn_chunk_scan(*args, interpret=True)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(o2[r, :, :n], o1[r, :, :n], atol=TOL)
    np.testing.assert_allclose(s2, s1, atol=TOL)
    assert float(jnp.abs(s1).max()) > 0.1


def test_padding_rows_leave_the_state_where_the_last_real_row_put_it():
    rng = np.random.default_rng(2)
    q, k, v, g, beta = _rule_inputs(rng, 1, 1, 2, 96, 128, 128, (41,))
    _, padded = gdn_chunk_scan(q, k, v, g, beta, interpret=True)
    cut = lambda t: t[:, :, :41]
    _, exact = gdn_scan_reference(cut(q), cut(k), cut(v), cut(g), cut(beta))
    np.testing.assert_allclose(padded, exact, atol=TOL)


def test_step_kernel_continues_what_the_scan_left():
    """A scan over the first 70 rows, then 10 single steps through the
    step kernel, is the token loop over 80 rows; a slot whose gate is shut
    (decay 1, beta 0) keeps its state bit for bit."""
    rng = np.random.default_rng(3)
    R, Hk, Hv, S, D = 3, 4, 8, 80, 128
    q, k, v, g, beta = _rule_inputs(rng, R, Hk, Hv, S, D, D, (S,) * R)
    want_o, want_s = gdn_scan_reference(q, k, v, g, beta)
    head = lambda t: t[:, :, :70]
    _, state = gdn_chunk_scan(head(q), head(k), head(v), head(g),
                              head(beta), interpret=True)
    shut = jnp.asarray([1.0, 1.0, 0.0])            # sequence 2 stands still
    frozen = np.asarray(state[2]).copy()
    rep = lambda t: jnp.repeat(t, Hv // Hk, axis=1)
    for t in range(70, 80):
        args = (state, rep(q[:, :, t]), rep(k[:, :, t]), v[:, :, t],
                jnp.exp(g[:, :, t] * shut[:, None]),
                beta[:, :, t] * shut[:, None])
        o_ref, s_ref = gdn_step_reference(*args)
        o, state = gdn_decode_step(*args, interpret=True)
        np.testing.assert_allclose(o, o_ref, atol=TOL)
        np.testing.assert_allclose(o[:2], want_o[:2, :, t], atol=TOL)
    np.testing.assert_allclose(state[:2], want_s[:2], atol=TOL)
    np.testing.assert_array_equal(np.asarray(state[2]), frozen)


# -- the op: convolution, tail, slots ------------------------------------------

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


def _naive_layer(x, w, a, b, a_log, dt_bias, Hk, Hv, Dk, Dv):
    """One sequence [T, C] through convolution, SiLU and the rule, in
    float64 numpy, a token at a time."""
    T, C = x.shape
    taps = w.shape[1]
    padded = np.concatenate([np.zeros((taps - 1, C)), x])
    c = sum(padded[j:j + T] * w[:, j] for j in range(taps))
    c = c / (1 + np.exp(-c))
    unit = lambda t: t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = unit(c[:, :Hk * Dk].reshape(T, Hk, Dk)) * Dk ** -0.5
    k = unit(c[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk))
    v = c[:, 2 * Hk * Dk:].reshape(T, Hv, Dv)
    q, k = (np.repeat(t, Hv // Hk, axis=1) for t in (q, k))
    g = -np.exp(a_log) * np.log1p(np.exp(a + dt_bias))
    beta = 1 / (1 + np.exp(-b))
    S = np.zeros((Hv, Dk, Dv))
    out = []
    for t in range(T):
        S = S * np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]).reshape(-1))
    return np.stack(out), S, padded[T:T + taps - 1]


@pytest.mark.parametrize("flash", ["auto", "always"])
def test_the_op_scans_prompts_into_named_slots_then_steps(flash):
    """Two prompts of 21 and 9 rows in a bucket of 32 go to slots 3 and 1
    of 4 (a third row of the dispatch is masked out); then two decode
    steps with slot 0 idle. Outputs, states and tails against the naive
    loop; slots nobody named keep what they held."""
    rng = np.random.default_rng(7)
    Hk, Hv, Dk, Dv, taps, B, R, S = 1, 2, 128, 128, 4, 4, 3, 32
    C = 2 * Hk * Dk + Hv * Dv
    lens = [21, 9, 30]
    x = rng.normal(size=(R, S + 2, C)).astype(np.float32)
    a = rng.normal(size=(R, S + 2, Hv)).astype(np.float32)
    b = rng.normal(size=(R, S + 2, Hv)).astype(np.float32)
    w = (rng.normal(size=(C, taps)) * 0.5).astype(np.float32)
    a_log = rng.uniform(-1.4, 0.7, Hv).astype(np.float32)
    dt_bias = rng.uniform(-4, -2, Hv).astype(np.float32)
    state0 = rng.normal(size=(B, Hv, Dk, Dv)).astype(np.float32)
    tail0 = rng.normal(size=(B, taps - 1, C)).astype(np.float32)
    mask = (np.arange(S)[None] < np.array(lens)[:, None]).astype(np.float32)
    slots = np.array([[3], [1], [2]], np.int64)
    smask = np.array([[1.0], [1.0], [0.0]], np.float32)
    heads = dict(num_k_heads=Hk, num_v_heads=Hv, head_k_dim=Dk,
                 head_v_dim=Dv)
    # the decode steps read each slot's next rows: slot 3 continues
    # sequence 0, slot 1 sequence 1
    of_slot = {3: 0, 1: 1}
    step_x = np.zeros((2, B, 1, C), np.float32)
    step_a = np.zeros((2, B, 1, Hv), np.float32)
    step_b = np.zeros((2, B, 1, Hv), np.float32)
    for slot, r in of_slot.items():
        for t in range(2):
            step_x[t, slot, 0] = x[r, lens[r] + t]
            step_a[t, slot, 0] = a[r, lens[r] + t]
            step_b[t, slot, 0] = b[r, lens[r] + t]
    gate = np.array([[0.0], [1.0], [0.0], [1.0]], np.float32)

    def build():
        st, tl = _data("state", state0), _data("tail", tail0)
        shared = (_data("w", w), _data("a_log", a_log),
                  _data("dt_bias", dt_bias))
        o, n = layers.gated_delta_rule(
            _data("x", x[:, :S]), shared[0], _data("a", a[:, :S]),
            _data("b", b[:, :S]), *shared[1:], st, tl, _data("mask", mask),
            slots=_data("slots", slots), slot_mask=_data("smask", smask),
            **heads)
        outs = [o, n]
        for t in range(2):
            o, n = layers.gated_delta_rule(
                _data(f"x{t}", step_x[t]), shared[0],
                _data(f"a{t}", step_a[t]), _data(f"b{t}", step_b[t]),
                *shared[1:], st, tl, _data(f"gate{t}", gate), mode="step",
                **heads)
            outs += [o, n]
        return outs + [st, tl]

    feed = dict(state=state0, tail=tail0, w=w, x=x[:, :S], a=a[:, :S],
                b=b[:, :S], a_log=a_log, dt_bias=dt_bias, mask=mask,
                slots=slots, smask=smask)
    for t in range(2):
        feed.update({f"x{t}": step_x[t], f"a{t}": step_a[t],
                     f"b{t}": step_b[t], f"gate{t}": gate})
    o, n, o0, n0, o1, n1, state, tail = _run(build, feed, flash)
    assert int(n[0]) == 21 + 9 and int(n0[0]) == int(n1[0]) == 2
    for slot, r in of_slot.items():
        L = lens[r]
        want_o, want_s, want_tail = _naive_layer(
            x[r, :L + 2].astype(np.float64), w, a[r, :L + 2], b[r, :L + 2],
            a_log, dt_bias, Hk, Hv, Dk, Dv)
        np.testing.assert_allclose(o[r, :L], want_o[:L], atol=TOL)
        np.testing.assert_allclose(o0[slot, 0], want_o[L], atol=TOL)
        np.testing.assert_allclose(o1[slot, 0], want_o[L + 1], atol=TOL)
        np.testing.assert_allclose(state[slot], want_s, atol=TOL)
        np.testing.assert_allclose(tail[slot], want_tail, atol=1e-6)
    for slot in (0, 2):                 # unnamed, masked out, and idle
        np.testing.assert_array_equal(state[slot], state0[slot])
        np.testing.assert_array_equal(tail[slot], tail0[slot])


# -- rotary on a part of a head, rotate-half pairs -----------------------------

@pytest.mark.parametrize("rot,pairing", [(16, "half"), (64, "half"),
                                         (16, "interleaved"),
                                         (64, "interleaved")])
def test_rotary_turns_the_first_dims_only(rot, pairing):
    rng = np.random.default_rng(0)
    B, H, S, D, theta = 2, 3, 7, 64, 1e7
    x = rng.normal(size=(B, H, S, D)).astype(np.float32)
    pos = rng.integers(0, 5000, (B, S)).astype(np.int64)
    (got,) = _run(lambda: [layers.rotary_embedding(
        _data("x", x), _data("pos", pos), theta=theta,
        rotary_dim=0 if rot == D else rot, pairing=pairing)],
        dict(x=x, pos=pos))
    ang = pos[:, None, :, None] * theta ** (-np.arange(0, rot, 2) / rot)
    want = x.astype(np.float64).copy()
    if pairing == "half":
        a, b = x[..., :rot // 2], x[..., rot // 2:rot]
        want[..., :rot // 2] = a * np.cos(ang) - b * np.sin(ang)
        want[..., rot // 2:rot] = b * np.cos(ang) + a * np.sin(ang)
    else:
        a, b = x[..., 0:rot:2], x[..., 1:rot:2]
        want[..., 0:rot:2] = a * np.cos(ang) - b * np.sin(ang)
        want[..., 1:rot:2] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=2e-4)   # f32 angles to 5000
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


@pytest.mark.parametrize("zero_centered", [False, True])
def test_rms_norm(zero_centered):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 32)).astype(np.float32) * 3
    w = rng.normal(size=(32,)).astype(np.float32) * 0.2
    (got,) = _run(lambda: [layers.rms_norm(
        _data("x", x), _data("w", w), epsilon=1e-6,
        zero_centered=zero_centered)], dict(x=x, w=w))
    want = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                       + 1e-6) * (1 + w if zero_centered else w)
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- (d) the attention kernels at head size 256, 8 query heads a k/v head ------

def _naive_attention(q, k, v, n_keys):
    """q [Hq, Sq, D] at the last Sq positions of n_keys; k, v [Hkv, S, D]."""
    G = q.shape[0] // k.shape[0]
    Sq = q.shape[1]
    out = np.zeros(q.shape)
    for h in range(q.shape[0]):
        s = q[h].astype(np.float64) @ k[h // G, :n_keys].T * q.shape[2] ** -0.5
        for i in range(Sq):
            s[i, n_keys - Sq + i + 1:] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        out[h] = p / p.sum(-1, keepdims=True) @ v[h // G, :n_keys]
    return out


def test_prefill_attention_at_head_256_group_8():
    rng = np.random.default_rng(3)
    B, Hq, Hkv, S, D = 1, 16, 2, 256, 256
    q = rng.normal(size=(B, Hq, S, D)).astype(np.float32)
    k = rng.normal(size=(B, Hkv, S, D)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, S, D)).astype(np.float32)
    lens = np.array([[200]])
    bias = ((np.arange(S)[None] < lens) - 1.0).astype(np.float32) * 10000.0
    (got,) = _run(lambda: [layers.fused_multihead_attention(
        _data("q", q), _data("k", k), _data("v", v),
        bias_qk=layers.unsqueeze(_data("bias", bias), [1, 2]), causal=True,
        scale=D ** -0.5, is_test=True)],
        dict(q=q, k=k, v=v, bias=bias), "always")
    want = _naive_attention(q[0, :, :200], k[0], v[0], 200)
    np.testing.assert_allclose(got[0, :, :200], want, atol=2e-4)


def test_decode_attention_at_head_256_group_8():
    rng = np.random.default_rng(4)
    B, Hq, Hkv, S, D = 3, 16, 2, 512, 256
    pos = np.array([[300], [0], [511]], np.int64)
    ck = rng.normal(size=(B, Hkv, S, D)).astype(np.float32)
    cv = rng.normal(size=(B, Hkv, S, D)).astype(np.float32)
    q = rng.normal(size=(B, Hq, 1, D)).astype(np.float32)
    kn = rng.normal(size=(B, Hkv, 1, D)).astype(np.float32)
    vn = rng.normal(size=(B, Hkv, 1, D)).astype(np.float32)

    def build():
        k_var, v_var = _data("ck", ck), _data("cv", cv)
        out = layers.fused_decode_attention(
            _data("q", q), _data("kn", kn), _data("vn", vn), k_var, v_var,
            _data("pos", pos), scale=D ** -0.5, page_size=128)
        return out, k_var

    got, ck2 = _run(build, dict(q=q, kn=kn, vn=vn, ck=ck, cv=cv, pos=pos),
                    "always")
    for b in range(B):
        n = int(pos[b, 0])
        kk, vv = ck[b].copy(), cv[b].copy()
        kk[:, n], vv[:, n] = kn[b, :, 0], vn[b, :, 0]
        want = _naive_attention(q[b], kk, vv, n + 1)
        np.testing.assert_allclose(got[b], want, atol=2e-4)
        np.testing.assert_array_equal(ck2[b, :, n], kn[b, :, 0])
