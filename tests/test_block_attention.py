"""The two mask variants and the reveal op that block-diffusion generation
adds (``models/sdar_moe.py``), each against a dense computation written
here:

* the decode kernel's in-block mask (``whole_chunk``): a chunk's rows see
  one another in both directions, every row ``lengths + q_len - 1`` keys;
* the flash forward's ``causal_block``: key j visible to query i iff
  ``j // L <= i // L``;
* ``block_seed`` / ``block_reveal``: the prompt's remainder, the order of
  the reveal, the commit.

The kernels run in interpret mode; the tolerances are those of the tests
of the row-causal forms (``tests/test_prefix_spec.py``,
``tests/test_cohere_moe.py``): f32 products in another order.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers
from paddle_tpu.kernels import (decode_attention_reference, flash_attention,
                                flash_attention_decode)


def _run(build, feed, flash="auto"):
    """One program built by ``build()`` (returns its fetches), run once."""
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


def _dense(q, k, v, seen, scale):
    """q [Hq, Sq, D] over k, v [Hkv, Sk, D] under ``seen`` [Sq, Sk]."""
    G = q.shape[0] // k.shape[0]
    k, v = np.repeat(k, G, axis=0), np.repeat(v, G, axis=0)
    s = np.where(seen, np.einsum("hqd,hkd->hqk", q, k) * scale, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)


# -- the decode kernel's in-block mask -----------------------------------------

@pytest.mark.parametrize("group", [1, 2, 8])
@pytest.mark.parametrize("q_len", [2, 4, 8])
def test_decode_kernel_rows_see_the_whole_chunk(q_len, group):
    """Grouped-query heads, chunks of 2/4/8 rows, lengths that end inside
    a page, on a page's edge and in the cache's last page: every row of
    the chunk sees ``lengths + q_len - 1`` keys, its later rows too."""
    rng = np.random.default_rng(q_len * 10 + group)
    B, H, S, D, P = 4, 2, 32, 64, 8
    q = rng.normal(size=(B * H, q_len * group, D)).astype(np.float32)
    k, v = (rng.normal(size=(B * H, S, D)).astype(np.float32)
            for _ in range(2))
    lens = np.asarray([1, 9 - q_len + 1, 14, S - q_len + 1], np.int32)
    got = np.asarray(flash_attention_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens, num_heads=H,
        page_size=P, group=group, interpret=True, whole_chunk=True))
    oracle = np.asarray(decode_attention_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(np.repeat(lens, H)), D ** -0.5, group=group,
        whole_chunk=True))
    for bh in range(B * H):
        n = lens[bh // H] + q_len - 1
        seen = np.broadcast_to(np.arange(S)[None, :] < n,
                               (q_len * group, S))
        # row i of a group is head i % group at chunk position i // group
        want = np.concatenate([_dense(q[bh, i:i + 1][None], k[bh][None],
                                      v[bh][None], seen[i:i + 1],
                                      D ** -0.5)[0]
                               for i in range(q_len * group)])
        np.testing.assert_allclose(got[bh], want, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(oracle[bh], want, atol=2e-5, rtol=1e-4)
    # and it is another mask than the causal one wherever a chunk has rows
    causal = np.asarray(flash_attention_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens, num_heads=H,
        page_size=P, group=group, interpret=True))
    assert np.abs(causal[:, :group] - got[:, :group]).max() > 1e-3
    np.testing.assert_array_equal(causal[:, -group:], got[:, -group:])


@pytest.mark.parametrize("flash", ["never", "always"])
def test_decode_op_appends_a_block_and_attends_it_whole(flash):
    """``fused_decode_attention(whole_chunk=True)``: a block of 4 rows a
    sequence is appended at its position and every row attends the rows
    before the block and the whole block; a shut slot's cache is left as
    it was."""
    rng = np.random.default_rng(8)
    B, Hq, Hkv, S, D, L = 3, 4, 2, 32, 32, 4
    pos = np.array([[8], [0], [20]], np.int64)
    gate = np.array([[1.0], [1.0], [0.0]], np.float32)
    hist = rng.normal(size=(2, B, Hkv, S, D)).astype(np.float32)
    cache = hist.copy()
    for b in range(B):                      # nothing at or after the block
        cache[:, b, :, pos[b, 0]:] = 7.0
    q = rng.normal(size=(B, Hq, L, D)).astype(np.float32)
    new = np.stack([hist[:, b, :, pos[b, 0]:pos[b, 0] + L]
                    for b in range(B)], axis=1)          # [2, B, Hkv, L, D]
    feed = dict(q=q, kn=new[0], vn=new[1], ck=cache[0], cv=cache[1],
                pos=pos, gate=gate)

    def build():
        ck, cv = _data("ck", cache[0]), _data("cv", cache[1])
        out = layers.fused_decode_attention(
            _data("q", q), _data("kn", new[0]), _data("vn", new[1]), ck, cv,
            _data("pos", pos), page_size=8, slot_mask=_data("gate", gate),
            whole_chunk=True)
        return out, ck

    got, ck2 = _run(build, feed, flash)
    for b in range(2):
        n = pos[b, 0] + L
        want = _dense(q[b], hist[0, b, :, :n], hist[1, b, :, :n],
                      np.ones((L, n), bool), D ** -0.5)
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=1e-4)
        np.testing.assert_array_equal(ck2[b, :, :n], hist[0, b, :, :n])
    np.testing.assert_array_equal(ck2[2], cache[0, 2])


# -- the flash forward's block-causal mask ---------------------------------------

def _block_seen(S, L):
    at = np.arange(S) // L
    return at[None, :] <= at[:, None]


@pytest.mark.parametrize("L", [4, 8, 32])
def test_flash_forward_is_causal_by_blocks(L):
    """Two query tiles and two key tiles, grouped-query heads, a padding
    bias: only the diagonal tiles' mask differs from the row-causal one,
    and there a query sees the later rows of its own block."""
    rng = np.random.default_rng(L)
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 32
    q = rng.normal(size=(B * Hq, S, D)).astype(np.float32)
    k, v = (rng.normal(size=(B * Hkv, S, D)).astype(np.float32)
            for _ in range(2))
    real = np.array([S, 150])
    bias = np.where(np.arange(S)[None, :] < real[:, None], 0.0,
                    -10000.0).astype(np.float32)
    got = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        bias=jnp.asarray(bias), causal=True, causal_block=L, num_heads=Hq,
        interpret=True))
    for b in range(B):
        seen = _block_seen(S, L) & (np.arange(S)[None, :] < real[b])
        want = _dense(q[b * Hq:(b + 1) * Hq], k[b * Hkv:(b + 1) * Hkv],
                      v[b * Hkv:(b + 1) * Hkv], seen, D ** -0.5)
        rows = real[b] // L * L     # a block of padding alone sees nothing
        np.testing.assert_allclose(got[b * Hq:(b + 1) * Hq, :rows],
                                   want[:, :rows], atol=2e-5, rtol=1e-4)
    with pytest.raises(ValueError, match="causal_block"):
        flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=False, causal_block=L, interpret=True)


@pytest.mark.parametrize("flash", ["never", "always"])
def test_prefill_op_is_causal_by_blocks(flash):
    rng = np.random.default_rng(13)
    B, Hq, Hkv, S, D, L = 2, 4, 2, 128, 32, 4
    q, k, v = (rng.normal(size=(B, h, S, D)).astype(np.float32)
               for h in (Hq, Hkv, Hkv))
    got, = _run(lambda: [layers.fused_multihead_attention(
        _data("q", q), _data("k", k), _data("v", v), causal=True,
        is_test=True, causal_block=L)], dict(q=q, k=k, v=v), flash)
    for b in range(B):
        want = _dense(q[b], k[b], v[b], _block_seen(S, L), D ** -0.5)
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=1e-4)
    with pytest.raises(Exception, match="causal_block"):
        _run(lambda: [layers.fused_multihead_attention(
            _data("q", q), _data("k", k), _data("v", v), causal=True,
            is_test=True, causal_block=L, window=16)],
            dict(q=q, k=k, v=v), flash)


# -- the block's state ---------------------------------------------------------------

def test_block_seed_opens_the_first_block_with_the_prompts_remainder():
    ids = np.arange(100, 132, dtype=np.int64).reshape(4, 8)
    plen = np.array([[8], [3], [6], [5]], np.int64)
    toks, at, start, seated = _run(lambda: layers.block_seed(
        _data("ids", ids), _data("plen", plen), 4, 0),
        dict(ids=ids, plen=plen))
    assert start[:, 0].tolist() == [8, 0, 4, 4]
    assert toks.tolist() == [[0, 0, 0, 0], [108, 109, 110, 0],
                             [120, 121, 0, 0], [128, 0, 0, 0]]
    assert at.tolist() == [[0, 0, 0, 0], [-1, -1, -1, 0], [-1, -1, 0, 0],
                           [-1, 0, 0, 0]]
    assert seated.sum(axis=1).tolist() == [8, 0, 4, 4]


def _reveal(logits, toks, at, start, step, gate, steps, max_seq=64):
    feed = dict(lg=logits.astype(np.float32), toks=toks.astype(np.int64),
                at=at.astype(np.int64),
                start=np.asarray(start, np.int64)[:, None],
                step=np.asarray(step, np.int64)[:, None],
                gate=np.asarray(gate, np.float32)[:, None])

    def build():
        state = [_data(n, feed[n]) for n in ("toks", "at", "start", "step")]
        out = layers.block_reveal(_data("lg", feed["lg"]), *state,
                                  _data("gate", feed["gate"]), 0, steps,
                                  max_seq)
        return state + list(out)

    return _run(build, feed)


def test_block_reveal_takes_the_most_confident_and_never_the_mask_id():
    """Four slots at forward 0 of a block of 4 in 2 steps (two positions a
    forward): plain; equal confidences (the lower position first); one
    position known from the prompt; a shut gate."""
    V = 16
    lg = np.zeros((4, 4, V))
    lg[:, :, 0] = 50.0                      # the mask id scores best of all
    peak = lambda b, i, tok, h: lg.__setitem__((b, i, tok), h)
    for i, h in enumerate((1.0, 4.0, 2.0, 3.0)):
        peak(0, i, 5 + i, h)                # positions 1 and 3 lead
        peak(3, i, 5 + i, h)
    for i in range(4):
        peak(1, i, 9, 2.0)                  # all alike: positions 0 and 1
    for i, h in enumerate((9.0, 1.0, 3.0, 2.0)):
        peak(2, i, 11 + i, h)               # position 0 is a prompt token
    toks = np.zeros((4, 4))
    toks[2, 0] = 77
    at = np.zeros((4, 4))
    at[2, 0] = -1
    toks2, at2, start2, step2, out, out_at, cnt = _reveal(
        lg.reshape(16, V), toks, at, [8, 8, 8, 8], [0, 0, 0, 0],
        [1, 1, 1, 0], steps=2)
    assert toks2.tolist() == [[0, 6, 0, 8], [9, 9, 0, 0], [77, 0, 13, 14],
                              [0, 0, 0, 0]]
    assert step2[:, 0].tolist() == [1, 1, 1, 0]
    assert start2[:, 0].tolist() == [8, 8, 8, 8] and not cnt.any()
    # forward 1 reveals the rest; forward 2 commits and moves on
    lg[0, 0, 3], lg[0, 2, 4] = 60.0, 60.0
    toks3, at3, _, step3, _, _, cnt = _reveal(
        lg.reshape(16, V), toks2, at2, [8, 8, 8, 8], step2[:, 0],
        [1, 1, 1, 0], steps=2)
    assert toks3[0].tolist() == [3, 6, 4, 8] and at3[0].tolist() == [1, 0, 1,
                                                                     0]
    assert toks3[2].tolist() == [77, 12, 13, 14] and not cnt.any()
    toks4, at4, start4, step4, out, out_at, cnt = _reveal(
        lg.reshape(16, V), toks3, at3, [8, 8, 60, 8], step3[:, 0],
        [1, 1, 1, 0], steps=2)
    assert cnt[:, 0].tolist() == [4, 4, 3, 0]
    assert out[0].tolist() == [3, 6, 4, 8] and out_at[0].tolist() == [1, 0,
                                                                      1, 0]
    assert out[2, :3].tolist() == [12, 13, 14]      # the prompt's left off
    assert out_at[2, :3].tolist() == [1, 0, 0]
    assert (toks4[:3] == 0).all() and step4[:, 0].tolist() == [0, 0, 0, 0]
    # a block never starts past the cache's last one
    assert start4[:, 0].tolist() == [12, 12, 60, 8]
