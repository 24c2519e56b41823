"""Latent attention: the decode kernel over a latent cache (interpreted on
the CPU) against its ``jax.numpy`` reference, and the op's two routes
through one set of weights — the expanded prefill form and the absorbed
decode form — against each other and against attention written out a head
at a time.

Tolerances: in f32 the kernel's online softmax and the reference's plain
one differ by the order of their sums, 2e-5 on outputs of order 0.1 to 1.
With bf16 operands the kernel rounds the unnormalised probabilities where
the reference rounds nothing after the scores: 2e-2.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import layers
from paddle_tpu.kernels.latent_attention import (
    latent_block_rows, latent_row_width, latent_walk_blocks,
    mla_decode_attention, mla_decode_attention_reference)

TOL = 2e-5


def _operands(rng, B, H, S, dc, dr, dtype=jnp.float32, spread=1.0):
    """A query ``[qt | q_rope | 0]`` a head and a cache of rows ``[c |
    k_rope | 0]``, as the op lays them out."""
    W = latent_row_width(dc, dr)
    lanes = jnp.arange(W) < dc + dr
    f = lambda *shape: jnp.where(
        lanes, jnp.asarray(rng.normal(size=shape) * spread, dtype), 0)
    return f(B, H, W), f(B, S, W)


# -- the kernel ---------------------------------------------------------------

# page 8 and 64 rows: latent_block_rows gives the whole cache as one block;
# block_rows=16 makes the walk four blocks long
@pytest.mark.parametrize("lengths,block", [
    ((16, 32, 48, 64), 16),         # at block ends, and the full cache
    ((1, 15, 17, 63), 16),          # one row; below and across a block
    ((5, 0, 40, 0), 16),            # masked-out slots see no key
    ((64, 1, 33, 20), None),        # the module's own choice of block
])
def test_kernel_equals_its_reference(lengths, block):
    B, H, S, dc, dr = 4, 5, 64, 128, 32
    q, cache = _operands(np.random.default_rng(0), B, H, S, dc, dr)
    lens = jnp.asarray(lengths, jnp.int32)
    want = mla_decode_attention_reference(q, cache, lens, dc, 0.1)
    got = mla_decode_attention(q, cache, lens, latent_dim=dc, scale=0.1,
                               page_size=8, block_rows=block,
                               interpret=True)
    np.testing.assert_allclose(got, want, atol=TOL)
    for b, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(got[b]).any()
    assert float(jnp.abs(want).max()) > 0.1


def test_rows_past_a_length_are_never_read():
    """Garbage past the length, non-finite too, in the tail of the last
    live block and in the blocks after it, changes nothing."""
    B, H, S, dc, dr = 2, 3, 64, 128, 32
    q, cache = _operands(np.random.default_rng(1), B, H, S, dc, dr)
    lens = jnp.asarray([21, 40], jnp.int32)
    run = lambda c: mla_decode_attention(
        q, c, lens, latent_dim=dc, scale=0.1, page_size=8, block_rows=16,
        interpret=True)
    past = np.arange(S)[None, :, None] >= np.asarray(lens)[:, None, None]
    later = np.arange(S)[None, :, None] >= 48       # no sequence's block
    dirty = jnp.where(past, jnp.where(later, jnp.nan, 1e4), cache)
    np.testing.assert_array_equal(run(dirty), run(cache))


def test_kernel_in_bf16_at_twenty_heads():
    """bf16, page 128, rows of 128 + 64 padded to 256 lanes: 20 heads ride
    32 sublanes."""
    B, H, S, dc, dr = 2, 20, 256, 128, 64
    q, cache = _operands(np.random.default_rng(2), B, H, S, dc, dr,
                         jnp.bfloat16, 0.3)
    cache = cache / 0.3
    lens = jnp.asarray([130, 256], jnp.int32)
    want = mla_decode_attention_reference(q, cache, lens, dc, 0.25)
    got = mla_decode_attention(q, cache, lens, latent_dim=dc, scale=0.25,
                               page_size=128, block_rows=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_block_and_walk_at_the_published_geometry():
    """A row of 512 + 64 numbers lies in 640 lanes, 1,280 bytes in bf16:
    eight pages of 128 make the step 1.31 MB (``kv_tile``'s rule on the
    bytes a step fetches), so a 4,096-row cache is four blocks, and a
    sequence is charged whole blocks up to its last live one."""
    assert latent_row_width(512, 64) == 640
    assert latent_block_rows(4096, 640, jnp.bfloat16, 128) == 1024
    fetched, held = latent_walk_blocks(
        np.array([1, 1024, 1025, 4096]), (4, 1, 4096, 640), jnp.bfloat16,
        128)
    assert (fetched, held) == (1 + 1 + 2 + 4, 16)


# -- the op -------------------------------------------------------------------

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


def _naive(q, c, kr, w, nh, dn, dv, n):
    """Row ``n - 1``'s attention over rows ``0..n-1`` a head at a time, in
    float64, keys and values expanded: [nh, dv]."""
    dc = c.shape[-1]
    wh = w.reshape(dc, nh, dn + dv).astype(np.float64)
    out = []
    for i in range(nh):
        k = np.concatenate([c[:n] @ wh[:, i, :dn], kr[:n]], axis=-1)
        v = c[:n] @ wh[:, i, dn:]
        s = k @ q[i] * (q.shape[-1] ** -0.5)
        p = np.exp(s - s.max())
        out.append((p / p.sum()) @ v)
    return np.stack(out)


@pytest.mark.parametrize("flash", ["auto", "always"])
def test_the_op_prefills_named_slots_then_decodes_the_same_numbers(flash):
    """Two prompts of 13 and 6 rows in a bucket of 16 go to slots 2 and 0
    of 4 (a third sequence of the dispatch is masked out). Every prompt
    row's output is the expanded form's; then two decode steps (slot 1
    idle, slot 3 never filled) give the absorbed form's, against the same
    attention written out a head at a time. Rows are appended then read;
    slots nobody named, and a slot whose gate is shut, keep their rows."""
    rng = np.random.default_rng(5)
    nh, dn, dr, dv, dc, B, R, S, S_max = 3, 24, 8, 32, 128, 4, 3, 16, 32
    lens = [13, 6, 9]
    f32 = lambda *shape: rng.normal(size=shape).astype(np.float32)
    q = f32(R, nh, S + 2, dn + dr)
    c, kr = f32(R, S + 2, dc) * 0.3, f32(R, S + 2, dr)
    w = f32(dc, nh * (dn + dv)) * 0.2
    cache0 = f32(B, 1, S_max, 256)                  # 128 + 8, in 256 lanes
    slots = np.array([[2], [0], [1]], np.int64)
    smask = np.array([[1.0], [1.0], [0.0]], np.float32)
    of_slot = {2: 0, 0: 1}
    gate = np.array([[1.0], [0.0], [1.0], [0.0]], np.float32)
    step = {"q": np.zeros((2, B, nh, 1, dn + dr), np.float32),
            "c": np.zeros((2, B, 1, dc), np.float32),
            "kr": np.zeros((2, B, 1, dr), np.float32)}
    pos = np.zeros((2, B, 1), np.int64)
    for slot, r in of_slot.items():
        for t in range(2):
            step["q"][t, slot, :, 0] = q[r, :, lens[r] + t]
            step["c"][t, slot, 0] = c[r, lens[r] + t]
            step["kr"][t, slot, 0] = kr[r, lens[r] + t]
            pos[t, slot, 0] = lens[r] + t

    def build():
        wv, cv = _data("w", w), _data("cache", cache0)
        o, n = layers.latent_attention(
            _data("q", q[:, :, :S]), _data("c", c[:, :S]),
            _data("kr", kr[:, :S]), wv, cv,
            _data("pos0", pos[0][:R]), dn, mode="prefill", page_size=8,
            slot_mask=_data("smask", smask), slots=_data("slots", slots))
        outs = [o, n]
        for t in range(2):
            o, n = layers.latent_attention(
                _data(f"q{t}", step["q"][t]), _data(f"c{t}", step["c"][t]),
                _data(f"kr{t}", step["kr"][t]), wv, cv,
                _data(f"pos{t + 1}", pos[t]), dn, page_size=8,
                slot_mask=_data("gate", gate))
            outs += [o, n]
        return outs + [cv]

    feed = dict(w=w, cache=cache0, q=q[:, :, :S], c=c[:, :S],
                kr=kr[:, :S], pos0=pos[0][:R], smask=smask, slots=slots,
                gate=gate)
    for t in range(2):
        feed.update({f"q{t}": step["q"][t], f"c{t}": step["c"][t],
                     f"kr{t}": step["kr"][t], f"pos{t + 1}": pos[t]})
    o, n, o0, n0, o1, n1, cache = _run(build, feed, flash)
    assert int(n[0]) == R * S
    # one block of 32 rows a live slot, one for a slot that sees nothing
    assert int(n0[0]) == int(n1[0]) == B * S_max
    for slot, r in of_slot.items():
        L = lens[r]
        for row in range(L):
            want = _naive(q[r, :, row], c[r], kr[r], w, nh, dn, dv, row + 1)
            np.testing.assert_allclose(o[r, :, row], want, atol=TOL)
        for t, got in enumerate((o0, o1)):
            want = _naive(q[r, :, L + t], c[r], kr[r], w, nh, dn, dv,
                          L + t + 1)
            np.testing.assert_allclose(got[slot, :, 0], want, atol=TOL)
        # appended: the prompt's rows, then the two steps', as [c | k_rope
        # | 0]
        np.testing.assert_array_equal(cache[slot, 0, :L + 2, :dc],
                                      c[r, :L + 2])
        np.testing.assert_array_equal(cache[slot, 0, :L + 2, dc:dc + dr],
                                      kr[r, :L + 2])
        assert not cache[slot, 0, :L + 2, dc + dr:].any()
        np.testing.assert_array_equal(cache[slot, 0, S:], cache0[slot, 0, S:])
    for slot in (1, 3):         # masked out of the prefill, idle in decode
        np.testing.assert_array_equal(cache[slot], cache0[slot])
        assert not np.asarray(o0[slot]).any()
