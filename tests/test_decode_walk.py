"""The decode kernel walks the keys a sequence HAS (PR 28).

``flash_attention_decode`` carries a tile of its own choosing a grid step
(``kernels.decode_attention.kv_tile``: a page of several of a sequence's heads, or a few
pages), fetches k-blocks up to each sequence's last live one and scores
nothing past it. Proven here on the CPU, ``interpret=True``, at the two
serving cells' head shapes: blocks wholly past a sequence's length hold NaN
and the tail of its last live block holds 1e30, so an output that is finite
and equal to the reference's (computed on the clean caches) was made
without a dead block ever being scored.
"""
import types
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.kernels import (decode_attention_reference,
                                decode_grid_steps, decode_walk_blocks,
                                flash_attention_decode,
                                kv_append, paged_kv_append,
                                paged_kv_append_rows, rows_minor)
from paddle_tpu.kernels.decode_attention import (_decode_call, kv_tile,
                                                 last_live_block, walk_steps)
from paddle_tpu.models.gpt import GptConfig, build_gpt_generative

PAGE = 128
# name: key/value heads, cache rows, head dim, dtype, query heads a group
SHAPES = {
    "f32-d64": (12, 512, 64, jnp.float32, 1),          # GPT-2's, R 8
    "bf16-d128-g16": (8, 1024, 128, jnp.bfloat16, 16),  # Command A+'s
    # heads of 64 again: the kernel reads all three rows-minor (PR 32)
    "f32-d64-g2": (12, 512, 64, jnp.float32, 2),
    "bf16-d64": (24, 512, 64, jnp.bfloat16, 1),
}
ROWS_MINOR = {"f32-d64", "f32-d64-g2", "bf16-d64"}
# name: (lengths in units of (k-blocks, rows): n = blocks * block + rows,
#        q_len)
WALKS = {
    "empty-and-one": ([(0, 0), (0, 1)], 1),
    "around-a-block": ([(1, -1), (1, 0), (1, 1)], 1),
    "ragged": ([(0, 1), (2, 7), (4, 0), (1, 1)], 1),
    "full-ring": ([(4, 0), (4, 0)], 1),
    "chunk-across-a-block": ([(1, -3), (2, -1), (0, 5), (1, 0)], 8),
    "chunk-across-the-end": ([(4, -2), (1, 0)], 4),
}


def _case(shape, walk):
    H, S, D, dt, G = SHAPES[shape]
    spec, q_len = WALKS[walk]
    _, block = kv_tile(H, S, D, dt, PAGE)
    assert S // block == 4, "the cases count in a cache of four k-blocks"
    assert rows_minor(D, dt, PAGE) == (shape in ROWS_MINOR)
    lengths = np.array([b * block + r for b, r in spec], np.int32)
    q_len = min(q_len, 32 // G)     # 2 x 16 heads: two sublane tiles
    return H, S, D, dt, G, block, lengths, q_len


@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_scores_nothing_past_a_sequences_length(shape, walk):
    H, S, D, dt, G, block, lengths, q_len = _case(shape, walk)
    B = len(lengths)
    rng = np.random.default_rng(zlib.crc32(f"{shape}/{walk}".encode()))
    q = jnp.asarray(rng.normal(size=(B * H, q_len * G, D)), dt)
    k, v = (rng.normal(size=(B, H, S, D)).astype(np.float32)
            for _ in range(2))
    ref = decode_attention_reference(
        q, jnp.asarray(k.reshape(B * H, S, D), dt),
        jnp.asarray(v.reshape(B * H, S, D), dt),
        jnp.asarray(np.repeat(lengths, H)), D ** -0.5, group=G)
    # what the chunk's last row sees is the last key any row may touch
    seen = np.minimum(lengths + q_len - 1, S)
    live = -(-np.maximum(seen, 1) // block) * block       # block 0 is kept
    k, v = k.copy(), v.copy()       # the reference may still read its own
    for b in range(B):
        for c in (k, v):
            c[b, :, seen[b]:live[b]] = 1e30
            c[b, :, live[b]:] = np.nan
    out = flash_attention_decode(
        q, jnp.asarray(k.reshape(B * H, S, D), dt),
        jnp.asarray(v.reshape(B * H, S, D), dt), lengths, num_heads=H,
        page_size=PAGE, group=G, interpret=True)
    out = np.asarray(out, np.float32).reshape(B, H, q_len * G, D)
    ref = np.asarray(ref, np.float32).reshape(B, H, q_len * G, D)
    assert np.isfinite(out).all()
    tol = dict(atol=2e-5, rtol=1e-4) if dt == jnp.float32 else dict(
        atol=3e-2, rtol=3e-2)
    for b in range(B):
        # rows that see no key at all are zeros (an empty slot, as ever);
        # the reference's softmax over nothing is a mean of every row
        blind = np.repeat(lengths[b] + np.arange(q_len) == 0, G)
        assert not out[b][:, blind].any()
        np.testing.assert_allclose(out[b][:, ~blind], ref[b][:, ~blind],
                                   **tol)


@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("q_len", [1, 8])
def test_walk_table_lists_each_visits_live_blocks_in_order(q_len, groups):
    """The grid of a call (PR 53): a step a k-block that is fetched. Visit
    ``v`` (a sequence's group of heads, in the order sequence, group) walks
    blocks 0 to its sequence's last live one in ascending order, the visits
    one after another, and the grid's bound is their number; past it the
    table repeats its last step. From traced lengths, as the call has them."""
    block, num_k = 128, 8
    lens = np.array([0, 1, 127, 128, 129, 300, 1017, 1024], np.int32)
    table, steps = jax.jit(
        lambda n: walk_steps(n, q_len, block, num_k, groups))(lens)
    want = []
    for b, n in enumerate(lens):
        last = min(max(int(n) + q_len - 2, 0) // block, num_k - 1)
        assert int(last_live_block(n, q_len, block, num_k)) == last
        for hg in range(groups):
            want += [(b * groups + hg) * num_k + ik
                     for ik in range(last + 1)]
    assert int(steps) == len(want)
    assert table.shape == (len(lens) * groups * num_k,)
    assert table.dtype == jnp.int32 and steps.dtype == jnp.int32
    assert list(np.asarray(table)[:len(want)]) == want
    assert set(np.asarray(table)[len(want):]) <= {want[-1]}


def test_view_is_read_from_the_cache_shape():
    """Rows in lanes where the head dimension is whole sublane tiles of the
    dtype and not whole lane tiles, and a page is whole lane tiles (a
    k-block is then whole vregs of rows): GPT-2's 64. The other decoders'
    heads, 128 and 256, keep the logical view, and so does every small
    shape the CPU tests drive."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert rows_minor(64, f32, 128) and rows_minor(64, bf16, 128)
    assert rows_minor(96, f32, 256) and rows_minor(192, bf16, 128)
    for D, dt, page in [(128, bf16, 128), (256, bf16, 128), (128, f32, 128),
                        (64, f32, 64), (64, f32, 8), (16, f32, 8),
                        (8, bf16, 128), (4, f32, 128), (72, bf16, 128)]:
        assert not rows_minor(D, dt, page)


_kv_append_jit = jax.jit(
    lambda c, n, p, m, ring: kv_append(c, n, p, m, ring, interpret=True),
    static_argnums=4)


def _kernel_append(cache, new, pos, mask=None, ring=False):
    """``kv_append`` under the interpreter, on the swapped cache, swapped
    back: what ``fused_decode_attention`` does on its Pallas routes."""
    return _kv_append_jit(cache.swapaxes(2, 3), new, pos, mask,
                          ring).swapaxes(2, 3)


def _bits(a):
    return np.asarray(a, np.float32).tobytes()


# name: dtype, rows a step, start rows of four sequences in a cache of 256
# rows (mask 1, 0, 1, 1), ring
COLUMN_APPENDS = {
    "f32-step": (jnp.float32, 1, (3, 200, 255, 300), False),
    "bf16-step": (jnp.bfloat16, 1, (0, 7, 128, 255), False),
    "f32-chunk-across-the-end": (jnp.float32, 8, (3, 100, 250, 255), False),
    "bf16-chunk": (jnp.bfloat16, 8, (0, 249, 127, 300), False),
    "f32-ring": (jnp.float32, 1, (3, 255, 256, 1000), True),
    "bf16-ring": (jnp.bfloat16, 1, (511, 5, 256, 257), True),
}


@pytest.mark.parametrize("case", sorted(COLUMN_APPENDS))
def test_column_append_is_the_row_append_in_the_other_view(case):
    """The kernel on the swapped cache writes what the row form writes on
    the logical one, bit for bit: the per-row clamp onto the last row, the
    ring, and a masked-out sequence's cache untouched."""
    dt, rows, positions, ring = COLUMN_APPENDS[case]
    B, H, S, D = 4, 3, 256, 64
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    cache = jnp.asarray(rng.normal(size=(B, H, S, D)), dt)
    new = jnp.asarray(rng.normal(size=(B, H, rows, D)), dt)
    pos = jnp.asarray(positions, jnp.int32)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32)
    want = np.asarray(paged_kv_append_rows(cache, new, pos, mask, ring=ring),
                      np.float32)
    got = np.asarray(_kernel_append(cache, new, pos, mask, ring), np.float32)
    assert got.tobytes() == want.tobytes()
    old = np.asarray(cache, np.float32)
    assert got[1].tobytes() == old[1].tobytes()
    assert (got[0] != old[0]).any()
    # written out: row i of a step lands on min(p + i, S - 1), or p % S
    for b in (0, 2, 3):
        for i in range(rows):
            at = (positions[b] + i) % S if ring else min(positions[b] + i,
                                                         S - 1)
            if i == rows - 1 or positions[b] + i < S - 1:
                np.testing.assert_array_equal(
                    got[b, :, at], np.asarray(new, np.float32)[b, :, i])


def test_column_append_of_a_chunk_is_the_bulk_write_where_nothing_clamps():
    """Five rows a sequence, row by row through the kernel, against the
    bulk form's one ``dynamic_update_slice`` a sequence on the logical
    cache: inside the cache the two agree, a chunk across a block edge
    (rows 126-130) and a masked-out sequence among them."""
    B, H, S, D = 4, 3, 256, 64
    rng = np.random.default_rng(32)
    cache = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(B, H, 5, D)), jnp.float32)
    pos, mask = jnp.asarray([7, 130, 126, 251]), jnp.asarray([1., 0., 1., 1.])
    want = np.asarray(paged_kv_append(cache, new, pos, mask))
    got = np.asarray(_kernel_append(cache, new, pos, mask))
    assert got.tobytes() == want.tobytes()
    np.testing.assert_array_equal(got[2, :, 126:131], np.asarray(new)[2])
    np.testing.assert_array_equal(got[1], np.asarray(cache)[1])


# name: dtype, heads, head dimension (64: GPT-2's; 16: the tiny test models')
APPEND_SHAPES = {
    "f32-d64-h12": (jnp.float32, 12, 64),
    "bf16-d64-h12": (jnp.bfloat16, 12, 64),
    "f32-d64-h1": (jnp.float32, 1, 64),
    "bf16-d64-h1": (jnp.bfloat16, 1, 64),
    "f32-d16-h2": (jnp.float32, 2, 16),
}
APPEND_MASKS = {"all-on": (1, 1, 1, 1, 1, 1), "mixed": (1, 0, 0, 1, 0, 1),
                "all-off": (0, 0, 0, 0, 0, 0), "none": None}


@pytest.mark.parametrize("rows", [1, 2, 8])
@pytest.mark.parametrize("mask", sorted(APPEND_MASKS))
@pytest.mark.parametrize("shape", sorted(APPEND_SHAPES))
def test_kv_append_kernel_is_the_row_form_bit_for_bit(shape, mask, rows):
    """``kv_append`` against ``paged_kv_append_rows``: six sequences in a
    cache of three lane blocks start at rows 0, 127 (a chunk crosses into
    the next block), 128, ``S_max - 2``, ``S_max - 1`` and past the end
    (both clamp onto the last row, where the chunk's last row wins); a
    sequence whose mask is 0 keeps its cache to the bit."""
    dt, H, D = APPEND_SHAPES[shape]
    B, S = 6, 384
    rng = np.random.default_rng(zlib.crc32(f"{shape}/{mask}/{rows}".encode()))
    cache = jnp.asarray(rng.normal(size=(B, H, S, D)), dt)
    new = jnp.asarray(rng.normal(size=(B, H, rows, D)), dt)
    pos = jnp.asarray([0, 127, 128, S - 2, S - 1, S + 40], jnp.int32)
    keep = APPEND_MASKS[mask]
    m = None if keep is None else jnp.asarray(keep, jnp.float32)[:, None]
    want = paged_kv_append_rows(cache, new, pos, m)
    got = _kernel_append(cache, new, pos, m)
    assert got.dtype == cache.dtype and _bits(got) == _bits(want)
    for b in range(B):
        same = _bits(got[b]) == _bits(cache[b])
        assert same == (keep is not None and not keep[b])
    if keep is None or keep[1]:
        last = min(rows, 2) - 1      # rows 127 and 128: two lane blocks
        np.testing.assert_array_equal(
            np.asarray(got[1, :, 127 + last], np.float32),
            np.asarray(new[1, :, last], np.float32))


def _rows_written_one_by_one(cache, new, positions, keep, ring):
    """NumPy: row ``i`` of sequence ``b`` to row ``min(p + i, S - 1)`` of
    its cache, or ``(p + i) % S`` of its ring, in the chunk's order (a
    later row overwrites an earlier one); nothing where ``keep[b]`` is 0."""
    out = np.array(cache)
    S = out.shape[-2]
    for b, p in enumerate(positions):
        for i in range(new.shape[-2]):
            if keep is None or keep[b]:
                at = (p + i) % S if ring else min(p + i, S - 1)
                out[b, ..., at, :] = np.asarray(new)[b, ..., i, :]
    return out


# name: dtype, head dimension (MiMo-V2-Flash's keys lie in 256 lanes beside
# values of 128; 16: the tiny test models'), cache rows, ring
SCATTER_SHAPES = {
    "f32-d128": (jnp.float32, 128, 32, False),
    "bf16-d128": (jnp.bfloat16, 128, 32, False),
    "bf16-d256": (jnp.bfloat16, 256, 32, False),
    "f32-d16": (jnp.float32, 16, 24, False),
    "bf16-d128-ring": (jnp.bfloat16, 128, 16, True),
    "f32-d256-ring": (jnp.float32, 256, 16, True),
}


@pytest.mark.parametrize("rows", [1, 4, 8, 16])
@pytest.mark.parametrize("mask", sorted(APPEND_MASKS))
@pytest.mark.parametrize("shape", sorted(SCATTER_SHAPES))
def test_row_scatter_is_the_row_by_row_write_bit_for_bit(shape, mask, rows):
    """``paged_kv_append_rows``, one scatter a cache for every row count,
    against the rows written one by one in NumPy: six sequences at row 0,
    inside the cache, with the chunk's last row on ``S - 1``, on ``S - 2``
    (the rows a later one shadows must not win), on ``S - 1`` and past the
    end (a ring wraps them all); a sequence whose mask is 0 keeps its
    cache to the bit, and so does every row the chunk does not name."""
    dt, D, S, ring = SCATTER_SHAPES[shape]
    if ring and rows > 8:
        with pytest.raises(NotImplementedError, match="ring"):
            paged_kv_append_rows(jnp.zeros((1, 1, S, D)),
                                 jnp.zeros((1, 1, rows, D)),
                                 jnp.zeros((1,), jnp.int32), ring=True)
        return
    B, H = 6, 3
    rng = np.random.default_rng(zlib.crc32(f"{shape}/{mask}/{rows}".encode()))
    cache = jnp.asarray(rng.normal(size=(B, H, S, D)), dt)
    new = jnp.asarray(rng.normal(size=(B, H, rows, D)), dt)
    positions = [0, 5, max(S - rows, 0), S - 2, S - 1, 3 * S + 7]
    keep = APPEND_MASKS[mask]
    m = None if keep is None else jnp.asarray(keep, jnp.float32)[:, None]
    got = jax.jit(paged_kv_append_rows, static_argnames="ring")(
        cache, new, jnp.asarray(positions, jnp.int32), m, ring=ring)
    assert got.dtype == cache.dtype and got.shape == cache.shape
    want = _rows_written_one_by_one(cache, new, positions, keep, ring)
    assert np.asarray(got).tobytes() == want.tobytes()
    for b in range(B):
        same = _bits(got[b]) == _bits(cache[b])
        assert same == (keep is not None and not keep[b])


def test_kv_append_kernel_walks_more_sequences_than_a_lane_tile():
    """The sequences' columns ride in lanes, 128 a block of ``new``: 130
    sequences take a second block."""
    B, H, S, D = 130, 1, 128, 16
    rng = np.random.default_rng(34)
    cache = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
    assert _bits(_kernel_append(cache, new, pos, mask)) == _bits(
        paged_kv_append_rows(cache, new, pos, mask))


def test_kv_append_kernel_refuses_a_cache_of_broken_lane_tiles():
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        kv_append(jnp.zeros((1, 1, 16, 192)), jnp.zeros((1, 1, 1, 16)),
                  jnp.zeros((1,), jnp.int32))


def _append_then_attend(q, ck, cv, kn, vn, pos, mask, H, G, tile=None):
    """The unfused route on caches [B, H, S, D]: ``kv_append`` of each
    cache, then the decode kernel on the results (``tile``: through
    ``_decode_call`` on that tile and not on ``kv_tile``'s)."""
    B, _, S, D = ck.shape
    ck, cv = (kv_append(c.swapaxes(2, 3), n, pos, mask,
                        interpret=True).swapaxes(2, 3)
              for c, n in ((ck, kn), (cv, vn)))
    return _attend(q, ck, cv, jnp.minimum(pos + 1, S), H, G, tile), ck, cv


def _attend(q, ck, cv, lengths, H, G, tile, append=None):
    B, _, S, D = ck.shape
    if tile is None:
        return flash_attention_decode(
            q, ck.reshape(B * H, S, D), cv.reshape(B * H, S, D), lengths,
            num_heads=H, page_size=PAGE, group=G, interpret=True,
            append=append)
    if append is not None:
        append = (*append[:2], (append[2].reshape(B) > 0).astype(jnp.int32))
    R = 8 * (4 // q.dtype.itemsize)
    q8 = jnp.concatenate([q, jnp.broadcast_to(
        q[:, -1:], (B * H, R - q.shape[1], D))], axis=1)
    out = _decode_call(q8, ck, cv, lengths, tile, minor=True,
                       scale=D ** -0.5, group=G, q_len=1, interpret=True,
                       append=append)
    return out[:, :G] if append is None else (out[0][:, :G], *out[1:])


# name: dtype, key/value heads, query heads a group, cache rows, the tile
# (a number of rows: `kv_tile`'s own, all heads and so many rows)
IN_KERNEL_APPENDS = {
    "f32-h12": (jnp.float32, 12, 1, 384, 128),           # GPT-2's tile
    "bf16-h12-g2-three-pages": (jnp.bfloat16, 12, 2, 384, 384),
    "f32-four-pages": (jnp.float32, 3, 1, 1024, 512),
    "f32-head-tile-under-heads": (jnp.float32, 4, 1, 384, (2, 128)),
    "bf16-two-pages-two-head-groups": (jnp.bfloat16, 4, 1, 512, (2, 256)),
}
IN_KERNEL_MASKS = {"mixed": (1, 0, 1, 1, 0, 1, 1), "all-on": (1,) * 7,
                   "all-off": (0,) * 7, "none": None}


@pytest.mark.parametrize("mask", sorted(IN_KERNEL_MASKS))
@pytest.mark.parametrize("case", sorted(IN_KERNEL_APPENDS))
def test_decode_kernel_appends_what_append_then_attend_does(case, mask):
    """``flash_attention_decode(append=...)`` against ``kv_append`` and
    then the kernel: seven sequences at positions 0, 127, 128, one inside a
    later block, ``S - 2``, ``S - 1`` and a saturated one past the end
    (its row clamps onto the last, which is its last live block's); the
    attention and both caches the same BITS, a sequence whose mask is 0
    with its caches untouched, a tile of fewer heads than the cache has
    (two groups of heads a sequence, each writing its own block) and tiles
    of two and four pages (the column lands in one lane tile of several)
    among the shapes."""
    dt, H, G, S, tile = IN_KERNEL_APPENDS[case]
    B, D = 7, 64
    if isinstance(tile, int):
        assert kv_tile(H, S, D, dt, PAGE) == (H, tile)
        tile = None
    rng = np.random.default_rng(zlib.crc32(f"{case}/{mask}".encode()))
    q = jnp.asarray(rng.normal(size=(B * H, G, D)), dt)
    ck, cv = (jnp.asarray(rng.normal(size=(B, H, S, D)), dt)
              for _ in range(2))
    kn, vn = (jnp.asarray(rng.normal(size=(B, H, 1, D)), dt)
              for _ in range(2))
    pos = jnp.asarray([0, 127, 128, 300, S - 2, S - 1, S + 40], jnp.int32)
    keep = IN_KERNEL_MASKS[mask]
    m = None if keep is None else jnp.asarray(keep, jnp.float32)[:, None]
    m1 = jnp.ones((B, 1), jnp.float32) if m is None else m
    want = jax.jit(lambda *a: _append_then_attend(
        *a, jnp.minimum(pos, S - 1), m, H, G, tile))(q, ck, cv, kn, vn)
    got = jax.jit(lambda *a: _attend(
        *a[:3], jnp.minimum(pos + 1, S), H, G, tile,
        append=(*a[3:], m if tile is None else m1)))(q, ck, cv, kn, vn)
    for w, g, name in zip(want, got, ("Out", "CacheK", "CacheV")):
        g = g.reshape(w.shape)
        assert g.dtype == w.dtype and _bits(g) == _bits(w), name
    for cache, old, new in ((got[1], ck, kn), (got[2], cv, vn)):
        cache = cache.reshape(old.shape)
        for b in range(B):
            untouched = _bits(cache[b]) == _bits(old[b])
            assert untouched == (keep is not None and not keep[b])
            if not untouched:
                at = min(int(pos[b]), S - 1)
                np.testing.assert_array_equal(
                    np.asarray(cache[b, :, at], np.float32),
                    np.asarray(new[b, :, 0], np.float32))


def test_decode_kernel_appends_one_row_to_a_rows_minor_cache_only():
    """A chunk of rows may cross a block's edge, a whole chunk sees past
    its first row and a cache of whole lane tiles a head has no column to
    merge: each is refused by name, so a caller appends first."""
    f32 = jnp.float32
    q, c = jnp.zeros((2, 1, 64), f32), jnp.zeros((2, 256, 64), f32)
    new = jnp.zeros((1, 2, 1, 64), f32)
    n = jnp.ones((1,), jnp.int32)
    for kw, qq, cc in [({}, jnp.zeros((2, 2, 64), f32), c),
                       ({"whole_chunk": True}, q, c),
                       ({}, jnp.zeros((2, 1, 128), f32),
                        jnp.zeros((2, 256, 128), f32))]:
        with pytest.raises(ValueError, match="appends one row a step"):
            flash_attention_decode(qq, cc, cc, n, num_heads=2,
                                   append=(new, new, None), **kw)


# name: dtype, q_len, query heads a group, head dim, window
ROUTED_STEPS = {
    "f32-step": (jnp.float32, 1, 1, 64, 0),
    "bf16-step-g2": (jnp.bfloat16, 1, 2, 64, 0),
    "f32-verify-chunk": (jnp.float32, 4, 1, 64, 0),
    "f32-two-rows": (jnp.float32, 2, 1, 64, 0),
    "f32-eight-rows-g2": (jnp.float32, 8, 2, 64, 0),
    "f32-ring": (jnp.float32, 1, 1, 64, 384),
    "bf16-heads-of-128": (jnp.bfloat16, 1, 1, 128, 0),
}


@pytest.mark.parametrize("case", sorted(ROUTED_STEPS))
def test_op_takes_the_in_kernel_append_for_a_step_of_one_row_only(case):
    """``fused_decode_attention`` on its kernel route picks the writer from
    what it sees in the shapes, and the counters say which: a step of one
    row on a rows-minor cache is written by the decode kernel
    (``IN_KERNEL``), chunks of 2 to 8 rows and a ring there by
    ``kv_append``, a cache of whole lane tiles a head by neither. Whatever
    the writer, the op returns what append-then-attend returns, bit for
    bit: positions 0, 127, 128, inside a later block, ``S - 1`` and a
    saturated one, the second slot masked out."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    dt, q_len, G, D, window = ROUTED_STEPS[case]
    B, H, S = 6, 3, 384
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)
    q, kn, vn = arr(B, H * G, q_len, D), arr(B, H, q_len, D), arr(
        B, H, q_len, D)
    ck, cv = arr(B, H, S, D), arr(B, H, S, D)
    pos = jnp.asarray([0, 127, 128, 300, S - 1, S + 40], jnp.int32)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 1.0, 1.0])[:, None]
    monitor.reset()
    fluid.set_flags({"FLAGS_use_flash_attention": "always"})
    try:
        got = get_op_def("fused_decode_attention").lower(
            LowerCtx(platform="cpu"),
            {"Q": [q], "KNew": [kn], "VNew": [vn], "CacheK": [ck],
             "CacheV": [cv], "Positions": [pos[:, None]],
             "SlotMask": [mask]},
            {"scale": 0.0, "page_size": PAGE, "window": window})
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    minor = rows_minor(D, dt, PAGE)
    in_kernel = minor and q_len == 1 and not window
    assert _append_routes(IN_KERNEL) == (
        {"pallas-interpret": 1} if in_kernel else {})
    assert _append_routes() == (
        {"pallas-interpret": 1} if minor and not in_kernel else {})
    # append-then-attend, written out
    if minor:
        ck2, cv2 = (_kernel_append(c, n, pos, mask, bool(window))
                    for c, n in ((ck, kn), (cv, vn)))
    else:
        ck2, cv2 = (paged_kv_append_rows(c, n, pos, mask)
                    for c, n in ((ck, kn), (cv, vn)))
    q3 = q.reshape(B * H, G, q_len, D).swapaxes(1, 2).reshape(
        B * H, q_len * G, D)
    o = flash_attention_decode(
        q3, ck2.reshape(B * H, S, D), cv2.reshape(B * H, S, D),
        jnp.minimum(pos + 1, S), num_heads=H, page_size=PAGE, group=G,
        interpret=True)
    o = o.reshape(B * H, q_len, G, D).swapaxes(1, 2).reshape(q.shape)
    assert _bits(got["Out"][0]) == _bits(o)
    assert _bits(got["CacheKOut"][0]) == _bits(ck2)
    assert _bits(got["CacheVOut"][0]) == _bits(cv2)
    assert _bits(got["CacheKOut"][0][1]) == _bits(ck[1])


IN_KERNEL = "fused_decode_attention.append_in_kernel"


def _append_routes(op="kv_append"):
    """``kernel_route_total{op=...}`` as {route: lowerings}: ``kv_append``
    where the append kernel writes a chunk's rows, ``IN_KERNEL`` where the
    decode kernel writes a step's one row itself."""
    fam = monitor.get_registry().get("kernel_route_total")
    out = {}
    for labels, ctr in (fam.children() if fam is not None else ()):
        if labels["op"] == op:
            out[labels["route"]] = out.get(labels["route"], 0) + int(
                ctr.value)
    return out


# name: dtype, q_len, query heads a key/value head, window
OP_STEPS = {
    "f32-step": (jnp.float32, 1, 1, 0),
    "f32-chunk-g2": (jnp.float32, 8, 2, 0),
    "bf16-step-g2": (jnp.bfloat16, 1, 2, 0),
    "bf16-chunk": (jnp.bfloat16, 8, 1, 0),
    "f32-ring": (jnp.float32, 1, 1, 256),
}


@pytest.mark.parametrize("case", sorted(OP_STEPS))
def test_op_appends_and_attends_in_one_view(case):
    """``fused_decode_attention`` on the kernel's route at heads of 64
    (append and kernel both rows-minor) against its primitive route (both
    on the logical shape): the caches it returns are the same bits, a
    masked-out slot's and one clamped onto the last row among them, and
    the attention agrees."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx
    from paddle_tpu.ops.generation import _route_decode

    dt, q_len, G, window = OP_STEPS[case]
    B, H, S, D = 4, 3, 256, 64
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    ins = {"Q": [jnp.asarray(rng.normal(size=(B, H * G, q_len, D)), dt)],
           "KNew": [jnp.asarray(rng.normal(size=(B, H, q_len, D)), dt)],
           "VNew": [jnp.asarray(rng.normal(size=(B, H, q_len, D)), dt)],
           "CacheK": [jnp.asarray(rng.normal(size=(B, H, S, D)), dt)],
           "CacheV": [jnp.asarray(rng.normal(size=(B, H, S, D)), dt)],
           "Positions": [jnp.asarray([[5], [130], [S - 3], [700]] if window
                                     else [[5], [130], [S - 3], [S + 9]])],
           "SlotMask": [jnp.asarray([[1.0], [0.0], [1.0], [1.0]])]}
    attrs = {"scale": 0.0, "page_size": PAGE, "window": window}
    got = {}
    monitor.reset()
    for mode in ("never", "always"):
        fluid.set_flags({"FLAGS_use_flash_attention": mode})
        try:
            route = _route_decode(S, PAGE, q_len=q_len, platform="cpu")
            got[route] = get_op_def("fused_decode_attention").lower(
                LowerCtx(platform="cpu"), ins, attrs)
        finally:
            fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    assert sorted(got) == ["pallas-interpret", "primitive"]
    assert rows_minor(D, dt, PAGE)
    # the rows-minor append is a kernel's, and counted where it engages:
    # the decode kernel's own for a step of one row, `kv_append` for a
    # chunk of rows and for a ring
    in_kernel = q_len == 1 and not window
    assert _append_routes() == ({} if in_kernel else {"pallas-interpret": 1})
    assert _append_routes(IN_KERNEL) == (
        {"pallas-interpret": 1} if in_kernel else {})
    for name in ("CacheKOut", "CacheVOut"):
        a, b = (np.asarray(got[r][name][0], np.float32) for r in sorted(got))
        assert a.tobytes() == b.tobytes()
        old = np.asarray(ins[name[:6]][0], np.float32)
        assert a[1].tobytes() == old[1].tobytes() and (a[0] != old[0]).any()
    a, b = (np.asarray(got[r]["Out"][0], np.float32) for r in sorted(got))
    live = [0, 1, 2] + ([3] if window else [])  # slot 3 ran off its cache
    tol = dict(atol=2e-5, rtol=1e-4) if dt == jnp.float32 else dict(
        atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(a[live], b[live], **tol)


SCATTER = "fused_decode_attention.append_scatter"
# name: dtype, q_len, key width, value width, window, whole_chunk
SCATTER_STEPS = {
    "f32-step": (jnp.float32, 1, 16, 16, 0, False),
    "bf16-step-keys-wider": (jnp.bfloat16, 1, 24, 16, 0, False),
    "bf16-ring-keys-wider": (jnp.bfloat16, 1, 24, 16, 32, False),
    "f32-verify-chunk": (jnp.float32, 4, 16, 16, 0, False),
    "bf16-block-of-4": (jnp.bfloat16, 4, 16, 16, 0, True),
    "f32-block-of-4-keys-wider": (jnp.float32, 4, 24, 16, 0, True),
}


@pytest.mark.parametrize("case", sorted(SCATTER_STEPS))
def test_op_scatters_a_steps_rows_into_caches_that_lie_as_declared(case):
    """``fused_decode_attention`` where the caches are not worked on
    rows-minor: the step's rows of every slot go in by one scatter a cache
    (counted as ``append_scatter``, and neither kernel append), keys wider
    than values and a block of 4 among the cases; both caches are the rows
    written one by one in NumPy, a masked-out slot's untouched, one slot
    clamped onto the last row (wrapped, on a ring)."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    dt, q_len, D, Dv, window, whole = SCATTER_STEPS[case]
    B, H, G, S = 4, 2, 2, 32
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)
    positions = [4, 9, S - 4, 70 if window else S + 9]
    keep = [1, 0, 1, 1]
    ins = {"Q": [mk(B, H * G, q_len, D)], "KNew": [mk(B, H, q_len, D)],
           "VNew": [mk(B, H, q_len, Dv)], "CacheK": [mk(B, H, S, D)],
           "CacheV": [mk(B, H, S, Dv)],
           "Positions": [jnp.asarray(positions)[:, None]],
           "SlotMask": [jnp.asarray(keep, jnp.float32)[:, None]]}
    monitor.reset()
    got = get_op_def("fused_decode_attention").lower(
        LowerCtx(platform="cpu"), ins,
        {"scale": 0.0, "page_size": 8, "window": window,
         "whole_chunk": whole})
    assert _append_routes(SCATTER) == {"primitive": 1}
    assert _append_routes() == {} and _append_routes(IN_KERNEL) == {}
    assert got["Out"][0].shape == (B, H * G, q_len, Dv)
    for out, cache, new in (("CacheKOut", "CacheK", "KNew"),
                            ("CacheVOut", "CacheV", "VNew")):
        want = _rows_written_one_by_one(ins[cache][0], ins[new][0],
                                        positions, keep, bool(window))
        assert np.asarray(got[out][0]).tobytes() == want.tobytes()


def _trace_for_tpu(program, fetch):
    """Trace ``program``'s step as the executor would lower it for a TPU
    (nothing compiles or runs): routes are counted at trace time."""
    from paddle_tpu.core.types import np_dtype
    from paddle_tpu.executor import analyze_block_io, make_step_fn

    block = program.global_block
    feeds = {n for n, v in block.vars.items() if getattr(v, "is_data", False)}
    io = analyze_block_io(block, feeds, [fetch.name])

    def shaped(name):
        v = block.var(name)
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in v.shape),
            jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype))))

    step = make_step_fn(block, io, [fetch.name], platform="tpu")
    jax.jit(step).trace(*([shaped(n) for n in io[k]] for k in (
        "feed_order", "donated", "ro")), jax.random.key(0))


def _decoder(name, **kw):
    from paddle_tpu.models import (cohere_moe, glm4_moe_lite,
                                   granite_moe_hybrid, mimo_v2_flash,
                                   qwen3_next, sdar_moe)

    if name == "gpt-heads-of-64":
        cfg = GptConfig(vocab_size=64, hidden_size=128, num_layers=3,
                        num_heads=2, intermediate_size=64, max_position=256)
        return build_gpt_generative(cfg, batch_slots=2, max_seq=256,
                                    page_size=128, prompt_buckets=(128,),
                                    **kw)
    if name == "gpt-tiny":
        return build_gpt_generative()
    return {"cohere-moe": cohere_moe.build_cohere_moe_generative,
            "qwen3-next": qwen3_next.build_qwen3_next_generative,
            "glm4-moe-lite": glm4_moe_lite.build_glm4_moe_lite_generative,
            "sdar-moe": sdar_moe.build_sdar_moe_generative,
            "granite-moe-hybrid":
                granite_moe_hybrid.build_granite_moe_hybrid_generative,
            "mimo-v2-flash":
                mimo_v2_flash.build_mimo_v2_flash_generative}[name]()


@pytest.mark.parametrize("name,appends,scatters", [
    ("gpt-heads-of-64", 3, 0), ("gpt-tiny", 0, 2), ("cohere-moe", 0, 4),
    ("qwen3-next", 0, 1), ("glm4-moe-lite", 0, 0), ("sdar-moe", 0, 2),
    ("granite-moe-hybrid", 0, 1), ("mimo-v2-flash", 0, 4)])
def test_kv_append_route_counts_the_layers_that_take_the_kernel(name,
                                                                appends,
                                                                scatters):
    """The three appends' counters for a decode program lowered for a TPU:
    where the caches are worked on rows-minor (heads of 64 in pages of
    128) the decode kernel writes the step's row itself, one ``IN_KERNEL``
    a layer, no ``kv_append`` and no scatter; the other decoders' caches
    (the tiny ones here, heads of 128 and 256 at the published widths) lie
    as declared and take one ``SCATTER`` an attention layer and neither
    kernel append, though their decode attention rides its kernel; a
    latent cache is its own op's and takes none of the three."""
    with un.guard():
        net = _decoder(name)
    monitor.reset()
    dec = net["decode"]     # a decoder by blocks yields tokens, not one
    _trace_for_tpu(dec["main"], dec["next_token"] if "next_token" in dec
                   else dec["yield"]["tokens"])
    assert _append_routes() == {}
    assert _append_routes(IN_KERNEL) == ({"pallas": appends} if appends
                                         else {})
    assert _append_routes(SCATTER) == ({"pallas": scatters} if scatters
                                       else {})
    fam = monitor.get_registry().get("kernel_route_total")
    assert any(labels["route"] == "pallas" for labels, _ in fam.children())


@pytest.mark.parametrize("program,fetch,slice_rows,appends", [
    ("verify", "sampled", 128, 3), ("chunk", "first_token", 8, 3),
    ("chunk", "first_token", 128, 0)])
def test_chunk_programs_keep_the_append_kernel(program, fetch, slice_rows,
                                               appends):
    """GPT-2's verify chunk (4 rows a slot) and a chunked-prefill slice of
    8 rows on rows-minor caches: a chunk may cross a block's edge, so its
    rows go through ``kv_append`` before the decode kernel, which appends
    nothing. A slice of a page of rows takes the primitive route, and
    neither counter."""
    with un.guard():
        net = _decoder("gpt-heads-of-64", prefill_chunk=slice_rows)
    monitor.reset()
    _trace_for_tpu(net[program]["main"], net[program][fetch])
    assert _append_routes(IN_KERNEL) == {}
    assert _append_routes() == ({"pallas": appends} if appends else {})


def test_tile_is_whole_pages_of_whole_heads_inside_its_budget():
    from paddle_tpu.kernels.decode_attention import _STEP_BYTES

    assert kv_tile(12, 1024, 64, jnp.float32, 128) == (12, 128)
    assert kv_tile(8, 1024, 128, jnp.bfloat16, 128) == (8, 256)
    for H, S, D, dt, page in [(12, 1024, 64, jnp.float32, 128),
                              (8, 4096, 128, jnp.bfloat16, 128),
                              (128, 2048, 128, jnp.bfloat16, 128),
                              (2, 32, 16, jnp.float32, 8),
                              (7, 96, 256, jnp.float32, 32),
                              (1, 64, 64, jnp.float32, 128)]:
        heads, rows = kv_tile(H, S, D, dt, page)
        assert H % heads == 0 and S % rows == 0
        assert rows % min(page, S) == 0
        lanes = -(-D // 128) * 128
        if (heads, rows) != (1, min(page, S)):
            assert 2 * heads * rows * lanes * jnp.dtype(
                dt).itemsize <= _STEP_BYTES


def _observed_walk():
    """A tiny GPT-2 engine's ``_observe_walk`` on four residents over a
    chunk of 4 steps, the monitor reset before it: lengths 31.., 127..
    (crosses into block 1 at its third step), 260.., 511.. (the cache's
    end), blocks of 128 rows, 4 a cache, two layers."""
    cfg = GptConfig(vocab_size=64, hidden_size=48, num_layers=2,
                    num_heads=12, intermediate_size=48, max_position=512)
    with un.guard():
        net = build_gpt_generative(cfg, batch_slots=4, max_seq=512,
                                   page_size=128, prompt_buckets=(128,))
    eng = serving.GenerativeEngine(
        net, scope=fluid.Scope(), executor=fluid.Executor(fluid.CPUPlace()),
        gen_config=serving.GenerationConfig(decode_chunk=4))
    active = [types.SimpleNamespace(prompt=np.zeros(p), emitted=e)
              for p, e in [(30, 1), (120, 7), (100, 160), (128, 383)]]
    monitor.reset()
    eng._observe_walk(active, 4)


def test_walk_share_histogram_counts_what_the_kernel_helper_counts():
    """``decode_attention_walk_share`` is the kernel module's own count on
    the lengths the dispatch thread holds: k-blocks fetched over k-blocks
    held, over every step of the chunk and every layer."""
    _observed_walk()
    got = monitor.metric_value("decode_attention_walk_share", default=None)
    lengths = np.array([31, 127, 260, 511]) + np.arange(4)[:, None]
    fetched, held = decode_walk_blocks(np.minimum(lengths, 512),
                                       (4, 12, 512, 4), "float32", 128)
    assert (fetched, held) == (4 * 1 + (2 * 1 + 2 * 2) + 4 * 3 + 4 * 4, 64)
    assert got["count"] == 1
    assert got["sum"] == pytest.approx(fetched / held)


# -- the live walk: a grid step only where a block is fetched (PR 53) --------

# name: dtype, key/value heads, query heads a group, key dim, value dim,
# q_len, whole_chunk, sink, append
LIVE_WALKS = {
    "append": (jnp.float32, 12, 1, 64, 64, 1, False, False, True),
    "sink": (jnp.float32, 8, 4, 128, 128, 1, False, True, False),
    "whole-chunk-q4": (jnp.bfloat16, 16, 1, 128, 128, 4, True, False, False),
    "whole-chunk-q8": (jnp.bfloat16, 16, 1, 128, 128, 8, True, False, False),
    "group16": (jnp.bfloat16, 16, 16, 128, 128, 1, False, False, False),
    "keys192-values128": (jnp.float32, 4, 4, 192, 128, 1, False, True,
                          False),
}
# name: lengths as (k-blocks, rows) of the chunk's LAST row's keys (what
# ends the walk), and the slot mask of the append
LIVE_LENGTHS = {
    "ones": ([(0, 1)] * 3, None),
    "block-edge": ([(1, 0), (2, 0), (3, 0), (1, 1)], None),
    "full": ([(4, 0)] * 3, None),
    "mixed": ([(0, 1), (0, 127), (1, 1), (2, 77), (4, 0), (0, 9)], None),
    "masked": ([(0, 5), (1, 0), (1, 1), (2, 100), (4, 0)], (1, 0, 1, 0, 1)),
}


@pytest.mark.parametrize("variant,lengths", [
    (v, n) for v in sorted(LIVE_WALKS) for n in sorted(LIVE_LENGTHS)
    if LIVE_LENGTHS[n][1] is None or LIVE_WALKS[v][-1]])
def test_live_walk_is_the_reference(variant, lengths):
    """The kernel whose grid is the table of live blocks against the
    primitive oracle, on caches whose dead blocks hold NaN: every variant
    the six decoders use, on sequences of one key, lengths that end exactly
    on a block's edge, full caches and a mix; on the append path the caches
    come back with the step's row where the slot's mask is set, and
    bit-identical where it is 0."""
    dt, H, G, D, Dv, q_len, whole, with_sink, append = LIVE_WALKS[variant]
    spec, mask = LIVE_LENGTHS[lengths]
    S = 512
    _, block = kv_tile(H, S, D, dt, PAGE, v_dim=Dv)
    assert S // block == 4, "the cases count in a cache of four k-blocks"
    # the chunk's first row sees q_len - 1 keys fewer than its last
    n = np.array([max(b * block + r - (q_len - 1), 1) for b, r in spec],
                 np.int32)
    B = len(n)
    rng = np.random.default_rng(zlib.crc32(f"{variant}/{lengths}".encode()))
    mk = lambda *shape: rng.normal(size=shape).astype(np.float32)
    q = jnp.asarray(mk(B * H, q_len * G, D), dt)
    k, v = mk(B, H, S, D), mk(B, H, S, Dv)
    sink = jnp.asarray(mk(H * G)) if with_sink else None
    kn = vn = keep = None
    clean_k, clean_v = jnp.asarray(k, dt), jnp.asarray(v, dt)
    if append:      # the step's row is the last visible key, row n - 1
        kn, vn = (jnp.asarray(mk(B, H, 1, D), dt) for _ in range(2))
        keep = None if mask is None else jnp.asarray(mask, jnp.float32)
        clean_k, clean_v = (paged_kv_append_rows(c, new, jnp.asarray(n - 1),
                                                 keep)
                            for c, new in ((clean_k, kn), (clean_v, vn)))
    ref = decode_attention_reference(
        q, clean_k.reshape(B * H, S, D), clean_v.reshape(B * H, S, Dv),
        jnp.asarray(np.repeat(n, H)), D ** -0.5, group=G, whole_chunk=whole,
        sink=None if sink is None else jnp.tile(sink.reshape(H, G), (B, 1)))
    live = (last_live_block(n, q_len, block, 4) + 1) * block
    for b in range(B):      # a block past the walk is never fetched
        k[b, :, live[b]:] = np.nan
        v[b, :, live[b]:] = np.nan
    out = flash_attention_decode(
        q, jnp.asarray(k.reshape(B * H, S, D), dt),
        jnp.asarray(v.reshape(B * H, S, Dv), dt), n, num_heads=H,
        page_size=PAGE, group=G, interpret=True, whole_chunk=whole,
        sink=sink, append=(kn, vn, keep) if append else None)
    if append:
        out, ck, cv = out
        for got, want, dirty in ((ck, clean_k, k), (cv, clean_v, v)):
            got = np.asarray(got).reshape(want.shape)
            for b in range(B):
                # the live blocks as the row-form append leaves them, and
                # nothing written past them; a masked slot bit-identical
                np.testing.assert_array_equal(got[b, :, :live[b]],
                                              np.asarray(want)[b, :, :live[b]])
                assert np.isnan(got[b, :, live[b]:]).all()
                if mask is not None and not mask[b]:
                    assert _bits(got[b]) == _bits(jnp.asarray(dirty[b], dt))
    assert out.shape == (B * H, q_len * G, Dv)
    tol = dict(atol=2e-5, rtol=1e-4) if dt == jnp.float32 else dict(
        atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


# name: cache [B, H, S, D], dtype, value dim, q_len
GRID_SHAPES = {
    "gpt2": ((64, 12, 1024, 64), jnp.float32, None, 1),
    "command-a-plus": ((64, 8, 1024, 128), jnp.bfloat16, None, 1),
    "sdar-block-of-4": ((64, 4, 2048, 128), jnp.bfloat16, None, 4),
    "mimo-v2-flash-full": ((128, 4, 4096, 256), jnp.bfloat16, 128, 1),
    "mimo-v2-flash-ring": ((128, 8, 128, 256), jnp.bfloat16, 128, 1),
    "heads-in-groups": ((16, 128, 2048, 128), jnp.bfloat16, None, 1),
}


@pytest.mark.parametrize("shape", sorted(GRID_SHAPES))
def test_host_counts_the_steps_the_kernel_is_given(shape):
    """``decode_grid_steps`` and ``decode_walk_blocks`` on the host's
    lengths against the grid bound the kernel's own table comes with on the
    same lengths traced: a step a fetched block and group of heads, at the
    stored cells' cache shapes and at one whose heads a step cannot carry
    at once."""
    (B, H, S, D), dt, v_dim, q_len = GRID_SHAPES[shape]
    rng = np.random.default_rng(zlib.crc32(shape.encode()))
    lengths = np.concatenate([[1, S, S - q_len + 1], rng.integers(
        1, S + 1, B - 3)]).astype(np.int32)
    heads, rows = kv_tile(H, S, D, dt, PAGE, v_dim=v_dim)
    groups = H // heads
    assert (groups > 1) == (shape == "heads-in-groups")
    _, steps = jax.jit(lambda n: walk_steps(
        n, q_len, rows, S // rows, groups))(lengths)
    fetched, held = decode_walk_blocks(lengths, (B, H, S, D), dt, PAGE,
                                       q_len=q_len, v_dim=v_dim)
    assert held == B * (S // rows)
    assert int(steps) == groups * fetched
    assert int(steps) == decode_grid_steps(lengths, (B, H, S, D), dt, PAGE,
                                           q_len=q_len, v_dim=v_dim)


def test_grid_steps_counter_reads_a_step_a_fetched_block():
    """``decode_attention_grid_steps_total{kind}`` beside
    ``decode_attention_rows_total{kind}``: over the rows a tile holds, one
    step a fetched block where a step carries all of a sequence's heads."""
    _observed_walk()
    steps = monitor.metric_value("decode_attention_grid_steps_total",
                                 kind="full")
    rows = monitor.metric_value("decode_attention_rows_total", kind="full")
    # two layers' blocks of 128 rows: 4 + 6 + 12 + 16 a layer (the walk
    # share's test counts them)
    assert steps == 2 * 38 and rows == steps * 128


# -- a sink column, values narrower than keys, the fold into a ring ----------

def _written_out(q, kc, vc, lengths, sink, group):
    """The decode softmax with the sink's column written out."""
    s = jnp.einsum("bqd,bkd->bqk", q, kc) * q.shape[-1] ** -0.5
    live = jnp.arange(kc.shape[1])[None, None] < lengths[:, None, None]
    s = jnp.where(live, s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, sink[:, :, None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :kc.shape[1]]
    return jnp.einsum("bqk,bkd->bqd", p, vc)


@pytest.mark.parametrize("with_sink", [False, True])
@pytest.mark.parametrize("group", [1, 4])
def test_decode_kernel_takes_a_sink_and_values_narrower_than_keys(with_sink,
                                                                  group):
    """Keys of 24 beside values of 16 (192 / 128 at an eighth), the group's
    query heads in the sublane rows each with its own sink, against the
    softmax with the extra column written out; the reference route too."""
    rng = np.random.default_rng(0)
    B, H, S, Dk, Dv = 3, 2, 64, 24, 16
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, kc, vc = mk(B * H, group, Dk), mk(B * H, S, Dk), mk(B * H, S, Dv)
    lens = jnp.asarray([5, 64, 17])
    sink = mk(H * group) if with_sink else None
    rows = None if sink is None else jnp.tile(sink.reshape(H, group), (B, 1))
    want = _written_out(q, kc, vc, jnp.repeat(lens, H), rows, group)
    got = flash_attention_decode(q, kc, vc, lens, num_heads=H, page_size=8,
                                 group=group, interpret=True, sink=sink)
    assert got.shape == (B * H, group, Dv)
    np.testing.assert_allclose(got, want, atol=2e-6)
    ref = decode_attention_reference(q, kc, vc, jnp.repeat(lens, H),
                                     Dk ** -0.5, group=group, sink=rows)
    np.testing.assert_allclose(ref, want, atol=2e-6)


FOLDS = [
    # lengths, mask, slots
    ([3, 8, 29], [1, 1, 1], [4, 0, 2]),
    ([32, 17, 9], [0, 1, 1], [0, 3, 1]),
    ([5, 5, 5], [0, 0, 0], [0, 0, 0]),
    ([16, 24, 1], [1, 0, 1], [2, 2, 0]),
]


@pytest.mark.parametrize("lengths,mask,slots", FOLDS)
def test_window_fold_takes_each_sequences_last_window_by_position(
        lengths, mask, slots):
    """Ring row ``r`` takes the last position ``p < length`` with ``p %
    window == r``, in the slot the row names; a masked row writes nothing
    (whatever slot it names, another row's too), and every other slot keeps
    every bit: the gather-and-write form against a loop over rows."""
    from paddle_tpu.kernels import window_fold

    rng = np.random.default_rng(1)
    B, H, W, D, R, S = 5, 2, 8, 16, 3, 32
    cache = jnp.asarray(rng.normal(size=(B, H, W, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(R, H, S, D)), jnp.float32)
    want = np.array(cache)
    for i in range(R):
        if mask[i]:
            for r in range(W):
                seen = [p for p in range(lengths[i]) if p % W == r]
                want[slots[i], :, r] = np.asarray(new)[
                    i, :, seen[-1] if seen else r]
    args = (jnp.asarray(lengths), jnp.asarray(mask, jnp.float32)[:, None],
            jnp.asarray(slots)[:, None])
    np.testing.assert_array_equal(
        np.asarray(window_fold(cache, new, *args)), want)


def test_fold_rows_end_where_the_decode_step_goes_on():
    """Ring row ``r`` holds the last position under the length that is
    ``r`` modulo the window: a whole number of windows leaves the last
    window in order, one token more puts it at row 0, and a sequence
    shorter than the window lies from row 0 with its padding rows after
    it, where a bucket written at row 0 leaves them."""
    from paddle_tpu.kernels import fold_rows

    got = np.asarray(fold_rows(jnp.asarray([3, 4, 8, 9, 11]), 4))
    assert got.tolist() == [[0, 1, 2, 3], [0, 1, 2, 3], [4, 5, 6, 7],
                            [8, 5, 6, 7], [8, 9, 10, 7]]
    # the next position, ``length``, is the oldest row's: ``length % 4``
    for n, rows in zip((8, 9, 11), got[2:]):
        assert rows[n % 4] == n - 4


def test_fold_op_refuses_a_bucket_that_fits_the_ring():
    """A bucket no longer than the ring is ``kv_cache_append``'s at row 0;
    the fold says so when its program is lowered."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        block = main.global_block
        block.create_var(name="ring", shape=(2, 1, 8, 4), dtype="float32",
                         persistable=True)
        new = fluid.layers.data("new", shape=[2, 1, 8, 4], dtype="float32",
                                append_batch_size=False)
        ln = fluid.layers.data("len", shape=[2, 1], dtype="int64",
                               append_batch_size=False)
        out, _ = fluid.layers.kv_cache_fold(block.var("ring"), new, ln)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    scope.set_var("ring", np.zeros((2, 1, 8, 4), np.float32))
    with pytest.raises(RuntimeError, match="fits a ring"):
        exe.run(main, scope=scope, fetch_list=[out], feed={
            "new": np.zeros((2, 1, 8, 4), np.float32),
            "len": np.full((2, 1), 5, np.int64)})


@pytest.mark.parametrize("flash", ["never", "always"])
def test_decode_op_wraps_a_folded_ring_with_a_sink(flash):
    """``kv_cache_fold`` then ``fused_decode_attention`` steps with a
    window, a sink and a narrower ``V``: after a prompt of three windows
    and a bit, each step's output is full attention over the last
    ``window`` positions, on the primitive route and through the kernels."""
    rng = np.random.default_rng(2)
    B, Hq, H, W, S, Dk, Dv, L, steps = 2, 4, 2, 8, 32, 24, 16, (27, 8), 18
    mk = lambda *s: rng.normal(size=s).astype(np.float32)
    k_all, v_all = mk(B, H, S + steps, Dk), mk(B, H, S + steps, Dv)
    q_all, sink = mk(B, Hq, S + steps, Dk), mk(Hq)
    fluid.set_flags({"FLAGS_use_flash_attention": flash})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(main, startup):
            d = lambda n, a: fluid.layers.data(
                n, shape=list(a.shape), dtype=str(a.dtype),
                append_batch_size=False)
            block = main.global_block
            caches = []
            for n, width in (("ck", Dk), ("cv", Dv)):
                block.create_var(name=n, shape=(B, H, W, width),
                                 dtype="float32", persistable=True)
                caches.append(block.var(n))
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        for c, width in zip(caches, (Dk, Dv)):
            scope.set_var(c.name, mk(B, H, W, width))
        lens = np.asarray(L, np.int64)[:, None]
        fold, fs = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(fold, fs):
            for c, (n, a) in zip(caches, (("k", k_all), ("v", v_all))):
                fb = fold.global_block
                fb.create_var(name=c.name, shape=c.shape, dtype="float32",
                              persistable=True)
                new = fluid.layers.data(n, shape=[B, H, S, a.shape[-1]],
                                        dtype="float32",
                                        append_batch_size=False)
                ln = fluid.layers.data("len", shape=[B, 1], dtype="int64",
                                       append_batch_size=False) \
                    if n == "k" else ln
                _, stats = fluid.layers.kv_cache_fold(fb.var(c.name), new,
                                                      ln)
        got_stats = exe.run(fold, scope=scope, fetch_list=[stats], feed={
            "k": k_all[:, :, :S], "v": v_all[:, :, :S], "len": lens})[0]
        assert list(got_stats) == [sum(min(n, W) for n in L),
                                   sum(max(n - W, 0) for n in L)]
        step, ss = fluid.Program(), fluid.Program()
        with un.guard(), fluid.program_guard(step, ss):
            sb = step.global_block
            cvars = []
            for c in caches:
                sb.create_var(name=c.name, shape=c.shape, dtype="float32",
                              persistable=True)
                cvars.append(sb.var(c.name))
            feeds = {n: fluid.layers.data(n, shape=list(s), dtype=t,
                                          append_batch_size=False)
                     for n, s, t in (("q", (B, Hq, 1, Dk), "float32"),
                                     ("kn", (B, H, 1, Dk), "float32"),
                                     ("vn", (B, H, 1, Dv), "float32"),
                                     ("pos", (B, 1), "int64"),
                                     ("sink", (Hq,), "float32"))}
            out = fluid.layers.fused_decode_attention(
                feeds["q"], feeds["kn"], feeds["vn"], *cvars, feeds["pos"],
                page_size=8, window=W, sink=feeds["sink"])
        # the prompts' tokens sit at positions 0..L-1 of each sequence's
        # own stream; the steps append positions L, L+1, ...
        for t in range(steps):
            pos = lens + t
            take = lambda a: np.stack([a[b, :, pos[b, 0]][:, None]
                                       for b in range(B)])
            got = exe.run(step, scope=scope, fetch_list=[out], feed={
                "q": take(q_all), "kn": take(k_all), "vn": take(v_all),
                "pos": pos, "sink": sink})[0]
            for b in range(B):
                p = int(pos[b, 0])
                lo = max(0, p - W + 1)
                want = _written_out(
                    jnp.asarray(take(q_all)[b].reshape(H, Hq // H, Dk)),
                    jnp.asarray(k_all[b, :, lo:p + 1]),
                    jnp.asarray(v_all[b, :, lo:p + 1]),
                    jnp.full((H,), p + 1 - lo),
                    jnp.asarray(sink.reshape(H, Hq // H)), Hq // H)
                np.testing.assert_allclose(
                    got[b].reshape(H, Hq // H, Dv), want, atol=3e-6)
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
