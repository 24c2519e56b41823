"""The grouped expert matmul (``kernels/moe.py``) in interpret mode
against its primitive oracle, on the counts that its schedule has to get
right: the column block is outermost and the tiles inside it, so an
expert's consecutive tiles share one weight block, and the dead tiles past
``n_valid`` end every column sweep. And the block rule, pinned for the
ten products of the five stored cells.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe
from paddle_tpu.kernels.moe import (gmm_blocks, gmm_vmem_bytes,
                                    grouped_matmul, grouped_matmul_reference)

TM = 16


def _tiles(counts, tm=TM, spare=3):
    """``(tile_expert, n_valid, row_is_real)`` of counts dealt to the held
    experts, as ``ops/moe.py`` lays them out: each group padded to whole
    tiles, ``spare`` dead tiles behind them (which name the last expert)."""
    per = [-(-c // tm) for c in counts]
    te = [e for e, n in enumerate(per) for _ in range(n)]
    real = np.concatenate([np.arange(n * tm) < c
                           for c, n in zip(counts, per)] or [np.zeros(0)])
    n_valid = len(te)
    te += [len(counts) - 1] * spare
    real = np.concatenate([real, np.zeros(spare * tm)]).astype(bool)
    return np.asarray(te, np.int32), n_valid, real


COUNTS = {
    # one expert with six consecutive tiles, one with none, a lone row
    "six_tiles_none_and_one": [96, 0, 1, 17],
    # every row on the last expert, the others starved
    "all_on_the_last": [0, 0, 0, 80],
    # one tile each
    "even": [16, 16, 16, 16],
    # nothing at all: every tile is dead
    "nothing": [0, 0, 0, 0],
}


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("blocks", [None, (128, 128), (256, 384)],
                         ids=["rule", "nk2_nn3", "nk1_nn1"])
@pytest.mark.parametrize("case", sorted(COUNTS))
def test_the_kernel_is_its_reference_on_every_live_tile(case, blocks, gated):
    """K 256, N 384 (3 x 128: no power of two divides it into lanes). With
    the rule's blocks K and N are whole (one grid step a tile, weights
    resident across an expert's tiles); (128, 128) splits both (``nk`` 2:
    the accumulators; three column sweeps, each ended by the dead tiles).
    Rows of dead tiles keep what the buffer held, so only live tiles are
    compared; the padding rows inside a live tile are zeros in, and
    ``silu(0) * 0`` or 0 out."""
    K, N, E = 256, 384, 4
    te, n_valid, real = _tiles(COUNTS[case])
    rng = np.random.default_rng(3)
    lhs = (rng.normal(size=(len(te) * TM, K)) * real[:, None]).astype(
        jnp.bfloat16)
    w = lambda: jnp.asarray(rng.normal(size=(E, K, N)) * 0.1, jnp.bfloat16)
    rhs, rhs2 = w(), (w() if gated else None)
    got = grouped_matmul(jnp.asarray(lhs), rhs, jnp.asarray(te), n_valid,
                         tm=TM, rhs2=rhs2, blocks=blocks, interpret=True)
    want = grouped_matmul_reference(jnp.asarray(lhs), rhs, jnp.asarray(te),
                                    n_valid, tm=TM, rhs2=rhs2)
    live = n_valid * TM
    assert got.shape == want.shape == (len(te) * TM, N)
    # one dot over K against partial dots summed: the last bits of an f32
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[:live][~real[:live]].any()


def test_blocks_that_do_not_divide_or_fit_are_refused():
    x = jnp.zeros((16, 256), jnp.bfloat16)
    w = jnp.zeros((2, 256, 384), jnp.bfloat16)
    te = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="do not divide"):
        grouped_matmul(x, w, te, 1, tm=16, blocks=(256, 256), interpret=True)
    with pytest.raises(ValueError, match="do not line up"):
        grouped_matmul(x, w[:, :128], te, 1, tm=16, interpret=True)


# (K, N) of the gated gate-and-up product and of the down product of each
# stored cell's experts, and the blocks the rule gives them
PUBLISHED = {
    "sdar-30b-a3b": ((2048, 768, (2048, 768)), (768, 2048, (768, 2048))),
    "granite-4.0-h-small": ((4096, 768, (4096, 384)),
                            (768, 4096, (768, 2048))),
    "glm-4.7-flash": ((2048, 1536, (2048, 768)), (1536, 2048, (1536, 1024))),
    "qwen3-next": ((2048, 512, (2048, 512)), (512, 2048, (512, 2048))),
    "command-a-plus": ((4096, 4096, (4096, 512)), (4096, 4096, (4096, 512))),
}


@pytest.mark.parametrize("K,N,want,gated", [
    pytest.param(*pair, gated, id=f"{name}-{'gate_up' if gated else 'down'}")
    for name, pairs in PUBLISHED.items()
    for pair, gated in zip(pairs, (True, False))])
def test_the_block_rule_at_the_published_widths(K, N, want, gated):
    """Every cell's experts take ``K`` whole (the weights stay resident
    across an expert's tiles), in blocks of 2 to 4 MiB that divide the
    product, and the pipeline's buffers fit the kernel's VMEM limit at
    either tile (16 rows in a decode step, 256 in a large prefill) with
    room to spare."""
    tk, tn = gmm_blocks(K, N)
    assert (tk, tn) == want
    assert tk == K and N % tn == 0 and tn % 128 == 0
    assert 2 * 2 ** 20 <= tk * tn * 2 <= moe._W_BLOCK
    for tm in (16, 256):
        assert gmm_vmem_bytes(tm, tk, tn, gated) <= moe._VMEM_LIMIT // 2


def test_the_block_rule_off_the_published_widths():
    """A ``K`` too long for one block splits into aligned divisors; a dim
    with no aligned divisor is taken whole; f32 weights halve the block."""
    assert gmm_blocks(32768, 4096) == (16384, 128)
    assert gmm_blocks(200, 72) == (200, 72)
    assert gmm_blocks(2048, 768, itemsize=4) == (2048, 384)
    assert gmm_blocks(3 * 8192, 256) == (12288, 128)
