"""Pallas kernels (interpret mode) vs pure-jnp oracles, swept over shapes.

Every call passes ``interpret=True``: the kernels run under the Pallas
interpreter on any backend, and nothing picks the mode for the caller."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frontier as fr
from repro.kernels import blocks, ops, ref


# --- bitmap OR-reduce --------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("w", [128, 1024, 4096])
def test_bitmap_or_reduce(k, w, rng):
    stack = rng.integers(0, 2**32, size=(k, w), dtype=np.uint32)
    got = ops.bitmap_or_reduce(jnp.asarray(stack), interpret=True)
    want = ref.bitmap_or_reduce(jnp.asarray(stack))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k,w_blocks,seed", [(1, 1, 0), (3, 5, 1), (6, 8, 2)])
def test_bitmap_or_reduce_property(k, w_blocks, seed):
    """Deterministic slice; randomized sweep in tests/test_properties.py."""
    rng = np.random.default_rng(seed)
    w = 128 * w_blocks
    stack = rng.integers(0, 2**32, size=(k, w), dtype=np.uint32)
    got = np.asarray(ops.bitmap_or_reduce(jnp.asarray(stack), block=128,
                                         interpret=True))
    assert np.array_equal(got, np.bitwise_or.reduce(stack, axis=0))


# --- frontier gather ---------------------------------------------------------


@pytest.mark.parametrize("nb,eb,ww", [(4, 128, 8), (7, 256, 16), (2, 512, 64)])
def test_frontier_gather_windowed(nb, eb, ww, rng):
    w = ww * 8
    words = rng.integers(0, 2**32, size=(w,), dtype=np.uint32)
    block_ws = rng.integers(0, w // ww, size=(nb,)).astype(np.int32)
    src_local = rng.integers(0, ww * 32, size=(nb, eb)).astype(np.int32)
    got = ops.frontier_gather(
        jnp.asarray(words), jnp.asarray(block_ws), jnp.asarray(src_local), ww=ww,
        interpret=True,
    )
    want = ref.frontier_gather(
        jnp.asarray(words), jnp.asarray(block_ws), jnp.asarray(src_local), ww
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("nb,eb", [(3, 128), (6, 512)])
def test_frontier_gather_full(nb, eb, rng):
    w = 256
    words = rng.integers(0, 2**32, size=(w,), dtype=np.uint32)
    src = rng.integers(0, w * 32, size=(nb, eb)).astype(np.int32)
    got = ops.frontier_gather_full(jnp.asarray(words), jnp.asarray(src),
                                   interpret=True)
    want = ref.frontier_gather_full(jnp.asarray(words), jnp.asarray(src))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- frontier scatter --------------------------------------------------------


@pytest.mark.parametrize("n_windows,ww,nb,eb", [(4, 8, 6, 128), (2, 64, 3, 512)])
def test_frontier_scatter(n_windows, ww, nb, eb, rng):
    bits = ww * 32
    # block_win must be sorted (consecutive blocks per window)
    block_win = np.sort(rng.integers(0, n_windows, size=(nb,))).astype(np.int32)
    block_first = np.zeros(nb, np.int32)
    seen = set()
    for i, wn in enumerate(block_win):
        if int(wn) not in seen:
            block_first[i] = 1
            seen.add(int(wn))
    dst_local = rng.integers(0, bits + 1, size=(nb, eb)).astype(np.int32)
    active = rng.integers(0, 2, size=(nb, eb)).astype(bool)
    got = ops.frontier_scatter(
        jnp.asarray(active), jnp.asarray(block_win), jnp.asarray(block_first),
        jnp.asarray(dst_local), n_windows=n_windows, ww=ww, interpret=True,
    )
    want = ref.frontier_scatter(
        jnp.asarray(active), jnp.asarray(block_win), jnp.asarray(dst_local),
        n_windows, ww,
    )
    # windows never covered by any block are undefined in the kernel output
    # (grid never writes them) — compare only covered windows.
    covered = np.zeros(n_windows, bool)
    covered[np.asarray(block_win)] = True
    g = np.asarray(got).reshape(n_windows, ww)
    w_ = np.asarray(want).reshape(n_windows, ww)
    np.testing.assert_array_equal(g[covered], w_[covered])


# --- layout ETL + end-to-end expansion ---------------------------------------


def test_gather_layout_covers_all_edges(rng):
    src = np.sort(rng.integers(0, 4096, size=1000)).astype(np.int32)
    lay = blocks.build_gather_layout(src, 1000, 4096 // 32 + 8, eb=128)
    # reconstruct global ids from (block_ws, src_local)
    ids = (
        lay.block_ws[:, None].astype(np.int64) * lay.ww * 32 + lay.src_local
    ).reshape(-1)[:1000]
    np.testing.assert_array_equal(ids, src)


def test_expand_push_matches_jnp(mesh8, rng):
    """Pallas expansion == XLA scatter on a real partitioned graph slice."""
    from repro.graph import generators, partition

    g = generators.kronecker(9, 6, seed=5)
    pg = partition.partition_1d(g, 1)
    layout = blocks.build_bfs_layout(pg)
    from repro.kernels import ops as kops

    frontier_bits = rng.integers(0, 2, size=(pg.n_words * 32,)).astype(bool)
    fw = fr.pack(jnp.asarray(frontier_bits))
    arrays = {k: jnp.asarray(v[0]) for k, v in pg.arrays().items()}
    arrays.update({k: jnp.asarray(v[0]) for k, v in layout.arrays.items()})
    got = kops.expand_push_pallas(fw, arrays, layout.meta, pg.n_words,
                                  interpret=True)
    # jnp reference path
    mask = jnp.arange(pg.emax) < arrays["edge_count"]
    active = fr.get_bits(fw, arrays["edge_src"]) & mask
    want = fr.scatter_or(pg.n_words, arrays["edge_dst"], active)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
