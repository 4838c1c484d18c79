"""Hypothesis property tests (graph ETL, butterfly schedules, BFS, and the
density-adaptive sparse frontier exchange).

``pytest.importorskip`` guards the whole module: where hypothesis is not
installed the suite degrades gracefully to the deterministic slices kept in
test_graph.py / test_butterfly.py / test_kernels.py / test_sparse_frontier.py.
"""

import pytest

pytest.importorskip("hypothesis")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import bfs, butterfly as bf, frontier as fr  # noqa: E402
from repro.graph import csr, partition  # noqa: E402
from repro.kernels import ops  # noqa: E402

INF32 = np.iinfo(np.int32).max


def _norm(d):
    return np.where(d >= INF32, -1, d)


# --- graph ETL ---------------------------------------------------------------


@given(
    n=st.integers(2, 200),
    m=st.integers(0, 500),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_etl_properties(n, m, seed):
    rng = np.random.default_rng(seed)
    g = csr.from_edges(
        rng.integers(0, n, size=m), rng.integers(0, n, size=m), n
    )
    g.validate()  # symmetry, sortedness, offsets
    assert g.n % 32 == 0


# --- butterfly schedule ------------------------------------------------------


@given(
    p=st.integers(min_value=1, max_value=64),
    fanout=st.integers(min_value=1, max_value=8),
)
@settings(max_examples=60, deadline=None)
def test_or_merge_reaches_everyone(p, fanout):
    """Every rank's contribution reaches every rank (the BFS requirement:
    after phase 2 each node knows the FULL frontier)."""
    vals = [np.uint32(1 << (i % 32)) * np.ones(1, np.uint32) for i in range(p)]
    out = bf.simulate_allreduce(vals, fanout, op=np.bitwise_or)
    want = np.bitwise_or.reduce(np.stack(vals))
    for o in out:
        assert np.array_equal(o, want)


# --- kernels -----------------------------------------------------------------


@given(
    k=st.integers(1, 6),
    w_blocks=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_bitmap_or_reduce_property(k, w_blocks, seed):
    rng = np.random.default_rng(seed)
    w = 128 * w_blocks
    stack = rng.integers(0, 2**32, size=(k, w), dtype=np.uint32)
    got = np.asarray(ops.bitmap_or_reduce(jnp.asarray(stack), block=128,
                                         interpret=True))
    assert np.array_equal(got, np.bitwise_or.reduce(stack, axis=0))


# --- distributed BFS ---------------------------------------------------------


@given(
    n=st.integers(min_value=2, max_value=120),
    m=st.integers(min_value=1, max_value=400),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_bfs_properties_random_graphs(n, m, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    g = csr.from_edges(src, dst, n)
    root = int(rng.integers(0, n))
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    pg = partition.partition_1d(g, 4)
    cfg = bfs.BFSConfig(axes=("data",), fanout=int(rng.integers(1, 5)))
    d, _, _ = bfs.distributed_bfs(pg, mesh, root, cfg)
    ref = bfs.bfs_reference(g, root)
    np.testing.assert_array_equal(_norm(d), _norm(ref))
    # triangle inequality over every edge: |d[u] - d[v]| <= 1 for reached
    du, dv = d[g.src], d[g.dst]
    both = (du < INF32) & (dv < INF32)
    assert np.all(np.abs(du[both].astype(np.int64) - dv[both]) <= 1)
    # an edge never connects reached to unreached (undirected closure)
    assert not np.any((du < INF32) ^ (dv < INF32))


# --- lane-packed frontiers (multi-source BFS, DESIGN.md §13) ----------------


@given(
    rows=st.integers(min_value=1, max_value=64),
    lane_words=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_lane_pack_unpack_roundtrip(rows, lane_words, seed):
    """lane_unpack ∘ lane_pack == id on bits; lane_pack ∘ lane_unpack == id
    on words (the MS-BFS wave layout loses nothing either way)."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, size=(rows, lane_words * 32)).astype(bool)
    words = np.asarray(fr.lane_pack(jnp.asarray(bits)))
    assert words.shape == (rows, lane_words) and words.dtype == np.uint32
    assert np.array_equal(np.asarray(fr.lane_unpack(jnp.asarray(words))), bits)
    w = rng.integers(0, 2**32, size=(rows, lane_words), dtype=np.uint32)
    assert np.array_equal(
        np.asarray(fr.lane_pack(fr.lane_unpack(jnp.asarray(w)))), w
    )
    # 1-D pack/unpack are the single-axis special case of the lane ops
    flat = bits[0]
    assert np.array_equal(
        np.asarray(fr.pack(jnp.asarray(flat))),
        np.asarray(fr.lane_pack(jnp.asarray(flat))),
    )


@given(
    rows=st.integers(min_value=1, max_value=64),
    lane_words=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_popcount_lanes_property(rows, lane_words, seed):
    """Per-lane popcount == column sums of the unpacked bit matrix, and the
    lane totals add up to the scalar popcount."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 2**32, size=(rows, lane_words), dtype=np.uint32)
    got = np.asarray(fr.popcount_lanes(jnp.asarray(w)))
    bits = np.unpackbits(
        w.view(np.uint8).reshape(rows, lane_words, 4), axis=-1, bitorder="little"
    ).reshape(rows, lane_words * 32)
    assert np.array_equal(got, bits.sum(axis=0))
    assert got.sum() == int(fr.popcount(jnp.asarray(w)))


# --- sparse frontier exchange (DESIGN.md §12) -------------------------------


@given(
    p=st.sampled_from([2, 4, 8]),
    fanout=st.sampled_from([1, 2, 4]),
    n_words=st.sampled_from([64, 256, 1024]),
    active=st.integers(min_value=0, max_value=64),
    capacity=st.sampled_from([4, 16, 64]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_sparse_oracle_matches_dense_or(p, fanout, n_words, active, capacity,
                                        seed):
    """The host sparse simulator == dense OR reduction for every density
    (below AND above capacity: the overflow path must reroute to dense)."""
    rng = np.random.default_rng(seed)
    bitmaps = []
    for _ in range(p):
        b = np.zeros(n_words, np.uint32)
        k = int(rng.integers(0, active + 1))
        ii = rng.choice(n_words, size=min(k, n_words), replace=False)
        b[ii] = rng.integers(1, 2**32, size=ii.size, dtype=np.uint32)
        bitmaps.append(b)
    want = np.bitwise_or.reduce(np.stack(bitmaps), axis=0)
    out, stats = bf.simulate_or_sparse(bitmaps, fanout, capacity)
    for o in out:
        assert np.array_equal(o, want), stats
    # mode choice mirrors the JAX guard exactly
    max_count = max(int(np.count_nonzero(b)) for b in bitmaps)
    want_mode = "sparse" if max_count <= min(capacity, n_words) else "dense"
    assert stats["mode"] == want_mode


@given(
    n_words=st.sampled_from([32, 128, 512]),
    active=st.integers(min_value=0, max_value=40),
    capacity=st.sampled_from([8, 32, 128]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_compact_expand_roundtrip(n_words, active, capacity, seed):
    """compact_words ∘ expand_words == identity whenever the count fits, and
    the overflow flag fires exactly when it does not."""
    rng = np.random.default_rng(seed)
    b = np.zeros(n_words, np.uint32)
    ii = rng.choice(n_words, size=min(active, n_words), replace=False)
    b[ii] = rng.integers(1, 2**32, size=ii.size, dtype=np.uint32)
    idx, vals, count, overflow = jax.jit(
        lambda w: fr.compact_words(w, capacity))(jnp.asarray(b))
    assert int(count) == int(np.count_nonzero(b))
    assert bool(overflow) == (int(count) > capacity)
    if not overflow:
        back = fr.expand_words(n_words, idx, vals)
        assert np.array_equal(np.asarray(back), b)


# --- query-engine dedup (serving, DESIGN.md §15) ----------------------------


def _dedup_engine():
    """One shared tiny engine (module-cached program) for the property."""
    global _DEDUP_ENGINE
    try:
        return _DEDUP_ENGINE
    except NameError:
        from repro.analytics.engine import BFSQueryEngine
        from repro.graph import generators

        g = generators.kronecker(8, 8, seed=2)
        pg = partition.partition_1d(g, 4)
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        _DEDUP_ENGINE = (
            g, BFSQueryEngine(pg, mesh, bfs.BFSConfig(axes=("data",)), lanes=4)
        )
        return _DEDUP_ENGINE


@given(
    roots=st.lists(st.integers(0, 255), min_size=1, max_size=6),
)
@settings(max_examples=15, deadline=None)
def test_engine_query_dedup_property(roots):
    """``query(r + r) == query(r)`` twice over, for ANY root list (the
    ISSUE-4 duplicate-fold contract), and distinct-root wave accounting."""
    g, eng = _dedup_engine()
    base = eng.query(roots)
    w0 = eng.stats.waves
    doubled = eng.query(roots + roots)
    waves = eng.stats.waves - w0
    assert np.array_equal(doubled, np.concatenate([base, base]))
    n_uniq = len(set(roots))
    assert waves == -(-n_uniq // eng.lanes)  # ceil(distinct / lanes)


# --- streaming delta overlay (DESIGN.md §16) --------------------------------


def _overlay_oracle(g, batches):
    """Pure-python oracle of the §16 overlay semantics: symmetrize, drop
    self-loops, min-weight on duplicate insert, delete both directions."""
    edges = {}
    for i, (u, v) in enumerate(zip(g.src.tolist(), g.dst.tolist())):
        edges[(u, v)] = int(g.weights[i]) if g.weighted else None
    for b in batches:
        ws = (b.insert_weights.tolist() if b.insert_weights is not None
              else [None] * b.insert_src.size)
        for u, v, w in zip(b.insert_src.tolist(), b.insert_dst.tolist(), ws):
            if u == v:
                continue
            for e in ((u, v), (v, u)):
                if e in edges and edges[e] is not None:
                    edges[e] = min(edges[e], w)
                elif e not in edges:
                    edges[e] = w
        for u, v in zip(b.delete_src.tolist(), b.delete_dst.tolist()):
            edges.pop((u, v), None)
            edges.pop((v, u), None)
    return edges


@given(
    n=st.integers(4, 80),
    m=st.integers(0, 200),
    weighted=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
    n_batches=st.integers(1, 4),
    compact_at=st.integers(0, 4),
)
@settings(max_examples=25, deadline=None)
def test_delta_overlay_stream_property(n, m, weighted, seed, n_batches,
                                       compact_at):
    """ISSUE-5 satellite: ANY random stream of insert/delete batches
    applied through ``dynamic.delta`` (with a compaction anywhere in the
    stream) yields a Graph identical — structure and min-dedup'd weights —
    to a from-scratch build of the final edge list."""
    from repro.dynamic import delta

    rng = np.random.default_rng(seed)
    g = csr.from_edges(
        rng.integers(0, n, size=m), rng.integers(0, n, size=m), n,
        weights=rng.integers(1, 16, size=m) if weighted else None,
    )
    ov = delta.DeltaOverlay(g)
    batches = []
    for i in range(n_batches):
        k_ins, k_del = int(rng.integers(0, 12)), int(rng.integers(0, 8))
        b = ov.sample_batch(rng, k_ins, k_del,
                            max_weight=16 if weighted else 0)
        batches.append(b)
        ov.apply(b)
        if i == compact_at:
            ov.compact()  # mid-stream compaction must not change anything
    got = ov.current_graph()
    got.validate()
    edges = _overlay_oracle(g, batches)
    keys = sorted(edges)
    np.testing.assert_array_equal(
        got.src, np.array([k[0] for k in keys], dtype=np.int32)
    )
    np.testing.assert_array_equal(
        got.dst, np.array([k[1] for k in keys], dtype=np.int32)
    )
    if weighted:
        np.testing.assert_array_equal(
            got.weights,
            np.array([edges[k] for k in keys], dtype=np.uint32),
        )
    else:
        assert got.weights is None
