"""Graph substrate: ETL invariants, partitioning, generators."""

import dataclasses

import numpy as np
import pytest

from repro.graph import csr, generators, partition


def test_etl_dedup_symmetrize():
    src = np.array([0, 0, 1, 2, 2, 2, 3])
    dst = np.array([1, 1, 0, 3, 3, 2, 2])  # dups + self-loop (2,2)
    g = csr.from_edges(src, dst, 4)
    g.validate()
    assert g.n_edges == 4  # {0-1, 1-0, 2-3, 3-2}
    assert np.all(g.src != g.dst)


@pytest.mark.parametrize("n,m,seed", [(2, 0, 0), (17, 40, 1), (100, 500, 2),
                                      (200, 1, 3), (64, 300, 4)])
def test_etl_properties(n, m, seed):
    """Deterministic slice of the ETL invariants; the randomized hypothesis
    sweep lives in tests/test_properties.py."""
    rng = np.random.default_rng(seed)
    g = csr.from_edges(
        rng.integers(0, n, size=m), rng.integers(0, n, size=m), n
    )
    g.validate()  # symmetry, sortedness, offsets
    assert g.n % 32 == 0


@pytest.mark.parametrize("p", [1, 2, 3, 5, 8])
def test_partition_covers_everything(p):
    g = generators.kronecker(9, 8, seed=0)
    pg = partition.partition_1d(g, p)
    assert pg.v_count.sum() == g.n
    assert pg.edge_count.sum() == g.n_edges
    assert pg.in_count.sum() == g.n_edges
    # vertex ranges contiguous & word-aligned
    assert pg.v_start[0] == 0
    assert np.all(pg.v_start % 32 == 0)
    for i in range(p - 1):
        assert pg.v_start[i] + pg.v_count[i] == pg.v_start[i + 1]
    # every out-edge's src belongs to its owner
    for i in range(p):
        c = pg.edge_count[i]
        s = pg.edge_src[i, :c]
        assert np.all((s >= pg.v_start[i]) & (s < pg.v_start[i] + pg.v_count[i]))


def test_partition_edge_balance():
    g = generators.kronecker(11, 8, seed=1)
    pg = partition.partition_1d(g, 8)
    frac = pg.edge_count / g.n_edges
    # paper: "near equal number of edges" — word-rounding slack allowed
    assert frac.max() < 2.5 / 8, frac


def test_generators_shapes():
    g = generators.torus_2d(10)
    assert g.n_real == 100 and g.n_edges == 400  # 4-regular
    g = generators.path_graph(50)
    assert g.n_edges == 98
    g = generators.star_graph(100)
    assert g.out_degree[:1] == [99]


def test_kronecker_degree_skew():
    g = generators.kronecker(10, 8, seed=0)
    deg = g.out_degree
    assert deg.max() > 20 * max(1, np.median(deg))  # heavy tail exists


def test_validate_rejects_corrupt_graphs():
    """validate() is wired into every host construction path: corrupt
    graphs must raise, not traverse wrongly on device."""
    g = csr.from_edges(np.array([0, 1, 2]), np.array([1, 2, 3]), 4)

    # n not a multiple of 32
    import dataclasses

    bad = dataclasses.replace(g, n=33)
    with pytest.raises(csr.GraphValidationError, match="multiple"):
        bad.validate()

    # self-loop
    bad = dataclasses.replace(
        g, src=g.src.copy(), dst=g.src.copy()
    )
    with pytest.raises(csr.GraphValidationError):
        bad.validate()

    # unsorted COO (swap first two edges)
    src, dst = g.src.copy(), g.dst.copy()
    src[[0, 1]], dst[[0, 1]] = src[[1, 0]], dst[[1, 0]]
    bad = dataclasses.replace(g, src=src, dst=dst)
    with pytest.raises(csr.GraphValidationError, match="sorted"):
        bad.validate()

    # the partitioner rejects the same corruption on its host path
    with pytest.raises(csr.GraphValidationError):
        partition.partition_1d(bad, 2)

    # broken offsets
    ro = g.row_offsets.copy()
    ro[-1] += 1
    bad = dataclasses.replace(g, row_offsets=ro)
    with pytest.raises(csr.GraphValidationError, match="edge count"):
        bad.validate()


def test_validate_rejects_bad_weights():
    import dataclasses

    g = csr.from_edges(
        np.array([0, 1]), np.array([1, 2]), 3,
        weights=np.array([4, 9], np.uint32),
    )
    # wrong length
    bad = dataclasses.replace(g, weights=np.array([1], np.uint32))
    with pytest.raises(csr.GraphValidationError, match="weights shape"):
        bad.validate()
    # wrong dtype
    bad = dataclasses.replace(
        g, weights=g.weights.astype(np.int64)
    )
    with pytest.raises(csr.GraphValidationError, match="uint32"):
        bad.validate()
    # asymmetric: bump one direction only
    w = g.weights.copy()
    w[0] += 1
    bad = dataclasses.replace(g, weights=w)
    with pytest.raises(csr.GraphValidationError, match="symmetric"):
        bad.validate()


def test_weighted_etl_dedup_keeps_min_and_symmetrizes():
    src = np.array([0, 0, 2, 1])
    dst = np.array([1, 1, 3, 0])
    w = np.array([7, 3, 5, 9], np.uint32)
    g = csr.from_edges(src, dst, 4, weights=w)
    g.validate()
    assert g.n_edges == 4  # {0-1, 1-0, 2-3, 3-2}

    def wt(u, v):
        sl = slice(g.row_offsets[u], g.row_offsets[u + 1])
        return int(g.weights[sl][np.flatnonzero(g.dst[sl] == v)[0]])

    # min over dup (0,1):7, (0,1):3 and the mirrored (1,0):9
    assert wt(0, 1) == 3 and wt(1, 0) == 3
    assert wt(2, 3) == 5 and wt(3, 2) == 5


def test_generator_weights_symmetric_and_partitioned():
    g = generators.kronecker(9, 8, seed=0, max_weight=16)
    g.validate()
    assert g.weighted and g.weights.min() >= 1 and g.weights.max() <= 16
    # unweighted by default, identical topology
    g0 = generators.kronecker(9, 8, seed=0)
    assert not g0.weighted
    np.testing.assert_array_equal(g.src, g0.src)

    pg = partition.partition_1d(g, 4)
    assert pg.weighted
    keys = pg.arrays().keys()
    assert "edge_weight" in keys and "in_weight" in keys
    # out-view weights line up with the global CSR slices per device
    cum = g.row_offsets
    for i in range(4):
        lo, hi = int(cum[pg.v_start[i]]), int(cum[pg.v_start[i]
                                                  + pg.v_count[i]])
        c = int(pg.edge_count[i])
        assert hi - lo == c
        np.testing.assert_array_equal(pg.edge_weight[i, :c], g.weights[lo:hi])
    # in-view weights: each (dst-grouped) edge carries its CSR weight
    pg0 = partition.partition_1d(generators.kronecker(9, 8, seed=0), 4)
    assert not pg0.weighted and "edge_weight" not in pg0.arrays()


def test_synthetic_shapes_match_real_partition():
    """Dry-run sizing must upper-bound a real partition of the same graph."""
    g = generators.kronecker(12, 8, seed=2)
    p = 8
    pg = partition.partition_1d(g, p)
    syn = partition.synthetic_shapes(1 << 12, 2 * (1 << 12) * 8, p)
    assert syn.emax >= pg.emax
    assert syn.vmax >= pg.vmax
    assert syn.n_words >= pg.n_words
    ashapes = syn.array_shapes()
    real = pg.arrays()
    assert set(ashapes) == set(real)


@pytest.mark.parametrize("p,vertex_pad", [(1, 32), (3, 32), (4, 512),
                                          (8, 32)])
@pytest.mark.parametrize("weighted", [False, True])
def test_out_offsets_mark_each_owned_vertex_run(p, vertex_pad, weighted):
    """``out_offsets`` is a searchsorted of each shard's (src, dst)-sorted
    out-edges at its owned vertex ids: runs as long as the out-degrees,
    padded slots empty at ``edge_count``; the dry-run shapes list it."""
    g = generators.kronecker(10, 8, seed=7, max_weight=9 if weighted else 0)
    pg = partition.partition_1d(g, p, vertex_pad=vertex_pad)
    assert pg.out_offsets.shape == (p, pg.vmax + 1)
    assert pg.arrays()["out_offsets"] is pg.out_offsets
    for i in range(p):
        s, c, e = int(pg.v_start[i]), int(pg.v_count[i]), int(pg.edge_count[i])
        src = pg.edge_src[i, :e]
        np.testing.assert_array_equal(
            pg.out_offsets[i],
            np.searchsorted(src, s + np.arange(pg.vmax + 1), side="left"))
        np.testing.assert_array_equal(np.diff(pg.out_offsets[i, : c + 1]),
                                      pg.deg_out[i, :c])
        assert np.all(pg.out_offsets[i, c:] == e)
    syn = partition.synthetic_shapes(g.n, g.n_edges, p,
                                     vertex_pad=vertex_pad)
    assert syn.array_shapes()["out_offsets"] == (p, syn.vmax + 1)


@pytest.mark.parametrize("vertex_pad", [512, 1024])
def test_vertex_pad_gives_one_layout_across_seeds(vertex_pad):
    """With ``vertex_pad`` the owned-vertex width no longer moves with the
    graph: four seeds of one Kronecker size give one (vmax, emax, n_words)
    at P = 4, and the slots past each device's ``v_count`` stay empty."""
    layouts = set()
    for seed in range(4):
        g = generators.kronecker(10, 16, seed=seed)
        pg = partition.partition_1d(g, 4, lane_pad=16384,
                                    vertex_pad=vertex_pad)
        layouts.add((pg.vmax, pg.emax, pg.n_words))
        assert pg.vmax % vertex_pad == 0 and pg.vmax > pg.v_count.max()
        assert pg.wmax == pg.vmax // 32
        assert pg.n_words >= g.n // 32 + pg.wmax
        assert pg.deg_out.shape == (4, pg.vmax)
        assert pg.in_offsets.shape == (4, pg.vmax + 1)
        for i in range(4):
            c = int(pg.v_count[i])
            assert np.all(pg.deg_out[i, c:] == 0)
            assert np.all(pg.in_offsets[i, c:] == pg.in_count[i])
    assert len(layouts) == 1, layouts


@pytest.mark.parametrize("p", [1, 4, 8])
@pytest.mark.parametrize("weighted", [False, True])
def test_vertex_pad_32_is_the_default_layout(p, weighted):
    """``vertex_pad=32`` (the default) rounds ``vmax`` to one bitmap word,
    as the layout always has: array for array the same partition."""
    g = generators.kronecker(10, 8, seed=5, max_weight=9 if weighted else 0)
    base = partition.partition_1d(g, p)
    pg = partition.partition_1d(g, p, vertex_pad=32)
    assert base.vmax == -(-max(32, int(base.v_count.max())) // 32) * 32
    for f in dataclasses.fields(partition.PartitionedGraph):
        a, b = getattr(base, f.name), getattr(pg, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and np.array_equal(a, b), f.name
        else:
            assert a == b, f.name
    assert partition.synthetic_shapes(1 << 12, 1 << 16, p) == \
        partition.synthetic_shapes(1 << 12, 1 << 16, p, vertex_pad=32)


@pytest.mark.parametrize("vertex_pad", [0, -32, 16, 48, 100])
def test_vertex_pad_not_a_multiple_of_32_raises(vertex_pad):
    g = generators.kronecker(8, 8, seed=0)
    with pytest.raises(ValueError, match="vertex_pad"):
        partition.partition_1d(g, 4, vertex_pad=vertex_pad)
    with pytest.raises(ValueError, match="vertex_pad"):
        partition.synthetic_shapes(1 << 8, 1 << 12, 4, vertex_pad=vertex_pad)


def test_largest_component_root():
    g = generators.kronecker(8, 8, seed=0)
    rng = np.random.default_rng(0)
    comp = csr.connected_components(g)
    largest = np.bincount(comp[: g.n_real]).argmax()
    for _ in range(5):
        r = csr.largest_component_root(g, rng)
        assert comp[r] == largest


def test_largest_component_roots_distinct_and_clamped():
    """§15 serving convention: distinct big-component roots, clamped to the
    component size (engine waves fold duplicates, so replacement sampling
    would under-count benchmark work)."""
    g = generators.kronecker(8, 8, seed=0)
    comp = csr.connected_components(g)
    largest = np.bincount(comp[: g.n_real]).argmax()
    comp_size = int(np.sum(comp[: g.n_real] == largest))

    rng = np.random.default_rng(0)
    roots = csr.largest_component_roots(g, 10, rng)
    assert roots.shape == (10,)
    assert len(set(roots.tolist())) == 10  # distinct
    assert np.all(comp[roots] == largest)  # inside the big component

    everything = csr.largest_component_roots(g, comp_size + 999, rng)
    assert everything.shape == (comp_size,)  # clamped, never raises


def _union_find_labels(g):
    """The sequential union-find the vectorised components replaced: union
    by smaller root, components numbered by their smallest vertex."""
    parent = list(range(g.n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(g.src.tolist(), g.dst.tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.unique([find(i) for i in range(g.n)], return_inverse=True)[1]


@pytest.mark.parametrize("make", [
    lambda: generators.kronecker(9, 4, seed=1),
    lambda: generators.uniform_random(700, 500, seed=2),
    lambda: generators.path_graph(77),
    lambda: csr.from_edges(np.array([5, 9, 40]), np.array([3, 2, 41]), 50,
                           symmetrize=False),
], ids=["kron9", "urand-sparse", "path-padded", "directed"])
def test_connected_components_same_labels_as_union_find(make):
    g = make()
    got = csr.connected_components(g)
    assert got.shape == (g.n,)
    np.testing.assert_array_equal(got, _union_find_labels(g))


def test_kronecker_etl_timings():
    etl = {}
    g = generators.kronecker(8, 8, seed=0, timings=etl)
    assert set(etl) == {"generate", "from_edges", "validate"}
    assert all(v >= 0.0 for v in etl.values())
    assert g._validated
    steps = {}
    csr.from_edges(g.src, g.dst, g.n_real, timings=steps)
    assert set(steps) == {"from_edges", "validate"}


@pytest.mark.parametrize("scale,edge_factor,seed", [(6, 4, 0), (11, 16, 7)])
def test_kronecker_edge_draw_matches_int64_loop(scale, edge_factor, seed):
    """The buffered uint32 edge draw builds the same graph as the plain
    per-bit int64 loop it replaced."""
    a, b, c = 0.57, 0.19, 0.19
    n, m = 1 << scale, (1 << scale) * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src |= (r >= a + b).astype(np.int64) << bit
        dst |= (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(
            np.int64) << bit
    perm = rng.permutation(n)
    want = csr.from_edges(perm[src], perm[dst], n)
    got = generators.kronecker(scale, edge_factor, seed=seed)
    np.testing.assert_array_equal(got.src, want.src)
    np.testing.assert_array_equal(got.dst, want.dst)
