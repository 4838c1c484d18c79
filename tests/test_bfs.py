"""Distributed ButterFly BFS correctness vs the sequential oracle."""

import jax
import numpy as np
import pytest

from repro.core import bfs
from repro.graph import csr, generators, partition

INF32 = np.iinfo(np.int32).max


def _dist(pg, mesh, root, interpret=False, **kw):
    cfg = bfs.BFSConfig(axes=("data",), **kw)
    d, levels, scanned = bfs.distributed_bfs(pg, mesh, root, cfg,
                                             interpret=interpret)
    return d, levels, scanned


def _norm(d):
    return np.where(d >= INF32, -1, d)


GRAPHS = {
    "kron10": lambda: generators.kronecker(10, 8, seed=1),
    "urand": lambda: generators.uniform_random(600, 3000, seed=2),
    "torus": lambda: generators.torus_2d(20),
    "path": lambda: generators.path_graph(200),
    "star": lambda: generators.star_graph(500),
}


@pytest.mark.parametrize("name", list(GRAPHS))
@pytest.mark.parametrize("sync,fanout", [("butterfly", 1), ("butterfly", 4),
                                         ("adaptive", 4),
                                         ("all_to_all", 1), ("xla", 1)])
def test_bfs_matches_reference(mesh8, name, sync, fanout):
    g = GRAPHS[name]()
    pg = partition.partition_1d(g, 8)
    ref = bfs.bfs_reference(g, 3)
    d, _, _ = _dist(pg, mesh8, 3, sync=sync, fanout=fanout)
    np.testing.assert_array_equal(_norm(d), _norm(ref))


@pytest.mark.parametrize("mode", ["top_down", "bottom_up", "direction_optimizing"])
def test_traversal_modes(mesh8, mode):
    g = GRAPHS["kron10"]()
    root = csr.largest_component_root(g, np.random.default_rng(0))
    pg = partition.partition_1d(g, 8)
    ref = bfs.bfs_reference(g, root)
    d, _, scanned = _dist(pg, mesh8, root, mode=mode)
    np.testing.assert_array_equal(_norm(d), _norm(ref))
    assert scanned > 0


def test_direction_optimizing_scans_fewer_edges(mesh8):
    """The Beamer switch must traverse fewer edges than pure top-down on a
    small-world graph (paper Sec. 2 'avoid traversing a majority')."""
    g = generators.kronecker(11, 16, seed=3)
    pg = partition.partition_1d(g, 8)
    root = csr.largest_component_root(g, np.random.default_rng(0))
    _, _, scanned_td = _dist(pg, mesh8, root, mode="top_down")
    _, _, scanned_do = _dist(pg, mesh8, root, mode="direction_optimizing")
    # at scale 11 the saving is ~25%; the paper's 90% shows at scale 27+
    assert scanned_do < 0.85 * scanned_td, (scanned_do, scanned_td)


def test_partition_count_invariance(mesh8):
    """P=1 vs P=2,4,8 must give identical distances (the distribution layer
    cannot change the algorithm's output)."""
    g = GRAPHS["kron10"]()
    ref = bfs.bfs_reference(g, 11)
    for p in (1, 2, 4, 8):
        pg = partition.partition_1d(g, p)
        mesh = jax.make_mesh((p,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        d, _, _ = _dist(pg, mesh, 11)
        np.testing.assert_array_equal(_norm(d), _norm(ref), err_msg=f"P={p}")


def test_fanout_invariance(mesh8):
    g = GRAPHS["urand"]()
    pg = partition.partition_1d(g, 8)
    ref = None
    for fanout in (1, 2, 3, 4, 8):
        d, _, _ = _dist(pg, mesh8, 0, fanout=fanout)
        if ref is None:
            ref = d
        np.testing.assert_array_equal(d, ref, err_msg=f"fanout={fanout}")


@pytest.mark.parametrize("mode", ["top_down", "direction_optimizing"])
def test_pallas_path_matches(mesh8, mode):
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    ref = bfs.bfs_reference(g, 3)
    d, _, _ = _dist(pg, mesh8, 3, mode=mode, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(_norm(d), _norm(ref))


def test_pallas_refused_on_tpu_backend(mesh8, monkeypatch):
    """The TPU compiler refuses the Pallas frontier kernels; building the
    Pallas path for a mesh of TPU devices fails at build time and names
    why, rather than falling back to the interpreter.  The mesh decides,
    not the process's default backend."""
    import types

    from repro.kernels import blocks

    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    layout = blocks.build_bfs_layout(pg)
    cfg = bfs.BFSConfig(axes=("data",), use_pallas=True)
    tpu_mesh = types.SimpleNamespace(
        devices=np.array([types.SimpleNamespace(platform="tpu")] * 8))
    for interpret in (False, True):
        with pytest.raises(NotImplementedError, match="Cannot do int indexing"):
            bfs.build_bfs_fn(pg, tpu_mesh, cfg, layout, interpret=interpret)
    # a CPU mesh builds, whatever the default backend says
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bfs.build_bfs_fn(pg, mesh8, cfg, layout, interpret=True)


def test_isolated_root(mesh8):
    g = generators.path_graph(100)  # padded vertices 100..127 are isolated
    pg = partition.partition_1d(g, 8)
    d, levels, scanned = _dist(pg, mesh8, 120)
    assert d[120] == 0
    assert np.all(_norm(np.delete(d, 120)) == -1)


def test_unreachable_marked_inf(mesh8):
    src = np.array([0, 1])  # two components: {0,1,2} wait: 0-1, 1-2
    dst = np.array([1, 2])
    g = csr.from_edges(src, dst, 10)
    pg = partition.partition_1d(g, 8)
    ref = bfs.bfs_reference(g, 0)
    d, _, _ = _dist(pg, mesh8, 0)
    np.testing.assert_array_equal(_norm(d), _norm(ref))
    assert _norm(d)[5] == -1


# property-based BFS invariants live in tests/test_properties.py
# (hypothesis-guarded so the tier-1 suite degrades gracefully without it)


def test_teps_accounting_top_down_total(mesh8):
    """Top-down scans each reached vertex's out-edges exactly once in total
    (paper Sec. 2: honest TEPS = true traversed edges)."""
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    d, _, scanned = _dist(pg, mesh8, 3)
    reached = _norm(d) >= 0
    want = int(g.out_degree[reached].sum())
    assert int(scanned) == want


def test_rabenseifner_frontier_sync(mesh8):
    """Beyond-paper OR-reduce-scatter+all-gather sync: same distances."""
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    ref = bfs.bfs_reference(g, 3)
    d, _, _ = _dist(pg, mesh8, 3, sync="rabenseifner", fanout=2)
    np.testing.assert_array_equal(_norm(d), _norm(ref))
    d, _, _ = _dist(pg, mesh8, 3, sync="rabenseifner", fanout=4)
    np.testing.assert_array_equal(_norm(d), _norm(ref))


# --- single-source top-down: the dst-sorted segmented expansion -------------


def _isolated_graph():
    """Two small components and zero-degree vertices: 14-29 and 32-59 have
    no edges, and 60-63 are the padding up to a multiple of 32."""
    src = np.array([0, 1, 2, 3, 10, 11, 12, 30])
    dst = np.array([1, 2, 3, 0, 11, 12, 13, 31])
    return csr.from_edges(src, dst, 60)


TOP_DOWN_GRAPHS = {  # name -> (graph, root); None: a largest-component root
    "kron9": (lambda: generators.kronecker(9, 8, seed=4), None),
    "torus12": (lambda: generators.torus_2d(12), 5),
    "path150": (lambda: generators.path_graph(150), 0),
    "isolated": (_isolated_graph, 10),
    "isolated_root": (_isolated_graph, 45),
}


@pytest.mark.parametrize("mode", ["top_down", "direction_optimizing"])
@pytest.mark.parametrize("sync", ["butterfly", "adaptive"])
@pytest.mark.parametrize("p", [1, 2, 8])
@pytest.mark.parametrize("name", list(TOP_DOWN_GRAPHS))
def test_single_source_top_down_matches_reference(name, p, sync, mode):
    """Top-down expansion reduces each owned vertex's run of dst-sorted
    in-edges; distances equal the oracle on every layout, with padding
    edges past every shard's ``in_count``."""
    make, root = TOP_DOWN_GRAPHS[name]
    g = make()
    if root is None:
        root = csr.largest_component_root(g, np.random.default_rng(0))
    pg = partition.partition_1d(g, p, lane_pad=1024)
    assert np.all(pg.in_count < pg.emax)
    mesh = jax.make_mesh((p,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    d, _, _ = _dist(pg, mesh, root, sync=sync, mode=mode)
    np.testing.assert_array_equal(_norm(d), _norm(bfs.bfs_reference(g, root)))


# --- four devices over a padded layout (partition_1d's vertex_pad) ----------


PADDED_GRAPHS = {  # name -> (graph, root); None: a largest-component root
    "kron10": (lambda: generators.kronecker(10, 8, seed=5), None),
    "path300": (lambda: generators.path_graph(300), 7),
    "isolated": (_isolated_graph, 10),
}
VERTEX_PAD = 512


def _padded_p4(name):
    make, root = PADDED_GRAPHS[name]
    g = make()
    if root is None:
        root = csr.largest_component_root(g, np.random.default_rng(0))
    pg = partition.partition_1d(g, 4, vertex_pad=VERTEX_PAD)
    assert pg.vmax == VERTEX_PAD and pg.v_count.max() < pg.vmax
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    return g, int(root), pg, mesh


@pytest.mark.parametrize("sync", ["adaptive", "sparse", "butterfly"])
@pytest.mark.parametrize("name", list(PADDED_GRAPHS))
def test_single_source_p4_padded_layout_matches_reference(name, sync):
    """Single-source BFS over four devices whose owned windows are padded
    past ``v_count`` equals the oracle, whichever exchange merges them."""
    g, root, pg, mesh = _padded_p4(name)
    d, _, _ = _dist(pg, mesh, root, sync=sync, fanout=2)
    np.testing.assert_array_equal(_norm(d), _norm(bfs.bfs_reference(g, root)))


def test_p4_padded_adaptive_exchange_takes_both_branches():
    """Across the padded four-device cases the adaptive exchange runs its
    dense and its sparse branch (the flight recorder's branch column), and
    the recorded traversals still equal the oracle."""
    from repro.core import flightrec

    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=2)
    branches = set()
    for name in PADDED_GRAPHS:
        g, root, pg, mesh = _padded_p4(name)
        d, levels, _, tr = flightrec.traced_bfs(pg, mesh, root, cfg)
        np.testing.assert_array_equal(_norm(d),
                                      _norm(bfs.bfs_reference(g, root)))
        branches |= set(tr.data[:levels, flightrec.COL_BRANCH].tolist())
    assert branches == {flightrec.BRANCH_DENSE, flightrec.BRANCH_SPARSE}


@pytest.mark.parametrize("n_words,wmax,seed", [
    (128, 1, 0), (128, 4, 1), (256, 32, 2), (1024, 128, 3)])
def test_segment_or_matches_scatter_or(n_words, wmax, seed):
    """The scatter-free segmented OR equals a scatter-OR of the same sorted
    runs; entries past the last run (padding) are never read."""
    import jax.numpy as jnp

    from repro.core import frontier as fr

    rng = np.random.default_rng(seed)
    vmax = wmax * 32
    word_start = int(rng.integers(0, n_words - wmax + 1))
    deg = rng.integers(0, 4, vmax) * (rng.random(vmax) < 0.7)
    count = int(deg.sum())
    emax = count + int(rng.integers(1, 64))
    dst = np.full(emax, n_words * 32, np.int32)  # padding: dropped below
    dst[:count] = word_start * 32 + np.repeat(np.arange(vmax), deg)
    offsets = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    active = rng.random(emax) < 0.3
    want = fr.scatter_or(n_words, jnp.asarray(dst), jnp.asarray(active))
    got = fr.segment_or(n_words, jnp.asarray(offsets), jnp.asarray(active),
                        jnp.int32(word_start))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,bound", [
    (1, 1), (127, 1), (129, 1), (16385, 1), (2_200_000, 1),
    (30_000, 60_000), (1_000, 2_000_000)])
def test_prefix_count_exact(n, bound):
    """The matmul prefix sum is exact: one level per 128 entries, and
    totals past 8 bits byte by byte."""
    import jax.numpy as jnp

    from repro.core import frontier as fr

    rng = np.random.default_rng(n)
    if bound == 1:
        bits = rng.random(n) < 0.5
        got = fr.prefix_count(jnp.asarray(bits))
        want = np.cumsum(bits)
    else:
        vals = rng.integers(0, bound + 1, n).astype(np.int32)
        got = fr._prefix_sum(jnp.asarray(vals), bound)
        want = np.cumsum(vals)
    np.testing.assert_array_equal(np.asarray(got), want)

