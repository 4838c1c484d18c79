"""Single-source top-down's three expansion branches against the oracle.

A level pushes the frontier's own out-edges when no chip's owned frontier
has more out-edge slots than the capacity ``K`` (``bfs.push_capacity``),
else pulls the unvisited vertices' own in-edges when no chip's owned
unvisited vertices have more in-edge slots than ``K``, and scans every
in-edge otherwise.  The tests force ``K`` by patching
``bfs.push_capacity``: 0 (every level dense), ``emax`` (every level
sparse), and a level's exact demand and one less (the boundary), besides
the default.
"""

import jax
import numpy as np
import pytest

from repro.core import bfs, flightrec
from repro.graph import csr, generators, partition

INF32 = np.iinfo(np.int32).max

GRAPHS = {  # name -> (graph, root); None: a largest-component root
    "kron9": (lambda: generators.kronecker(9, 8, seed=4), None),
    "path150": (lambda: generators.path_graph(150), 0),
    "star500": (lambda: generators.star_graph(500), 7),
}
CAPACITIES = ("default", "zero", "emax", "level", "level-1")


def _norm(d):
    return np.where(np.asarray(d) >= INF32, -1, np.asarray(d))


def _mesh(p):
    return jax.make_mesh((p,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _graph(name):
    make, root = GRAPHS[name]
    g = make()
    if root is None:
        root = csr.largest_component_root(g, np.random.default_rng(0))
    return g, int(root)


def demands(pg, dist):
    """``int64[levels, P]``: at each level (its frontier is the vertices at
    that distance) the out-edge slots of every chip's owned frontier."""
    runs = np.diff(pg.out_offsets, axis=1)
    reached = dist < INF32
    m = np.zeros((int(dist[reached].max()) + 1, pg.p), np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        d = dist[s: s + c]
        ok = d < INF32
        np.add.at(m[:, i], d[ok], runs[i, :c][ok])
    return m


def pull_demands(pg, dist):
    """``int64[levels, P]``: at each level the in-edge slots of every
    chip's owned vertices not yet visited (farther than the level, or
    never reached)."""
    runs = np.diff(pg.in_offsets, axis=1)
    levels = int(dist[dist < INF32].max()) + 1
    m = np.zeros((levels, pg.p), np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        d, r = dist[s: s + c], runs[i, :c]
        for level in range(levels):
            m[level, i] = r[d > level].sum()
    return m


def expansions(pg, dist, k):
    """The host model of each level's EXPAND code at capacity ``k``: push
    (1) when every chip's frontier out-edge slots fit, else pull (2) when
    every chip's unvisited in-edge slots fit, else scan (0)."""
    push = demands(pg, dist).max(axis=1) <= k
    pull = pull_demands(pg, dist).max(axis=1) <= k
    return np.where(push, flightrec.EXPAND_PUSH,
                    np.where(pull, flightrec.EXPAND_PULL,
                             flightrec.EXPAND_SCAN)).astype(np.int32)


def capacity_for(kind, pg, dist):
    """The ``K`` a case forces; ``None`` keeps the default.  ``level`` is
    the second largest demand a level makes (the largest if all are
    equal), so that the levels above it scan and the others push."""
    if kind == "default":
        return None
    if kind == "zero":
        return 0
    if kind == "emax":
        return pg.emax
    levels = np.unique(demands(pg, dist).max(axis=1))
    k = int(levels[-2] if levels.size > 1 else levels[-1])
    return k if kind == "level" else k - 1


def force_capacity(monkeypatch, k):
    if k is not None:
        monkeypatch.setattr(bfs, "push_capacity", lambda emax: k)


@pytest.mark.parametrize("mode", ["top_down", "direction_optimizing"])
@pytest.mark.parametrize("sync", ["butterfly", "adaptive"])
@pytest.mark.parametrize("capacity", CAPACITIES)
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_single_source_matches_reference_on_both_branches(
        monkeypatch, name, p, capacity, sync, mode):
    """Distances equal the oracle whichever branch each level takes."""
    g, root = _graph(name)
    ref = bfs.bfs_reference(g, root)
    pg = partition.partition_1d(g, p)
    force_capacity(monkeypatch, capacity_for(capacity, pg, ref))
    cfg = bfs.BFSConfig(axes=("data",), sync=sync, mode=mode)
    d, _, _ = bfs.distributed_bfs(pg, _mesh(p), root, cfg)
    np.testing.assert_array_equal(_norm(d), _norm(ref))


def _traced(pg, mesh, root, cfg):
    """Distances and the raw ``[P, levels, COLS]`` flight-recorder rows of
    every chip."""
    arrays = bfs.place_arrays(pg, mesh, cfg.axes)
    d_owned, levels, _, tbuf = bfs.build_bfs_fn(pg, mesh, cfg, trace=True)(
        arrays, np.int32(root))
    d_owned = np.asarray(d_owned)
    dist = np.full(pg.n, INF32, np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        dist[s: s + c] = d_owned[i, :c]
    return dist, np.asarray(tbuf)[:, : int(np.max(levels))]


def test_one_chip_over_capacity_sends_every_chip_dense(monkeypatch):
    """At P = 4, a level where one chip's frontier has more out-edge slots
    than ``K`` and the others' fit: every chip scans, and the distances
    stay exact (a chip that pushed alone would lose the destinations it
    owns whose frontier in-neighbours the scanning chip owns)."""
    g, root = _graph("kron9")
    ref = bfs.bfs_reference(g, root)
    pg = partition.partition_1d(g, 4)
    m = demands(pg, ref)
    top = np.sort(m, axis=1)
    split = np.flatnonzero(top[:, -1] > top[:, -2])
    level = int(split[np.argmax(top[split, -2])])
    k = int(top[level, -2])
    force_capacity(monkeypatch, k)
    assert (m[level] > k).sum() == 1 and (m[level] <= k).sum() == 3
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    dist, rows = _traced(pg, _mesh(4), root, cfg)
    np.testing.assert_array_equal(_norm(dist), _norm(ref))
    expand = rows[:, :, flightrec.COL_EXPAND]
    assert (expand[:, level] != flightrec.EXPAND_PUSH).all()
    assert (expand == expansions(pg, ref, k)).all()


@pytest.mark.parametrize("p", [1, 4])
def test_flight_recorder_logs_the_branch_each_level_took(p):
    """The EXPAND column is 1 exactly where no chip's owned frontier has
    more out-edge slots than ``K``, and 2 where, besides, no chip's owned
    unvisited vertices have more in-edge slots than ``K``: on a small
    Kronecker graph the first and last levels push and the peak level
    scans.  ``trace=False`` still stages a program with no trace
    buffer."""
    g, root = _graph("kron9")
    ref = bfs.bfs_reference(g, root)
    pg = partition.partition_1d(g, p)
    mesh = _mesh(p)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    dist, levels, _, trace = flightrec.traced_bfs(pg, mesh, root, cfg)
    np.testing.assert_array_equal(_norm(dist), _norm(ref))
    m = demands(pg, ref).max(axis=1)
    expand = trace.data[:, flightrec.COL_EXPAND]
    np.testing.assert_array_equal(
        expand, expansions(pg, ref, bfs.push_capacity(pg.emax)))
    assert expand[0] == 1 and expand[-1] == 1
    assert expand[int(np.argmax(m))] == 0
    assert trace.summary()["sparse_push_levels"] == int((expand == 1).sum())
    assert trace.summary()["sparse_pull_levels"] == int((expand == 2).sum())
    assert all(row["expand"] == e
               for row, e in zip(trace.level_table(), expand))

    arrays = bfs.place_arrays(pg, mesh, cfg.axes)
    text = bfs.build_bfs_fn(pg, mesh, cfg).lower(
        arrays, np.int32(root)).as_text()
    t_levels = flightrec.resolve_trace_levels(None, pg.n)
    assert f"{t_levels}x{flightrec.TRACE_COLS}xi32" not in text


def test_path_graph_pushes_every_level():
    """A high-diameter graph's frontier never outgrows the default
    capacity: every level pushes, and the segmented level step logs the
    same branches as the fused program."""
    g, root = _graph("path150")
    pg = partition.partition_1d(g, 2)
    mesh = _mesh(2)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    dist, levels, _, trace = flightrec.traced_bfs(pg, mesh, root, cfg)
    np.testing.assert_array_equal(_norm(dist),
                                  _norm(bfs.bfs_reference(g, root)))
    assert (trace.data[:, flightrec.COL_EXPAND] == 1).all()
    dist2, timed = flightrec.timed_bfs_levels(pg, mesh, cfg, root,
                                              warmup=False)
    np.testing.assert_array_equal(dist2, dist)
    np.testing.assert_array_equal(timed.data[:, flightrec.COL_EXPAND],
                                  trace.data[:, flightrec.COL_EXPAND])


def three_way_capacity(pg, dist):
    """The smallest ``K`` at which some level pushes, some pulls and some
    scans, by the host model."""
    for k in np.unique(np.concatenate([demands(pg, dist).ravel(),
                                       pull_demands(pg, dist).ravel()])):
        if set(expansions(pg, dist, int(k))) == {0, 1, 2}:
            return int(k)
    raise AssertionError("no capacity gives all three branches")


@pytest.mark.parametrize("mode", ["top_down", "direction_optimizing"])
@pytest.mark.parametrize("p", [1, 4])
def test_all_three_branches_in_one_traversal(monkeypatch, p, mode):
    """At a forced ``K`` some levels push, some pull and some scan: the
    distances equal the oracle, the fused program's EXPAND column is the
    host model's level for level, and the segmented level step logs the
    same rows."""
    g, root = _graph("kron9")
    ref = bfs.bfs_reference(g, root)
    pg = partition.partition_1d(g, p)
    k = three_way_capacity(pg, ref)
    force_capacity(monkeypatch, k)
    mesh = _mesh(p)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", mode=mode)
    dist, _, _, trace = flightrec.traced_bfs(pg, mesh, root, cfg)
    np.testing.assert_array_equal(_norm(dist), _norm(ref))
    expand = trace.data[:, flightrec.COL_EXPAND]
    want = expansions(pg, ref, k)
    if mode == "direction_optimizing":  # a bottom-up level logs 0
        want = np.where(trace.data[:, flightrec.COL_DIR] == 1, 0, want)
    np.testing.assert_array_equal(expand, want)
    assert trace.summary()["sparse_pull_levels"] == int((want == 2).sum())
    dist2, timed = flightrec.timed_bfs_levels(pg, mesh, cfg, root,
                                              warmup=False)
    np.testing.assert_array_equal(dist2, dist)
    np.testing.assert_array_equal(timed.data, trace.data)


def test_one_chip_over_pull_capacity_sends_every_chip_dense(monkeypatch):
    """At P = 4, a level too large to push where one chip's unvisited
    vertices have more in-edge slots than ``K`` and the others' fit: the
    one ``pmax`` of the demands sends every chip to the scan, so the
    chips run the same inner loop and pair up their exchanges, and the
    distances stay exact."""
    g, root = _graph("kron9")
    ref = bfs.bfs_reference(g, root)
    pg = partition.partition_1d(g, 4)
    push, pull = demands(pg, ref), np.sort(pull_demands(pg, ref), axis=1)
    cases = [(int(pull[lv, -2]), lv) for lv in range(pull.shape[0])
             if pull[lv, -1] > pull[lv, -2]
             and push[lv].max() > pull[lv, -2]]
    assert cases, "no level splits the chips' pull demands"
    k, level = max(cases)
    force_capacity(monkeypatch, k)
    assert (pull_demands(pg, ref)[level] > k).sum() == 1
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    dist, rows = _traced(pg, _mesh(4), root, cfg)
    np.testing.assert_array_equal(_norm(dist), _norm(ref))
    expand = rows[:, :, flightrec.COL_EXPAND]
    assert (expand[:, level] == flightrec.EXPAND_SCAN).all()
    assert (expand == expansions(pg, ref, k)).all()


def test_prefix_sum_exact_with_run_lengths_past_24_bits():
    """The push lays out runs with a prefix sum bounded by ``emax``: at
    ``emax`` past 2**24 the row totals' bound is capped at 31 bits and the
    sum stays exact."""
    import jax.numpy as jnp

    from repro.core import frontier as fr

    rng = np.random.default_rng(11)
    vals = np.zeros(5000, np.int32)
    at = rng.choice(vals.size, 24, replace=False)
    vals[at] = rng.integers(1 << 24, 1 << 26, at.size)
    got = fr._prefix_sum(jnp.asarray(vals), 1 << 26)
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(vals))
