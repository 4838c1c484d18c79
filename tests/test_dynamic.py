"""Streaming mutations: delta overlay, incremental repair, versioned cache
(DESIGN.md §16).

Tier-1 covers, on small graphs: overlay ETL-equivalence against a
from-scratch build of the final edge list, partition patching vs a fresh
partition of the materialized graph, repair bit-exactness against host
oracles across dense/sparse/adaptive sync for insert / delete / mixed /
weighted batches, the zero-cost unchanged-row proof, graph versioning +
partial cache invalidation through the live service, the identity-swap
regression, and the update-stream CLIs.  The kron13/P=8 acceptance bars
(repair ≥ 5× full recompute, ≥ 50% cache survival) run under ``tier2``
off the emitted ``dynamic_update`` rows.
"""

import json

import numpy as np
import pytest

from repro.core import bfs
from repro.dynamic import delta, repair, versioning
from repro.dynamic.versioning import GraphVersion
from repro.graph import csr, generators, partition
from repro.graph.csr import GraphValidationError
from repro.service import GraphQueryService
from repro.service.cache import ResultCache, result_key
from repro.traversal import sssp as sssp_mod

INF32 = np.iinfo(np.int32).max
RESULT_S = 120.0


def _norm(d):
    return np.where(np.asarray(d) >= INF32, -1, np.asarray(d))


def _oracle_edges(g, batches):
    """Independent pure-python simulation of the overlay semantics:
    symmetrized, self-loop-free, min-weight on duplicate insert, delete
    removes both directions (missing edges ignored)."""
    edges = {}
    for u, v in zip(g.src.tolist(), g.dst.tolist()):
        edges[(u, v)] = None
    if g.weighted:
        for (u, v), w in zip(zip(g.src.tolist(), g.dst.tolist()),
                             g.weights.tolist()):
            edges[(u, v)] = w
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
    keys = sorted(edges)
    src = np.array([k[0] for k in keys], dtype=np.int32)
    dst = np.array([k[1] for k in keys], dtype=np.int32)
    w = (np.array([edges[k] for k in keys], dtype=np.uint32)
         if g.weighted else None)
    return src, dst, w


@pytest.fixture(scope="module")
def graph_u():
    return generators.kronecker(9, 8, seed=2)  # n=512, unweighted


@pytest.fixture(scope="module")
def graph_w():
    return generators.kronecker(9, 8, seed=3, max_weight=8)


# --- delta overlay ----------------------------------------------------------


def test_overlay_stream_matches_scratch_build(graph_w):
    g = graph_w
    ov = delta.DeltaOverlay(g)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(2):
        b = ov.sample_batch(rng, 10, 5, max_weight=8)
        batches.append(b)
        ov.apply(b)
    # crafted edge cases: duplicate insert with LOWER weight (must lower),
    # with higher weight (no-op), a self-loop (dropped), a missing delete
    u, v = int(g.src[0]), int(g.dst[0])
    w_uv = int(g.weights[0])
    crafted = delta.EdgeBatch(
        insert_src=[u, u, 3, 1],
        insert_dst=[v, v, 3, 2],
        insert_weights=[max(w_uv - 1, 1), w_uv + 3, 5, 4],
        delete_src=[g.n_real + 1],  # never an edge: ignored
        delete_dst=[0],
    )
    batches.append(crafted)
    ov.apply(crafted)
    got = ov.current_graph()
    got.validate()
    src, dst, w = _oracle_edges(g, batches)
    np.testing.assert_array_equal(got.src, src)
    np.testing.assert_array_equal(got.dst, dst)
    np.testing.assert_array_equal(got.weights, w)
    # compaction rebases without changing the edge set
    before = ov.n_edges
    g2 = ov.compact()
    assert ov.pending_ops == 0 and ov.base is g2
    assert g2.n_edges == before
    ov.apply(delta.EdgeBatch.insert([1], [100], [2]))
    assert ov.n_edges == before + 2


def test_zero_weight_edges_rejected(graph_w):
    """Repair soundness needs w >= 1 (a zero-weight edge would let the
    deletion-taint closure reach the root): both entrances to the dynamic
    subsystem enforce it."""
    with pytest.raises(ValueError, match=">= 1"):
        delta.EdgeBatch.insert([0], [1], [0])
    g0 = csr.from_edges(
        np.array([0, 1]), np.array([1, 2]), 64,
        weights=np.array([0, 5]),
    )
    with pytest.raises(GraphValidationError, match=">= 1"):
        delta.DeltaOverlay(g0)


def test_overlay_validation(graph_u, graph_w):
    ov = delta.DeltaOverlay(graph_u)
    with pytest.raises(GraphValidationError, match="unweighted"):
        ov.apply(delta.EdgeBatch.insert([0], [1], [5]))
    ovw = delta.DeltaOverlay(graph_w)
    with pytest.raises(GraphValidationError, match="weight"):
        ovw.apply(delta.EdgeBatch.insert([0], [1]))
    with pytest.raises(GraphValidationError, match="out of range"):
        ov.apply(delta.EdgeBatch.insert([0], [graph_u.n + 5]))
    with pytest.raises(ValueError):
        delta.DeltaOverlay(graph_u, compact_ratio=0)
    # a batch that dedups away entirely is empty
    u, v = int(graph_u.src[0]), int(graph_u.dst[0])
    upd = ov.apply(delta.EdgeBatch.insert([u, 5], [v, 5]))
    assert upd.empty


def test_partition_patch_matches_materialized(graph_w):
    g = graph_w
    pg = partition.partition_1d(g, 8)
    ov = delta.DeltaOverlay(g)
    upd = ov.apply(ov.sample_batch(np.random.default_rng(1), 15, 8,
                                   max_weight=8))
    assert delta.apply_update_to_partition(pg, upd)
    gm = ov.current_graph()
    keys, ws = delta.partition_edge_multiset(pg)
    np.testing.assert_array_equal(
        keys, (gm.src.astype(np.int64) << 32) | gm.dst.astype(np.int64)
    )
    np.testing.assert_array_equal(ws, gm.weights)
    # in-edge side stays consistent with the out-edge side
    assert int(pg.edge_count.sum()) == int(pg.in_count.sum())
    # deg_out tracks the deduplicated out-degree of the materialized graph
    deg = gm.out_degree
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        np.testing.assert_array_equal(pg.deg_out[i, :c], deg[s : s + c])


def _in_edge_batches(g, ov, kind, rng):
    """Three batches of one kind against the overlay's current edge set;
    on a weighted graph the inserts also lower an existing edge's weight
    (a duplicate in-edge slot)."""
    n_ins, n_del = {"insert": (12, 0), "delete": (0, 10),
                    "mixed": (10, 8)}[kind]
    for _ in range(3):
        b = ov.sample_batch(rng, n_ins, n_del,
                            max_weight=8 if g.weighted else 0)
        if g.weighted and n_ins:
            e = int(np.flatnonzero(g.weights > 1)[0])
            b = delta.EdgeBatch(
                insert_src=np.append(b.insert_src, g.src[e]),
                insert_dst=np.append(b.insert_dst, g.dst[e]),
                insert_weights=np.append(b.insert_weights, 1),
                delete_src=b.delete_src, delete_dst=b.delete_dst)
        yield ov.apply(b)


@pytest.mark.parametrize("kind", ["insert", "delete", "mixed"])
@pytest.mark.parametrize("weighted", [False, True])
def test_partition_patch_keeps_in_edges_sorted(graph_u, graph_w, mesh8,
                                               weighted, kind):
    """After inserts and deletes every shard's active in-edges stay sorted
    by (dst, src), its ``in_offsets`` equal a recount, and single-source
    top-down BFS on the patched partition matches the oracle on the
    mutated graph."""
    g = graph_w if weighted else graph_u
    pg = partition.partition_1d(g, 8)
    ov = delta.DeltaOverlay(g)
    for upd in _in_edge_batches(g, ov, kind, np.random.default_rng(4)):
        assert delta.apply_update_to_partition(pg, upd)
    for i in range(pg.p):
        c = int(pg.in_count[i])
        key = (pg.in_dst[i, :c].astype(np.int64) << 32) | pg.in_src[i, :c]
        assert np.all(np.diff(key) >= 0), i
        runs = np.bincount(pg.in_dst[i, :c] - pg.v_start[i],
                           minlength=pg.vmax)
        np.testing.assert_array_equal(
            pg.in_offsets[i], np.concatenate([[0], np.cumsum(runs)]),
            err_msg=f"shard {i}")
    gm = ov.current_graph()
    root = int(csr.largest_component_root(gm, np.random.default_rng(0)))
    d, _, _ = bfs.distributed_bfs(pg, mesh8, root,
                                  bfs.BFSConfig(axes=("data",)))
    np.testing.assert_array_equal(_norm(d),
                                  _norm(bfs.bfs_reference(gm, root)))


@pytest.mark.parametrize("kind", ["insert", "delete", "mixed"])
@pytest.mark.parametrize("weighted", [False, True])
def test_partition_patch_keeps_out_edges_sorted(graph_u, graph_w, mesh8,
                                                monkeypatch, weighted, kind):
    """After inserts, deletes and weight-lowerings every shard's active
    out-edges stay sorted by (src, dst), its ``out_offsets`` equal a
    recount and hold the updated graph's edges, ``deg_out`` its degrees;
    single-source BFS on the patched partition matches the oracle whether
    every level pushes the frontier's out-edges or scans the in-edges."""
    g = graph_w if weighted else graph_u
    pg = partition.partition_1d(g, 8)
    ov = delta.DeltaOverlay(g)
    for upd in _in_edge_batches(g, ov, kind, np.random.default_rng(5)):
        assert delta.apply_update_to_partition(pg, upd)
    gm = ov.current_graph()
    for i in range(pg.p):
        s, c, e = int(pg.v_start[i]), int(pg.v_count[i]), int(pg.edge_count[i])
        src, dst = pg.edge_src[i, :e], pg.edge_dst[i, :e]
        key = (src.astype(np.int64) << 32) | dst
        assert np.all(np.diff(key) >= 0), i
        np.testing.assert_array_equal(
            pg.out_offsets[i],
            np.searchsorted(src, s + np.arange(pg.vmax + 1)),
            err_msg=f"shard {i}")
        lo, hi = gm.row_offsets[s], gm.row_offsets[s + c]
        np.testing.assert_array_equal(
            np.unique(key), (gm.src[lo:hi].astype(np.int64) << 32)
            | gm.dst[lo:hi], err_msg=f"shard {i}")
        np.testing.assert_array_equal(pg.deg_out[i, :c],
                                      gm.out_degree[s: s + c])
        if not weighted:  # no duplicate slots: a fresh partition's runs
            np.testing.assert_array_equal(
                pg.out_offsets[i, : c + 1],
                gm.row_offsets[s: s + c + 1] - lo)
    root = int(csr.largest_component_root(gm, np.random.default_rng(0)))
    ref = _norm(bfs.bfs_reference(gm, root))
    for k in (0, pg.emax):
        monkeypatch.setattr(bfs, "push_capacity", lambda emax, k=k: k)
        d, _, _ = bfs.distributed_bfs(pg, mesh8, root,
                                      bfs.BFSConfig(axes=("data",)))
        np.testing.assert_array_equal(_norm(d), ref, err_msg=f"K={k}")


@pytest.mark.parametrize("kind", ["insert", "mixed"])
def test_partition_patch_keeps_vertex_pad(graph_u, kind):
    """A partition whose owned-vertex width is padded (``vertex_pad``)
    keeps that width and every array shape through updates; its padded
    slots stay empty runs, and single-source BFS over four devices on the
    patched partition matches the oracle on the mutated graph."""
    import jax

    g = graph_u
    pg = partition.partition_1d(g, 4, lane_pad=4096, vertex_pad=512)
    assert pg.vmax == 512 and pg.v_count.max() < pg.vmax
    shapes = {k: v.shape for k, v in pg.arrays().items()}
    ov = delta.DeltaOverlay(g)
    for upd in _in_edge_batches(g, ov, kind, np.random.default_rng(6)):
        assert delta.apply_update_to_partition(pg, upd)
    assert pg.vmax == 512 and pg.wmax == 512 // 32
    assert {k: v.shape for k, v in pg.arrays().items()} == shapes
    for i in range(pg.p):
        c = int(pg.v_count[i])
        assert np.all(pg.in_offsets[i, c:] == pg.in_count[i]), i
        assert np.all(pg.deg_out[i, c:] == 0), i
    gm = ov.current_graph()
    root = int(csr.largest_component_root(gm, np.random.default_rng(0)))
    mesh4 = jax.make_mesh((4,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    d, _, _ = bfs.distributed_bfs(pg, mesh4, root,
                                  bfs.BFSConfig(axes=("data",),
                                                sync="adaptive"))
    np.testing.assert_array_equal(_norm(d),
                                  _norm(bfs.bfs_reference(gm, root)))


def test_partition_patch_overflow_refused_atomically(graph_u):
    g = graph_u
    pg = partition.partition_1d(g, 8)
    snapshot = {k: v.copy() for k, v in pg.arrays().items()}
    slack = int(pg.emax - pg.edge_count.max())
    rng = np.random.default_rng(0)
    n = 2 * (slack + pg.emax)  # guaranteed not to fit somewhere
    ov = delta.DeltaOverlay(g)
    upd = ov.apply(delta.EdgeBatch.insert(
        rng.integers(0, g.n_real, n), rng.integers(0, g.n_real, n)
    ))
    assert not delta.apply_update_to_partition(pg, upd)
    for k, v in pg.arrays().items():
        np.testing.assert_array_equal(v, snapshot[k], err_msg=k)


def test_update_stream_roundtrip(tmp_path):
    batches = [
        delta.EdgeBatch.insert([1, 2], [3, 4]),
        delta.EdgeBatch(insert_src=[5], insert_dst=[6], insert_weights=[7],
                        delete_src=[1], delete_dst=[3]),
        delta.EdgeBatch.delete([2], [4]),
    ]
    path = str(tmp_path / "updates.jsonl")
    delta.write_update_stream(path, batches)
    back = delta.read_update_stream(path)
    assert len(back) == len(batches)
    for a, b in zip(batches, back):
        np.testing.assert_array_equal(a.insert_src, b.insert_src)
        np.testing.assert_array_equal(a.insert_dst, b.insert_dst)
        np.testing.assert_array_equal(a.delete_src, b.delete_src)
        np.testing.assert_array_equal(a.delete_dst, b.delete_dst)
        if a.insert_weights is None:
            assert b.insert_weights is None
        else:
            np.testing.assert_array_equal(a.insert_weights, b.insert_weights)


# --- incremental repair -----------------------------------------------------


@pytest.mark.parametrize("sync", ["butterfly", "sparse", "adaptive"])
def test_repair_mixed_batch_bfs_exact(graph_u, mesh8, sync):
    """Insert + delete batch: repaired levels are bit-exact vs a
    from-scratch reference on the mutated graph, in every sync mode."""
    g = graph_u
    pg = partition.partition_1d(g, 8)
    root = int(csr.largest_component_root(g, np.random.default_rng(0)))
    row0 = bfs.bfs_reference(g, root)
    ov = delta.DeltaOverlay(g)
    upd = ov.apply(ov.sample_batch(np.random.default_rng(1), 20, 10))
    assert delta.apply_update_to_partition(pg, upd)
    cfg = sssp_mod.SSSPConfig(axes=("data",), fanout=2, sync=sync)
    new_row, touched, iters = repair.repair_row(
        pg, mesh8, row0, upd, cfg, unit_weight=True
    )
    want = bfs.bfs_reference(ov.current_graph(), root)
    np.testing.assert_array_equal(new_row, want)
    assert iters > 0
    # touched is a conservative superset: tainted vertices whose distance
    # re-relaxed back to its old value still count
    assert touched >= int(np.sum(new_row != row0)) > 0


def test_repair_insert_only_and_sssp_exact(graph_w, mesh8):
    """Insert-only batches take the taint-free program; weighted SSSP
    repair (including a weight-lowering of an existing edge) matches
    Dijkstra on the mutated graph."""
    g = graph_w
    pg = partition.partition_1d(g, 8)
    root = int(csr.largest_component_root(g, np.random.default_rng(0)))
    row0 = sssp_mod.sssp_reference(g, root)
    ov = delta.DeltaOverlay(g)
    e = 5  # lower an existing edge's weight: repair must propagate it
    lower = delta.EdgeBatch.insert(
        [int(g.src[e])], [int(g.dst[e])],
        [max(int(g.weights[e]) - 1, 1)],
    )
    ov.apply(lower)
    b = ov.sample_batch(np.random.default_rng(4), 16, 0, max_weight=8)
    # fold both into one partition patch by replaying through the overlay
    ov2 = delta.DeltaOverlay(g)
    for batch in (lower, b):
        upd = ov2.apply(batch)
        assert delta.apply_update_to_partition(pg, upd)
        cfg = sssp_mod.SSSPConfig(axes=("data",), fanout=2, sync="adaptive")
        row0, touched, _ = repair.repair_row(
            pg, mesh8, row0, upd, cfg, unit_weight=False
        )
    want = sssp_mod.sssp_reference(ov2.current_graph(), root)
    np.testing.assert_array_equal(row0, want)


def test_repair_unchanged_proof_is_free(graph_u, mesh8):
    """A batch that provably cannot change the row (no improving insert,
    no tight delete) is vouched for with ZERO device work."""
    g = graph_u
    root = int(csr.largest_component_root(g, np.random.default_rng(0)))
    row0 = bfs.bfs_reference(g, root)
    # an edge between two same-level vertices changes no BFS level
    lvl = _norm(row0)
    cands = np.flatnonzero(lvl == 2)
    pair = None
    existing = set(zip(g.src.tolist(), g.dst.tolist()))
    for i in range(cands.size):
        for j in range(i + 1, cands.size):
            if (int(cands[i]), int(cands[j])) not in existing:
                pair = (int(cands[i]), int(cands[j]))
                break
        if pair:
            break
    assert pair is not None, "no same-level non-edge found"
    ov = delta.DeltaOverlay(g)
    upd = ov.apply(delta.EdgeBatch.insert([pair[0]], [pair[1]]))
    assert not upd.empty
    relax_ids, taint_ids = repair.repair_seeds(row0, upd, unit_weight=True)
    assert relax_ids.size == 0 and taint_ids.size == 0
    pg = partition.partition_1d(g, 8)
    assert delta.apply_update_to_partition(pg, upd)
    new_row, touched, iters = repair.repair_row(
        pg, mesh8, row0, upd, sssp_mod.SSSPConfig(axes=("data",)),
        unit_weight=True,
    )
    assert touched == 0 and iters == 0 and new_row is row0
    # sanity: the proof is not vacuous — the reference agrees
    np.testing.assert_array_equal(
        bfs.bfs_reference(ov.current_graph(), root), row0
    )


# --- versioning + cache -----------------------------------------------------


def test_graph_version_ordering_and_cache_keys():
    v = GraphVersion()
    assert v.bump_delta() == GraphVersion(0, 1)
    assert v.bump_epoch() == GraphVersion(1, 0)
    assert v < v.bump_delta() < v.bump_epoch() < GraphVersion(1, 1)
    assert str(GraphVersion(2, 3)) == "2.3" and GraphVersion(2, 3).json() == [2, 3]
    # result_key passes versions through and still normalizes ints
    key = result_key(GraphVersion(1, 2), "bfs", "cfg", 7)
    assert key[0] == GraphVersion(1, 2)
    assert result_key(np.int64(3), "bfs", "cfg", 7)[0] == 3
    # drop_stale orders versioned keys correctly
    c = ResultCache(capacity=8)
    c.put(result_key(GraphVersion(0, 1), "bfs", "cfg", 1), "a")
    c.put(result_key(GraphVersion(0, 2), "bfs", "cfg", 1), "b")
    assert c.drop_stale(GraphVersion(0, 2)) == 1
    assert c.peek(result_key(GraphVersion(0, 2), "bfs", "cfg", 1))


def test_service_apply_updates_partial_invalidation(graph_w, mesh8):
    """The §16 protocol end to end: version bumps delta_seq, bfs/sssp/
    closeness rows survive (kept or repaired) and serve the MUTATED graph
    from cache with zero engine waves; bc rows cold-start."""
    g = graph_w
    pg = partition.partition_1d(g, 8)
    svc = GraphQueryService(pg, mesh8, bfs.BFSConfig(axes=("data",), fanout=2),
                            lanes=4, n_real=g.n_real, max_linger_s=0.005)
    try:
        roots = [int(r) for r in csr.largest_component_roots(
            g, 3, np.random.default_rng(0))]
        for r in roots:
            svc.query("bfs", r, timeout=RESULT_S)
        svc.query("sssp", roots[0], timeout=RESULT_S)
        svc.query("closeness", roots[1], timeout=RESULT_S)
        svc.query("bc", roots[2], timeout=RESULT_S)
        rows_before = len(svc.cache)

        batch = svc.overlay.sample_batch(np.random.default_rng(5), 8, 4,
                                         max_weight=8)
        version = svc.apply_updates(batch)
        assert version == GraphVersion(0, 1)
        gm = svc.overlay.current_graph()
        mut = svc.snapshot()["mutations"]
        assert mut["batches"] == 1 and mut["compactions"] == 0
        assert mut["rows_dropped"] >= 1  # at least the bc row
        assert mut["rows_kept"] + mut["rows_repaired"] >= rows_before - 2

        waves0 = svc.engine.stats.waves
        for r in roots:
            d = svc.query("bfs", r, timeout=RESULT_S)
            np.testing.assert_array_equal(
                _norm(d), _norm(bfs.bfs_reference(gm, r))
            )
        np.testing.assert_array_equal(
            svc.query("sssp", roots[0], timeout=RESULT_S),
            sssp_mod.sssp_reference(gm, roots[0]),
        )
        assert svc.engine.stats.waves == waves0  # all served from cache
        # closeness rode its bfs row (kept or re-derived)
        from repro.analytics import measures

        got = svc.query("closeness", roots[1], timeout=RESULT_S)
        assert svc.engine.stats.waves == waves0
        want = float(measures.closeness_centrality(
            bfs.bfs_reference(gm, roots[1])[None, :], n=g.n_real)[0])
        assert got == pytest.approx(want)
        # an empty batch bumps nothing
        assert svc.apply_updates(delta.EdgeBatch.insert([], [])) == version
    finally:
        svc.stop()


def test_apply_updates_with_unliftable_sync_drops_not_raises(graph_w, mesh8):
    """A weighted graph served with a sync that has no min-monoid analogue
    (rabenseifner) must still apply updates cleanly: distance rows drop
    (nothing can vouch for them) but the batch commits and the version
    bumps — no half-applied mutation escaping as an exception."""
    g = graph_w
    svc = GraphQueryService(
        partition.partition_1d(g, 8), mesh8,
        bfs.BFSConfig(axes=("data",), fanout=2, sync="rabenseifner"),
        lanes=4, n_real=g.n_real, max_linger_s=0.005,
    )
    try:
        root = int(csr.largest_component_root(g, np.random.default_rng(0)))
        svc.query("bfs", root, timeout=RESULT_S)
        version = svc.apply_updates(
            delta.EdgeBatch.insert([1], [400], [3])
        )
        assert version == GraphVersion(0, 1)
        mut = svc.snapshot()["mutations"]
        assert mut["batches"] == 1 and mut["rows_dropped"] == 1
        # the dropped row recomputes correctly on the mutated graph
        gm = svc.overlay.current_graph()
        np.testing.assert_array_equal(
            _norm(svc.query("bfs", root, timeout=RESULT_S)),
            _norm(bfs.bfs_reference(gm, root)),
        )
    finally:
        svc.stop()


def test_repair_budget_drops_excess_suspects(graph_u, mesh8):
    """`max_repairs` bounds device work: suspects past the budget return
    None (the service drops them) while in-budget rows still repair."""
    g = graph_u
    pg = partition.partition_1d(g, 8)
    roots = [int(r) for r in csr.largest_component_roots(
        g, 4, np.random.default_rng(0))]
    rows = [bfs.bfs_reference(g, r) for r in roots]
    ov = delta.DeltaOverlay(g)
    upd = ov.apply(ov.sample_batch(np.random.default_rng(1), 20, 0))
    assert delta.apply_update_to_partition(pg, upd)
    outs = repair.repair_rows(
        pg, mesh8, rows, upd, sssp_mod.SSSPConfig(axes=("data",)),
        unit_weight=True, max_repairs=1,
    )
    suspects = [o for o in outs if o is None or o[2] > 0]
    repaired = [o for o in outs if o is not None and o[2] > 0]
    assert len(repaired) <= 1
    assert len(suspects) > 1  # the rest were dropped, not silently kept
    gm = ov.current_graph()
    for r, o in zip(roots, outs):
        if o is not None:
            np.testing.assert_array_equal(
                o[0], bfs.bfs_reference(gm, r)
            )


def test_service_compaction_takes_full_swap_path(graph_w, mesh8):
    g = graph_w
    svc = GraphQueryService(
        partition.partition_1d(g, 8), mesh8,
        bfs.BFSConfig(axes=("data",), fanout=2), lanes=4, n_real=g.n_real,
        compact_ratio=1e-9, max_linger_s=0.005,
    )
    try:
        root = int(csr.largest_component_root(g, np.random.default_rng(0)))
        svc.query("bfs", root, timeout=RESULT_S)
        version = svc.apply_updates(
            delta.EdgeBatch.insert([1], [400], [3])
        )
        assert version == GraphVersion(1, 0)  # epoch bump, delta reset
        assert len(svc.cache) == 0  # full swap cold-starts the cache
        assert svc.snapshot()["mutations"]["compactions"] == 1
        gm = svc.overlay.current_graph()
        np.testing.assert_array_equal(
            _norm(svc.query("bfs", root, timeout=RESULT_S)),
            _norm(bfs.bfs_reference(gm, root)),
        )
    finally:
        svc.stop()


def test_identity_swap_preserves_cache(graph_u, mesh8):
    """Regression (ISSUE-5 fix): swapping in a partition of the SAME graph
    must not bump the version, rebuild the engine, or cold-start the
    cache — while a genuinely different graph still does."""
    g = graph_u
    svc = GraphQueryService(
        partition.partition_1d(g, 8), mesh8,
        bfs.BFSConfig(axes=("data",), fanout=2), lanes=4, n_real=g.n_real,
        max_linger_s=0.005,
    )
    try:
        root = int(csr.largest_component_root(g, np.random.default_rng(0)))
        svc.query("bfs", root, timeout=RESULT_S)
        engine0 = svc.engine
        version0 = svc.epoch
        assert svc.swap_graph(
            partition.partition_1d(g, 8), n_real=g.n_real
        ) == version0
        assert svc.engine is engine0  # no rebuild, no recompile
        waves = svc.engine.stats.waves
        svc.query("bfs", root, timeout=RESULT_S)
        assert svc.engine.stats.waves == waves  # cache survived
        # a real change still bumps and recomputes
        g2 = generators.kronecker(9, 8, seed=11)
        v2 = svc.swap_graph(partition.partition_1d(g2, 8), n_real=g2.n_real)
        assert v2 == version0.bump_epoch()
        np.testing.assert_array_equal(
            _norm(svc.query("bfs", root, timeout=RESULT_S)),
            _norm(bfs.bfs_reference(g2, root)),
        )
    finally:
        svc.stop()


# --- CLI wiring -------------------------------------------------------------


def test_serve_graph_mutate_rate_and_bfs_run_replay(tmp_path):
    from repro.launch import bfs_run, serve_graph

    stats = tmp_path / "stats.json"
    stream = tmp_path / "updates.jsonl"
    assert serve_graph.main([
        "--scale", "8", "--devices", "2", "--lanes", "4",
        "--qps", "40", "--duration", "1.0", "--sync", "butterfly",
        "--mutate-rate", "4", "--mutate-edges", "4",
        "--stats-json", str(stats), "--record-updates", str(stream),
    ]) == 0
    doc = json.loads(stats.read_text())
    mut = doc["telemetry"]["mutations"]
    assert mut["batches"] >= 1
    assert 0.0 <= mut["survival_rate"] <= 1.0
    assert stream.exists()
    batches = delta.read_update_stream(str(stream))
    assert len(batches) == mut["batches"]
    # replay the recorded stream through bfs_run
    assert bfs_run.main([
        "--scale", "8", "--devices", "2", "--roots", "2",
        "--updates", str(stream),
    ]) == 0


# --- tier-2 acceptance off the benchmark rows -------------------------------


@pytest.mark.tier2
def test_dynamic_acceptance_kron13_p8():
    """ISSUE-5 bars from the emitted ``dynamic_update`` rows: on kron13 at
    P=8, incremental repair of an ≤0.1% insert batch beats the full
    recompute path by ≥5× per cached row (and beats even a
    charitably-warm recompute outright), the service keeps ≥50% of its
    cached rows across the mutation, and repaired results are bit-exact
    vs from-scratch traversal in every sync mode."""
    from benchmarks import dynamic as dbench

    rep = dbench.run(smoke=True)
    rows = rep.extra["dynamic_update"]
    for sync in ("butterfly", "sparse", "adaptive"):
        row = rows[f"kron13_P8_{sync}"]
        assert row["exact_vs_scratch"], row
        assert row["batch_frac"] <= 0.001 + 1e-9, row
    row = rows["kron13_P8_butterfly"]
    assert row["repair_speedup"] >= 5.0, row
    assert row["repair_speedup_warm"] >= 3.0, row
    svc_row = row["service"]
    assert svc_row["survival_rate"] >= 0.5, svc_row
    assert svc_row["post_mutation_hit_rate"] >= 0.5, svc_row
