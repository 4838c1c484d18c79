"""The benchmark's Kronecker draw gives the program's graph, and the plain
reference agrees with the program's own host oracle."""

import bench_tiny  # noqa: F401  (paths)
import numpy as np
import pytest

from harness import graph500, reference
from repro.core.bfs import bfs_reference
from repro.graph import csr, generators

ABC = (0.57, 0.19, 0.19)


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 3), (9, 2**31 + 7)])
def test_edges_through_the_program_etl_give_its_graph(scale, seed):
    src, dst, n = graph500.kronecker_edges(scale, 16, seed, *ABC)
    ours = csr.from_edges(src, dst, n)
    theirs = generators.kronecker(scale, 16, seed=seed)
    assert np.array_equal(ours.row_offsets, theirs.row_offsets)
    assert np.array_equal(ours.dst, theirs.dst)


def test_reference_graph_and_distances():
    src, dst, n = graph500.kronecker_edges(9, 16, 4, *ABC)
    g = csr.from_edges(src, dst, n)
    adj = reference.adjacency(src, dst, n)
    assert adj.nnz == g.n_edges
    roots = [0, 5, 17]
    want = reference.distances(adj, roots, g.n)
    for r in roots:
        oracle = bfs_reference(g, r)
        assert np.array_equal(want[r].astype(np.int64), oracle)


def test_closeness_matches_the_formula():
    d = np.array([0, 1, 1, 2, reference.INF32], dtype=np.int32)
    # r = 4 reached, sum 4: (3/4) * (3/4)
    assert reference.closeness(d, 5) == pytest.approx(0.75 * 3 / 4)
    assert reference.closeness(np.array([0, reference.INF32]), 2) == 0.0


def test_bit_parallel_depths_match_the_reference():
    import json

    from harness import system

    with open(bench_tiny.CHECKOUT / "bench/configs/g500-s21.json") as f:
        config = json.load(f)
    config["graph"]["scale"] = 10
    data = system.build_graph(config, 6, 1, lambda msg: None)
    isolated = int(np.flatnonzero(np.diff(data.g.row_offsets) == 0)[0])
    roots = list(data.candidates[:40]) + [isolated, int(data.g.n - 1)]
    adj = reference.adjacency(data.src, data.dst, data.n)
    want = reference.distances(adj, roots, data.g.n)
    expect = [int(want[r][want[r] < reference.INF32].max()) for r in roots]
    assert data.depths(roots) == expect
