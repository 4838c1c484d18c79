"""The work count, bytes needed and the peaks table, on hand-counted
graphs."""

import bench_tiny  # noqa: F401  (paths)
import numpy as np
import pytest

from harness import peaks, work


def _csr(n, edges):
    """Symmetric CSR row offsets of an undirected edge list."""
    deg = np.zeros(n, np.int64)
    for u, v in edges:
        deg[u] += 1
        deg[v] += 1
    return np.concatenate([[0], np.cumsum(deg)])


def test_triangle_plus_path_plus_isolated():
    # component 0: triangle 0-1-2; component 1: path 3-4-5; 6 isolated
    offsets = _csr(7, [(0, 1), (1, 2), (0, 2), (3, 4), (4, 5)])
    labels = np.array([0, 0, 0, 1, 1, 1, 2])
    w = work.component_work(offsets, labels)
    assert w.teps_edges(0) == 3 and w.teps_edges(2) == 3
    assert w.teps_edges(4) == 2
    assert w.teps_edges(6) == 0
    # 6 directed edges x 4 B + 3 vertices x 12 B
    assert w.bytes_needed(1) == 6 * 4 + 3 * 12
    assert w.bytes_needed(5) == 4 * 4 + 3 * 12
    assert w.bytes_needed(6) == 12


def test_star():
    offsets = _csr(5, [(0, k) for k in range(1, 5)])
    w = work.component_work(offsets, np.zeros(5, np.int64))
    assert w.teps_edges(3) == 4
    assert w.bytes_needed(0) == 8 * 4 + 5 * 12


def test_peaks_table():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
