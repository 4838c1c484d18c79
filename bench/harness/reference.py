"""The plain reference: BFS levels and closeness by ``scipy.sparse.csgraph``
on a graph built here from the raw edge list.  It imports nothing of the
program and reads nothing the program made."""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np
from scipy.sparse import coo_matrix, csgraph

INF32 = np.iinfo(np.int32).max
_BLOCK = 16  # roots per csgraph call: bounds the float64 distance block


def adjacency(src: np.ndarray, dst: np.ndarray, n: int):
    """The undirected simple graph of the edge list: both directions, no
    self-loops, duplicates merged.  A CSR matrix with 1 on every edge."""
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    keep = s != d
    s, d = s[keep], d[keep]
    adj = coo_matrix((np.ones(s.size), (s, d)),
                     shape=(n, n)).tocsr()  # sums duplicates
    adj.data[:] = 1.0  # float64: the type csgraph works in, no conversion
    return adj


def distances(adj, roots: Iterable[int], n_out: int) -> Dict[int, np.ndarray]:
    """``{root: int32[n_out]}`` hop distances, ``INF32`` where unreached
    (vertices past the adjacency's size are unreached)."""
    roots = sorted({int(r) for r in roots})
    out = {}
    for lo in range(0, len(roots), _BLOCK):
        block = roots[lo:lo + _BLOCK]
        d = csgraph.shortest_path(adj, unweighted=True, directed=True,
                                  indices=np.asarray(block, np.int64))
        for root, row in zip(block, d):
            full = np.full(n_out, INF32, dtype=np.int32)
            reached = np.isfinite(row)
            full[: row.size][reached] = row[reached].astype(np.int32)
            out[root] = full
    return out


def closeness(dist: np.ndarray, n_real: int) -> float:
    """Wasserman-Faust closeness of one distance row:
    ``(r-1)/sum(d) * (r-1)/(n_real-1)`` over the ``r`` reached vertices,
    0 where nothing but the root is reached."""
    reached = dist < INF32
    r = int(reached.sum())
    total = int(dist[reached].astype(np.int64).sum())
    if total == 0:
        return 0.0
    return (r - 1) / total * (r - 1) / (n_real - 1)
