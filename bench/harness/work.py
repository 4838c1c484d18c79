"""The work a traversal needs, counted from the graph alone, whatever
implements the traversal.

- Graph500 kernel-2 TEPS counts, for each search, the undirected edges of
  the component it reached: the sum of the out-degrees of its vertices in
  the symmetric CSR, over 2.  Never the program's own ``scanned``, which
  grows with every full-edge scan per level.
- ``bytes_needed`` is the least HBM traffic any traversal of that
  component makes: each directed edge's destination id read once (4 B),
  and per reached vertex its 8-byte row offset read and its 4-byte
  distance written.
"""

from __future__ import annotations

import dataclasses

import numpy as np

EDGE_BYTES = 4  # int32 destination id, read once per directed edge
VERTEX_BYTES = 8 + 4  # int64 row offset read, int32 distance written


@dataclasses.dataclass(frozen=True)
class ComponentWork:
    """Per-component counts: ``labels[v]`` is v's component,
    ``directed_edges[k]`` and ``vertices[k]`` are component k's sizes."""

    labels: np.ndarray
    directed_edges: np.ndarray
    vertices: np.ndarray

    def teps_edges(self, root: int) -> int:
        """Undirected edges a search from ``root`` traverses."""
        return int(self.directed_edges[self.labels[root]]) // 2

    def bytes_needed(self, root: int) -> int:
        k = self.labels[root]
        return (EDGE_BYTES * int(self.directed_edges[k])
                + VERTEX_BYTES * int(self.vertices[k]))


def component_work(row_offsets: np.ndarray, labels: np.ndarray) -> ComponentWork:
    """Counts of each component of a symmetric CSR graph, from its row
    offsets and its component labels (``int[n]``, 0..k-1)."""
    degree = np.diff(row_offsets)
    k = int(labels.max()) + 1 if labels.size else 0
    edges = np.bincount(labels, weights=degree, minlength=k).astype(np.int64)
    vertices = np.bincount(labels, minlength=k).astype(np.int64)
    return ComponentWork(labels=labels, directed_edges=edges,
                         vertices=vertices)
