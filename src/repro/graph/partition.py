"""1D edge-balanced partitioning (paper Sec. 4 "Graph Partitioning").

The paper: "a straightforward 1D partitioning scheme where we divide the
vertices to the multiple GPUs such that each GPU gets a near equal number of
edges and the vertices are consecutive in their ids."  We reproduce exactly
that, with two TPU-specific refinements:

* partition boundaries are rounded to multiples of 32 so each device's owned
  vertex range is a whole number of frontier-bitmap words;
* the owned-vertex width ``vmax`` is rounded up to a multiple of
  ``vertex_pad`` (default 32, one bitmap word).  An edge-balanced split
  moves each device's vertex count with the graph; a coarser pad gives
  every graph of one size the same shapes, and so one compiled program;
* per-device edge arrays are padded to a common static shape (XLA needs
  static shapes) and stacked into ``[P, Emax]`` so a single ``shard_map``
  consumes them with the leading axis sharded over the device mesh.

Out-edges are kept sorted by (src, dst) and in-edges by (dst, src), with
``out_offsets`` and ``in_offsets`` marking where each owned vertex's runs
start: single-source top-down either pushes the frontier's own runs of
out-edges, or reduces each owned vertex's contiguous run of in-edges (the
degree-uniform layout that stands in for the paper's LRB load balancing,
see DESIGN.md Sec. 3).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.core.frontier import WORD_BITS
from repro.graph import csr


@dataclasses.dataclass
class PartitionedGraph:
    """Static-shape, device-stacked view of a 1D-partitioned graph.

    All ``[P, ...]`` arrays are sharded over the (flattened) device axis by
    the BFS ``shard_map``; scalars are replicated Python ints.
    """

    p: int
    n: int  # global vertex count (multiple of 32)
    n_words: int  # bitmap words EXCHANGED (includes slack, multiple of 128)
    n_edges: int  # global directed edge count
    vmax: int  # max owned vertices per device
    emax: int  # max owned edges per device (same pad for out and in)
    v_start: np.ndarray  # int32[P]
    v_count: np.ndarray  # int32[P]
    word_start: np.ndarray  # int32[P] == v_start // 32
    wmax: int  # max owned bitmap words per device
    edge_src: np.ndarray  # int32[P, emax]   out-edges, sorted by (src, dst)
    edge_dst: np.ndarray  # int32[P, emax]
    edge_count: np.ndarray  # int32[P]
    in_src: np.ndarray  # int32[P, emax]   in-edges, sorted by (dst, src)
    in_dst: np.ndarray  # int32[P, emax]
    in_count: np.ndarray  # int32[P]
    deg_out: np.ndarray  # int32[P, vmax]  out-degree of owned vertices
    # int32[P, vmax + 1]  shard-local in-edge row offsets of the owned slots:
    # slot v's in-edges are in_*[i, in_offsets[i, v]:in_offsets[i, v + 1]];
    # slots past v_count hold in_count (empty runs)
    in_offsets: np.ndarray
    # int32[P, vmax + 1]  the same for the out-edges: slot v's out-edges are
    # edge_*[i, out_offsets[i, v]:out_offsets[i, v + 1]]; slots past
    # v_count hold edge_count
    out_offsets: np.ndarray
    # uint32[P, emax] edge weights, partitioned alongside dst (out view) and
    # src (in view); None for unweighted graphs (DESIGN.md §14).
    edge_weight: Optional[np.ndarray] = None
    in_weight: Optional[np.ndarray] = None

    @property
    def weighted(self) -> bool:
        return self.edge_weight is not None

    def owner_of(self, v: int) -> int:
        return int(np.searchsorted(self.v_start, v, side="right") - 1)

    def arrays(self) -> dict:
        """The pytree handed to the distributed traversal step.  Weighted
        partitions add ``edge_weight``/``in_weight``."""
        out = dict(
            v_start=self.v_start,
            v_count=self.v_count,
            word_start=self.word_start,
            edge_src=self.edge_src,
            edge_dst=self.edge_dst,
            edge_count=self.edge_count,
            in_src=self.in_src,
            in_dst=self.in_dst,
            in_count=self.in_count,
            deg_out=self.deg_out,
            in_offsets=self.in_offsets,
            out_offsets=self.out_offsets,
        )
        if self.edge_weight is not None:
            out["edge_weight"] = self.edge_weight
            out["in_weight"] = self.in_weight
        return out


def _round_up(x: int, pad: int) -> int:
    return (x + pad - 1) // pad * pad


def _check_vertex_pad(vertex_pad: int) -> None:
    if vertex_pad <= 0 or vertex_pad % WORD_BITS:
        raise ValueError(f"vertex_pad must be a positive multiple of "
                         f"{WORD_BITS}, got {vertex_pad}")


@dataclasses.dataclass(frozen=True)
class SyntheticShapes:
    """Shape-only stand-in for :class:`PartitionedGraph` (dry-run: lower +
    compile the distributed BFS with ShapeDtypeStructs, no graph ETL).

    Sizing rules (documented in EXPERIMENTS.md §Dry-run): edges are
    1D-balanced with 15% slack; a Kronecker partition can own up to ~4× the
    mean vertex count (degree skew pushes edge-balanced cuts off the uniform
    grid), hence ``vmax = 4 * n/p``.
    """

    p: int
    n: int
    n_edges: int
    n_words: int
    vmax: int
    emax: int
    wmax: int

    def array_shapes(self) -> dict:
        p, emax, vmax = self.p, self.emax, self.vmax
        return dict(
            v_start=(p,),
            v_count=(p,),
            word_start=(p,),
            edge_src=(p, emax),
            edge_dst=(p, emax),
            edge_count=(p,),
            in_src=(p, emax),
            in_dst=(p, emax),
            in_count=(p,),
            deg_out=(p, vmax),
            in_offsets=(p, vmax + 1),
            out_offsets=(p, vmax + 1),
        )


def synthetic_shapes(n: int, m_directed: int, p: int, *, lane_pad: int = 128,
                     vertex_pad: int = WORD_BITS, slack: float = 1.15,
                     vskew: float = 4.0) -> SyntheticShapes:
    _check_vertex_pad(vertex_pad)
    n_pad = _round_up(n, WORD_BITS)
    emax = _round_up(int(m_directed / p * slack), lane_pad)
    vmax = _round_up(int(n_pad / p * vskew), vertex_pad)
    wmax = vmax // WORD_BITS
    n_words = _round_up(n_pad // WORD_BITS + wmax, lane_pad)
    return SyntheticShapes(
        p=p, n=n_pad, n_edges=m_directed, n_words=n_words,
        vmax=vmax, emax=emax, wmax=wmax,
    )


def partition_1d(g: csr.Graph, p: int, *, lane_pad: int = 128,
                 vertex_pad: int = WORD_BITS) -> PartitionedGraph:
    """Split vertices into ``p`` contiguous ranges with near-equal edges.

    ``lane_pad`` rounds the edge width ``emax`` and the exchanged bitmap
    length ``n_words``; ``vertex_pad`` (a positive multiple of 32) rounds
    the owned-vertex width ``vmax``, and the window ``wmax`` follows.
    Slots past a device's ``v_count`` stay empty: out-degree 0 and empty
    out- and in-edge runs.
    """
    _check_vertex_pad(vertex_pad)
    if not g._validated:  # corrupt inputs fail here, not as wrong traversals
        g.validate()
    cum = g.row_offsets  # int64[n+1], cumulative out-degree
    bounds: List[int] = [0]
    for i in range(1, p):
        target = g.n_edges * i // p
        b = int(np.searchsorted(cum, target, side="left"))
        b = min(max(_round_up(b, WORD_BITS), bounds[-1]), g.n)
        bounds.append(b)
    bounds.append(g.n)
    v_start = np.array(bounds[:-1], dtype=np.int32)
    v_end = np.array(bounds[1:], dtype=np.int32)
    v_count = v_end - v_start

    # --- out-edges per device (already sorted by (src, dst) globally)
    e_lo = cum[v_start]
    e_hi = cum[v_end]
    edge_count = (e_hi - e_lo).astype(np.int32)

    # --- in-edges per device (CSC view, grouped by destination)
    in_offsets, in_src_all, in_dst_all, in_w_all = csr.in_csr(g)
    ie_lo = in_offsets[v_start]
    ie_hi = in_offsets[v_end]
    in_count = (ie_hi - ie_lo).astype(np.int32)

    emax = _round_up(
        int(max(1, edge_count.max(initial=0), in_count.max(initial=0))),
        lane_pad)
    vmax = _round_up(int(max(WORD_BITS, v_count.max(initial=0))), vertex_pad)
    wmax = vmax // WORD_BITS

    edge_src = np.zeros((p, emax), dtype=np.int32)
    edge_dst = np.zeros((p, emax), dtype=np.int32)
    in_src = np.zeros((p, emax), dtype=np.int32)
    in_dst = np.zeros((p, emax), dtype=np.int32)
    deg_out = np.zeros((p, vmax), dtype=np.int32)
    local_in_offsets = np.zeros((p, vmax + 1), dtype=np.int32)
    local_out_offsets = np.zeros((p, vmax + 1), dtype=np.int32)
    edge_weight = np.zeros((p, emax), dtype=np.uint32) if g.weighted else None
    in_weight = np.zeros((p, emax), dtype=np.uint32) if g.weighted else None
    degrees = g.out_degree
    for i in range(p):
        s, e = int(e_lo[i]), int(e_hi[i])
        edge_src[i, : e - s] = g.src[s:e]
        edge_dst[i, : e - s] = g.dst[s:e]
        if g.weighted:
            edge_weight[i, : e - s] = g.weights[s:e]
        s, e = int(ie_lo[i]), int(ie_hi[i])
        in_src[i, : e - s] = in_src_all[s:e]
        in_dst[i, : e - s] = in_dst_all[s:e]
        if g.weighted:
            in_weight[i, : e - s] = in_w_all[s:e]
        deg_out[i, : v_count[i]] = degrees[v_start[i] : v_end[i]]
        local_in_offsets[i, : v_count[i] + 1] = (
            in_offsets[v_start[i] : v_end[i] + 1] - ie_lo[i])
        local_in_offsets[i, v_count[i] + 1 :] = in_count[i]
        local_out_offsets[i, : v_count[i] + 1] = (
            cum[v_start[i] : v_end[i] + 1] - e_lo[i])
        local_out_offsets[i, v_count[i] + 1 :] = edge_count[i]

    # Exchanged bitmap length: whole graph + one device window of slack so
    # every device can dynamic-slice its aligned [word_start, word_start+wmax)
    # window without clamping; padded to the 128-lane boundary.
    n_words = _round_up(g.n // WORD_BITS + wmax, lane_pad)

    return PartitionedGraph(
        p=p,
        n=g.n,
        n_words=n_words,
        n_edges=g.n_edges,
        vmax=vmax,
        emax=emax,
        v_start=v_start,
        v_count=v_count,
        word_start=(v_start // WORD_BITS).astype(np.int32),
        wmax=wmax,
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_count=edge_count,
        in_src=in_src,
        in_dst=in_dst,
        in_count=in_count,
        deg_out=deg_out,
        in_offsets=local_in_offsets,
        out_offsets=local_out_offsets,
        edge_weight=edge_weight,
        in_weight=in_weight,
    )
