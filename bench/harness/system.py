"""The system under test, built for one cell: the graph through the
program's ETL, and the compiled traversal or the query service on the
cell's chips.

Set-up prints the seconds of each step on its own line, so that the steps
a program change could shorten are visible run by run.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import numpy as np

from harness import graph500, work as work_mod


@dataclasses.dataclass
class GraphData:
    """The run's graph: the raw Kronecker edges (for the reference), the
    program's CSR and partition, and what the traffic and the work count
    read from the CSR."""

    src: np.ndarray
    dst: np.ndarray
    n: int
    g: object  # repro.graph.csr.Graph
    pg: object  # repro.graph.partition.PartitionedGraph
    labels: np.ndarray
    candidates: np.ndarray  # the largest component, ascending
    work: work_mod.ComponentWork
    etl: Dict[str, float]
    _adj: object = None

    def adjacency(self):
        """The program's CSR as a scipy matrix, for the components."""
        if self._adj is None:
            from scipy.sparse import csr_matrix

            g = self.g
            self._adj = csr_matrix(
                (np.ones(g.n_edges, np.int8), g.dst, g.row_offsets),
                shape=(g.n, g.n))
        return self._adj

    def depths(self, roots) -> list:
        """BFS depth (hop distance to the farthest vertex reached) of each
        of up to 64 roots, by one bit-parallel search over the program's
        symmetric CSR: bit i of a vertex's word is root i's frontier."""
        roots = [int(r) for r in roots]
        if len(roots) > 64:
            raise ValueError("at most 64 roots per bit-parallel search")
        g = self.g
        has_edges = np.diff(g.row_offsets) > 0
        starts = g.row_offsets[:-1][has_edges]  # empty rows reduce nothing
        frontier = np.zeros(g.n, np.uint64)
        for i, r in enumerate(roots):
            frontier[r] |= np.uint64(1) << np.uint64(i)
        visited = frontier.copy()
        depth = np.zeros(len(roots), np.int64)
        level = 0
        while True:
            # next[v] = OR of frontier over v's neighbours (rows of the CSR)
            reached = np.zeros(g.n, np.uint64)
            reached[has_edges] = np.bitwise_or.reduceat(frontier[g.dst],
                                                        starts)
            frontier = reached & ~visited
            lanes = int(np.bitwise_or.reduce(frontier))
            if not lanes:
                return depth.tolist()
            visited |= frontier
            level += 1
            for i in range(len(roots)):
                if lanes >> i & 1:
                    depth[i] = level


def build_graph(config: dict, seed: int, parts: int, log: Callable) -> GraphData:
    """Draw the Kronecker edges from the seed, run the program's ETL and
    1D partition, and find the largest component."""
    from scipy.sparse import csgraph

    from repro.graph import csr, generators, partition

    spec = config["graph"]
    abc = (spec["A"], spec["B"], spec["C"])
    if abc != (generators._A, generators._B, generators._C):
        raise ValueError(f"the program's ETL expects Graph500's initiator "
                         f"{(generators._A, generators._B, generators._C)}, "
                         f"the configuration states {abc}")
    etl: Dict[str, float] = {}
    t0 = time.perf_counter()
    src, dst, n = graph500.kronecker_edges(
        spec["scale"], spec["edge_factor"], seed, *abc)
    etl["generate"] = time.perf_counter() - t0
    g = csr.from_edges(src, dst, n, symmetrize=spec["symmetrize"],
                       timings=etl)
    t0 = time.perf_counter()
    pg = partition.partition_1d(g, parts, **config.get("partition", {}))
    etl["partition_1d"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    data = GraphData(src=src, dst=dst, n=n, g=g, pg=pg, labels=None,
                     candidates=None, work=None, etl=etl)
    _, labels = csgraph.connected_components(data.adjacency(), directed=False)
    largest = np.bincount(labels[: g.n_real]).argmax()
    data.labels = labels
    data.candidates = np.flatnonzero(labels[: g.n_real] == largest)
    data.work = work_mod.component_work(g.row_offsets, labels)
    etl["components"] = time.perf_counter() - t0
    for step, secs in etl.items():
        log(f"setup: {step} {secs:.2f} s")
    log(f"setup: graph n={g.n_real:,} directed edges {g.n_edges:,}; largest "
        f"component {data.candidates.size:,} vertices, "
        f"{data.work.teps_edges(int(data.candidates[0])):,} undirected edges;"
        f" {sum(a.nbytes for a in pg.arrays().values()) / parts / 2**30:.3f}"
        f" GiB of graph arrays per chip")
    return data


def mesh_of(devices):
    import jax

    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)


def bfs_config(config: dict, max_levels: Optional[int]):
    from repro.core import bfs

    return bfs.BFSConfig(axes=("data",), max_levels=max_levels,
                         **config["bfs"])


class SingleSource:
    """The compiled ``core.bfs.build_bfs_fn`` over arrays placed once, as
    ``launch/bfs_run`` runs it; compiled ahead of the window, so set-up
    warms this one program and runs no traversal."""

    def __init__(self, data: GraphData, devices, config: dict,
                 max_levels: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from repro.core import bfs

        self.pg = data.pg
        mesh = mesh_of(devices)
        cfg = bfs_config(config, max_levels)
        self.arrays = bfs.place_arrays(self.pg, mesh, cfg.axes)
        fn = bfs.build_bfs_fn(self.pg, mesh, cfg)
        self.compiled = fn.lower(
            self.arrays, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        self.module = self.compiled.as_text().split("\n", 1)[0].split()[1] \
            .rstrip(",")

    def __call__(self, root: int):
        return self.compiled(self.arrays, np.int32(root))

    def assemble(self, d_owned: np.ndarray) -> np.ndarray:
        """Global ``int32[n]`` distances from every chip's owned slice."""
        pg = self.pg
        dist = np.full(pg.n, np.iinfo(np.int32).max, dtype=np.int32)
        for i in range(pg.p):
            s, c = int(pg.v_start[i]), int(pg.v_count[i])
            dist[s: s + c] = d_owned[i, :c]
        return dist

    def close(self):
        self.arrays = self.compiled = None


class Service:
    """``GraphQueryService`` on the cell's chips with the configuration's
    lanes and the service's default linger and cache.  Set-up warms the
    wave program with one query from a vertex outside the largest
    component, which no request of the traffic names."""

    def __init__(self, data: GraphData, devices, config: dict,
                 max_levels: Optional[int] = None):
        from repro.service import GraphQueryService

        mesh = mesh_of(devices)
        cfg = bfs_config(config, max_levels)
        self.svc = GraphQueryService(data.pg, mesh, cfg,
                                     lanes=int(config["serve"]["lanes"]),
                                     n_real=data.g.n_real)
        outside = np.flatnonzero(data.labels[: data.g.n_real]
                                 != data.labels[data.candidates[0]])
        warm = int(outside[0]) if outside.size else int(data.candidates[-1])
        self.svc.query("bfs", warm, timeout=600)
        self.svc.reset_telemetry()

    def close(self):
        self.svc.stop()
        self.svc = None
