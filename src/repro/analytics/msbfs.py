"""Bit-parallel multi-source BFS (MS-BFS) on the butterfly sync (DESIGN.md §13).

One wave runs up to ``B`` breadth-first searches concurrently, one BIT-LANE
per root: the wave frontier is lane-packed ``uint32[n_rows, B_words]``
(``B_words = ceil(B/32)``) where row ``v`` is vertex ``v`` and bit ``b`` of
lane-word ``b >> 5`` says "search ``b`` has ``v`` in its frontier" — the
Then et al. *The More the Merrier* layout, distributed.

Why this rides the butterfly for free: the phase-2 sync at low frontier
density is LATENCY-bound — ``log_f(P)`` rounds of small messages — and the
round count is independent of how many searches share the words.  Packing
32 lanes into the same exchange multiplies the effective traversal rate at
near-zero extra sync cost (Buluç & Madduri; Pan, Pearce & Owens — see
PAPERS.md).

A wave runs the single-source search's level step,
:func:`repro.core.bfs.level_step`, over the lane-packed frontier format
:class:`repro.core.bfs.LaneFormat` (its push and pull generalized over the
lane axis, Beamer's β scaled by the active lanes); phase 2 reuses
``collectives.butterfly_or`` / ``_sparse`` / ``_adaptive`` UNCHANGED on the
flattened word buffer.  The whole B-search wave compiles to ONE XLA
program: ``jit(shard_map(lax.while_loop))``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import flightrec
from repro.core import frontier as fr
from repro.core import loop
from repro.core.bfs import (
    INF,
    BFSConfig,
    LaneFormat,
    graph_array_keys,
    place_arrays,
    run_levels,
)
from repro.graph.partition import PartitionedGraph

LANE_BITS = fr.WORD_BITS


def lane_words(n_lanes: int) -> int:
    """Words per row: ceil(B/32)."""
    return (n_lanes + LANE_BITS - 1) // LANE_BITS


def wave_rows(pg: PartitionedGraph, *, lane_pad: int = 128) -> int:
    """Vertex rows of the wave buffer: the whole graph plus one device
    window of slack (every device dynamic-slices its aligned
    ``[v_start, v_start + vmax)`` rows without clamping), lane-padded."""
    rows = pg.n + pg.vmax
    return (rows + lane_pad - 1) // lane_pad * lane_pad


def build_msbfs_fn(
    pg: PartitionedGraph, mesh: jax.sharding.Mesh, cfg: BFSConfig,
    n_lanes: int, *, trace: bool = False, trace_levels=None,
):
    """Compile-ready B-lane multi-source BFS.

    Returns ``run(arrays, roots)`` where ``arrays`` is the SAME placed pytree
    the single-source BFS consumes and ``roots`` a replicated
    ``int32[n_lanes]`` (``-1`` = inactive lane; duplicates allowed).  Output:

    * ``d_owned int32[P, vmax, n_lanes]`` — per-device owned distances, one
      column per lane (INF for unreached / inactive lanes),
    * ``levels int32[P]`` — wave depth (max over lanes, all lanes step
      levels in lock-step),
    * ``scanned float32[P]`` — edges examined, summed over lanes (honest
      aggregate TEPS, paper Sec. 2).

    ``trace=True`` appends the §18 flight-recorder buffer
    ``int32[P, trace_levels, TRACE_COLS]`` (stats over the FLATTENED
    lane-word buffer the sync exchanges; POP/CHANGED aggregate over all
    lanes).  ``trace=False`` stages the exact uninstrumented program.
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    if cfg.use_pallas:
        raise NotImplementedError(
            "use_pallas=True is single-source only; MS-BFS uses the XLA path"
        )
    bw = lane_words(n_lanes)
    n_rows = wave_rows(pg)
    max_levels = cfg.max_levels if cfg.max_levels is not None else pg.n
    spec = P(cfg.axes if len(cfg.axes) > 1 else cfg.axes[0])
    t_levels = (flightrec.resolve_trace_levels(trace_levels, max_levels)
                if trace else None)

    def body(arrays, roots):
        arrays = jax.tree.map(lambda a: a[0], arrays)
        owned_mask = jnp.arange(pg.vmax, dtype=jnp.int32) < arrays["v_count"]

        lane_ids = jnp.arange(n_lanes, dtype=jnp.int32)
        lane_active = roots >= 0
        seed_rows = jnp.where(lane_active, roots, 0).astype(jnp.int32)
        # one-hot lane masks: row per lane, bit per lane; OR-scattered so
        # duplicate roots compose (two lanes may share a seed vertex).
        onehot = (
            jnp.arange(bw * LANE_BITS, dtype=jnp.int32)[None, :] == lane_ids[:, None]
        ) & lane_active[:, None]
        seen = fr.scatter_or_lanes(n_rows, seed_rows, fr.lane_pack(onehot))

        fmt = LaneFormat(arrays, owned_mask, lane_active, pg.n)
        d_owned = jnp.where(fmt.owned(seen), 0, INF)
        return run_levels(fmt, cfg, seen, seen, d_owned, max_levels,
                          trace=trace, trace_levels=t_levels)

    return loop.jit_shard(body, mesh, graph_array_keys(pg), spec, trace=trace)


def assemble_distances(
    pg: PartitionedGraph, d_owned: np.ndarray, n_lanes: int
) -> np.ndarray:
    """``d_owned [P, vmax, B]`` -> global ``int64[B, n]`` distance matrix
    (row per search lane, INT32_MAX sentinel for unreached)."""
    d_owned = np.asarray(d_owned)
    dist = np.full((n_lanes, pg.n), np.iinfo(np.int32).max, dtype=np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        dist[:, s : s + c] = d_owned[i, :c, :].T
    return dist


def multi_source_bfs(
    pg: PartitionedGraph,
    mesh: jax.sharding.Mesh,
    roots: Sequence[int],
    cfg: BFSConfig = BFSConfig(),
) -> Tuple[np.ndarray, int, float]:
    """End-to-end helper: one wave over ``roots`` (one lane per root).

    Returns ``(dist int64[B, n], levels, scanned)``; ``dist[b]`` matches
    ``bfs_reference(g, roots[b])`` exactly.  ``-1`` marks an inactive lane
    (all-INF row); any other out-of-range root raises.
    """
    roots = np.asarray(roots, dtype=np.int32)
    if roots.ndim != 1 or roots.size < 1:
        raise ValueError("roots must be a non-empty 1-D sequence")
    if np.any((roots < -1) | (roots >= pg.n)):
        raise ValueError(f"root out of range (n={pg.n}, -1=inactive): {roots}")
    arrays = place_arrays(pg, mesh, cfg.axes)
    fn = build_msbfs_fn(pg, mesh, cfg, int(roots.size))
    d_owned, levels, scanned = fn(arrays, jnp.asarray(roots))
    dist = assemble_distances(pg, d_owned, int(roots.size))
    return dist, int(np.max(levels)), float(np.asarray(scanned)[0])
