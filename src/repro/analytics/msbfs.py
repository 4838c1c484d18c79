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

Phase 1 reuses :func:`repro.core.bfs._expand_push` / ``_expand_pull`` with
``lanes=True`` (the push/pull machinery generalized over the lane axis);
phase 2 reuses ``collectives.butterfly_or`` / ``_sparse`` / ``_adaptive``
UNCHANGED on the flattened word buffer.  The whole B-search wave compiles to
ONE XLA program: ``jit(shard_map(lax.while_loop))``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import frontier as fr
from repro.core import loop
from repro.core.bfs import (
    INF,
    BFSConfig,
    _expand_pull,
    _expand_push,
    _sync_frontier,
    graph_array_keys,
    place_arrays,
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
    vmax = pg.vmax
    max_levels = cfg.max_levels if cfg.max_levels is not None else pg.n
    spec = P(cfg.axes if len(cfg.axes) > 1 else cfg.axes[0])
    if trace:
        from repro.core import flightrec

        t_levels = flightrec.resolve_trace_levels(trace_levels, max_levels)

    def body(arrays, roots):
        arrays = jax.tree.map(lambda a: a[0], arrays)
        v_start = arrays["v_start"]
        v_count = arrays["v_count"]
        vown_ids = jnp.arange(vmax, dtype=jnp.int32)
        owned_mask = vown_ids < v_count

        lane_ids = jnp.arange(n_lanes, dtype=jnp.int32)
        lane_active = roots >= 0
        seed_rows = jnp.where(lane_active, roots, 0).astype(jnp.int32)
        # one-hot lane masks: row per lane, bit per lane; OR-scattered so
        # duplicate roots compose (two lanes may share a seed vertex).
        onehot = (
            jnp.arange(bw * LANE_BITS, dtype=jnp.int32)[None, :] == lane_ids[:, None]
        ) & lane_active[:, None]
        seen = fr.scatter_or_lanes(n_rows, seed_rows, fr.lane_pack(onehot))
        frontier = seen

        def owned_lanes(buf):
            win = lax.dynamic_slice(buf, (v_start, 0), (vmax, bw))
            return fr.lane_unpack(win)[:, :n_lanes] & owned_mask[:, None]

        d_owned = jnp.where(owned_lanes(seen), 0, INF)

        if cfg.mode == "bottom_up":
            init_dir = jnp.array(True)
        else:
            init_dir = jnp.array(False)  # False == push

        def cond(state):
            frontier, seen, d_owned, level, scanned, pull = state[:6]
            with loop.phase("cond"):
                return (fr.popcount(frontier) > 0) & (level < max_levels)

        def step(state):
            frontier, seen, d_owned, level, scanned, pull = state[:6]

            # -- Phase 1: lane-parallel traversal ------------------------
            def do_push(_):
                return _expand_push(arrays, frontier, n_rows, False,
                                    lanes=True)[0]

            def do_pull(_):
                return _expand_pull(
                    arrays, frontier, seen, n_rows, False, lanes=True
                )

            with loop.phase("expand"):
                if cfg.mode == "top_down":
                    gq = do_push(None)
                elif cfg.mode == "bottom_up":
                    gq = do_pull(None)
                else:
                    gq = lax.cond(pull, do_pull, do_push, None)

                # edges examined this level, summed over ACTIVE lanes
                # (inactive lanes would otherwise count every vertex as
                # unvisited):
                owned_front = owned_lanes(frontier)
                m_f = (arrays["deg_out"][:, None] * owned_front).sum()
                owned_unvis = (
                    ~fr.lane_unpack(
                        lax.dynamic_slice(seen, (v_start, 0), (vmax, bw))
                    )[:, :n_lanes]
                    & owned_mask[:, None]
                    & lane_active[None, :]
                )
                m_u = (arrays["deg_out"][:, None] * owned_unvis).sum()
                if cfg.mode == "bottom_up":
                    lvl_scanned = m_u
                elif cfg.mode == "top_down":
                    lvl_scanned = m_f
                else:
                    lvl_scanned = jnp.where(pull, m_u, m_f)

            # -- Phase 2: butterfly sync, UNCHANGED on the flat buffer ---
            with loop.phase("exchange"):
                if trace:
                    t_words, t_branch, t_shipped = flightrec.or_sync_stats(
                        gq.reshape(-1), cfg
                    )
                merged = _sync_frontier(gq.reshape(-1), cfg).reshape(
                    n_rows, bw)

            # -- Per-lane enqueue-if-new + level capture -----------------
            with loop.phase("update"):
                new = merged & ~seen
                seen = seen | new
                d_owned = jnp.where(owned_lanes(new), level + 1, d_owned)

            # -- Direction-optimizing switch, wave-aggregated ------------
            if cfg.mode == "direction_optimizing":
                with loop.phase("direction"):
                    g_mf = lax.psum(m_f, cfg.axes)
                    g_mu = lax.psum(m_u, cfg.axes)
                    n_f = fr.popcount(new)
                    active_count = jnp.maximum(
                        lane_active.sum(dtype=jnp.int32), 1
                    )
                    go_pull = g_mf.astype(jnp.float32) > (
                        g_mu.astype(jnp.float32) / cfg.alpha
                    )
                    go_push = n_f.astype(jnp.float32) < (
                        active_count * pg.n / cfg.beta
                    )
                    pull = jnp.where(pull, ~go_push, go_pull)

            out = (
                new,
                seen,
                d_owned,
                level + 1,
                scanned + lvl_scanned.astype(jnp.float32),
                pull,
            )
            if not trace:
                return out, None
            if cfg.mode == "top_down":
                direction = jnp.int32(0)
            elif cfg.mode == "bottom_up":
                direction = jnp.int32(1)
            else:
                direction = state[5].astype(jnp.int32)
            row = flightrec.trace_row(
                level, t_words, fr.popcount(new), direction, t_branch,
                t_shipped, jnp.count_nonzero(new).astype(jnp.int32),
            )
            return out, (level, row)

        init = (
            frontier,
            seen,
            d_owned,
            jnp.int32(0),
            jnp.float32(0),
            init_dir,
        )
        state = loop.traced_while(
            cond, step, init, trace=trace,
            trace_levels=t_levels if trace else None,
        )
        frontier, seen, d_owned, level, scanned, _ = state[:6]
        total_scanned = lax.psum(scanned, cfg.axes)
        out = (d_owned[None], level[None], total_scanned[None])
        if trace:
            out = out + (state[6][None],)
        return out

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
