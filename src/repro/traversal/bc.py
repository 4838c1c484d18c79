"""Distributed Brandes betweenness centrality on the MS-BFS bit-lanes.

Brandes (2001) decomposes betweenness into per-source *dependencies*:

  ``BC(v) = sum_s delta_s(v)``,
  ``delta_s(v) = sum_{w: succ} sigma_s(v)/sigma_s(w) * (1 + delta_s(w))``

Riding the §13 lane machinery, B sources run concurrently (DESIGN.md §14):

* **Forward wave** — the lane-packed frontier expands exactly like MS-BFS
  (phase 1 push + phase 2 butterfly OR), while per-lane shortest-path
  counts ``sigma[v, b]`` accumulate: each rank sums ``sigma[u]`` over its
  OWNED in-edges ``(u -> v)`` with ``u`` in the frontier and ``v`` newly
  reached, and the disjoint partial sums merge with a butterfly ADD-reduce
  (the non-idempotent monoid rides the dense exchange).  Per-lane levels
  are captured en route.
* **Backward replay** — levels run in reverse: each rank scores its OWNED
  out-edges ``(u -> w)`` with ``lvl[u] == L-1`` and ``lvl[w] == L`` as
  ``sigma[u]/sigma[w] * (1 + delta[w])``, scatter-adds into ``delta[u]``,
  and the partials merge with the same butterfly ADD-reduce.  No per-level
  frontier history is stored — the level array IS the replay index.

Forward and backward together compile to ONE XLA program:
``jit(shard_map(lax.while_loop))`` twice inside one ``shard_map`` body.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.core import frontier as fr
from repro.core import loop
from repro.core.bfs import (
    INF,
    BFSConfig,
    _sync_frontier,
    LaneFormat,
    graph_array_keys,
    place_arrays,
)
from repro.graph.csr import Graph
from repro.graph.partition import PartitionedGraph
from repro.analytics.msbfs import lane_words, wave_rows


# ---------------------------------------------------------------------------
# Host oracle (Brandes)
# ---------------------------------------------------------------------------


def bc_reference(g: Graph, sources: Sequence[int]) -> np.ndarray:
    """Host Brandes over the given sources — ground truth for every BC test.

    Unnormalized directed-pair accumulation (each ordered pair ``(s, t)``
    contributes once); on the symmetric graphs the ETL produces this is 2x
    the undirected convention, matching the distributed path exactly.
    Returns ``float64[n]``.
    """
    bc = np.zeros(g.n, dtype=np.float64)
    offs, dst = g.row_offsets, g.dst
    for s in sources:
        s = int(s)
        sigma = np.zeros(g.n)
        sigma[s] = 1.0
        d = np.full(g.n, -1, dtype=np.int64)
        d[s] = 0
        order = [s]
        frontier = [s]
        while frontier:
            nxt = []
            for u in frontier:
                for v in dst[offs[u] : offs[u + 1]]:
                    if d[v] < 0:
                        d[v] = d[u] + 1
                        nxt.append(int(v))
            for u in frontier:
                for v in dst[offs[u] : offs[u + 1]]:
                    if d[v] == d[u] + 1:
                        sigma[v] += sigma[u]
            order.extend(nxt)
            frontier = nxt
        delta = np.zeros(g.n)
        for u in reversed(order):
            for v in dst[offs[u] : offs[u + 1]]:
                if d[v] == d[u] + 1:
                    delta[u] += sigma[u] / sigma[v] * (1.0 + delta[v])
        delta[s] = 0.0
        bc += delta
    return bc


# ---------------------------------------------------------------------------
# Distributed BC
# ---------------------------------------------------------------------------


def _sync_add(buf: jax.Array, cfg: BFSConfig) -> jax.Array:
    """ADD all-reduce of per-rank partial sums.  ADD is not idempotent, so
    the sparse changed-word wire format does not apply — sparse/adaptive
    configs ride the dense butterfly here while their frontier OR-sync
    stays sparse (DESIGN.md §14)."""
    if cfg.sync == "all_to_all":
        return collectives.all_to_all_merge(buf, cfg.axes, op="add")
    if cfg.sync == "xla":
        return lax.psum(buf, cfg.axes)
    if cfg.sync == "rabenseifner":
        return collectives.butterfly_allreduce_rabenseifner(
            buf, cfg.axes, fanout=cfg.fanout
        )
    return collectives.butterfly_allreduce(buf, cfg.axes, fanout=cfg.fanout)


def build_bc_fn(
    pg: PartitionedGraph, mesh: jax.sharding.Mesh, cfg: BFSConfig,
    n_lanes: int, *, trace: bool = False, trace_levels=None,
):
    """Compile-ready B-lane betweenness centrality.

    Returns ``run(arrays, roots)`` where ``roots`` is a replicated
    ``int32[n_lanes]`` (``-1`` = inactive lane).  Output: per-device owned
    dependency sums ``float32[P, vmax]`` (the BC contribution of this
    wave's sources, root rows excluded per lane), wave depth ``int32[P]``,
    and edges examined ``float32[P]``.

    ``trace=True`` appends the §18 flight-recorder buffer for the FORWARD
    wave's frontier OR sync (the backward replay makes no sparse/direction
    decisions — it re-walks the recorded levels with the dense ADD merge,
    one extra dense sync per level, which ``TraversalTrace.summary()``
    reports as ``extra_dense_syncs``).  ``trace=False`` stages the exact
    uninstrumented program.
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    if cfg.mode != "top_down":
        raise NotImplementedError(
            "betweenness centrality uses the push traversal; build the "
            "config with mode='top_down'"
        )
    if cfg.use_pallas:
        raise NotImplementedError(
            "use_pallas=True is single-source only; BC uses the XLA path"
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
        onehot = (
            jnp.arange(bw * fr.WORD_BITS, dtype=jnp.int32)[None, :]
            == lane_ids[:, None]
        ) & lane_active[:, None]
        seen0 = fr.scatter_or_lanes(n_rows, seed_rows, fr.lane_pack(onehot))
        fmt = LaneFormat(arrays, owned_mask, lane_active, pg.n)

        sigma0 = jnp.zeros((n_rows, n_lanes), jnp.float32).at[
            seed_rows, lane_ids
        ].add(lane_active.astype(jnp.float32))
        lvl0 = jnp.full((n_rows, n_lanes), INF, jnp.int32).at[
            seed_rows, lane_ids
        ].min(jnp.where(lane_active, 0, INF))

        isrc, idst = arrays["in_src"], arrays["in_dst"]
        imask = jnp.arange(isrc.shape[0], dtype=jnp.int32) < arrays["in_count"]
        osrc, odst = arrays["edge_src"], arrays["edge_dst"]
        omask = jnp.arange(osrc.shape[0], dtype=jnp.int32) < arrays["edge_count"]

        def lanes_of(buf_rows):
            return fr.lane_unpack(buf_rows)[..., :n_lanes]

        # ---- forward wave: frontier expansion + sigma accumulation ------
        def fcond(state):
            frontier, seen, lvl, sigma, level, scanned = state[:6]
            return (fr.popcount(frontier) > 0) & (level < max_levels)

        def fstep(state):
            frontier, seen, lvl, sigma, level, scanned = state[:6]

            gq, _ = fmt.push(frontier)
            if trace:
                t_words, t_branch, t_shipped = flightrec.or_sync_stats(
                    gq.reshape(-1), cfg
                )
            merged = _sync_frontier(gq.reshape(-1), cfg).reshape(n_rows, bw)
            new = merged & ~seen

            # sigma increments over OWNED in-edges u -> v (v newly reached,
            # u in the closing level's frontier); partial sums are disjoint
            # across ranks, so one ADD all-reduce finalizes the level.
            u_front = lanes_of(frontier[isrc])
            v_new = lanes_of(new[idst])
            contrib = jnp.where(
                u_front & v_new & imask[:, None], sigma[isrc], 0.0
            )
            partial = jnp.zeros((n_rows, n_lanes), jnp.float32).at[idst].add(
                contrib
            )
            sigma = sigma + _sync_add(
                partial.reshape(-1), cfg
            ).reshape(n_rows, n_lanes)

            lvl = jnp.where(lanes_of(new), level + 1, lvl)

            # edges examined: out-degree of owned frontier rows, per lane
            m_f = fmt.edges(fmt.owned(frontier))

            out = (
                new,
                seen | new,
                lvl,
                sigma,
                level + 1,
                scanned + m_f.astype(jnp.float32),
            )
            if not trace:
                return out, None
            row = flightrec.trace_row(
                level, t_words, fr.popcount(new), jnp.int32(0), t_branch,
                t_shipped, jnp.count_nonzero(new).astype(jnp.int32),
            )
            return out, (level, row)

        finit = (seen0, seen0, lvl0, sigma0, jnp.int32(0), jnp.float32(0))
        fstate = loop.traced_while(
            fcond, fstep, finit, trace=trace,
            trace_levels=t_levels if trace else None,
        )
        _, _, lvl, sigma, depth, scanned = fstate[:6]

        # ---- backward replay: dependency accumulation, deepest first ----
        sig_src = sigma[osrc]
        sig_dst = jnp.maximum(sigma[odst], 1.0)  # reached => sigma >= 1
        lvl_src = lvl[osrc]
        lvl_dst = lvl[odst]

        def bcond(state):
            delta, level = state
            return level >= 1

        def bstep(state):
            delta, level = state
            on_dag = (
                (lvl_src == level - 1) & (lvl_dst == level) & omask[:, None]
            )
            c = jnp.where(
                on_dag, sig_src / sig_dst * (1.0 + delta[odst]), 0.0
            )
            partial = jnp.zeros((n_rows, n_lanes), jnp.float32).at[osrc].add(c)
            inc = _sync_add(partial.reshape(-1), cfg).reshape(n_rows, n_lanes)
            return (delta + inc, level - 1), None

        delta0 = jnp.zeros((n_rows, n_lanes), jnp.float32)
        delta, _ = loop.traced_while(bcond, bstep, (delta0, depth))

        # a source never scores its own lane (Brandes excludes s)
        delta = delta.at[seed_rows, lane_ids].set(0.0)
        bc_owned = lax.dynamic_slice(delta, (v_start, 0), (vmax, n_lanes)).sum(
            axis=1
        )
        total_scanned = lax.psum(scanned, cfg.axes)
        out = (bc_owned[None], depth[None], total_scanned[None])
        if trace:
            out = out + (fstate[6][None],)
        return out

    return loop.jit_shard(body, mesh, graph_array_keys(pg), spec, trace=trace)


def assemble_bc(pg: PartitionedGraph, bc_owned: np.ndarray) -> np.ndarray:
    """``bc_owned [P, vmax]`` -> global ``float64[n]``."""
    bc_owned = np.asarray(bc_owned)
    out = np.zeros(pg.n, dtype=np.float64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        out[s : s + c] = bc_owned[i, :c]
    return out


def betweenness_centrality(
    pg: PartitionedGraph,
    mesh: jax.sharding.Mesh,
    sources: Sequence[int],
    cfg: BFSConfig = BFSConfig(),
) -> Tuple[np.ndarray, int, float]:
    """End-to-end helper: one wave over ``sources`` (one lane per source).

    Returns ``(bc float64[n], depth, scanned)``; ``bc`` matches
    :func:`bc_reference` over the same sources.  ``-1`` marks an inactive
    lane; any other out-of-range source raises.
    """
    sources = np.asarray(sources, dtype=np.int32)
    if sources.ndim != 1 or sources.size < 1:
        raise ValueError("sources must be a non-empty 1-D sequence")
    if np.any((sources < -1) | (sources >= pg.n)):
        raise ValueError(
            f"source out of range (n={pg.n}, -1=inactive): {sources}"
        )
    arrays = place_arrays(pg, mesh, cfg.axes)
    fn = build_bc_fn(pg, mesh, cfg, int(sources.size))
    bc_owned, depth, scanned = fn(arrays, jnp.asarray(sources))
    return (
        assemble_bc(pg, bc_owned),
        int(np.max(depth)),
        float(np.asarray(scanned)[0]),
    )
