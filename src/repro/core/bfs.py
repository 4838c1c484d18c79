"""ButterFly BFS (paper Alg. 2) — distributed breadth-first search in JAX.

Structure mirrors the paper exactly:

* **Phase 1 — traversal** (per compute node, here: per TPU chip): expand the
  current frontier over the node's owned edges.  Both *top-down* (push) and
  *bottom-up* (pull) formulations are implemented, plus Beamer's
  direction-optimizing switch — the paper's Contribution 3 is that the
  communication pattern is independent of the traversal direction, and it is
  here: both feed the same phase-2 merge.
* **Phase 2 — butterfly frontier synchronization**: the per-node "global
  queue" (a packed bitmap, DESIGN.md Sec. 3) is OR-merged across nodes with
  the butterfly network of :mod:`repro.core.collectives` (configurable
  fanout), or with the paper's all-to-all baseline for comparison.

The whole traversal (level loop included) compiles to ONE XLA program:
``jit(shard_map(...))`` with a ``lax.while_loop`` over levels.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.core import frontier as fr
from repro.core import loop
from repro.graph.csr import Graph
from repro.graph.partition import PartitionedGraph

INF = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# Host oracle (paper Alg. 1 semantics)
# ---------------------------------------------------------------------------


def bfs_reference(g: Graph, root: int) -> np.ndarray:
    """Level-synchronous frontier BFS over the host CSR — the ground truth
    for every test.  Each level gathers the out-edges of the whole frontier
    at once (vectorised over the frontier, no per-vertex Python loop)."""
    d = np.full(g.n, np.iinfo(np.int32).max, dtype=np.int64)
    d[root] = 0
    frontier = np.array([root])
    level = 0
    while frontier.size:
        starts = g.row_offsets[frontier]
        counts = g.row_offsets[frontier + 1] - starts
        before = np.cumsum(counts) - counts
        edge = np.repeat(starts - before, counts) + np.arange(counts.sum())
        nbrs = g.dst[edge]
        frontier = np.unique(nbrs[d[nbrs] > level + 1])
        d[frontier] = level + 1
        level += 1
    return d


# ---------------------------------------------------------------------------
# Distributed ButterFly BFS
# ---------------------------------------------------------------------------


MODES = ("top_down", "bottom_up", "direction_optimizing")
SYNCS = ("butterfly", "sparse", "adaptive", "rabenseifner", "all_to_all", "xla")


@dataclasses.dataclass(frozen=True)
class BFSConfig:
    """Algorithm knobs (paper Sec. 3/4)."""

    axes: Tuple[str, ...] = ("data",)
    fanout: int = 2  # paper fanout: 1 -> pairwise, 4 -> radix-4 rounds
    # butterfly | sparse | adaptive | rabenseifner | all_to_all | xla
    sync: str = "butterfly"
    mode: str = "top_down"  # top_down | bottom_up | direction_optimizing
    alpha: float = 15.0  # Beamer push->pull threshold
    beta: float = 18.0  # Beamer pull->push threshold
    max_levels: Optional[int] = None
    # frontier kernels via Pallas (interpret mode only: the TPU compiler
    # refuses them, see kernels.ops.PALLAS_REFUSED) vs XLA ops
    use_pallas: bool = False
    # --- sparse/adaptive sync knobs (DESIGN.md §12) -----------------------
    # max (word_index, word) pairs shipped in the first sparse round;
    # 0 -> auto-size to n_words // 64 (>= 64) at build time.
    sparse_capacity: int = 0
    # adaptive dispatch: go sparse while the densest rank's popcount stays
    # under this fraction of the bitmap bits (and its word count fits the
    # capacity).
    density_threshold: float = 0.02

    def __post_init__(self):
        # Fail at construction, not at trace time: an unknown mode used to
        # fall through to direction_optimizing silently.
        if self.mode not in MODES:
            raise ValueError(
                f"unknown BFS mode {self.mode!r}; expected one of {MODES}"
            )
        if self.sync not in SYNCS:
            raise ValueError(
                f"unknown frontier sync {self.sync!r}; expected one of {SYNCS}"
            )

    def resolved_capacity(self, n_words: int) -> int:
        cap = self.sparse_capacity or max(64, n_words // 64)
        return min(cap, n_words)


def _sync_frontier(words: jax.Array, cfg: BFSConfig) -> jax.Array:
    if cfg.sync == "butterfly":
        return collectives.butterfly_or(words, cfg.axes, fanout=cfg.fanout)
    if cfg.sync == "sparse":
        # always-sparse wire format, dense fallback only on overflow
        return collectives.butterfly_or_sparse(
            words, cfg.axes, fanout=cfg.fanout,
            capacity=cfg.resolved_capacity(words.shape[0]),
        )
    if cfg.sync == "adaptive":
        # per-level dense/sparse dispatch keyed on frontier density
        return collectives.butterfly_or_adaptive(
            words, cfg.axes, fanout=cfg.fanout,
            capacity=cfg.resolved_capacity(words.shape[0]),
            density_threshold=cfg.density_threshold,
        )
    if cfg.sync == "rabenseifner":
        # beyond-paper: OR-reduce-scatter + all-gather on the same wiring —
        # 2(P-1)/P of the bitmap per node vs log_f(P) full-bitmap ships
        return collectives.butterfly_allreduce_rabenseifner(
            words, cfg.axes, fanout=cfg.fanout, op="or"
        )
    if cfg.sync == "all_to_all":
        return collectives.all_to_all_merge(words, cfg.axes, op="or")
    if cfg.sync == "xla":
        return collectives.xla_allreduce(words, cfg.axes, op="or")
    raise ValueError(f"unknown sync {cfg.sync!r}")


def _expand_push(arrays, frontier_words, n_words, use_pallas, meta=None, *,
                 lanes=False, interpret=False):
    """Top-down: propagate frontier bits along the graph's edges (paper
    Alg. 2 phase 1).  Returns the node's 'global queue' bitmap.

    ``lanes=False``: vertex-packed ``uint32[n_words]`` (single-source).  An
    owned vertex joins when any of its in-edges starts in the frontier: one
    prefix count over the dst-sorted in-edges, read at ``in_offsets``
    (:func:`fr.segment_or`), so no per-edge scatter.  Only the node's owned
    window is set; phase 2 ORs the windows together.
    ``lanes=True``: lane-packed ``uint32[n_words, B/32]`` rows — the same
    traversal bit-parallel over B concurrent searches (``analytics.msbfs``),
    where ``n_words`` counts vertex ROWS and merge is a per-row lane-mask OR
    scattered along the owned out-edges.
    """
    if use_pallas:
        if lanes:
            raise NotImplementedError("Pallas frontier kernels are "
                                      "single-source (vertex-packed) only")
        from repro.kernels import ops as kops

        return kops.expand_push_pallas(frontier_words, arrays, meta, n_words,
                                       interpret=interpret)
    if not lanes:
        active = fr.get_bits(frontier_words, arrays["in_src"])
        return fr.segment_or(n_words, arrays["in_offsets"], active,
                             arrays["word_start"])
    src, dst = arrays["edge_src"], arrays["edge_dst"]
    mask = jnp.arange(src.shape[0], dtype=jnp.int32) < arrays["edge_count"]
    active = jnp.where(mask[:, None], frontier_words[src], jnp.uint32(0))
    return fr.scatter_or_lanes(n_words, dst, active)


def _expand_pull(arrays, frontier_words, visited_words, n_words, use_pallas,
                 meta=None, *, lanes=False, interpret=False):
    """Bottom-up: every unvisited owned vertex probes its in-edges for a
    parent in the frontier (Beamer; paper Sec. 3 'Parallelization Schemes').
    ``lanes=True`` runs the probe per search lane: a vertex can be settled
    in one search and still pulling in another, all in one bitwise op."""
    if use_pallas:
        if lanes:
            raise NotImplementedError("Pallas frontier kernels are "
                                      "single-source (vertex-packed) only")
        from repro.kernels import ops as kops

        return kops.expand_pull_pallas(frontier_words, visited_words, arrays,
                                       meta, n_words, interpret=interpret)
    src, dst = arrays["in_src"], arrays["in_dst"]
    mask = jnp.arange(src.shape[0], dtype=jnp.int32) < arrays["in_count"]
    if lanes:
        parent = jnp.where(mask[:, None], frontier_words[src], jnp.uint32(0))
        found = parent & ~visited_words[dst]
        return fr.scatter_or_lanes(n_words, dst, found)
    parent_in_frontier = fr.get_bits(frontier_words, src) & mask
    unvisited = ~fr.get_bits(visited_words, dst)
    found = parent_in_frontier & unvisited
    return fr.scatter_or(n_words, dst, found)


def build_bfs_fn(
    pg: PartitionedGraph, mesh: jax.sharding.Mesh, cfg: BFSConfig, layout=None,
    *, trace: bool = False, trace_levels: Optional[int] = None,
    interpret: bool = False,
):
    """Compile-ready distributed BFS.

    Returns ``run(arrays, root)`` where ``arrays`` is ``pg.arrays()`` placed
    on ``mesh`` (leading [P] axis sharded over ``cfg.axes``) and ``root`` a
    replicated int32 scalar.  Output: per-device owned distances
    ``int32[P, vmax]`` (INF for unreached), levels executed, and the number
    of edges examined (for honest TEPS, paper Sec. 2 metric discussion).

    ``trace=True`` threads a §18 flight-recorder buffer through the level
    loop and appends an ``int32[P, trace_levels, TRACE_COLS]`` output (row
    [0] authoritative — every cell is replicated; see
    :mod:`repro.core.flightrec`).  ``trace=False`` stages the EXACT
    uninstrumented program — all recording is Python-gated, so the jaxpr
    (hence the compiled HLO) is byte-identical to the pre-§18 seed.

    ``cfg.use_pallas`` runs the Pallas frontier kernels, which only the
    Pallas interpreter executes: pass ``interpret=True``.  On a mesh of TPU
    devices it raises, naming the lowerings the chip's compiler refuses.
    """
    n_words = pg.n_words
    vmax = pg.vmax
    wmax = pg.wmax
    max_levels = cfg.max_levels if cfg.max_levels is not None else pg.n
    spec = P(cfg.axes if len(cfg.axes) > 1 else cfg.axes[0])
    if cfg.use_pallas and mesh.devices.flat[0].platform == "tpu":
        from repro.kernels import ops as kops

        raise NotImplementedError(
            "use_pallas=True does not compile for TPU: " + kops.PALLAS_REFUSED
            + ". Use the XLA frontier path (use_pallas=False)."
        )
    if cfg.use_pallas and layout is None:
        raise ValueError("use_pallas=True requires a BFSPallasLayout")
    meta = layout.meta if layout is not None else None
    array_keys = graph_array_keys(pg) + (
        tuple(sorted(layout.arrays)) if layout is not None else ()
    )
    if trace:
        from repro.core import flightrec

        t_levels = flightrec.resolve_trace_levels(trace_levels, max_levels)

    def body(arrays, root):
        # [P, ...] -> local [...]  (shard_map gives a leading axis of 1)
        arrays = jax.tree.map(lambda a: a[0], arrays)
        v_start = arrays["v_start"]
        v_count = arrays["v_count"]
        word_start = arrays["word_start"]
        vown_ids = jnp.arange(vmax, dtype=jnp.int32)
        owned_mask = vown_ids < v_count

        visited = jnp.zeros((n_words,), jnp.uint32)
        visited = fr.set_bit(visited, root)
        frontier_words = visited
        d_owned = jnp.full((vmax,), INF, jnp.int32)
        is_owner = (root >= v_start) & (root < v_start + v_count)
        d_owned = jnp.where(
            is_owner & (vown_ids == root - v_start), 0, d_owned
        )

        if cfg.mode == "top_down":
            init_dir = jnp.array(False)  # False == push
        elif cfg.mode == "bottom_up":
            init_dir = jnp.array(True)
        else:
            init_dir = jnp.array(False)

        def cond(state):
            frontier_words, visited, d_owned, level, scanned, pull = state[:6]
            with loop.phase("cond"):
                return (fr.popcount(frontier_words) > 0) & (level < max_levels)

        def step(state):
            frontier_words, visited, d_owned, level, scanned, pull = state[:6]

            # -- Phase 1: traversal -------------------------------------
            def do_push(_):
                return _expand_push(
                    arrays, frontier_words, n_words, cfg.use_pallas, meta,
                    interpret=interpret,
                )

            def do_pull(_):
                return _expand_pull(
                    arrays, frontier_words, visited, n_words, cfg.use_pallas,
                    meta, interpret=interpret,
                )

            with loop.phase("expand"):
                if cfg.mode == "top_down":
                    gq = do_push(None)
                elif cfg.mode == "bottom_up":
                    gq = do_pull(None)
                else:
                    gq = lax.cond(pull, do_pull, do_push, None)

                # edges examined this level (honest TEPS accounting):
                owned_front = fr.unpack(
                    lax.dynamic_slice(frontier_words, (word_start,), (wmax,))
                )[:vmax] & owned_mask
                m_f = (arrays["deg_out"] * owned_front).sum()
                owned_unvis = (
                    ~fr.unpack(lax.dynamic_slice(visited, (word_start,),
                                                 (wmax,)))[:vmax]
                ) & owned_mask
                m_u = (arrays["deg_out"] * owned_unvis).sum()
                if cfg.mode == "bottom_up":
                    lvl_scanned = m_u  # pull probes unvisited in-edges
                elif cfg.mode == "top_down":
                    lvl_scanned = m_f
                else:
                    lvl_scanned = jnp.where(pull, m_u, m_f)

            # -- Phase 2: butterfly frontier synchronization -------------
            with loop.phase("exchange"):
                if trace:
                    t_words, t_branch, t_shipped = flightrec.or_sync_stats(
                        gq, cfg)
                merged = _sync_frontier(gq, cfg)

            # -- Update (enqueue-if-new as set ops) -----------------------
            with loop.phase("update"):
                new = merged & ~visited
                visited = visited | new
                owned_new = fr.unpack(
                    lax.dynamic_slice(new, (word_start,), (wmax,))
                )[:vmax] & owned_mask
                d_owned = jnp.where(owned_new, level + 1, d_owned)

            # -- Direction-optimizing switch (Beamer alpha/beta) ----------
            if cfg.mode == "direction_optimizing":
                with loop.phase("direction"):
                    g_mf = lax.psum(m_f, cfg.axes)
                    g_mu = lax.psum(m_u, cfg.axes)
                    n_f = fr.popcount(new)
                    go_pull = g_mf.astype(jnp.float32) > (
                        g_mu.astype(jnp.float32) / cfg.alpha
                    )
                    go_push = n_f.astype(jnp.float32) < (pg.n / cfg.beta)
                    pull = jnp.where(pull, ~go_push, go_pull)

            out = (
                new,
                visited,
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
                direction = state[5].astype(jnp.int32)  # level's own dir
            row = flightrec.trace_row(
                level, t_words, fr.popcount(new), direction, t_branch,
                t_shipped, jnp.count_nonzero(new).astype(jnp.int32),
            )
            return out, (level, row)

        init = (
            frontier_words,
            visited,
            d_owned,
            jnp.int32(0),
            jnp.float32(0),
            init_dir,
        )
        state = loop.traced_while(
            cond, step, init, trace=trace,
            trace_levels=t_levels if trace else None,
        )
        frontier_words, visited, d_owned, level, scanned, _ = state[:6]
        total_scanned = lax.psum(scanned, cfg.axes)
        out = (d_owned[None], level[None], total_scanned[None])
        if trace:
            out = out + (state[6][None],)
        return out

    return loop.jit_shard(body, mesh, array_keys, spec, trace=trace)


_ARRAY_KEYS = (
    "v_start",
    "v_count",
    "word_start",
    "edge_src",
    "edge_dst",
    "edge_count",
    "in_src",
    "in_dst",
    "in_count",
    "deg_out",
    "in_offsets",
)


def graph_array_keys(pg) -> Tuple[str, ...]:
    """Keys of the placed graph pytree: the base BFS arrays plus, for
    weighted partitions, the edge-weight planes (every traversal driver's
    ``in_specs`` must mirror what :func:`place_arrays` ships)."""
    if getattr(pg, "edge_weight", None) is not None:
        return _ARRAY_KEYS + ("edge_weight", "in_weight")
    return _ARRAY_KEYS


def place_arrays(
    pg: PartitionedGraph, mesh: jax.sharding.Mesh, axes, layout=None
) -> dict:
    """Device-put the stacked partition arrays, [P] axis sharded over axes."""
    spec = P(axes if len(axes) > 1 else axes[0])
    sharding = jax.sharding.NamedSharding(mesh, spec)
    arrays = dict(pg.arrays())
    if layout is not None:
        arrays.update(layout.arrays)
    return {k: jax.device_put(v, sharding) for k, v in arrays.items()}


def distributed_bfs(
    pg: PartitionedGraph,
    mesh: jax.sharding.Mesh,
    root: int,
    cfg: BFSConfig = BFSConfig(),
    *,
    interpret: bool = False,
) -> Tuple[np.ndarray, int, float]:
    """End-to-end helper: place arrays, run, assemble global distances.
    ``interpret`` is :func:`build_bfs_fn`'s (Pallas path only)."""
    layout = None
    if cfg.use_pallas:
        from repro.kernels import blocks

        layout = blocks.build_bfs_layout(pg)
    arrays = place_arrays(pg, mesh, cfg.axes, layout)
    fn = build_bfs_fn(pg, mesh, cfg, layout, interpret=interpret)
    d_owned, levels, scanned = fn(arrays, jnp.int32(root))
    d_owned = np.asarray(d_owned)
    levels = int(np.max(levels))
    dist = np.full(pg.n, np.iinfo(np.int32).max, dtype=np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        dist[s : s + c] = d_owned[i, :c]
    return dist, levels, float(np.asarray(scanned)[0])
