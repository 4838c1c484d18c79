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


def push_capacity(emax: int) -> int:
    """``K``, the out-edge slots the sparse push expands in one level: a
    thirty-second of a shard's edge slots.  A sparse level costs about
    ``vmax + 3K`` random accesses against the in-edge scan's ``emax``."""
    return emax // 32


def _push_sparse(arrays, run, n_words, n, capacity):
    """Set the out-neighbours of the owned frontier vertices, whose runs of
    (src, dst)-sorted out-edges have lengths ``run`` (``int32[vmax]``, 0
    off the frontier) and fit ``capacity`` slots together; vertex ids are
    below ``n``.

    Slot ``s`` of the frontier's concatenated runs lies in the run of
    vertex ``v`` and is edge ``s`` plus the out-edges of the vertices
    before ``v`` that were not pushed.  Each vertex adds its unpushed
    out-edges at its run's first slot, and a prefix sum over the slots
    hands every slot its offset: no search, no sort.  Destinations may be
    any vertex; phase 2 ORs the nodes' bitmaps."""
    offsets = arrays["out_offsets"]
    emax = arrays["edge_dst"].shape[0]
    first = fr._prefix_sum(run, emax) - run
    unpushed = offsets[1:] - offsets[:-1] - run
    slot = jnp.arange(capacity, dtype=jnp.int32)
    edge = slot + fr._prefix_sum(
        fr.scatter_int32(capacity, first, unpushed, "add"), emax)
    dst = jnp.take(arrays["edge_dst"], edge, mode="clip")
    hit = fr.scatter_int32(n, jnp.where(slot < run.sum(), dst, n), 1, "max")
    return jnp.pad(fr.pack(hit > 0), (0, n_words - n // fr.WORD_BITS))


def _owned_runs(arrays, frontier_words):
    """``int32[vmax]``: each owned frontier vertex's out-edge slots, 0 for
    every other owned slot."""
    offsets = arrays["out_offsets"]
    vmax = offsets.shape[0] - 1
    owned = fr.unpack(lax.dynamic_slice(
        frontier_words, (arrays["word_start"],), (vmax // fr.WORD_BITS,)))
    return (offsets[1:] - offsets[:-1]) * owned


def frontier_demand(arrays, frontier_words, axes):
    """``(demand, live)``: replicated int32 scalars of a frontier.
    ``demand`` is the most out-edge slots any node's owned frontier
    vertices have, which the sparse push compares with its capacity;
    ``live`` the most frontier vertices any node holds.  One ``pmax`` over
    ``axes`` gives both, so every node takes the same expansion branch, and
    every node runs another level while any node's frontier is live: the
    nodes' collectives pair up even if their frontiers differ."""
    both = jnp.stack([_owned_runs(arrays, frontier_words).sum(),
                      fr.popcount(frontier_words)])
    if axes:
        both = lax.pmax(both, axes)
    return both[0], both[1]


def _expand_push(arrays, frontier_words, n_words, use_pallas, meta=None, *,
                 lanes=False, interpret=False, axes=(), n=None, sparse=None):
    """Top-down: propagate frontier bits along the graph's edges (paper
    Alg. 2 phase 1).  Returns the node's 'global queue' bitmap and whether
    the level pushed the frontier's own out-edges (a bool scalar).

    ``lanes=False``: vertex-packed ``uint32[n_words]`` (single-source)
    over vertex ids below ``n``.  Two branches, the same on every node:
    ``sparse`` (a Python bool) names one, or, when it is ``None``, the
    frontier's :func:`frontier_demand` over ``axes`` against
    :func:`push_capacity` picks one with a ``lax.cond``.

    * ``sparse_push``, when no node's owned frontier has more out-edge
      slots than the capacity: push those out-edges
      (:func:`_push_sparse`);
    * ``dense_scan`` otherwise: an owned vertex joins when any of its
      in-edges starts in the frontier, one prefix count over the
      dst-sorted in-edges read at ``in_offsets`` (:func:`fr.segment_or`),
      so no per-edge scatter; only the node's owned window is set.

    A node on one branch and a node on the other would lose the owned
    destinations of the one whose frontier in-neighbours the other owns.
    Phase 2 ORs the bitmaps together.
    ``lanes=True``: lane-packed ``uint32[n_words, B/32]`` rows — the same
    traversal bit-parallel over B concurrent searches (``analytics.msbfs``),
    where ``n_words`` counts vertex ROWS and merge is a per-row lane-mask OR
    scattered along the owned out-edges.  It and the Pallas path read every
    edge: never sparse.
    """
    full = jnp.array(False)
    if use_pallas:
        if lanes:
            raise NotImplementedError("Pallas frontier kernels are "
                                      "single-source (vertex-packed) only")
        from repro.kernels import ops as kops

        return kops.expand_push_pallas(frontier_words, arrays, meta, n_words,
                                       interpret=interpret), full
    if lanes:
        src, dst = arrays["edge_src"], arrays["edge_dst"]
        mask = jnp.arange(src.shape[0], dtype=jnp.int32) < arrays["edge_count"]
        active = jnp.where(mask[:, None], frontier_words[src], jnp.uint32(0))
        return fr.scatter_or_lanes(n_words, dst, active), full

    def dense(_):
        with jax.named_scope("dense_scan"):
            active = fr.get_bits(frontier_words, arrays["in_src"])
            return fr.segment_or(n_words, arrays["in_offsets"], active,
                                 arrays["word_start"])

    capacity = push_capacity(arrays["edge_dst"].shape[0])

    def push(_):
        with jax.named_scope("sparse_push"):
            return _push_sparse(arrays, _owned_runs(arrays, frontier_words),
                                n_words, n, capacity)

    if capacity <= 0 or sparse is False:
        return dense(None), full
    if sparse:
        return push(None), jnp.array(True)
    go_sparse = frontier_demand(arrays, frontier_words, axes)[0] <= capacity
    return lax.cond(go_sparse, push, dense, None), go_sparse


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
            level, live = state[3], state[7]
            with loop.phase("cond"):
                return (live > 0) & (level < max_levels)

        def step(state, sparse=None):
            frontier_words, visited, d_owned, level, scanned, pull = state[:6]

            # -- Phase 1: traversal -------------------------------------
            def do_push(_):
                return _expand_push(
                    arrays, frontier_words, n_words, cfg.use_pallas, meta,
                    interpret=interpret, axes=cfg.axes, n=pg.n,
                    sparse=sparse,
                )

            def do_pull(_):
                return _expand_pull(
                    arrays, frontier_words, visited, n_words, cfg.use_pallas,
                    meta, interpret=interpret,
                ), jnp.array(False)

            with loop.phase("expand"):
                if cfg.mode == "top_down":
                    gq, pushed = do_push(None)
                elif cfg.mode == "bottom_up":
                    gq, pushed = do_pull(None)
                else:
                    gq, pushed = lax.cond(pull, do_pull, do_push, None)

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

            # the next level's expansion branch, and whether it runs
            with loop.phase("expand"):
                demand, live = frontier_demand(arrays, new, cfg.axes)

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
                demand,
                live,
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
                t_shipped, jnp.count_nonzero(new).astype(jnp.int32), pushed,
            )
            return out, (level, row)

        with loop.phase("expand"):
            demand, live = frontier_demand(arrays, frontier_words, cfg.axes)
        init = (
            frontier_words,
            visited,
            d_owned,
            jnp.int32(0),
            jnp.float32(0),
            init_dir,
            demand,
            live,
        )
        # top-down levels run as two inner loops, one per expansion
        # branch, so that neither branch sits in a lax.cond: XLA's memory
        # space assignment prefetches the in-edge scan's bitmap into VMEM
        # in a loop body, and not in a conditional's branch
        capacity = push_capacity(pg.emax)
        steps, pick = step, None
        if cfg.mode != "bottom_up" and not cfg.use_pallas and capacity > 0:
            steps = (functools.partial(step, sparse=True),
                     functools.partial(step, sparse=False))

            def pick(state):  # 0: the sparse push, 1: the in-edge scan
                return (state[6] > capacity).astype(jnp.int32)
        state = loop.traced_while(
            cond, steps, init, trace=trace,
            trace_levels=t_levels if trace else None, pick=pick,
        )
        frontier_words, visited, d_owned, level, scanned, _ = state[:6]
        total_scanned = lax.psum(scanned, cfg.axes)
        out = (d_owned[None], level[None], total_scanned[None])
        if trace:
            out = out + (state[-1][None],)
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
    "out_offsets",
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
