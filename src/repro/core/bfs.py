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
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.core import flightrec
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
    """``K``, the edge slots a sparse expansion (the push of the frontier's
    out-edges, the pull of the unvisited vertices' in-edges) reads in one
    level: a thirty-second of a shard's edge slots.  A sparse level costs
    about ``vmax + 3K`` random accesses against the in-edge scan's
    ``emax``."""
    return emax // 32


def pick_expansion(push_demand, pull_demand, capacity):
    """Top-down's expansion branch, as the flight recorder's EXPAND code:
    push when the frontier's out-edge slots fit ``capacity``, else pull
    when the unvisited vertices' in-edge slots fit it, else scan."""
    return jnp.where(
        push_demand <= capacity, flightrec.EXPAND_PUSH,
        jnp.where(pull_demand <= capacity, flightrec.EXPAND_PULL,
                  flightrec.EXPAND_SCAN)).astype(jnp.int32)


def _run_slots(offsets, run, capacity, emax):
    """Lay the vertices' runs of sorted edges end to end in ``capacity``
    slots.  ``offsets`` (``int32[vmax + 1]``) marks each vertex's run in
    an edge array of ``emax`` slots; ``run`` (``int32[vmax]``) is the run's
    length for the vertices laid out, 0 for the others, and the lengths
    fit ``capacity`` together.  Returns ``(ends, edge)``: the inclusive
    prefix sum of ``run`` and, for each slot below ``ends[-1]``, the edge
    it holds (``int32[capacity]``; slots past it hold no run's edge).

    Slot ``s`` in the run of vertex ``v`` holds edge ``s`` plus the edges
    of the vertices before ``v`` that were left out.  Each vertex adds its
    left-out edges at its run's first slot, and a prefix sum over the
    slots hands every slot its offset: no search, no sort."""
    ends = fr._prefix_sum(run, emax)
    left_out = offsets[1:] - offsets[:-1] - run
    slot = jnp.arange(capacity, dtype=jnp.int32)
    edge = slot + fr._prefix_sum(
        fr.scatter_int32(capacity, ends - run, left_out, "add"), emax)
    return ends, edge


def _push_sparse(arrays, run, n_words, n, capacity):
    """Set the out-neighbours of the owned frontier vertices, whose runs of
    (src, dst)-sorted out-edges have lengths ``run`` (``int32[vmax]``, 0
    off the frontier) and fit ``capacity`` slots together; vertex ids are
    below ``n``.  Destinations may be any vertex; phase 2 ORs the nodes'
    bitmaps."""
    ends, edge = _run_slots(arrays["out_offsets"], run, capacity,
                            arrays["edge_dst"].shape[0])
    dst = jnp.take(arrays["edge_dst"], edge, mode="clip")
    slot = jnp.arange(capacity, dtype=jnp.int32)
    hit = fr.scatter_int32(n, jnp.where(slot < ends[-1], dst, n), 1, "max")
    return jnp.pad(fr.pack(hit > 0), (0, n_words - n // fr.WORD_BITS))


def _pull_sparse(arrays, run, frontier_words, capacity):
    """Set the owned unvisited vertices with an in-neighbour in the
    frontier.  Their runs of (dst, src)-sorted in-edges have lengths
    ``run`` (``int32[vmax]``, 0 for every other owned slot) and fit
    ``capacity`` slots together.  The runs' sources are read at the slots
    (:func:`_run_slots`) and each run's frontier bits ORed by one prefix
    count (:func:`fr.segment_or`); the slots past the last run are never
    read.  Only the node's owned window is set, as by the in-edge scan."""
    ends, edge = _run_slots(arrays["in_offsets"], run, capacity,
                            arrays["in_src"].shape[0])
    active = fr.get_bits(frontier_words,
                         jnp.take(arrays["in_src"], edge, mode="clip"))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return fr.segment_or(frontier_words.shape[0], offsets, active,
                         arrays["word_start"])


# ---------------------------------------------------------------------------
# Frontier formats: what one level step needs to know of a frontier buffer
# ---------------------------------------------------------------------------


class VertexFormat:
    """One search: a vertex-packed ``uint32[n_words]`` bitmap over vertex
    ids below ``n``, replicated on every node, of which a node owns the
    ``vmax`` bits at word ``word_start``.

    Besides the level loop's carry it carries the ``(push demand, live,
    pull demand)`` of a frontier (:meth:`carry`, slots 6 to 8): the two
    demands pick the next top-down expansion, ``live`` keeps the loop
    running.  ``pallas`` is ``(meta, interpret)`` for the Pallas frontier
    kernels (a ``BFSPallasLayout``'s meta, interpreter only), ``None`` for
    the XLA expansion.  ``expand`` fixes top-down's expansion branch, an
    EXPAND code of :mod:`repro.core.flightrec` (:meth:`push`).
    """

    def __init__(self, arrays, owned_mask, n, axes, pallas=None, expand=None):
        self.arrays = arrays
        self.owned_mask = owned_mask  # bool[vmax]: the node's real vertices
        self.n = n
        self.axes = axes
        self.pallas = pallas
        self.expand = expand

    def branch(self, expand):
        """This format with top-down's expansion branch fixed."""
        return VertexFormat(self.arrays, self.owned_mask, self.n, self.axes,
                            self.pallas, expand)

    def window(self, buf):
        """``bool[vmax]``: the node's owned bits of ``buf``."""
        words = self.owned_mask.shape[0] // fr.WORD_BITS
        return fr.unpack(lax.dynamic_slice(
            buf, (self.arrays["word_start"],), (words,)))

    def owned(self, buf):
        return self.window(buf) & self.owned_mask

    def unvisited(self, visited):
        return ~self.window(visited) & self.owned_mask

    def runs(self, frontier_words):
        """``int32[vmax]``: each owned frontier vertex's out-edge slots, 0
        for every other owned slot."""
        owned = self.window(frontier_words)
        offsets = self.arrays["out_offsets"]
        return (offsets[1:] - offsets[:-1]) * owned

    def in_runs(self, visited_words):
        """``int32[vmax]``: each owned unvisited vertex's in-edge slots, 0
        for every other owned slot."""
        offsets = self.arrays["in_offsets"]
        return (offsets[1:] - offsets[:-1]) * self.unvisited(visited_words)

    def edges(self, marked):
        """Out-edges of the owned vertices ``marked``."""
        return (self.arrays["deg_out"] * marked).sum()

    def push(self, frontier_words, visited_words):
        """Top-down: propagate frontier bits along the graph's edges (paper
        Alg. 2 phase 1).  Returns the node's 'global queue' bitmap and the
        level's EXPAND code (an int32 scalar).  Three branches, the same on
        every node: ``self.expand`` names one, or, when it is ``None``, the
        two demands (:meth:`carry`) against :func:`push_capacity` pick one
        (:func:`pick_expansion`) with a ``lax.switch``.

        * ``sparse_push``, when no node's owned frontier has more out-edge
          slots than the capacity: push those out-edges
          (:func:`_push_sparse`);
        * ``sparse_pull``, else when no node's owned unvisited vertices
          have more in-edge slots than the capacity: read only those
          in-edges (:func:`_pull_sparse`), Beamer's bottom-up work set
          without the early exit; only the node's owned window is set;
        * ``dense_scan`` otherwise: an owned vertex joins when any of its
          in-edges starts in the frontier, one prefix count over the
          dst-sorted in-edges read at ``in_offsets``
          (:func:`fr.segment_or`), so no per-edge scatter; only the node's
          owned window is set.

        The scan sets visited vertices too; the update masks them.  A
        node on the push and a node on another branch would lose the
        owned destinations of the one whose frontier in-neighbours the
        other owns.  Phase 2 ORs the bitmaps together.  The Pallas kernel
        reads every edge: never sparse.
        """
        arrays, n_words = self.arrays, frontier_words.shape[0]
        if self.pallas is not None:
            from repro.kernels import ops as kops

            meta, interpret = self.pallas
            return kops.expand_push_pallas(
                frontier_words, arrays, meta, n_words, interpret=interpret
            ), jnp.int32(flightrec.EXPAND_SCAN)

        def scan():
            with jax.named_scope("dense_scan"):
                active = fr.get_bits(frontier_words, arrays["in_src"])
                return fr.segment_or(n_words, arrays["in_offsets"], active,
                                     arrays["word_start"])

        capacity = push_capacity(arrays["edge_dst"].shape[0])

        def push():
            with jax.named_scope("sparse_push"):
                return _push_sparse(arrays, self.runs(frontier_words),
                                    n_words, self.n, capacity)

        def pull():
            with jax.named_scope("sparse_pull"):
                return _pull_sparse(arrays, self.in_runs(visited_words),
                                    frontier_words, capacity)

        branches = (scan, push, pull)  # indexed by EXPAND code
        expand = flightrec.EXPAND_SCAN if capacity <= 0 else self.expand
        if expand is not None:
            return branches[expand](), jnp.int32(expand)
        push_demand, _, pull_demand = self.carry(frontier_words, visited_words)
        expand = pick_expansion(push_demand, pull_demand, capacity)
        return lax.switch(expand, branches), expand

    def pull(self, frontier_words, visited_words):
        """Bottom-up: every unvisited owned vertex probes its in-edges for
        a parent in the frontier (Beamer; paper Sec. 3 'Parallelization
        Schemes').  Never pushes."""
        arrays, n_words = self.arrays, frontier_words.shape[0]
        if self.pallas is not None:
            from repro.kernels import ops as kops

            meta, interpret = self.pallas
            return kops.expand_pull_pallas(
                frontier_words, visited_words, arrays, meta, n_words,
                interpret=interpret), jnp.int32(flightrec.EXPAND_SCAN)
        src, dst = arrays["in_src"], arrays["in_dst"]
        mask = jnp.arange(src.shape[0], dtype=jnp.int32) < arrays["in_count"]
        parent_in_frontier = fr.get_bits(frontier_words, src) & mask
        unvisited = ~fr.get_bits(visited_words, dst)
        found = parent_in_frontier & unvisited
        return (fr.scatter_or(n_words, dst, found),
                jnp.int32(flightrec.EXPAND_SCAN))

    def carry(self, frontier_words, visited_words):
        """``(push demand, live, pull demand)``: replicated int32 scalars of
        a frontier and the visited set.  The push demand is the most
        out-edge slots any node's owned frontier vertices have, the pull
        demand the most in-edge slots any node's owned unvisited vertices
        have, which top-down compares with its capacity
        (:func:`pick_expansion`); ``live`` the most frontier vertices any
        node holds.  One ``pmax`` over the axes gives all three, so every
        node takes the same expansion branch, and every node runs another
        level while any node's frontier is live: the nodes' collectives
        pair up even if their frontiers differ."""
        three = jnp.stack([self.runs(frontier_words).sum(),
                           fr.popcount(frontier_words),
                           self.in_runs(visited_words).sum()])
        if self.axes:
            three = lax.pmax(three, self.axes)
        return three[0], three[1], three[2]

    def live(self, state):
        return state[7]

    def searches(self):
        return 1


class LaneFormat:
    """A wave of B searches (``analytics.msbfs``): lane-packed
    ``uint32[n_rows, B/32]`` rows, row ``v`` holding vertex ``v``'s bit in
    each search; a node owns the ``vmax`` rows at ``v_start``.  Only the
    ``lane_active`` lanes count towards the edges a level examines and
    towards Beamer's β; the loop runs while any lane's frontier is live.
    Both expansions read every edge: never sparse."""

    def __init__(self, arrays, owned_mask, lane_active, n):
        self.arrays = arrays
        self.owned_mask = owned_mask  # bool[vmax]: the node's real vertices
        self.lane_active = lane_active  # bool[B]
        self.n = n

    def window(self, buf):
        """``bool[vmax, B]``: the node's owned rows of ``buf``."""
        win = lax.dynamic_slice(buf, (self.arrays["v_start"], 0),
                                (self.owned_mask.shape[0], buf.shape[1]))
        return fr.lane_unpack(win)[:, :self.lane_active.shape[0]]

    def owned(self, buf):
        return self.window(buf) & self.owned_mask[:, None]

    def unvisited(self, visited):
        return (~self.window(visited) & self.owned_mask[:, None]
                & self.lane_active[None, :])

    def edges(self, marked):
        """Out-edges of the owned vertices ``marked``, summed over lanes."""
        return (self.arrays["deg_out"][:, None] * marked).sum()

    def push(self, frontier, visited=None):
        """Top-down: each owned out-edge ORs its source row's lane mask
        into its destination row, one scatter for all B searches
        (``visited`` is not read)."""
        src, dst = self.arrays["edge_src"], self.arrays["edge_dst"]
        mask = (jnp.arange(src.shape[0], dtype=jnp.int32)
                < self.arrays["edge_count"])
        active = jnp.where(mask[:, None], frontier[src], jnp.uint32(0))
        return fr.scatter_or_lanes(frontier.shape[0], dst, active), None

    def pull(self, frontier, visited):
        """Bottom-up, the probe per search lane: a vertex can be settled in
        one search and still pulling in another, all in one bitwise op."""
        src, dst = self.arrays["in_src"], self.arrays["in_dst"]
        mask = (jnp.arange(src.shape[0], dtype=jnp.int32)
                < self.arrays["in_count"])
        parent = jnp.where(mask[:, None], frontier[src], jnp.uint32(0))
        found = parent & ~visited[dst]
        return fr.scatter_or_lanes(frontier.shape[0], dst, found), None

    def carry(self, frontier, visited):
        return ()

    def live(self, state):
        return fr.popcount(state[0])

    def searches(self):
        return jnp.maximum(self.lane_active.sum(dtype=jnp.int32), 1)


# ---------------------------------------------------------------------------
# The level step and the level loop
# ---------------------------------------------------------------------------


def level_step(fmt, cfg: BFSConfig, state, *, trace=False):
    """One BFS level over a frontier in format ``fmt`` (:class:`VertexFormat`
    or :class:`LaneFormat`).

    ``state`` is the level loop's carry ``(frontier, visited, d_owned,
    level, scanned, pull)`` followed by the format's own carry.  Returns
    ``(next_state, rec)``: ``rec`` is ``None``, or with ``trace`` the
    flight-recorder ``(level, row)``.
    """
    frontier, visited, d_owned, level, scanned, pull = state[:6]

    # -- Phase 1: traversal ---------------------------------------------
    with loop.phase("expand"):
        if cfg.mode == "top_down":
            gq, expand = fmt.push(frontier, visited)
        elif cfg.mode == "bottom_up":
            gq, expand = fmt.pull(frontier, visited)
        else:
            gq, expand = lax.cond(
                pull, lambda _: fmt.pull(frontier, visited),
                lambda _: fmt.push(frontier, visited), None)

        # edges examined this level (honest TEPS accounting): a push reads
        # the frontier's out-edges, a pull probes the unvisited vertices'
        m_f = fmt.edges(fmt.owned(frontier))
        m_u = fmt.edges(fmt.unvisited(visited))
        if cfg.mode == "bottom_up":
            lvl_scanned = m_u
        elif cfg.mode == "top_down":
            lvl_scanned = m_f
        else:
            lvl_scanned = jnp.where(pull, m_u, m_f)

    # -- Phase 2: butterfly frontier synchronization, on the flat words ----
    with loop.phase("exchange"):
        if trace:
            t_words, t_branch, t_shipped = flightrec.or_sync_stats(gq, cfg)
        merged = _sync_frontier(gq.reshape(-1), cfg).reshape(gq.shape)

    # -- Update (enqueue-if-new as set ops) -------------------------------
    with loop.phase("update"):
        new = merged & ~visited
        visited = visited | new
        d_owned = jnp.where(fmt.owned(new), level + 1, d_owned)

    # what the next level's expansion and the loop's condition read
    with loop.phase("expand"):
        carried = fmt.carry(new, visited)

    # -- Direction-optimizing switch (Beamer alpha/beta) ------------------
    if cfg.mode == "direction_optimizing":
        with loop.phase("direction"):
            g_mf = lax.psum(m_f, cfg.axes)
            g_mu = lax.psum(m_u, cfg.axes)
            n_f = fr.popcount(new)
            searches = fmt.searches()
            go_pull = (g_mf.astype(jnp.float32)
                       > g_mu.astype(jnp.float32) / cfg.alpha)
            go_push = n_f.astype(jnp.float32) < searches * fmt.n / cfg.beta
            pull = jnp.where(pull, ~go_push, go_pull)

    out = (new, visited, d_owned, level + 1,
           scanned + lvl_scanned.astype(jnp.float32), pull) + carried
    if not trace:
        return out, None
    if cfg.mode == "direction_optimizing":
        direction = state[5].astype(jnp.int32)  # the level's own direction
    else:
        direction = jnp.int32(cfg.mode == "bottom_up")
    row = flightrec.trace_row(
        level, t_words, fr.popcount(new), direction, t_branch, t_shipped,
        jnp.count_nonzero(new).astype(jnp.int32),
        0 if expand is None else expand,  # a lane wave never runs sparse
    )
    return out, (level, row)


def run_levels(fmt, cfg: BFSConfig, frontier, visited, d_owned, max_levels,
               *, trace=False, trace_levels=None, pick=None):
    """The level loop from a seeded ``frontier``: :func:`level_step` while
    ``fmt`` says a frontier is live and fewer than ``max_levels`` levels
    ran.  The first level pushes unless ``cfg.mode`` is bottom-up.

    With ``pick``, top-down levels run as three inner loops, one per
    :meth:`VertexFormat.branch`, ``pick(state)`` naming the one the next
    level runs by its EXPAND code: 0 the in-edge scan, 1 the sparse push,
    2 the sparse pull (:func:`loop.traced_while`).

    Returns the per-device outputs ``(d_owned, levels, edges examined)``,
    each with a leading axis of 1, and with ``trace`` the flight-recorder
    buffer of ``trace_levels`` rows.
    """

    def cond(state):
        with loop.phase("cond"):
            return (fmt.live(state) > 0) & (state[3] < max_levels)

    steps = functools.partial(level_step, fmt, cfg, trace=trace)
    if pick is not None:
        steps = tuple(
            functools.partial(level_step, fmt.branch(expand), cfg, trace=trace)
            for expand in (flightrec.EXPAND_SCAN, flightrec.EXPAND_PUSH,
                           flightrec.EXPAND_PULL))
    with loop.phase("expand"):
        carried = fmt.carry(frontier, visited)
    init = (frontier, visited, d_owned, jnp.int32(0), jnp.float32(0),
            jnp.array(cfg.mode == "bottom_up")) + carried  # False == push
    state = loop.traced_while(cond, steps, init, trace=trace,
                              trace_levels=trace_levels, pick=pick)
    out = (state[2], state[3], lax.psum(state[4], cfg.axes))
    out += (state[-1],) if trace else ()
    return tuple(x[None] for x in out)


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
    vmax = pg.vmax
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
    pallas = (layout.meta, interpret) if cfg.use_pallas else None
    array_keys = graph_array_keys(pg) + (
        tuple(sorted(layout.arrays)) if layout is not None else ()
    )
    t_levels = (flightrec.resolve_trace_levels(trace_levels, max_levels)
                if trace else None)

    def body(arrays, root):
        # [P, ...] -> local [...]  (shard_map gives a leading axis of 1)
        arrays = jax.tree.map(lambda a: a[0], arrays)
        v_start = arrays["v_start"]
        v_count = arrays["v_count"]
        vown_ids = jnp.arange(vmax, dtype=jnp.int32)
        owned_mask = vown_ids < v_count

        visited = fr.set_bit(jnp.zeros((pg.n_words,), jnp.uint32), root)
        d_owned = jnp.full((vmax,), INF, jnp.int32)
        is_owner = (root >= v_start) & (root < v_start + v_count)
        d_owned = jnp.where(is_owner & (vown_ids == root - v_start), 0, d_owned)

        # top-down levels run as three inner loops, one per expansion
        # branch, so that no branch sits in a lax.cond: XLA's memory space
        # assignment prefetches the in-edge scan's bitmap into VMEM in a
        # loop body, and not in a conditional's branch
        capacity = push_capacity(pg.emax)
        pick = None
        if cfg.mode != "bottom_up" and pallas is None and capacity > 0:

            def pick(state):  # the two demands against the capacity
                return pick_expansion(state[6], state[8], capacity)

        fmt = VertexFormat(arrays, owned_mask, pg.n, cfg.axes, pallas)
        return run_levels(fmt, cfg, visited, visited, d_owned, max_levels,
                          trace=trace, trace_levels=t_levels, pick=pick)

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


def assemble_distances(pg: PartitionedGraph, d_owned) -> np.ndarray:
    """``d_owned [P, vmax]`` -> global ``int64[n]`` distances (INT32_MAX
    for unreached)."""
    d_owned = np.asarray(d_owned)
    dist = np.full(pg.n, np.iinfo(np.int32).max, dtype=np.int64)
    for i in range(pg.p):
        s, c = int(pg.v_start[i]), int(pg.v_count[i])
        dist[s : s + c] = d_owned[i, :c]
    return dist


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
    return (assemble_distances(pg, d_owned), int(np.max(levels)),
            float(np.asarray(scanned)[0]))
