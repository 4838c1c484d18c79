"""Traversal flight recorder (DESIGN.md §18).

Every level-synchronous traversal in this repo compiles to ONE
``jit(shard_map(lax.while_loop))`` program — which makes it a black box:
nothing records which levels ran sparse, how dense the frontier was, or
where the wire bytes went.  The flight recorder threads a fixed-shape
``int32[trace_levels, TRACE_COLS]`` buffer through the while-loop carry
and writes one row per level:

====  ===========  =====================================================
col   name         meaning
====  ===========  =====================================================
0     LEVEL        1-based level / iteration index (0 = row unwritten)
1     WORDS        densest rank's active-word count of the exchanged
                   buffer (nonzero words for OR syncs, changed-vs-ref
                   words for monoid syncs) — the sparse-dispatch driver
2     POP          bit population of the NEW frontier after the merge
                   (BFS/MS-BFS/BC: vertices discovered this level; SSSP/
                   repair relax: distances improved this iteration)
3     DIR          direction chosen: 0 = push, 1 = pull (repair: 0 =
                   taint phase, 1 = relax phase; SSSP/BC: 0)
4     BRANCH       sync branch taken: 0 dense, 1 sparse, 2 overflow-
                   fallback (dense-family syncs always report 0)
5     SHIPPED      active ``(word, value)`` pairs in the densest rank's
                   compaction when the sparse wire format ran, else 0
6     CHANGED      words the merge actually changed (OR: words gaining
                   bits; MIN: words lowered) — the monoid-changed count
7     EXPAND       phase-1 expansion branch: 1 when single-source
                   top-down pushed the frontier's own out-edges, 0 when
                   it scanned every edge slot (and for every other
                   expansion: pull, lanes, SSSP, BC, programs)
====  ===========  =====================================================

§19 convergence programs (``repro.programs``) share the buffer and
reinterpret the two frontier columns as convergence columns — POP is the
program's PROGRESS measure (pagerank: L1 residual in ppm of total rank
mass; cc: labels changed this round; kcore: vertices peeled this wave;
tri: wedge checks issued) and DIR its phase indicator (kcore: the
current peel threshold ``k``; others 0).  ``VertexProgram.metrics``
documents each program's pair; the schema and byte model are otherwise
identical, so one Perfetto/CLI pipeline reads every algo's trace.

Every cell is replicated across ranks (scalars are ``pmax``-reduced with
the EXACT predicates the collectives dispatch on), so the host reads row
``[0]`` of the ``[P, L, COLS]`` output authoritatively.

Cost contract: all recording is gated behind Python-level ``if trace:``
in the builders — ``trace=False`` traces the byte-identical jaxpr of the
pre-instrumentation program (pinned by the StableHLO fingerprints of
``tests/test_programs.py``), and ``trace=True`` adds only scalar ops + a
handful of scalar ``pmax`` collectives per level (≤ 10 % wall-clock on
kron13/P=8, the acceptance budget).

Host side, :class:`TraversalTrace` turns the raw buffer into per-level
tables, attributes analytic wire bytes per level via the §3/§12 byte
model (reconciled against the compiled HLO through
``launch/hlo_stats.py``), and :func:`timed_bfs_levels` re-runs a BFS one
compiled level-step per host call to attach wall-clock per level (the
fused program's own :func:`repro.core.bfs.level_step`, so its rows are
the fused trace's).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import butterfly
from repro.core import frontier as fr

TRACE_COLS = 8
COL_LEVEL = 0
COL_WORDS = 1
COL_POP = 2
COL_DIR = 3
COL_BRANCH = 4
COL_SHIPPED = 5
COL_CHANGED = 6
COL_EXPAND = 7

COL_NAMES = ("level", "words", "pop", "dir", "branch", "shipped", "changed",
             "expand")

BRANCH_DENSE = 0
BRANCH_SPARSE = 1
BRANCH_FALLBACK = 2

#: Default trace-buffer depth: covers every graph family in the repo
#: (kron/urand diameters are ~10, torus64 ~96, path8k is the pathological
#: tail) without bloating the carry.
DEFAULT_TRACE_LEVELS = 256

TRACE_SCHEMA = "traversal_trace/v1"


def resolve_trace_levels(trace_levels: Optional[int], max_levels: int) -> int:
    """Buffer depth: explicit request wins; otherwise the loop bound capped
    at :data:`DEFAULT_TRACE_LEVELS`.  Levels beyond the buffer still RUN —
    their rows are dropped (``.at[].set(mode="drop")``), never corrupted."""
    if trace_levels is not None:
        if trace_levels < 1:
            raise ValueError(f"trace_levels must be >= 1, got {trace_levels}")
        return int(trace_levels)
    return max(1, min(int(max_levels), DEFAULT_TRACE_LEVELS))


# ---------------------------------------------------------------------------
# In-program helpers (must be called inside shard_map, on the EXACT
# pre-sync buffer the collectives see)
# ---------------------------------------------------------------------------


def _pmax_all(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    for a in axes:
        x = lax.pmax(x, a)
    return x


def or_sync_stats(buf: jax.Array, cfg):
    """``(words, branch, shipped)`` replicated int32 scalars for a bitmap
    OR sync, mirroring ``bfs._sync_frontier``'s dispatch exactly.

    ``cfg`` is a :class:`~repro.core.bfs.BFSConfig` (duck-typed: ``sync``,
    ``axes``, ``resolved_capacity``, ``density_threshold``).  ``buf`` is
    the pre-sync buffer (any shape; flattened like the sync call sites).
    The predicates recompute what the collectives dispatch on —
    ``butterfly_or_adaptive``'s ``(popcount, count_nonzero)`` pair and
    ``butterfly_or_sparse``'s changed-count fallback guard — so BRANCH in
    the trace is the branch the compiled ``lax.cond`` actually took.
    """
    flat = buf.reshape(-1)
    n_words = flat.shape[0]
    nz = _pmax_all(jnp.count_nonzero(flat).astype(jnp.int32), cfg.axes)
    zero = jnp.int32(0)
    if cfg.sync in ("butterfly", "rabenseifner", "all_to_all", "xla"):
        return nz, zero, zero
    cap = cfg.resolved_capacity(n_words)
    if cfg.sync == "sparse":
        ok = nz <= cap
        branch = jnp.where(ok, BRANCH_SPARSE, BRANCH_FALLBACK).astype(jnp.int32)
        return nz, branch, jnp.where(ok, nz, zero)
    if cfg.sync == "adaptive":
        pops = _pmax_all(fr.popcount(flat), cfg.axes)
        bits_limit = jnp.int32(cfg.density_threshold * n_words * fr.WORD_BITS)
        go_sparse = (pops <= bits_limit) & (nz <= cap)
        branch = go_sparse.astype(jnp.int32)  # BRANCH_SPARSE == 1
        return nz, branch, jnp.where(go_sparse, nz, zero)
    raise ValueError(f"unknown sync {cfg.sync!r}")


def monoid_sync_stats(new: jax.Array, prev: jax.Array, cfg, capacity: int):
    """``(words, branch, shipped)`` for a monoid distance sync, mirroring
    ``sssp._sync_dist``'s dispatch (``cfg`` is an ``SSSPConfig``;
    ``capacity`` the build-time resolved capacity the sync was given)."""
    flat_new = new.reshape(-1)
    flat_prev = prev.reshape(-1)
    n_words = flat_new.shape[0]
    changed = _pmax_all(fr.changed_count(flat_new, flat_prev), cfg.axes)
    zero = jnp.int32(0)
    if cfg.sync in ("butterfly", "all_to_all", "xla"):
        return changed, zero, zero
    cap = min(int(capacity), n_words)
    if cfg.sync == "sparse":
        ok = changed <= cap
        branch = jnp.where(ok, BRANCH_SPARSE, BRANCH_FALLBACK).astype(jnp.int32)
        return changed, branch, jnp.where(ok, changed, zero)
    if cfg.sync == "adaptive":
        words_limit = jnp.int32(cfg.density_threshold * n_words)
        go_sparse = (changed <= words_limit) & (changed <= cap)
        branch = go_sparse.astype(jnp.int32)
        return changed, branch, jnp.where(go_sparse, changed, zero)
    raise ValueError(f"unknown sync {cfg.sync!r}")


def trace_row(level, words, pop, direction, branch, shipped, changed,
              expand=0):
    """Assemble one ``int32[TRACE_COLS]`` row (LEVEL is stored 1-based so a
    zero LEVEL cell marks an unwritten row)."""
    cols = (words, pop, direction, branch, shipped, changed, expand)
    return jnp.stack([jnp.asarray(level, jnp.int32) + 1]
                     + [jnp.asarray(c, jnp.int32) for c in cols])


def record(tbuf: jax.Array, index, row: jax.Array) -> jax.Array:
    """Write ``row`` at ``index``; out-of-buffer levels drop silently."""
    return tbuf.at[index].set(row, mode="drop")


def zeros(trace_levels: int) -> jax.Array:
    return jnp.zeros((trace_levels, TRACE_COLS), jnp.int32)


# ---------------------------------------------------------------------------
# Host-side trace object
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraversalTrace:
    """Per-level flight-recorder table of one traversal.

    ``data`` is the trimmed ``int32[levels, TRACE_COLS]`` buffer (see the
    module docstring for columns).  ``n_words`` / ``capacity`` describe the
    EXCHANGED buffer (the flattened word count the sync ran over), which is
    what the byte attribution is computed against.  ``wall_ms`` is per-level
    wall-clock when the trace came from :func:`timed_bfs_levels`.

    Byte attribution covers the level's FRONTIER/DISTANCE sync; BC's
    additional dense sigma/delta ADD all-reduce per level is a constant
    dense buffer and is reported in ``summary()['extra_dense_syncs']``
    rather than folded into per-level branch attribution.
    """

    algo: str
    sync: str
    p: int
    fanout: int
    n_words: int
    capacity: int
    density_threshold: float = 0.02
    data: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, TRACE_COLS), np.int32)
    )
    wall_ms: Optional[np.ndarray] = None

    @classmethod
    def from_buffer(
        cls,
        buf,
        *,
        algo: str,
        sync: str,
        p: int,
        fanout: int,
        n_words: int,
        capacity: int,
        density_threshold: float = 0.02,
        wall_ms=None,
    ) -> "TraversalTrace":
        """Build from the raw program output (``[P, L, COLS]`` — row [0] is
        authoritative, every cell is replicated — or ``[L, COLS]``),
        trimming unwritten rows (LEVEL cell 0)."""
        buf = np.asarray(buf)
        if buf.ndim == 3:
            buf = buf[0]
        if buf.ndim != 2 or buf.shape[1] != TRACE_COLS:
            raise ValueError(f"expected [levels, {TRACE_COLS}] buffer, "
                             f"got shape {buf.shape}")
        data = buf[buf[:, COL_LEVEL] > 0].astype(np.int32)
        if wall_ms is not None:
            wall_ms = np.asarray(wall_ms, dtype=np.float64)[: data.shape[0]]
        return cls(
            algo=algo, sync=sync, p=int(p), fanout=int(fanout),
            n_words=int(n_words), capacity=int(capacity),
            density_threshold=float(density_threshold),
            data=data, wall_ms=wall_ms,
        )

    @property
    def levels(self) -> int:
        return int(self.data.shape[0])

    def word_density(self) -> np.ndarray:
        """Active-word fraction of the exchanged buffer per level."""
        return self.data[:, COL_WORDS].astype(np.float64) / max(self.n_words, 1)

    # -- analytic byte attribution (§3/§12 model) --------------------------

    def _dense_bytes_per_node(self) -> float:
        nbytes = self.n_words * 4
        if self.sync == "rabenseifner":
            return float(butterfly.bytes_per_node_rabenseifner(
                self.p, self.fanout, nbytes
            ))
        if self.sync == "all_to_all":
            return float((self.p - 1) * nbytes)
        if self.sync == "xla":
            # compiler-scheduled all-reduce: standard ring estimate
            return 2.0 * nbytes * (self.p - 1) / max(self.p, 1)
        return float(butterfly.bytes_per_node_allreduce(
            self.p, self.fanout, nbytes
        ))

    def _sparse_bytes_per_node(self) -> float:
        return float(butterfly.bytes_per_node_sparse(
            self.p, self.fanout, self.capacity, self.n_words
        ))

    def level_bytes_per_node(self) -> np.ndarray:
        """Wire bytes per node per level from the analytic model: sparse
        levels pay the §12 capacity-growth schedule, dense and
        overflow-fallback levels the full-buffer butterfly (the fallback
        predicate fires BEFORE any compaction ships, so a fallback level
        costs exactly a dense level)."""
        dense = self._dense_bytes_per_node()
        sparse = self._sparse_bytes_per_node()
        branch = self.data[:, COL_BRANCH]
        return np.where(branch == BRANCH_SPARSE, sparse, dense)

    def level_table(self) -> List[Dict]:
        """One dict per level — the human-facing flight log."""
        bytes_per_node = self.level_bytes_per_node()
        density = self.word_density()
        out = []
        for i in range(self.levels):
            row = {name: int(self.data[i, c])
                   for c, name in enumerate(COL_NAMES)}
            row["density"] = float(density[i])
            row["bytes_per_node"] = float(bytes_per_node[i])
            if self.wall_ms is not None and i < self.wall_ms.size:
                row["wall_ms"] = float(self.wall_ms[i])
            out.append(row)
        return out

    def summary(self) -> Dict:
        branch = self.data[:, COL_BRANCH]
        out = {
            "algo": self.algo,
            "sync": self.sync,
            "p": self.p,
            "fanout": self.fanout,
            "n_words": self.n_words,
            "capacity": self.capacity,
            "levels": self.levels,
            "total_pop": int(self.data[:, COL_POP].sum()),
            "dense_levels": int((branch == BRANCH_DENSE).sum()),
            "sparse_levels": int((branch == BRANCH_SPARSE).sum()),
            "fallback_levels": int((branch == BRANCH_FALLBACK).sum()),
            "pull_levels": int((self.data[:, COL_DIR] == 1).sum()),
            "sparse_push_levels": int((self.data[:, COL_EXPAND] == 1).sum()),
            "bytes_per_node_total": float(self.level_bytes_per_node().sum()),
        }
        if self.algo == "bc":
            # the per-level dense sigma ADD all-reduce rides on top of the
            # frontier sync (one per forward level, one per backward level)
            out["extra_dense_syncs"] = 2 * self.levels
        if self.wall_ms is not None:
            out["wall_ms_total"] = float(self.wall_ms.sum())
        return out

    def to_dict(self) -> Dict:
        """JSON-ready form (``BENCH_bfs.json`` / ``--trace`` payloads)."""
        return {
            "schema": TRACE_SCHEMA,
            **self.summary(),
            "per_level": self.level_table(),
        }


def trace_chrome_doc(trace: TraversalTrace) -> Dict:
    """Render one :class:`TraversalTrace` as a Perfetto/Chrome
    ``trace_event`` document (``repro.core.tracing`` timebase, one
    ``traversal`` track).  Levels with host-measured wall clock
    (:func:`timed_bfs_levels`) become duration spans laid end to end;
    without wall clock each level is an instant — durations are never
    fabricated."""
    from repro.core import tracing

    tracer = tracing.Tracer(clock=lambda: 0.0)
    t = 0.0
    branch_names = {BRANCH_DENSE: "dense", BRANCH_SPARSE: "sparse",
                    BRANCH_FALLBACK: "fallback"}
    for row in trace.level_table():
        name = (f"L{row['level']} {branch_names[row['branch']]}"
                f"{' pull' if row['dir'] else ''}"
                f"{' sparse-push' if row['expand'] else ''}")
        if "wall_ms" in row:
            dur = row["wall_ms"] / 1e3
            tracer.add_span(name, t, t + dur, track="traversal",
                            cat=trace.algo, args=row)
            t += dur
        else:
            tracer.instant(name, track="traversal", cat=trace.algo,
                           args=row, t=float(row["level"]) * 1e-3)
    doc = tracer.to_chrome()
    doc["otherData"] = {"schema": TRACE_SCHEMA, **trace.summary()}
    return doc


# ---------------------------------------------------------------------------
# HLO reconciliation (launch/hlo_stats.py)
# ---------------------------------------------------------------------------


def reconcile_bytes(trace: TraversalTrace, hlo_text: str) -> Dict:
    """Check the trace's analytic per-level byte attribution against the
    COMPILED program's branch-attributed collective bytes.

    For an ``adaptive`` program the dispatch ``lax.cond`` carries the
    heaviest collective traffic of any conditional in the module; its
    branch 0 (the False path — dense) must carry exactly the model's
    dense bytes/node in ``collective-permute`` wire bytes and branch 1
    (sparse) exactly the §12 capacity schedule.  For an unconditional
    dense program one level body's permute bytes are compared (a level
    loop with one inner loop per expansion branch holds one copy of the
    exchange in each).
    Returns ``{"model": {...}, "hlo": {...}, "matches": bool}``.
    """
    from repro.launch import hlo_stats

    model = {"dense": trace._dense_bytes_per_node(),
             "sparse": trace._sparse_bytes_per_node()}
    out: Dict = {"model": model, "hlo": {}, "matches": False}
    if trace.sync == "adaptive":
        conds = hlo_stats.conditional_branch_stats(hlo_text)
        scored = [
            (sum(st["collective-permute"]["wire_bytes"] for _, st in branches),
             branches)
            for branches in conds if len(branches) == 2
        ]
        if not scored:
            return out
        _, branches = max(scored, key=lambda t: t[0])
        hlo_dense = branches[0][1]["collective-permute"]["wire_bytes"]
        hlo_sparse = branches[1][1]["collective-permute"]["wire_bytes"]
        out["hlo"] = {"dense": hlo_dense, "sparse": hlo_sparse}
        out["matches"] = (
            hlo_dense == model["dense"] and hlo_sparse == model["sparse"]
        )
        return out
    hlo_dense = max(
        (st["collective-permute"]["wire_bytes"]
         for _, st in hlo_stats.level_body_collective_stats(hlo_text)),
        default=0.0)
    out["hlo"] = {"dense": hlo_dense}
    out["matches"] = hlo_dense == model["dense"]
    return out


# ---------------------------------------------------------------------------
# Convenience drivers
# ---------------------------------------------------------------------------


def traced_bfs(pg, mesh, root: int, cfg, *, trace_levels: Optional[int] = None):
    """End-to-end single-source BFS with the flight recorder on.

    Returns ``(dist int64[n], levels, scanned, TraversalTrace)`` — the
    first three exactly as :func:`repro.core.bfs.distributed_bfs`.
    """
    from repro.core import bfs as bfs_mod

    arrays = bfs_mod.place_arrays(pg, mesh, cfg.axes)
    fn = bfs_mod.build_bfs_fn(pg, mesh, cfg, trace=True,
                              trace_levels=trace_levels)
    d_owned, levels, scanned, tbuf = fn(arrays, jnp.int32(root))
    return (bfs_mod.assemble_distances(pg, d_owned), int(np.max(levels)),
            float(np.asarray(scanned)[0]), _bfs_trace(pg, cfg, tbuf))


def _bfs_trace(pg, cfg, buf, wall_ms=None) -> TraversalTrace:
    """The :class:`TraversalTrace` of a single-source BFS's rows."""
    return TraversalTrace.from_buffer(
        buf, algo="bfs", sync=cfg.sync, p=pg.p, fanout=cfg.fanout,
        n_words=pg.n_words, capacity=cfg.resolved_capacity(pg.n_words),
        density_threshold=cfg.density_threshold, wall_ms=wall_ms,
    )


def build_bfs_level_fn(pg, mesh, cfg):
    """One compiled BFS LEVEL step (host-driven segmented execution).

    ``run(arrays, frontier, visited, d_owned, level, pull)`` advances the
    traversal exactly one level and returns
    ``(new_frontier, visited, d_owned, pull, row)`` where ``row`` is the
    flight-recorder ``int32[P, TRACE_COLS]`` row for that level.  Frontier
    and visited bitmaps are replicated; ``d_owned`` is per-device.  The
    step is the fused program's own :func:`repro.core.bfs.level_step`, so
    the per-level results, trace rows included, are the fused program's;
    only the host sync between levels (what buys the wall-clock) differs.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core import bfs as bfs_mod

    spec = P(cfg.axes if len(cfg.axes) > 1 else cfg.axes[0])
    if cfg.use_pallas:
        raise NotImplementedError(
            "host-timed segmented execution uses the XLA frontier path"
        )

    def body(arrays, frontier_words, visited, d_owned, level, pull):
        arrays = jax.tree.map(lambda a: a[0], arrays)
        owned_mask = jnp.arange(pg.vmax, dtype=jnp.int32) < arrays["v_count"]
        fmt = bfs_mod.VertexFormat(arrays, owned_mask, pg.n, cfg.axes)
        state = (frontier_words, visited, d_owned[0], level, jnp.float32(0),
                 pull)
        out, (_, row) = bfs_mod.level_step(fmt, cfg, state, trace=True)
        new, visited, d_owned, _, _, next_pull = out[:6]
        return new, visited, d_owned[None], next_pull, row[None]

    graph = {k: spec for k in bfs_mod.graph_array_keys(pg)}
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(graph, P(), P(), spec, P(), P()),
        out_specs=(P(), P(), spec, P(), spec), check_vma=False))


def timed_bfs_levels(
    pg, mesh, cfg, root: int, *, arrays=None,
    trace_levels: Optional[int] = None, warmup: bool = True,
):
    """Host-timed segmented BFS: one compiled level step per host call,
    ``block_until_ready`` + wall-clock around each.

    Returns ``(dist int64[n], TraversalTrace)`` with ``wall_ms`` filled.
    The distances are bit-exact vs the fused program; the wall-clock adds
    a host-device round trip per level, so treat the per-level times as
    RELATIVE weights (the fused program's total is the honest absolute).
    """
    from repro.core import bfs as bfs_mod

    if arrays is None:
        arrays = bfs_mod.place_arrays(pg, mesh, cfg.axes)
    fn = build_bfs_level_fn(pg, mesh, cfg)
    max_levels = cfg.max_levels if cfg.max_levels is not None else pg.n
    t_levels = resolve_trace_levels(trace_levels, max_levels)

    def init_state():  # fn's (frontier, visited, d_owned, level, pull)
        frontier = np.zeros(pg.n_words, dtype=np.uint32)
        frontier[root >> 5] |= np.uint32(1) << np.uint32(root & 31)
        d_owned = np.full((pg.p, pg.vmax), np.iinfo(np.int32).max, np.int32)
        owner = pg.owner_of(root)
        d_owned[owner, root - int(pg.v_start[owner])] = 0
        return (jnp.asarray(frontier), jnp.asarray(frontier),
                jnp.asarray(d_owned), jnp.int32(0),
                jnp.asarray(cfg.mode == "bottom_up"))

    if warmup:  # compile + first-touch outside the timed loop.  TWO steps:
        # the first call takes uncommitted host arrays, later calls feed
        # back device-committed outputs — distinct specializations, and the
        # steady-state one is the one the timed loop must not compile in.
        f, v, d, p, _ = fn(arrays, *init_state())
        jax.block_until_ready(fn(arrays, f, v, d, jnp.int32(1), p))

    frontier, visited, d_owned, _, pull = init_state()
    rows, walls = [], []
    for level in range(max_levels):
        t0 = time.perf_counter()
        frontier, visited, d_owned, pull, row = fn(
            arrays, frontier, visited, d_owned, jnp.int32(level), pull
        )
        row = np.asarray(jax.block_until_ready(row))[0]
        walls.append((time.perf_counter() - t0) * 1e3)
        rows.append(row)
        if row[COL_POP] == 0:  # frontier exhausted
            break

    buf = np.asarray(rows[:t_levels], dtype=np.int32).reshape(-1, TRACE_COLS)
    return (bfs_mod.assemble_distances(pg, d_owned),
            _bfs_trace(pg, cfg, buf, np.asarray(walls[: len(buf)])))
