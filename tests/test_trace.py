"""Flight-recorder invariants (DESIGN.md §18, the in-program trace).

Three properties the recorder must keep forever:

1. **observer effect = zero on results** — ``trace=True`` returns
   bit-exact distances/levels/scanned vs the uninstrumented program;
2. **zero cost when off** — ``trace=False`` stages a program whose
   lowered HLO carries no trace buffer at all (recording is Python-gated,
   not ``lax.cond``-gated);
3. **the log is self-consistent** — per-level POP sums to the reached
   count minus the root, levels are consecutive from 1, dense levels ship
   zero sparse pairs, and the analytic byte attribution reconciles
   EXACTLY against the compiled HLO's collective bytes.

Tier-1 runs a two-graph slice; the full family × sync sweep and the
multi-algorithm (SSSP / MS-BFS) exactness checks are tier-2.
"""

import json
import os

import numpy as np
import pytest

from repro.core import bfs, flightrec
from repro.core.flightrec import (
    BRANCH_DENSE,
    COL_BRANCH,
    COL_LEVEL,
    COL_POP,
    COL_SHIPPED,
    COL_WORDS,
)
from repro.core.tracing import validate_schema
from repro.graph import generators, partition

INF32 = np.iinfo(np.int32).max
SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "trace_schema.json")

GRAPHS = {
    "kron10": lambda: generators.kronecker(10, 8, seed=1),
    "urand": lambda: generators.uniform_random(600, 3000, seed=2),
    "torus": lambda: generators.torus_2d(20),
    "path": lambda: generators.path_graph(200),
}


def _schema():
    with open(SCHEMA_PATH) as f:
        return json.load(f)


def _check_invariants(trace, dist, levels):
    """The §18 self-consistency contract for a single-source BFS trace."""
    data = trace.data
    assert trace.levels == levels
    # levels are consecutive 1..L (no dropped or duplicated rows)
    assert data[:, COL_LEVEL].tolist() == list(range(1, levels + 1))
    # every vertex the traversal reached was logged at exactly one level
    reached = int(np.sum(dist < INF32))
    assert int(data[:, COL_POP].sum()) == reached - 1  # root pre-seeded
    # per-level POP is positive while the traversal is running; only the
    # final level may log 0 (the termination-detection round that found
    # the frontier drained)
    assert (data[:-1, COL_POP] > 0).all()
    # active words never exceed the exchanged buffer
    assert (data[:, COL_WORDS] >= 0).all()
    assert (data[:, COL_WORDS] <= trace.n_words).all()
    # dense levels ship no sparse pairs; shipped never exceeds capacity
    dense = data[:, COL_BRANCH] == BRANCH_DENSE
    assert (data[dense, COL_SHIPPED] == 0).all()
    assert (data[:, COL_SHIPPED] <= trace.capacity).all()
    # derived views stay in range
    assert ((trace.word_density() >= 0) & (trace.word_density() <= 1)).all()
    assert (trace.level_bytes_per_node() > 0).all()
    if trace.sync == "butterfly":
        assert dense.all()  # pure-dense program never takes a sparse branch


def _run_pair(g, sync, root=3, fanout=4):
    mesh = _mesh8()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync=sync, fanout=fanout)
    d0, lv0, sc0 = bfs.distributed_bfs(pg, mesh, root, cfg)
    d1, lv1, sc1, trace = flightrec.traced_bfs(pg, mesh, root, cfg)
    return (d0, lv0, sc0), (d1, lv1, sc1), trace


def _mesh8():
    import jax

    return jax.make_mesh(
        (8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )


@pytest.mark.parametrize("name", ["kron10", "torus"])
@pytest.mark.parametrize("sync", ["butterfly", "adaptive"])
def test_trace_bit_exact_and_self_consistent(name, sync):
    (d0, lv0, sc0), (d1, lv1, sc1), trace = _run_pair(GRAPHS[name](), sync)
    np.testing.assert_array_equal(d0, d1)  # the recorder never perturbs
    assert lv0 == lv1 and sc0 == sc1
    _check_invariants(trace, d1, lv1)


@pytest.mark.tier2
@pytest.mark.parametrize("name", list(GRAPHS))
@pytest.mark.parametrize("sync,fanout", [("butterfly", 1), ("butterfly", 4),
                                         ("adaptive", 4)])
def test_trace_sweep_bit_exact_and_self_consistent(name, sync, fanout):
    (d0, lv0, sc0), (d1, lv1, sc1), trace = _run_pair(
        GRAPHS[name](), sync, fanout=fanout
    )
    np.testing.assert_array_equal(d0, d1)
    assert lv0 == lv1 and sc0 == sc1
    _check_invariants(trace, d1, lv1)


def test_trace_false_stages_uninstrumented_hlo():
    """``trace=False`` must lower to a program with no trace buffer —
    identical to never importing flightrec.  (Byte-identity vs the actual
    pre-§18 seed was verified at integration time; this regression guards
    the Python-gating so the buffer can never leak into the default
    path.)"""
    import jax

    mesh = _mesh8()
    g = GRAPHS["torus"]()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=4)
    arrays = bfs.place_arrays(pg, mesh, cfg.axes)
    root = np.int32(3)

    text_off = bfs.build_bfs_fn(pg, mesh, cfg).lower(arrays, root).as_text()
    text_off2 = (
        bfs.build_bfs_fn(pg, mesh, cfg, trace=False)
        .lower(arrays, root)
        .as_text()
    )
    assert text_off == text_off2  # default IS trace=False, deterministically

    t_levels = flightrec.resolve_trace_levels(None, pg.n)
    buf_shape = f"{t_levels}x{flightrec.TRACE_COLS}xi32"
    assert buf_shape not in text_off  # no trace tensor anywhere in the HLO
    text_on = (
        bfs.build_bfs_fn(pg, mesh, cfg, trace=True)
        .lower(arrays, root)
        .as_text()
    )
    assert buf_shape in text_on  # ... and the instrumented program has it


def test_reconcile_bytes_matches_compiled_hlo():
    """The analytic per-level byte attribution must equal the compiled
    program's branch-attributed collective-permute wire bytes EXACTLY —
    the §3/§12 model is machine-checked, not estimated."""
    import jax  # noqa: F401

    mesh = _mesh8()
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    for sync in ("adaptive", "butterfly"):
        cfg = bfs.BFSConfig(axes=("data",), sync=sync, fanout=4)
        arrays = bfs.place_arrays(pg, mesh, cfg.axes)
        fn = bfs.build_bfs_fn(pg, mesh, cfg, trace=True)
        hlo = fn.lower(arrays, np.int32(3)).compile().as_text()
        _, _, _, trace = flightrec.traced_bfs(pg, mesh, 3, cfg)
        rec = flightrec.reconcile_bytes(trace, hlo)
        assert rec["matches"], rec


def test_timed_bfs_levels_exact_and_timed():
    mesh = _mesh8()
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=4)
    d_ref, lv_ref, _ = bfs.distributed_bfs(pg, mesh, 3, cfg)
    dist, trace = flightrec.timed_bfs_levels(pg, mesh, cfg, 3)
    np.testing.assert_array_equal(d_ref, dist)  # segmented == fused
    assert trace.levels == lv_ref
    assert trace.wall_ms is not None and trace.wall_ms.size == trace.levels
    assert (trace.wall_ms > 0).all()
    summ = trace.summary()
    assert summ["wall_ms_total"] == pytest.approx(float(trace.wall_ms.sum()))
    for row in trace.level_table():
        assert row["wall_ms"] > 0

    # the Perfetto rendering of a timed trace is spans laid end to end
    doc = flightrec.trace_chrome_doc(trace)
    assert validate_schema(doc, _schema()) == []
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == trace.levels
    assert doc["otherData"]["schema"] == flightrec.TRACE_SCHEMA


@pytest.mark.parametrize("mode,sync,alpha,beta,seed", [
    ("direction_optimizing", "butterfly", 2.0, 18.0, 3),
    ("direction_optimizing", "butterfly", 1.0, 4.0, 2),
    ("top_down", "adaptive", 15.0, 18.0, 3),
])
def test_timed_bfs_levels_replays_the_fused_trace(mode, sync, alpha, beta,
                                                  seed):
    """The host-segmented level step runs the fused program's traversal:
    every column of every flight-recorder row (Beamer's direction and the
    expansion branch included) and the distances agree."""
    import jax

    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    pg = partition.partition_1d(generators.kronecker(12, 16, seed=seed), 4)
    cfg = bfs.BFSConfig(axes=("data",), sync=sync, mode=mode, alpha=alpha,
                        beta=beta)
    d_fused, _, _, fused = flightrec.traced_bfs(pg, mesh, 0, cfg)
    d_seg, seg = flightrec.timed_bfs_levels(pg, mesh, cfg, 0)
    np.testing.assert_array_equal(d_seg, d_fused)
    assert seg.data.shape == fused.data.shape
    for col, name in enumerate(flightrec.COL_NAMES):
        np.testing.assert_array_equal(seg.data[:, col], fused.data[:, col],
                                      err_msg=name)


def test_untimed_trace_renders_as_instants():
    mesh = _mesh8()
    g = GRAPHS["torus"]()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync="butterfly", fanout=4)
    _, lv, _, trace = flightrec.traced_bfs(pg, mesh, 3, cfg)
    doc = flightrec.trace_chrome_doc(trace)
    assert validate_schema(doc, _schema()) == []
    instants = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert len(instants) == lv  # no wall clock -> never invent durations
    assert not any(e.get("ph") == "X" for e in doc["traceEvents"])


def test_trace_to_dict_is_json_ready():
    mesh = _mesh8()
    g = GRAPHS["torus"]()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=4)
    _, _, _, trace = flightrec.traced_bfs(pg, mesh, 3, cfg)
    doc = json.loads(json.dumps(trace.to_dict()))
    assert doc["schema"] == flightrec.TRACE_SCHEMA
    assert doc["levels"] == len(doc["per_level"])
    assert doc["dense_levels"] + doc["sparse_levels"] + \
        doc["fallback_levels"] == doc["levels"]


@pytest.mark.tier2
def test_msbfs_trace_is_bit_exact():
    from repro.analytics import msbfs as ms

    mesh = _mesh8()
    g = GRAPHS["kron10"]()
    pg = partition.partition_1d(g, 8)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=4)
    arrays = bfs.place_arrays(pg, mesh, cfg.axes)
    roots = np.asarray([3, 5, 9, -1], dtype=np.int32)
    base = ms.build_msbfs_fn(pg, mesh, cfg, 4)(arrays, roots)
    traced = ms.build_msbfs_fn(pg, mesh, cfg, 4, trace=True)(arrays, roots)
    for a, b in zip(base, traced[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tbuf = np.asarray(traced[3])
    trace = flightrec.TraversalTrace.from_buffer(
        tbuf, algo="msbfs", sync=cfg.sync, p=pg.p, fanout=cfg.fanout,
        n_words=ms.wave_rows(pg) * ms.lane_words(4),
        capacity=cfg.resolved_capacity(ms.wave_rows(pg) * ms.lane_words(4)),
    )
    assert trace.levels == int(np.max(np.asarray(traced[1])))
    assert (trace.data[:, COL_LEVEL] == np.arange(1, trace.levels + 1)).all()


@pytest.mark.tier2
def test_sssp_trace_is_bit_exact():
    from repro.traversal import sssp as ss

    mesh = _mesh8()
    g = generators.kronecker(10, 8, seed=1, max_weight=15)
    pg = partition.partition_1d(g, 8)
    cfg = ss.SSSPConfig(axes=("data",))
    arrays = ss.place_arrays(pg, mesh, cfg.axes)
    root = np.int32(3)
    base = ss.build_sssp_fn(pg, mesh, cfg)(arrays, root)
    traced = ss.build_sssp_fn(pg, mesh, cfg, trace=True)(arrays, root)
    for a, b in zip(base, traced[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tbuf = np.asarray(traced[3])
    n_rows = ss.dist_rows(pg)
    trace = flightrec.TraversalTrace.from_buffer(
        tbuf, algo="sssp", sync=cfg.sync, p=pg.p, fanout=cfg.fanout,
        n_words=n_rows, capacity=cfg.resolved_capacity(n_rows),
    )
    assert trace.levels == int(np.max(np.asarray(traced[1])))
    assert (trace.data[:, COL_LEVEL] == np.arange(1, trace.levels + 1)).all()
