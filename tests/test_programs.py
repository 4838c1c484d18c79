"""Vertex programs: one gather-apply-scatter core serving PageRank, CC,
triangle counting, and k-core (DESIGN.md §19).

Tier-1 covers: every program bit-exact (PageRank: documented float
tolerance — the stopping rule bounds distance-to-fixed-point by
``tol/(1-damping)``) against hand-rolled host oracles across graph
family × sync (dense butterfly / sparse / adaptive) × P; the PageRank
delta-shipping dichotomy (sparse wire BIT-IDENTICAL to the dense reduce,
on both the dense-fallback and the genuinely-sparse regimes); the engine
program cache + stats counters; end-to-end service queries with
root normalization and result caching; §16 mutation survival via
incremental re-push; §18 convergence trace rows through the schema gate;
and the shared while-loop builder's HLO fingerprints (the satellite-1
refactor must not change what XLA compiles).  The kron12/P=8 performance
bars (re-push ≥3× recompute, sparse k-core wire win) run under ``tier2``
off the ``vertex_program`` benchmark rows.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bfs, flightrec
from repro.core import monoid as mono
from repro.dynamic import delta
from repro.graph import generators, partition
from repro import programs
from repro.programs import ProgramConfig

try:
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # deterministic slices below still run
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed"
)

SYNCS = ("butterfly", "sparse", "adaptive")
RESULT_S = 120.0
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "hlo_fingerprints.json")

# PageRank stopping rule: L1 residual < tol implies distance to the fixed
# point < tol * damping / (1 - damping); double it for float32 round-trip
PR_TOL = 1e-5
PR_SLACK = 2 * PR_TOL * 0.85 / 0.15


def _mesh(p):
    return jax.make_mesh((p,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


_GRAPHS = {
    "kron8": lambda: generators.kronecker(8, 8, seed=3),
    "torus16": lambda: generators.torus_2d(16),
}

_cache = {}


def _run(family, algo, sync, p, **cfg_kw):
    """One compiled run per (family, algo, sync, p) across the module —
    the sweep and the bit-identity tests share outputs."""
    key = (family, algo, sync, p, tuple(sorted(cfg_kw.items())))
    if key not in _cache:
        g = _GRAPHS[family]()
        pg = partition.partition_1d(g, p)
        cfg = ProgramConfig(sync=sync, tol=PR_TOL, **cfg_kw)
        res, iters, work = programs.run_program(
            pg, _mesh(p), programs.by_name(algo), cfg
        )
        _cache[key] = (g, res, iters, work)
    return _cache[key]


_ORACLES = {
    "cc": lambda g: programs.cc_reference(g),
    "tri": lambda g: programs.triangles_reference(g),
    "kcore": lambda g: programs.kcore_reference(g),
}


def _check_oracle(g, algo, res):
    if algo == "pagerank":
        ref = programs.pagerank_reference(g, damping=0.85, tol=1e-12,
                                          max_iters=1000)
        np.testing.assert_allclose(res[: g.n], ref, atol=PR_SLACK, rtol=0)
        assert abs(res[: g.n].sum() - 1.0) < 1e-4  # rank mass conserved
    else:
        want = _ORACLES[algo](g)
        np.testing.assert_array_equal(res[: g.n], want)


# --- oracle sweep: family x sync x P ---------------------------------------


@pytest.mark.parametrize("family", sorted(_GRAPHS))
@pytest.mark.parametrize("sync", SYNCS)
@pytest.mark.parametrize("algo", programs.PROGRAM_ALGOS)
def test_program_matches_oracle_p8(family, algo, sync):
    g, res, iters, work = _run(family, algo, sync, 8)
    _check_oracle(g, algo, res)
    assert iters >= 1 and work > 0


@pytest.mark.parametrize("family", sorted(_GRAPHS))
@pytest.mark.parametrize("algo", programs.PROGRAM_ALGOS)
def test_program_matches_oracle_p2_adaptive(family, algo):
    """P=2 exercises the single-stage butterfly (fanout >= P collapses to
    one exchange hop) — the degenerate cube the sweep above never hits."""
    g, res, _, _ = _run(family, algo, "adaptive", 2)
    _check_oracle(g, algo, res)


def test_triangle_total_is_global_invariant():
    g, res, _, _ = _run("kron8", "tri", "butterfly", 8)
    per_vertex = programs.triangles_reference(g)
    assert programs.total_triangles(res) == programs.total_triangles(
        per_vertex
    )


# --- the delta dichotomy: PageRank sparse wire == dense reduce, bitwise ----


@pytest.mark.parametrize("family", sorted(_GRAPHS))
@pytest.mark.parametrize("sync", ("sparse", "adaptive"))
def test_pagerank_delta_bit_identical_to_dense(family, sync):
    """The first non-idempotent monoid on the sparse path: each rank ships
    its own ADD contribution against ``ref=None`` and the butterfly
    delivers every subcube partial exactly once — so the float sums
    associate IDENTICALLY and the result is bit-equal to the dense
    reduce, not merely close."""
    _, dense, _, _ = _run(family, "pagerank", "butterfly", 8)
    _, other, _, _ = _run(family, "pagerank", sync, 8)
    assert np.array_equal(
        dense.astype(np.float32).view(np.uint32),
        other.astype(np.float32).view(np.uint32),
    )


def test_pagerank_bit_identity_survives_genuine_sparse_branch():
    """A near-empty graph under an explicit capacity keeps the sparse sync
    on its compacted wire format (no dense fallback) — the regime where a
    REMERGE-style merge of an ADD buffer would double-count."""
    from repro.graph import csr

    n = 1024
    src = np.array([1, 50, 200, 700, 900])
    dst = np.array([2, 51, 201, 701, 901])
    g = csr.from_edges(src, dst, n)
    pg = partition.partition_1d(g, 8)
    mesh = _mesh(8)
    outs = {}
    for sync in ("butterfly", "sparse"):
        cfg = ProgramConfig(sync=sync, sparse_capacity=256, tol=PR_TOL)
        res, _, _ = programs.run_program(
            pg, mesh, programs.by_name("pagerank"), cfg
        )
        outs[sync] = res
    assert np.array_equal(
        outs["butterfly"].astype(np.float32).view(np.uint32),
        outs["sparse"].astype(np.float32).view(np.uint32),
    )
    _check_oracle(g, "pagerank", outs["butterfly"])


def test_nonidempotent_sparse_ref_contract():
    """The monoid layer refuses REMERGE mode for ADD — the invariant the
    whole delta dichotomy hangs on."""
    with pytest.raises(mono.MonoidContractError):
        mono.ADD_F32.check_sparse_ref(jnp.zeros((4,), jnp.float32))
    assert mono.ADD_F32.sparse_mode == "delta"
    assert mono.MIN_U32.sparse_mode == "remerge"


if HAS_HYPOTHESIS:

    @needs_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=16, max_value=96),
        n_edges=st.integers(min_value=4, max_value=120),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_pagerank_property_random_graphs(n, n_edges, seed):
        """Random graphs: sparse delta shipping stays bit-identical to
        dense and both stay within the stopping-rule tolerance of the
        float64 host oracle."""
        rng = np.random.default_rng(seed)
        from repro.graph import csr

        src = rng.integers(0, n, size=n_edges)
        dst = rng.integers(0, n, size=n_edges)
        g = csr.from_edges(src, dst, n)
        pg = partition.partition_1d(g, 2)
        mesh = _mesh(2)
        out = {}
        for sync in ("butterfly", "sparse"):
            cfg = ProgramConfig(sync=sync, tol=PR_TOL)
            res, _, _ = programs.run_program(
                pg, mesh, programs.by_name("pagerank"), cfg
            )
            out[sync] = res
        assert np.array_equal(
            out["butterfly"].astype(np.float32).view(np.uint32),
            out["sparse"].astype(np.float32).view(np.uint32),
        )
        _check_oracle(g, "pagerank", out["butterfly"])


# --- engine + service integration ------------------------------------------


def test_engine_program_cache_and_stats(mesh8):
    from repro.analytics.engine import BFSQueryEngine, compiled_program_fn

    g = generators.kronecker(8, 8, seed=3)
    pg = partition.partition_1d(g, 8)
    eng = BFSQueryEngine(pg, mesh8, bfs.BFSConfig(axes=("data",)))
    cfg = eng._program_cfg(None)
    fn1 = compiled_program_fn(pg, mesh8, "cc", cfg)
    fn2 = compiled_program_fn(pg, mesh8, "cc", cfg)
    assert fn1 is fn2  # program-cache hit on (graph, mesh, algo, cfg)
    assert fn1 is not compiled_program_fn(pg, mesh8, "kcore", cfg)
    res = eng.vertex_program("cc")
    np.testing.assert_array_equal(res[: g.n], programs.cc_reference(g))
    assert eng.stats.program_runs == 1
    assert eng.stats.program_iters >= 1
    assert eng.stats.program_edges > 0


def test_program_algos_literal_matches_registry():
    """service.queue keeps PROGRAM_ALGOS as a literal (importing the queue
    must not drag in jax) — pin it to the real registry."""
    from repro.service import queue

    assert queue.PROGRAM_ALGOS == programs.PROGRAM_ALGOS


def test_service_serves_programs_end_to_end(mesh8):
    from repro.service import GraphQueryService
    from repro.service.cache import result_key
    from repro.service.scheduler import WAVE_CLASS, WAVE_CLASSES

    g = generators.kronecker(8, 8, seed=3)
    pg = partition.partition_1d(g, 8)
    svc = GraphQueryService(
        pg, mesh8, bfs.BFSConfig(axes=("data",)), lanes=4,
        n_real=g.n_real, max_linger_s=0.005,
    )
    try:
        for algo in programs.PROGRAM_ALGOS:
            assert WAVE_CLASS[algo] == algo and algo in WAVE_CLASSES
            assert svc.scheduler.wave_width(algo) == 1
            a = np.asarray(svc.query(algo, 17, timeout=RESULT_S))
            b = np.asarray(svc.query(algo, 3, timeout=RESULT_S))
            # root-free: every root normalizes to 0 and shares one result
            assert np.array_equal(a, b)
            hit, _ = svc.cache.get(
                result_key(svc.epoch, algo, svc.program_cfg, 0)
            )
            assert hit  # cached under the normalized root 0
        _check_oracle(g, "pagerank",
                      np.asarray(svc.query("pagerank", 0, timeout=RESULT_S)))
        np.testing.assert_array_equal(
            np.asarray(svc.query("cc", 0, timeout=RESULT_S))[: g.n],
            programs.cc_reference(g),
        )
        snap = svc.snapshot()
        assert snap["completed"] >= 2 * len(programs.PROGRAM_ALGOS)
    finally:
        svc.stop()


def test_service_pagerank_survives_mutation_by_repush(mesh8, rng):
    """The §16 showcase: a mutation batch repairs the cached pagerank row
    by warm-started re-push (rows_repaired >= 1), drops the cc/tri/kcore
    rows (no incremental story), and the post-mutation query matches the
    mutated graph's oracle within the stopping tolerance."""
    from repro.service import GraphQueryService

    g = generators.kronecker(9, 8, seed=3)
    pg = partition.partition_1d(g, 8)
    svc = GraphQueryService(
        pg, mesh8, bfs.BFSConfig(axes=("data",)), lanes=4,
        n_real=g.n_real, max_linger_s=0.005,
    )
    try:
        for algo in programs.PROGRAM_ALGOS:
            svc.query(algo, 0, timeout=RESULT_S)
        n_cached = len(svc.cache)
        batch = svc.overlay.sample_batch(rng, 8, 2)
        svc.apply_updates(batch)
        mut = svc.snapshot()["mutations"]
        assert mut["rows_repaired"] >= 1
        assert mut["rows_dropped"] >= 3  # cc/tri/kcore have no repairer
        assert len(svc.cache) < n_cached
        gm = svc.overlay.current_graph()
        pr = np.asarray(svc.query("pagerank", 0, timeout=RESULT_S))
        ref = programs.pagerank_reference(gm, damping=0.85, tol=1e-12,
                                          max_iters=1000)
        np.testing.assert_allclose(pr[: gm.n], ref, atol=PR_SLACK, rtol=0)
        # the dropped programs cold-start correctly on the mutated graph
        np.testing.assert_array_equal(
            np.asarray(svc.query("cc", 0, timeout=RESULT_S))[: gm.n],
            programs.cc_reference(gm),
        )
    finally:
        svc.stop()


# --- §18 convergence trace rows --------------------------------------------


def test_program_trace_rows_and_schema_gate(tmp_path):
    """Trace mode fills one row per round with the program's POP/DIR
    reinterpretation (pagerank: residual ppm, monotone at the tail;
    kcore: peel count + threshold k), and the exported Perfetto doc
    passes the repo's schema CLI gate."""
    from repro.core import tracing

    g = generators.kronecker(8, 8, seed=3)
    pg = partition.partition_1d(g, 2)
    mesh = _mesh(2)
    arrays = bfs.place_arrays(pg, mesh, ("data",))
    cfg = ProgramConfig(sync="adaptive", tol=PR_TOL)

    prog = programs.by_name("pagerank")
    tfn = programs.build_program_fn(pg, mesh, prog, cfg, trace=True)
    out = tfn(arrays, prog.default_arg(pg))
    n_words = programs.program_msg_words(pg, prog)
    tr = flightrec.TraversalTrace.from_buffer(
        np.asarray(out[-1]), algo="pagerank", sync="adaptive", p=pg.p,
        fanout=cfg.fanout, n_words=n_words,
        capacity=cfg.resolved_capacity(n_words),
        density_threshold=cfg.density_threshold,
    )
    iters = int(np.max(np.asarray(out[1])))
    buf = np.asarray(out[-1])[0]
    rows = buf[buf[:, flightrec.COL_LEVEL] > 0]
    assert rows.shape[0] == iters
    resid = rows[:, flightrec.COL_POP]
    assert resid[-1] < resid[0]  # residual ppm decays
    assert resid[-1] * 1e-6 <= PR_TOL * 1.5  # stopped at the tolerance
    # untraced and traced programs agree on the result
    fn = programs.build_program_fn(pg, mesh, prog, cfg)
    plain = fn(arrays, prog.default_arg(pg))
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(out[0]))

    kprog = programs.by_name("kcore")
    ktfn = programs.build_program_fn(pg, mesh, kprog, cfg, trace=True)
    kout = ktfn(arrays, kprog.default_arg(pg))
    kbuf = np.asarray(kout[-1])[0]
    krows = kbuf[kbuf[:, flightrec.COL_LEVEL] > 0]
    # DIR column carries the peel threshold k: non-decreasing, ends at the
    # degeneracy + 1
    ks = krows[:, flightrec.COL_DIR]
    assert (np.diff(ks) >= 0).all()
    assert ks[-1] == programs.kcore_reference(g).max() + 1
    # peeled counts (POP) sum to every real vertex exactly once
    assert krows[:, flightrec.COL_POP].sum() == g.n

    doc = flightrec.trace_chrome_doc(tr)
    path = tmp_path / "trace_pagerank.json"
    path.write_text(json.dumps(doc))
    schema = os.path.join(os.path.dirname(__file__), "trace_schema.json")
    assert tracing.main([str(path), "--schema", schema]) == 0


# --- satellite 1: the shared loop builder compiles byte-identical HLO ------


# Lowered StableHLO text is only deterministic in a FRESH interpreter:
# jax's helper-function uniquification counters (``@_where_5`` vs
# ``@_where_6``) and its lowering-dedup cache (whether two identical
# ``_where`` helpers share one definition) are process-global, so earlier
# lowerings in the same process shift both the names and the emitted
# function set.  The fingerprints are therefore computed in a subprocess
# — same fresh-process conditions the goldens were captured under — and
# symbol names are canonicalized on top for extra safety.
_FINGERPRINT_SCRIPT = r"""
import hashlib, json, os, re, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
import jax.numpy as jnp
from repro.analytics import msbfs
from repro.core import bfs
from repro.graph import generators, partition
from repro.traversal import bc as bc_mod
from repro.traversal import sssp as sssp_mod

_SYM = re.compile(r"@[A-Za-z_][\w$.]*")

def canonical(txt):
    names = {}
    return _SYM.sub(
        lambda m: names.setdefault(m.group(0), "@f%d" % len(names)), txt)

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = generators.kronecker(10, 8, seed=3, max_weight=255)
pg = partition.partition_1d(g, 8)
arrays = bfs.place_arrays(pg, mesh, ("data",))
got = {}
for sync in ("butterfly", "sparse", "adaptive"):
    for mode in ("top_down", "direction_optimizing"):
        cfg = bfs.BFSConfig(sync=sync, mode=mode)
        txt = bfs.build_bfs_fn(pg, mesh, cfg).lower(
            arrays, jnp.int32(0)).as_text()
        got["bfs/%s/%s" % (sync, mode)] = hashlib.sha256(
            canonical(txt).encode()).hexdigest()
    scfg = sssp_mod.SSSPConfig(sync=sync,
                               delta=64 if sync != "butterfly" else 0)
    txt = sssp_mod.build_sssp_fn(pg, mesh, scfg).lower(
        arrays, jnp.int32(0)).as_text()
    got["sssp/%s" % sync] = hashlib.sha256(
        canonical(txt).encode()).hexdigest()
roots = jnp.arange(32, dtype=jnp.int32)
for sync in ("butterfly", "sparse", "adaptive"):
    for mode in ("top_down", "direction_optimizing"):
        cfg = bfs.BFSConfig(sync=sync, mode=mode)
        txt = msbfs.build_msbfs_fn(pg, mesh, cfg, 32).lower(
            arrays, roots).as_text()
        got["msbfs/%s/%s" % (sync, mode)] = hashlib.sha256(
            canonical(txt).encode()).hexdigest()
for sync in ("butterfly", "adaptive"):
    cfg = bfs.BFSConfig(sync=sync)
    txt = bc_mod.build_bc_fn(pg, mesh, cfg, 32).lower(
        arrays, roots).as_text()
    got["bc/%s" % sync] = hashlib.sha256(
        canonical(txt).encode()).hexdigest()
json.dump(got, sys.stdout)
"""


def test_hlo_fingerprints_stable():
    """The XLA programs the bfs/sssp drivers lower to must not change
    unnoticed.  Golden sha256s were captured on the installed jax version
    (fresh process, symbol names canonicalized — see
    ``_FINGERPRINT_SCRIPT``) — any drift is a real compilation change,
    not suite-ordering noise; a deliberate one re-captures the golden."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    if jax.__version__ != golden["jax"]:
        pytest.skip(f"golden HLO captured on jax {golden['jax']}, "
                    f"running {jax.__version__}")
    env = dict(os.environ)
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _FINGERPRINT_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout)
    want = {k: v for k, v in golden.items() if k != "jax"}
    assert got == want


# --- tier-2 acceptance off the benchmark rows ------------------------------


@pytest.mark.tier2
def test_vertex_program_acceptance_kron12_p8():
    """ISSUE-8 bars from the ``vertex_program`` rows: PageRank re-push
    beats the recompute path ≥3× per §16 batch, lands within the
    stopping tolerance of the mutated graph's float64 oracle, and the
    k-core sparse wire ships fewer bytes than the dense butterfly."""
    from benchmarks import analytics as abench

    rep = abench.run(smoke=True)
    rows = rep.extra["vertex_program"]
    rp = rows["repush"]
    assert rp["speedup"] >= 3.0
    assert rp["oracle_l1"] < 10 * rp["tol"]
    assert rows["wire/kcore/sparse"]["bytes_per_node"] < (
        rows["wire/kcore/butterfly"]["bytes_per_node"]
    )
    # the delta dichotomy costs nothing: pagerank dense/sparse wire equal
    assert rows["wire/pagerank/sparse"]["bytes_per_node"] == pytest.approx(
        rows["wire/pagerank/butterfly"]["bytes_per_node"]
    )
    for algo in programs.PROGRAM_ALGOS:
        assert rows[f"rate/{algo}"]["rounds"] >= 1
