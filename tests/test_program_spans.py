"""The program's own names on the profiler's trace: the ``traversal.*``
phase scopes of the level loop in the compiled modules, the ``repro:``
annotations of live spans, and the scheduler's and engine's stage spans
with their parents."""

import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import msbfs
from repro.core import bfs, loop
from repro.core.tracing import NULL_TRACER, PROFILER_PREFIX, Tracer
from repro.graph import generators, partition
from repro.service import GraphQueryService

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^=]*\)|\S+)\s+"
                    r"([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVE = ("collective-permute", "collective-permute-start",
               "all-reduce", "all-reduce-start", "all-gather", "all-to-all")


@pytest.fixture(scope="module")
def pg8():
    return partition.partition_1d(generators.kronecker(9, 8, seed=2), 8)


def _instructions(text):
    """``(opcode, op_name)`` of every instruction of compiled HLO text."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(2), op.group(1) if op else ""))
    return out


@pytest.mark.parametrize("program", ["bfs", "msbfs"])
@pytest.mark.parametrize("mode", ["top_down", "direction_optimizing"])
def test_compiled_phases(pg8, mesh8, program, mode):
    """Every collective of the compiled module sits in
    ``traversal.exchange`` (the Beamer switch's psums in
    ``traversal.direction``, single-source top-down's branch ``pmax`` in
    ``traversal.expand``); the level loop's gathers and scatters in
    ``traversal.expand``."""
    cfg = bfs.BFSConfig(axes=("data",), sync="butterfly", mode=mode)
    arrays = bfs.place_arrays(pg8, mesh8, cfg.axes)
    if program == "bfs":
        fn, arg = bfs.build_bfs_fn(pg8, mesh8, cfg), jnp.int32(0)
    else:
        fn = msbfs.build_msbfs_fn(pg8, mesh8, cfg, 32)
        arg = jnp.zeros(32, jnp.int32)
    instrs = _instructions(fn.lower(arrays, arg).compile().as_text())
    in_loop = [(op, name) for op, name in instrs if "/while/" in name]
    assert any("traversal.cond" in name for _, name in in_loop)
    permutes = [name for op, name in instrs if op.startswith(
        "collective-permute")]
    assert permutes and all("traversal.exchange" in n for n in permutes)
    for op, name in instrs:
        if op in _COLLECTIVE and "/while/" in name:
            assert ("traversal.exchange" in name
                    or "traversal.direction" in name
                    or (program == "bfs" and op.startswith("all-reduce")
                        and "traversal.expand/pmax" in name)), (op, name)
    moved = [name for op, name in in_loop if op in ("gather", "scatter")]
    assert moved and all("traversal.expand" in n for n in moved)


def test_adaptive_exchange_branches_are_scoped(pg8, mesh8):
    """The adaptive exchange's two branches carry their own scopes inside
    ``traversal.exchange``: every permute of the compiled module is under
    ``…/sparse/…`` or ``…/dense/…``, and both occur."""
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    arrays = bfs.place_arrays(pg8, mesh8, cfg.axes)
    text = bfs.build_bfs_fn(pg8, mesh8, cfg).lower(
        arrays, jnp.int32(0)).compile().as_text()
    permutes = [name for op, name in _instructions(text)
                if op.startswith("collective-permute")]
    branch = re.compile(r"traversal\.exchange/.*/(sparse|dense)/")
    found = {m.group(1) for m in map(branch.search, permutes) if m}
    assert found == {"sparse", "dense"}
    assert all(branch.search(n) for n in permutes), permutes


def test_phase_rejects_unknown_names():
    assert loop.PHASES == ("expand", "exchange", "update", "direction",
                           "cond")
    with pytest.raises(ValueError):
        loop.phase("sync")


def test_tracing_imports_no_jax():
    """Importing the span API stays stdlib-only: jax is resolved on the
    first live span."""
    code = ("import sys; import repro.core.tracing as t; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120,
                         env={"PYTHONPATH": ":".join(sys.path)})
    assert out.stdout.strip() == "False"


def _repro_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(log_dir.rglob("*.xplane.pb"))[-1]
    return [e.name for plane in ProfileData.from_file(str(path)).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(PROFILER_PREFIX)]


def test_live_spans_annotate_the_profile(tmp_path):
    tracer = Tracer()
    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(64)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("dispatch", track="scheduler"):
            with NULL_TRACER.span("wave", track="engine"):
                f(jnp.ones(64)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = _repro_events(tmp_path)
    assert "repro:scheduler/dispatch" in names
    assert "repro:engine/wave" in names
    (ev,) = tracer.events()  # the null tracer kept nothing
    assert ev["name"] == "dispatch" and ev["parent_id"] == ""


def test_scheduler_and_engine_stage_spans(pg8, mesh8):
    """One dispatch: ``scheduler/dispatch`` holds ``triage``, the engine's
    ``wave`` (which holds ``device-wait``, ``copy-back``, ``assemble``),
    ``cache-put`` and ``answer``, all on the scheduler's thread; its
    duration and the waves' device wait reach the telemetry stages."""
    tracer = Tracer()
    svc = GraphQueryService(pg8, mesh8, bfs.BFSConfig(axes=("data",)),
                            lanes=8, tracer=tracer, max_linger_s=0.05)
    try:
        svc.query("bfs", 0, timeout=300)  # compiles the wave program
        # its dispatch may still be closing: its stages stay with the
        # telemetry it began under
        svc.reset_telemetry()
        futures = [svc.submit("bfs", r) for r in (1, 2, 3)]
        for fut in futures:
            fut.result(timeout=300)
    finally:
        svc.stop()  # joins the scheduler: every span has closed
    spans = [e for e in tracer.events() if e["kind"] == "span"]
    by_id = {e["span_id"]: e for e in spans}
    warm, dispatch = [e for e in spans if e["name"] == "dispatch"]
    assert dispatch["track"] == "scheduler" and dispatch["parent_id"] == ""
    assert (warm["args"]["roots"], dispatch["args"]["roots"]) == (1, 3)

    def children(parent):
        return [e["name"] for e in sorted(spans, key=lambda e: e["ts_us"])
                if e["parent_id"] == parent["span_id"]]

    assert children(dispatch) == ["triage", "wave", "cache-put", "answer"]
    wave = [e for e in spans if e["name"] == "wave"][-1]
    assert wave["track"] == "engine"
    assert wave["parent_id"] == dispatch["span_id"]
    assert children(wave) == ["device-wait", "copy-back", "assemble"]
    for e in spans:
        if e["parent_id"]:
            parent = by_id[e["parent_id"]]
            assert parent["ts_us"] <= e["ts_us"]
            assert (e["ts_us"] + e["dur_us"]
                    <= parent["ts_us"] + parent["dur_us"])
    # the per-request spans stay in memory only, under no parent
    assert {e["name"] for e in spans if not e["parent_id"]} >= {
        "dispatch", "queue-wait:bfs", "coalesce:bfs"}
    stages = svc.snapshot()["stages_ms"]
    assert stages["dispatch"]["count"] == 1
    assert 0 < stages["device_wait"]["mean"] <= stages["dispatch"]["mean"]


def test_engine_counts_device_wait(pg8, mesh8):
    from repro.analytics.engine import BFSQueryEngine

    eng = BFSQueryEngine(pg8, mesh8, bfs.BFSConfig(axes=("data",)), lanes=8)
    assert eng.stats.device_wait_s == 0.0
    dist = eng.query([0, 5])
    assert eng.stats.device_wait_s > 0
    ref = bfs.bfs_reference(generators.kronecker(9, 8, seed=2), 5)
    np.testing.assert_array_equal(dist[1][: ref.size], ref)
