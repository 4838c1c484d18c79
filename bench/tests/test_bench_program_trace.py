"""Reading the program's own names from a trace: phase times from a
compiled module's HLO text, idle gaps labelled by ``repro:`` spans, the
device-idle time of each dispatch, and the per-layer metrics that read
them (``level_ms``, ``dispatch_idle_ms``)."""

import bench_tiny
import numpy as np
import pytest

import trace_report
from harness import program_trace as pt
from harness.trace import HostSpan, Op

NS = 1e9


def _op(name, s, e, dev="d0", module="jit_body"):
    return Op(dev, name, module, s * NS, e * NS)


def _span(name, s, e, thread="scheduler"):
    return pt.ProgramSpan(name, s * NS, e * NS, thread)


HLO = """HloModule jit_body, is_scheduled=true

%fused_computation.1 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %g = u32[8]{0} gather(%p, %p), metadata={op_name="jit(body)/while/body/traversal.expand/gather"}
}

%body (s: u32[8]) -> u32[8] {
  %s = u32[8]{0} parameter(0)
  %zeros = u32[8]{0} broadcast(%c)
  %fusion.1 = u32[8]{0} fusion(%s, %zeros), kind=kLoop, calls=%fused_computation.1
  %sort.2 = u32[8]{0} sort(%fusion.1), metadata={op_name="jit(body)/while/body/traversal.expand/scatter-max"}
  %cp.3 = u32[8]{0} collective-permute(%sort.2), metadata={op_name="jit(body)/while/body/traversal.exchange/ppermute"}
  ROOT %or.4 = u32[8]{0} or(%cp.3, %s), metadata={op_name="jit(body)/while/body/traversal.update/or"}
}

ENTRY %main (a: u32[8]) -> u32[8] {
  %a = u32[8]{0} parameter(0)
  %copy.5 = u32[8]{0} copy(%a)
  ROOT %while.6 = u32[8]{0} while(%copy.5), condition=%cond, body=%body, metadata={op_name="jit(body)/while"}
}
"""


def test_phase_map_from_hlo_text():
    phases = pt.phase_map(HLO)
    assert phases["fusion.1"] == "traversal.expand"  # its callee's root
    assert phases["zeros"] == "traversal.expand"  # its user's phase
    assert phases["sort.2"] == "traversal.expand"
    assert phases["cp.3"] == "traversal.exchange"
    assert phases["or.4"] == "traversal.update"
    assert "copy.5" not in phases and "while.6" not in phases


def test_phase_times_partition_the_module_busy_time():
    phases = pt.phase_map(HLO)
    ops = [
        _op("while.6", 0, 10),  # the loop: its own time only where
        _op("fusion.1", 0, 4),  # nothing inside it runs
        _op("sort.2", 4, 6),
        _op("cp.3", 6, 7),
        _op("or.4", 8, 9),
        _op("copy.5", 10, 11),
        _op("fusion.1", 0, 3, dev="d1"),
        _op("fusion.1", 0, 5, module="jit_other"),  # another program
    ]
    times = pt.phase_times(ops, "jit_body", phases, 0, 20 * NS)
    assert times["traversal.expand"] == pytest.approx((6 + 3) / 2)
    assert times["traversal.exchange"] == pytest.approx(1 / 2)
    assert times["traversal.update"] == pytest.approx(1 / 2)
    assert times["unscoped"] == pytest.approx(3 / 2)  # the loop, the copy
    assert sum(times.values()) == pytest.approx((11 + 3) / 2)


def test_idle_gaps_are_labelled_by_program_spans_first():
    ops = [_op("fusion.1", 0, 1), _op("fusion.1", 5, 6),
           _op("fusion.1", 9, 10)]
    spans = [_span("scheduler/dispatch", 0.5, 8),
             _span("scheduler/answer", 6, 8),
             _span("scheduler/wait", 8, 9)]
    bench = [HostSpan("bench:window", 0, 10 * NS),
             HostSpan("bench:client-wait", 0, 10 * NS)]
    gaps = pt.gaps(ops, spans, bench, 0, 10 * NS)
    assert gaps[0] == ("scheduler/dispatch", pytest.approx(4.0))
    assert gaps[1] == ("scheduler/answer", pytest.approx(3.0))
    split = pt.idle_split(ops, spans, bench, 0, 10 * NS)
    assert split == pytest.approx({"scheduler/dispatch": 4.0,
                                   "scheduler/answer": 2.0,
                                   "scheduler/wait": 1.0})
    # without program spans, the bench span labels the same gaps
    assert pt.gaps(ops, [], bench, 0, 10 * NS)[0][0] == "bench:client-wait"
    assert pt.dispatch_idle(ops, spans, 0, 10 * NS) == [pytest.approx(6.0)]


@pytest.fixture(scope="module")
def served_report(tmp_path_factory):
    cell = bench_tiny.tiny_cell("g500-s18.serve")
    import jax

    return trace_report.report(cell, 7, 1.0, tmp_path_factory.mktemp("tr"),
                               jax.devices()[: cell.chips], lambda m: None)


def test_trace_report_on_a_served_window(served_report):
    r = served_report
    assert r["spans"] > 0 and r["dispatches"] > 0
    assert r["idle_in_program_span"] > 0.5
    assert r["idle_gaps"][0][0].split("/")[0] in ("scheduler", "engine")
    assert r["phase_s"]["traversal.expand"] > 0
    assert sum(r["phase_s"].values()) == pytest.approx(r["module_s"])
    assert r["dispatch_idle_s"] and r["dispatch_idle_ms.telemetry"] > 0


def test_level_ms_counts_the_levels_the_program_ran():
    """A traversal runs eccentricity + 1 levels: what ``level_ms`` reads
    from the distances is the program's own ``level`` output."""
    import jax

    from harness import system

    cell = bench_tiny.tiny_cell("g500-s21.kernel2")
    data = system.build_graph(cell.config, 3, 1, lambda m: None)
    sut = system.SingleSource(data, jax.devices()[:1], cell.config)
    for root in data.candidates[:4]:
        d_owned, level, _ = sut(int(root))
        dist = sut.assemble(np.asarray(d_owned))
        reached = dist[dist != np.iinfo(np.int32).max]
        assert int(np.max(level)) == int(reached.max()) + 1


@pytest.mark.parametrize("name, metric", [
    ("g500-s21.kernel2", "level_ms"),
    ("g500-s18.serve", "dispatch_idle_ms"),
])
def test_new_metrics_in_traced_tiny_runs(name, metric):
    result = bench_tiny.run_tiny(name, traced=True, seed=2**31 + 5)
    assert result["correct"] is True
    value = result["metrics"][metric]["value"]
    assert np.isfinite(value) and value > 0
    assert result["metrics"][metric]["unit"] == "ms"
    untraced = bench_tiny.run_tiny(name, traced=False, seed=2**31 + 5)
    assert metric not in untraced["metrics"]
