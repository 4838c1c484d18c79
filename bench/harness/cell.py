"""One run of one cell: set-up, the measured window, the reference check,
and the result line.

Set-up (everything before the window, and ``setup_s``): the graph drawn
from the seed through the program's ETL and partition, the largest
component, the traffic plan, the arrays placed and the cell's one program
compiled or read from the persistent cache.  Then the window, traced or
not.  After it: the device's memory peak, the program's state freed, the
trace reduced, and the answers compared with the reference, which is not
counted in ``setup_s``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from harness import checks, drivers, reference, spec, system, trace as trace_mod
from harness import traffic as traffic_mod
from harness.compile_clock import CompileClock
from harness.peaks import PEAKS, peak


@dataclasses.dataclass
class RunRecord:
    """What the metric readers (``bench/metrics/*.py``) read."""

    driver: str
    chips: int
    setup_s: float
    window: drivers.Window
    teps_edges: int = 0  # Graph500 edges of the completed traversals
    bytes_needed: int = 0  # least HBM bytes of those traversals
    snapshot: Optional[dict] = None  # service telemetry of the window
    trace: Optional[trace_mod.Summary] = None
    device_kind: str = ""
    peaks_table: dict = dataclasses.field(default_factory=lambda: PEAKS)

    def peak(self, key: str) -> float:
        return peak(self.device_kind, self.peaks_table)[key]


def control_levels(data, plan) -> int:
    """``max_levels`` of the control: one level short of the shallowest of
    the first roots the plan asks for (the hot ones and up to 64 in all)."""
    roots = np.concatenate([plan.hot, plan.cold[: 64 - plan.hot.size]])
    return max(min(data.depths(roots[:64])) - 1, 0)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, devices,
        t0: float, log: Callable[[str], None], *, control: bool = False,
        peaks_table: dict = PEAKS) -> dict:
    """The result line of one run (a dict).  ``t0`` is the process's start
    on ``time.perf_counter``'s clock; ``control`` runs the program with its
    traversal cut one level short (the control of the comparison)."""
    mix = cell.traffic
    driver = mix["driver"]
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    log(f"device: {d0.platform} {d0.device_kind} x{len(devices)}; cell "
        f"{cell.name}, seed {seed}, {seconds} s window, trace {int(traced)}")

    data = system.build_graph(cell.config, seed, len(devices), log)
    t = time.perf_counter()
    plan = traffic_mod.plan(mix, seed, data.candidates)
    log(f"setup: traffic {time.perf_counter() - t:.2f} s: {len(plan.clients)}"
        f" client(s), {plan.hot.size} hot roots, {plan.cold.size} cold")
    max_levels = control_levels(data, plan) if control else None
    if control:
        log(f"control: traversal cut at max_levels={max_levels}")

    clock = CompileClock()
    try:
        t = time.perf_counter()
        if driver == "single_source":
            sut = system.SingleSource(data, devices, cell.config, max_levels)
        elif driver in ("service_open", "service_closed"):
            sut = system.Service(data, devices, cell.config, max_levels)
        else:
            raise ValueError(f"unknown driver {driver!r}")
        log(f"setup: placement and compile {time.perf_counter() - t:.2f} s, "
            f"of which compile {clock.seconds:.2f} s ({clock.compiles} "
            f"programs, {clock.cache_hits} persistent-cache hits)")
        setup_s = time.perf_counter() - t0
        log(f"setup: setup_s {setup_s:.2f}")
        clock.reset()

        spans = trace_mod.Spans(traced)
        summary = None
        snapshot = None
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            recording = (trace_mod.recording(Path(tmp)) if traced
                         else contextlib.nullcontext())
            with recording:
                sample = traffic_mod.Sample(mix, seed)
                if driver == "single_source":
                    window = drivers.single_source(sut, plan, seconds, spans,
                                                   log, sample)
                elif driver == "service_open":
                    window = drivers.service_open(
                        sut.svc, plan, seconds, spans, log, sample,
                        rate_per_s=float(mix["rate_per_s"]))
                else:
                    window = drivers.service_closed(sut.svc, plan, seconds,
                                                    spans, log, sample)
                if driver != "single_source":
                    snapshot = sut.svc.snapshot()
            if clock.compiles:
                log(f"window: WARNING {clock.compiles} compiles inside the "
                    f"window ({clock.seconds:.2f} s)")
            device["memory_peak_bytes"] = memory_peak(devices)
            module = getattr(sut, "module", None)
            sut.close()
            del sut
            if traced:
                t = time.perf_counter()
                ops, host_spans = trace_mod.load(trace_mod.xplane_file(tmp))
                summary = trace_mod.reduce(ops, host_spans, module=module)
                log(f"trace: {len(ops)} device ops read in "
                    f"{time.perf_counter() - t:.2f} s; busy "
                    f"{summary.busy_s:.4f} s of {summary.window_s:.4f} s")
    finally:
        clock.close()

    log(f"window: {window.seconds:.3f} s, attempted {window.attempted}, "
        f"completed {window.completed}, failed {window.failed}")
    record = RunRecord(driver=driver, chips=len(devices), setup_s=setup_s,
                       window=window, snapshot=snapshot, trace=summary,
                       device_kind=d0.device_kind, peaks_table=peaks_table)
    if driver == "single_source":
        roots = window.roots
        record.teps_edges = sum(data.work.teps_edges(r) for r in roots)
        record.bytes_needed = sum(data.work.bytes_needed(r) for r in roots)

    t = time.perf_counter()
    adj = reference.adjacency(data.src, data.dst, data.n)
    found, compared = checks.compare(window.answers, window.missing, adj,
                                     data.pg.n, data.g.n_real)
    log(f"check: {compared} answers against scipy.sparse.csgraph in "
        f"{time.perf_counter() - t:.2f} s")

    result = {
        "correct": checks.correct(found, compared),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": spec.read_metrics(cell.metrics(traced), record),
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    result["checks"] = found
    for name, c in found.items():
        log(f"check: {name} {c['value']} limit {c['limit']}")
    return result
