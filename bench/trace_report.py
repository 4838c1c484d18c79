#!/usr/bin/env python3
"""Attribute one traced window of a cell by the program's own names.

    python3 bench/trace_report.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

Sets the cell up as ``bench/run.py`` does, records a ``jax.profiler`` trace
of its window into ``<dir>`` (kept), and prints one JSON object as the last
line of standard output (:func:`harness.program_trace.report`): the
traversal module's device time by ``traversal.*`` phase and the phase of
each of its top operations, the device's idle time split by the innermost
``repro:`` span open at each instant, the longest idle gaps so labelled,
the device-idle time inside each ``scheduler/dispatch`` span beside the
same quantity from the service's telemetry (``dispatch_idle_ms``), and,
for single-source cells, the levels the window's traversals ran and the
device time per level.  It checks no answer and is not a benchmark run:
``bench/run.py`` is.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compiled_text(sut, lanes: int) -> str:
    """HLO text of the cell's one traversal program, as compiled."""
    if hasattr(sut, "compiled"):
        return sut.compiled.as_text()
    import jax
    import jax.numpy as jnp

    engine = sut.svc.engine
    return engine._fn.lower(engine._arrays, jax.ShapeDtypeStruct(
        (lanes,), jnp.int32)).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    for p in (str(CHECKOUT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import spec

    cell = spec.load_cell(args.workload, CHECKOUT)
    print(json.dumps(report(cell, args.seed, args.seconds, args.out,
                            jax.devices()[: cell.chips], log)), flush=True)
    return 0


def report(cell, seed: int, seconds: float, out: Path, devices, log) -> dict:
    import numpy as np

    from harness import drivers, program_trace, system
    from harness import trace as trace_mod, traffic as traffic_mod

    mix = cell.traffic
    driver = mix["driver"]
    data = system.build_graph(cell.config, seed, len(devices), log)
    plan = traffic_mod.plan(mix, seed, data.candidates)
    if driver == "single_source":
        sut = system.SingleSource(data, devices, cell.config)
    else:
        sut = system.Service(data, devices, cell.config)
    lanes = int(cell.config.get("serve", {}).get("lanes", 1))
    hlo = compiled_text(sut, lanes)
    module = hlo.split("\n", 1)[0].split()[1].rstrip(",")
    spans = trace_mod.Spans(True)
    sample = traffic_mod.Sample(mix, seed)
    out.mkdir(parents=True, exist_ok=True)
    snapshot = None
    with trace_mod.recording(out):
        if driver == "single_source":
            window = drivers.single_source(sut, plan, seconds, spans, log,
                                           sample)
        elif driver == "service_open":
            window = drivers.service_open(
                sut.svc, plan, seconds, spans, log, sample,
                rate_per_s=float(mix["rate_per_s"]))
        else:
            window = drivers.service_closed(sut.svc, plan, seconds, spans,
                                            log, sample)
        if driver != "single_source":
            snapshot = sut.svc.snapshot()
    sut.close()
    result = program_trace.report(trace_mod.xplane_file(out), module, hlo)
    result.update(workload=cell.name, seed=seed, module=module,
                  device=f"{devices[0].platform} {devices[0].device_kind} "
                         f"x{len(devices)}",
                  completed=window.completed)
    if driver == "single_source":
        unreached = np.iinfo(np.int32).max
        levels = [int(np.max(d[d != unreached])) + 1
                  for _, d in window.answers]
        result["levels"] = levels
        result["level_ms"] = 1e3 * result["module_s"] / sum(levels)
    if snapshot is not None:
        st = snapshot["stages_ms"]
        if st.get("dispatch", {}).get("count"):
            result["dispatch_idle_ms.telemetry"] = (
                st["dispatch"]["mean"] - st["device_wait"]["mean"])
            result["dispatches"] = st["dispatch"]["count"]
    return result


if __name__ == "__main__":
    raise SystemExit(main())
