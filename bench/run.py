#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a workload of ``BENCHMARK.json``.  The run builds its graph
and its traffic from ``--seed``, warms the cell's one program (through
JAX's persistent compilation cache at ``<checkout>/.jax_cache``), measures
for ``--seconds``, compares the answers with a plain host reference, and
prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
Progress, set-up seconds per step and, last, the checks go to standard
error.

It exits nonzero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the program's source is not in the
checkout.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # the process's start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
PLATFORM = "tpu"  # the only platform whose numbers count


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    return args


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prepare(argv=None, control: bool = False) -> int:
    args = parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        log(f"bench: no program source under {CHECKOUT / 'src'}; no result")
        return 2
    for p in (str(CHECKOUT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    # the cache lives at a fixed path inside the checkout, whatever the
    # environment names, so that only the first run of a cell compiles
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from harness import cell as cell_mod, spec

    cell = spec.load_cell(args.workload, CHECKOUT)
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        log(f"bench: JAX found no {PLATFORM} (platform "
            f"{devs[0].platform!r}); no result")
        return 3
    if len(devs) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devs)}; no result")
        return 3
    result = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                          devs[: cell.chips], T0, log, control=control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(prepare())
