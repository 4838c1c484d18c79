"""Shared set-up of the benchmark's CPU tests: the import paths, the CPU
backend's host devices (eight, as the repository's tests ask), and runs of
a cell at a tiny scale.

Imported first by every test module here, before JAX starts a backend.
"""

import copy
import dataclasses
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

CHECKOUT = Path(__file__).resolve().parents[2]
for _p in (CHECKOUT / "src", CHECKOUT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from harness import cell as cell_mod, peaks, spec  # noqa: E402


# arrivals that fill the CPU backend's waves, which take milliseconds
TINY_RATE_PER_S = 400.0


def tiny_cell(name, scale=9, root=CHECKOUT):
    """The workload ``name`` with its graph cut to ``2**scale`` vertices and
    an open-loop rate that keeps several roots in a wave."""
    cell = spec.load_cell(name, root)
    config = copy.deepcopy(cell.config)
    config["graph"]["scale"] = scale
    traffic = dict(cell.traffic)
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = max(traffic["rate_per_s"], TINY_RATE_PER_S)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run_tiny(name, *, seed=5, seconds=1.0, traced=False, scale=9,
             control=False, root=CHECKOUT, log=None):
    """One run of a cell on the CPU backend at a tiny scale: the result
    line as a dict.  The CPU's device kind gets a stand-in peak so that
    the roofline reader has something to divide by."""
    import jax

    cell = tiny_cell(name, scale, root)
    devices = jax.devices()[: cell.chips]
    table = dict(peaks.PEAKS)
    table[devices[0].device_kind] = {"hbm_bytes_per_s": 1e11}
    return cell_mod.run(cell, seed, seconds, traced, devices,
                        time.perf_counter(), log or (lambda msg: None),
                        control=control, peaks_table=table)
