"""Devices, the persistent compile cache and the host ETL of the entry
points.

``bfs_run``, ``serve_graph`` and ``chip_smoke.py`` run on the devices JAX
finds: the chips of a TPU host, or the CPU backend's host devices (one,
unless ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` asks for
more, as the tests do).  Every printed result names the platform, the
device kind and the count, so a CPU number is never read as a chip number.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable, Dict, Optional

# <checkout>/src/repro/launch/devices.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is set here.  Otherwise the cache lives at a fixed
    path inside the checkout, so the next run from the same checkout finds
    it.  Returns the cache directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def resolve_device_count(requested: Optional[int]) -> int:
    """``requested`` devices, or every device JAX finds when ``None``.
    Raises ``ValueError`` when more are asked for than exist."""
    import jax

    found = len(jax.devices())
    if requested is None:
        return found
    if not 1 <= requested <= found:
        raise ValueError(
            f"--devices {requested}: {found} {jax.devices()[0].platform} "
            f"device(s) found"
        )
    return requested


def device_label(count: int) -> str:
    """``"<platform> <device_kind> x<count>"`` of the devices in use."""
    import jax

    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{count}"


def partitioned_graph(make_graph: Callable[[Dict[str, float]], object],
                      n_parts: int):
    """Host ETL of an entry point: ``make_graph(timings)`` builds the graph
    (a generator given ``timings=`` records its steps there), then
    ``partition_1d`` splits it over ``n_parts`` devices.  Returns ``(g, pg,
    line)``, ``line`` the host seconds of each step."""
    from repro.graph import partition

    etl: Dict[str, float] = {}
    g = make_graph(etl)
    t0 = time.perf_counter()
    pg = partition.partition_1d(g, n_parts)
    etl["partition_1d"] = time.perf_counter() - t0
    line = "host ETL seconds: " + "  ".join(
        f"{k} {v:.2f}" for k, v in etl.items())
    return g, pg, line
