#!/usr/bin/env python3
"""Smoke run of the graph-serving path on the TPU chips of this host.

Drives the main path once, through the entry points a user calls, on a
Graph500 deployment: kernel 2 (BFS) on a Kronecker graph (A/B/C =
0.57/0.19/0.19, edge factor 16) made from ``--seed``, 1D-partitioned over
the chips, served by ``GraphQueryService`` (32-lane waves, adaptive
butterfly exchange) under a burst of ``bfs``/``closeness`` queries with one
hot root.  One single-source ``build_bfs_fn`` traversal follows.  Served
distances and closeness of a sample of roots, and the single-source
distances, are checked against ``scipy.sparse.csgraph`` on the host.

    python chip_smoke.py              # every chip found, scale 21
    python chip_smoke.py --scale 22
    python chip_smoke.py --chips 4    # the four-chip path only, scale 22

``--chips 4`` runs the partition over four chips, the butterfly exchange at
P = 4, the served path and its reference check, and no other phase.

What it prints are set-up and smoke figures from one run on the host clock,
not benchmark metrics.  The last line of standard output is one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It exits nonzero and prints no result when JAX finds no TPU or the
repository's ``src`` is not beside this file; it exits nonzero on any
failed request, any mismatch with the reference, or any phase that raised.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent
PLATFORM = "tpu"  # the only platform whose run counts
LANES = 32
EDGE_FACTOR = 16  # Graph500's
# the served burst: a third on the hot root, the rest one wave of cold roots
REQUESTS = 3 * LANES // 2
CHECK_ROOTS = 8  # served roots checked against the reference
INF32 = 2**31 - 1
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # wraps the backend compile, or the read of a persistent-cache hit
    "/jax/core/compile/backend_compile_duration",
)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(4,), default=None,
                    help="run only the four-chip path")
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 scale: 2^scale vertices (default 21; 22 "
                         "with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = 22 if args.chips else 21
    return args


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), and the persistent-cache hits, since ``reset``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event in COMPILE_EVENTS:
            self.seconds += duration_secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.cache_hits = 0.0, 0

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def reference_distances(g, roots):
    """BFS levels from ``roots`` by scipy's unweighted shortest paths:
    ``int64[len(roots), n]``, INT32_MAX where unreached."""
    import numpy as np
    from scipy.sparse import csgraph, csr_matrix

    adj = csr_matrix((np.ones(g.n_edges, np.int8), g.dst, g.row_offsets),
                     shape=(g.n, g.n))
    d = csgraph.shortest_path(adj, unweighted=True,
                              indices=np.asarray(roots, np.int64))
    return np.where(np.isfinite(d), d, INF32).astype(np.int64)


def reference_closeness(dist, n_real):
    """Wasserman-Faust closeness of one reference distance row."""
    import numpy as np

    reached = dist < INF32
    r = int(reached.sum())
    total = int(dist[reached].sum())
    if total == 0:
        return 0.0
    return (r - 1) / total * (r - 1) / (n_real - 1)


def serve_burst(svc, hot, cold, n_requests, clock):
    """Warm the service on the hot root (a one-request wave, dispatched on
    linger), then submit a burst of ``bfs``/``closeness`` requests at once:
    every third hits the hot root (a cache hit), the rest take distinct
    cold roots, one wave's worth, so the burst dispatches as one full wave.
    Returns the submitted ``(algo, root, future)`` triples and the warm-up
    seconds."""
    clock.reset()
    t0 = time.perf_counter()
    svc.query("bfs", hot)
    warm_s = time.perf_counter() - t0
    svc.reset_telemetry()  # compile must not count as latency
    subs = []
    cold_iter = iter(cold)
    for i in range(n_requests):
        algo = "bfs" if i % 2 == 0 else "closeness"
        root = hot if i % 3 == 0 else int(next(cold_iter))
        subs.append((algo, root, svc.submit(algo, root)))
    return subs, warm_s


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (HERE / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository source under {HERE / 'src'}",
              file=sys.stderr)
        return 2
    if str(HERE / "src") not in sys.path:
        sys.path.insert(0, str(HERE / "src"))
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != PLATFORM:
        print(f"chip_smoke: JAX found no {PLATFORM} "
              f"(platform {device['platform']!r}); no result", file=sys.stderr)
        return 3
    chips = args.chips or len(devs)
    if chips > len(devs):
        print(f"chip_smoke: --chips {chips} but {len(devs)} found",
              file=sys.stderr)
        return 3

    clock = CompileClock()
    try:
        return run(args, devs[:chips], device, clock)
    finally:
        clock.close()


def run(args, devs, device, clock) -> int:
    import jax
    import numpy as np

    from repro.core import bfs
    from repro.core.events import EventLog
    from repro.graph import csr, generators
    from repro.launch import devices
    from repro.service import GraphQueryService

    chips = len(devs)
    failed = []
    print(f"device: {device['platform']} {device['kind']} x{chips} "
          f"(of {device['count']}); Graph500 kernel-2 graph scale "
          f"{args.scale}, edge factor {EDGE_FACTOR}, seed {args.seed}")
    print(f"setup: compile cache "
          f"{jax.config.jax_compilation_cache_dir or 'off'}")

    def phase(name, fn):
        try:
            return fn()
        except Exception:
            failed.append(name)
            print(f"PHASE {name} FAILED", file=sys.stderr)
            traceback.print_exc()
            return None

    # -- host ETL ---------------------------------------------------------
    built = phase("etl", lambda: devices.partitioned_graph(
        lambda timings: generators.kronecker(
            args.scale, EDGE_FACTOR, seed=args.seed, timings=timings),
        chips))
    if built is None:
        return finish(device, failed)
    g, pg, etl_line = built
    per_chip = sum(a.nbytes for a in pg.arrays().values()) // chips
    print(f"setup: graph n={g.n_real:,} (padded {g.n:,}) directed edges "
          f"{g.n_edges:,}; {per_chip / 2**30:.3f} GiB of graph arrays per "
          f"chip (emax {pg.emax:,}, vmax {pg.vmax:,})")
    print(f"setup: {etl_line}")

    mesh = jax.make_mesh((chips,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devs)
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=2)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    roots = csr.largest_component_roots(g, REQUESTS, rng)
    print(f"setup: {time.perf_counter() - t0:.2f} s to pick "
          f"{roots.size} distinct roots in the largest component")
    hot, cold = int(roots[0]), roots[1:]

    # -- served path ------------------------------------------------------
    events = EventLog()
    svc = GraphQueryService(pg, mesh, cfg, lanes=LANES, n_real=g.n_real,
                            max_linger_s=1.0, events=events)
    served = {}

    def serve():
        subs, warm_s = serve_burst(svc, hot, cold, REQUESTS, clock)
        print(f"setup: first wave {warm_s:.2f} s, of which compile "
              f"{clock.seconds:.2f} s ({clock.cache_hits} persistent-cache "
              f"hits)")
        n_ok = n_err = 0
        for algo, root, fut in subs:
            try:
                served.setdefault(root, []).append((algo, fut.result(600)))
                n_ok += 1
            except Exception as e:
                n_err += 1
                print(f"request {algo} root={root} failed: {e!r}",
                      file=sys.stderr)
        snap = svc.snapshot()
        lat = snap["latency_ms"]
        triggers = sorted({ev["args"]["trigger"]
                           for ev in events.query(kind="sched")})
        print(f"smoke: served {n_ok}/{len(subs)}, failed {n_err}; latency "
              f"p50 {lat['p50']:.1f} ms p99 {lat['p99']:.1f} ms; "
              f"{snap['engine']['waves']} waves, dispatched on {triggers}, "
              f"occupancy {snap['wave_occupancy']:.2f}, cache hit-rate "
              f"{snap['cache']['hit_rate']:.2f}")
        if n_err:
            raise RuntimeError(f"{n_err} served request(s) failed")
        if not {"full", "linger"} <= set(triggers):
            raise RuntimeError(f"expected a full and a linger wave, saw "
                               f"{triggers}")

    phase("serve", serve)
    svc.stop()
    stats = devs[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"setup: chip 0 peak_bytes_in_use "
              f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB")

    def check_served():
        check = [hot] + [r for r in served if r != hot]
        check = check[:CHECK_ROOTS]
        t0 = time.perf_counter()
        want = reference_distances(g, check)
        bad = 0
        for root, ref in zip(check, want):
            for algo, got in served.get(root, []):
                if algo == "bfs":
                    ok = np.array_equal(np.asarray(got), ref)
                else:
                    ok = np.isclose(got, reference_closeness(ref, g.n_real),
                                    rtol=1e-12, atol=0.0)
                if not ok:
                    bad += 1
                    print(f"MISMATCH {algo} root={root}", file=sys.stderr)
        print(f"check: served rows of {len(check)} roots vs "
              f"scipy.sparse.csgraph: {bad} mismatches "
              f"({time.perf_counter() - t0:.2f} s)")
        if bad:
            raise RuntimeError(f"{bad} served result(s) differ")

    if served:
        phase("check-served", check_served)

    # -- single-source traversal (one-chip path) -------------------------
    def single_source():
        root = int(cold[-1])
        clock.reset()
        t0 = time.perf_counter()
        dist, levels, scanned = bfs.distributed_bfs(pg, mesh, root, cfg)
        print(f"setup: single-source run {time.perf_counter() - t0:.2f} s, "
              f"of which compile {clock.seconds:.2f} s; smoke: {levels} "
              f"levels, {scanned:,.0f} edges examined")
        want = reference_distances(g, [root])[0]
        bad = int(np.count_nonzero(dist != want))
        print(f"check: single-source root={root} vs scipy.sparse.csgraph: "
              f"{bad} vertices differ")
        if bad:
            raise RuntimeError(f"single-source distances differ at {bad}")

    if not args.chips:
        phase("single-source", single_source)
    return finish(device, failed)


def finish(device, failed) -> int:
    ok = not failed
    doc = {"ok": ok, "device": device}
    if failed:
        doc["failed"] = failed
    print(json.dumps(doc))
    return 0 if ok else 1


if __name__ == "__main__":
    if (HERE / "src" / "repro").is_dir():
        sys.path.insert(0, str(HERE / "src"))
        from repro.launch import devices

        devices.enable_compile_cache()
    raise SystemExit(main())
