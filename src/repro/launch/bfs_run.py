"""Distributed ButterFly BFS launcher (the paper's workload, end to end).

``python -m repro.launch.bfs_run --scale 16 --fanout 4``

Generates a Kronecker graph, 1D-partitions it over the devices JAX finds
(``--devices`` takes fewer), runs BFS from random roots with the paper's
benchmarking protocol (100 roots, trim fastest/slowest 25%) and reports
GTEP/s.

``--num-sources B`` (B > 1) switches to the bit-parallel multi-source
engine (DESIGN.md §13): the ``--roots`` queries are packed into B-lane
waves and the report adds aggregate searches/s.

``--algo sssp`` runs weighted single-source shortest paths (butterfly
min-reduce; requires ``--max-weight``, defaulted when omitted) and
``--algo bc`` runs Brandes betweenness centrality waves over the root
queries (DESIGN.md §14).

``--algo {pagerank,cc,tri,kcore}`` runs a §19 vertex program (root-free
global analytics) on the same butterfly exchange: the run reports rounds,
edge-examination rate, and an algo-specific summary (top ranks / component
count / triangle total / degeneracy); ``--trace`` exports the convergence
flight-recorder rows (POP column = residual ppm, labels changed, or peel
count — see ``repro.core.flightrec``).

``--stats-json PATH`` dumps the run's ``EngineStats`` (plus graph/config
identity and wall timing) as machine-readable JSON — the serving CLI
(``repro.launch.serve_graph``) emits the same schema extended with service
telemetry (DESIGN.md §15).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

STATS_SCHEMA = "bfs_run_stats/v1"


def write_stats_json(path, *, algo, graph, devices, config, timing_ms,
                     engine_stats, **extra) -> None:
    """Persist one run's machine-readable stats (schema asserted by the
    smoke test; ``serve_graph`` adds a ``telemetry`` extra)."""
    doc = {
        "schema": STATS_SCHEMA,
        "algo": algo,
        "graph": graph,
        "devices": devices,
        "config": config,
        "timing_ms": timing_ms,
        "engine_stats": (
            dataclasses.asdict(engine_stats)
            if dataclasses.is_dataclass(engine_stats) else engine_stats
        ),
    }
    doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--graph", default="kronecker",
                    choices=["kronecker", "urand", "torus"])
    ap.add_argument("--devices", type=int, default=None,
                    help="devices to shard over (default: every device "
                         "JAX finds)")
    ap.add_argument("--fanout", type=int, default=4)
    ap.add_argument("--sync", default="butterfly",
                    choices=["butterfly", "sparse", "adaptive", "rabenseifner",
                             "all_to_all", "xla"])
    ap.add_argument("--sparse-capacity", type=int, default=0,
                    help="first-round (word,idx)-pair capacity of the sparse "
                         "sync; 0 = auto (n_words//64)")
    ap.add_argument("--density-threshold", type=float, default=0.02,
                    help="adaptive sync: go sparse while max popcount <= "
                         "threshold * bitmap bits")
    ap.add_argument("--mode", default="top_down",
                    choices=["top_down", "bottom_up", "direction_optimizing"])
    ap.add_argument("--algo", default="bfs",
                    choices=["bfs", "sssp", "bc",
                             "pagerank", "cc", "tri", "kcore"],
                    help="traversal workload (bfs/sssp/bc) or §19 vertex "
                         "program (pagerank, connected components, triangle "
                         "counting, k-core decomposition)")
    ap.add_argument("--max-weight", type=int, default=0,
                    help="uint32 edge weights in [1, max-weight]; 0 = "
                         "unweighted (sssp defaults to 64)")
    ap.add_argument("--delta", type=int, default=0,
                    help="sssp bucket width (delta-stepping-style); 0 = "
                         "level-synchronous relaxation")
    ap.add_argument("--roots", type=int, default=16,
                    help="number of root queries to run")
    ap.add_argument("--num-sources", type=int, default=1,
                    help="BFS lanes per wave: 1 = classic single-source; "
                         ">1 packs the root queries into bit-parallel "
                         "multi-source waves (analytics.msbfs)")
    ap.add_argument("--updates", default=None, metavar="FILE",
                    help="replay a recorded JSONL edge-update stream "
                         "(serve_graph --record-updates) through the §16 "
                         "delta overlay + partition patch before measuring")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="dump EngineStats + run identity as JSON")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="export the §18 per-level flight-recorder trace "
                         "of one traversal (first root) as Perfetto/Chrome "
                         "trace_event JSON; --algo bfs additionally "
                         "host-times every level so spans carry real "
                         "durations")
    ap.add_argument("--profile", default=None, metavar="FILE", nargs="?",
                    const="-",
                    help="run the §20 cost-model profiler on the compiled "
                         "single-source BFS program: reconcile analytic "
                         "sync bytes against the compiled HLO, report "
                         "achieved-vs-modeled GTEPS and the per-level "
                         "time×bytes table; FILE (optional) also receives "
                         "the profile as JSON")
    args = ap.parse_args(argv)
    if args.profile and args.algo != "bfs":
        ap.error("--profile profiles the single-source BFS program; "
                 "use --algo bfs")

    import time

    import jax
    import numpy as np

    from repro.core import bfs
    from repro.graph import csr, generators, partition
    from repro.launch import devices as devices_mod

    try:
        args.devices = devices_mod.resolve_device_count(args.devices)
    except ValueError as e:
        ap.error(str(e))
    on = devices_mod.device_label(args.devices)
    max_weight = args.max_weight
    if args.algo == "sssp" and not max_weight:
        max_weight = 64

    def make_graph(timings):
        if args.graph == "kronecker":
            return generators.kronecker(
                args.scale, args.edge_factor, seed=args.seed,
                max_weight=max_weight, timings=timings)
        if args.graph == "urand":
            return generators.uniform_random(
                1 << args.scale, (1 << args.scale) * args.edge_factor,
                seed=args.seed, max_weight=max_weight,
            )
        return generators.torus_2d(1 << (args.scale // 2),
                                   max_weight=max_weight, seed=args.seed)

    g, pg, etl_line = devices_mod.partitioned_graph(make_graph, args.devices)
    print(f"graph: n={g.n:,} m={g.n_edges:,} (directed, symmetrized"
          f"{', weighted' if g.weighted else ''})")
    print(etl_line)
    if args.updates:
        from repro.dynamic import delta as delta_mod

        overlay = delta_mod.DeltaOverlay(g)
        n_ins = n_del = n_comp = 0
        for batch in delta_mod.read_update_stream(args.updates):
            if g.weighted and batch.insert_weights is None:
                # replaying an unweighted stream onto a weighted graph:
                # unit weights keep the stream applicable
                batch = delta_mod.EdgeBatch(
                    insert_src=batch.insert_src,
                    insert_dst=batch.insert_dst,
                    insert_weights=np.ones(batch.insert_src.size, np.uint32),
                    delete_src=batch.delete_src,
                    delete_dst=batch.delete_dst,
                )
            update = overlay.apply(batch)
            n_ins += update.ins_src.size
            n_del += update.del_src.size
            if (not delta_mod.apply_update_to_partition(pg, update)
                    or overlay.needs_compaction()):
                pg = partition.partition_1d(overlay.compact(), args.devices)
                n_comp += 1
        g = overlay.current_graph()
        print(f"replayed updates: {n_ins} directed inserts, {n_del} "
              f"deletes, {n_comp} compactions -> m={g.n_edges:,}")
    mesh = jax.make_mesh((args.devices,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = bfs.BFSConfig(
        axes=("data",), fanout=args.fanout, sync=args.sync, mode=args.mode,
        sparse_capacity=args.sparse_capacity,
        density_threshold=args.density_threshold,
    )
    rng = np.random.default_rng(args.seed)
    # DISTINCT roots (clamped to the big component): engine waves fold
    # duplicate roots (DESIGN.md §15), so sampling with replacement would
    # silently under-count the work behind the reported rates
    roots = csr.largest_component_roots(g, args.roots, rng).tolist()
    n_roots = len(roots)

    graph_doc = {"name": args.graph, "scale": args.scale,
                 "edge_factor": args.edge_factor, "n": g.n,
                 "n_real": g.n_real, "n_edges": g.n_edges,
                 "weighted": bool(g.weighted)}
    config_doc = {"sync": args.sync, "mode": args.mode,
                  "fanout": args.fanout, "lanes": args.num_sources,
                  "delta": args.delta, "max_weight": max_weight}

    def emit_profile(report: dict) -> None:
        """Print the §20 profile table (+ cached-program reconciliation)
        and optionally persist the whole report as JSON."""
        prof = report["program"]
        print()
        print(prof.table())
        for ent in report.get("cache", []):
            verdict = ("reconciled" if ent.reconciled else
                       "MISMATCH" if ent.supported else "unsupported")
            print(f"cached {ent.algo} sync={ent.sync} "
                  f"lanes={ent.lanes} n_words={ent.n_words}: {verdict}")
        if args.profile != "-":
            doc = {"schema": "bfs_profile/v1",
                   "program": prof.to_dict(),
                   "cache": [e.to_dict() for e in report.get("cache", [])]}
            with open(args.profile, "w") as f:
                json.dump(doc, f, indent=1)
            print(f"profile -> {args.profile}")

    def export_trace(trace) -> dict:
        """Write the Perfetto doc and return the JSON trace table (lands
        in --stats-json as a ``trace`` extra)."""
        from repro.core import flightrec

        doc = flightrec.trace_chrome_doc(trace)
        with open(args.trace, "w") as f:
            json.dump(doc, f, indent=1)
        s = trace.summary()
        print(f"trace: {s['levels']} levels ({s['dense_levels']} dense / "
              f"{s['sparse_levels']} sparse / {s['fallback_levels']} "
              f"fallback exchanges; {s['sparse_push_levels']} sparse "
              f"pushes), {s['bytes_per_node_total']:.0f} sync B/node "
              f"-> {args.trace}")
        return trace.to_dict()

    if args.algo == "sssp":
        from repro.traversal import sssp as sssp_mod

        if args.sync not in sssp_mod.SYNCS:
            ap.error(f"--algo sssp supports --sync {sssp_mod.SYNCS}, "
                     f"got {args.sync!r}")
        scfg = sssp_mod.SSSPConfig(
            axes=("data",), fanout=args.fanout, sync=args.sync,
            delta=args.delta, sparse_capacity=args.sparse_capacity,
            density_threshold=args.density_threshold,
        )
        arrays = bfs.place_arrays(pg, mesh, scfg.axes)
        fn = sssp_mod.build_sssp_fn(pg, mesh, scfg)
        d, it, relaxed = fn(arrays, np.int32(roots[0]))  # warmup / compile
        jax.block_until_ready(d)
        times, rates, relaxed_total = [], [], 0.0
        for r in roots:
            t0 = time.time()
            d, it, relaxed = fn(arrays, np.int32(r))
            jax.block_until_ready(d)
            dt = time.time() - t0
            times.append(dt)
            rates.append(float(relaxed[0]) / dt / 1e9)
            relaxed_total += float(relaxed[0])
        t = np.array(times)
        print(
            f"SSSP {scfg.sync} fanout={args.fanout} delta={args.delta} "
            f"devices={args.devices}: time {t.mean()*1e3:.1f}ms  "
            f"GRelax/s {np.mean(rates):.4f} ({on})"
        )
        trace_doc = None
        if args.trace:
            from repro.core import flightrec

            n_rows = sssp_mod.dist_rows(pg)
            tfn = sssp_mod.build_sssp_fn(pg, mesh, scfg, trace=True)
            _, _, _, buf = tfn(arrays, np.int32(roots[0]))
            trace_doc = export_trace(flightrec.TraversalTrace.from_buffer(
                np.asarray(buf), algo="sssp", sync=scfg.sync, p=pg.p,
                fanout=scfg.fanout, n_words=n_rows,
                capacity=scfg.resolved_capacity(n_rows),
                density_threshold=scfg.density_threshold,
            ))
        if args.stats_json:
            from repro.analytics.engine import EngineStats

            write_stats_json(
                args.stats_json, algo="sssp", graph=graph_doc,
                devices=args.devices, config=config_doc,
                timing_ms={"mean": float(t.mean() * 1e3),
                           "total": float(t.sum() * 1e3)},
                engine_stats=EngineStats(
                    sssp_queries=len(roots), relaxed_edges=relaxed_total
                ),
                **({"trace": trace_doc} if trace_doc else {}),
            )
        return 0

    if args.algo == "bc":
        from repro.analytics.engine import BFSQueryEngine

        lanes = max(args.num_sources, 1)
        eng = BFSQueryEngine(pg, mesh, cfg, lanes=lanes)
        eng.betweenness(roots[:lanes])  # warmup / compile
        t0 = time.time()
        bc_scores = eng.betweenness(np.asarray(roots, np.int32))
        dt = time.time() - t0
        top = np.argsort(bc_scores)[::-1][:5]
        print(
            f"BC {args.sync} fanout={args.fanout} devices={args.devices} "
            f"lanes={lanes}: {n_roots} sources in {dt*1e3:.1f}ms "
            f"({n_roots/dt:.1f} sources/s; {on})"
        )
        print("top-5 central vertices:",
              ", ".join(f"{v}={bc_scores[v]:.1f}" for v in top))
        trace_doc = None
        if args.trace:
            from repro.analytics import msbfs as ms
            from repro.core import flightrec
            from repro.traversal import bc as bc_mod

            # flattened lane-word buffer the forward-wave sync exchanges
            n_flat = ms.wave_rows(pg) * ms.lane_words(lanes)
            arrays = bfs.place_arrays(pg, mesh, cfg.axes)
            tfn = bc_mod.build_bc_fn(pg, mesh, cfg, lanes, trace=True)
            out = tfn(arrays, np.asarray(
                (roots[:lanes] + [-1] * lanes)[:lanes], np.int32))
            trace_doc = export_trace(flightrec.TraversalTrace.from_buffer(
                np.asarray(out[-1]), algo="bc", sync=cfg.sync, p=pg.p,
                fanout=cfg.fanout, n_words=n_flat,
                capacity=cfg.resolved_capacity(n_flat),
                density_threshold=cfg.density_threshold,
            ))
        if args.stats_json:
            write_stats_json(
                args.stats_json, algo="bc", graph=graph_doc,
                devices=args.devices, config=config_doc,
                timing_ms={"mean": dt * 1e3 / max(n_roots, 1),
                           "total": dt * 1e3},
                engine_stats=eng.stats,
                **({"trace": trace_doc} if trace_doc else {}),
            )
        return 0

    if args.algo in ("pagerank", "cc", "tri", "kcore"):
        from repro import programs
        from repro.analytics.engine import BFSQueryEngine, EngineStats

        if args.sync not in programs.SYNCS:
            ap.error(f"--algo {args.algo} supports --sync {programs.SYNCS}, "
                     f"got {args.sync!r}")
        prog = programs.by_name(args.algo)
        pcfg = programs.ProgramConfig(
            axes=("data",), fanout=args.fanout, sync=args.sync,
            sparse_capacity=args.sparse_capacity,
            density_threshold=args.density_threshold,
        )
        eng = BFSQueryEngine(pg, mesh, cfg)
        eng.run_program(args.algo, pcfg)  # warmup / compile
        eng.stats = EngineStats()
        reps = 3  # programs are root-free: a few reps average the timing
        times = []
        for _ in range(reps):
            t0 = time.time()
            res, iters, work = eng.run_program(args.algo, pcfg)
            times.append(time.time() - t0)
        t = np.array(times)
        print(
            f"{args.algo} {args.sync} fanout={args.fanout} "
            f"devices={args.devices}: {iters} rounds in {t.mean()*1e3:.1f}ms"
            f"  GEdge/s {work/t.mean()/1e9:.4f} ({on})"
        )
        if args.algo == "pagerank":
            top = np.argsort(res)[::-1][:5]
            print("top-5 ranked vertices:",
                  ", ".join(f"{v}={res[v]:.2e}" for v in top))
        elif args.algo == "cc":
            print(f"components: {np.unique(res[:g.n_real]).size}")
        elif args.algo == "tri":
            print(f"total triangles: {programs.total_triangles(res):,}")
        else:
            print(f"max core number: {int(res.max())} "
                  f"(degeneracy of the symmetrized graph)")
        trace_doc = None
        if args.trace:
            from repro.core import flightrec

            n_words = programs.program_msg_words(pg, prog)
            arrays = bfs.place_arrays(pg, mesh, pcfg.axes)
            tfn = programs.build_program_fn(pg, mesh, prog, pcfg, trace=True)
            out = tfn(arrays, prog.default_arg(pg))
            trace_doc = export_trace(flightrec.TraversalTrace.from_buffer(
                np.asarray(out[-1]), algo=args.algo, sync=pcfg.sync, p=pg.p,
                fanout=pcfg.fanout, n_words=n_words,
                capacity=pcfg.resolved_capacity(n_words),
                density_threshold=pcfg.density_threshold,
            ))
        if args.stats_json:
            write_stats_json(
                args.stats_json, algo=args.algo, graph=graph_doc,
                devices=args.devices, config=config_doc,
                timing_ms={"mean": float(t.mean() * 1e3),
                           "total": float(t.sum() * 1e3)},
                engine_stats=eng.stats,
                **({"trace": trace_doc} if trace_doc else {}),
            )
        return 0

    if args.num_sources > 1:
        from repro.analytics.engine import BFSQueryEngine, EngineStats

        eng = BFSQueryEngine(pg, mesh, cfg, lanes=args.num_sources)
        eng.query(roots[: args.num_sources])  # warmup / compile
        eng.stats = EngineStats()
        t0 = time.time()
        eng.query(np.asarray(roots, np.int32))
        dt = time.time() - t0
        print(
            f"MS-BFS {args.sync} fanout={args.fanout} mode={args.mode} "
            f"devices={args.devices} lanes={args.num_sources}: "
            f"{n_roots} searches in {dt*1e3:.1f}ms over {eng.stats.waves} "
            f"waves  ({n_roots/dt:.1f} searches/s, aggregate GTEP/s "
            f"{eng.stats.scanned_edges/dt/1e9:.4f}; {on})"
        )
        trace_doc = None
        if args.trace:
            from repro.analytics import msbfs as ms
            from repro.core import flightrec

            n_flat = ms.wave_rows(pg) * ms.lane_words(args.num_sources)
            arrays = bfs.place_arrays(pg, mesh, cfg.axes)
            tfn = ms.build_msbfs_fn(pg, mesh, cfg, args.num_sources,
                                    trace=True)
            wave = (roots[: args.num_sources]
                    + [-1] * args.num_sources)[: args.num_sources]
            _, _, _, buf = tfn(arrays, np.asarray(wave, np.int32))
            trace_doc = export_trace(flightrec.TraversalTrace.from_buffer(
                np.asarray(buf), algo="msbfs", sync=cfg.sync, p=pg.p,
                fanout=cfg.fanout, n_words=n_flat,
                capacity=cfg.resolved_capacity(n_flat),
                density_threshold=cfg.density_threshold,
            ))
        if args.profile:
            emit_profile(eng.profile(roots[0]))
        if args.stats_json:
            write_stats_json(
                args.stats_json, algo="bfs", graph=graph_doc,
                devices=args.devices, config=config_doc,
                timing_ms={"mean": dt * 1e3 / max(n_roots, 1),
                           "total": dt * 1e3},
                engine_stats=eng.stats,
                **({"trace": trace_doc} if trace_doc else {}),
            )
        return 0

    arrays = bfs.place_arrays(pg, mesh, cfg.axes)
    fn = bfs.build_bfs_fn(pg, mesh, cfg)
    # warmup / compile
    d, lvl, scanned = fn(arrays, np.int32(roots[0]))
    jax.block_until_ready(d)

    times, gteps = [], []
    scanned_total, max_lvl = 0.0, 0
    for r in roots:
        t0 = time.time()
        d, lvl, scanned = fn(arrays, np.int32(r))
        jax.block_until_ready(d)
        dt = time.time() - t0
        times.append(dt)
        gteps.append(float(scanned[0]) / dt / 1e9)
        scanned_total += float(scanned[0])
        max_lvl = max(max_lvl, int(np.max(lvl)))
    # paper protocol: drop fastest/slowest quartile
    order = np.argsort(times)
    keep = order[len(order) // 4 : -len(order) // 4] if len(order) >= 8 else order
    t = np.array(times)[keep]
    g_ = np.array(gteps)[keep]
    print(
        f"BFS {args.sync} fanout={args.fanout} mode={args.mode} "
        f"devices={args.devices}: time {t.mean()*1e3:.1f}ms  "
        f"GTEP/s {g_.mean():.4f} ({on})"
    )
    trace_doc = None
    if args.trace:
        from repro.core import flightrec

        # host-timed segmented execution: per-level wall clock next to the
        # in-program sync/branch/byte attribution (DESIGN.md §18)
        _, tr = flightrec.timed_bfs_levels(
            pg, mesh, cfg, roots[0], arrays=arrays
        )
        trace_doc = export_trace(tr)
    if args.profile:
        from repro.core import profiler

        emit_profile({"program": profiler.profile_bfs(
            pg, mesh, cfg, roots[0], arrays=arrays
        ), "cache": []})
    if args.stats_json:
        from repro.analytics.engine import EngineStats

        write_stats_json(
            args.stats_json, algo="bfs", graph=graph_doc,
            devices=args.devices, config=config_doc,
            timing_ms={"mean": float(t.mean() * 1e3),
                       "total": float(np.sum(times) * 1e3)},
            engine_stats=EngineStats(
                queries=len(roots), waves=len(roots),
                scanned_edges=scanned_total, max_levels=max_lvl,
            ),
            **({"trace": trace_doc} if trace_doc else {}),
        )
    return 0


if __name__ == "__main__":
    from repro.launch import devices

    devices.enable_compile_cache()
    raise SystemExit(main())
