"""Online rank-serving driver: replay a SNAP temporal stream as a timed
event feed against the repro.serve service, interleaving rank queries.

The first 90% of the temporal edges preload G⁰ (paper §5.1.4); the rest
arrive one event at a time through the ingest queue (optionally paced at
``--rate`` events/s), the engine micro-batches them, and every
``--query-every`` events a query burst (point ranks + top-k) is served
from the current snapshot.  Prints the metrics summary and ``serve
complete``; exits non-zero if fewer than ``--min-queries`` queries were
served (CI smoke contract).

    PYTHONPATH=src python -m repro.launch.serve \
        --dataset sx-mathoverflow --events 5000

With ``--ckpt-dir``, (ranks, generation, last_seq) checkpoints are
written every ``--ckpt-every`` generations; on restart the driver
replays events [0, last_seq] into the graph and resumes the feed from
there — same replay-from-stream contract as launch/pagerank.py.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import repro  # noqa: F401
from repro import obs
from repro.core.api import ENGINES, METHODS
from repro.data.snap import PAPER_TABLE1, SCALES, load_temporal
from repro.graph.dynamic import apply_batch, make_batch_update
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import auto_mesh
from repro.launch.pagerank import MESHES as PAGERANK_MESHES
from repro.launch.pagerank import _resolve_mesh as resolve_pagerank_mesh
from repro.ppr import IndexConfig
from repro.serve import IngestQueue, QueryClient, RankStore, ServeEngine, \
    ServeMetrics, preload_graph_and_feed


MESHES = (*PAGERANK_MESHES, "model")


def _resolve_mesh(name: str):
    """``model`` puts every visible device on the ``model`` axis, the one
    the kernel engine and the PPR index shard over; the other names are
    ``launch.pagerank``'s."""
    if name == "model":
        return auto_mesh((1, len(jax.devices())), ("data", "model"))
    return resolve_pagerank_mesh(name)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sx-mathoverflow",
                    choices=list(PAPER_TABLE1))
    ap.add_argument("--scale", default="small", choices=SCALES,
                    help="synthetic stand-in size: 'small' cuts |V| for "
                         "CPU runs, 'paper' keeps Table 1's |V| and |E_T|")
    ap.add_argument("--method", default="frontier_prune", choices=METHODS)
    ap.add_argument("--engine", default="xla", choices=list(ENGINES),
                    help="rank-update engine: 'xla' (f64 segment_sum) or "
                         "'kernel' (Pallas frontier-gated SpMV with "
                         "device-side incremental PackedGraph maintenance "
                         "and the f32→f64 hybrid-precision ladder); "
                         "combined with --mesh the kernel path shards the "
                         "packed structure by dst-window ranges over the "
                         "mesh's model axis (on CPU force devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N, DESIGN.md §9)")
    ap.add_argument("--events", type=int, default=5000,
                    help="number of post-preload edge events to feed")
    ap.add_argument("--flush-size", type=int, default=64)
    ap.add_argument("--flush-interval-ms", type=float, default=50.0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="event feed pacing in events/s (0 = unpaced)")
    ap.add_argument("--query-every", type=int, default=100,
                    help="issue a query burst every K submitted events")
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--static-fallback-frac", type=float, default=0.25)
    ap.add_argument("--ppr-walks", type=int, default=0,
                    help="maintain a PPR walk index with R walks/vertex "
                         "(0 = off); query bursts then include an "
                         "index-backed personalized top-k; combined with "
                         "--mesh the index is range-sharded over the "
                         "mesh's model axis and repaired per shard "
                         "(DESIGN.md §14)")
    ap.add_argument("--ppr-len", type=int, default=16,
                    help="walk-index max length L (with --ppr-walks)")
    ap.add_argument("--mesh", choices=MESHES, default="none")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint every K generations (with --ckpt-dir)")
    ap.add_argument("--min-queries", type=int, default=0,
                    help="exit non-zero unless this many queries were served")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace JSON of the serve run here "
                         "(enables span tracing + per-iteration frontier "
                         "telemetry; rows land in <PATH>.frontier.jsonl)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="run a Prometheus scrape server on this port "
                         "(0 = ephemeral, printed; -1 = off)")
    ap.add_argument("--metrics-path", default="",
                    help="write the final Prometheus exposition text here")
    ap.add_argument("--monitor", action="store_true",
                    help="enable correctness monitoring: invariant "
                         "sentinels, sampled shadow verification, flight "
                         "recorder, SLO burn-rate alerts (DESIGN.md §12)")
    ap.add_argument("--shadow-every", type=int, default=64,
                    help="shadow-verify every Kth micro-batch against "
                         "the f64 reference solve (0 = off)")
    ap.add_argument("--incident-dir", default="",
                    help="dump a replayable flight-recorder bundle here "
                         "on the first error-severity incident "
                         "(implies --monitor)")
    ap.add_argument("--inject-fault", default="",
                    help="DEBUG: corrupt the engine at a generation, as "
                         "GEN[:KIND[:VERTEX[:SCALE]]] with KIND rank|"
                         "event (e.g. 5:rank:0:4.0); implies --monitor")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run(args):
    """Serve the feed ``args`` (``build_parser``) describes; returns
    ``(engine, store, metrics)`` after the last event, or None when a
    checkpoint cannot be resumed."""
    mesh = _resolve_mesh(args.mesh)
    ds = load_temporal(args.dataset, scale=args.scale)
    graph, events = preload_graph_and_feed(ds, args.events)
    shards = (f" shards={int(mesh.shape['model'])}"
              if mesh is not None and args.engine == "kernel" else "")
    print(f"dataset {ds.name}: |V|={ds.num_vertices:,} preload="
          f"{int(graph.num_valid_edges()):,} events={len(events):,} "
          f"method={args.method} engine={args.engine}{shards} "
          f"flush={args.flush_size}/{args.flush_interval_ms:g}ms")

    metrics = ServeMetrics()
    store = RankStore(ckpt_dir=args.ckpt_dir or None,
                      ckpt_every=args.ckpt_every)
    restored = store.restore_latest(ds.num_vertices) if args.ckpt_dir \
        else None
    start_event = 0
    if restored is not None:
        ranks, gen, last_seq = restored
        start_event = last_seq + 1
        if start_event > len(events):
            # the checkpointed ranks reflect events this run's feed does
            # not contain — replaying a truncated prefix would publish a
            # graph inconsistent with the restored ranks/last_seq
            print(f"FAIL: checkpoint last_seq={last_seq} exceeds the "
                  f"--events {args.events} feed; rerun with --events > "
                  f"{last_seq} (or a fresh --ckpt-dir)")
            return None
        store.seed_generation(gen)             # gen clock survives restart
        if start_event > 0:         # replay the already-served prefix
            replay = events[:start_event]
            graph = apply_batch(graph, make_batch_update(
                np.zeros((0, 2)), replay, 8, max(8, len(replay))))
        print(f"restored generation {gen}; replayed {start_event} events")
    ingest = IngestQueue(flush_size=args.flush_size,
                         flush_interval=args.flush_interval_ms * 1e-3,
                         start_seq=start_event)
    ppr_cfg = (IndexConfig(num_walks=args.ppr_walks, max_len=args.ppr_len,
                           seed=args.seed)
               if args.ppr_walks > 0 else None)
    monitor = incident_sink = None
    if args.monitor or args.incident_dir or args.inject_fault:
        if args.incident_dir and args.trace:
            incident_sink = obs.JsonlSink(args.trace + ".incidents.jsonl")
        monitor = obs.CorrectnessMonitor(
            obs.MonitorConfig(shadow_every=args.shadow_every,
                              incident_dir=args.incident_dir or None),
            sink=incident_sink)
        print(f"correctness monitor on: shadow 1/{args.shadow_every}"
              + (f" incidents -> {args.incident_dir}"
                 if args.incident_dir else ""))
    engine = ServeEngine(graph, ingest, store, metrics=metrics,
                         method=args.method, mesh=mesh,
                         engine=args.engine,
                         static_fallback_frac=args.static_fallback_frac,
                         ppr_index=ppr_cfg, monitor=monitor)
    if args.inject_fault:
        parts = args.inject_fault.split(":")
        engine.inject_fault(
            int(parts[0]),
            kind=parts[1] if len(parts) > 1 else "rank",
            vertex=int(parts[2]) if len(parts) > 2 else 0,
            scale=float(parts[3]) if len(parts) > 3 else 2.0)
        print(f"fault armed: {args.inject_fault}")
    sink = None
    if args.trace:
        obs.start_tracing(args.trace)
        sink = obs.JsonlSink(args.trace + ".frontier.jsonl")
        engine.telemetry_sink = sink
        print(f"tracing to {args.trace} "
              f"(frontier rows: {args.trace}.frontier.jsonl)")
    exporter = None
    if args.metrics_port >= 0 or args.metrics_path:
        exporter = obs.MetricsExporter(metrics)
        if args.metrics_port >= 0:
            port = exporter.serve(port=args.metrics_port)
            print(f"metrics: http://127.0.0.1:{port}/metrics")
    if restored is not None:
        engine.bootstrap(ranks=restored[0], last_seq=start_event - 1)
    else:
        engine.bootstrap()
    if engine.kernel_geometry is not None:
        info = engine.tune_info
        how = (f"{info.source}"
               f"{' (cache hit)' if info.cache_hit else ''} "
               f"key={info.key} in {info.tune_time_s * 1e3:.1f}ms"
               if info is not None else "explicit (tuning off)")
        print(f"kernel geometry: {engine.kernel_geometry.describe()} "
              f"via {how}")
    client = QueryClient(store, ingest, metrics)
    rng = np.random.default_rng(args.seed)

    t0 = time.perf_counter()
    next_due = t0
    for i in range(start_event, len(events)):
        if args.rate > 0:                     # timed feed
            next_due += 1.0 / args.rate
            lag = next_due - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
        u, v = int(events[i, 0]), int(events[i, 1])
        metrics.record_admission(ingest.submit_insert(u, v) is not None)
        engine.step()                          # flush when size/deadline hit
        if args.query_every and (i + 1) % args.query_every == 0:
            verts = rng.integers(0, ds.num_vertices, size=4)
            client.get_ranks(verts)
            r = client.top_k(args.topk)
            ppr_note = ""
            if args.ppr_walks > 0:
                p = client.personalized_top_k(
                    [int(verts[0])], args.topk, mode="auto")
                ppr_note = f" ppr_top1={p.vertices[0]}"
            print(f"event {i + 1:6d}: gen={r.generation:5d} "
                  f"stale={r.staleness_events:4d}ev "
                  f"top1={r.vertices[0]} ({r.ranks[0]:.3e})"
                  f"{ppr_note}", flush=True)
    engine.drain()
    wall = time.perf_counter() - t0
    engine.close()   # joins the shadow thread, flushes its mailbox
    if monitor is not None:
        print("monitor " + json.dumps(monitor.summary()))
        if incident_sink is not None:
            incident_sink.close()
    if args.trace:
        written = obs.stop_tracing()
        sink.close()
        print(f"trace written to {written}")
    if exporter is not None:
        if args.metrics_path:
            exporter.write(args.metrics_path)
            print(f"metrics written to {args.metrics_path}")
        exporter.close()

    m = metrics.as_dict()
    m["wall_s"] = wall
    m["feed_events_per_s"] = (len(events) - start_event) / wall \
        if wall > 0 else 0.0
    snap = store.snapshot()
    print("metrics " + json.dumps(
        {k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in m.items()}))
    print(f"final generation {snap.generation}, last_seq {snap.last_seq}, "
          f"queries served {m['queries_served']}")
    print("serve complete")
    return engine, store, m


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    served = run(args)
    if served is None:
        return 1
    m = served[2]
    if m["queries_served"] < args.min_queries:
        print(f"FAIL: served {m['queries_served']} < --min-queries "
              f"{args.min_queries}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
