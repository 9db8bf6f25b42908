"""Serving benchmark: sustained event throughput + query staleness per
method — the paper's update-cost comparison restated in service units.

For each method the same synthetic temporal feed (one dataset, fixed
event count, fixed flush policy) is driven through the full serve path
(ingest → coalesce → apply_batch → rank update → publish) with a query
burst every ``query_every`` events.  Emitted rows:

    serving/<method>            us per *event* end-to-end, derived =
                                events/s, p99 update latency, p99
                                query staleness (events), mean
                                |affected|, static fallbacks

The 131k-vertex RMAT section (graph via the seeded ``common`` cache,
built once for the whole suite) compares the XLA f64 engine, the kernel
engine (autotuned geometry, fused update+sweep, incremental PackedGraph
maintenance + hybrid-precision ladder) and the **sharded** kernel engine
(window-range shards + routed deltas + boundary-halo exchange over a
``model`` mesh spanning every visible device — force more with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) on the same
stream, emits the events/s deltas per method plus each engine's
``comm_bytes`` / ``device_programs_per_batch`` counters and the tuned
geometry, and times one incremental ``apply_batch_packed`` against a
full host ``pack_blocks`` rebuild — all registered in ``run.py --json``.

Wall-clock on a CPU host does not show the TPU win, so the kernel-vs-XLA
comparison is ALSO emitted **roofline-normalized** (the ``*_modeled``
rows): device seconds modeled from each engine's recorded work counters
via ``roofline.analysis`` — the XLA f64 engine re-streams the full edge
list every iteration with random-access gather/scatter (sector-
inflated, ``dense_spmv_iteration_cost``), the kernel engine pays
``gated_spmv_iteration_cost`` per sweep (the ungated XLA gather of
``rsc[src]`` over every packed lane, then the gated windows' f32 lanes
at element width, and the MXU passes of every grid step), and its
cross-shard halo bytes ride the interconnect.  The modeled ratio is the number the ≥3x acceptance gate
and the CI regression check read.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, rmat_dataset, time_fn
from repro.data.snap import load_temporal
from repro.obs import timeit
from repro.serve import IngestQueue, QueryClient, RankStore, ServeEngine, \
    ServeMetrics, preload_graph_and_feed

METHODS = ("traversal", "frontier", "frontier_prune")
RMAT_METHODS = ("frontier", "frontier_prune")

def _modeled_seconds(m, num_edges, num_vertices, engine, serve):
    """Roofline device time for one serve run from its recorded work
    counters (see module docstring; model in roofline.analysis)."""
    from repro.roofline.analysis import (PEAKS, TARGET_KIND,
                                         dense_spmv_iteration_cost,
                                         gated_spmv_iteration_cost)
    iters = m["iterations_mean"] * m["batches"]
    if engine == "xla":
        return iters * dense_spmv_iteration_cost(
            num_edges=num_edges, num_vertices=num_vertices)["total_s"]
    # every iteration (the f64 polish's included) is charged as one gated
    # sweep over the whole pack at the run's mean active entries (live
    # lanes / BE) and windows; halo bytes ride the interconnect
    # (single-pod comm = 0)
    be, vb = serve.kernel_geometry.be, serve.kernel_geometry.vb
    per_iter = max(1.0, iters)
    sweep = gated_spmv_iteration_cost(
        total_entries=serve.packed.src.size // be,
        active_entries=m["edges_processed"] / be / per_iter,
        active_windows=m["vertices_processed"] / vb / per_iter,
        be=be, vb=vb)["total_s"]
    return iters * sweep + m["comm_bytes"] / PEAKS[TARGET_KIND]["link_bw"]


def _mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()), ("model",))


def _serve_once(ds, events, method, flush_size=64, query_every=100,
                topk=10, seed=0, engine="xla", kernel_opts=None,
                mesh=None, monitor=None):
    graph, feed = preload_graph_and_feed(ds, events)
    # short deadline: while the engine is busy, pending events coalesce
    # into full flush_size batches (the adaptive micro-batching regime)
    ingest = IngestQueue(flush_size=flush_size, flush_interval=5e-3,
                         max_pending=max(events, 8 * flush_size))
    store = RankStore()
    engine = ServeEngine(graph, ingest, store, method=method,
                         engine=engine, kernel_opts=kernel_opts,
                         mesh=mesh, monitor=monitor)
    engine.bootstrap()
    rng = np.random.default_rng(seed)
    # warm the compiled step so the timed run measures steady state
    u, v = int(feed[0, 0]), int(feed[0, 1])
    ingest.submit_insert(u, v)
    engine.drain()

    # fresh metrics AFTER warm-up: the reported p50/p99 must be
    # steady-state serving latency, not the one-time compile
    metrics = ServeMetrics()
    engine.metrics = metrics
    client = QueryClient(store, ingest, metrics)

    with timeit() as t:
        for i in range(1, len(feed)):
            ingest.submit_insert(int(feed[i, 0]), int(feed[i, 1]))
            engine.step()
            if (i + 1) % query_every == 0:
                client.get_ranks(rng.integers(0, ds.num_vertices, size=4))
                client.top_k(topk)
        engine.drain()
    return t.seconds, len(feed) - 1, metrics.as_dict(), engine


def run(dataset="sx-mathoverflow", events=600, flush_size=64,
        query_every=100, rmat_events=320, monitor_events=4096):
    ds = load_temporal(dataset)
    for method in METHODS:
        wall, n, m, _ = _serve_once(ds, events, method, flush_size,
                                    query_every)
        emit(f"serving/{method}", wall / max(1, n),
             f"events_per_s={n / wall:.1f};"
             f"p99_update_ms={m['update_latency_p99_ms']:.1f};"
             f"p99_staleness_ev={m['staleness_p99_events']:.0f};"
             f"affected={m['affected_mean']:.0f};"
             f"fallbacks={m['static_fallbacks']}")

    # ---- correctness-monitor overhead (sentinels + recorder on every
    # batch, background shadow verification sampling 1/64) ---------------
    # long enough that the timed window spans many multiples of the
    # shadow period, so the sampled reference solves land inside it and
    # the ratio is an honest steady-state cost, not a lucky miss.  The
    # acceptance bar is <=5% events/s overhead (check_regression gates
    # rows named monitor_overhead at an absolute floor, no baseline
    # needed).
    from repro.obs import CorrectnessMonitor, MonitorConfig
    wall0, n0, _, _ = _serve_once(ds, monitor_events, "frontier_prune",
                                  flush_size, query_every)
    # latency/staleness SLOs are meaningless for a firehose feed on a
    # CPU bench host, so park them out of reach: the incidents count in
    # the row then reflects correctness violations only
    mon = CorrectnessMonitor(MonitorConfig(
        shadow_every=64, latency_slo_ms=1e9, staleness_slo_events=10**9))
    wall1, n1, mm, _ = _serve_once(ds, monitor_events, "frontier_prune",
                                   flush_size, query_every, monitor=mon)
    mon.close()
    rate0, rate1 = n0 / wall0, n1 / wall1
    emit(f"serving/{ds.name}/monitor_overhead", 0.0,
         f"events_per_s_ratio={rate1 / rate0:.3f};shadow_every=64;"
         f"shadow_samples={int(mm.get('shadow_samples', 0))};"
         f"incidents={int(mm.get('incidents_total', 0))};"
         f"events_per_s_plain={rate0:.1f};"
         f"events_per_s_monitored={rate1:.1f}")

    # ---- xla vs kernel vs sharded-kernel, 131k-vertex RMAT stream ------
    rmat = rmat_dataset()
    mesh = _mesh()
    shards = int(mesh.shape["model"])
    graph0, _ = preload_graph_and_feed(rmat, rmat_events)
    num_edges = int(graph0.num_valid_edges()) + rmat_events
    geometry_emitted = False
    for method in RMAT_METHODS:
        rate, modeled = {}, {}
        for eng, m_arg in (("xla", None), ("kernel", None),
                           ("sharded_kernel", mesh)):
            wall, n, m, serve = _serve_once(rmat, rmat_events, method,
                                            flush_size, query_every,
                                            engine=eng.split("_")[-1],
                                            mesh=m_arg)
            rate[eng] = n / wall
            modeled[eng] = n / max(1e-12,
                                   _modeled_seconds(m, num_edges,
                                                    rmat.num_vertices,
                                                    eng, serve))
            extra = f";shards={shards}" if m_arg is not None else ""
            emit(f"serving/{rmat.name}/{method}/{eng}", wall / max(1, n),
                 f"events_per_s={rate[eng]:.1f};"
                 f"p99_update_ms={m['update_latency_p99_ms']:.1f};"
                 f"affected={m['affected_mean']:.0f};"
                 f"rebuilds={m['packed_rebuilds']};"
                 f"progs_per_batch={m['device_programs_per_batch']:.1f};"
                 f"comm_bytes={m['comm_bytes']}{extra}")
            if eng == "kernel" and not geometry_emitted and \
                    serve.kernel_geometry is not None:
                geometry_emitted = True
                info = serve.tune_info
                emit(f"serving/{rmat.name}/tuned_geometry",
                     info.tune_time_s if info else 0.0,
                     serve.kernel_geometry.describe()
                     + (f";source={info.source};key={info.key}" if info
                        else ";source=explicit"))
            if eng == "sharded_kernel":
                sh = serve._sharded
                ci = getattr(sh, "last_comm_info", {}) or {}
                v_pad = sh.spec.padded_vertices
                slots = ci.get("halo_slots", 0)
                emit(f"serving/{rmat.name}/{method}/halo",
                     0.0,
                     f"halo_slots={slots};v_pad={v_pad};"
                     f"slots_over_v={slots / max(1, v_pad):.4f};"
                     f"shards={shards}")
        emit(f"serving/{rmat.name}/{method}/kernel_vs_xla", 0.0,
             f"events_per_s_ratio={rate['kernel'] / rate['xla']:.2f}")
        emit(f"serving/{rmat.name}/{method}/sharded_kernel_vs_xla", 0.0,
             f"events_per_s_ratio="
             f"{rate['sharded_kernel'] / rate['xla']:.2f};shards={shards}")
        # roofline-normalized ratios: the acceptance-gate numbers (the
        # CPU host can't show the TPU memory-hierarchy win in wall time)
        emit(f"serving/{rmat.name}/{method}/kernel_vs_xla_modeled", 0.0,
             f"events_per_s_ratio="
             f"{modeled['kernel'] / modeled['xla']:.2f}")
        emit(f"serving/{rmat.name}/{method}/sharded_kernel_vs_xla_modeled",
             0.0, f"events_per_s_ratio="
             f"{modeled['sharded_kernel'] / modeled['xla']:.2f};"
             f"shards={shards}")

    # ---- incremental PackedGraph update vs full host repack ------------
    from repro.graph.dynamic import make_batch_update
    from repro.kernels.pagerank_spmv.update import apply_batch_packed, \
        pack_graph
    from repro.serve.engine import KERNEL_PACK_DEFAULTS
    graph, feed = preload_graph_and_feed(rmat, rmat_events)
    packed = pack_graph(graph, **KERNEL_PACK_DEFAULTS)
    upd = make_batch_update(np.zeros((0, 2), np.int32),
                            feed[:flush_size], 8, max(8, flush_size))
    t_upd, _ = time_fn(apply_batch_packed, packed, upd, check=False)
    t_pack, _ = time_fn(pack_graph, graph, **KERNEL_PACK_DEFAULTS)
    emit(f"serving/{rmat.name}/pack_update/incremental", t_upd,
         f"entries={packed.num_entries}")
    emit(f"serving/{rmat.name}/pack_update/rebuild", t_pack, "")
    emit(f"serving/{rmat.name}/pack_update/speedup", 0.0,
         f"rebuild_over_update={t_pack / max(t_upd, 1e-12):.1f}")


# span taxonomy the phase-breakdown mode reports (DESIGN.md §11); names
# absent from a run (e.g. kernel-only phases on the xla engine) are
# skipped rather than emitted as zeros
PHASES = ("serve.step", "ingest.coalesce", "route_update", "solve",
          "fused_update_loop", "kernel_loop.f32", "polish.f64",
          "snapshot.publish", "ppr.repair")


def run_traced(dataset="sx-mathoverflow", events=600, flush_size=64,
               trace_path=None, engine="xla"):
    """Phase-breakdown pass: the same serve run with the obs tracer on,
    emitting mean span duration per phase as ``serving/<ds>/phase/<name>``
    rows (+ the batch frontier-telemetry digest), and writing the
    Chrome-trace JSON to ``trace_path`` for the nightly artifact."""
    from repro import obs

    ds = load_temporal(dataset)
    with obs.tracing(trace_path) as tr:
        wall, n, m, _ = _serve_once(ds, events, "frontier_prune",
                                    flush_size, engine=engine)
        for name in PHASES:
            spans = tr.spans(name)
            if not spans:
                continue
            emit(f"serving/{ds.name}/phase/{name}",
                 float(np.mean([s.dur for s in spans])),
                 f"count={len(spans)};"
                 f"total_ms={sum(s.dur for s in spans) * 1e3:.1f}")
    emit(f"serving/{ds.name}/phase/traced_overhead", wall / max(1, n),
         f"events_per_s_traced={n / wall:.1f};"
         f"frontier_batches={m.get('frontier_batches', 0)};"
         f"frontier_iters_mean={m.get('frontier_iterations_mean', 0.0):.1f}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="",
                    help="run the traced phase-breakdown pass and write "
                         "the Chrome-trace JSON here (skips the full "
                         "untraced suite)")
    ap.add_argument("--engine", default="xla", choices=["xla", "kernel"])
    a = ap.parse_args()
    if a.trace:
        run_traced(trace_path=a.trace, engine=a.engine)
    else:
        run()
