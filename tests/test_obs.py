"""repro.obs: span tracer, frontier telemetry, exporters, and the
zero-overhead-when-off contract of the traced serving stack."""
import json
import threading
import urllib.request

import numpy as np
import pytest
import jax.numpy as jnp

import repro  # noqa: F401
from repro import obs
from repro.core import kernel_engine as ke
from repro.core import pagerank as pr
from repro.graph.generators import erdos_renyi_edges, rmat_edges
from repro.graph.structure import from_coo
from repro.kernels.pagerank_spmv.update import pack_graph
from repro.obs.frontier import FIELDS, NUM_FIELDS, FrontierTelemetry
from repro.obs.trace import Tracer, _NOP
from repro.serve import (IngestQueue, QueryClient, RankStore, ServeEngine,
                         ServeMetrics)


def _graph(seed=0, n_exp=9, ef=8, cap_extra=512):
    edges, n = rmat_edges(n_exp, ef, seed=seed)
    return from_coo(edges[:, 0], edges[:, 1], n,
                    edge_capacity=len(edges) + cap_extra)


def _service(graph, flush_size=8, **engine_kw):
    metrics = ServeMetrics()
    ingest = IngestQueue(flush_size=flush_size, flush_interval=0.0,
                         max_pending=4096)
    store = RankStore()
    engine = ServeEngine(graph, ingest, store, metrics=metrics,
                         method="frontier_prune", **engine_kw)
    return ingest, store, engine, metrics


def _feed(ingest, engine, n, events, rng):
    for _ in range(events):
        u, v = rng.integers(0, n, size=2)
        if u != v:
            ingest.submit_insert(int(u), int(v))
    engine.drain()


# ---------------------------------------------------------------------------
# timeit + tracer core
# ---------------------------------------------------------------------------

def test_timeit_measures_elapsed():
    fake = iter([10.0, 10.25])
    with obs.timeit(clock=lambda: next(fake)) as t:
        pass
    assert t.seconds == pytest.approx(0.25)


def test_tracer_records_spans_with_args():
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]
    outer = tr.spans("outer")[0]
    inner = tr.spans("inner")[0]
    assert outer.args == {"k": 1}
    # interval containment: inner nests inside outer on the same thread
    assert outer.tid == inner.tid
    assert outer.t0 <= inner.t0
    assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9


def test_tracer_spans_nest_per_thread():
    tr = Tracer()
    barrier = threading.Barrier(2)

    def work(name):
        barrier.wait()
        with tr.span(name):
            pass

    threads = [threading.Thread(target=work, args=(f"t{i}",))
               for i in range(2)]
    with tr.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"main", "t0", "t1"}
    # each worker thread gets its own track
    assert spans["t0"].tid != spans["t1"].tid
    assert spans["t0"].tid != spans["main"].tid


def test_disabled_tracer_is_free_and_shared():
    tr = Tracer(enabled=False)
    assert tr.span("x") is _NOP          # shared no-op context manager
    with tr.span("x", k=1) as sp:
        sp.set(n=2)                       # arguments at exit: dropped
    tr.record("y", 0.0, 1.0)
    tr.instant("z")
    assert len(tr) == 0
    # sync must not touch the device path at all when disabled
    tr.sync(object())


def test_tracer_ring_buffer_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.record(f"s{i}", float(i), 0.5)
    assert len(tr) == 4
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6


def test_chrome_trace_round_trips_through_json(tmp_path):
    tr = Tracer()
    with tr.span("phase", detail="abc"):
        pass
    tr.instant("marker", n=np.int64(3))
    path = tr.write(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert "traceEvents" in doc
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(complete) == 1
    ev = complete[0]
    assert ev["name"] == "phase"
    assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    assert ev["args"] == {"detail": "abc"}
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "M"} <= phs
    # numpy scalar coerced to a plain int by _jsonable
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"][0]
    assert inst["args"] == {"n": 3}


def test_global_tracer_disabled_by_default_and_scoped():
    assert not obs.get_tracer().enabled
    assert obs.span("x") is _NOP
    with obs.tracing() as tr:
        assert obs.get_tracer() is tr and tr.enabled
        with obs.span("inside"):
            pass
        assert len(tr.spans("inside")) == 1
    assert not obs.get_tracer().enabled


def test_traced_decorator():
    calls = []

    @obs.traced("decorated")
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2                      # disabled: plain call
    with obs.tracing() as tr:
        assert fn(2) == 3
        assert len(tr.spans("decorated")) == 1
    assert calls == [1, 2]


# ---------------------------------------------------------------------------
# frontier telemetry: schema, loops, engine parity
# ---------------------------------------------------------------------------

def test_frontier_schema_helpers():
    rows = np.arange(2 * NUM_FIELDS, dtype=np.float64).reshape(2, NUM_FIELDS)
    ft = FrontierTelemetry(rows)
    assert ft.iterations == 2
    for i, name in enumerate(FIELDS):
        assert ft.column(name).tolist() == [float(i), float(i + NUM_FIELDS)]
    s = ft.summary()
    assert s["iterations"] == 2
    assert s["affected_initial"] == 0.0 and s["affected_final"] == 5.0
    assert len(ft.rows()) == 2 and set(ft.rows()[0]) == set(FIELDS)
    cat = FrontierTelemetry.concat(ft, FrontierTelemetry(rows[:1]))
    assert cat.iterations == 3
    assert FrontierTelemetry.concat().iterations == 0


def test_xla_loop_telemetry_matches_endpoint_scalars():
    g = _graph(seed=1)
    V = g.num_vertices
    ranks = jnp.full((V,), 1.0 / V, jnp.float64)
    touched = np.zeros(V, bool)
    touched[:4] = True
    aff = pr.initial_affected(g, g, jnp.asarray(touched))
    res = pr._pagerank_loop(g, ranks, aff, tol=1e-10, frontier_tol=1e-6,
                            prune_tol=1e-6, max_iter=200, expand=True,
                            prune=True, closed_form=True, telemetry=True)
    assert res.telemetry.shape == (200, NUM_FIELDS)   # padded device rows
    ft = FrontierTelemetry.from_padded(res.telemetry, res.iterations)
    assert ft.iterations == int(res.iterations)
    # first row's affected = the initial affected set, final row's
    # residual = the loop's final delta
    assert ft.column("affected")[0] == float(jnp.sum(aff))
    assert ft.column("residual")[-1] == pytest.approx(float(res.delta))
    # identical solve without telemetry: same ranks, same iterations
    base = pr._pagerank_loop(g, ranks, aff, tol=1e-10, frontier_tol=1e-6,
                             prune_tol=1e-6, max_iter=200, expand=True,
                             prune=True, closed_form=True)
    assert base.telemetry is None
    assert int(base.iterations) == int(res.iterations)
    np.testing.assert_allclose(np.asarray(base.ranks),
                               np.asarray(res.ranks), rtol=0, atol=0)


def test_kernel_vs_xla_telemetry_parity():
    g = _graph(seed=5)
    packed = pack_graph(g, be=256, vb=256)
    V = g.num_vertices
    ranks = jnp.full((V,), 1.0 / V, jnp.float64)
    touched = np.zeros(V, bool)
    touched[:8] = True
    aff = pr.initial_affected(g, g, jnp.asarray(touched))
    kw = dict(tol=1e-7, frontier_tol=1e-5, prune_tol=1e-5, max_iter=100,
              expand=True, prune=True, closed_form=True)
    x = pr._pagerank_loop(g, ranks, aff, telemetry=True, **kw)
    k = ke.kernel_pagerank_loop(g, packed, ranks, aff, use_kernel=False,
                                telemetry=True, **kw)
    tx = FrontierTelemetry.from_padded(x.telemetry, x.iterations)
    tk = FrontierTelemetry.from_padded(k.telemetry, k.iterations)
    m = min(10, tx.iterations, tk.iterations)
    assert m >= 3
    # the two engines walk the same frontier: affected counts exact,
    # residuals agree to f32 precision while far from convergence
    np.testing.assert_array_equal(tx.column("affected")[:m],
                                  tk.column("affected")[:m])
    np.testing.assert_allclose(tx.column("residual")[:m],
                               tk.column("residual")[:m], rtol=1e-3)


def test_hybrid_telemetry_concatenates_phases():
    g = _graph(seed=7)
    packed = pack_graph(g, be=256, vb=256)
    V = g.num_vertices
    ranks = jnp.full((V,), 1.0 / V, jnp.float64)
    touched = np.zeros(V, bool)
    touched[:8] = True
    aff = pr.initial_affected(g, g, jnp.asarray(touched))
    res = ke.hybrid_pagerank(g, packed, ranks, aff, use_kernel=False,
                             prune=True, closed_form=True, telemetry=True)
    # trimmed host rows: kernel phase + polish phase = total iterations
    assert isinstance(res.telemetry, np.ndarray)
    assert res.telemetry.shape == (int(res.iterations), NUM_FIELDS)
    base = ke.hybrid_pagerank(g, packed, ranks, aff, use_kernel=False,
                              prune=True, closed_form=True)
    assert base.telemetry is None
    np.testing.assert_allclose(np.asarray(base.ranks),
                               np.asarray(res.ranks), rtol=0, atol=1e-15)


# ---------------------------------------------------------------------------
# zero overhead when off: program counts and trace counters
# ---------------------------------------------------------------------------

def test_disabled_tracing_adds_no_device_programs():
    g = _graph(seed=2, cap_extra=2048)
    n = g.num_vertices
    ingest, _, engine, metrics = _service(
        g, engine="kernel",
        kernel_opts=dict(use_kernel=False, be=256, vb=256))
    engine.bootstrap()
    rng = np.random.default_rng(0)
    _feed(ingest, engine, n, 24, rng)
    fused0 = ke.TRACE_COUNTS["fused_update_loop"]
    progs0 = list(metrics.batch_device_programs)
    # more untraced batches: no retrace, same programs per batch
    _feed(ingest, engine, n, 24, rng)
    assert ke.TRACE_COUNTS["fused_update_loop"] == fused0
    assert set(metrics.batch_device_programs) == set(progs0)


def test_tracing_toggles_one_retrace_and_preserves_programs():
    g = _graph(seed=3, cap_extra=2048)
    n = g.num_vertices
    ingest, _, engine, metrics = _service(
        g, engine="kernel",
        kernel_opts=dict(use_kernel=False, be=256, vb=256))
    engine.bootstrap()
    rng = np.random.default_rng(1)
    _feed(ingest, engine, n, 24, rng)
    untraced = metrics.as_dict()["device_programs_per_batch"]
    fused0 = ke.TRACE_COUNTS["fused_update_loop"]
    with obs.tracing():
        _feed(ingest, engine, n, 24, rng)
    # telemetry=True is a static flag: exactly one extra trace of the
    # fused loop, and the per-batch device-program count is unchanged
    assert ke.TRACE_COUNTS["fused_update_loop"] == fused0 + 1
    assert metrics.as_dict()["device_programs_per_batch"] == untraced
    with obs.tracing():
        _feed(ingest, engine, n, 8, rng)
    assert ke.TRACE_COUNTS["fused_update_loop"] == fused0 + 1   # cached


# ---------------------------------------------------------------------------
# serve engine: span tree + telemetry capture + gauges
# ---------------------------------------------------------------------------

def test_serve_step_span_tree_and_telemetry(tmp_path):
    g = _graph(seed=4, n_exp=11, cap_extra=2048)
    n = g.num_vertices
    ingest, _, engine, metrics = _service(
        g, engine="kernel",
        kernel_opts=dict(use_kernel=False, be=256, vb=256))
    engine.bootstrap()
    sink_path = str(tmp_path / "frontier.jsonl")
    engine.telemetry_sink = obs.JsonlSink(sink_path)
    rng = np.random.default_rng(2)
    trace_path = str(tmp_path / "trace.json")
    with obs.tracing(trace_path) as tr:
        _feed(ingest, engine, n, 40, rng)
        names = {s.name for s in tr.spans()}
    engine.telemetry_sink.close()
    # the batch span tree: every phase of the fused kernel path
    assert {"serve.step", "ingest.coalesce", "route_update",
            "fused_update_loop", "polish.f64",
            "snapshot.publish"} <= names
    # each serve.step contains its phases by interval
    steps = tr.spans("serve.step")
    inner = tr.spans("fused_update_loop")
    assert steps and inner
    s0 = steps[0]
    assert any(s0.t0 <= sp.t0 and sp.t0 + sp.dur <= s0.t0 + s0.dur + 1e-9
               for sp in inner)
    # frontier telemetry captured and summarized
    assert engine.last_telemetry is not None
    assert engine.last_telemetry.data.shape[1] == NUM_FIELDS
    d = metrics.as_dict()
    assert d["frontier_batches"] >= 1
    assert d["frontier_iterations_mean"] > 0
    # the JSONL sink got one frontier record per traced batch
    lines = [json.loads(ln) for ln in open(sink_path)]
    assert len(lines) == d["frontier_batches"]
    assert lines[0]["kind"] == "frontier"
    assert set(lines[0]["rows"][0]) == set(FIELDS)
    # trace file is valid Chrome-trace JSON
    doc = json.loads(open(trace_path).read())
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in evs} >= {"serve.step", "fused_update_loop"}
    assert all("ts" in e and "dur" in e for e in evs)


def test_ppr_repair_span_recorded():
    edges, n = erdos_renyi_edges(64, 400, seed=0)
    g = from_coo(edges[:, 0], edges[:, 1], n,
                 edge_capacity=len(edges) + 512)
    from repro.ppr import IndexConfig
    ingest, _, engine, _ = _service(
        g, ppr_index=IndexConfig(num_walks=4, max_len=8, seed=0))
    engine.bootstrap()
    rng = np.random.default_rng(3)
    with obs.tracing() as tr:
        _feed(ingest, engine, n, 20, rng)
        spans = tr.spans("ppr.repair")
    assert spans
    assert all("stale" in (s.args or {}) for s in spans)


def test_engine_gauges_in_as_dict():
    g = _graph(seed=6, cap_extra=2048)
    n = g.num_vertices
    ingest, _, engine, metrics = _service(
        g, engine="kernel", telemetry=False,
        kernel_opts=dict(use_kernel=False, be=256, vb=256))
    engine.bootstrap()
    rng = np.random.default_rng(4)
    _feed(ingest, engine, n, 16, rng)
    d = metrics.as_dict()
    assert "staleness_in_events" in d
    # stable snake_case serving counters (the PR 4-6 set)
    for key in ("comm_bytes", "device_programs_per_batch",
                "packed_rebuilds", "packed_rebuilds_by_shard",
                "events_per_s", "walks_resampled"):
        assert key in d
    # gauges never shadow core counters
    metrics.set_gauge("events_per_s", -1.0)
    assert metrics.as_dict()["events_per_s"] != -1.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    text = obs.prometheus_text(dict(
        events_per_s=12.5, batches=3, skip_me="a string",
        packed_rebuilds_by_shard={"0": 2, "3": 1}))
    lines = text.strip().splitlines()
    assert "repro_events_per_s 12.5" in lines
    assert "repro_batches 3" in lines
    assert '# TYPE repro_packed_rebuilds_by_shard gauge' in lines
    assert 'repro_packed_rebuilds_by_shard{key="0"} 2' in lines
    assert 'repro_packed_rebuilds_by_shard{key="3"} 1' in lines
    assert not any("skip_me" in ln for ln in lines)


def test_jsonl_sink_appends_records(tmp_path):
    path = str(tmp_path / "records.jsonl")
    sink = obs.JsonlSink(path, clock=lambda: 42.0)
    sink.write(dict(a=1, arr=np.arange(3)), kind="test")
    sink.write(dict(b=np.float32(2.5)))
    sink.close()
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0] == {"a": 1, "arr": [0, 1, 2], "kind": "test", "t": 42.0}
    assert rows[1]["b"] == 2.5


def test_metrics_exporter_scrape_server():
    m = ServeMetrics()
    m.record_batch(0.01, 8, 2, affected=5, iterations=3, fallback=False)
    m.set_gauge("halo_occupancy", 0.5)
    exporter = obs.MetricsExporter(m, extra=lambda: dict(extra_gauge=7))
    try:
        port = exporter.serve(port=0)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "repro_events_applied 8" in text
        assert "repro_halo_occupancy 0.5" in text
        assert "repro_extra_gauge 7" in text
        blob = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json").read()
        d = json.loads(blob)
        assert d["events_applied"] == 8 and d["extra_gauge"] == 7
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        exporter.close()


def test_metrics_exporter_write(tmp_path):
    m = ServeMetrics()
    m.record_batch(0.02, 4, 0, affected=2, iterations=1, fallback=True)
    path = str(tmp_path / "metrics.prom")
    obs.MetricsExporter(m).write(path)
    text = open(path).read()
    assert "repro_static_fallbacks 1" in text
    assert text.endswith("\n")


def test_halo_occupancy_gauge():
    from repro.kernels.pagerank_spmv.shard import HaloSpec, halo_occupancy
    halo = HaloSpec(ids=jnp.zeros((2, 8), jnp.int32),
                    count=jnp.asarray([4, 2], jnp.int32))
    assert halo_occupancy(halo) == pytest.approx(6 / 16)
    empty = HaloSpec(ids=jnp.zeros((2, 0), jnp.int32),
                     count=jnp.zeros((2,), jnp.int32))
    assert halo_occupancy(empty) == 0.0
