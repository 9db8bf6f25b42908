"""Spans the program records for the benchmark's per-layer metrics:
arguments set at exit, the ingest queue's wait, work counters on the
polish and the walk repair, the query plane, and the spans reaching a
profiler session as host events."""
import glob
import os
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro import obs
from repro.core import kernel_engine as ke
from repro.core import pagerank as pr
from repro.graph.dynamic import apply_batch, make_batch_update, \
    touched_vertices_mask
from repro.graph.generators import erdos_renyi_edges, rmat_edges
from repro.graph.structure import from_coo
from repro.kernels.pagerank_spmv.update import pack_graph
from repro.obs.trace import Tracer
from repro.ppr import IndexConfig, build_walk_index, repair_walk_index
from repro.ppr.repair import STALE_SCAN_IDS
from repro.ppr.shard import repair_walk_index_sharded, shard_walk_index
from repro.serve import IngestQueue, QueryClient, RankStore, ServeEngine, \
    ServeMetrics
from repro.serve.ingest import INSERT

KERNEL = dict(engine="kernel",
              kernel_opts=dict(use_kernel=False, be=256, vb=256))


def _rmat(seed=0, n_exp=9, ef=8, cap_extra=2048):
    edges, n = rmat_edges(n_exp, ef, seed=seed)
    return from_coo(edges[:, 0], edges[:, 1], n,
                    edge_capacity=len(edges) + cap_extra)


def _er(seed=0):
    edges, n = erdos_renyi_edges(64, 400, seed=seed)
    return from_coo(edges[:, 0], edges[:, 1], n,
                    edge_capacity=len(edges) + 512)


def _service(graph, flush_size=8, clock=None, **engine_kw):
    kw = dict(flush_size=flush_size, flush_interval=0.0, max_pending=4096)
    if clock is not None:
        kw["clock"] = clock
    ingest = IngestQueue(**kw)
    store = RankStore()
    engine = ServeEngine(graph, ingest, store, metrics=ServeMetrics(),
                         method="frontier_prune", **engine_kw)
    return ingest, store, engine


def _submit(ingest, n, events, rng):
    for _ in range(events):
        u, v = rng.integers(0, n, size=2)
        if u != v:
            ingest.submit_insert(int(u), int(v))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def polish_iterations(monkeypatch):
    """Iterations of every float64 polish run, read off the loop's own
    result as it returns."""
    seen = []
    loop = pr._pagerank_loop

    def spy(*a, **kw):
        p = loop(*a, **kw)
        seen.append(int(p.iterations))
        return p

    monkeypatch.setattr(pr, "_pagerank_loop", spy)
    return seen


# ---------------------------------------------------------------------------
# tracer: set at exit, on one thread and across threads
# ---------------------------------------------------------------------------

def test_span_set_adds_args_at_exit():
    tr = Tracer()
    with tr.span("work", k=1) as sp:
        sp.set(n=2)
        sp.set(n=3, m=4)
    assert tr.spans("work")[0].args == {"k": 1, "n": 3, "m": 4}
    with tr.span("bare") as sp:
        pass
    assert tr.spans("bare")[0].args is None


def test_span_set_keeps_each_threads_args():
    tr = Tracer()
    workers = 8
    inside = threading.Barrier(workers)

    def work(i):
        with tr.span("worker", i=i) as sp:
            inside.wait(timeout=30)        # every span open at once
            sp.set(square=i * i)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    spans = tr.spans("worker")
    assert sorted(s.args["i"] for s in spans) == list(range(workers))
    assert all(s.args["square"] == s.args["i"] ** 2 for s in spans)
    assert len({s.tid for s in spans}) == workers


# ---------------------------------------------------------------------------
# ingest queue and serve step
# ---------------------------------------------------------------------------

def test_coalesce_span_oldest_wait_on_the_queue_clock():
    clk = FakeClock()
    q = IngestQueue(flush_size=100, flush_interval=0.5, clock=clk)
    clk.t = 1.0
    q.submit(INSERT, 1, 2)
    clk.t = 1.25
    q.submit(INSERT, 1, 2)                 # coalesces with the first
    q.submit(INSERT, 3, 4)
    clk.t = 2.0
    with obs.tracing() as tr:
        batch = q.poll()
    (span,) = tr.spans("ingest.coalesce")
    assert span.args == dict(events=3, coalesced=1, first_seq=0,
                             last_seq=2, oldest_wait_s=1.0)
    assert batch.oldest_t == 1.0


def test_empty_poll_records_no_serve_step():
    g = _er()
    ingest, _, engine = _service(g, flush_size=64)
    engine.bootstrap()
    with obs.tracing() as tr:
        assert not engine.step()           # nothing queued
        ingest.submit_insert(1, 2)
        ingest.flush_interval = 1e9
        assert not engine.step()           # queued, not due
    assert tr.spans() == []
    with obs.tracing() as tr:
        assert engine.step(force=True)
    assert len(tr.spans("serve.step")) == 1
    assert len(tr.spans("ingest.coalesce")) == 1


def test_serve_step_names_the_batch_it_served():
    g = _rmat(seed=4)
    ingest, _, engine = _service(g, static_fallback_frac=1.0, **KERNEL)
    engine.bootstrap()
    rng = np.random.default_rng(5)
    with obs.tracing() as tr:
        _submit(ingest, g.num_vertices, 24, rng)
        engine.drain()
    steps = tr.spans("serve.step")
    polls = tr.spans("ingest.coalesce")
    assert steps and len(steps) == len(polls)
    for step, poll in zip(steps, polls):
        assert (step.args["first_seq"], step.args["last_seq"]) == \
            (poll.args["first_seq"], poll.args["last_seq"])
        # the poll happens before the step opens
        assert poll.t0 + poll.dur <= step.t0 + 1e-9
        assert step.args["events"] == poll.args["events"]
        assert poll.args["oldest_wait_s"] >= 0
    # the step's phases nest inside it
    for name in ("route_update", "fused_update_loop", "polish.f64",
                 "snapshot.publish"):
        for sp in tr.spans(name):
            assert any(s.t0 <= sp.t0 and
                       sp.t0 + sp.dur <= s.t0 + s.dur + 1e-9
                       for s in steps), name


# ---------------------------------------------------------------------------
# work counters: polish sweeps, walks scanned by the repair
# ---------------------------------------------------------------------------

def _polish_fused():
    g = _rmat(seed=7)
    ingest, _, engine = _service(g, static_fallback_frac=1.0, **KERNEL)
    engine.bootstrap()
    return lambda: (_submit(ingest, g.num_vertices, 24,
                            np.random.default_rng(8)), engine.drain())


def _polish_hybrid():
    g = _rmat(seed=9)
    packed = pack_graph(g, be=256, vb=256)
    r0 = pr.static_pagerank(g).ranks * 0.5 + 0.5 / g.num_vertices
    aff = jnp.ones((g.num_vertices,), bool)
    return lambda: ke.hybrid_pagerank(g, packed, r0, aff, use_kernel=False)


def _polish_mesh():
    from jax.sharding import Mesh
    from repro.core.api import update_pagerank
    from repro.graph.generators import random_batch_update
    g = _rmat(seed=11, n_exp=7)
    n = g.num_vertices
    r0 = pr.static_pagerank(g).ranks
    live = np.stack([np.asarray(g.src), np.asarray(g.dst)], 1)[
        np.asarray(g.valid)]
    dele, ins = random_batch_update(live, n, 16, seed=12)
    upd = make_batch_update(dele, ins, 32, 32)
    g2 = apply_batch(g, upd)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    return lambda: update_pagerank(g, g2, upd, r0, "frontier_prune",
                                   mesh=mesh, engine="kernel",
                                   pack_kw=dict(be=32, vb=16))


@pytest.mark.parametrize("path", ["fused", "hybrid", "mesh"])
def test_polish_span_counts_its_sweeps(path, polish_iterations):
    run = dict(fused=_polish_fused, hybrid=_polish_hybrid,
               mesh=_polish_mesh)[path]()
    del polish_iterations[:]
    with obs.tracing() as tr:
        run()
    sweeps = [s.args["sweeps"] for s in tr.spans("polish.f64")]
    assert sweeps and sweeps == polish_iterations
    assert all(s > 0 for s in sweeps)
    if path == "mesh":
        (loop,) = tr.spans("sharded_f32_loop")
        assert loop.args["comm_bytes"] > 0
        assert tr.spans("halo.exchange") == []


def _stale_batch(g, rng, events=20):
    n = g.num_vertices
    pairs = rng.integers(0, n, size=(events, 2))
    pairs = pairs[pairs[:, 0] != pairs[:, 1]].astype(np.int32)
    upd = make_batch_update(np.zeros((0, 2), np.int32), pairs, 8, 32)
    return apply_batch(g, upd), touched_vertices_mask(upd, n)


@pytest.mark.parametrize("shards", [None, 2, 3])
def test_repair_span_counts_walks_scanned(shards):
    g = _er(seed=1)
    cfg = IndexConfig(num_walks=4, max_len=8, seed=0)
    index = build_walk_index(g, cfg)
    g2, touched = _stale_batch(g, np.random.default_rng(2))
    name = "ppr.repair"
    with obs.tracing() as tr:
        if shards is None:
            _, stale = repair_walk_index(index, g2, touched)
        else:
            name = "ppr.repair_sharded"
            sidx = shard_walk_index(index, shards)
            _, stale = repair_walk_index_sharded(sidx, g2, touched)
    (span,) = tr.spans(name)
    V, R = g.num_vertices, cfg.num_walks
    rows = V if shards is None else -(-V // shards) * shards
    assert span.args["scanned"] == rows * R
    assert 0 < span.args["stale"] == stale <= span.args["scanned"]
    # the sharded capacity is per shard, sized from its most stale one
    assert span.args["capacity"] * (shards or 1) >= stale
    if shards is None:
        assert span.args["touched"] == int(jnp.sum(touched))
        assert span.args["scan"] == "ids"


@pytest.mark.parametrize("over", [False, True])
def test_repair_span_names_the_scan_branch(over):
    """``touched`` and ``scan`` say which branch of the stale scan ran:
    compare with the ids up to ``STALE_SCAN_IDS`` touched vertices, the
    gather past them; ``scanned`` is V·R either way."""
    g = _rmat(seed=5)
    n = g.num_vertices
    cfg = IndexConfig(num_walks=4, max_len=8, seed=0)
    index = build_walk_index(g, cfg)
    count = STALE_SCAN_IDS + 1 if over else STALE_SCAN_IDS
    rng = np.random.default_rng(6)
    src = rng.choice(n, count, replace=False).astype(np.int32)
    pairs = np.stack([src, (src + 1) % n], axis=1)
    upd = make_batch_update(np.zeros((0, 2), np.int32), pairs, 8, count)
    touched = touched_vertices_mask(upd, n)
    assert int(jnp.sum(touched)) == count
    with obs.tracing() as tr:
        _, stale = repair_walk_index(index, apply_batch(g, upd), touched)
    (span,) = tr.spans("ppr.repair")
    assert span.args["touched"] == count
    assert span.args["scan"] == ("gather" if over else "ids")
    assert span.args["scanned"] == n * cfg.num_walks
    assert span.args["stale"] == stale > 0


def test_repair_span_with_nothing_stale():
    g = _er(seed=3)
    index = build_walk_index(g, IndexConfig(num_walks=2, max_len=4))
    touched = jnp.zeros((g.num_vertices,), bool)
    with obs.tracing() as tr:
        _, stale = repair_walk_index(index, g, touched)
    (span,) = tr.spans("ppr.repair")
    assert stale == 0
    assert span.args == dict(scanned=g.num_vertices * 2, stale=0,
                             touched=0, scan="ids")


# ---------------------------------------------------------------------------
# query plane
# ---------------------------------------------------------------------------

def _queries(client, n):
    client.get_ranks([0, 1, 2, 3])
    client.top_k(5)
    client.personalized_top_k([1], 5, mode="index")
    client.personalized_top_k([2], 5, mode="exact")


def test_each_query_records_one_span_and_one_device_child():
    g = _er(seed=4)
    ingest, store, engine = _service(
        g, ppr_index=IndexConfig(num_walks=4, max_len=8, seed=0))
    engine.bootstrap()
    ingest.submit_insert(3, 4)             # one event not yet served
    client = QueryClient(store, ingest)
    _queries(client, g.num_vertices)       # compile outside the trace
    with obs.tracing() as tr:
        _queries(client, g.num_vertices)
    calls = [s for s in tr.spans() if s.name in
             ("query.point", "query.top_k", "query.ppr")]
    device = tr.spans("query.device")
    assert [s.name for s in calls] == ["query.point", "query.top_k",
                                       "query.ppr", "query.ppr"]
    assert [s.args["mode"] for s in calls[2:]] == ["index", "exact"]
    assert len({s.args["qid"] for s in calls}) == 4
    for call in calls:
        assert call.args["generation"] == store.generation
        assert call.args["staleness"] == 1
        (child,) = [d for d in device if d.args["qid"] == call.args["qid"]]
        assert child.args == {"qid": call.args["qid"]}
        assert child.tid == call.tid
        assert call.t0 <= child.t0
        assert child.t0 + child.dur <= call.t0 + call.dur + 1e-9
    assert len(device) == 4


def test_queries_record_nothing_with_tracing_off():
    g = _er(seed=5)
    _, store, engine = _service(
        g, ppr_index=IndexConfig(num_walks=4, max_len=8, seed=0))
    engine.bootstrap()
    tr = Tracer(enabled=False)
    prev = obs.set_tracer(tr)
    try:
        _queries(QueryClient(store), g.num_vertices)
    finally:
        obs.set_tracer(prev)
    assert len(tr) == 0


# ---------------------------------------------------------------------------
# profiler: program spans are host events of the profiler's own trace
# ---------------------------------------------------------------------------

def test_spans_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    g = _rmat(seed=13)
    ingest, _, engine = _service(g, static_fallback_frac=1.0, **KERNEL)
    engine.bootstrap()
    rng = np.random.default_rng(14)
    with obs.tracing():                    # compile the traced programs
        _submit(ingest, g.num_vertices, 8, rng)
        engine.drain()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with obs.tracing():
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            _submit(ingest, g.num_vertices, 8, rng)
            engine.drain()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.update(ev.name for ev in line.events)
    assert {"serve.step", "route_update", "polish.f64",
            "ingest.coalesce"} <= host
