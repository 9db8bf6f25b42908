"""One run of one cell: set up the deployment, warm it, measure a window.

The program is driven through its serving entry: events go into an
``IngestQueue``, a thread of this driver runs ``ServeEngine.step`` in a
loop (as ``ServeEngine.start`` would), each publish is timed through
``engine.on_publish``, and queries go through a ``QueryClient`` from a
pool of worker threads.  Arrivals are an open loop: every event and
query has a due time fixed before the window opens, and its latency is
counted from that due time.
"""
from __future__ import annotations

import concurrent.futures
import math
import threading
import time

import numpy as np

from harness import gen
from harness.reference import unique_keys

WARM_STEPS = 2          # serve steps before the window opens: the second
                        # runs the programs on graphs the first produced,
                        # whose placement differs from the preload's
BACKLOG_AHEAD = 4       # batches a backlog keeps queued at all times
DRAIN_LIMIT_S = 120.0   # how long a paced window waits for its last events


class CompileCounter:
    """(time, function) of each backend compile (built, or loaded from
    the persistent cache), from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append((time.perf_counter(), kw.get("fun_name")))


def build_data(cfg: dict, seed: int) -> dict:
    """Preload edges, the event feed and which events insert, from the
    configuration and the run's seed."""
    g = cfg["graph"]
    if g["kind"] == "temporal":
        n, m = g["num_vertices"], g["num_events"]
        ev = gen.temporal_stream(n, m, g["data_seed"], **g["model"])
        pre_end = int(g["preload_frac"] * m)
        preload, feed = ev[:pre_end], ev[pre_end:]
        insert = np.ones(len(feed), bool)
        capacity = pre_end + len(feed) + g["capacity_slack"]
    elif g["kind"] == "kronecker":
        n = 1 << g["scale"]
        preload = gen.kronecker(g["scale"], g["edge_factor"], g["graph_seed"],
                                **g["initiator"])
        drawn = g["edge_factor"] * n
        batch = int(round(g["batch_frac"] * drawn))
        batches = int(round(g["feed_frac"] * drawn / batch))
        # one fixed set of batches (``update_seed``): the run's seed only
        # orders the events inside each batch, so every seed does the
        # same work (a seed drawing its own random batches moved a
        # 40 s window's events/s by 19% between seeds)
        feed, insert = gen.random_updates(preload, n, batches, batch,
                                          g["update_seed"], g["insert_frac"])
        order = np.argsort(np.random.default_rng(seed).random(
            (batches, batch)), axis=1) + np.arange(batches)[:, None] * batch
        feed, insert = feed[order.ravel()], insert[order.ravel()]
        capacity = len(preload) + int(insert.sum()) + g["capacity_slack"]
    else:
        raise ValueError(f"unknown graph kind {g['kind']!r}")
    return dict(n=n, preload=preload, feed=feed, insert=insert,
                capacity=capacity)


class CellRun:
    """Set-up, warm-up and the measured window of one run."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 trace: bool, log):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds, self.trace, self.log = seconds, trace, log
        self.served = cfg["served"]
        self.flush = self.served["flush_size"]
        self.rng = np.random.default_rng(seed)
        self.publishes: list = []
        self.ranks_by_gen: dict = {}
        self.acked: list = []          # feed index of each accepted seq
        self.due: dict = {}            # seq -> due time (window events)
        self.feed_lags: list = []
        self.queries: list = []
        self.rejected = 0
        self.w0 = self.w1 = None
        self.deadline = None
        self.last_window_seq = None
        self._next = 0                 # next feed index to submit
        self._stop = threading.Event()
        self._lock = threading.Lock()

    # ---- set-up ----------------------------------------------------------
    def setup(self):
        import jax
        from repro.graph.structure import from_coo
        from repro.ppr import IndexConfig
        from repro.serve import IngestQueue, QueryClient, RankStore, \
            ServeEngine

        t = time.perf_counter()
        self.data = d = build_data(self.cfg, self.seed)
        self.n = d["n"]
        self.log(f"data: |V|={self.n} preload={len(d['preload'])} "
                 f"feed={len(d['feed'])} capacity={d['capacity']} "
                 f"in {time.perf_counter() - t:.3f}s")
        graph = from_coo(d["preload"][:, 0], d["preload"][:, 1], self.n,
                         edge_capacity=d["capacity"])
        self.ingest = IngestQueue(
            flush_size=self.flush,
            flush_interval=self.served["flush_interval_ms"] * 1e-3,
            max_pending=self.served["max_pending"])
        self.store = RankStore()
        ppr = self.served.get("ppr")
        index = None
        kernel_opts = self.served.get("kernel_opts")
        if ppr:
            index = IndexConfig(num_walks=ppr["walks"], max_len=ppr["length"],
                                alpha=self.served["alpha"],
                                seed=self.seed % (2 ** 31 - 1))
        self.engine = ServeEngine(
            graph, self.ingest, self.store, method=self.served["method"],
            engine=self.served["engine"],
            static_fallback_frac=self.served["static_fallback_frac"],
            ppr_index=index, telemetry=False, kernel_opts=kernel_opts,
            alpha=self.served["alpha"], tol=self.served["tol"])
        self.engine.on_publish = self._on_publish
        t = time.perf_counter()
        self.engine.bootstrap()
        jax.block_until_ready(self.store.snapshot().ranks)
        self.log(f"bootstrap: {time.perf_counter() - t:.3f}s "
                 f"geometry={self.engine.kernel_geometry}")
        self.client = QueryClient(self.store, self.ingest)
        if ppr:
            self._warm_walk_repair(ppr)
        if self.mix["query_rate"] > 0:
            self._warm_queries()

    def _warm_walk_repair(self, ppr: dict):
        """Compile the walk repair for every batch the window can bring,
        through the program's public ``repair_walk_index``: its
        ``min_capacity`` argument at each power of two from 64 up to
        ``ppr["warm_repair_walks"]`` (the most stale walks one batch of
        the deployment is served without a compile).  The touched vertex
        has no in-edges, so only its own walks go stale; the repaired
        index is dropped."""
        import jax
        from repro.ppr import repair_walk_index
        snap = self.store.snapshot()
        pre = unique_keys(self.data["preload"], self.n)
        indeg = np.bincount(pre % self.n, minlength=self.n)
        outdeg = np.bincount(pre // self.n, minlength=self.n)
        u = int(np.flatnonzero((indeg == 0) & (outdeg > 0))[0])
        touched = jax.numpy.zeros(self.n, bool).at[u].set(True)
        t = time.perf_counter()
        cap, top = 64, ppr["warm_repair_walks"]
        while True:
            index, resampled = repair_walk_index(
                snap.ppr_index, snap.graph, touched, min_capacity=cap)
            jax.block_until_ready(index.steps)
            del index                  # one repaired index alive at a time
            if resampled == 0:
                raise RuntimeError(f"no walk of vertex {u} went stale")
            if cap >= top:
                break
            cap *= 2
        self.log(f"warm walk repair 64..{cap}: "
                 f"{time.perf_counter() - t:.3f}s")

    def _warm_queries(self):
        """One query of each kind, PPR from a seed in every power-of-two
        band of out-degree the preload has."""
        k = self.mix["top_k"]
        pre = unique_keys(self.data["preload"], self.n)
        deg = np.bincount(pre // self.n, minlength=self.n)
        self.client.get_ranks([0, 1, 2, 3])
        self.client.top_k(k)
        if self.store.snapshot().ppr_index is None:
            return
        band = np.where(deg > 0, np.ceil(np.log2(np.maximum(deg, 1))), -1)
        for b in np.unique(band):
            seed = int(np.flatnonzero(band == b)[0])
            self.client.personalized_top_k([seed], k, mode="index")

    # ---- feed --------------------------------------------------------------
    def _submit(self, i: int, due=None) -> None:
        u, v = int(self.data["feed"][i, 0]), int(self.data["feed"][i, 1])
        if self.data["insert"][i]:
            seq = self.ingest.submit_insert(u, v)
        else:
            seq = self.ingest.submit_delete(u, v)
        if seq is None:
            self.rejected += 1
            return
        if seq != len(self.acked):
            raise RuntimeError(f"ingest seq {seq} != {len(self.acked)}")
        self.acked.append(i)
        if due is not None:
            self.due[seq] = due

    def _top_up(self) -> bool:
        """Keep BACKLOG_AHEAD batches queued; False once the feed is out."""
        want = BACKLOG_AHEAD * self.flush
        while self.ingest.pending() < want:
            if self._next >= len(self.data["feed"]):
                return False
            self._submit(self._next)
            self._next += 1
        return True

    def _backlog_feeder(self):
        while not self._stop.is_set():
            if not self._top_up():
                return
            time.sleep(0.002)

    def _paced_feeder(self):
        rate = self.mix["event_rate"]
        i = 0
        while not self._stop.is_set():
            due = self.w0 + i / rate
            if due >= self.deadline or self._next >= len(self.data["feed"]):
                break
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.feed_lags.append(time.perf_counter() - due)
            self._submit(self._next, due)
            self._next += 1
            i += 1
        self._stop.wait(max(0.0, self.deadline - time.perf_counter()))
        with self._lock:
            self.last_window_seq = last = len(self.acked) - 1
            done = [p for p in self.publishes if p["last_seq"] >= last]
        if done:                       # published before the feed ended
            self.w1 = done[0]["t"]
            self._stop.set()

    # ---- queries -----------------------------------------------------------
    def _query_plan(self) -> list:
        rate = self.mix["query_rate"]
        count = int(math.floor(self.seconds * rate))
        kinds = np.tile(np.arange(len(self.mix["query_kinds"])),
                        -(-count // len(self.mix["query_kinds"])))[:count]
        self.rng.shuffle(kinds)
        plan = []
        for i, k in enumerate(kinds):
            kind = self.mix["query_kinds"][k]
            if kind == "point":
                arg = self.rng.integers(0, self.n, size=4).tolist()
            elif kind == "ppr":
                arg = [int(self.rng.integers(0, self.n))]
            else:
                arg = None
            plan.append(dict(kind=kind, arg=arg, due=(i + 0.5) / rate))
        return plan

    def _run_query(self, q: dict):
        k = self.mix["top_k"]
        try:
            if q["kind"] == "point":
                r = self.client.get_ranks(q["arg"])
            elif q["kind"] == "top":
                r = self.client.top_k(k)
            else:
                r = self.client.personalized_top_k(q["arg"], k, mode="index")
            q.update(done=time.perf_counter(), gen=r.generation,
                     vertices=np.asarray(r.vertices),
                     values=np.asarray(r.ranks))
        except Exception as e:          # a failed query counts as failed
            q.update(done=None, error=repr(e))

    def _dispatcher(self, plan: list, pool):
        futures = []
        for q in plan:
            q["due"] += self.w0
            wait = q["due"] - time.perf_counter()
            if wait > 0:
                if self._stop.wait(wait):
                    break
            q["issued"] = time.perf_counter()
            self.feed_lags.append(q["issued"] - q["due"])
            self.queries.append(q)
            futures.append(pool.submit(self._run_query, q))
        for f in futures:
            f.result()

    # ---- serve loop ----------------------------------------------------------
    def _on_publish(self, snap, batch):
        t = time.perf_counter()
        record = dict(t=t, gen=snap.generation, events=batch.num_events,
                      first_seq=batch.first_seq, last_seq=batch.last_seq,
                      window=self.w0 is not None)
        self.ranks_by_gen[snap.generation] = snap.ranks
        if self.mix["feed"] == "backlog":
            with self._lock:
                self.publishes.append(record)
            if self.w0 is not None and t >= self.deadline:
                self.w1 = t
                self._stop.set()
            return
        with self._lock:
            self.publishes.append(record)
            last = self.last_window_seq
        if last is not None and batch.last_seq >= last \
                and self.w1 is None:
            self.w1 = t
            self._stop.set()

    def _loop(self):
        import jax
        annotate = self.trace
        while not self._stop.is_set():
            if annotate:
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    did = self.engine.step()
            else:
                did = self.engine.step()
            if not did:
                if self.deadline is not None and \
                        time.perf_counter() > self.deadline + DRAIN_LIMIT_S:
                    self._stop.set()
                time.sleep(0.001)

    def counts(self) -> dict:
        m = self.engine.metrics
        return dict(batches=len(m.batch_events),
                    affected=sum(m.batch_affected),
                    iterations=sum(m.batch_iterations),
                    fallbacks=m.static_fallbacks, repacks=m.packed_rebuilds,
                    walks_resampled=m.walks_resampled)

    def warm_and_measure(self, start_profile):
        """Warm-up steps, then the window; ``start_profile`` (or None)
        starts the device trace before the last warm-up step."""
        backlog = self.mix["feed"] == "backlog"
        for s in range(WARM_STEPS):
            if backlog:
                self._top_up()
            else:
                for _ in range(self.flush):
                    self._submit(self._next)
                    self._next += 1
            if s == WARM_STEPS - 1 and start_profile is not None:
                start_profile()
            self.engine.step(force=True)
        self.w0 = self.publishes[-1]["t"]
        self.counts_at_w0 = self.counts()
        self.deadline = self.w0 + self.seconds
        self.publishes[-1]["window"] = False
        threads = [threading.Thread(target=self._loop, name="chipbench-serve")]
        pool = None
        if backlog:
            threads.append(threading.Thread(target=self._backlog_feeder,
                                            name="chipbench-feed"))
        else:
            threads.append(threading.Thread(target=self._paced_feeder,
                                            name="chipbench-feed"))
        if self.mix["query_rate"] > 0:
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.mix["query_workers"],
                thread_name_prefix="chipbench-query")
            threads.append(threading.Thread(
                target=self._dispatcher, args=(self._query_plan(), pool),
                name="chipbench-queries"))
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if pool is not None:
            pool.shutdown(wait=True)
        self.engine.close()
