"""The serve-loop engine: micro-batch in, snapshot out.

One ``step`` = poll the ingest queue for a coalesced micro-batch, apply
it (``apply_batch``), build the method's initial affected set via the
shared ``core.api.build_initial_state`` dispatch, run the DF/DF-P loop,
publish the new (graph, ranks, generation) snapshot.  The step is
synchronous and single-consumer; ``start``/``stop`` wrap it in a daemon
thread for online operation, while tests and benchmarks drive ``step``
directly for determinism.

Static fallback (paper §5.2.2 observation: DF/DF-P lose to Static once
the affected fraction is large): when the *initial* affected set of the
chosen dynamic method covers more than ``static_fallback_frac`` of the
vertices, the step reruns from a cold start instead — same fixed point,
less work at very large coalesced batches.  The initial affected set is
a cheap one-hop (frontier) or reachability (traversal) mask we need
anyway, so the decision adds no extra passes for frontier methods.

``mesh=`` routes the rank update through the distributed shard_map
engine (repro.dist) — ingest/snapshot/query stay host-side either way.

``engine="kernel"`` makes the Pallas frontier-gated SpMV the serving
hot path: bootstrap packs the graph into the blocked ``PackedGraph``
once, every micro-batch maintains it *on device* with
``apply_batch_packed`` (no host repack), and dynamic-method solves run
the hybrid-precision ladder (f32 kernel iterations + f64 polish,
core.kernel_engine.hybrid_pagerank).  Published snapshots are unchanged
— f64 ranks, same generation clock.  Static solves (bootstrap, fallback)
stay on the XLA engine: with every window active the gated kernel has
nothing to skip and the cold start wants f64 end-to-end.  If a window's
spill lanes run out, the engine repacks from the current graph at the
same capacity (``metrics.packed_rebuilds`` counts these) — the kernels
never recompile because every shape is pinned at bootstrap.

``engine="kernel"`` + ``mesh=`` is the **sharded** kernel path: the
packed structure is partitioned by dst-window ranges over the mesh's
``model`` axis (kernels.pagerank_spmv.shard), each micro-batch's deltas
are routed to their owning shard and applied under shard_map, and the
hybrid ladder runs the shard_map'd kernel loop with a replicated rank
vector (dist.pagerank_dist.ShardedKernelEngine).  Overflow recovery is
per the single-pod contract — repack at pinned shapes, zero recompiles —
with ``metrics.packed_rebuilds_by_shard`` attributing which shards
overflowed; ``kernel_opts["delta_budget"]`` bounds routed per-shard
rows per batch (None = whole-batch capacity).  Engine work counters
(``edges_processed``/``vertices_processed``) are psum-aggregated across
shards by the solve and land in the same metrics fields as the
single-pod path.
``kernel_opts`` tunes the path: pack sizing (``be``, ``vb``,
``spill_lanes_per_window``, ``num_entries``), ``use_kernel`` (True =
Pallas kernel [interpret mode off-TPU], False = jnp oracle, "auto" =
kernel on TPU only) and any ``hybrid_pagerank`` kwarg (``tol_f32``,
``polish``, ...).  When the caller does NOT fix ``be``/``vb``, bootstrap
**autotunes** the pack geometry for the bootstrap graph via
``kernels.pagerank_spmv.tune`` (roofline model over the graph's degree
distribution, optional first-batch measured search, persistent cache
keyed by graph shape + device kind); the winner is exposed as
``self.kernel_geometry`` / ``self.tune_info`` for the launch log.
``kernel_opts["tune"]=False`` opts out (fixed ``KERNEL_PACK_DEFAULTS``),
``tune_measure=True`` enables the timed candidate search,
``tune_cache_path`` overrides the cache file, ``frontier_frac`` is the
expected per-batch affected fraction the model optimises for.

``ppr_index=`` (an ``repro.ppr.IndexConfig`` or prebuilt ``WalkIndex``)
opts the engine into maintaining a random-walk PPR index alongside the
ranks: built at bootstrap, repaired inside every micro-batch step from
the batch's ``touched_vertices_mask`` (only walks intersecting touched
vertices resample), and published with each snapshot so index-backed
``personalized_top_k`` answers stay consistent with the served ranks.

``monitor=`` (an ``obs.monitor.CorrectnessMonitor``) opts the engine
into correctness observability: per-batch invariant sentinels, sampled
shadow verification, flight recording with bit-for-bit replay, and SLO
burn-rate alerts (DESIGN.md §12).  ``inject_fault`` arms a one-shot
debug corruption so that pipeline can be exercised end-to-end.

``iteration_budget=`` (an ``ft.straggler.IterationBudget`` or an int
``max_iter_per_batch``) caps each dynamic batch's solver iterations so
one pathological micro-batch cannot stall the publish cadence: a solve
that exits at the cap carries its unconverged frontier into the next
batch's seed set (sound for DF/DF-P — vertices re-mark until Δ ≤ τ,
DESIGN.md §13), and ``metrics.budget_carryover`` counts the batches
that started from a carried frontier.  Bootstrap and explicit static
solves are never capped — a cold start wants full convergence.

``on_publish`` (assignable attribute, like ``telemetry_sink``) is
called after every post-batch snapshot publish with ``(snapshot,
batch)`` — the hook the replication writer (serve/replicate.py) uses to
emit generation-stamped deltas without the engine knowing about
replication.

``close()`` shuts the engine down completely: stops the background step
thread if one is running and closes the correctness monitor, which
joins the shadow-verifier thread and flushes its latest-wins mailbox so
a pending divergence is reported rather than dropped on exit.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import pagerank as pr
from repro.core.api import ENGINES, KERNEL_FLAGS, LOOP_FLAGS, Method, \
    build_initial_state, distributed_pagerank
from repro.graph.dynamic import apply_batch, touched_vertices_mask
from repro.graph.structure import EdgeListGraph
from repro.obs import trace as obs_trace
from repro.obs.frontier import FrontierTelemetry
from repro.ppr import IndexConfig, ShardedWalkIndex, WalkIndex, \
    build_sharded_walk_index, build_walk_index, repair_walk_index, \
    repair_walk_index_sharded
from repro.serve.ingest import IngestQueue
from repro.serve.metrics import ServeMetrics
from repro.serve.state import RankStore

DYNAMIC_METHODS = ("naive", "traversal", "frontier", "frontier_prune")

# host-sync round trips the serve loop has issued (block_until_ready
# calls) — tests assert exactly one per step, PPR repair or not
import collections as _collections
SYNC_COUNTS: _collections.Counter = _collections.Counter()


def _block(x) -> None:
    SYNC_COUNTS["block_until_ready"] += 1
    jax.block_until_ready(x)

# serving pack defaults: smaller entries than the offline DEFAULT_BE=2048
# keep the per-window spill reservation (and the padded-lane overhead the
# contributions gather over) small relative to the live edges, while VB
# stays 2×128 lanes (DESIGN.md §8 capacity model)
KERNEL_PACK_DEFAULTS = dict(be=512, vb=256, spill_lanes_per_window=256)
_PACK_KEYS = ("be", "vb", "spill_lanes_per_window", "num_entries",
              "extra_entries", "overlay_capacity")


class ServeEngine:
    def __init__(self, graph: EdgeListGraph, ingest: IngestQueue,
                 store: RankStore, metrics: Optional[ServeMetrics] = None,
                 method: Method = "frontier_prune", mesh=None,
                 engine: str = "xla",
                 kernel_opts: Optional[dict] = None,
                 static_fallback_frac: float = 0.25,
                 ppr_index=None, clock=time.monotonic,
                 telemetry: Optional[bool] = None, monitor=None,
                 iteration_budget=None, **pr_kw):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; options {ENGINES}")
        self.ingest = ingest
        self.store = store
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.method = method
        self.mesh = mesh
        self.engine = engine
        opts = dict(kernel_opts or {})
        explicit = {k: opts.pop(k) for k in _PACK_KEYS if k in opts}
        # autotune unless the caller fixed the geometry (be/vb) themselves
        self._tune = opts.pop("tune", not ({"be", "vb"} & set(explicit)))
        self._tune_measure = opts.pop("tune_measure", False)
        self._tune_cache_path = opts.pop("tune_cache_path", None)
        self._frontier_frac = opts.pop("frontier_frac", 0.05)
        self._explicit_pack = explicit
        self._pack_kw = {**KERNEL_PACK_DEFAULTS, **explicit}
        self.kernel_geometry = None   # set at bootstrap (kernel engine)
        self.tune_info = None
        self._delta_budget = opts.pop("delta_budget", None)
        use_kernel = opts.pop("use_kernel", "auto")
        if use_kernel == "auto":
            use_kernel = jax.default_backend() == "tpu"
        # what "auto" resolved to: True runs the Pallas kernel
        self.use_kernel = bool(use_kernel)
        self._kernel_kw = dict(use_kernel=self.use_kernel, **opts)
        self._packed = None
        self._sharded = None   # dist.ShardedKernelEngine (kernel + mesh)
        self.static_fallback_frac = static_fallback_frac
        # opt-in walk index (repro.ppr): an IndexConfig to build at
        # bootstrap (sharded over `mesh` when one is given), or a prebuilt
        # WalkIndex / ShardedWalkIndex valid for `graph`
        self._ppr_cfg: Optional[IndexConfig] = None
        self._ppr = None
        if isinstance(ppr_index, IndexConfig):
            self._ppr_cfg = ppr_index
        elif isinstance(ppr_index, (WalkIndex, ShardedWalkIndex)):
            self._ppr = ppr_index
        elif ppr_index is not None:
            raise TypeError("ppr_index must be an IndexConfig, WalkIndex "
                            "or ShardedWalkIndex")
        # frontier telemetry: None = follow the global tracer (rows are
        # recorded exactly when a trace is being taken), True/False pins
        # it.  Toggling retraces the solve loops once (static jit flag).
        self.telemetry = telemetry
        self.last_telemetry: Optional[FrontierTelemetry] = None
        # optional obs.export.JsonlSink receiving one frontier record
        # per batch (assigned by the launch driver behind --trace)
        self.telemetry_sink = None
        self.pr_kw = pr_kw
        self._clock = clock
        self._graph = graph
        self._ranks: Optional[jax.Array] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # correctness monitor (obs.monitor.CorrectnessMonitor): hooked
        # after bootstrap and after every publish; None = zero overhead
        self.monitor = monitor
        # per-batch iteration cap with frontier carryover
        # (ft.straggler.IterationBudget); an int means max_iter_per_batch
        if isinstance(iteration_budget, int):
            from repro.ft.straggler import IterationBudget
            iteration_budget = IterationBudget(iteration_budget)
        self._budget = iteration_budget
        # post-publish hook (snapshot, batch) for the replication writer
        self.on_publish = None
        self._closed = False
        # one-shot debug fault armed by inject_fault(); applied (and
        # cleared) by the step that publishes the chosen generation
        self._fault: Optional[dict] = None
        self.faults_injected = 0

    @property
    def packed(self):
        """The packed structure the kernel engine maintains on device: a
        ``PackedGraph``, the ``ShardedPacked`` of a mesh run, or None."""
        if self._sharded is not None:
            return self._sharded.sharded
        return self._packed

    # ---- lifecycle -------------------------------------------------------
    def bootstrap(self, ranks: Optional[jax.Array] = None,
                  last_seq: Optional[int] = None) -> int:
        """Publish generation 0: a cold static solve, or restored ranks.
        Builds the walk index if one was requested — sampling is a pure
        function of (graph, config seed), so a checkpointed restart
        reproduces the index bit-identically from the replayed graph."""
        if ranks is None:
            ranks = self._solve("static", self._graph, None, None).ranks
        if self.engine == "kernel" and self.kernel_geometry is None:
            from repro.kernels.pagerank_spmv.tune import KernelGeometry, \
                tune_geometry
            if self._tune:
                geom, self.tune_info = tune_geometry(
                    self._graph, frontier_frac=self._frontier_frac,
                    expected_inserts=max(1024, 64 * self.ingest.capacity),
                    measure=self._tune_measure,
                    use_kernel=self._kernel_kw.get("use_kernel"),
                    cache_path=self._tune_cache_path)
                # caller-fixed keys still win over the tuned geometry
                self._pack_kw = {**self._pack_kw, **geom.pack_kw(),
                                 **self._explicit_pack}
            self.kernel_geometry = KernelGeometry(
                be=self._pack_kw["be"], vb=self._pack_kw["vb"],
                spill_lanes_per_window=self._pack_kw[
                    "spill_lanes_per_window"])
        if self.engine == "kernel" and self.mesh is not None \
                and self._sharded is None:
            from repro.dist.pagerank_dist import ShardedKernelEngine
            pack_kw = dict(self._pack_kw)
            if "num_entries" not in pack_kw:
                spare = (self._graph.edge_capacity
                         - int(self._graph.num_valid_edges()))
                pack_kw.setdefault("extra_entries",
                                   -(-spare // pack_kw["be"]))
            pack_kw.setdefault(
                "overlay_capacity", max(1024, 64 * self.ingest.capacity))
            kw = dict(self._kernel_kw)
            self._sharded = ShardedKernelEngine(
                self.mesh, self._graph, pack_kw=pack_kw,
                delta_budget=self._delta_budget,
                use_kernel=kw.pop("use_kernel", False), **kw)
        if self.engine == "kernel" and self.mesh is None \
                and self._packed is None:
            from repro.kernels.pagerank_spmv.update import pack_graph
            if "num_entries" not in self._pack_kw:
                # mirror the edge list's stream headroom as empty tail
                # entries, so an overflow repack at the pinned capacity
                # can redistribute them to whichever windows grew
                spare = (self._graph.edge_capacity
                         - int(self._graph.num_valid_edges()))
                self._pack_kw.setdefault(
                    "extra_entries", -(-spare // self._pack_kw["be"]))
            # ~64 micro-batches of insertions between locator repacks
            self._pack_kw.setdefault(
                "overlay_capacity", max(1024, 64 * self.ingest.capacity))
            self._packed = pack_graph(self._graph, **self._pack_kw)
            # pin every static: overflow repacks must not change any
            # shape or static field, or the compiled update/kernel would
            # retrace mid-recovery.  max_entries_per_window is pinned at
            # the total entry capacity — the trivially safe bound, since
            # a repack may redistribute entries to windows that grew (the
            # free-slot scan it bounds is O(|Δ|·M), still tiny at M=NE)
            cap = self._packed.num_entries
            self._pack_kw["num_entries"] = cap
            self._pack_kw["max_entries_per_window"] = cap
            self._pack_kw.pop("extra_entries", None)
            import dataclasses
            self._packed = dataclasses.replace(
                self._packed, max_entries_per_window=cap)
        if self._ppr_cfg is not None and self._ppr is None:
            if self.mesh is not None:
                self._ppr = build_sharded_walk_index(
                    self._graph, self._ppr_cfg, mesh=self.mesh)
            else:
                self._ppr = build_walk_index(self._graph, self._ppr_cfg)
        self._ranks = ranks
        seq = self.ingest.start_seq - 1 if last_seq is None else last_seq
        gen = self.store.publish(self._graph, ranks, seq,
                                 ppr_index=self._ppr)
        if self.monitor is not None:
            # bind the recorder's config + capture the bootstrap anchor
            self.monitor.on_bootstrap(self)
        return gen

    # ---- debug fault injection ------------------------------------------
    def inject_fault(self, generation: int, kind: str = "rank",
                     vertex: int = 0, scale: float = 2.0) -> None:
        """DEBUG ONLY: arm a one-shot corruption for ``generation``.

        ``kind="rank"`` multiplies ``ranks[vertex]`` by ``scale`` on the
        solve's *output*, after convergence but before publish — the
        exact shape of the DF blind spot (a vertex no later frontier
        revisits keeps the corrupt value forever), which is what the
        mass sentinel and shadow verifier exist to catch.
        ``kind="event"`` redirects every insertion in that generation's
        coalesced batch to land on ``vertex`` *before* the update is
        applied (or recorded), so the served graph silently diverges
        from the submitted feed.  Used by tests and the CI incident-
        replay smoke lane; never call it in production serving.
        """
        if kind not in ("rank", "event"):
            raise ValueError(f"unknown fault kind {kind!r}")
        self._fault = dict(generation=int(generation), kind=str(kind),
                           vertex=int(vertex), scale=float(scale))

    # ---- one micro-batch -------------------------------------------------
    def step(self, force: bool = False) -> bool:
        """Apply one coalesced micro-batch if due; True if work was done."""
        if self._ranks is None:
            raise RuntimeError("bootstrap() before step()")
        tr = obs_trace.get_tracer()
        s0 = tr.now()
        batch = self.ingest.poll(force=force)
        if batch is None:
            return False
        # poll may yield nothing, so the span is recorded after the fact
        # (Chrome-trace nesting is by timestamps, not buffer order)
        tr.record("ingest.coalesce", s0, tr.now() - s0,
                  events=batch.num_events, coalesced=batch.num_coalesced)
        tel = tr.enabled if self.telemetry is None else bool(self.telemetry)
        fault = None
        if self._fault is not None \
                and self.store.generation + 1 == self._fault["generation"]:
            fault, self._fault = self._fault, None
            self.faults_injected += 1
        if fault is not None and fault["kind"] == "event":
            # corrupt the batch BEFORE it is applied or recorded: the
            # flight recorder sees (and replays) the corrupted stream,
            # exactly as a feed bug would present
            upd = batch.update
            upd = upd._replace(ins_dst=jnp.where(
                upd.ins_mask,
                jnp.asarray(fault["vertex"], upd.ins_dst.dtype),
                upd.ins_dst))
            batch = batch._replace(update=upd)
        t0 = self._clock()
        r0 = tr.now()
        graph_new = apply_batch(self._graph, batch.update)
        method = self.method
        init_state = build_initial_state(self._graph, graph_new,
                                         batch.update, self._ranks, method)
        if (self._budget is not None and method in DYNAMIC_METHODS
                and self._budget.carried_frontier is not None):
            # a capped previous batch left an unconverged frontier: fold
            # it into this batch's seed set (DF re-marks until Δ ≤ τ)
            seeds = self._budget.seeds_for_batch(np.asarray(init_state[1]))
            init_state = (init_state[0], jnp.asarray(seeds))
            self.metrics.record_budget_carryover()
        affected = init_state[1]
        fallback = False
        if method in ("traversal", "frontier", "frontier_prune"):
            frac = float(jnp.mean(affected.astype(jnp.float64)))
            if frac > self.static_fallback_frac:
                method, fallback = "static", True
                init_state = build_initial_state(
                    self._graph, graph_new, batch.update, self._ranks,
                    "static")
        # budget cap applies to dynamic solves only: a capped static
        # solve restarts cold every batch and would never converge,
        # while a capped DF/DF-P batch soundly resumes from its carried
        # frontier (straggler.IterationBudget)
        cap = (self._budget.max_iter
               if self._budget is not None and method in DYNAMIC_METHODS
               else None)
        # the fused path folds packed maintenance into the solve's first
        # sweep — one device program for the whole f32 phase
        fuse = (self._packed is not None and not fallback
                and method in DYNAMIC_METHODS)
        programs = 0
        if self._sharded is not None:
            from repro.kernels.pagerank_spmv.shard import ShardCapacityError
            try:
                self._sharded.apply_update(batch.update)
                programs += 1
            except ShardCapacityError as e:
                # budget/spill/overlay exhaustion on some shard(s):
                # repack every shard at the pinned shapes (defragments
                # freed lanes back into window order, zero recompiles).
                # Only the typed capacity error means "recoverable by
                # repack" — anything else is a real bug and propagates.
                self._sharded.repack(graph_new)
                self.metrics.record_packed_rebuild(shards=e.shards)
        elif self._packed is not None and not fuse:
            from repro.kernels.pagerank_spmv.update import \
                apply_batch_packed
            try:
                self._packed = apply_batch_packed(self._packed, batch.update)
                programs += 1
            except ValueError:
                # spill/overlay exhaustion: repack at the pinned shapes,
                # which also defragments freed lanes back into window order
                self._packed = self._repack(graph_new)
                self.metrics.record_packed_rebuild()
        # edge-list update + delta routing/packed maintenance (the fused
        # path defers maintenance into the solve program, traced there)
        tr.record("route_update", r0, tr.now() - r0,
                  programs=programs, fused=fuse)
        if fuse:
            from repro.core.kernel_engine import fused_hybrid_pagerank
            kw = dict(KERNEL_FLAGS[method], **self._kernel_kw, **self.pr_kw)
            kw.setdefault("telemetry", tel)
            if cap is not None:
                kw["max_iter"] = cap
            try:
                self._packed, res = fused_hybrid_pagerank(
                    graph_new, self._packed, batch.update, *init_state,
                    **kw)
            except ValueError:
                # overflow surfaced inside the fused program: repack at
                # the pinned shapes and re-run with the SAME update —
                # maintenance is idempotent after the repack (deletions
                # already absent, insertions already live), so only the
                # solve repeats
                self._packed = self._repack(graph_new)
                self.metrics.record_packed_rebuild()
                self._packed, res = fused_hybrid_pagerank(
                    graph_new, self._packed, batch.update, *init_state,
                    **kw)
            programs += 1 + (1 if kw.get("polish", True) else 0)
        else:
            with tr.span("solve", method=method, engine=self.engine):
                res = self._solve(method, graph_new, batch.update,
                                  self._ranks, graph_prev=self._graph,
                                  init_state=init_state, telemetry=tel,
                                  max_iter=cap)
                tr.sync(res.ranks)
            if self.engine == "kernel" and self.mesh is None \
                    and method in DYNAMIC_METHODS:
                programs += 1 + (1 if self._kernel_kw.get("polish", True)
                                 else 0)
            else:
                programs += 1   # one XLA solve (mesh paths count theirs)
        if self._budget is not None:
            if cap is not None:
                # exit-at-cap with Δ still above τ means unconverged:
                # the ever-affected set is the frontier to re-seed
                tol = float(self.pr_kw.get("tol", pr.TOL))
                converged = (int(res.iterations) < cap
                             or float(res.delta) <= tol)
                self._budget.after_batch(converged,
                                         np.asarray(res.affected_ever))
            else:
                # static fallback ran uncapped to full convergence
                self._budget.after_batch(True, None)
        if fault is not None and fault["kind"] == "rank":
            res = res._replace(
                ranks=res.ranks.at[fault["vertex"]].multiply(
                    fault["scale"]))
        resampled = 0
        if self._ppr is not None:
            # the same touched signal that seeds the DF frontier drives
            # walk invalidation — stale suffixes resample on Gᵗ
            touched = touched_vertices_mask(batch.update,
                                            graph_new.num_vertices)
            if isinstance(self._ppr, ShardedWalkIndex):
                self._ppr, resampled = repair_walk_index_sharded(
                    self._ppr, graph_new, touched)
            else:
                self._ppr, resampled = repair_walk_index(
                    self._ppr, graph_new, touched)
        # one host sync covers the batch: the repair kernels (when any
        # walk actually resampled) were enqueued after the rank update,
        # so waiting on both keeps the reported latency honest without a
        # second device round trip — and a no-stale batch never touches
        # the (unchanged) steps buffer at all
        _block((res.ranks, self._ppr.steps) if resampled > 0
               else res.ranks)
        latency = self._clock() - t0
        self._graph, self._ranks = graph_new, res.ranks
        with tr.span("snapshot.publish"):
            self.store.publish(graph_new, res.ranks, batch.last_seq,
                               ppr_index=self._ppr)
        if self.on_publish is not None:
            self.on_publish(self.store.snapshot(), batch)
        comm = 0
        if self._sharded is not None:
            comm = int(getattr(self._sharded, "last_comm_bytes", 0))
        affected_count = int(jnp.sum(res.affected_ever))
        self.metrics.record_batch(
            latency, batch.num_events, batch.num_coalesced,
            affected=affected_count,
            iterations=int(res.iterations), fallback=fallback,
            walks_resampled=resampled,
            edges_processed=int(res.edges_processed),
            vertices_processed=int(res.vertices_processed),
            comm_bytes=comm, device_programs=programs)
        self._observe_batch(tr, batch, res, tel)
        if self.monitor is not None:
            m0 = tr.now()
            self.monitor.on_batch(
                engine=self, batch=batch, graph=graph_new, result=res,
                method=method, fallback=fallback, latency_s=latency,
                affected=affected_count, fault=fault)
            tr.record("monitor.observe", m0, tr.now() - m0)
            if self.faults_injected:
                self.metrics.set_gauge("faults_injected",
                                       float(self.faults_injected))
        tr.record("serve.step", s0, tr.now() - s0, method=method,
                  events=batch.num_events, fallback=fallback,
                  device_programs=programs)
        return True

    def _observe_batch(self, tr, batch, res, tel: bool):
        """Per-batch telemetry capture + engine-attribute gauges."""
        self.last_telemetry = None
        raw = getattr(res, "telemetry", None)
        if tel and raw is not None:
            if isinstance(raw, np.ndarray):
                ft = FrontierTelemetry(raw)   # pre-trimmed by a wrapper
            else:
                # padded device rows straight out of a jitted loop
                ft = FrontierTelemetry.from_padded(raw, res.iterations)
            self.last_telemetry = ft
            summary = ft.summary()
            self.metrics.record_frontier(summary)
            tr.instant("frontier.telemetry", **summary)
            if self.telemetry_sink is not None:
                self.telemetry_sink.write(
                    dict(seq=int(batch.last_seq), summary=summary,
                         rows=ft.rows()), kind="frontier")
        m = self.metrics
        if self.tune_info is not None:
            m.set_gauge("tune_cache_hit_rate",
                        1.0 if getattr(self.tune_info, "cache_hit", False)
                        else 0.0)
        if self._sharded is not None \
                and getattr(self._sharded, "halo", None) is not None:
            from repro.kernels.pagerank_spmv.shard import halo_occupancy
            m.set_gauge("halo_occupancy", halo_occupancy(self._sharded.halo))
        m.set_gauge("staleness_in_events",
                    max(0, self.ingest.latest_seq - int(batch.last_seq)))

    def _repack(self, graph: EdgeListGraph):
        """Repack at the pinned shapes, degrading the spill guarantee.

        Once windows have grown, the bootstrap ``spill_lanes_per_window``
        may no longer fit the pinned ``num_entries``; serving must not
        die on its own recovery path, so retry on the windows' natural
        slack alone.  A failure beyond that is the genuine capacity
        limit (the edge list itself is near overflow) and propagates.
        """
        from repro.kernels.pagerank_spmv.update import pack_graph
        try:
            return pack_graph(graph, **self._pack_kw)
        except ValueError:
            return pack_graph(graph,
                              **{**self._pack_kw,
                                 "spill_lanes_per_window": 0})

    def _solve(self, method: Method, graph_new: EdgeListGraph, update,
               prev_ranks, graph_prev: Optional[EdgeListGraph] = None,
               init_state: Optional[tuple] = None, telemetry: bool = False,
               max_iter: Optional[int] = None):
        graph_prev = graph_prev if graph_prev is not None else graph_new
        # budget cap (constant across batches, so one trace variant)
        capkw = {} if max_iter is None else dict(max_iter=max_iter)
        if self.mesh is not None:
            if self._sharded is not None and method in DYNAMIC_METHODS:
                init_ranks, init_affected = (
                    init_state if init_state is not None
                    else build_initial_state(graph_prev, graph_new, update,
                                             prev_ranks, method))
                return self._sharded.solve(graph_new, init_ranks,
                                           init_affected,
                                           telemetry=telemetry,
                                           **KERNEL_FLAGS[method],
                                           **{**self.pr_kw, **capkw})
            # the XLA shard_map step exposes endpoint scalars only —
            # per-iteration rows would ride the wire every sweep
            return distributed_pagerank(graph_prev, graph_new, update,
                                        prev_ranks, method, self.mesh,
                                        init_state=init_state,
                                        **{**self.pr_kw, **capkw})
        init_ranks, init_affected = (
            init_state if init_state is not None else build_initial_state(
                graph_prev, graph_new, update, prev_ranks, method))
        if self.engine == "kernel" and method in DYNAMIC_METHODS:
            from repro.core.kernel_engine import hybrid_pagerank
            kw = dict(KERNEL_FLAGS[method], **self._kernel_kw,
                      **self.pr_kw, **capkw)
            kw.setdefault("telemetry", telemetry)
            return hybrid_pagerank(graph_new, self._packed, init_ranks,
                                   init_affected, **kw)
        kw = dict(LOOP_FLAGS[method], **self.pr_kw, **capkw)
        kw.setdefault("telemetry", telemetry)
        return pr._pagerank_loop(graph_new, init_ranks, init_affected, **kw)

    def drain(self, force: bool = True) -> int:
        """Run steps until the ingest queue is empty; returns batch count."""
        n = 0
        while self.step(force=force):
            n += 1
        return n

    # ---- background thread ----------------------------------------------
    def start(self, idle_sleep: float = 0.001):
        """Run the step loop in a daemon thread until ``stop``."""
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                if not self.step():
                    time.sleep(idle_sleep)

        self._thread = threading.Thread(target=loop, name="serve-engine",
                                        daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        if drain:
            self.drain(force=True)

    def close(self):
        """Full shutdown: stop the step thread (without force-draining a
        shedding queue) and close the correctness monitor, which joins
        the shadow-verifier thread and flushes its latest-wins mailbox
        so a pending divergence is reported, never dropped.  Idempotent.
        """
        if self._closed:
            return
        self._closed = True
        self.stop(drain=False)
        if self.monitor is not None:
            self.monitor.close()
