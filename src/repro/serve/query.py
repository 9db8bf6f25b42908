"""Query surface over the front snapshot: point ranks, top-k, PPR top-k.

Every query reads ONE atomically-published ``Snapshot`` — the (graph,
ranks, generation) triple is consistent by construction (state.py), and
the answer carries the generation it was served from.  Staleness is
measured in *events*: how many accepted ingest events the snapshot's
``last_seq`` trails the newest submitted seq at query time.

``top_k`` is jit-compiled (``jax.lax.top_k``) and cached per k, so the
hot query path is one compiled executable on the already-device-resident
rank vector.

``personalized_top_k`` has two paths, selected by ``mode``:

* ``"index"`` — answer from the snapshot's random-walk index
  (``repro.ppr``), a few device ops per query; requires the engine to
  maintain one (``ServeEngine(ppr_index=...)``).
* ``"exact"`` — full DF-P PPR solve on the snapshot graph
  (``core.extensions.personalized_pagerank``), the accuracy oracle.
  Solves are memoized per (generation, seed set, solver options), so
  repeated identical queries within a generation are O(1) — the solve
  runs once per snapshot, not once per call.
* ``"auto"`` (default) — the index when the snapshot carries one, no
  solver options were passed (they imply exact semantics), AND the
  seed set's effective sample (Σ deg·R, ``ppr.effective_walks``)
  clears ``min_effective_walks``; the exact path otherwise.  Cold/thin
  seeds get oracle answers, warm seeds get the fast path.

With ``repro.obs`` tracing on, each call records one span —
``query.point``, ``query.top_k`` or ``query.ppr`` — with its ``qid`` (a
per-client counter), ``generation`` and ``staleness`` (and the PPR path
taken as ``mode``), and inside it one ``query.device`` span with the
same ``qid``: from dispatching the device work (for a point query, the
device-to-host copy) to the host holding the answer.  The answers are
read with ``np.asarray``, which blocks, so the spans add no sync.
"""
from __future__ import annotations

import itertools
import threading
from collections import OrderedDict
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.extensions import personalized_pagerank
from repro.obs import trace as obs_trace
from repro.ppr import DEFAULT_MIN_EFFECTIVE_WALKS, effective_walks, \
    ppr_top_k
from repro.serve.ingest import IngestQueue
from repro.serve.metrics import ServeMetrics
from repro.serve.state import RankStore

_EXACT_CACHE_MAX = 32


class QueryResult(NamedTuple):
    vertices: np.ndarray   # int64[k]
    ranks: np.ndarray      # f64[k]
    generation: int
    staleness_events: int


class QueryClient:
    def __init__(self, store: RankStore, ingest: Optional[IngestQueue] = None,
                 metrics: Optional[ServeMetrics] = None,
                 min_effective_walks: int = DEFAULT_MIN_EFFECTIVE_WALKS):
        self.store = store
        self.ingest = ingest
        self.metrics = metrics
        self.min_effective_walks = min_effective_walks
        self._topk_fns: dict = {}
        # exact-PPR memo: (generation, seeds, solver kw) -> rank vector;
        # queries run from any thread, so cache ops take the lock
        self._exact_cache: OrderedDict = OrderedDict()
        self._cache_lock = threading.Lock()
        self._qids = itertools.count()     # next() is atomic under the GIL

    def _staleness(self, snap) -> int:
        if self.ingest is None:
            return 0
        return max(0, self.ingest.latest_seq - snap.last_seq)

    def _record(self, staleness: int):
        if self.metrics is not None:
            self.metrics.record_query(staleness)

    # ---- queries ---------------------------------------------------------
    def get_ranks(self, vertices: Sequence[int]) -> QueryResult:
        """Point lookups of the current ranks for the given vertices."""
        tr = obs_trace.get_tracer()
        qid = next(self._qids)
        with tr.span("query.point", qid=qid) as sp:
            snap = self.store.snapshot()
            verts = np.asarray(vertices, np.int64).reshape(-1)
            stale = self._staleness(snap)
            self._record(stale)
            with tr.span("query.device", qid=qid):
                ranks = np.asarray(snap.ranks)[verts]
            sp.set(generation=snap.generation, staleness=stale)
        return QueryResult(verts, ranks, snap.generation, stale)

    def _topk(self, ranks: jax.Array, k: int):
        fn = self._topk_fns.get(k)
        if fn is None:
            fn = self._topk_fns.setdefault(
                k, jax.jit(partial(jax.lax.top_k, k=k)))
        vals, idx = fn(ranks)
        return np.asarray(idx, np.int64), np.asarray(vals)

    def top_k(self, k: int) -> QueryResult:
        """The k highest-ranked vertices (jit, cached per k)."""
        tr = obs_trace.get_tracer()
        qid = next(self._qids)
        with tr.span("query.top_k", qid=qid) as sp:
            snap = self.store.snapshot()
            with tr.span("query.device", qid=qid):
                idx, vals = self._topk(snap.ranks, k)
            stale = self._staleness(snap)
            self._record(stale)
            sp.set(generation=snap.generation, staleness=stale)
        return QueryResult(idx, vals, snap.generation, stale)

    def _exact_ppr_ranks(self, snap, seeds: Sequence[int],
                         **ppr_kw) -> jax.Array:
        """Memoized exact PPR solve on one snapshot (LRU per (generation,
        seed set, options)) — a published snapshot is immutable, so the
        solution cannot change within a generation."""
        key = (snap.generation,
               tuple(sorted(set(int(s) for s in np.asarray(seeds)
                                .reshape(-1)))),
               tuple(sorted(ppr_kw.items())))
        with self._cache_lock:
            ranks = self._exact_cache.get(key)
            if ranks is not None:
                self._exact_cache.move_to_end(key)
                return ranks
        # solve outside the lock (seconds-long); a concurrent identical
        # query may duplicate the solve, which is wasteful but correct
        V = snap.graph.num_vertices
        seed_mask = jnp.zeros((V,), bool).at[
            jnp.asarray(np.asarray(seeds, np.int64))].set(True)
        ranks = personalized_pagerank(snap.graph, seed_mask, **ppr_kw).ranks
        with self._cache_lock:
            while len(self._exact_cache) >= _EXACT_CACHE_MAX:
                self._exact_cache.popitem(last=False)
            self._exact_cache[key] = ranks
        return ranks

    def personalized_top_k(self, seeds: Sequence[int], k: int,
                           mode: str = "auto", **ppr_kw) -> QueryResult:
        """Top-k by Personalized PageRank from a seed set, on the snapshot
        (see module docstring for the index/exact/auto routing)."""
        if mode not in ("auto", "index", "exact"):
            raise ValueError(f"unknown personalized_top_k mode {mode!r}")
        tr = obs_trace.get_tracer()
        qid = next(self._qids)
        with tr.span("query.ppr", qid=qid) as sp:
            snap = self.store.snapshot()
            seeds = np.asarray(seeds, np.int64).reshape(-1)
            if len(seeds) == 0 or seeds.min() < 0 or \
                    seeds.max() >= snap.graph.num_vertices:
                raise ValueError("seeds must be non-empty and within "
                                 f"[0, {snap.graph.num_vertices})")
            index = snap.ppr_index
            if mode == "index" and index is None:
                raise ValueError("mode='index' but the snapshot carries no "
                                 "walk index (start ServeEngine with "
                                 "ppr_index=)")
            if mode == "index" and ppr_kw:
                raise ValueError("solver options are exact-path only; "
                                 f"mode='index' got {sorted(ppr_kw)}")
            # auto: solver options imply the exact solver's semantics, so
            # their presence routes to it (only explicit mode="index"
            # rejects)
            use_index = index is not None and (
                mode == "index" or
                (mode == "auto" and not ppr_kw and
                 effective_walks(index, seeds) >= self.min_effective_walks))
            with tr.span("query.device", qid=qid):
                if use_index:
                    idx, vals = ppr_top_k(index, seeds, k)
                    idx, vals = np.asarray(idx, np.int64), np.asarray(vals)
                else:
                    ranks = self._exact_ppr_ranks(snap, seeds, **ppr_kw)
                    idx, vals = self._topk(ranks, k)
            stale = self._staleness(snap)
            self._record(stale)
            sp.set(generation=snap.generation, staleness=stale,
                   mode="index" if use_index else "exact")
        return QueryResult(idx, vals, snap.generation, stale)
