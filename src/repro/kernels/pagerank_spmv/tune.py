"""Autotuned kernel geometry: per-graph (BE, VB, spill) instead of fixed
pack constants.

The frontier-gated SpMV's cost profile is asymmetric (see
``roofline.analysis.gated_spmv_iteration_cost``): HBM traffic is gated to
active entries, but the static grid runs every entry's MXU step, so the
right (BE, VB) depends on the graph — its size, its *dst in-degree
distribution* (which fixes how many entries each candidate geometry
packs, including padding waste on skewed windows) and the frontier
fraction serving actually sees.  This module derives the geometry in two
stages:

  1. **model ranking** — for each candidate on the (BE, VB) grid, compute
     the exact per-window entry counts from the graph's dst histogram
     (degree distribution, not a uniform-fill guess) and rank by the
     roofline iteration cost at the expected frontier fraction;
  2. **measured search (fallback)** — time the top ``measure_top``
     candidates on one representative gated contribution (pack + SpMV on
     a clustered frontier of the expected fraction) and keep the winner.

Winners are cached keyed by ``(device kind, graph-shape signature,
frontier bucket)`` and the cache persists as JSON
(``~/.cache/repro/kernel_tune.json`` or ``$REPRO_TUNE_CACHE``), so a
serving restart — or any later stream over a same-shaped graph — skips
the search entirely.  ``ServeEngine`` bootstrap, ``pack_graph`` /
``pack_blocks`` (via ``KernelGeometry.pack_kw``) and
``dist.ShardedKernelEngine`` (``pack_shards``) all consume the result;
``launch/serve.py`` logs what was picked.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Optional, Sequence

import numpy as np

from repro.roofline.analysis import TARGET_KIND, gated_spmv_iteration_cost

__all__ = ["KernelGeometry", "TuneCache", "TuneInfo", "candidate_costs",
           "default_cache_path", "graph_signature", "tune_geometry",
           "CANDIDATE_GRID"]

# (be, vb) candidates: VB stays a multiple of 128 lanes (the TPU lane
# width constraint the default 256 = 2x128 encodes), BE spans the
# paper's OpenMP chunk (2048) down to serving-fine entries
CANDIDATE_GRID: tuple = tuple(
    (be, vb) for be in (256, 512, 1024, 2048) for vb in (128, 256, 512))


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """One pack geometry: entry width, window width, spill reservation."""

    be: int
    vb: int
    spill_lanes_per_window: int

    def pack_kw(self) -> dict:
        """kwargs for pack_blocks / pack_graph / pack_shards."""
        return dict(be=self.be, vb=self.vb,
                    spill_lanes_per_window=self.spill_lanes_per_window)

    def describe(self) -> str:
        return (f"be={self.be} vb={self.vb} "
                f"spill={self.spill_lanes_per_window}")


@dataclasses.dataclass(frozen=True)
class TuneInfo:
    """How a geometry was picked (logged by launch/serve, benched)."""

    source: str                      # "cache" | "model" | "measured"
    cache_hit: bool
    tune_time_s: float
    key: str
    # (geometry, predicted_s, measured_s|None) per candidate considered
    candidates: tuple = ()


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, int(math.ceil(math.log2(max(1, x)))))


def spill_for_stream(num_windows: int, expected_inserts: int,
                     be: int) -> int:
    """Spill lanes per window sized to absorb ``expected_inserts`` net
    insertions between repacks with 4x skew headroom, clamped to [16, BE]
    (a window never reserves more than one extra entry of slack)."""
    per_window = -(-4 * max(0, expected_inserts) // max(1, num_windows))
    return int(min(be, max(16, _pow2_ceil(per_window))))


def graph_signature(num_vertices: int, num_edges: int,
                    frontier_frac: float) -> str:
    """Bucketed shape key: graphs within ~2x in V/E and the same frontier
    decade share a tuned geometry (re-tuning inside a bucket would churn
    the cache for sub-model-resolution differences)."""
    lv = int(round(math.log2(max(2, num_vertices))))
    le = int(round(math.log2(max(2, num_edges))))
    lf = int(round(math.log10(max(1e-6, min(1.0, frontier_frac)))))
    return f"v2^{lv}-e2^{le}-f1e{lf}"


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def default_cache_path() -> str:
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "kernel_tune.json")


class TuneCache:
    """Persistent {key: geometry} store (JSON, atomic rewrite).

    Tolerant by construction: a missing, corrupt or wrong-schema file is
    an empty cache, never an error — tuning must not take serving down.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: dict = {}
        try:
            with open(self.path) as f:
                raw = json.load(f)
            for k, v in raw.items():
                self._data[k] = KernelGeometry(
                    be=int(v["be"]), vb=int(v["vb"]),
                    spill_lanes_per_window=int(v["spill_lanes_per_window"]))
        except (OSError, ValueError, KeyError, TypeError):
            self._data = {}

    def __len__(self) -> int:
        return len(self._data)

    def get(self, key: str) -> Optional[KernelGeometry]:
        return self._data.get(key)

    def put(self, key: str, geom: KernelGeometry) -> None:
        self._data[key] = geom
        self.save()

    def save(self) -> None:
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({k: dataclasses.asdict(g)
                           for k, g in self._data.items()}, f, indent=2,
                          sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:                                # pragma: no cover
            pass                                       # cache is best-effort


# ---------------------------------------------------------------------------
# model ranking
# ---------------------------------------------------------------------------

def _geometry_cost(dst: np.ndarray, num_vertices: int, be: int, vb: int,
                   spill: int, frontier_frac: float, kind: str) -> float:
    """Roofline iteration cost of (be, vb, spill) on THIS graph: entry
    counts come from the actual dst histogram (pack_blocks' exact sizing
    arithmetic), active work from the expected frontier fraction."""
    nw = -(-num_vertices // vb)
    counts = np.bincount(dst // vb, minlength=nw).astype(np.int64)
    n_base = -(-counts // be)
    slack = n_base * be - counts
    need = np.maximum(0, spill - slack)
    n_w = n_base + -(-need // be)                      # entries per window
    total_entries = int(np.sum(n_w))
    # clustered frontier of fraction f: ~f of the windows are active and
    # (sampling windows proportionally) carry ~f of the entries
    f = min(1.0, max(frontier_frac, 1.0 / max(1, nw)))
    active_windows = max(1.0, f * nw)
    active_entries = max(1.0, f * total_entries)
    return gated_spmv_iteration_cost(
        total_entries=total_entries, active_entries=active_entries,
        active_windows=active_windows, be=be, vb=vb, kind=kind)["total_s"]


def candidate_costs(dst: np.ndarray, num_vertices: int,
                    frontier_frac: float, expected_inserts: int,
                    grid: Sequence = CANDIDATE_GRID,
                    kind: str = TARGET_KIND) -> list:
    """[(KernelGeometry, predicted_s)] ranked ascending by model cost on
    the chip ``kind`` names (``roofline.analysis.peaks_for``)."""
    dst = np.asarray(dst)
    out = []
    for be, vb in grid:
        if vb > max(128, _pow2_ceil(num_vertices)):
            continue                  # window wider than the whole graph
        nw = -(-num_vertices // vb)
        spill = spill_for_stream(nw, expected_inserts, be)
        geom = KernelGeometry(be=be, vb=vb, spill_lanes_per_window=spill)
        out.append((geom, _geometry_cost(dst, num_vertices, be, vb, spill,
                                         frontier_frac, kind)))
    out.sort(key=lambda t: t[1])
    return out


# ---------------------------------------------------------------------------
# measured search
# ---------------------------------------------------------------------------

def _measure(graph, geom: KernelGeometry, frontier_frac: float,
             use_kernel: bool, repeats: int = 2) -> float:
    """Seconds for one gated contribution at ``geom`` on a clustered
    frontier of the expected fraction (pack time excluded — packing is
    per-repack, the SpMV is per-iteration)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.pagerank_spmv.ops import gated_contrib
    from repro.kernels.pagerank_spmv.update import pack_graph

    n = graph.num_vertices
    packed = pack_graph(graph, **geom.pack_kw())
    aff = np.zeros(n, bool)
    aff[: max(1, int(frontier_frac * n))] = True
    aff = jnp.asarray(aff)
    ranks = jnp.full((n,), 1.0 / n, jnp.float32)
    inv = (1.0 / graph.out_degree(include_self_loop=True)).astype(
        jnp.float32)
    out = gated_contrib(packed, ranks, inv, aff, use_kernel=use_kernel)
    jax.block_until_ready(out)
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = gated_contrib(packed, ranks, inv, aff, use_kernel=use_kernel)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def tune_geometry(graph, *, frontier_frac: float = 0.05,
                  expected_inserts: int = 1024,
                  measure: bool = False, measure_top: int = 3,
                  use_kernel: Optional[bool] = None,
                  cache: Optional[TuneCache] = None,
                  cache_path: Optional[str] = None,
                  grid: Sequence = CANDIDATE_GRID
                  ) -> tuple[KernelGeometry, TuneInfo]:
    """Pick (BE, VB, spill) for ``graph``.

    Order of attack: persistent cache (keyed by device kind + bucketed
    graph shape + frontier decade) → roofline model ranking over the
    candidate grid → optional measured search over the model's top
    ``measure_top`` (the 2-3-candidate first-batch timing fallback).
    The winner is written back to the cache either way, so restarts and
    same-shaped streams skip straight to the cache hit.
    """
    t0 = time.perf_counter()
    n = graph.num_vertices
    e = int(graph.num_valid_edges())
    kind = device_kind()
    key = f"{kind.replace(' ', '_')}/{graph_signature(n, e, frontier_frac)}"
    if cache is None:
        cache = TuneCache(cache_path)
    hit = cache.get(key)
    if hit is not None:
        return hit, TuneInfo(source="cache", cache_hit=True,
                             tune_time_s=time.perf_counter() - t0, key=key)

    dst = np.asarray(graph.dst)[np.asarray(graph.valid)]
    ranked = candidate_costs(dst, n, frontier_frac, expected_inserts,
                             grid=grid, kind=kind)
    source = "model"
    cands = [(g, p, None) for g, p in ranked]
    best = ranked[0][0]
    if measure and len(ranked) > 1:
        import jax
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        timed = []
        for geom, pred in ranked[: max(2, measure_top)]:
            timed.append((geom, pred,
                          _measure(graph, geom, frontier_frac, use_kernel)))
        timed.sort(key=lambda t: t[2])
        best = timed[0][0]
        cands = timed + cands[len(timed):]
        source = "measured"
    cache.put(key, best)
    return best, TuneInfo(source=source, cache_hit=False,
                          tune_time_s=time.perf_counter() - t0, key=key,
                          candidates=tuple(cands))
