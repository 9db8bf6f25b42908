"""Autotuner: model ranking sanity, cache hit/miss, persistence, and the
ServeEngine bootstrap wiring that consumes the tuned geometry."""
import json

import numpy as np
import pytest

import repro  # noqa: F401
from repro.graph.generators import rmat_edges
from repro.graph.structure import from_coo
from repro.kernels.pagerank_spmv.tune import (CANDIDATE_GRID, KernelGeometry,
                                              TuneCache, candidate_costs,
                                              graph_signature,
                                              spill_for_stream,
                                              tune_geometry)


def _graph(scale=9, edge_factor=6, seed=11, extra=512):
    edges, n = rmat_edges(scale, edge_factor, seed=seed)
    return from_coo(edges[:, 0], edges[:, 1], n,
                    edge_capacity=len(edges) + extra)


# ---------------------------------------------------------------------------
# model ranking
# ---------------------------------------------------------------------------

def test_candidate_costs_covers_grid_and_ranks():
    g = _graph()
    dst = np.asarray(g.dst)[np.asarray(g.valid)]
    ranked = candidate_costs(dst, g.num_vertices, 0.05, 1024)
    assert len(ranked) == len(CANDIDATE_GRID)
    costs = [c for _, c in ranked]
    assert costs == sorted(costs)
    assert all(c > 0 for c in costs)
    geoms = {(geo.be, geo.vb) for geo, _ in ranked}
    assert geoms == set(CANDIDATE_GRID)


def test_model_prefers_wider_blocks_on_dense_frontier():
    # at frontier=1.0 every entry is active: traffic is fixed, so the
    # model must rank by grid-step overhead, which favours larger BE*VB
    g = _graph()
    dst = np.asarray(g.dst)[np.asarray(g.valid)]
    best, _ = candidate_costs(dst, g.num_vertices, 1.0, 0)[0]
    worst, _ = candidate_costs(dst, g.num_vertices, 1.0, 0)[-1]
    assert best.be * best.vb > worst.be * worst.vb


def test_peaks_keyed_by_device_kind():
    from repro.roofline.analysis import PEAKS, TARGET_KIND, peaks_for
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]
    assert "source" in PEAKS["TPU v5 lite"]
    assert peaks_for("cpu") is PEAKS[TARGET_KIND]     # no chip: the target
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")                           # unknown chip: error
    dst = np.asarray(_graph().dst)
    with pytest.raises(KeyError):
        candidate_costs(dst, 512, 0.05, 1024, kind="TPU v9")


def test_spill_for_stream_bounds():
    assert spill_for_stream(100, 0, 512) == 16          # floor
    assert spill_for_stream(1, 10**9, 512) == 512       # ceil at BE
    s = spill_for_stream(64, 1024, 512)
    assert 16 <= s <= 512 and (s & (s - 1)) == 0        # pow2 in range


def test_graph_signature_buckets():
    a = graph_signature(1000, 8000, 0.05)
    assert a == graph_signature(1100, 8800, 0.06)       # same bucket
    assert a != graph_signature(4000, 8000, 0.05)       # V moved 2 octaves
    assert a != graph_signature(1000, 8000, 0.005)      # frontier decade


# ---------------------------------------------------------------------------
# cache: hit/miss + persistence roundtrip
# ---------------------------------------------------------------------------

def test_tune_cache_miss_then_hit(tmp_path):
    path = str(tmp_path / "tune.json")
    g = _graph()
    geom1, info1 = tune_geometry(g, cache_path=path)
    assert info1.source == "model" and not info1.cache_hit
    assert len(info1.candidates) == len(CANDIDATE_GRID)
    geom2, info2 = tune_geometry(g, cache_path=path)
    assert info2.source == "cache" and info2.cache_hit
    assert geom2 == geom1
    assert info2.key == info1.key


def test_tune_cache_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = TuneCache(path)
    geom = KernelGeometry(be=1024, vb=256, spill_lanes_per_window=64)
    cache.put("k", geom)
    # fresh instance reads the same JSON back
    reloaded = TuneCache(path)
    assert len(reloaded) == 1
    assert reloaded.get("k") == geom
    # the file itself is plain {key: {be, vb, spill}} JSON
    with open(path) as f:
        raw = json.load(f)
    assert raw["k"]["be"] == 1024


def test_tune_cache_tolerates_corrupt_file(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write("{not json")
    cache = TuneCache(path)
    assert len(cache) == 0
    cache.put("k", KernelGeometry(be=256, vb=128, spill_lanes_per_window=16))
    assert TuneCache(path).get("k") is not None


def test_tune_frontier_decade_changes_key(tmp_path):
    path = str(tmp_path / "tune.json")
    g = _graph()
    _, a = tune_geometry(g, frontier_frac=0.05, cache_path=path)
    _, b = tune_geometry(g, frontier_frac=0.005, cache_path=path)
    assert a.key != b.key and not b.cache_hit


def test_measured_search_times_top_candidates(tmp_path):
    path = str(tmp_path / "tune.json")
    g = _graph(scale=8)
    geom, info = tune_geometry(g, cache_path=path, measure=True,
                               measure_top=2, use_kernel=False)
    assert info.source == "measured"
    timed = [c for c in info.candidates if c[2] is not None]
    assert len(timed) == 2
    assert all(t > 0 for _, _, t in timed)
    assert geom == min(timed, key=lambda c: c[2])[0]


# ---------------------------------------------------------------------------
# ServeEngine consumes the tuned geometry at bootstrap
# ---------------------------------------------------------------------------

def _serve_parts(graph):
    from repro.serve import IngestQueue, RankStore
    return IngestQueue(flush_size=8, flush_interval=1e9,
                       max_pending=1024), RankStore()


def test_serve_bootstrap_tunes_and_logs_geometry(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    from repro.serve import ServeEngine
    g = _graph(scale=8)
    ingest, store = _serve_parts(g)
    eng = ServeEngine(g, ingest, store, method="frontier", engine="kernel",
                      kernel_opts=dict(use_kernel=False))
    eng.bootstrap()
    assert eng.kernel_geometry is not None
    assert eng.tune_info is not None and not eng.tune_info.cache_hit
    assert (eng.kernel_geometry.be, eng.kernel_geometry.vb) in CANDIDATE_GRID
    # second engine over the same-shaped graph hits the persisted cache
    ingest2, store2 = _serve_parts(g)
    eng2 = ServeEngine(g, ingest2, store2, method="frontier",
                       engine="kernel", kernel_opts=dict(use_kernel=False))
    eng2.bootstrap()
    assert eng2.tune_info.cache_hit
    assert eng2.kernel_geometry == eng.kernel_geometry


def test_serve_explicit_geometry_disables_tuning(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    from repro.serve import ServeEngine
    g = _graph(scale=8)
    ingest, store = _serve_parts(g)
    eng = ServeEngine(g, ingest, store, method="frontier", engine="kernel",
                      kernel_opts=dict(be=32, vb=16,
                                       spill_lanes_per_window=64,
                                       use_kernel=False))
    eng.bootstrap()
    assert eng.tune_info is None                        # no tuning ran
    assert eng.kernel_geometry.be == 32
    assert eng.kernel_geometry.vb == 16
    assert not (tmp_path / "tune.json").exists()
