"""repro.ppr: walk-index structure, estimator accuracy vs the exact
oracle, repair equivalence + resample-count invariant, deterministic
(process-independent) seeding, serve integration, query routing."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.core.extensions import personalized_pagerank
from repro.graph.dynamic import (apply_batch, make_batch_update,
                                 touched_vertices_mask)
from repro.graph.generators import rmat_edges
from repro.graph.structure import from_coo
from repro.kernels.pagerank_spmv.shard import ShardCapacityError
from repro.ppr import (IndexConfig, ShardedWalkIndex, build_sharded_walk_index,
                       build_walk_index, diagnostics, effective_walks,
                       error_bound, ppr_estimate, ppr_top_k, precision_at_k,
                       repair_walk_index, repair_walk_index_sharded,
                       shard_walk_index, stale_walks, truncation_bias,
                       unshard_walk_index, walks_for_error)
from repro.ppr.repair import STALE_SCAN_IDS
from repro.serve import (IngestQueue, QueryClient, RankStore, ServeEngine,
                         ServeMetrics)


@pytest.fixture(scope="module")
def small():
    edges, n = rmat_edges(8, 8, seed=1)               # 256 vertices
    g = from_coo(edges[:, 0], edges[:, 1], n,
                 edge_capacity=len(edges) + 512)
    return g, edges, n


@pytest.fixture(scope="module")
def index(small):
    g, _, _ = small
    return build_walk_index(g, IndexConfig(num_walks=64, max_len=16,
                                           seed=3))


# ---------------------------------------------------------------------------
# structure: layout, hop validity, determinism
# ---------------------------------------------------------------------------

def test_walk_layout(small, index):
    g, _, n = small
    assert index.steps.shape == (n, 64, 16)
    assert index.steps.dtype == jnp.int32
    # slot 0 is the source, always occupied
    assert bool(jnp.all(index.steps[:, :, 0] ==
                        jnp.arange(n, dtype=jnp.int32)[:, None]))
    # sentinel discipline: -1 once terminated, never revived
    m = np.asarray(index.mask())
    assert not np.any(~m[:, :, :-1] & m[:, :, 1:])
    assert int(index.steps.min()) >= -1
    assert int(index.steps.max()) < n


def test_hops_follow_edges_or_self_loop(small, index):
    _, edges, n = small
    live = set(map(tuple, edges.tolist()))
    s = np.asarray(index.steps)
    rng = np.random.default_rng(0)
    for v in rng.integers(0, n, 48):
        for r in rng.integers(0, 64, 4):
            w = s[v, r]
            for t in range(1, 16):
                if w[t] < 0:
                    break
                a, b = int(w[t - 1]), int(w[t])
                assert a == b or (a, b) in live       # self-loop or edge


def test_build_deterministic_same_key(small, index):
    g, _, _ = small
    again = build_walk_index(g, IndexConfig(num_walks=64, max_len=16,
                                            seed=3))
    assert bool(jnp.all(again.steps == index.steps))
    other = build_walk_index(g, IndexConfig(num_walks=64, max_len=16,
                                            seed=4))
    assert not bool(jnp.all(other.steps == index.steps))


def test_seeding_is_process_independent(tmp_path):
    """Regression (extends the PR 1 crc32-seeding fix): the walk index
    must be a pure function of (graph, config seed) so checkpointed
    serving restarts rebuild it bit-identically — no builtin hash() or
    other process-randomized state anywhere in the sampling path."""
    prog = (
        "import zlib, numpy as np, repro\n"
        "from repro.graph.generators import rmat_edges\n"
        "from repro.graph.structure import from_coo\n"
        "from repro.ppr import IndexConfig, build_walk_index\n"
        "e, n = rmat_edges(6, 4, seed=2)\n"
        "g = from_coo(e[:, 0], e[:, 1], n, edge_capacity=len(e) + 64)\n"
        "i = build_walk_index(g, IndexConfig(num_walks=8, max_len=8,"
        " seed=5))\n"
        "print(zlib.crc32(np.asarray(i.steps).tobytes()))\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digests = []
    for hash_seed in ("0", "424242"):
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(repo_root, "src"),
                   PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, env=env,
                           cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr
        digests.append(r.stdout.strip().splitlines()[-1])
    assert digests[0] == digests[1]


# ---------------------------------------------------------------------------
# estimator accuracy vs the power-iteration oracle
# ---------------------------------------------------------------------------

def test_direct_estimator_converges_to_oracle(small):
    """The raw (un-unrolled) visit-count estimator is unbiased: L1 error
    vs the exact solve shrinks ~1/sqrt(R)."""
    g, _, n = small
    sm = jnp.zeros((n,), bool).at[5].set(True)
    oracle = np.asarray(personalized_pagerank(g, sm).ranks)
    l1 = []
    for R in (64, 1024):
        idx = build_walk_index(g, IndexConfig(num_walks=R, max_len=24,
                                              seed=3))
        est = np.asarray(ppr_estimate(idx, [5], unroll=False))
        l1.append(np.abs(est - oracle).sum())
    assert l1[1] < 0.5 * l1[0]                        # 16x walks, >=2x better


@pytest.mark.slow
def test_topk_precision_vs_oracle_paper_scale(small):
    """Index top-10 matches the exact DF-P oracle at precision@10 >= 0.9
    (tie-tolerant) at paper-scale R on an RMAT graph, for both
    single-seed and seed-set queries."""
    g, _, n = small
    idx = build_walk_index(g, IndexConfig(num_walks=256, max_len=20,
                                          seed=7))
    deg = np.asarray(idx.csr.deg)
    rng = np.random.default_rng(1)
    seeds = rng.choice(np.flatnonzero(deg >= 2), 8, replace=False)
    ps = []
    for s in seeds:
        ap, _ = ppr_top_k(idx, [int(s)], 10)
        sm = jnp.zeros((n,), bool).at[int(s)].set(True)
        oracle = personalized_pagerank(g, sm).ranks
        ps.append(precision_at_k(np.asarray(ap), np.asarray(oracle), 10))
    assert np.mean(ps) >= 0.9, ps
    # seed-set query
    ss = [int(v) for v in seeds[:4]]
    ap, _ = ppr_top_k(idx, ss, 10)
    sm = jnp.zeros((n,), bool).at[jnp.asarray(ss)].set(True)
    oracle = personalized_pagerank(g, sm).ranks
    assert precision_at_k(np.asarray(ap), np.asarray(oracle), 10) >= 0.9


def test_estimate_is_distribution(index):
    est = np.asarray(ppr_estimate(index, [3, 9]))
    assert est.min() >= 0
    assert abs(est.sum() - 1.0) < 1e-9                # normalize=True


# ---------------------------------------------------------------------------
# repair: bitwise equivalence + resample-count invariant
# ---------------------------------------------------------------------------

def _batch(small, seed, n_del=6, n_ins=6):
    g, edges, n = small
    rng = np.random.default_rng(seed)
    dele = edges[rng.choice(len(edges), n_del, replace=False)]
    ins = rng.integers(0, n, size=(n_ins, 2)).astype(np.int32)
    ins = ins[ins[:, 0] != ins[:, 1]]
    return make_batch_update(dele, ins, max(8, n_del), max(8, n_ins))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_repair_matches_fresh_rebuild_bitwise(small, index, seed):
    """repair(index, Δ) == build(apply_batch(G, Δ)) bit-for-bit: same
    PRNG stream => untouched walks are kept verbatim AND resampled
    suffixes reproduce exactly what a fresh build would draw."""
    g, _, n = small
    upd = _batch(small, seed)
    g2 = apply_batch(g, upd)
    touched = touched_vertices_mask(upd, n)
    repaired, resampled = repair_walk_index(index, g2, touched)
    fresh = build_walk_index(g2, IndexConfig(num_walks=64, max_len=16,
                                             seed=3))
    assert bool(jnp.all(repaired.steps == fresh.steps))
    assert bool(jnp.all(repaired.csr.indptr == fresh.csr.indptr))
    # resample-count invariant: exactly the walks intersecting touched
    stale, _ = stale_walks(index.steps, touched)
    assert resampled == int(jnp.sum(stale)) > 0


def test_repair_untouched_walks_kept_verbatim(small, index):
    g, _, n = small
    upd = _batch(small, 5)
    g2 = apply_batch(g, upd)
    touched = touched_vertices_mask(upd, n)
    repaired, _ = repair_walk_index(index, g2, touched)
    stale, _ = stale_walks(index.steps, touched)
    keep = ~np.asarray(stale)
    assert np.array_equal(np.asarray(repaired.steps)[keep],
                          np.asarray(index.steps)[keep])


def test_repair_empty_batch_is_noop(small, index):
    g, _, n = small
    touched = jnp.zeros((n,), bool)
    repaired, resampled = repair_walk_index(index, g, touched)
    assert resampled == 0
    assert repaired.steps is index.steps


def test_repair_chain_over_stream(small):
    """Repair composes: N successive batches == one fresh build on the
    final graph (the serve-loop invariant)."""
    g, _, n = small
    cfg = IndexConfig(num_walks=32, max_len=12, seed=11)
    idx = build_walk_index(g, cfg)
    cur = g
    for seed in range(4):
        upd = _batch(small, 100 + seed, n_del=4, n_ins=8)
        nxt = apply_batch(cur, upd)
        idx, _ = repair_walk_index(idx, nxt,
                                   touched_vertices_mask(upd, n))
        cur = nxt
    fresh = build_walk_index(cur, cfg)
    assert bool(jnp.all(idx.steps == fresh.steps))


# ---------------------------------------------------------------------------
# the stale scan: compare branch, gather branch, one executable
# ---------------------------------------------------------------------------

def _gather_oracle(steps, touched):
    """The scan as one gather of the mask at every walk position."""
    V = touched.shape[0]
    visited = touched[jnp.clip(steps, 0, V - 1)] & (steps >= 0)
    return visited.any(-1), jnp.argmax(visited, axis=-1).astype(jnp.int32)


def _random_steps(rng, V, R=4, L=8):
    """Random walks over V vertices with -1 tails (length 1..L)."""
    steps = rng.integers(0, V, size=(V, R, L)).astype(np.int32)
    steps[:, :, 0] = np.arange(V)[:, None]
    length = rng.integers(1, L + 1, size=(V, R, 1))
    return np.where(np.arange(L) < length, steps, -1).astype(np.int32)


_SCAN_V = 200


def _scan_mask(case, rng):
    V, K = _SCAN_V, STALE_SCAN_IDS
    m = np.zeros(V, bool)
    if case == "random":
        m[rng.choice(V, 12, replace=False)] = True
    elif case == "vertex_0":
        m[0] = True
    elif case == "vertex_last":
        m[V - 1] = True
    elif case in ("exactly_k", "k_plus_1"):
        m[rng.choice(V, K + (case == "k_plus_1"), replace=False)] = True
    elif case == "all":
        m[:] = True
    return m


@pytest.mark.parametrize("case", ["random", "vertex_0", "vertex_last",
                                  "empty", "exactly_k", "k_plus_1", "all"])
def test_stale_scan_matches_gather_oracle(case):
    """``stale_walks`` gives the gather's (stale, first_stale_hop) bit for
    bit on both sides of the compare/gather switch: vertex 0 is where a
    -1 step lands once clipped, V-1 the last id, K and K+1 touched ids
    the two sides of the switch."""
    rng = np.random.default_rng(sum(map(ord, case)))
    steps = jnp.asarray(_random_steps(rng, _SCAN_V))
    touched = jnp.asarray(_scan_mask(case, rng))
    stale, t0 = stale_walks(steps, touched)
    want_stale, want_t0 = _gather_oracle(steps, touched)
    assert stale.dtype == want_stale.dtype and t0.dtype == want_t0.dtype
    assert np.array_equal(np.asarray(stale), np.asarray(want_stale))
    assert np.array_equal(np.asarray(t0), np.asarray(want_t0))
    if case == "empty":
        assert not bool(jnp.any(stale))
    elif case == "all":     # every source is touched
        assert bool(jnp.all(stale)) and not bool(jnp.any(t0))
    else:   # both stale and kept walks, and stale ones past hop 0
        assert 0 < int(jnp.sum(stale)) < stale.size
        assert int(t0.max()) > 0


def test_stale_scan_one_executable_for_every_touched_count():
    """After a one-vertex warm-up (the benchmark's walk warm-up), touched
    counts of 1, K and K+1 run the same executable: the branch is picked
    on the device, so no batch compiles inside a serving window."""
    rng = np.random.default_rng(9)
    V = _SCAN_V + 3                      # a shape no other test compiles
    steps = jnp.asarray(_random_steps(rng, V))
    masks = []
    for n in (1, STALE_SCAN_IDS, STALE_SCAN_IDS + 1):
        m = np.zeros(V, bool)
        m[rng.choice(V, n, replace=False)] = True
        masks.append(jnp.asarray(m))
    compiled = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        jax.block_until_ready(stale_walks(steps, masks[0]))     # warm-up
        assert any("stale_walks" in str(f) for f in compiled)
        del compiled[:]
        for m in masks:
            got = stale_walks(steps, m)
            want = _gather_oracle(steps, m)
            assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
        assert not any("stale_walks" in str(f) for f in compiled)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def test_stale_scan_cond_survives_the_shard_vmap():
    """The mesh-free sharded scan maps over the steps alone, so the
    cond's predicate (a count of ``touched``) is not batched and the
    program keeps a cond that runs one branch, not a select of both."""
    from repro.ppr.shard import _stale_stacked_host
    steps = jnp.zeros((2, 10, 4, 8), jnp.int32)
    touched = jnp.zeros(20, bool)
    jaxpr = str(jax.make_jaxpr(_stale_stacked_host)(steps, touched))
    assert "cond[" in jaxpr


# ---------------------------------------------------------------------------
# error accounting
# ---------------------------------------------------------------------------

def test_error_accounting_roundtrip():
    R = walks_for_error(0.05, 0.1, 0.85, 16)
    assert R >= 1
    eps = error_bound(R, 0.1, 0.85, 16)
    assert eps <= 0.05 * 1.01                         # inverse within slack
    # more walks -> tighter bound; longer walks -> looser visit cap
    assert error_bound(4 * R, 0.1, 0.85, 16) < eps
    assert walks_for_error(0.025, 0.1, 0.85, 16) > R
    assert 0 < truncation_bias(0.85, 16) < 0.1


def test_diagnostics_shape(index):
    d = diagnostics(index)
    assert d["num_walks"] == 64 and d["max_len"] == 16
    assert 1.0 <= d["mean_length"] <= 16.0
    assert 0.0 <= d["truncated_frac"] <= 1.0
    assert d["nbytes"] == index.steps.size * 4


def test_effective_walks_routing_signal(small, index):
    _, _, n = small
    deg = np.asarray(index.csr.deg)
    v_hi = int(np.argmax(deg))
    assert effective_walks(index, [v_hi]) == deg[v_hi] * 64
    assert effective_walks(index, [v_hi, v_hi]) == deg[v_hi] * 64  # dedup


# ---------------------------------------------------------------------------
# serve integration: engine maintenance + query routing + memoization
# ---------------------------------------------------------------------------

def _service(g, **kw):
    metrics = ServeMetrics()
    ingest = IngestQueue(flush_size=16, flush_interval=0.0)
    store = RankStore()
    engine = ServeEngine(g, ingest, store, metrics=metrics, **kw)
    return ingest, store, engine, metrics


def test_engine_maintains_index_and_snapshot_carries_it(small):
    g, _, n = small
    cfg = IndexConfig(num_walks=16, max_len=12, seed=2)
    ingest, store, engine, metrics = _service(g, ppr_index=cfg)
    engine.bootstrap()
    assert store.snapshot().ppr_index is not None
    rng = np.random.default_rng(4)
    for _ in range(48):
        u, v = rng.integers(0, n, 2)
        if u != v:
            ingest.submit_insert(int(u), int(v))
        engine.step()
    engine.drain()
    snap = store.snapshot()
    fresh = build_walk_index(snap.graph, cfg)
    assert bool(jnp.all(snap.ppr_index.steps == fresh.steps))
    assert metrics.as_dict()["walks_resampled"] > 0


def test_engine_without_index_publishes_none(small):
    g, _, _ = small
    _, store, engine, _ = _service(g)
    engine.bootstrap()
    assert store.snapshot().ppr_index is None


def test_query_mode_routing(small):
    g, _, n = small
    cfg = IndexConfig(num_walks=64, max_len=16, seed=2)
    ingest, store, engine, metrics = _service(g, ppr_index=cfg)
    engine.bootstrap()
    client = QueryClient(store, ingest, metrics, min_effective_walks=64)
    deg = np.asarray(store.snapshot().ppr_index.csr.deg)
    warm = int(np.argmax(deg))
    r = client.personalized_top_k([warm], 5, mode="index")
    assert warm in r.vertices.tolist()                # seed holds mass
    r2 = client.personalized_top_k([warm], 5, mode="exact")
    assert warm in r2.vertices.tolist()
    # auto: warm seed -> index answer == forced-index answer
    ra = client.personalized_top_k([warm], 5, mode="auto")
    assert ra.vertices.tolist() == r.vertices.tolist()
    # auto: cold seed (deg 0 -> 0 effective walks) -> exact path
    cold = int(np.flatnonzero(deg == 0)[0])
    rc = client.personalized_top_k([cold], 5, mode="auto")
    assert rc.vertices[0] == cold
    with pytest.raises(ValueError):
        client.personalized_top_k([warm], 5, mode="nope")
    with pytest.raises(ValueError):                   # solver kw on index
        client.personalized_top_k([warm], 5, mode="index", max_iter=3)
    # auto + solver options routes to exact for ANY seed (never raises
    # data-dependently on the seed's degree)
    rw = client.personalized_top_k([warm], 5, mode="auto", max_iter=50)
    assert warm in rw.vertices.tolist()
    # seed validation is mode-independent
    for bad in ([], [n], [-1]):
        with pytest.raises(ValueError):
            client.personalized_top_k(bad, 5, mode="auto")


def test_query_mode_index_requires_index(small):
    g, _, _ = small
    _, store, engine, _ = _service(g)
    engine.bootstrap()
    client = QueryClient(store)
    with pytest.raises(ValueError):
        client.personalized_top_k([1], 5, mode="index")


# ---------------------------------------------------------------------------
# sharded index (ppr/shard.py): bitwise parity with the single-device path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 3, 4])
def test_sharded_build_matches_single_device(small, index, num_shards):
    """Per-shard build with global walk ids == the same slice of a full
    build — including the uneven split (S=3 pads the last shard)."""
    g, _, n = small
    cfg = IndexConfig(num_walks=64, max_len=16, seed=3)
    sharded = build_sharded_walk_index(g, cfg, num_shards=num_shards)
    want = shard_walk_index(index, num_shards)
    assert sharded.steps.shape == want.steps.shape
    assert bool(jnp.all(sharded.steps == want.steps))
    # unshard round-trips, dropping the padding rows
    assert bool(jnp.all(unshard_walk_index(sharded).steps == index.steps))


@pytest.mark.parametrize("num_shards", [2, 3, 4])
def test_sharded_repair_bitwise_vs_single_device(small, index, num_shards):
    """Sharded repair == unshard → single-device repair → reshard, walk
    for walk — the tentpole's acceptance invariant."""
    g, _, n = small
    upd = _batch(small, 7)
    g2 = apply_batch(g, upd)
    touched = touched_vertices_mask(upd, n)
    want, want_n = repair_walk_index(index, g2, touched)
    got, got_n = repair_walk_index_sharded(
        shard_walk_index(index, num_shards), g2, touched)
    assert got_n == want_n > 0
    assert bool(jnp.all(unshard_walk_index(got).steps == want.steps))
    assert bool(jnp.all(got.csr.indptr == want.csr.indptr))


def test_sharded_repair_chain_over_stream(small):
    """The serve-loop invariant survives sharding: N sharded repairs ==
    one fresh single-device build on the final graph."""
    g, _, n = small
    cfg = IndexConfig(num_walks=32, max_len=12, seed=11)
    idx = build_sharded_walk_index(g, cfg, num_shards=4)
    cur = g
    for seed in range(4):
        upd = _batch(small, 100 + seed, n_del=4, n_ins=8)
        nxt = apply_batch(cur, upd)
        idx, _ = repair_walk_index_sharded(idx, nxt,
                                           touched_vertices_mask(upd, n))
        cur = nxt
    fresh = build_walk_index(cur, cfg)
    assert bool(jnp.all(unshard_walk_index(idx).steps == fresh.steps))


def test_sharded_repair_capacity_budget(small, index):
    """Overflowing an explicit per-shard budget raises a checked error
    naming the shards; check=False degrades (drops) instead — repaired
    rows are exact, dropped rows are the old rows, nothing corrupt."""
    from repro.ppr.shard import shard_stale_counts
    g, _, n = small
    upd = _batch(small, 3)
    g2 = apply_batch(g, upd)
    touched = touched_vertices_mask(upd, n)
    sharded = shard_walk_index(index, 4)
    counts = shard_stale_counts(sharded, touched)
    assert counts.sum() > 0
    tight = max(1, int(counts.max()) // 2)
    with pytest.raises(ShardCapacityError) as ei:
        repair_walk_index_sharded(sharded, g2, touched, capacity=tight)
    assert ei.value.shards
    assert all(counts[s] > tight for s in ei.value.shards)
    got, _ = repair_walk_index_sharded(sharded, g2, touched,
                                       capacity=tight, check=False,
                                       min_capacity=1)
    want, _ = repair_walk_index(index, g2, touched)
    gu = np.asarray(unshard_walk_index(got).steps)
    row_old = (gu == np.asarray(index.steps)).all(-1)
    row_new = (gu == np.asarray(want.steps)).all(-1)
    assert np.all(row_old | row_new)
    assert not np.all(row_new)        # something was actually dropped
    assert not np.all(row_old)        # ... and something repaired


def test_sharded_query_matches_single_device(small, index):
    """Per-shard segment_sum + one (p)sum matches the single-device
    estimate to f64 rounding; top-k is identical."""
    sharded = shard_walk_index(index, 4)
    for unroll in (True, False):
        est_s = np.asarray(ppr_estimate(sharded, [7, 12], unroll=unroll))
        est_1 = np.asarray(ppr_estimate(index, [7, 12], unroll=unroll))
        np.testing.assert_allclose(est_s, est_1, rtol=0, atol=1e-12)
    vs, _ = ppr_top_k(sharded, [7], 10)
    v1, _ = ppr_top_k(index, [7], 10)
    assert vs.tolist() == v1.tolist()


def test_sharded_program_cache_bounded(small):
    """A temporal stream reuses a handful of compiled repair programs
    (pow2 capacities), mirroring the SpMV shard layer's contract."""
    import repro.ppr.shard as shard_mod
    g, _, n = small
    cfg = IndexConfig(num_walks=32, max_len=12, seed=11)
    idx = build_sharded_walk_index(g, cfg, num_shards=4)
    before = dict(shard_mod.TRACE_COUNTS)
    cur = g
    for seed in range(5):
        upd = _batch(small, 300 + seed, n_del=3, n_ins=5)
        cur = apply_batch(cur, upd)
        idx, _ = repair_walk_index_sharded(idx, cur,
                                           touched_vertices_mask(upd, n))
    delta = {k: shard_mod.TRACE_COUNTS[k] - before.get(k, 0)
             for k in shard_mod.TRACE_COUNTS}
    assert delta.get("repairs", 0) == 5
    # host path: no shard_map programs get built at all
    assert delta.get("build_repair", 0) == 0


# ---------------------------------------------------------------------------
# serve integration: mesh engine + the single-host-sync contract
# ---------------------------------------------------------------------------

def _one_shard_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:1]), ("model",))


def test_engine_mesh_shards_index_and_repairs(small):
    """An engine given a mesh builds the index sharded at bootstrap and
    keeps it bitwise equal to a fresh single-device build while
    streaming — the in-process 1-way mesh; the 4-way run is the slow
    subprocess test + the CI mesh smoke lane."""
    g, _, n = small
    cfg = IndexConfig(num_walks=16, max_len=12, seed=2)
    ingest, store, engine, metrics = _service(g, ppr_index=cfg,
                                              mesh=_one_shard_mesh())
    engine.bootstrap()
    assert isinstance(store.snapshot().ppr_index, ShardedWalkIndex)
    rng = np.random.default_rng(6)
    for _ in range(32):
        u, v = rng.integers(0, n, 2)
        if u != v:
            ingest.submit_insert(int(u), int(v))
        engine.step()
    engine.drain()
    snap = store.snapshot()
    fresh = build_walk_index(snap.graph, cfg)
    assert bool(jnp.all(unshard_walk_index(snap.ppr_index).steps ==
                        fresh.steps))
    assert metrics.as_dict()["walks_resampled"] > 0


def test_step_issues_single_host_sync(small):
    """The PPR repair wait is folded into the batch's one
    block_until_ready: an index-maintaining engine issues exactly as
    many host syncs per step as one without an index (the serve/engine
    double-sync bug, fixed)."""
    import repro.serve.engine as eng_mod
    g, _, n = small
    for kw in (dict(),
               dict(ppr_index=IndexConfig(num_walks=16, max_len=12,
                                          seed=2))):
        ingest, store, engine, _ = _service(g, **kw)
        engine.bootstrap()
        rng = np.random.default_rng(1)
        for _ in range(3):
            u, v = rng.integers(0, n, 2)
            if u == v:
                continue
            ingest.submit_insert(int(u), int(v))
            before = eng_mod.SYNC_COUNTS["block_until_ready"]
            assert engine.step(force=True)
            assert eng_mod.SYNC_COUNTS["block_until_ready"] == before + 1


@pytest.mark.slow
def test_sharded_mesh_multidevice_subprocess(tmp_path):
    """4-way mesh on 8 forced host devices: mesh build/repair parity and
    bounded shard_map compiles — the real-SPMD twin of the host-path
    tests above."""
    prog = (
        "import numpy as np, jax, jax.numpy as jnp, repro\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec\n"
        "import repro.ppr.shard as sm\n"
        "from repro.graph.dynamic import apply_batch, make_batch_update, \\\n"
        "    touched_vertices_mask\n"
        "from repro.graph.generators import rmat_edges\n"
        "from repro.graph.structure import from_coo\n"
        "from repro.ppr import (IndexConfig, build_sharded_walk_index,\n"
        "    build_walk_index, ppr_top_k, repair_walk_index,\n"
        "    repair_walk_index_sharded, unshard_walk_index)\n"
        "assert len(jax.devices()) == 8, jax.devices()\n"
        "mesh = Mesh(np.asarray(jax.devices()[:4]), ('model',))\n"
        "edges, n = rmat_edges(8, 8, seed=1)\n"
        "g = from_coo(edges[:, 0], edges[:, 1], n,\n"
        "             edge_capacity=len(edges) + 512)\n"
        "cfg = IndexConfig(num_walks=32, max_len=12, seed=3)\n"
        "idx = build_sharded_walk_index(g, cfg, mesh=mesh)\n"
        "one = build_walk_index(g, cfg)\n"
        "assert bool(jnp.all(unshard_walk_index(idx).steps == one.steps))\n"
        "spec = idx.steps.sharding.spec\n"
        "assert spec == PartitionSpec('model'), spec\n"
        "rng = np.random.default_rng(0)\n"
        "cur = g\n"
        "for s in range(6):\n"
        "    dele = edges[rng.choice(len(edges), 4, replace=False)]\n"
        "    ins = rng.integers(0, n, size=(8, 2)).astype(np.int32)\n"
        "    ins = ins[ins[:, 0] != ins[:, 1]]\n"
        "    upd = make_batch_update(dele, ins, 8, 8)\n"
        "    nxt = apply_batch(cur, upd)\n"
        "    t = touched_vertices_mask(upd, n)\n"
        "    idx, k1 = repair_walk_index_sharded(idx, nxt, t)\n"
        "    one, k2 = repair_walk_index(one, nxt, t)\n"
        "    assert k1 == k2, (k1, k2)\n"
        "    cur = nxt\n"
        "assert bool(jnp.all(unshard_walk_index(idx).steps == one.steps))\n"
        "v_s, _ = ppr_top_k(idx, [7], 10)\n"
        "v_1, _ = ppr_top_k(one, [7], 10)\n"
        "assert v_s.tolist() == v_1.tolist()\n"
        "assert sm.TRACE_COUNTS['build_build'] == 1\n"
        "assert sm.TRACE_COUNTS['build_stale'] == 1\n"
        "assert sm.TRACE_COUNTS['repairs'] == 6\n"
        "assert sm.TRACE_COUNTS['build_repair'] <= 3  # pow2 capacities\n"
        "print('MESH_PPR_OK')\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo_root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert "MESH_PPR_OK" in r.stdout


def test_exact_path_memoized_within_generation(small, monkeypatch):
    g, _, n = small
    ingest, store, engine, _ = _service(g)
    engine.bootstrap()
    client = QueryClient(store, ingest)
    import repro.serve.query as q
    calls = []
    orig = q.personalized_pagerank
    monkeypatch.setattr(q, "personalized_pagerank",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    r1 = client.personalized_top_k([3, 7], 5, mode="exact")
    r2 = client.personalized_top_k([7, 3], 5, mode="exact")  # same set
    assert calls == [1]                               # solved once
    assert r1.vertices.tolist() == r2.vertices.tolist()
    # distinct options / seed sets do solve
    client.personalized_top_k([3, 7], 5, mode="exact", max_iter=7)
    client.personalized_top_k([3], 5, mode="exact")
    assert len(calls) == 3
    # a new generation invalidates the memo key
    ingest.submit_insert(0, 9)
    engine.step(force=True)
    client.personalized_top_k([3, 7], 5, mode="exact")
    assert len(calls) == 4
