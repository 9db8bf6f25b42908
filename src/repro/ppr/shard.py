"""Start-vertex-range sharding of the PPR walk index (DESIGN.md §14).

The walk index is O(V·R·L) — by far the largest serving-side state
(537 MB at the bench scale) — and until now lived replicated on one
device while the rank path was already sharded (kernels/pagerank_spmv/
shard.py).  This module partitions ``WalkIndex.steps`` by contiguous
start-vertex ranges over the same ``model`` mesh axis:

  ``steps: int32[S, vps, R, L]``   shard s owns walks started at global
                                   vertices [s·vps, (s+1)·vps); rows
                                   past V (last-shard padding) are all
                                   ``-1`` — inert for staleness and
                                   queries alike.

What makes range sharding *free* correctness-wise is the PRNG
discipline of walks.py: every draw is a pure function of (base_key,
**global** flat walk id, hop).  A shard maps local row (vl, r) to the
global id (s·vps + vl)·R + r (``lax.axis_index`` under shard_map) and
feeds it to the same fold_in stream, so per-shard build and repair are
bitwise identical to the single-device ones — asserted in
tests/test_ppr.py.  The CSR view and the touched mask stay replicated:
walks *visit* arbitrary global vertices even though they are *owned* by
start vertex, and the CSR is O(E) against the O(V·R·L) steps.

Staleness routing follows the delta-routing idiom of the SpMV shard
layer: each shard detects its own stale walks from the replicated
touched mask, compacts them (stable flat order, sentinel-padded) to a
shared pow2 capacity chosen from the max per-shard stale count — one
host sync, the same cost class as the single-device ``int(jnp.sum)`` —
and overflow against an explicit budget is a checked
``ShardCapacityError`` naming the shards, never silent truncation.
Compiled shard_map programs are cached per (mesh, geometry, capacity)
with the same bounded-eviction scheme as ``build_sharded_apply``.

Queries never reassemble the index: each shard segment-sums the visit
counts of the sources it owns and one psum of the f64[V] estimate
(8·V bytes) crosses the wire — vs shipping the multi-hundred-MB steps
array (comm-volume table: DESIGN.md §14).

Resampling runs the jnp hop recurrence (``repair._resample_impl``) on
every backend: its per-hop CSR gathers have no Mosaic lowering.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.graph.structure import CSRView, EdgeListGraph
from repro.kernels.pagerank_spmv.shard import ShardCapacityError
from repro.obs import trace as obs_trace
from repro.ppr.repair import (_device_csr, _resample_impl, _stale_ids,
                              stale_walks)
from repro.ppr.walks import IndexConfig, WalkIndex, _build_steps_range

# compiled-program builds per kind — tests assert a temporal stream
# reuses one program per (geometry, capacity), like the SpMV layer
TRACE_COUNTS: collections.Counter = collections.Counter()

_COMPILED_CACHE: dict = {}
_MAX_CACHED = 8


class WalkShardSpec(NamedTuple):
    """Static geometry of a sharded walk index (hashable: jit/cache key)."""

    num_shards: int
    vertices_per_shard: int
    num_vertices: int

    @property
    def padded_vertices(self) -> int:
        return self.num_shards * self.vertices_per_shard


def make_walk_shard_spec(num_vertices: int, num_shards: int) -> WalkShardSpec:
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    vps = -(-num_vertices // num_shards)
    return WalkShardSpec(num_shards=num_shards, vertices_per_shard=vps,
                         num_vertices=num_vertices)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedWalkIndex:
    """Range-sharded walk index; a pytree, safe under jit/shard_map.

    ``csr``/``key`` are replicated; only ``steps`` is partitioned.  The
    mesh rides along as a static so query/repair dispatch (and the
    serving snapshot that carries this object) need no side channel;
    ``mesh=None`` runs every collective as its vmap host oracle — the
    mesh-free differential path the tests compare against.
    """

    steps: jax.Array     # int32[S, vps, R, L]; -1 = terminated / padding
    csr: CSRView         # replicated adjacency the walks are valid for
    key: jax.Array       # uint32[2] base PRNG key (shared by all shards)
    num_walks: int = dataclasses.field(metadata=dict(static=True))
    max_len: int = dataclasses.field(metadata=dict(static=True))
    alpha: float = dataclasses.field(metadata=dict(static=True))
    spec: WalkShardSpec = dataclasses.field(metadata=dict(static=True))
    mesh: Optional[Mesh] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def num_vertices(self) -> int:
        return self.spec.num_vertices

    @property
    def num_shards(self) -> int:
        return self.spec.num_shards

    def nbytes(self) -> int:
        return self.steps.size * 4


def _usable_mesh(index: ShardedWalkIndex) -> Optional[Mesh]:
    m = index.mesh
    if m is None or m.shape.get("model") != index.spec.num_shards:
        return None
    return m


def _cached(cache_key, builder):
    fn = _COMPILED_CACHE.get(cache_key)
    if fn is None:
        while len(_COMPILED_CACHE) >= _MAX_CACHED:
            _COMPILED_CACHE.pop(next(iter(_COMPILED_CACHE)))
        TRACE_COUNTS[f"build_{cache_key[0]}"] += 1
        fn = builder()
        _COMPILED_CACHE[cache_key] = fn
    return fn


# ---------------------------------------------------------------------------
# shard / unshard / build
# ---------------------------------------------------------------------------

def shard_walk_index(index: WalkIndex, num_shards: int,
                     mesh: Optional[Mesh] = None) -> ShardedWalkIndex:
    """Partition a single-device index by start-vertex range.  Padding
    rows (global vertex ≥ V on the last shard) are all ``-1``."""
    V, R, L = index.steps.shape
    spec = make_walk_shard_spec(V, num_shards)
    pad = spec.padded_vertices - V
    steps = index.steps
    if pad:
        steps = jnp.concatenate(
            [steps, jnp.full((pad, R, L), -1, jnp.int32)])
    steps = steps.reshape(spec.num_shards, spec.vertices_per_shard, R, L)
    if mesh is not None:
        steps = jax.device_put(steps, NamedSharding(mesh, P("model")))
    return ShardedWalkIndex(steps=steps, csr=index.csr, key=index.key,
                            num_walks=index.num_walks,
                            max_len=index.max_len, alpha=index.alpha,
                            spec=spec, mesh=mesh)


def unshard_walk_index(index: ShardedWalkIndex) -> WalkIndex:
    """Reassemble the single-device index (tests/benchmarks only — the
    serving path never does this)."""
    S, vps, R, L = index.steps.shape
    steps = index.steps.reshape(S * vps, R, L)[: index.spec.num_vertices]
    return WalkIndex(steps=steps, csr=index.csr, key=index.key,
                     num_walks=index.num_walks, max_len=index.max_len,
                     alpha=index.alpha)


def _build_build_fn(mesh: Mesh, spec: WalkShardSpec, num_walks: int,
                    max_len: int, alpha: float):
    vps = spec.vertices_per_shard

    def step(csr, key):
        s = jax.lax.axis_index("model").astype(jnp.int32)
        local = _build_steps_range(csr, key, s * vps, spec.num_vertices,
                                   vps, num_walks, max_len, alpha)
        return local[None]

    return jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P(), P()), out_specs=P("model"),
        check_vma=False))


def build_sharded_walk_index(graph: EdgeListGraph,
                             config: IndexConfig = IndexConfig(), *,
                             num_shards: Optional[int] = None,
                             mesh: Optional[Mesh] = None
                             ) -> ShardedWalkIndex:
    """Sample the index directly in sharded form — each shard builds its
    own start-vertex range with global walk ids, so the result equals
    ``shard_walk_index(build_walk_index(graph, config), S)`` bitwise."""
    if num_shards is None:
        if mesh is None:
            raise ValueError("need num_shards or a mesh")
        num_shards = mesh.shape["model"]
    spec = make_walk_shard_spec(graph.num_vertices, num_shards)
    key = jax.random.PRNGKey(config.seed)
    csr = graph.to_device_csr()
    R, L, alpha = config.num_walks, config.max_len, config.alpha
    if mesh is not None and mesh.shape.get("model") == num_shards:
        fn = _cached(("build", mesh, spec, R, L, alpha),
                     lambda: _build_build_fn(mesh, spec, R, L, alpha))
        steps = fn(csr, key)
    else:
        vps = spec.vertices_per_shard
        steps = jnp.stack([
            _build_steps_range(csr, key, jnp.int32(s * vps),
                               spec.num_vertices, vps, R, L, alpha)
            for s in range(num_shards)])
    return ShardedWalkIndex(steps=steps, csr=csr, key=key, num_walks=R,
                            max_len=L, alpha=alpha, spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# staleness + repair
# ---------------------------------------------------------------------------

@jax.jit
def _stale_stacked_host(steps_stacked: jax.Array, touched: jax.Array):
    """Mesh-free path: per-shard (count, stale, t0) via vmap over the
    steps.  ``touched`` is not mapped, so the stale scan's ``lax.cond``
    keeps an unbatched predicate and runs one branch, as on a mesh."""

    def per(local):
        stale, t0 = stale_walks(local, touched)
        return jnp.sum(stale.astype(jnp.int32)), stale, t0

    return jax.vmap(per)(steps_stacked)


def _build_stale_fn(mesh: Mesh):
    def step(steps, touched):
        stale, t0 = stale_walks(steps[0], touched)
        return (jnp.sum(stale.astype(jnp.int32))[None],
                stale[None], t0[None])

    return jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("model"), P()),
        out_specs=(P("model"), P("model"), P("model")), check_vma=False))


def _build_repair_fn(mesh: Mesh, spec: WalkShardSpec, num_walks: int,
                     alpha: float, cap: int):
    nl = spec.vertices_per_shard * num_walks

    def step(steps, stale, t0, csr, key):
        s = jax.lax.axis_index("model").astype(jnp.int32)
        ids, t0_sel = _stale_ids(stale[0], t0[0], cap)
        new = _resample_impl(csr, key, steps[0], ids, t0_sel, alpha,
                             id_offset=s * nl)
        return new[None]

    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("model"), P("model"), P("model"), P(), P()),
        out_specs=P("model"), check_vma=False))


@partial(jax.jit, static_argnames=("cap", "alpha"))
def _repair_stacked_host(steps_stacked: jax.Array, csr: CSRView,
                         key: jax.Array, stale: jax.Array, t0: jax.Array,
                         cap: int, alpha: float) -> jax.Array:
    """Mesh-free oracle for the sharded resample."""
    S, vps, R, L = steps_stacked.shape
    offs = jnp.arange(S, dtype=jnp.int32) * (vps * R)

    def per(local, st, t0_l, off):
        ids, t0_sel = _stale_ids(st, t0_l, cap)
        return _resample_impl(csr, key, local, ids, t0_sel, alpha,
                              id_offset=off)

    return jax.vmap(per)(steps_stacked, stale, t0, offs)


def repair_walk_index_sharded(index: ShardedWalkIndex,
                              graph_new: EdgeListGraph,
                              touched: jax.Array, *,
                              min_capacity: int = 64,
                              capacity: Optional[int] = None,
                              check: bool = True
                              ) -> Tuple[ShardedWalkIndex, int]:
    """Sharded twin of ``repair_walk_index``: every shard repairs its own
    stale walks under shard_map; the result is bitwise equal to
    unsharding, repairing on one device, and resharding.

    ``capacity`` pins an explicit per-shard compaction budget; a shard
    whose stale count exceeds it raises ``ShardCapacityError`` naming
    the shards (``check=False`` drops the overflow instead — those
    walks simply stay stale, degrading estimates, never corrupting
    them).  Without it the budget is the shard-local walk count, which
    cannot overflow.
    """
    S, vps, R, L = index.steps.shape
    tr = obs_trace.get_tracer()
    # ``scanned``: walks whose positions the stale scan reads, padded
    # rows included
    with tr.span("ppr.repair_sharded", scanned=S * vps * R,
                 shards=S) as sp:
        spec = index.spec
        csr_new = _device_csr(graph_new)
        mesh = _usable_mesh(index)
        if mesh is not None:
            fn = _cached(("stale", mesh, S, vps, R, L),
                         lambda: _build_stale_fn(mesh))
            counts, stale, t0 = fn(index.steps, touched)
        else:
            counts, stale, t0 = _stale_stacked_host(index.steps, touched)
        counts_h = np.asarray(counts)        # the one host sync per batch
        num_stale = int(counts_h.sum())
        max_stale = int(counts_h.max())
        TRACE_COUNTS["repairs"] += 1
        if num_stale == 0:
            sp.set(stale=0)
            return dataclasses.replace(index, csr=csr_new), 0
        nl = vps * R
        budget = nl if capacity is None else min(capacity, nl)
        if max_stale > budget:
            over = [s for s, c in enumerate(counts_h.tolist())
                    if c > budget]
            if check:
                raise ShardCapacityError(
                    f"stale-walk compaction overflow: {max_stale} stale "
                    f"walks on one shard exceed the budget {budget} on "
                    f"shards {over} (raise capacity or repair unsharded)",
                    shards=over)
            TRACE_COUNTS["dropped_stale"] += sum(
                int(c) - budget for c in counts_h if int(c) > budget)
        # shared pow2 capacity from the max per-shard count: every shard
        # runs the same executable, streams reuse a handful of capacities
        cap = min(budget,
                  max(min_capacity,
                      1 << (min(max_stale, budget) - 1).bit_length()))
        if mesh is not None:
            rfn = _cached(("repair", mesh, spec, R, L, cap),
                          lambda: _build_repair_fn(mesh, spec, R,
                                                   index.alpha, cap))
            steps = rfn(index.steps, stale, t0, csr_new, index.key)
        else:
            steps = _repair_stacked_host(index.steps, csr_new, index.key,
                                         stale, t0, cap, index.alpha)
        tr.sync(steps)
        sp.set(stale=num_stale, capacity=cap)
    return dataclasses.replace(index, steps=steps, csr=csr_new), num_stale


def shard_stale_counts(index: ShardedWalkIndex, touched: jax.Array
                       ) -> np.ndarray:
    """int per-shard stale-walk counts — the load-balance signal
    bench_ppr's modeled scaling row is derived from."""
    counts, _, _ = _stale_stacked_host(index.steps, touched)
    return np.asarray(counts)


# ---------------------------------------------------------------------------
# queries: per-shard segment_sum + one psum
# ---------------------------------------------------------------------------

def _build_counts_fn(mesh: Mesh, spec: WalkShardSpec):
    from repro.ppr.query import _counts_local
    vps, V = spec.vertices_per_shard, spec.num_vertices

    def step(steps, sources, weights):
        s = jax.lax.axis_index("model").astype(jnp.int32)
        c = _counts_local(steps[0], sources, weights, s * vps, V)
        return jax.lax.psum(c, "model")

    return jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("model"), P(), P()), out_specs=P(),
        check_vma=False))


@partial(jax.jit, static_argnames=("num_vertices",))
def _counts_stacked_host(steps_stacked: jax.Array, sources: jax.Array,
                         weights: jax.Array, num_vertices: int) -> jax.Array:
    from repro.ppr.query import _counts_local
    S, vps = steps_stacked.shape[0], steps_stacked.shape[1]
    v0 = jnp.arange(S, dtype=jnp.int32) * vps
    per = jax.vmap(
        lambda st, v: _counts_local(st, sources, weights, v, num_vertices)
    )(steps_stacked, v0)
    return jnp.sum(per, axis=0)


def sharded_counts(index: ShardedWalkIndex, sources: jax.Array,
                   weights: jax.Array) -> jax.Array:
    """f64[V] visit-count aggregation over the sharded rows: each shard
    segment-sums the sources it owns, one psum crosses the mesh."""
    mesh = _usable_mesh(index)
    if mesh is not None:
        fn = _cached(("counts", mesh, index.spec),
                     lambda: _build_counts_fn(mesh, index.spec))
        return fn(index.steps, sources, weights)
    return _counts_stacked_host(index.steps, sources, weights,
                                index.spec.num_vertices)


def sharded_ppr_estimate(index: ShardedWalkIndex, seeds: Sequence[int],
                         normalize: bool = True, unroll: bool = True
                         ) -> jax.Array:
    """Sharded twin of ``query.ppr_estimate`` — same estimator math, the
    counts stage runs per shard (matches the single-device estimate to
    f64 rounding; summation order differs across shards)."""
    from repro.ppr import query as q

    idx, mask = q._pad_seeds(seeds, index.num_vertices)
    R, alpha = index.num_walks, index.alpha
    deg = index.csr.deg.astype(jnp.float64)
    if not unroll:
        n_seeds = jnp.maximum(jnp.sum(mask.astype(jnp.float64)), 1.0)
        w = jnp.where(mask, (1.0 - alpha) / (R * n_seeds), 0.0)
        est = sharded_counts(index, idx, w)
    else:
        nbr_cap = q._nbr_cap(index, idx, mask)
        width = min(nbr_cap, q._MAX_NBR_WIDTH)
        est = None
        for offset in range(0, nbr_cap, width):
            nbr, w_nbr = q._nbr_slab(index.csr.indptr, index.csr.indices,
                                     deg, alpha, idx, mask,
                                     jnp.asarray(offset, jnp.int32),
                                     width, R)
            c = sharded_counts(index, nbr, w_nbr)
            est = c if est is None else est + c
        est = q._seed_point_mass(est, deg, alpha, idx, mask)
    if normalize:
        est = est / jnp.maximum(jnp.sum(est), 1e-300)
    return est
