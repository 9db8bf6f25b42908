"""Window-range sharding of ``PackedGraph`` across the device mesh.

The single-pod kernel engine (core.kernel_engine) runs the frontier-gated
SpMV over one ``PackedGraph`` on one device.  This module partitions that
blocked structure by **contiguous destination-window ranges** — shard *s*
of *S* owns global windows ``[s·wps, (s+1)·wps)`` (``wps`` windows per
shard, the global window count padded up to ``S·wps``) — which is the
blocked analogue of the dst-range ownership the XLA distributed engine
already uses (``graph/partition.py``): all in-edges of a vertex live on
exactly one shard, so per-shard SpMV partials have **disjoint support**
and a single ``psum`` reassembles the full contribution vector exactly.

Representation: a ``ShardedPacked`` pytree stacks S equally-shaped
per-shard ``PackedGraph``s along a leading shard axis (placed on the
mesh's ``model`` axis under ``shard_map``).  Each per-shard structure is
a *bona fide* ``PackedGraph`` over the shard's local vertex range
(``num_vertices = wps·vb``, window ids and ``dst`` rebased to the shard)
except that ``src`` stays **global** — sources are gathered from the
replicated rank vector, destinations are shard-local.  Because
``pack_blocks`` and ``update.apply_batch_packed`` key edges as
``src·num_vertices + dst``, the global-src/local-dst convention keeps
keys injective and the *unmodified* incremental update correct per
shard.

Micro-batch deltas are routed to their owning shard by dst
(``route_update``): per shard, matching rows are stably compacted into a
static per-shard budget (default: the full batch capacity, so any batch
fits even when every edge lands on one shard).  Overflowing a smaller
budget is a **checked capacity error** (``ShardCapacityError``), never a
silent truncation — the same contract as lane/overlay exhaustion.  The
per-shard update then runs under ``shard_map`` (``build_sharded_apply``)
so the one-compiled-update-per-stream invariant survives sharding: all
shapes are static, ``TRACE_COUNTS`` asserts no retraces.

``frontier_spmv_shard`` is the kernel entry for one shard: identical to
``frontier_spmv_padded`` except the rank-scale input spans the *full*
replicated padded vertex range (src is global) while the output spans
only the shard's ``wps`` windows.  DESIGN.md §9 has the layout diagram,
budget model and psum cost analysis.
"""
from __future__ import annotations

import collections
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.graph.dynamic import BatchUpdate
from repro.graph.structure import EdgeListGraph
from repro.kernels.pagerank_spmv import ops
from repro.kernels.pagerank_spmv.pagerank_spmv import (
    DEFAULT_BE, DEFAULT_VB, PackedGraph, frontier_spmv_padded, pack_blocks)
from repro.kernels.pagerank_spmv.ref import frontier_spmv_ref_padded
from repro.kernels.pagerank_spmv.update import _apply_batch_packed

__all__ = ["ShardSpec", "ShardedPacked", "ShardCapacityError", "HaloSpec",
           "pack_shards", "route_update", "build_sharded_apply",
           "apply_batch_sharded_host", "frontier_spmv_shard",
           "gated_contrib_shard", "shard_graph", "sharded_edge_set",
           "build_halo", "extend_halo", "halo_slots", "TRACE_COUNTS"]

# retracing telemetry for the sharded path (same contract as
# kernels.pagerank_spmv.update.TRACE_COUNTS): one compiled route, one
# compiled per-shard update and one compiled kernel loop per stream
TRACE_COUNTS: collections.Counter = collections.Counter()


class ShardCapacityError(ValueError):
    """A checked sharded-capacity overflow (delta budget, spill lanes or
    locator overlay).  ``shards`` names the shards that overflowed."""

    def __init__(self, message: str, shards: tuple = ()):
        super().__init__(message)
        self.shards = tuple(shards)


class ShardSpec(NamedTuple):
    """Static geometry of a sharded pack (hashable: jit/cache key).

    Shard *s* owns global windows ``[s·wps, (s+1)·wps)``, i.e. global
    vertices ``[s·wps·vb, (s+1)·wps·vb)``.
    """

    num_shards: int
    windows_per_shard: int
    vb: int
    be: int
    num_vertices: int            # global V (<= num_shards·wps·vb)
    num_entries: int             # per-shard entry capacity (equal shapes)
    max_entries_per_window: int
    overlay_capacity: int

    @property
    def vertices_per_shard(self) -> int:
        return self.windows_per_shard * self.vb

    @property
    def padded_vertices(self) -> int:
        return self.num_shards * self.vertices_per_shard


class ShardedPacked(NamedTuple):
    """S per-shard ``PackedGraph``s stacked on a leading shard axis.

    Field semantics match ``PackedGraph`` per shard; ``window`` ids and
    ``dst_rel`` windows are shard-local, ``src`` is global.
    """

    src: jax.Array          # int32[S, NE, BE]   global sources
    dst_rel: jax.Array      # int32[S, NE, BE]
    valid: jax.Array        # f32[S, NE, BE]
    window: jax.Array       # int32[S, NE]       local window ids
    entry_start: jax.Array  # int32[S, WPS+1]
    sorted_key: jax.Array   # int64[S, NE*BE]
    sorted_lane: jax.Array  # int32[S, NE*BE]
    ovl_key: jax.Array      # int64[S, K]
    ovl_lane: jax.Array     # int32[S, K]


def _local_packed(sharded: ShardedPacked, spec: ShardSpec,
                  index=0) -> PackedGraph:
    """One shard's arrays -> a shard-local PackedGraph (spec statics)."""
    return PackedGraph(
        src=sharded.src[index], dst_rel=sharded.dst_rel[index],
        valid=sharded.valid[index], window=sharded.window[index],
        entry_start=sharded.entry_start[index],
        sorted_key=sharded.sorted_key[index],
        sorted_lane=sharded.sorted_lane[index],
        ovl_key=sharded.ovl_key[index], ovl_lane=sharded.ovl_lane[index],
        num_vertices=spec.vertices_per_shard, vb=spec.vb, be=spec.be,
        max_entries_per_window=spec.max_entries_per_window)


def shard_graph(sharded: ShardedPacked, spec: ShardSpec,
                s: int) -> PackedGraph:
    """Host-side extraction of shard ``s`` (tests, oracles)."""
    return _local_packed(jax.tree_util.tree_map(np.asarray, sharded),
                         spec, s)


def sharded_edge_set(sharded: ShardedPacked, spec: ShardSpec) -> set:
    """Global live (src, dst) pairs across all shards — the parity oracle
    against ``update.packed_edge_set`` / the edge-list graph."""
    out: set = set()
    vps = spec.vertices_per_shard
    for s in range(spec.num_shards):
        src = np.asarray(sharded.src[s])
        dst = (np.asarray(sharded.window[s])[:, None] * spec.vb
               + np.asarray(sharded.dst_rel[s]) + s * vps)
        live = np.asarray(sharded.valid[s]) > 0
        out |= set(zip(src[live].tolist(), dst[live].tolist()))
    return out


# ---------------------------------------------------------------------------
# host-side pack
# ---------------------------------------------------------------------------

def pack_shards(graph: EdgeListGraph, num_shards: int, *,
                be: int = DEFAULT_BE, vb: int = DEFAULT_VB,
                spill_lanes_per_window: int = 1,
                num_entries: int | None = None,
                extra_entries: int = 0,
                overlay_capacity: int = 1024,
                max_entries_per_window: int | None = None
                ) -> tuple[ShardedPacked, ShardSpec]:
    """Partition ``graph`` into S window-range shards, each packed with
    ``pack_blocks`` at one shared per-shard entry capacity.

    ``num_entries`` pins the per-shard capacity (repacks mid-stream must
    pass the bootstrap value or the compiled update/kernel retrace);
    otherwise the capacity is the widest shard's requirement plus
    ``extra_entries`` **total** headroom spread evenly across shards.
    ``spill_lanes_per_window >= 1`` is required: every owned window must
    hold at least one entry so active windows always have a block the
    kernel writes (same invariant as the single-device pack).
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if spill_lanes_per_window < 1:
        raise ValueError("sharded packs need spill_lanes_per_window >= 1 "
                         "(every owned window must hold an entry)")
    V = graph.num_vertices
    nw = -(-V // vb)
    wps = -(-nw // num_shards)
    vps = wps * vb
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    valid = np.asarray(graph.valid)
    shard_of = dst // vps

    if num_entries is None:
        # per-shard entry requirement, mirroring pack_blocks' sizing
        need_cap = 0
        for s in range(num_shards):
            m = valid & (shard_of == s)
            counts = np.bincount(dst[m] // vb - s * wps,
                                 minlength=wps).astype(np.int64)
            n_base = -(-counts // be)
            slack = n_base * be - counts
            need = np.maximum(0, spill_lanes_per_window - slack)
            need_cap = max(need_cap, int(np.sum(n_base + -(-need // be))))
        num_entries = need_cap + -(-max(0, extra_entries) // num_shards)

    packs = []
    for s in range(num_shards):
        m = valid & (shard_of == s)
        packs.append(pack_blocks(
            src[m], dst[m] - s * vps, np.ones(int(m.sum()), bool), vps,
            be=be, vb=vb, num_entries=num_entries,
            spill_lanes_per_window=spill_lanes_per_window,
            overlay_capacity=overlay_capacity,
            max_entries_per_window=None))
    widest = max(p.max_entries_per_window for p in packs)
    if max_entries_per_window is None:
        max_entries_per_window = widest
    elif widest > max_entries_per_window:
        raise ValueError(
            f"{widest} entries in one window exceed the pinned "
            f"max_entries_per_window {max_entries_per_window}")
    stack = lambda f: jnp.stack([getattr(p, f) for p in packs])
    sharded = ShardedPacked(
        src=stack("src"), dst_rel=stack("dst_rel"), valid=stack("valid"),
        window=stack("window"), entry_start=stack("entry_start"),
        sorted_key=stack("sorted_key"), sorted_lane=stack("sorted_lane"),
        ovl_key=stack("ovl_key"), ovl_lane=stack("ovl_lane"))
    spec = ShardSpec(num_shards=num_shards, windows_per_shard=wps, vb=vb,
                     be=be, num_vertices=V, num_entries=num_entries,
                     max_entries_per_window=max_entries_per_window,
                     overlay_capacity=overlay_capacity)
    return sharded, spec


# ---------------------------------------------------------------------------
# delta routing
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("spec", "del_budget", "ins_budget"))
def _route_update(update: BatchUpdate, spec: ShardSpec,
                  del_budget: int, ins_budget: int):
    TRACE_COUNTS["route_update"] += 1                  # trace-time only
    vps = spec.vertices_per_shard
    # int32 shard ids: routed endpoint arrays must keep BatchUpdate's
    # int32 dtype (int64 would be unsafely cast back in the lane scatter)
    sids = jnp.arange(spec.num_shards, dtype=jnp.int32)

    def side(srcs, dsts, mask, budget):
        shard = dsts // vps

        def per_shard(s):
            m = mask & (shard == s)
            order = jnp.argsort(~m, stable=True)[:budget]
            kept = m[order]
            # masked rows get in-range sentinels so downstream window /
            # locator indexing never reads out of bounds
            return (jnp.where(kept, srcs[order], 0),
                    jnp.where(kept, dsts[order] - s * vps, 0),
                    kept,
                    jnp.sum(m.astype(jnp.int32))
                    - jnp.sum(kept.astype(jnp.int32)))

        return jax.vmap(per_shard)(sids)

    d_src, d_dst, d_mask, d_drop = side(update.del_src, update.del_dst,
                                        update.del_mask, del_budget)
    i_src, i_dst, i_mask, i_drop = side(update.ins_src, update.ins_dst,
                                        update.ins_mask, ins_budget)
    routed = BatchUpdate(del_src=d_src, del_dst=d_dst, del_mask=d_mask,
                         ins_src=i_src, ins_dst=i_dst, ins_mask=i_mask)
    return routed, d_drop, i_drop


def route_update(update: BatchUpdate, spec: ShardSpec, *,
                 del_budget: int | None = None,
                 ins_budget: int | None = None,
                 check: bool = True) -> BatchUpdate:
    """Δ -> per-shard Δ: rows land on the shard owning their dst window,
    stably compacted into ``[S, budget]`` arrays with dst rebased to the
    shard.  Budgets default to the full batch capacity (any batch fits,
    even one whose edges all hit one shard); a smaller budget that
    overflows raises ``ShardCapacityError`` — never silent truncation.
    """
    if del_budget is None:
        del_budget = update.del_src.shape[0]
    if ins_budget is None:
        ins_budget = update.ins_src.shape[0]
    routed, d_drop, i_drop = _route_update(update, spec, del_budget,
                                           ins_budget)
    if check:
        d = np.asarray(d_drop)
        i = np.asarray(i_drop)
        if d.sum() or i.sum():
            bad = tuple(int(s) for s in np.flatnonzero(d + i))
            raise ShardCapacityError(
                f"{int(d.sum())} deletions / {int(i.sum())} insertions "
                f"exceed the per-shard delta budget "
                f"(del={del_budget}, ins={ins_budget}) on shards {bad}; "
                "raise the budget (delta routing model: DESIGN.md §9)",
                shards=bad)
    return routed


# ---------------------------------------------------------------------------
# per-shard incremental update under shard_map
# ---------------------------------------------------------------------------

_APPLY_CACHE: dict = {}


def build_sharded_apply(mesh, spec: ShardSpec):
    """Compiled ``(ShardedPacked, routed Δ) -> (ShardedPacked, dropped[S])``
    running ``update.apply_batch_packed``'s body per shard under
    shard_map.  Cached per (mesh, spec) so a stream compiles once."""
    key = (mesh, spec)
    fn = _APPLY_CACHE.get(key)
    if fn is not None:
        return fn

    def step(sharded, routed):
        TRACE_COUNTS["sharded_apply"] += 1             # trace-time only
        packed = _local_packed(sharded, spec, index=0)
        upd = BatchUpdate(*[x[0] for x in routed])
        new, dropped = _apply_batch_packed(packed, upd)
        return (ShardedPacked(
            src=new.src[None], dst_rel=new.dst_rel[None],
            valid=new.valid[None], window=new.window[None],
            entry_start=new.entry_start[None],
            sorted_key=new.sorted_key[None],
            sorted_lane=new.sorted_lane[None],
            ovl_key=new.ovl_key[None], ovl_lane=new.ovl_lane[None]),
            dropped[None])

    fn = jax.jit(shard_map(step, mesh=mesh,
                           in_specs=(P("model"), P("model")),
                           out_specs=(P("model"), P("model")),
                           check_vma=False))
    while len(_APPLY_CACHE) >= 8:
        _APPLY_CACHE.pop(next(iter(_APPLY_CACHE)))
    _APPLY_CACHE[key] = fn
    return fn


def apply_batch_sharded_host(sharded: ShardedPacked, spec: ShardSpec,
                             update: BatchUpdate, *,
                             del_budget: int | None = None,
                             ins_budget: int | None = None,
                             check: bool = True) -> ShardedPacked:
    """Mesh-free reference: route + apply each shard sequentially on the
    default device.  Same result as the shard_map path — used by tests
    and as the oracle for the differential harness."""
    routed = route_update(update, spec, del_budget=del_budget,
                          ins_budget=ins_budget, check=check)
    outs, dropped = [], []
    for s in range(spec.num_shards):
        local = _local_packed(sharded, spec, s)
        upd = BatchUpdate(*[x[s] for x in routed])
        new, drop = _apply_batch_packed(local, upd)
        outs.append(new)
        dropped.append(int(drop))
    if check and any(dropped):
        bad = tuple(s for s, d in enumerate(dropped) if d)
        raise ShardCapacityError(
            f"{sum(dropped)} insertions exceed spill/overlay capacity on "
            f"shards {bad}; repack with pack_shards (sizing: DESIGN.md "
            "§8-§9)", shards=bad)
    stack = lambda f: jnp.stack([getattr(p, f) for p in outs])
    return ShardedPacked(
        src=stack("src"), dst_rel=stack("dst_rel"), valid=stack("valid"),
        window=stack("window"), entry_start=stack("entry_start"),
        sorted_key=stack("sorted_key"), sorted_lane=stack("sorted_lane"),
        ovl_key=stack("ovl_key"), ovl_lane=stack("ovl_lane"))


# ---------------------------------------------------------------------------
# shard-local frontier-gated SpMV
# ---------------------------------------------------------------------------

def frontier_spmv_shard(packed: PackedGraph, rsc_full: jax.Array,
                        active_window: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """``frontier_spmv_padded`` for one shard: gathers from the FULL
    replicated scaled-rank vector (``src`` is global) and scatters into
    this shard's ``wps`` local windows.  Returns f32[wps·vb]; windows
    inactive (or unowned — by construction absent) are zero.

    The base kernel already accepts an rsc longer than its own padded
    window range, so this is pure delegation — there is exactly one
    compaction/pinning/first-write implementation to maintain.
    """
    return frontier_spmv_padded(packed, rsc_full, active_window,
                                interpret=interpret)


def gated_contrib_shard(packed: PackedGraph, rsc_full: jax.Array,
                        active_window: jax.Array, *,
                        use_kernel: bool = True) -> jax.Array:
    """Shard-local contributions for the active local windows.

    ``use_kernel=True`` runs the compiled Pallas kernel **on TPU only**.
    Off-TPU the jnp oracle is used even when the kernel is requested:
    interpret-mode Pallas was not SPMD-safe under shard_map on jax 0.4.37
    when the scalar-prefetch values diverge across devices (which
    per-shard frontier gating inherently does) — revisited output
    blocks read uninitialized memory on some shards.  A six-entry
    minimal repro and the full caveat live in DESIGN.md §9; the oracle
    computes the identical gated contributions (same f32 math, XLA
    segment_sum instead of the MXU one-hot scatter), so CPU CI exercises
    the same semantics.  ``frontier_spmv_shard`` itself stays correct in
    any single-device context (tests compare it against the oracle).
    """
    if use_kernel and ops._on_tpu():
        return frontier_spmv_shard(packed, rsc_full, active_window,
                                   interpret=False)
    return frontier_spmv_ref_padded(packed.src, packed.dst_rel,
                                    packed.valid, packed.window, rsc_full,
                                    active_window, packed.vb)


# ---------------------------------------------------------------------------
# halo: the cross-shard source boundary (dist boundary-only exchange)
# ---------------------------------------------------------------------------

class HaloSpec(NamedTuple):
    """Each shard's boundary-in set: the global src vertices whose rank
    the shard must RECEIVE each iteration because they feed its dst
    windows but live on another shard.

    ``ids[s]`` holds shard s's halo as an int32 row of capacity H; live
    entries occupy the ``count[s]``-long prefix, the tail is the
    out-of-range sentinel ``S·vps`` (scatters with ``mode="drop"``
    ignore it, the ownership test inside the exchange zeroes it).  The
    table is small — Σ|halo| is the number of distinct cut srcs, the
    graph's edge-cut boundary — and replicated on every device, which is
    what turns the per-iteration full-rank ``psum`` (O(V) wire) into one
    ``[S, H]`` exchange (O(boundary) wire).  Deletions leave stale
    entries behind (a few extra exchanged floats, never wrong values);
    repacks rebuild the table exactly.
    """

    ids: jax.Array      # int32[S, H] global src ids, sentinel-padded
    count: jax.Array    # int32[S] live prefix length


def halo_slots(halo: HaloSpec) -> int:
    """Total exchanged slots per iteration (the comm-volume unit)."""
    return int(halo.ids.shape[0] * halo.ids.shape[1])


def halo_occupancy(halo: HaloSpec) -> float:
    """Live fraction of the pinned halo table (obs gauge): 1.0 means the
    next boundary-crossing insertion forces a capacity repack."""
    slots = halo_slots(halo)
    if slots == 0:
        return 0.0
    return float(np.asarray(halo.count).sum()) / slots


def build_halo(sharded: ShardedPacked, spec: ShardSpec, *,
               capacity: int | None = None,
               min_capacity: int = 8) -> HaloSpec:
    """Host-side halo construction from the live sharded pack.

    Per shard: the unique live srcs outside its own vertex range.
    ``capacity`` pins H (streaming repacks must keep the compiled loop's
    shapes); by default H is the widest shard's halo plus 25% + 64 slots
    of insert headroom, rounded to a multiple of 64.  A pinned capacity
    smaller than a shard's rebuilt halo is a ``ShardCapacityError``.
    """
    vps = spec.vertices_per_shard
    rows = []
    for s in range(spec.num_shards):
        src = np.asarray(sharded.src[s]).reshape(-1)
        live = np.asarray(sharded.valid[s]).reshape(-1) > 0
        remote = np.unique(src[live & ((src < s * vps)
                                       | (src >= (s + 1) * vps))])
        rows.append(remote.astype(np.int32))
    widest = max((len(r) for r in rows), default=0)
    if capacity is None:
        capacity = max(min_capacity, -(-int(widest * 1.25 + 64) // 64) * 64)
    elif widest > capacity:
        bad = tuple(s for s, r in enumerate(rows) if len(r) > capacity)
        raise ShardCapacityError(
            f"shard halo of {widest} srcs exceeds the pinned halo "
            f"capacity {capacity} on shards {bad}; grow the halo "
            "(comm-volume model: DESIGN.md §10)", shards=bad)
    sentinel = spec.padded_vertices
    ids = np.full((spec.num_shards, capacity), sentinel, np.int32)
    for s, r in enumerate(rows):
        ids[s, : len(r)] = r
    return HaloSpec(ids=jnp.asarray(ids),
                    count=jnp.asarray([len(r) for r in rows], jnp.int32))


@partial(jax.jit, static_argnames=("vps",))
def _extend_halo(ids: jax.Array, count: jax.Array, ins_src: jax.Array,
                 ins_mask: jax.Array, vps: int):
    """Append each routed insertion's src to its shard's halo row.

    ``ins_src``/``ins_mask`` are ``route_update``'s [S, B] per-shard
    views (replicated host arrays, NOT under shard_map), so every row
    extends independently via vmap.  Skips own-range srcs and srcs
    already present; in-batch duplicates collapse to their first
    occurrence (same argsort scheme as the packed-lane update).  Returns
    ``(ids, count, dropped[S])`` — dropped > 0 means the pinned capacity
    overflowed and the caller repacks/regrows.
    """
    TRACE_COUNTS["extend_halo"] += 1                   # trace-time only
    S, H = ids.shape

    def row(s, row_ids, row_count, srcs, mask):
        cand = mask & ((srcs < s * vps) | (srcs >= (s + 1) * vps))
        present = jnp.any(srcs[:, None] == row_ids[None, :], axis=1)
        keep = cand & ~present
        key = jnp.where(keep, srcs, -1)
        sorted_key = jnp.sort(key)
        first = jnp.concatenate(
            [jnp.array([True]), sorted_key[1:] != sorted_key[:-1]])
        order = jnp.argsort(key)
        keep = keep & jnp.zeros_like(keep).at[order].set(
            first & (sorted_key >= 0))
        pos = row_count + jnp.cumsum(keep.astype(jnp.int32)) - 1
        ok = keep & (pos < H)
        slot = jnp.where(ok, pos, H)
        return (row_ids.at[slot].set(srcs, mode="drop"),
                (row_count
                 + jnp.sum(ok.astype(jnp.int32))).astype(jnp.int32),
                jnp.sum((keep & ~ok).astype(jnp.int32)))

    sids = jnp.arange(S, dtype=jnp.int32)
    return jax.vmap(row)(sids, ids, count, ins_src, ins_mask)


def extend_halo(halo: HaloSpec, routed: BatchUpdate, spec: ShardSpec, *,
                check: bool = True) -> HaloSpec:
    """Halo maintenance for one routed micro-batch (insertions only —
    deletions just leave stale slots).  Capacity overflow is the usual
    checked ``ShardCapacityError``; the stream owner repacks, which
    rebuilds the halo exactly (dropping any stale slots too)."""
    ids, count, dropped = _extend_halo(halo.ids, halo.count,
                                       routed.ins_src, routed.ins_mask,
                                       spec.vertices_per_shard)
    if check:
        d = np.asarray(dropped)
        if d.sum():
            bad = tuple(int(s) for s in np.flatnonzero(d))
            raise ShardCapacityError(
                f"{int(d.sum())} inserted boundary srcs exceed the halo "
                f"capacity {halo.ids.shape[1]} on shards {bad}; repack "
                "with a larger halo (comm model: DESIGN.md §10)",
                shards=bad)
    return HaloSpec(ids=ids, count=count)
