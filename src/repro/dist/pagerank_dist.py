"""shard_map DF/DF-P PageRank over the 2-D/3-D production mesh.

Two engines live here:

  * the **XLA engine** (``build_distributed_step`` /
    ``DistributedEngine``): f64 segment_sum contributions over
    model-sharded vertex ranges and data-striped edges — the original
    distributed path, described below;
  * the **kernel engine** (``sharded_kernel_pagerank`` /
    ``ShardedKernelEngine``): the Pallas frontier-gated SpMV over a
    window-range-sharded ``PackedGraph`` (kernels.pagerank_spmv.shard),
    f32 iterations with a replicated rank vector maintained by one
    ``psum`` of shard-local contributions per iteration, then the same
    f32→f64 hybrid polish as the single-pod kernel engine
    (core.kernel_engine) over the union of shard affected_ever masks.
    This makes the fast path and the scale path the same path
    (DESIGN.md §9).

Layout (DESIGN.md §4, graph/partition.py): the ``model`` axis owns
contiguous dst ranges — vertex state (ranks, inv out-degree, frontier
mask) lives model-sharded, replicated across the data axes; the ``data``
(+``pod``) axes stripe the edges *within* each dst range.  The kernel
engine reuses the same dst-range ownership at window granularity.

One iteration on a device (m, p):
  1. all_gather across ``model`` of the rank/degree product PACKED with
     the previous sweep's above-tau_f mask (one [V/M, 2] gather — the
     {0,1} mask rides the float lanes exactly; expansion marks are
     consumed one sweep later, which only reassociates the affected-set
     union);
  2. gather per-edge contributions for the local stripe, segment-sum into
     the local dst range;
  3. psum partials across the data axes → exact pull-step contributions;
  4. DF / DF-P rank update + frontier expansion (and pruning): the
     per-stripe ``push_or`` marks are OR-combined across the data axes over
     the int8-compressed wire (collectives.bool_or_psum — exact for {0,1}).

The returned step is a single jit-able function whose while_loop carries
only model-shard-local state, so per-iteration wire traffic is one
packed [V/M, 2] all_gather + one contribution psum + one compressed mask
exchange — independent of |E|.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.core import pagerank as pr
from repro.core.pagerank import (ALPHA, FRONTIER_TOL, MAX_ITER, PRUNE_TOL,
                                 TOL)
from repro.dist.collectives import bool_or_psum
from repro.dist.sharding import data_axes as _data_axes
from repro.obs import trace as obs_trace
from repro.obs.frontier import FrontierTelemetry
from repro.graph.partition import (edges_per_device, partition_graph,
                                   vertices_per_shard)

from jax.sharding import NamedSharding, PartitionSpec as P


def _mesh_dims(mesh):
    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
    dax = _data_axes(mesh)
    sizes = dict(mesh.shape)
    m = sizes["model"]
    p = int(math.prod(sizes[a] for a in dax) or 1)
    return m, p, dax


def _edge_pspec(dax) -> P:
    stripe = dax[0] if len(dax) == 1 else dax
    return P("model", stripe, None)


def distributed_in_shardings(mesh):
    """NamedShardings for the 6 step args:
    (src, dst_local, valid, ranks, inv_out_deg, affected)."""
    dax = _data_axes(mesh)
    es = NamedSharding(mesh, _edge_pspec(dax))
    vs = NamedSharding(mesh, P("model"))
    return (es, es, es, vs, vs, vs)


def distributed_input_specs(mesh, n_vertices: int, edge_capacity: int,
                            dtype=jnp.float32):
    """Abstract (ShapeDtypeStruct) inputs for ``jit(...).lower`` — the
    balanced-stripe shapes of partition_graph for this mesh."""
    m, p, _ = _mesh_dims(mesh)
    v_pad = vertices_per_shard(n_vertices, m) * m
    e_dev = edges_per_device(edge_capacity, m, p)
    sds = jax.ShapeDtypeStruct
    return (sds((m, p, e_dev), jnp.int32),
            sds((m, p, e_dev), jnp.int32),
            sds((m, p, e_dev), jnp.bool_),
            sds((v_pad,), dtype),
            sds((v_pad,), dtype),
            sds((v_pad,), jnp.bool_))


class _DistState(NamedTuple):
    ranks: jax.Array          # local [V/M]
    base: jax.Array           # local bool[V/M]: affected, pre-expansion
    big: jax.Array            # local bool[V/M]: above tau_f last sweep
    ever: jax.Array           # local bool[V/M]
    delta: jax.Array          # replicated scalar
    it: jax.Array
    edges: jax.Array
    verts: jax.Array


def build_distributed_step(mesh, n_vertices: int, *,
                           alpha: float = ALPHA, tol: float = TOL,
                           frontier_tol: float = FRONTIER_TOL,
                           prune_tol: float = PRUNE_TOL,
                           max_iter: int = MAX_ITER,
                           prune: bool = False,
                           closed_form: Optional[bool] = None,
                           int8_frontier: bool = True,
                           full_result: bool = False):
    """DF (default) / DF-P (``prune=True``) iteration as one shard_map step.

    Returns ``fn(src, dst_local, valid, ranks, inv_out_deg, affected)``
    over partition_graph's layout: edge arrays [M, P, E_dev], vertex
    arrays [v_per·M] (padded; pad slots must be unaffected with
    inv_out_deg 0).  ``fn`` → (ranks, iterations, delta), plus
    (affected_ever, edges_processed, vertices_processed) when
    ``full_result``.  The fixed point matches core.pagerank — pruning,
    expansion and the DF-P closed form are applied per Jacobi iteration
    exactly as Algorithm 1 lines 9-26.
    """
    if closed_form is None:
        closed_form = prune
    _, _, dax = _mesh_dims(mesh)
    c0_val = (1.0 - alpha) / n_vertices

    def psum_data(x):
        return jax.lax.psum(x, dax) if dax else x

    def or_data(flags):
        if not dax:
            return flags
        if int8_frontier:
            return bool_or_psum(flags, dax)
        return jax.lax.psum(flags.astype(jnp.int32), dax) > 0

    def step(src, dst, valid, ranks, inv_deg, affected):
        src, dst, valid = src[0, 0], dst[0, 0], valid[0, 0]
        cdt = ranks.dtype
        ranks = ranks.astype(jnp.float64) \
            if jax.config.jax_enable_x64 else ranks
        inv = inv_deg.astype(ranks.dtype)
        v_per = ranks.shape[0]
        c0 = jnp.asarray(c0_val, ranks.dtype)
        tiny = jnp.asarray(jnp.finfo(ranks.dtype).tiny, ranks.dtype)
        in_deg = psum_data(jax.ops.segment_sum(
            valid.astype(jnp.int64), dst, num_segments=v_per))

        def push_marks(big_full):
            """Alg.1 line 22 marks for the local stripe: out-neighbours of
            the gathered above-tau_f set, OR-combined across stripes."""
            hit = valid & big_full[src]
            return or_data(jax.ops.segment_max(
                hit.astype(jnp.int32), dst, num_segments=v_per) > 0)

        def body(st: _DistState) -> _DistState:
            r = st.ranks
            # ONE [V/M, 2] all_gather per iteration: the R/d pull view
            # packed with last sweep's above-tau_f mask ({0,1} rides the
            # float lanes exactly), so expansion costs no extra gather —
            # its marks are simply consumed one sweep later, which only
            # reassociates the affected-set union, never changes it.
            packed = jnp.stack([r * inv, st.big.astype(r.dtype)], axis=1)
            full = jax.lax.all_gather(packed, "model", tiled=True)
            w_full = full[:, 0]
            marks = push_marks(full[:, 1] > 0)
            aff = st.base | st.big | marks

            w = jnp.where(valid, w_full[src], 0.0)
            contrib = psum_data(
                jax.ops.segment_sum(w, dst, num_segments=v_per))
            if closed_form:                       # DF-P (paper Eq. 2)
                r_all = (c0 + alpha * contrib) / (1.0 - alpha * inv)
            else:                                 # DF: self-loop as a term
                r_all = c0 + alpha * (contrib + r * inv)
            r_new = jnp.where(aff, r_all, r)
            dr = jnp.abs(r_new - r)
            rel = dr / jnp.maximum(jnp.maximum(r_new, r), tiny)
            delta = jax.lax.pmax(
                jnp.max(jnp.where(aff, dr, 0.0)), ("model",) + dax)

            base = aff
            if prune:                             # Alg.1 line 19
                base = base & ~(aff & (rel <= prune_tol))
            big = aff & (rel > frontier_tol)

            edges = st.edges + jax.lax.psum(
                jnp.sum(jnp.where(aff, in_deg, 0)), "model")
            verts = st.verts + jax.lax.psum(
                jnp.sum(aff.astype(jnp.int64)), "model")
            return _DistState(r_new, base, big, st.ever | aff, delta,
                              st.it + 1, edges, verts)

        def cond(st: _DistState):
            return (st.delta > tol) & (st.it < max_iter)

        st0 = _DistState(
            ranks=ranks, base=affected,
            big=jnp.zeros_like(affected), ever=affected,
            delta=jnp.asarray(jnp.inf, ranks.dtype),
            it=jnp.asarray(0, jnp.int32),
            edges=jnp.asarray(0, jnp.int64),
            verts=jnp.asarray(0, jnp.int64))
        out = jax.lax.while_loop(cond, body, st0)
        res = (out.ranks.astype(cdt), out.it, out.delta)
        if full_result:
            # fold in the final sweep's unexpanded marks so affected_ever
            # matches the single-device engine exactly
            last = jax.lax.all_gather(out.big, "model", tiled=True)
            res += (out.ever | push_marks(last), out.edges, out.verts)
        return res

    es = _edge_pspec(dax)
    vs = P("model")
    out_specs = (vs, P(), P())
    if full_result:
        out_specs += (vs, P(), P())
    return shard_map(step, mesh=mesh,
                     in_specs=(es, es, es, vs, vs, vs),
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# streaming engine: host partitioning + a cached compiled step
# ---------------------------------------------------------------------------

class DistributedEngine:
    """Replays a dynamic-graph stream on a mesh with one compiled step.

    Pre-sizes the per-device edge capacity from the graph's (static)
    edge_capacity so the partition shape — and hence the compiled
    shard_map program — is stable across stream batches; a heavily skewed
    dst range can still grow e_dev, costing one retrace.
    """

    def __init__(self, mesh, n_vertices: int, edge_capacity: int, **opts):
        import numpy as np
        self._np = np
        self.mesh = mesh
        self.m, self.p, _ = _mesh_dims(mesh)
        self.n_vertices = n_vertices
        self.v_per = vertices_per_shard(n_vertices, self.m)
        self.v_pad = self.v_per * self.m
        self.e_dev = edges_per_device(edge_capacity, self.m, self.p)
        self._fn = jax.jit(build_distributed_step(
            mesh, n_vertices, full_result=True, **opts))
        self._shardings = distributed_in_shardings(mesh)

    def _pad(self, host_vec, dtype):
        np = self._np
        out = np.zeros((self.v_pad,), dtype)
        out[: self.n_vertices] = host_vec
        return out

    def run(self, graph, ranks, affected):
        """graph: EdgeListGraph; ranks f[V]; affected bool[V] →
        (ranks f[V], iterations, delta, affected_ever bool[V],
        edges_processed, vertices_processed)."""
        np = self._np
        part = partition_graph(graph, self.m, self.p,
                               min_edges_per_device=self.e_dev)
        self.e_dev = part.src.shape[2]            # sticky growth on skew
        deg = np.asarray(graph.out_degree(include_self_loop=True))
        inv = self._pad(1.0 / deg.astype(np.float64), np.float64)
        args = (jnp.asarray(part.src), jnp.asarray(part.dst_local),
                jnp.asarray(part.valid),
                jnp.asarray(self._pad(np.asarray(ranks), np.float64)),
                jnp.asarray(inv),
                jnp.asarray(self._pad(np.asarray(affected), bool)))
        args = tuple(jax.device_put(a, s)
                     for a, s in zip(args, self._shardings))
        r, it, delta, ever, edges, verts = self._fn(*args)
        return (r[: self.n_vertices], it, delta,
                ever[: self.n_vertices], edges, verts)


# ---------------------------------------------------------------------------
# kernel engine on the mesh: window-range-sharded frontier-gated SpMV
# ---------------------------------------------------------------------------

# compiled sharded kernel loops, keyed by (mesh, spec, solver statics);
# FIFO-bounded like the XLA engine cache
_SHARDED_LOOPS: dict = {}
_SHARDED_LOOPS_MAX = 8


def _get_sharded_loop(mesh, spec, *, alpha: float, tol: float,
                      frontier_tol: float, prune_tol: float, max_iter: int,
                      closed_form: bool, prune: bool, expand: bool,
                      use_kernel: bool):
    """One compiled shard_map'd f32 kernel loop per (mesh, spec, flags).

    Mirrors ``core.kernel_engine.kernel_pagerank_loop`` with two
    distributed moves per iteration: the shard-local gated SpMV over the
    shard's windows, and one ``psum`` over ``model`` that reassembles the
    full contribution vector (per-shard supports are disjoint — shard s
    owns all in-edges of its dst windows — so the sum is exact, not an
    approximation).  Rank state, frontier masks and expansion
    (``graph.push_or``) stay replicated: every device runs the identical
    O(V)/O(E) mask math, only the O(active edges) SpMV is sharded.
    """
    from repro.kernels.pagerank_spmv import shard as _sh

    key = (mesh, spec, alpha, tol, frontier_tol, prune_tol, max_iter,
           closed_form, prune, expand, use_kernel)
    fn = _SHARDED_LOOPS.get(key)
    if fn is not None:
        return fn
    S, wps, vb = spec.num_shards, spec.windows_per_shard, spec.vb
    vps = spec.vertices_per_shard
    v_pad = spec.padded_vertices
    V = spec.num_vertices

    def step(sharded, graph, ranks_pad, inv_deg_pad, affected):
        _sh.TRACE_COUNTS["sharded_kernel_loop"] += 1   # trace-time only
        packed = _sh._local_packed(sharded, spec, index=0)
        idx = jax.lax.axis_index("model")
        entry_edges = jnp.sum((packed.valid > 0), axis=1).astype(jnp.int64)
        c0 = jnp.float32((1.0 - alpha) / V)
        a32 = jnp.float32(alpha)

        def body(state):
            r_pad, aff, ever, _, it, edges, verts = state
            aff_pad = jnp.pad(aff, (0, v_pad - V))
            active = jnp.any(aff_pad.reshape(S * wps, vb), axis=1)
            active_l = jax.lax.dynamic_slice(active, (idx * wps,), (wps,))
            rsc = r_pad * inv_deg_pad
            contrib_l = _sh.gated_contrib_shard(packed, rsc, active_l,
                                                use_kernel=use_kernel)
            contrib = jax.lax.psum(
                jax.lax.dynamic_update_slice(
                    jnp.zeros((v_pad,), jnp.float32), contrib_l,
                    (idx * vps,)), "model")
            if closed_form:
                r_new_all = (c0 + a32 * contrib) / (1.0 - a32 * inv_deg_pad)
            else:
                r_new_all = c0 + a32 * (contrib + r_pad * inv_deg_pad)
            r_new = jnp.where(aff_pad, r_new_all, r_pad)
            dr = jnp.abs(r_new - r_pad)[:V]
            rel = dr / jnp.maximum(jnp.maximum(r_new[:V], r_pad[:V]), 1e-30)
            delta = jnp.max(jnp.where(aff, dr, 0.0))
            new_aff = aff
            if prune:
                new_aff = new_aff & ~(aff & (rel <= prune_tol))
            if expand:
                big = aff & (rel > frontier_tol)
                new_aff = new_aff | graph.push_or(big) | big
            edges = edges + jax.lax.psum(jnp.sum(
                jnp.where(active_l[packed.window], entry_edges, 0)),
                "model")
            verts = verts + jax.lax.psum(
                jnp.sum(active_l.astype(jnp.int64)) * vb, "model")
            return (r_new, new_aff, ever | new_aff, delta, it + 1,
                    edges, verts)

        def cond(state):
            return (state[3] > tol) & (state[4] < max_iter)

        state0 = (ranks_pad, affected, affected,
                  jnp.asarray(jnp.inf, jnp.float32),
                  jnp.asarray(0, jnp.int32),
                  jnp.asarray(0, jnp.int64), jnp.asarray(0, jnp.int64))
        r_out, _, ever, delta, it, edges, verts = jax.lax.while_loop(
            cond, body, state0)
        return r_out, it, delta, ever, edges, verts

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("model"), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()), check_vma=False))
    while len(_SHARDED_LOOPS) >= _SHARDED_LOOPS_MAX:
        _SHARDED_LOOPS.pop(next(iter(_SHARDED_LOOPS)))
    _SHARDED_LOOPS[key] = fn
    return fn


def _get_halo_loop(mesh, spec, halo_h: int, *, alpha: float, tol: float,
                   frontier_tol: float, prune_tol: float, max_iter: int,
                   closed_form: bool, prune: bool, expand: bool,
                   use_kernel: bool, wire: str):
    """Boundary-only sharded loop: rank stays SHARD-RESIDENT and each
    iteration exchanges just the halo table — O(boundary) wire, not O(V).

    Replaces ``_get_sharded_loop``'s replicated-rank recipe (full-rank
    ``psum`` every iteration) with the dist-engine exchange contract at
    window granularity:

      1. ONE ``[S, H, 2]`` psum per iteration carries every shard's
         owned (rank/deg, above-tau_f flag) values for every halo slot —
         each slot has exactly one owner, the rest contribute zeros, so
         the sum reconstructs the table exactly.  ``wire="quantized"``
         sends the {0,1} flags over the int8/s16 wire
         (collectives.bool_or_psum, exact) and only the f32 ranks at
         full width; ``wire="packed"`` rides both in f32 lanes.
      2. Each shard scatters its row into a local full-width rsc/flag
         buffer (own range + halo; all other slots are zero and by
         construction unread: every src in the shard's lanes is either
         owned or in its halo), runs the gated SpMV over its OWN windows
         only, and updates its local rank slice in place.
      3. Frontier expansion marks come from the shard's own packed lanes
         (``valid & big[src]`` segment-max into local windows) — the
         replicated ``graph.push_or`` is gone.  Like the XLA dist
         engine, expansion marks are consumed ONE SWEEP LATER (the
         ``[.., flag]`` lane carries the previous sweep's mask), which
         only reassociates the affected-set union; the final sweep's
         marks are folded in after the loop with one extra exchange.

    The full rank vector is reassembled (out_spec ``P("model")`` concat)
    only once, at convergence.
    """
    from repro.kernels.pagerank_spmv import shard as _sh

    key = (mesh, spec, halo_h, wire, alpha, tol, frontier_tol, prune_tol,
           max_iter, closed_form, prune, expand, use_kernel)
    fn = _SHARDED_LOOPS.get(key)
    if fn is not None:
        return fn
    S, wps, vb = spec.num_shards, spec.windows_per_shard, spec.vb
    vps = spec.vertices_per_shard
    v_pad = spec.padded_vertices
    V = spec.num_vertices

    def step(sharded, halo_ids, r_loc, inv_loc, aff_loc):
        _sh.TRACE_COUNTS["sharded_kernel_loop"] += 1   # trace-time only
        packed = _sh._local_packed(sharded, spec, index=0)
        me = jax.lax.axis_index("model")
        lo = me * vps
        entry_edges = jnp.sum((packed.valid > 0), axis=1).astype(jnp.int64)
        c0 = jnp.float32((1.0 - alpha) / V)
        a32 = jnp.float32(alpha)
        src_flat = packed.src.reshape(-1)
        valid_flat = packed.valid.reshape(-1) > 0
        dst_local = (packed.window[:, None] * vb
                     + packed.dst_rel).reshape(-1)
        owned = (halo_ids >= lo) & (halo_ids < lo + vps)      # [S, H]
        lid = jnp.clip(halo_ids - lo, 0, vps - 1)

        def exchange(rsc_loc, big_loc):
            """halo table in, (rsc_full, big_full) local buffers out."""
            vals = jnp.where(owned, rsc_loc[lid], 0.0)
            fl = jnp.where(owned, big_loc[lid], False)
            if wire == "quantized":
                vals = jax.lax.psum(vals, "model")
                fl = bool_or_psum(fl, "model")
            else:
                both = jax.lax.psum(
                    jnp.stack([vals, fl.astype(jnp.float32)], axis=-1),
                    "model")
                vals, fl = both[..., 0], both[..., 1] > 0
            my_ids = halo_ids[me]
            rsc_full = jax.lax.dynamic_update_slice(
                jnp.zeros((v_pad,), jnp.float32), rsc_loc, (lo,))
            rsc_full = rsc_full.at[my_ids].set(vals[me], mode="drop")
            big_full = jax.lax.dynamic_update_slice(
                jnp.zeros((v_pad,), bool), big_loc, (lo,))
            big_full = big_full.at[my_ids].set(fl[me], mode="drop")
            return rsc_full, big_full

        def marks_from(big_full):
            hit = valid_flat & big_full[src_flat]
            return jax.ops.segment_max(hit.astype(jnp.int32), dst_local,
                                       num_segments=vps) > 0

        def body(state):
            r, base, big, ever, _, it, edges, verts = state
            rsc_full, big_full = exchange(r * inv_loc, big)
            aff = base | big
            if expand:
                aff = aff | marks_from(big_full)
            active_l = jnp.any(aff.reshape(wps, vb), axis=1)
            contrib_l = _sh.gated_contrib_shard(packed, rsc_full, active_l,
                                                use_kernel=use_kernel)
            if closed_form:
                r_all = (c0 + a32 * contrib_l) / (1.0 - a32 * inv_loc)
            else:
                r_all = c0 + a32 * (contrib_l + r * inv_loc)
            r_new = jnp.where(aff, r_all, r)
            dr = jnp.abs(r_new - r)
            rel = dr / jnp.maximum(jnp.maximum(r_new, r), 1e-30)
            delta = jax.lax.pmax(jnp.max(jnp.where(aff, dr, 0.0)), "model")
            new_base = aff
            if prune:
                new_base = new_base & ~(aff & (rel <= prune_tol))
            new_big = (aff & (rel > frontier_tol)) if expand \
                else jnp.zeros_like(aff)
            edges = edges + jax.lax.psum(jnp.sum(
                jnp.where(active_l[packed.window], entry_edges, 0)),
                "model")
            verts = verts + jax.lax.psum(
                jnp.sum(active_l.astype(jnp.int64)) * vb, "model")
            return (r_new, new_base, new_big, ever | aff, delta, it + 1,
                    edges, verts)

        def cond(state):
            return (state[4] > tol) & (state[5] < max_iter)

        state0 = (r_loc, aff_loc, jnp.zeros_like(aff_loc), aff_loc,
                  jnp.asarray(jnp.inf, jnp.float32),
                  jnp.asarray(0, jnp.int32),
                  jnp.asarray(0, jnp.int64), jnp.asarray(0, jnp.int64))
        r_out, _, big, ever, delta, it, edges, verts = jax.lax.while_loop(
            cond, body, state0)
        if expand:
            # fold in the final sweep's unconsumed marks (one extra
            # exchange), matching the XLA dist engine's full_result
            _, big_full = exchange(r_out * inv_loc, big)
            ever = ever | marks_from(big_full)
        return r_out, it, delta, ever, edges, verts

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("model"), P(), P("model"), P("model"), P("model")),
        out_specs=(P("model"), P(), P(), P("model"), P(), P()),
        check_vma=False))
    while len(_SHARDED_LOOPS) >= _SHARDED_LOOPS_MAX:
        _SHARDED_LOOPS.pop(next(iter(_SHARDED_LOOPS)))
    _SHARDED_LOOPS[key] = fn
    return fn


# nominal per-link ICI bandwidth used ONLY to give the modeled
# ``halo.exchange`` trace span a plausible duration — never for decisions
_LINK_BW_BYTES_PER_S = 25e9


def halo_comm_bytes(halo, iterations: int, *, wire: str = "packed",
                    expand: bool = True) -> int:
    """Wire bytes of one solve's halo exchanges (per device): each
    iteration moves the [S, H] rank lanes (f32) plus the flag lanes (f32
    packed, or s16 over the quantized wire), and the final fold-in is
    one more exchange.  Sublinear in V: proportional to S·H, the padded
    boundary size."""
    from repro.kernels.pagerank_spmv.shard import halo_slots

    slots = halo_slots(halo)
    per_iter = slots * (4 + (2 if wire == "quantized" else 4))
    return (int(iterations) + (1 if expand else 0)) * per_iter


def sharded_hybrid_pagerank(mesh, sharded, spec, graph, init_ranks,
                            init_affected, *, alpha: float = ALPHA,
                            tol: float = TOL, tol_f32: float = 1e-7,
                            frontier_tol: float = FRONTIER_TOL,
                            prune_tol: float = PRUNE_TOL,
                            kernel_frontier_tol: float = 1e-5,
                            kernel_prune_tol: float = 1e-5,
                            max_iter: int = MAX_ITER,
                            closed_form: bool = False, prune: bool = False,
                            expand: bool = True, polish: bool = True,
                            use_kernel: bool = False, halo=None,
                            wire: str = "packed",
                            comm_info: Optional[dict] = None,
                            telemetry: bool = False
                            ) -> pr.PageRankResult:
    """The sharded precision ladder: f32 kernel iterations on the mesh to
    ``tol_f32``, then the f64 XLA polish on the default device seeded
    with the union of shard ``affected_ever`` masks — same fixed point
    and ``PageRankResult`` contract as ``core.kernel_engine
    .hybrid_pagerank`` and the f64 engine (L∞ ≤ 1e-6, DESIGN.md §8-§9).

    ``halo`` (a ``shard.HaloSpec``) switches the f32 phase to the
    boundary-only exchange loop — shard-resident ranks, per-iteration
    wire ∝ halo size instead of V (``wire="quantized"`` compresses the
    flag lanes over the int8/s16 wire; the f64 polish stays exact
    either way).  ``comm_info`` (a dict, mutated) receives the solve's
    ``comm_bytes`` / ``halo_slots`` / ``f32_iterations`` accounting.

    ``telemetry=True`` records per-iteration obs.frontier rows in the
    polish phase (the sharded f32 loops expose only their endpoint
    scalars — per-iteration rows would ride the wire every sweep, so the
    f32 phase is summarized in ``comm_info`` instead); the tracer gets a
    span per mesh program and a modeled ``halo.exchange`` span from the
    wire accounting (the exchange runs inside the compiled loop and
    cannot be host-timed; ``args["modeled"]`` marks it).
    """
    import numpy as np

    def put(x, spec):
        # commit every loop input to the sharding its in_spec names: the
        # jit cache keys on input shardings, and ranks arrive sharded from
        # a mesh static solve but unsharded from the polish
        return jax.device_put(x, NamedSharding(mesh, spec))

    tr = obs_trace.get_tracer()
    V = spec.num_vertices
    v_pad = spec.padded_vertices
    deg = graph.out_degree(include_self_loop=True)
    inv_pad = jnp.pad((1.0 / deg).astype(jnp.float32), (0, v_pad - V))
    r_pad = jnp.pad(init_ranks.astype(jnp.float32), (0, v_pad - V))
    sharded = put(sharded, P("model"))
    s0 = tr.now()
    if halo is not None:
        loop = _get_halo_loop(mesh, spec, halo.ids.shape[1], alpha=alpha,
                              tol=tol_f32,
                              frontier_tol=kernel_frontier_tol,
                              prune_tol=kernel_prune_tol,
                              max_iter=max_iter, closed_form=closed_form,
                              prune=prune, expand=expand,
                              use_kernel=use_kernel, wire=wire)
        aff_pad = jnp.pad(init_affected, (0, v_pad - V))
        r_out, it, delta, ever, edges, verts = loop(
            sharded, put(halo.ids, P()), put(r_pad, P("model")),
            put(inv_pad, P("model")), put(aff_pad, P("model")))
        ever = ever[:V]
        if comm_info is not None:
            from repro.kernels.pagerank_spmv.shard import halo_slots
            comm_info["f32_iterations"] = int(it)
            comm_info["halo_slots"] = halo_slots(halo)
            comm_info["comm_bytes"] = halo_comm_bytes(
                halo, int(it), wire=wire, expand=expand)
    else:
        loop = _get_sharded_loop(mesh, spec, alpha=alpha, tol=tol_f32,
                                 frontier_tol=kernel_frontier_tol,
                                 prune_tol=kernel_prune_tol,
                                 max_iter=max_iter, closed_form=closed_form,
                                 prune=prune, expand=expand,
                                 use_kernel=use_kernel)
        r_out, it, delta, ever, edges, verts = loop(
            sharded, put(graph, P()), put(r_pad, P()), put(inv_pad, P()),
            put(init_affected, P()))
        if comm_info is not None:
            # replicated-rank recipe: one full-rank [v_pad] f32 psum per
            # iteration on every device — the O(V) cost the halo removes
            comm_info["f32_iterations"] = int(it)
            comm_info["halo_slots"] = 0
            comm_info["comm_bytes"] = int(it) * v_pad * 4
    if tr.enabled:
        tr.sync(r_out)
        tr.record("sharded_f32_loop", s0, tr.now() - s0,
                  exchange="halo" if halo is not None else "psum",
                  iterations=int(it))
        cb = (comm_info or {}).get("comm_bytes")
        if cb is None:
            cb = halo_comm_bytes(halo, int(it), wire=wire, expand=expand) \
                if halo is not None else int(it) * v_pad * 4
        # the exchange lives inside the compiled loop — model its span
        # from the wire accounting instead of pretending to host-time it
        tr.record("halo.exchange", s0, cb / _LINK_BW_BYTES_PER_S,
                  comm_bytes=int(cb), modeled=True,
                  wire=wire if halo is not None else "psum")
    # hop the replicated results off the mesh so the f64 polish runs as a
    # plain single-device jit (mixing committed mesh arrays into it would
    # be a device mismatch)
    k_ranks = jnp.asarray(np.asarray(r_out[:V]))
    ever = jnp.asarray(np.asarray(ever))
    it = jnp.asarray(np.asarray(it))
    edges = jnp.asarray(np.asarray(edges))
    verts = jnp.asarray(np.asarray(verts))
    if not polish:
        return pr.PageRankResult(k_ranks.astype(jnp.float64), it,
                                 jnp.asarray(np.asarray(delta),
                                             jnp.float64),
                                 ever, edges, verts)
    with tr.span("polish.f64", program="xla_polish"):
        p = pr._pagerank_loop(graph, k_ranks.astype(jnp.float64), ever,
                              alpha=alpha, tol=tol,
                              frontier_tol=frontier_tol,
                              prune_tol=prune_tol, max_iter=max_iter,
                              closed_form=closed_form, prune=prune,
                              expand=expand, telemetry=telemetry)
        tr.sync(p.ranks)
    tel = None
    if telemetry and p.telemetry is not None:
        tel = FrontierTelemetry.from_padded(p.telemetry, p.iterations).data
    return pr.PageRankResult(p.ranks, it + p.iterations, p.delta,
                             ever | p.affected_ever,
                             edges + p.edges_processed,
                             verts + p.vertices_processed,
                             telemetry=tel)


def sharded_kernel_pagerank(graph, init_ranks, init_affected, mesh, *,
                            sharded=None, spec=None, pack_kw=None,
                            **kw) -> pr.PageRankResult:
    """One-shot ``engine="kernel"`` on a mesh: pack (unless the caller
    maintains the sharded structure incrementally — see
    ``ShardedKernelEngine``) and run the sharded hybrid ladder."""
    from repro.kernels.pagerank_spmv.shard import build_halo, pack_shards

    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
    if sharded is None:
        sharded, spec = pack_shards(graph, int(mesh.shape["model"]),
                                    **(pack_kw or {}))
    if kw.pop("exchange", "halo") == "halo" and "halo" not in kw:
        kw["halo"] = build_halo(sharded, spec)
    return sharded_hybrid_pagerank(mesh, sharded, spec, graph, init_ranks,
                                   init_affected, **kw)


class ShardedKernelEngine:
    """Streaming owner of the sharded kernel path: one sharded pack per
    bootstrap, per-batch delta routing + shard_map'd incremental update,
    one compiled kernel loop — the mesh analogue of the ``ServeEngine``'s
    single-pod kernel path.

    All pack statics are pinned at construction (entry capacity, the
    per-window entry bound, overlay size), so overflow ``repack``s never
    change the ``ShardSpec`` and therefore never retrace the compiled
    update or loop.  ``delta_budget`` bounds the routed per-shard rows of
    each micro-batch (None = the full batch capacity — any batch fits);
    overflowing it, a window's spill lanes or the locator overlay raises
    ``ShardCapacityError`` naming the shards, which stream owners resolve
    by ``repack`` (the serve engine counts these per shard).

    ``exchange="halo"`` (the default) keeps ranks shard-resident and
    exchanges only the cross-shard boundary each f32 iteration: the halo
    table is built at bootstrap, extended on-device as routed insertions
    land (capacity-checked like every other structure; a repack rebuilds
    it exactly, shedding deletion-stale slots), and its pinned capacity
    keeps the compiled loop's shapes static.  ``exchange="psum"`` is the
    replicated-rank full-psum recipe (the PR-5 baseline, kept for
    differentials).  After each solve, ``last_comm_info`` /
    ``last_comm_bytes`` expose the per-solve wire accounting.
    """

    def __init__(self, mesh, graph, *, pack_kw=None, delta_budget=None,
                 use_kernel: bool = False, exchange: str = "halo",
                 wire: str = "packed", halo_capacity=None, **loop_kw):
        from repro.kernels.pagerank_spmv.shard import (build_halo,
                                                       build_sharded_apply,
                                                       pack_shards)

        if "model" not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
        if exchange not in ("halo", "psum"):
            raise ValueError(f"exchange must be 'halo' or 'psum', "
                             f"got {exchange!r}")
        self.mesh = mesh
        self.num_shards = int(mesh.shape["model"])
        pack_kw = dict(pack_kw or {})
        pack_kw.setdefault("spill_lanes_per_window", 1)
        sharded, spec = pack_shards(graph, self.num_shards, **pack_kw)
        self.sharded = self._on_mesh(sharded)
        # pin every static: repacks must not change any shape or static
        # field (max_entries_per_window at the trivially safe bound —
        # a repack may redistribute entries to windows that grew)
        self.spec = spec._replace(max_entries_per_window=spec.num_entries)
        pack_kw["num_entries"] = self.spec.num_entries
        pack_kw["max_entries_per_window"] = self.spec.num_entries
        pack_kw["overlay_capacity"] = self.spec.overlay_capacity
        pack_kw.pop("extra_entries", None)
        self._pack_kw = pack_kw
        self.delta_budget = delta_budget
        self.use_kernel = use_kernel
        self.exchange = exchange
        self.wire = wire
        self.halo = None
        if exchange == "halo":
            self.halo = build_halo(self.sharded, self.spec,
                                   capacity=halo_capacity)
            self._halo_capacity = int(self.halo.ids.shape[1])
        self.last_comm_info: dict = {}
        self.last_comm_bytes = 0
        self.loop_kw = loop_kw
        self._apply = build_sharded_apply(mesh, self.spec)

    def _on_mesh(self, sharded):
        """Place a fresh pack on the ``model`` axis, where the compiled
        update leaves its output: a pack left on the default device would
        give the update a second input sharding, and so a retrace."""
        return jax.device_put(sharded, NamedSharding(self.mesh, P("model")))

    def apply_update(self, update):
        """Route Δ to its owning shards, apply under shard_map, extend
        the halo with any inserted boundary srcs.  Raises
        ``ShardCapacityError`` (budget/spill/overlay/halo) unchanged —
        the structures are only replaced on success, atomically."""
        import numpy as np

        from repro.kernels.pagerank_spmv.shard import (ShardCapacityError,
                                                       extend_halo,
                                                       route_update)

        routed = route_update(update, self.spec,
                              del_budget=self.delta_budget,
                              ins_budget=self.delta_budget)
        new, dropped = self._apply(self.sharded, routed)
        d = np.asarray(dropped)
        if d.sum():
            bad = tuple(int(s) for s in np.flatnonzero(d))
            raise ShardCapacityError(
                f"{int(d.sum())} insertions exceed spill capacity of "
                f"their dst windows or the locator overlay on shards "
                f"{bad}; repack with pack_shards (capacity sizing: "
                "DESIGN.md §8-§9)", shards=bad)
        new_halo = None
        if self.halo is not None:
            new_halo = extend_halo(self.halo, routed, self.spec)
        self.sharded = new
        if new_halo is not None:
            self.halo = new_halo

    def repack(self, graph):
        """Rebuild the sharded pack from ``graph`` at the pinned shapes,
        degrading the spill guarantee to the sharded minimum (1 lane) if
        regrown windows no longer fit it — same recovery contract as the
        single-pod serve path.  The halo is rebuilt exactly (stale slots
        dropped); if the boundary outgrew its pinned capacity the table
        grows, costing the one loop recompile the growth forces."""
        from repro.kernels.pagerank_spmv.shard import (ShardCapacityError,
                                                       build_halo,
                                                       pack_shards)

        try:
            sharded, spec = pack_shards(graph, self.num_shards,
                                        **self._pack_kw)
        except ValueError:
            sharded, spec = pack_shards(
                graph, self.num_shards,
                **{**self._pack_kw, "spill_lanes_per_window": 1})
        spec = spec._replace(max_entries_per_window=self.spec.num_entries)
        assert spec == self.spec, "repack changed pinned statics"
        self.sharded = self._on_mesh(sharded)
        if self.halo is not None:
            try:
                self.halo = build_halo(self.sharded, self.spec,
                                       capacity=self._halo_capacity)
            except ShardCapacityError:
                self.halo = build_halo(self.sharded, self.spec)
                self._halo_capacity = int(self.halo.ids.shape[1])

    def solve(self, graph, init_ranks, init_affected,
              **flags) -> pr.PageRankResult:
        self.last_comm_info = {}
        res = sharded_hybrid_pagerank(
            self.mesh, self.sharded, self.spec, graph, init_ranks,
            init_affected, use_kernel=self.use_kernel, halo=self.halo,
            wire=self.wire, comm_info=self.last_comm_info,
            **{**self.loop_kw, **flags})
        self.last_comm_bytes = self.last_comm_info.get("comm_bytes", 0)
        return res
