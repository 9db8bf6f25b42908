"""DF/DF-P engine variant running on the Pallas frontier-gated kernel.

This is the single-pod *performance path*: contributions come from the
blocked, window-gated SpMV (f32, MXU scatter) instead of the XLA
segment_sum (f64).  Frontier marking still uses the edge-list ``push_or``
(boolean propagation is cheap).

Two precision regimes:

  * ``kernel_pagerank_loop`` — pure f32, tolerances default to
    f32-appropriate values; fixed points agree with the f64 engine to
    f32 precision.  The loop keeps its rank buffer *padded* to NW·VB and
    receives a precomputed ``active_window`` per iteration, so the
    while_loop body pays no pad/reduce/slice glue around the kernel.
  * ``hybrid_pagerank`` — the serving ladder: f32 kernel iterations to
    ``tol_f32``, then a short f64 XLA polish seeded with the kernel
    phase's ``affected_ever`` set, down to the paper's τ.  The result is
    a drop-in ``PageRankResult`` meeting the tier-1 L∞ ≤ 1e-6
    equivalence contracts of the f64 engine (DESIGN.md §8).

Work accounting matches the kernel's actual granularity: per iteration,
``edges_processed`` adds the live-edge counts of *active entries* and
``vertices_processed`` adds VB per active window — what the gated SpMV
really gathers/updates, comparable against ``PageRankResult``'s
per-vertex numbers from the XLA engine.
"""
from __future__ import annotations

import collections
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import pagerank as pr
from repro.core.pagerank import ALPHA, initial_affected
from repro.graph.structure import EdgeListGraph
from repro.kernels.pagerank_spmv.ops import PackedGraph, gated_contrib
from repro.obs import trace as obs_trace
from repro.obs.frontier import NUM_FIELDS as _TEL_K
from repro.obs.frontier import FrontierTelemetry
from repro.obs.frontier import telemetry_row as _tel_row

# trace-time counters (see kernels.pagerank_spmv.update.TRACE_COUNTS):
# a temporal stream must compile the loop once and never again
TRACE_COUNTS: collections.Counter = collections.Counter()


class KernelPRResult(NamedTuple):
    ranks: jax.Array             # f32[V]
    iterations: jax.Array
    delta: jax.Array
    affected_ever: jax.Array
    edges_processed: jax.Array   # i64[] Σ live edges of active entries
    vertices_processed: jax.Array  # i64[] Σ VB per active window
    telemetry: Optional[jax.Array] = None  # f32[max_iter, k] when requested


def _loop_setup(graph, packed, *, alpha, tol, frontier_tol, prune_tol,
                max_iter, closed_form, prune, expand, use_kernel,
                telemetry=False):
    """Shared (cond, body, state0) builder for the plain and fused loops.

    Both entry points run the IDENTICAL body/cond closures, so the fused
    path (which peels the first sweep out of the while_loop) is bitwise
    equal to the plain loop — ``cond(state0)`` is always true (delta
    starts at inf, it at 0), so peeling one ``body`` application off the
    front is a pure re-association.
    """
    V = graph.num_vertices
    nw, vb = packed.num_windows, packed.vb
    v_pad = nw * vb
    deg = graph.out_degree(include_self_loop=True)
    inv_deg_pad = jnp.pad((1.0 / deg).astype(jnp.float32), (0, v_pad - V))
    # per-entry live-edge counts: constant across the loop (the packed
    # structure only changes between solves), so the per-iteration work
    # counter is an O(NE) masked sum, not an O(NE·BE) rescan
    entry_edges = jnp.sum((packed.valid > 0), axis=1).astype(jnp.int64)
    c0 = jnp.float32((1.0 - alpha) / V)
    a32 = jnp.float32(alpha)

    def body(state):
        ranks_pad, affected, ever, _, it, edges, verts = state[:7]
        aff_pad = jnp.pad(affected, (0, v_pad - V))
        active_window = jnp.any(aff_pad.reshape(nw, vb), axis=1)
        contrib = gated_contrib(packed, ranks_pad, inv_deg_pad,
                                active_window=active_window,
                                use_kernel=use_kernel, pad_out=True)
        if closed_form:
            r_new_all = (c0 + a32 * contrib) / (1.0 - a32 * inv_deg_pad)
        else:
            r_new_all = c0 + a32 * (contrib + ranks_pad * inv_deg_pad)
        r_new = jnp.where(aff_pad, r_new_all, ranks_pad)
        dr = jnp.abs(r_new - ranks_pad)[:V]
        rel = dr / jnp.maximum(jnp.maximum(r_new[:V], ranks_pad[:V]), 1e-30)
        delta = jnp.max(jnp.where(affected, dr, 0.0))
        new_affected = affected
        if prune:
            new_affected = new_affected & ~(affected & (rel <= prune_tol))
        if expand:
            big = affected & (rel > frontier_tol)
            new_affected = new_affected | graph.push_or(big) | big
        edges = edges + jnp.sum(
            jnp.where(active_window[packed.window], entry_edges, 0))
        verts = verts + jnp.sum(active_window.astype(jnp.int64)) * vb
        out = (r_new, new_affected, ever | new_affected, delta, it + 1,
               edges, verts)
        if not telemetry:
            return out
        row = _tel_row(jnp.sum(affected), delta,
                       jnp.sum(new_affected & ~affected),
                       jnp.sum(affected & ~new_affected),
                       jnp.sum(active_window), jnp.float32)
        tel = jax.lax.dynamic_update_slice(
            state[7], row[None, :], (it, jnp.asarray(0, jnp.int32)))
        return out + (tel,)

    def cond(state):
        return (state[3] > tol) & (state[4] < max_iter)

    def state0(init_ranks, init_affected):
        st = (jnp.pad(init_ranks.astype(jnp.float32), (0, v_pad - V)),
              init_affected, init_affected,
              jnp.asarray(jnp.inf, jnp.float32),
              jnp.asarray(0, jnp.int32),
              jnp.asarray(0, jnp.int64), jnp.asarray(0, jnp.int64))
        if telemetry:
            st += (jnp.zeros((max_iter, _TEL_K), jnp.float32),)
        return st

    return cond, body, state0


@partial(jax.jit, static_argnames=("closed_form", "prune", "expand",
                                   "max_iter", "use_kernel", "telemetry"))
def kernel_pagerank_loop(graph: EdgeListGraph, packed: PackedGraph,
                         init_ranks: jax.Array, init_affected: jax.Array, *,
                         alpha: float = ALPHA, tol: float = 1e-7,
                         frontier_tol: float = 1e-5, prune_tol: float = 1e-5,
                         max_iter: int = 500, closed_form: bool = False,
                         prune: bool = False, expand: bool = True,
                         use_kernel: bool = True,
                         telemetry: bool = False) -> KernelPRResult:
    TRACE_COUNTS["kernel_pagerank_loop"] += 1          # trace-time only
    V = graph.num_vertices
    cond, body, state0 = _loop_setup(
        graph, packed, alpha=alpha, tol=tol, frontier_tol=frontier_tol,
        prune_tol=prune_tol, max_iter=max_iter, closed_form=closed_form,
        prune=prune, expand=expand, use_kernel=use_kernel,
        telemetry=telemetry)
    out = jax.lax.while_loop(cond, body, state0(init_ranks, init_affected))
    ranks_pad, _, ever, delta, it, edges, verts = out[:7]
    return KernelPRResult(ranks_pad[:V], it, delta, ever, edges, verts,
                          telemetry=out[7] if telemetry else None)


@partial(jax.jit, static_argnames=("closed_form", "prune", "expand",
                                   "max_iter", "use_kernel", "telemetry"))
def _fused_update_loop(graph_new: EdgeListGraph, packed: PackedGraph,
                       update, init_ranks: jax.Array,
                       init_affected: jax.Array, *,
                       alpha: float = ALPHA, tol: float = 1e-7,
                       frontier_tol: float = 1e-5, prune_tol: float = 1e-5,
                       max_iter: int = 500, closed_form: bool = False,
                       prune: bool = False, expand: bool = True,
                       use_kernel: bool = True, telemetry: bool = False):
    """ONE device program: packed micro-batch maintenance + the whole
    f32 loop, first sweep peeled so it fuses with the update pass.

    Applies ``update`` to ``packed`` (inlining ``_apply_batch_packed``),
    runs the first gated sweep on the freshly updated structure in the
    same program, then enters the while_loop at iteration 1.  Returns
    ``(new_packed, dropped, KernelPRResult)``; ``dropped`` is the spill
    overflow count the host wrapper turns into the usual checked error.
    Re-running after a repack is safe: the update's deletions are
    already absent and its insertions already live, so maintenance
    degenerates to a no-op and only the solve repeats.
    """
    TRACE_COUNTS["fused_update_loop"] += 1             # trace-time only
    from repro.kernels.pagerank_spmv.update import _apply_batch_packed
    new_packed, dropped = _apply_batch_packed(packed, update)
    V = graph_new.num_vertices
    cond, body, state0 = _loop_setup(
        graph_new, new_packed, alpha=alpha, tol=tol,
        frontier_tol=frontier_tol, prune_tol=prune_tol, max_iter=max_iter,
        closed_form=closed_form, prune=prune, expand=expand,
        use_kernel=use_kernel, telemetry=telemetry)
    # cond(state0) is unconditionally true (delta=inf, it=0 < max_iter),
    # so the peel preserves the plain loop's exact iteration sequence
    state1 = body(state0(init_ranks, init_affected))
    out = jax.lax.while_loop(cond, body, state1)
    ranks_pad, _, ever, delta, it, edges, verts = out[:7]
    return new_packed, dropped, KernelPRResult(
        ranks_pad[:V], it, delta, ever, edges, verts,
        telemetry=out[7] if telemetry else None)


def _merged_telemetry(k: KernelPRResult, p: Optional[pr.PageRankResult]):
    """Trimmed f64 [iters, k] rows of the hybrid ladder, kernel phase
    first then polish — the one host transfer the telemetry path does."""
    parts = [FrontierTelemetry.from_padded(k.telemetry, k.iterations)]
    if p is not None and p.telemetry is not None:
        parts.append(FrontierTelemetry.from_padded(p.telemetry,
                                                   p.iterations))
    return FrontierTelemetry.concat(*parts).data


def fused_hybrid_pagerank(graph_new: EdgeListGraph, packed: PackedGraph,
                          update, init_ranks: jax.Array,
                          init_affected: jax.Array, *,
                          alpha: float = ALPHA, tol: float = pr.TOL,
                          tol_f32: float = 1e-7,
                          frontier_tol: float = pr.FRONTIER_TOL,
                          prune_tol: float = pr.PRUNE_TOL,
                          kernel_frontier_tol: float = 1e-5,
                          kernel_prune_tol: float = 1e-5,
                          max_iter: int = pr.MAX_ITER,
                          closed_form: bool = False, prune: bool = False,
                          expand: bool = True, polish: bool = True,
                          use_kernel: bool = True,
                          telemetry: bool = False):
    """Fused serving step: ``(new_packed, PageRankResult)`` from one
    device program for maintenance + the entire f32 phase (plus the
    usual f64 polish program when ``polish=True``).

    Spill/overlay exhaustion raises the same checked ``ValueError`` as
    ``apply_batch_packed`` — the caller repacks at the pinned shapes and
    re-invokes with the SAME update (idempotent, see _fused_update_loop).

    ``telemetry=True`` records per-iteration obs.frontier rows in both
    phases (result.telemetry: trimmed f64 [iters, k], kernel rows then
    polish rows); the tracer, when enabled, gets one span per device
    program with honest durations (``Tracer.sync``).
    """
    tr = obs_trace.get_tracer()
    with tr.span("fused_update_loop", program="update+f32_loop"):
        new_packed, dropped, k = _fused_update_loop(
            graph_new, packed, update, init_ranks, init_affected,
            alpha=alpha, tol=tol_f32, frontier_tol=kernel_frontier_tol,
            prune_tol=kernel_prune_tol, max_iter=max_iter,
            closed_form=closed_form, prune=prune, expand=expand,
            use_kernel=use_kernel, telemetry=telemetry)
        tr.sync(k.ranks)
    n = int(dropped)
    if n:
        raise ValueError(
            f"{n} insertions exceed spill capacity of their dst "
            f"windows or the locator overlay; repack with pack_graph "
            "/ raise spill_lanes_per_window or overlay_capacity "
            "(capacity sizing: DESIGN.md §8)")
    if not polish:
        return new_packed, pr.PageRankResult(
            k.ranks.astype(jnp.float64), k.iterations,
            k.delta.astype(jnp.float64), k.affected_ever,
            k.edges_processed, k.vertices_processed,
            telemetry=_merged_telemetry(k, None) if telemetry else None)
    with tr.span("polish.f64", program="xla_polish") as sp:
        p = pr._pagerank_loop(graph_new, k.ranks.astype(jnp.float64),
                              k.affected_ever, alpha=alpha, tol=tol,
                              frontier_tol=frontier_tol,
                              prune_tol=prune_tol,
                              max_iter=max_iter, closed_form=closed_form,
                              prune=prune, expand=expand,
                              telemetry=telemetry)
        tr.sync(p.ranks)
        if tr.enabled:
            sp.set(sweeps=int(p.iterations))
    return new_packed, pr.PageRankResult(
        p.ranks, k.iterations + p.iterations, p.delta,
        k.affected_ever | p.affected_ever,
        k.edges_processed + p.edges_processed,
        k.vertices_processed + p.vertices_processed,
        telemetry=_merged_telemetry(k, p) if telemetry else None)


def hybrid_pagerank(graph: EdgeListGraph, packed: PackedGraph,
                    init_ranks: jax.Array, init_affected: jax.Array, *,
                    alpha: float = ALPHA, tol: float = pr.TOL,
                    tol_f32: float = 1e-7,
                    frontier_tol: float = pr.FRONTIER_TOL,
                    prune_tol: float = pr.PRUNE_TOL,
                    kernel_frontier_tol: float = 1e-5,
                    kernel_prune_tol: float = 1e-5,
                    max_iter: int = pr.MAX_ITER, closed_form: bool = False,
                    prune: bool = False, expand: bool = True,
                    polish: bool = True, use_kernel: bool = True,
                    telemetry: bool = False) -> pr.PageRankResult:
    """Precision ladder: f32 kernel iterations to ``tol_f32``, then an
    optional f64 XLA polish seeded from the kernel phase's affected_ever
    set down to ``tol`` — same fixed point and result type as the f64
    engine, with the bulk of the iterations on the gated f32 path."""
    tr = obs_trace.get_tracer()
    with tr.span("kernel_loop.f32", program="f32_loop"):
        k = kernel_pagerank_loop(graph, packed, init_ranks, init_affected,
                                 alpha=alpha, tol=tol_f32,
                                 frontier_tol=kernel_frontier_tol,
                                 prune_tol=kernel_prune_tol,
                                 max_iter=max_iter,
                                 closed_form=closed_form, prune=prune,
                                 expand=expand, use_kernel=use_kernel,
                                 telemetry=telemetry)
        tr.sync(k.ranks)
    if not polish:
        return pr.PageRankResult(
            k.ranks.astype(jnp.float64), k.iterations,
            k.delta.astype(jnp.float64), k.affected_ever,
            k.edges_processed, k.vertices_processed,
            telemetry=_merged_telemetry(k, None) if telemetry else None)
    with tr.span("polish.f64", program="xla_polish") as sp:
        p = pr._pagerank_loop(graph, k.ranks.astype(jnp.float64),
                              k.affected_ever, alpha=alpha, tol=tol,
                              frontier_tol=frontier_tol,
                              prune_tol=prune_tol,
                              max_iter=max_iter, closed_form=closed_form,
                              prune=prune, expand=expand,
                              telemetry=telemetry)
        tr.sync(p.ranks)
        if tr.enabled:
            sp.set(sweeps=int(p.iterations))
    return pr.PageRankResult(
        p.ranks, k.iterations + p.iterations, p.delta,
        k.affected_ever | p.affected_ever,
        k.edges_processed + p.edges_processed,
        k.vertices_processed + p.vertices_processed,
        telemetry=_merged_telemetry(k, p) if telemetry else None)


def df_pagerank_kernel(graph_prev: EdgeListGraph, graph_new: EdgeListGraph,
                       packed_new: PackedGraph, touched: jax.Array,
                       prev_ranks: jax.Array, *, prune: bool = False,
                       **kw) -> KernelPRResult:
    aff = initial_affected(graph_prev, graph_new, touched)
    return kernel_pagerank_loop(graph_new, packed_new, prev_ranks, aff,
                                prune=prune, closed_form=prune, **kw)
