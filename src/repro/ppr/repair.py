"""Incremental walk-index maintenance from the DF ``touched`` signal.

The DF/DF-P engines localise a batch update Δᵗ to ``touched_vertices_mask``
— the vertices whose out-transition distribution changed.  The same signal
drives Monte-Carlo index repair (Zhang, Lofgren & Goel, *Approximate
Personalized PageRank on Dynamic Graphs*):

  a stored walk is **stale** iff it occupies a touched vertex at any hop
  (including its source slot — a degree-changed source changes the very
  first transition).  Every transition of a non-stale walk left an
  untouched vertex, whose neighbour list is identical (same order — see
  ``EdgeListGraph.to_device_csr``) in Gᵗ⁻¹ and Gᵗ, so the walk is already
  a valid Gᵗ walk and is kept bit-for-bit.

Stale walks are repaired from their **first stale hop** t₀: the prefix
[0..t₀] only ever left untouched vertices, so it is still a valid Gᵗ
trajectory; the suffix is resampled on Gᵗ with the walk's own per-hop
PRNG draws (walks.py).  Because those draws are a pure function of
(base_key, walk, hop), the repaired suffix is exactly what a fresh
build on Gᵗ would produce — repair is *bitwise equivalent* to a full
rebuild while touching only the stale walks (tests assert both).

Cost shape: staleness detection is one fused gather-reduce over the
index (the unavoidable O(V·R·L) read, analogous to DF's per-iteration
frontier scan); resampling is compacted to the S stale walks, padded to
a power-of-two capacity so a temporal stream reuses a handful of
compiled resamplers instead of recompiling per batch.  The scatter back
into the step array copies it — deliberately: the serve engine's
published snapshot still references the previous index's buffers until
the next publish, so in-place buffer donation would corrupt answers
being served from it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.graph.structure import CSRView, EdgeListGraph
from repro.obs import trace as obs_trace
from repro.ppr.walks import WalkIndex, _transition, _walk_draws, _walk_keys

_device_csr = jax.jit(EdgeListGraph.to_device_csr)


@jax.jit
def stale_walks(steps: jax.Array, touched: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """(stale bool[V, R], first_stale_hop int32[V, R]) for a touched mask."""
    V = touched.shape[0]
    visited = touched[jnp.clip(steps, 0, V - 1)] & (steps >= 0)  # [V, R, L]
    return visited.any(-1), jnp.argmax(visited, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cap",))
def _stale_ids(stale: jax.Array, t0: jax.Array, cap: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Compact the stale mask to flat walk ids [cap] (sentinel N past the
    stale count) and their first-stale hops, in one fused pass."""
    sf = stale.ravel()
    N = sf.shape[0]
    rank = jnp.cumsum(sf.astype(jnp.int32)) - 1          # id -> output slot
    ids = jnp.full((cap,), N, jnp.int32).at[
        jnp.where(sf, rank, cap)].set(jnp.arange(N, dtype=jnp.int32),
                                      mode="drop")
    t0_sel = t0.ravel()[jnp.minimum(ids, N - 1)]
    return ids, t0_sel


def _resample_impl(csr: CSRView, key: jax.Array, steps: jax.Array,
                   ids: jax.Array, t0: jax.Array, alpha: float,
                   id_offset: jax.Array = 0) -> jax.Array:
    """Re-walk the ``ids`` walks on the new graph, keeping each walk's
    prefix [0..t0]; sentinel ids scatter with mode="drop".

    ``id_offset`` shifts local walk ids into the global PRNG id space —
    a shard whose rows start at global vertex v₀ passes v₀·R so its
    draws are the ones the full-index build would have used
    (ppr/shard.py); 0 for the unsharded index.
    """
    V, R, L = steps.shape
    v = ids // R                                         # sentinel -> V
    r = jnp.minimum(ids % R, R - 1)
    rows = steps[jnp.minimum(v, V - 1), r]               # [cap, L]
    walk_keys = _walk_keys(key, (ids + id_offset).astype(jnp.uint32))
    cur0 = rows[:, 0]                                    # source vertex

    def hop(carry, t):
        cur, alive = carry
        u = _walk_draws(walk_keys, t)
        # the continue draw is graph-independent, so recomputing `alive`
        # from the walk's own stream reproduces the stored mask bitwise
        # inside the kept prefix and extends it correctly past t0
        alive = alive & (u[:, 0] < alpha)
        nxt = _transition(csr, cur, u[:, 1])
        val = jnp.where(t <= t0, rows[:, t],
                        jnp.where(alive, nxt, -1))
        cur = jnp.where(val >= 0, val, cur)
        return (cur, alive), val

    cap = ids.shape[0]
    _, tail = jax.lax.scan(hop, (cur0, jnp.ones((cap,), bool)),
                           jnp.arange(1, L, dtype=jnp.int32))
    new_rows = jnp.concatenate([cur0[None, :], tail], axis=0).T   # [cap, L]
    return steps.at[v, r].set(new_rows, mode="drop")


_resample = jax.jit(_resample_impl, static_argnames=("alpha",))


def repair_walk_index(index: WalkIndex, graph_new: EdgeListGraph,
                      touched: jax.Array, min_capacity: int = 64
                      ) -> Tuple[WalkIndex, int]:
    """Repair ``index`` (valid for Gᵗ⁻¹) into the index for ``graph_new``.

    ``touched``: bool[V] from ``touched_vertices_mask`` of the applied
    batch.  Returns (repaired index, number of walks resampled); the
    count is exactly the number of stale walks — the resample-count
    invariant bench_ppr and the tests assert.  The input index is left
    intact (see the module docstring on why no buffer donation).
    """
    V, R, L = index.steps.shape
    N = V * R
    tr = obs_trace.get_tracer()
    # ``scanned``: walks whose positions the stale scan reads (all of them)
    with tr.span("ppr.repair", scanned=N) as sp:
        csr_new = _device_csr(graph_new)
        stale, t0 = stale_walks(index.steps, touched)
        num_stale = int(jnp.sum(stale))
        if num_stale == 0:
            sp.set(stale=0)
            return dataclasses.replace(index, csr=csr_new), 0
        # pow2 capacity buckets: a stream of varying batches reuses a few
        # compiled resamplers instead of one per distinct stale count
        cap = min(N, max(min_capacity, 1 << (num_stale - 1).bit_length()))
        ids, t0_sel = _stale_ids(stale, t0, cap)
        steps = _resample(csr_new, index.key, index.steps, ids, t0_sel,
                          index.alpha)
        tr.sync(steps)
        sp.set(stale=num_stale, capacity=cap)
    return dataclasses.replace(index, steps=steps, csr=csr_new), num_stale
