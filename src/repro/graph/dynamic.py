"""Dynamic-graph batch updates as pure jit-able functions (paper §3.2).

A batch update Δᵗ = (Δᵗ⁻ deletions, Δᵗ⁺ insertions) transforms Gᵗ⁻¹ → Gᵗ.
Updates are themselves capacity-padded so one compiled ``apply_batch`` serves
every batch of a temporal stream (paper applies 100 consecutive batches).

Semantics match the paper:
  * deletion (u, v): mark matching live slot invalid (no-op if absent);
  * insertion (u, v): claim a free slot (no-op duplicate insert is prevented
    by callers using `dedup_insertions`, matching the paper's static-edge
    dedup); vertices are never added/removed;
  * self-loops are implicit (graph/structure.py) so update batches never
    carry them.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import EdgeListGraph


class BatchUpdate(NamedTuple):
    """Padded edge-update batch. Invalid rows carry count-mask False."""

    del_src: jax.Array   # int32[D_cap]
    del_dst: jax.Array   # int32[D_cap]
    del_mask: jax.Array  # bool[D_cap]
    ins_src: jax.Array   # int32[I_cap]
    ins_dst: jax.Array   # int32[I_cap]
    ins_mask: jax.Array  # bool[I_cap]


def make_batch_update(deletions: np.ndarray, insertions: np.ndarray,
                      del_capacity: int, ins_capacity: int) -> BatchUpdate:
    """Host-side helper: (k,2) int arrays -> padded BatchUpdate."""
    deletions = np.asarray(deletions, np.int32).reshape(-1, 2)
    insertions = np.asarray(insertions, np.int32).reshape(-1, 2)
    nd, ni = len(deletions), len(insertions)
    if nd > del_capacity or ni > ins_capacity:
        raise ValueError("update exceeds capacity")

    def pad(a, cap):
        out = np.zeros((cap,), np.int32)
        out[: len(a)] = a
        return jnp.asarray(out)

    mask = lambda n, cap: jnp.asarray(np.arange(cap) < n)
    return BatchUpdate(
        del_src=pad(deletions[:, 0], del_capacity),
        del_dst=pad(deletions[:, 1], del_capacity),
        del_mask=mask(nd, del_capacity),
        ins_src=pad(insertions[:, 0], ins_capacity),
        ins_dst=pad(insertions[:, 1], ins_capacity),
        ins_mask=mask(ni, ins_capacity),
    )


def _edge_key(src: jax.Array, dst: jax.Array, num_vertices: int) -> jax.Array:
    return src.astype(jnp.int64) * num_vertices + dst.astype(jnp.int64)


@jax.jit
def apply_batch(graph: EdgeListGraph, update: BatchUpdate) -> EdgeListGraph:
    """Pure function Gᵗ⁻¹, Δᵗ → Gᵗ.  O(E_cap·log|Δ| + |Δ|·log|Δ|) with
    static shapes: nothing sorts the edge list.

    Deletions and already-present insertions: membership test via
    sorted-key binary search over the *batch* (small), applied to every
    live slot.  Insertions: claim the first |Δ⁺| free slots via a
    cumulative-sum compaction.
    """
    V = graph.num_vertices
    # ---- deletions -------------------------------------------------------
    live_key = _edge_key(graph.src, graph.dst, V)
    del_key = jnp.where(
        update.del_mask, _edge_key(update.del_src, update.del_dst, V), -1)
    del_sorted = jnp.sort(del_key)
    pos = jnp.searchsorted(del_sorted, live_key)
    pos = jnp.clip(pos, 0, del_sorted.shape[0] - 1)
    is_deleted = (del_sorted[pos] == live_key) & graph.valid
    valid = graph.valid & ~is_deleted

    # ---- insertions ------------------------------------------------------
    # Skip inserts that already exist (paper's graphs are simple digraphs)
    # and de-dup within the batch: sort the (small) batch, not the edge
    # list, and look every live slot up in it.
    ins_key = jnp.where(update.ins_mask,
                        _edge_key(update.ins_src, update.ins_dst, V), -1)
    order = jnp.argsort(ins_key)
    ins_sorted_key = ins_key[order]
    first_occurrence = jnp.concatenate(
        [jnp.array([True]), ins_sorted_key[1:] != ins_sorted_key[:-1]])
    run = jnp.cumsum(first_occurrence.astype(jnp.int32)) - 1
    live_key_after = jnp.where(valid, live_key, -2)
    lpos = jnp.clip(jnp.searchsorted(ins_sorted_key, live_key_after), 0,
                    ins_sorted_key.shape[0] - 1)
    hit = ins_sorted_key[lpos] == live_key_after
    # a live hit lands on the first key of its run; spread it to the run
    already = jnp.zeros_like(update.ins_mask).at[run[lpos]].max(hit)[run]
    # map back: a key is kept iff it is the first among equals
    keep_sorted = first_occurrence & (ins_sorted_key >= 0) & ~already
    ins_mask = jnp.zeros_like(update.ins_mask).at[order].set(keep_sorted)

    # free-slot compaction: i-th masked insertion -> i-th free slot
    free = ~valid
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1           # rank per slot
    ins_rank = jnp.cumsum(ins_mask.astype(jnp.int32)) - 1        # rank per ins
    # slot index of the k-th free slot:
    E_cap = graph.edge_capacity
    slot_of_rank = jnp.full((E_cap,), E_cap, jnp.int32).at[
        jnp.where(free, free_rank, E_cap)].min(jnp.arange(E_cap, dtype=jnp.int32))
    target = jnp.where(ins_mask, slot_of_rank[jnp.clip(ins_rank, 0, E_cap - 1)],
                       E_cap)  # E_cap = drop (out of bounds)
    src = graph.src.at[target].set(update.ins_src, mode="drop")
    dst = graph.dst.at[target].set(update.ins_dst, mode="drop")
    new_valid = valid.at[target].set(True, mode="drop")
    num_edges = jnp.sum(new_valid.astype(jnp.int32))
    return dataclasses.replace(
        graph, src=src, dst=dst, valid=new_valid, num_edges=num_edges)


def touched_vertices_mask(update: BatchUpdate, num_vertices: int) -> jax.Array:
    """bool[V]: u-endpoints of every edge in Δ — seeds for frontier marking."""
    m = jnp.zeros((num_vertices,), bool)
    m = m.at[jnp.where(update.del_mask, update.del_src, 0)].max(
        update.del_mask)
    m = m.at[jnp.where(update.ins_mask, update.ins_src, 0)].max(
        update.ins_mask)
    return m
