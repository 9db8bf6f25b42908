"""The plain reference: what the served graph, ranks, walks and answers
must be, worked out from the benchmark's own event log.

Nothing here imports the program or reads anything it made: the graph
at any point of the stream follows from the preload and the events in
seq order (an insertion adds an absent edge, a deletion removes a
present one); PageRank and personalized PageRank are plain power
iterations over that graph with the implicit self-loop of the DF*
paper (out-degree + 1); the walk statistics need only the graph.

Edges travel as sorted unique int64 keys ``src * n + dst``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

REF_TOL_L1 = 1e-14   # stop when an iteration changes the ranks by less
REF_MAX_ITER = 1000


# ---- graph ------------------------------------------------------------------

def edge_keys(src, dst, n: int) -> np.ndarray:
    return np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)


def unique_keys(edges: np.ndarray, n: int) -> np.ndarray:
    return np.unique(edge_keys(edges[:, 0], edges[:, 1], n))


def _last_event(keys: np.ndarray, insert: np.ndarray):
    """(sorted unique keys, whether each is present after its last event)."""
    uniq, idx = np.unique(keys[::-1], return_index=True)
    return uniq, insert[::-1][idx]


def keys_after(preload: np.ndarray, ev_keys: np.ndarray,
               ev_insert: np.ndarray, upto: int) -> np.ndarray:
    """Sorted keys of the graph after events [0, upto) on ``preload``."""
    touched, present = _last_event(ev_keys[:upto], ev_insert[:upto])
    base = preload[~np.isin(preload, touched, assume_unique=True)]
    return np.union1d(base, touched[present])


def presence(keys: np.ndarray, preload: np.ndarray, ev_keys: np.ndarray,
             ev_insert: np.ndarray, upto: int) -> np.ndarray:
    """bool per (sorted unique) key: is it an edge after events [0, upto)?"""
    sel = np.isin(ev_keys[:upto], keys)
    touched, present = _last_event(ev_keys[:upto][sel], ev_insert[:upto][sel])
    out = np.isin(keys, preload, assume_unique=True)
    hit = np.searchsorted(keys, touched)
    out[hit] = present
    return out


def in_matrix(keys: np.ndarray, n: int) -> sp.csr_matrix:
    """A[v, u] = 1 for each edge u -> v."""
    src, dst = keys // n, keys % n
    return sp.csr_matrix((np.ones(len(keys)), (dst, src)), shape=(n, n))


class GraphAt:
    """The graph after the last event of each publish, as the final
    graph plus the few edges that differ (they are the later events)."""

    def __init__(self, n: int, preload: np.ndarray, ev_keys: np.ndarray,
                 ev_insert: np.ndarray, final_upto: int):
        self.n = n
        self.preload = preload
        self.ev_keys = ev_keys
        self.ev_insert = ev_insert
        self.final_upto = final_upto
        self.final_keys = keys_after(preload, ev_keys, ev_insert, final_upto)
        self.final_matrix = in_matrix(self.final_keys, n)

    def matrix(self, upto: int) -> sp.csr_matrix:
        if upto == self.final_upto:
            return self.final_matrix
        later = np.unique(self.ev_keys[upto:self.final_upto])
        then = presence(later, self.preload, self.ev_keys, self.ev_insert,
                        upto)
        now = presence(later, self.preload, self.ev_keys, self.ev_insert,
                       self.final_upto)
        return (self.final_matrix - in_matrix(later[now & ~then], self.n)
                + in_matrix(later[then & ~now], self.n)).tocsr()


# ---- ranks ------------------------------------------------------------------

def pagerank(a: sp.csr_matrix, alpha: float, x0=None) -> np.ndarray:
    """f64 PageRank with the implicit self-loop, to L1 change REF_TOL_L1."""
    n = a.shape[0]
    deg = np.asarray(a.sum(axis=0)).ravel() + 1.0
    r = np.full(n, 1.0 / n) if x0 is None else np.array(x0, np.float64)
    c0 = (1.0 - alpha) / n
    for _ in range(REF_MAX_ITER):
        y = r / deg
        r_new = c0 + alpha * (a @ y + y)
        delta = np.abs(r_new - r).sum()
        r = r_new
        if delta <= REF_TOL_L1:
            break
    return r


@partial(jax.jit, static_argnames=("n", "alpha", "dtype"))
def _pagerank_device(src, dst, n: int, alpha: float, dtype):
    deg = jax.ops.segment_sum(jnp.ones(src.shape, dtype), src,
                              num_segments=n) + 1
    c0 = jnp.asarray((1.0 - alpha) / n, dtype)

    def body(state):
        r, _, it = state
        y = r / deg
        r_new = c0 + alpha * (jax.ops.segment_sum(y[src], dst,
                                                  num_segments=n) + y)
        return r_new, jnp.sum(jnp.abs(r_new - r)), it + 1

    def cond(state):
        _, delta, it = state
        return (delta > REF_TOL_L1) & (it < REF_MAX_ITER)

    r0 = jnp.full((n,), 1.0 / n, dtype)
    r, _, _ = jax.lax.while_loop(
        cond, body, (r0, jnp.asarray(jnp.inf, dtype), 0))
    return r


def pagerank_lower_precision(keys: np.ndarray, n: int, alpha: float
                             ) -> np.ndarray:
    """The control: the same power iteration computed in float32, one
    precision below the float64 the deployment states, on the device."""
    src = jnp.asarray((keys // n).astype(np.int32))
    dst = jnp.asarray((keys % n).astype(np.int32))
    r = _pagerank_device(src, dst, n, alpha, jnp.float32)
    return np.asarray(r).astype(np.float64)


def personalized(a: sp.csr_matrix, seeds: np.ndarray, alpha: float,
                 tol: float = 1e-8) -> np.ndarray:
    """f64 [n, len(seeds)] PPR vectors, one seed each, teleporting to the
    seed, with the implicit self-loop; each to an L1 change of ``tol``."""
    n = a.shape[0]
    deg = np.asarray(a.sum(axis=0)).ravel() + 1.0
    out = np.zeros((n, len(seeds)))
    for j, s in enumerate(seeds):
        pi = np.zeros(n)
        pi[s] = 1.0 - alpha
        for _ in range(REF_MAX_ITER):
            y = pi / deg
            new = alpha * (a @ y + y)
            new[s] += 1.0 - alpha
            delta = np.abs(new - pi).sum()
            pi = new
            if delta <= tol:
                break
        out[:, j] = pi
    return out


def top_mass_gap(pi: np.ndarray, answer: np.ndarray, k: int) -> float:
    """1 - (PPR mass on the answered vertices) / (mass on the true top k)."""
    best = np.sort(pi)[-k:].sum()
    got = pi[np.unique(answer)].sum()
    return float(max(0.0, 1.0 - got / best))


# ---- walk index -------------------------------------------------------------

def walk_counts(steps, final_keys: np.ndarray, first_keys: np.ndarray,
                n: int, block: int = 1 << 16) -> dict:
    """Checks of a walk array [V, R, L] (a vertex per position, -1 once
    the walk ended) against the graph: each walk starts at its vertex,
    stays ended once ended, and moves only along edges or the self-loop;
    and its hops along edges inserted since the walks were first drawn
    (``first_keys``' graph) against what walks drawn on the current
    graph would make in expectation, the sum over hops from u of
    new_out(u) / (out(u) + 1)."""
    steps = np.asarray(steps)
    new = np.setdiff1d(final_keys, first_keys, assume_unique=True)
    deg = np.bincount(final_keys // n, minlength=n) + 1.0
    new_frac = np.bincount(new // n, minlength=n) / deg
    bad_source = bad_end = off_graph = on_new = 0
    expected = 0.0
    for v0 in range(0, steps.shape[0], block):
        blk = steps[v0:v0 + block]
        rows = np.arange(v0, v0 + blk.shape[0])
        bad_source += int(np.sum(blk[:, :, 0] != rows[:, None]))
        cur, nxt = blk[:, :, :-1], blk[:, :, 1:]
        bad_end += int(np.sum((cur < 0) & (nxt >= 0)))
        hop = (cur >= 0) & (nxt >= 0)
        expected += float(new_frac[cur[hop]].sum())
        moved = hop & (nxt != cur)
        key = cur[moved].astype(np.int64) * n + nxt[moved]
        off_graph += int(np.sum(~_member(final_keys, key)))
        on_new += int(np.sum(_member(new, key)))
    return dict(bad_source=bad_source, bad_end=bad_end, off_graph=off_graph,
                on_new=on_new, expected_new=expected, new_edges=int(len(new)))


def _member(sorted_keys: np.ndarray, key: np.ndarray) -> np.ndarray:
    if not len(sorted_keys):
        return np.zeros(key.shape, bool)
    pos = np.minimum(np.searchsorted(sorted_keys, key), len(sorted_keys) - 1)
    return sorted_keys[pos] == key
