"""Seeded data for the benchmark's deployments, made in bulk on the host.

Two generators, each a vectorised copy of a model the program's own
tests use (``graph/generators.py``), kept here so that no later change
to the program can move the benchmark's data:

* ``temporal_stream``: the localised temporal stream of
  ``temporal_stream_edges`` (Zipf-sized communities, a drifting hot
  community, Zipf-skewed sources), drawn in whole arrays instead of one
  Python iteration per event.
* ``kronecker``: the Graph500 Kronecker generator (initiator A, B, C,
  D = 1 - A - B - C) with Graph500's random vertex-label permutation;
  self-loops and duplicate edges are dropped, as LDBC Graphalytics does.

``random_updates`` builds the DF* paper's §5.2.2 update stream: each
batch is 80% uniformly random insertions and 20% deletions of existing
edges, the deletions drawn without replacement from the preload.
"""
from __future__ import annotations

import numpy as np


def community_bounds(n: int, n_communities: int, size_exponent: float
                     ) -> np.ndarray:
    """int64[C + 1] vertex-range bounds of Zipf-sized communities."""
    sizes = 1.0 / np.arange(1, n_communities + 1) ** size_exponent
    bounds = np.concatenate([[0], np.cumsum(sizes / sizes.sum())]) * n
    bounds = bounds.astype(np.int64)
    bounds[-1] = n
    return bounds


def temporal_stream(n: int, m: int, seed: int, *, locality: float = 0.9,
                    n_communities: int = 64, drift: float = 0.02,
                    source_zipf: float = 1.6, size_exponent: float = 0.8
                    ) -> np.ndarray:
    """int32[m, 2] timestamp-ordered (src, dst) events.

    Per event: with probability ``drift`` the hot community moves to a
    uniformly drawn one; the source community is the hot one with
    probability ``locality`` (else uniform); the source is the
    ``Zipf(source_zipf)``-th vertex of that community (capped at its
    size); the destination community is the source's with probability
    ``locality`` (else uniform) and the destination uniform in it,
    moved one vertex on where it would equal the source.
    """
    rng = np.random.default_rng(seed)
    bounds = community_bounds(n, n_communities, size_exponent)
    lo = bounds[:-1]
    size = np.maximum(bounds[1:] - lo, 1)
    moved = rng.random(m) < drift
    moved_to = rng.integers(0, n_communities, size=m)
    last_move = np.where(moved, np.arange(m), -1)
    np.maximum.accumulate(last_move, out=last_move)
    hot = np.where(last_move >= 0, moved_to[np.maximum(last_move, 0)],
                   rng.integers(0, n_communities))
    c = np.where(rng.random(m) < locality, hot,
                 rng.integers(0, n_communities, size=m))
    rank = rng.zipf(source_zipf, size=m)
    src = lo[c] + np.minimum(rank - 1, size[c] - 1)
    c2 = np.where(rng.random(m) < locality, c,
                  rng.integers(0, n_communities, size=m))
    dst = lo[c2] + np.minimum((rng.random(m) * size[c2]).astype(np.int64),
                              size[c2] - 1)
    clash = dst == src
    dst[clash] = lo[c2[clash]] + (src[clash] + 1 - lo[c2[clash]]) \
        % size[c2[clash]]
    return np.stack([src, dst], 1).astype(np.int32)


def kronecker(scale: int, edge_factor: int, seed: int, *, a: float = 0.57,
              b: float = 0.19, c: float = 0.19) -> np.ndarray:
    """int32[E, 2] unique (src, dst) edges, no self-loops, of a Graph500
    Kronecker graph on 2**scale vertices from edge_factor * 2**scale
    draws, vertex labels permuted uniformly at random."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        # one draw picks the quadrant: [0,A) (0,0), [A,A+B) (0,1),
        # [A+B,A+B+C) (1,0), [A+B+C,1) (1,1)
        u = rng.random(m, dtype=np.float32)
        src <<= 1
        src |= u >= a + b
        dst <<= 1
        dst |= ((u >= a) & (u < a + b)) | (u >= a + b + c)
    perm = rng.permutation(n)
    keys = perm[src] * n + perm[dst]
    keys.sort()
    first = np.empty(len(keys), bool)
    first[0] = True
    np.not_equal(keys[1:], keys[:-1], out=first[1:])
    keys = keys[first]
    src, dst = keys // n, keys % n
    keep = src != dst
    return np.stack([src[keep], dst[keep]], 1).astype(np.int32)


def random_updates(preload: np.ndarray, n: int, num_batches: int,
                   batch_size: int, seed: int, frac_insert: float = 0.8
                   ) -> tuple[np.ndarray, np.ndarray]:
    """(int32[F, 2] events, bool[F] is_insert), F = num_batches *
    batch_size, in batch order: each consecutive ``batch_size`` events
    hold ``round(frac_insert * batch_size)`` uniformly random insertions
    (no self-loops) and deletions of preloaded edges, shuffled together.
    Deletions never repeat an edge."""
    rng = np.random.default_rng(seed)
    n_ins = int(round(batch_size * frac_insert))
    n_del = batch_size - n_ins
    ins = rng.integers(0, n, size=(num_batches * n_ins, 2), dtype=np.int64)
    clash = ins[:, 0] == ins[:, 1]
    ins[clash, 1] = (ins[clash, 1] + 1) % n
    picks = rng.choice(len(preload), size=num_batches * n_del, replace=False)
    dels = preload[picks].astype(np.int64)
    ev = np.concatenate([ins.reshape(num_batches, n_ins, 2),
                         dels.reshape(num_batches, n_del, 2)], axis=1)
    kind = np.concatenate([np.ones((num_batches, n_ins), bool),
                           np.zeros((num_batches, n_del), bool)], axis=1)
    order = np.argsort(rng.random((num_batches, batch_size)), axis=1)
    ev = np.take_along_axis(ev, order[:, :, None], axis=1)
    kind = np.take_along_axis(kind, order, axis=1)
    return ev.reshape(-1, 2).astype(np.int32), kind.reshape(-1)
