"""The benchmark's generators keep the models they copy.

The temporal stream is compared with the program's own loop generator
(``graph/generators.py`` ``temporal_stream_edges``) at the 30,000-vertex
CPU scale, statistic by statistic, within sampling error, over three
seeds; and so is the quantity the serving cost follows, the share of
vertices a 64-event batch's one-hop frontier covers.
"""
import numpy as np
import pytest

from harness import gen
from harness.reference import unique_keys

N = 30_000
M = int(7_833_140 / 1_140_149 * N)
SEEDS = (0, 1, 2)


def stats(ev: np.ndarray) -> dict:
    b = gen.community_bounds(N, 64, 0.8)
    cs = np.searchsorted(b, ev[:, 0], side="right") - 1
    cd = np.searchsorted(b, ev[:, 1], side="right") - 1
    return dict(
        locality=np.mean(cs == cd),                   # dst in src's community
        stay=np.mean(cs[1:] == cs[:-1]),              # hot-community drift
        first_source=np.mean(ev[:, 0] == b[cs]),      # Zipf source skew
        sources=len(np.unique(ev[:, 0])) / N,
        community=np.bincount(cd, minlength=64) / len(ev))


def frontier_share(ev: np.ndarray, batch: int = 64) -> float:
    """Mean share of vertices in a batch's one-hop frontier (sources,
    their out-neighbours, the new destinations) after a 90% preload."""
    pre = int(0.9 * len(ev))
    keys = unique_keys(ev[:pre], N)
    src, dst = keys // N, keys % N
    ptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=N))])
    shares = []
    for i in range(pre, len(ev) - batch, batch):
        hit = np.zeros(N, bool)
        for u in np.unique(ev[i:i + batch, 0]):
            hit[u] = True
            hit[dst[ptr[u]:ptr[u + 1]]] = True
        hit[ev[i:i + batch, 1]] = True
        shares.append(hit.mean())
    return float(np.mean(shares))


@pytest.fixture(scope="module")
def streams():
    from repro.graph.generators import temporal_stream_edges
    return [(temporal_stream_edges(N, M, seed=s),
             gen.temporal_stream(N, M, s)) for s in SEEDS]


def test_temporal_stream_statistics_match_the_loop_generator(streams):
    for loop, vec in streams:
        a, b = stats(loop), stats(vec)
        assert abs(a["locality"] - b["locality"]) < 0.005
        assert abs(a["stay"] - b["stay"]) < 0.01
        assert abs(a["first_source"] - b["first_source"]) < 0.01
        assert abs(a["sources"] - b["sources"]) < 0.01
        # communities are drawn uniformly, so events per community vary
        # with the hot community's path: total variation within 0.15
        assert 0.5 * np.abs(a["community"] - b["community"]).sum() < 0.15


def test_batch_frontier_matches_the_loop_generator(streams):
    loop = [frontier_share(a) for a, _ in streams]
    vec = [frontier_share(b) for _, b in streams]
    print(f"one-hop frontier share per 64-event batch at {N} vertices: "
          f"loop generator {np.round(loop, 5)} mean {np.mean(loop):.5f}, "
          f"vectorised {np.round(vec, 5)} mean {np.mean(vec):.5f}")
    assert abs(np.mean(vec) / np.mean(loop) - 1.0) < 0.1


def test_temporal_stream_is_seeded():
    a = gen.temporal_stream(5000, 20000, 7)
    assert np.array_equal(a, gen.temporal_stream(5000, 20000, 7))
    assert not np.array_equal(a, gen.temporal_stream(5000, 20000, 8))
    assert (a[:, 0] != a[:, 1]).all() and a.min() >= 0 and a.max() < 5000


def test_kronecker_is_simple_and_permuted():
    e = gen.kronecker(12, 16, seed=3)
    n = 1 << 12
    keys = e[:, 0].astype(np.int64) * n + e[:, 1]
    assert len(np.unique(keys)) == len(keys)
    assert (e[:, 0] != e[:, 1]).all()
    assert 0.7 * 16 * n < len(e) < 16 * n       # duplicates dropped
    # without the permutation vertex 0 would hold the most edges (A is
    # the largest quadrant at every level); with it, the heaviest
    # vertex lies anywhere
    deg = np.bincount(e[:, 0], minlength=n)
    assert np.argmax(deg) != 0
    assert deg.max() > 20 * deg.mean()           # still skewed


def test_random_updates_are_80_20_and_delete_existing_edges():
    pre = gen.kronecker(10, 16, seed=1)
    n = 1 << 10
    ev, ins = gen.random_updates(pre, n, num_batches=5, batch_size=100,
                                 seed=9)
    assert ev.shape == (500, 2)
    assert (ins.reshape(5, 100).sum(axis=1) == 80).all()
    dels = unique_keys(ev[~ins], n)
    assert len(dels) == 100                      # no deletion repeats
    assert np.isin(dels, unique_keys(pre, n)).all()
    assert (ev[ins, 0] != ev[ins, 1]).all()
    same, _ = gen.random_updates(pre, n, 5, 100, seed=9)
    assert np.array_equal(ev, same)
