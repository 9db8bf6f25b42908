"""The plain reference against simpler statements of the same thing."""
import numpy as np

from harness import gen
from harness import reference as ref


def sequential(preload, events, insert):
    live = {tuple(e) for e in preload.tolist()}
    for (u, v), ins in zip(events.tolist(), insert.tolist()):
        if ins:
            live.add((u, v))
        else:
            live.discard((u, v))
    return live


def test_graph_after_events_is_sequential_set_semantics():
    rng = np.random.default_rng(0)
    n = 50
    pre = rng.integers(0, n, size=(300, 2))
    pre = pre[pre[:, 0] != pre[:, 1]]
    ev = np.concatenate([pre[rng.integers(0, len(pre), 200)],
                         rng.integers(0, n, size=(200, 2))])[
        rng.permutation(400)]
    ev = ev[ev[:, 0] != ev[:, 1]]
    ins = rng.random(len(ev)) < 0.5
    keys = ref.edge_keys(ev[:, 0], ev[:, 1], n)
    preload = ref.unique_keys(pre, n)
    for upto in (0, 1, 57, len(ev)):
        want = sorted(u * n + v for u, v in
                      sequential(pre, ev[:upto], ins[:upto]))
        got = ref.keys_after(preload, keys, ins, upto)
        assert got.tolist() == want
    graph = ref.GraphAt(n, preload, keys, ins, len(ev))
    for upto in (0, 57, 200):
        a = graph.matrix(upto).toarray()
        b = ref.in_matrix(ref.keys_after(preload, keys, ins, upto),
                          n).toarray()
        assert np.array_equal(a, b)


def test_pagerank_matches_the_loop_reference():
    from repro.core.reference import static_pagerank_ref
    e = gen.kronecker(9, 8, seed=2)
    n = 1 << 9
    keys = ref.unique_keys(e, n)
    r = ref.pagerank(ref.in_matrix(keys, n), 0.85)
    want, _ = static_pagerank_ref(e[:, 0], e[:, 1], n, tol=1e-15)
    assert np.abs(r - want).sum() < 1e-12
    assert abs(r.sum() - 1.0) < 1e-12


def test_float32_control_is_a_lower_precision_answer():
    e = gen.kronecker(12, 16, seed=2)
    n = 1 << 12
    keys = ref.unique_keys(e, n)
    r64 = ref.pagerank(ref.in_matrix(keys, n), 0.85)
    r32 = ref.pagerank_lower_precision(keys, n, 0.85)
    gap = np.abs(r64 - r32).sum()
    assert 1e-9 < gap < 1e-4


def test_personalized_pagerank_of_a_sink_is_the_sink():
    n = 4
    keys = ref.edge_keys([0, 0, 1], [1, 2, 2], n)
    pi = ref.personalized(ref.in_matrix(keys, n), np.array([2, 0]), 0.85)
    assert np.allclose(pi[:, 0], [0, 0, 1, 0])
    assert abs(pi[:, 1].sum() - 1.0) < 1e-6 and pi[0, 1] > pi[3, 1]
    assert ref.top_mass_gap(pi[:, 1], np.array([0, 1, 2]), 3) < 1e-12
    assert ref.top_mass_gap(pi[:, 1], np.array([3]), 1) == 1.0


def test_walk_counts():
    n = 4
    final = ref.edge_keys([0, 1, 2, 0], [1, 2, 0, 3], n)
    first = ref.edge_keys([0, 1, 2], [1, 2, 0], n)   # 0 -> 3 is new
    steps = np.full((4, 2, 4), -1, np.int32)
    steps[0, 0] = [0, 1, 2, 2]        # edges and the self-loop
    steps[0, 1] = [0, 3, -1, -1]      # the new edge
    steps[1, 0] = [1, 2, 0, 3]
    steps[1, 1] = [1, -1, -1, -1]
    steps[2, 0] = [2, 0, 0, -1]
    steps[2, 1] = [2, -1, -1, -1]
    steps[3, 0] = [3, -1, -1, -1]
    steps[3, 1] = [3, 3, -1, -1]
    w = ref.walk_counts(steps, np.sort(final), np.sort(first), n, block=3)
    assert (w["bad_source"], w["bad_end"], w["off_graph"]) == (0, 0, 0)
    assert w["on_new"] == 2 and w["new_edges"] == 1
    # hops from 0 (out-degree 2 + self-loop) pick 0 -> 3 with chance 1/3
    hops_from_0 = 4          # 0->1, 0->3, 0->3 (from walk 1, 0), 0->0
    assert abs(w["expected_new"] - hops_from_0 / 3) < 1e-12
    broken = steps.copy()
    broken[3, 1] = [2, 1, -1, 3]      # wrong source, 2->1 no edge, resumes
    w = ref.walk_counts(broken, np.sort(final), np.sort(first), n)
    assert (w["bad_source"], w["off_graph"], w["bad_end"]) == (1, 1, 1)
