"""The correctness check catches a broken timed path.

Each test drives a whole run of a small copy of a cell on the CPU (the
look for a chip skipped), with one fault planted in the program
underneath, and sees ``correct`` come out false; one sound run sees it
true.  The faults are the ones these cells can have: a step that
publishes its state unchanged, half of each batch left out, an answer
or a rank altered where it is produced, and the walk repair skipped.
(The exchange between chips does not exist on one chip.)
"""
import numpy as np
import pytest

import run as chipbench_run


@pytest.fixture
def serve(tiny):
    """One run of a cell of the committed benchmark at CPU-test size."""
    root, here = tiny

    def run(workload: str, seed: int = 41, control: bool = False) -> dict:
        return chipbench_run.run_cell(workload, seed, 3.0, False, control,
                                      require_tpu=False, root=root,
                                      here=here)
    return run


def failing(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct(serve):
    result = serve("wiki-talk.paced")
    assert result["correct"], result["checks"]
    assert set(result["checks"]) >= {"graph_diff", "rank_l1", "query_diff",
                                     "ppr_gap", "walk_bad",
                                     "walk_new_deficit", "unanswered"}


def test_step_that_publishes_its_state_unchanged(monkeypatch, serve):
    from repro.serve.state import RankStore
    publish = RankStore.publish

    def unchanged(self, graph, ranks, last_seq, ppr_index=None):
        if self._snap is not None and self._snap.generation >= 3:
            old = self._snap
            graph, ranks, ppr_index = old.graph, old.ranks, old.ppr_index
        return publish(self, graph, ranks, last_seq, ppr_index)

    monkeypatch.setattr(RankStore, "publish", unchanged)
    result = serve("wiki-talk.paced")
    assert not result["correct"]
    assert {"graph_diff", "rank_l1"} <= failing(result)


def test_half_of_each_batch_left_out(monkeypatch, serve):
    from repro.serve import ingest
    coalesce = ingest.coalesce_events

    def half(events, del_capacity, ins_capacity):
        batch = coalesce(events[::2], del_capacity, ins_capacity)
        return batch._replace(num_events=len(events),
                              last_seq=events[-1].seq)

    monkeypatch.setattr(ingest, "coalesce_events", half)
    result = serve("graph500-s19.backlog")
    assert not result["correct"]
    assert "graph_diff" in failing(result)


def test_rank_altered_where_it_is_produced(monkeypatch, serve):
    from repro.core import kernel_engine
    solve = kernel_engine.fused_hybrid_pagerank

    def altered(*a, **kw):
        packed, res = solve(*a, **kw)
        return packed, res._replace(ranks=res.ranks.at[0].multiply(1.5))

    monkeypatch.setattr(kernel_engine, "fused_hybrid_pagerank", altered)
    result = serve("wiki-talk.backlog")
    assert not result["correct"]
    assert "rank_l1" in failing(result)


@pytest.mark.parametrize("kind", ["top", "ppr"])
def test_answer_altered_where_it_is_produced(monkeypatch, serve, kind):
    from repro.serve.query import QueryClient
    name = "top_k" if kind == "top" else "personalized_top_k"
    query = getattr(QueryClient, name)

    def altered(self, *a, **kw):
        r = query(self, *a, **kw)
        return r._replace(vertices=np.roll(r.vertices, 1) + 1)

    monkeypatch.setattr(QueryClient, name, altered)
    result = serve("wiki-talk.paced")
    assert not result["correct"]
    assert ("query_diff" if kind == "top" else "ppr_gap") in failing(result)


def test_walk_repair_skipped(monkeypatch, serve):
    from repro.serve import engine

    monkeypatch.setattr(engine, "repair_walk_index",
                        lambda index, graph, touched: (index, 0))
    result = serve("wiki-talk.backlog")
    assert not result["correct"]
    assert "walk_new_deficit" in failing(result)


def test_control_fails_the_rank_limit(serve):
    """The control at this size: DF-P on the program's float32 path
    alone, without its float64 polish, reads over the rank limit."""
    result = serve("wiki-talk.backlog", control=True)
    assert not result["correct"]
    assert failing(result) == {"rank_l1"}
