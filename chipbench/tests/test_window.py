"""Window arithmetic: whole batches between publishes, tails over all."""
from harness.window import freshness, nearest_rank, throughput_window


def pub(t, events, first, last):
    return dict(t=t, events=events, first_seq=first, last_seq=last)


def test_window_starts_and_ends_at_publishes():
    pubs = [pub(12.0, 64, 0, 63), pub(23.0, 64, 64, 127),
            pub(33.5, 64, 128, 191), pub(41.0, 64, 192, 255)]
    # opens at the publish at 10.0, closes at the first publish at or
    # after 10 + 20 = 30: three whole batches over 23.5 s
    events, span, batches = throughput_window(pubs, 10.0, 20.0)
    assert (events, span) == (192, 23.5)
    assert [b["t"] for b in batches] == [12.0, 23.0, 33.5]


def test_window_counts_a_publish_exactly_at_the_end():
    pubs = [pub(15.0, 10, 0, 9), pub(30.0, 7, 10, 16), pub(45.0, 5, 17, 21)]
    assert throughput_window(pubs, 10.0, 20.0)[:2] == (17, 20.0)


def test_window_not_closed_yields_none():
    assert throughput_window([pub(15.0, 10, 0, 9)], 10.0, 20.0) is None


def test_nearest_rank_is_a_value_that_occurred():
    values = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert nearest_rank(values, 0.95) == 5.0
    assert nearest_rank(values, 0.5) == 3.0
    assert nearest_rank(list(range(1, 101)), 0.95) == 95
    assert nearest_rank([], 0.95) is None


def test_freshness_counts_queue_wait_from_the_due_time():
    # events 0..5 due at 0..5 s; a batch of 0..2 published at 7, one of
    # 3..5 at 11: each event waits for the publish that covers it
    pubs = [pub(7.0, 3, 0, 2), pub(11.0, 3, 3, 5)]
    fresh = freshness([0, 1, 2, 3, 4, 5], [0, 1, 2, 3, 4, 5], pubs)
    assert fresh == [7.0, 6.0, 5.0, 8.0, 7.0, 6.0]
    assert nearest_rank(fresh, 0.95) == 8.0


def test_freshness_of_an_event_never_published_is_none():
    fresh = freshness([0.0, 1.0], [0, 1], [pub(2.0, 1, 0, 0)])
    assert fresh == [2.0, None]


def test_query_tail_is_over_every_query():
    latencies = [0.01] * 90 + [1.0] * 10
    # the slow tenth sets the 95th percentile: no query is left out
    assert nearest_rank(latencies, 0.95) == 1.0
