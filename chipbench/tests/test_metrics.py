"""Readers of the program's span arguments and query spans, on hand-made
records: the value, clipping to the window, and no reading where the
program recorded no such span (or, as an older program, no such
argument)."""
import pytest

from harness import registry
from harness.record import Record, Span

W0, W1 = 10.0, 20.0


def record(spans):
    return Record("x", W0, W1, [{"events": 1}], spans=spans)


def read(name, spans):
    return registry.metric_reader(name)(record(spans))


def test_ingest_wait_is_the_mean_oldest_wait_in_the_window():
    spans = [Span("ingest.coalesce", 9.0, 0.001, dict(oldest_wait_s=50.0)),
             Span("ingest.coalesce", 11.0, 0.001, dict(oldest_wait_s=6.0)),
             Span("ingest.coalesce", 17.0, 0.001, dict(oldest_wait_s=7.0)),
             Span("ingest.coalesce", 21.0, 0.001, dict(oldest_wait_s=90.0))]
    assert read("ingest_wait_ms.fresh", spans) == pytest.approx(6500.0)


def test_query_device_p95_by_nearest_rank_in_the_window():
    spans = [Span("query.device", 10.0 + i * 0.1, (i + 1) * 1e-3,
                  dict(qid=i)) for i in range(20)]
    spans += [Span("query.device", 5.0, 9.0, dict(qid=99)),
              Span("query.point", 12.0, 1.0, dict(qid=100))]
    # 20 in the window: the 19th smallest of 1..20 ms
    assert read("query_device_p95_ms", spans) == pytest.approx(19.0)


def test_query_call_p95_over_every_kind_of_call():
    spans = [Span(name, 11.0, dur, {}) for name, dur in
             [("query.point", 0.001), ("query.top_k", 0.002),
              ("query.ppr", 0.004), ("query.ppr", 0.003)]]
    spans += [Span("query.device", 11.0, 5.0, {}),
              Span("query.point", 25.0, 8.0, {})]
    # nearest rank of 4 at 0.95 is the largest
    assert read("query_call_p95_ms", spans) == pytest.approx(4.0)


@pytest.mark.parametrize("suffix", ["fresh", "tput"])
def test_polish_sweeps_is_the_mean_per_polish(suffix):
    spans = [Span("polish.f64", 12.0, 2.0, dict(sweeps=40)),
             Span("polish.f64", 15.0, 2.0, dict(sweeps=45)),
             Span("polish.f64", 30.0, 2.0, dict(sweeps=500)),
             Span("fused_update_loop", 12.0, 1.0, dict(sweeps=9))]
    assert read(f"polish_sweeps.{suffix}", spans) == pytest.approx(42.5)


@pytest.mark.parametrize("suffix", ["fresh", "tput"])
def test_walk_scan_yield_is_stale_over_scanned(suffix):
    spans = [Span("ppr.repair", 12.0, 3.0,
                  dict(stale=500, scanned=1000000, capacity=512)),
             Span("ppr.repair", 16.0, 3.0, dict(stale=0, scanned=1000000)),
             Span("ppr.repair", 1.0, 3.0, dict(stale=9e5, scanned=1e6))]
    assert read(f"walk_scan_yield.{suffix}", spans) == \
        pytest.approx(500 / 2e6)


NEW = ("ingest_wait_ms.fresh", "query_device_p95_ms", "query_call_p95_ms",
       "polish_sweeps.tput", "walk_scan_yield.tput")


@pytest.mark.parametrize("name", NEW)
def test_no_reading_without_the_span(name):
    assert read(name, []) is None
    # the span only outside the window
    outside = [Span(n, 30.0, 1.0, dict(oldest_wait_s=1.0, sweeps=3,
                                       stale=1, scanned=4))
               for n in ("ingest.coalesce", "query.device", "query.ppr",
                         "polish.f64", "ppr.repair")]
    assert read(name, outside) is None


@pytest.mark.parametrize("name", ["ingest_wait_ms.fresh",
                                  "polish_sweeps.fresh",
                                  "walk_scan_yield.fresh"])
def test_no_reading_from_spans_without_the_argument(name):
    # the spans as a program without these counters records them
    spans = [Span("ingest.coalesce", 12.0, 0.001,
                  dict(events=50, coalesced=0)),
             Span("polish.f64", 12.0, 2.0, dict(program="xla_polish")),
             Span("ppr.repair", 12.0, 3.0, dict(stale=500, capacity=512))]
    assert read(name, spans) is None
