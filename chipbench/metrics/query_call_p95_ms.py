"""The 95th percentile (nearest rank) of the window's query calls into
the program (``query.point``, ``query.top_k`` and ``query.ppr`` spans),
in ms.  Its gap to ``query_p95_ms`` is time a query spent outside the
program: waiting for a worker thread, or for the interpreter."""
from harness.window import nearest_rank

CALLS = ("query.point", "query.top_k", "query.ppr")


def read(record):
    p95 = nearest_rank([s.dur for name in CALLS
                        for s in record.window_spans(name)], 0.95)
    return None if p95 is None else 1e3 * p95
