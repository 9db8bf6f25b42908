"""The 95th percentile (nearest rank) of the window's ``query.device``
spans, in ms: from a query's device dispatch (for a point query, the
copy of the ranks to the host) to the host holding its answer."""
from harness.window import nearest_rank


def read(record):
    p95 = nearest_rank([s.dur for s in record.window_spans("query.device")],
                       0.95)
    return None if p95 is None else 1e3 * p95
