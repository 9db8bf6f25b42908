"""How late the event and query generators ran: the 95th percentile of
(submit time - due time) over the window's events and queries, in ms."""
from harness.window import nearest_rank


def read(record):
    lag = nearest_rank(record.feed_lags, 0.95)
    return None if lag is None else 1e3 * lag
