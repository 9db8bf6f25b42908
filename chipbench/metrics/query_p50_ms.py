"""Median query latency in ms, from due time to the answer on the host."""
from harness.window import nearest_rank


def read(record):
    lat = nearest_rank([q["done"] - q["due"] for q in record.queries], 0.5)
    return None if lat is None else 1e3 * lat
