"""Milliseconds per published batch of the program's ``route_update`` span:
the edge-list update, the initial frontier and the fallback check.  A host-clock span that ends in a device sync."""


def read(record):
    return record.per_batch_ms("route_update")
