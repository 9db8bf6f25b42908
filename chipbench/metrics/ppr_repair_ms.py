"""Milliseconds per published batch of the program's ``ppr.repair`` span:
the walk index repair (CSR rebuild, stale scan, resample).  A host-clock span that ends in a device sync."""


def read(record):
    return record.per_batch_ms("ppr.repair")
