"""Milliseconds per published batch of the program's ``polish.f64`` span:
the float64 polish of DF-P.  A host-clock span that ends in a device sync."""


def read(record):
    return record.per_batch_ms("polish.f64")
