"""Milliseconds per published batch of the program's ``serve.step`` span:
the whole serve step (poll, update, solve, repair, publish).  A host-clock span that ends in a device sync."""


def read(record):
    return record.per_batch_ms("serve.step")
