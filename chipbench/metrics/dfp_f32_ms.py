"""Milliseconds per published batch of the program's ``fused_update_loop`` span:
the packed update and the float32 kernel loop of DF-P.  A host-clock span that ends in a device sync."""


def read(record):
    return record.per_batch_ms("fused_update_loop")
