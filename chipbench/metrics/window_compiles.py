"""Backend compiles (built, or loaded from the persistent cache) inside
the window, from JAX's monitoring events.  Warm-up should leave none."""


def read(record):
    return record.window_compiles
