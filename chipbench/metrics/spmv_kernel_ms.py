"""Device milliseconds of the ``frontier_spmv`` Pallas kernel per
published batch, summed over its events in the profiler trace."""


def read(record):
    if record.trace is None or not record.publishes:
        return None
    seconds = record.trace["kernels"].get("frontier_spmv")
    if not seconds:
        return None
    return 1e3 * seconds / len(record.publishes)
