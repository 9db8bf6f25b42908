"""How long a batch's oldest event waited in the ingest queue before the
batch was taken: the mean of the ``oldest_wait_s`` argument over the
window's ``ingest.coalesce`` spans, in ms (the queue's own clock)."""


def read(record):
    waits = [s.args["oldest_wait_s"]
             for s in record.window_spans("ingest.coalesce")
             if "oldest_wait_s" in s.args]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
