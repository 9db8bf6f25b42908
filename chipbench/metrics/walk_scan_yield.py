"""Useful share of the walk repair's stale scan: walks found stale over
walks whose positions the scan read, summed over the window's
``ppr.repair`` spans (their ``stale`` and ``scanned`` arguments)."""


def read(record):
    spans = [s for s in record.window_spans("ppr.repair")
             if "scanned" in s.args]
    scanned = sum(s.args["scanned"] for s in spans)
    if not scanned:
        return None
    return sum(s.args["stale"] for s in spans) / scanned
