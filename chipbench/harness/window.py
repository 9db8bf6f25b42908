"""Window arithmetic: which batches, events and queries a run measures.

Times are host-clock seconds (``time.perf_counter``).  A publish is a
dict with ``t`` (when ``RankStore.publish`` returned), ``events``,
``first_seq`` and ``last_seq`` (the ingest seqs its batch covered).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-quantile by the nearest-rank rule: the ceil(q * n)-th
    smallest value, one that some event or query really had."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def throughput_window(publishes: list, w0: float, seconds: float):
    """(events, span_s, batches) of a window that starts at the publish
    at ``w0`` and ends at the first publish at or after ``w0 + seconds``;
    every event of the batches published after ``w0`` up to that end
    counts.  None while no publish has reached the end."""
    batches = [p for p in publishes if p["t"] > w0]
    for i, p in enumerate(batches):
        if p["t"] >= w0 + seconds:
            inside = batches[:i + 1]
            return sum(b["events"] for b in inside), p["t"] - w0, inside
    return None


def covering_publish_times(seqs: Sequence[int], publishes: list
                           ) -> list:
    """For each seq, the time of the first publish whose batch covers it
    (``last_seq >= seq``), or None if none did.  ``publishes`` in order."""
    res = [None] * len(seqs)
    j = 0
    for i in sorted(range(len(seqs)), key=lambda i: seqs[i]):
        while j < len(publishes) and publishes[j]["last_seq"] < seqs[i]:
            j += 1
        if j < len(publishes):
            res[i] = publishes[j]["t"]
    return res


def freshness(due: Sequence[float], seqs: Sequence[int], publishes: list
              ) -> list:
    """Seconds from each event's due time to the publish that covers it;
    None for an event no publish covered."""
    return [None if t is None else t - d
            for d, t in zip(due, covering_publish_times(seqs, publishes))]
