"""What a run leaves for the per-layer readers in ``metrics/``."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Span:
    name: str
    t0: float          # host clock (time.perf_counter), seconds
    dur: float
    args: dict


@dataclass
class Record:
    cell: str
    w0: float                      # window start and end (publish times)
    w1: float
    publishes: list                # the window's publishes, in order
    spans: list = field(default_factory=list)      # program obs spans
    queries: list = field(default_factory=list)    # answered window queries
    feed_lags: list = field(default_factory=list)  # seconds, events+queries
    window_compiles: int = 0
    trace: Optional[dict] = None   # trace.reduce() of the traced window

    def window_spans(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and self.w0 <= s.t0 <= self.w1]

    def per_batch_ms(self, name: str) -> Optional[float]:
        """Milliseconds of the ``name`` spans in the window per published
        batch; None when the program recorded no such span."""
        spans = self.window_spans(name)
        if not spans or not self.publishes:
            return None
        return 1e3 * sum(s.dur for s in spans) / len(self.publishes)
