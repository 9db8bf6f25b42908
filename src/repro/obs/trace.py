"""Low-overhead span tracing for the serving stack (`repro.obs`).

One ``Tracer`` holds a bounded, thread-safe ring buffer of *complete*
spans (name, start, duration, thread, args) recorded against a
monotonic clock.  The API is deliberately tiny:

  * ``with tracer.span("polish.f64", program=p) as sp: ...;
    sp.set(sweeps=n)`` — a context-manager span; ``set`` adds arguments
    known only once the work is done (counts read after the span's
    device sync).  A span's parent is the span that contains it on the
    same thread: each thread's spans land on its own Chrome-trace track
    and nest by interval containment, the format's native rule;
  * ``@traced("name")`` — decorator form of the same;
  * ``tracer.record(name, t0, dur, **args)`` — an interval measured by
    the caller on the tracer's clock;
  * ``tracer.instant(name, **args)`` — a point annotation;
  * ``tracer.sync(x)`` — ``jax.block_until_ready`` *only when tracing
    is enabled*, so device-program boundaries get honest durations
    without perturbing the untraced hot path.

Where one request crosses several spans, the spans carry a shared
identifier in their args rather than a parent pointer: ``qid`` for a
query (``query.*`` and its ``query.device`` child), ``first_seq``/
``last_seq`` for an ingest batch (``ingest.coalesce`` and the
``serve.step`` that serves it).

While the tracer is enabled, each live span also enters
``jax.profiler.TraceAnnotation(name)``: a running profiler session
(``jax.profiler.trace(dir)``, or the benchmark's) then holds the
program's spans as host events on its own clock, beside the device
ops, with no flag of its own.  ``record``ed intervals and instants stay
in the ring buffer only.

Disabled tracers are free: ``span`` returns a shared no-op context
manager (whose ``set`` does nothing), nothing is allocated, nothing is
locked, no profiler annotation is entered, and — critically — nothing
forces a device sync, so with tracing off the serving hot path runs
the same device-program schedule (tests assert the trace counters and
``device_programs_per_batch`` are unchanged).

Export is Chrome trace format (the JSON array-of-events flavour):
``to_chrome()`` returns ``{"traceEvents": [...]}`` with complete-event
(``"ph": "X"``) records carrying ``name``/``ts``/``dur``/``pid``/
``tid``/``args`` in microseconds — loadable in ``chrome://tracing`` and
Perfetto as-is.  ``write(path)`` dumps it; round-tripping through
``json.loads`` is part of the tier-1 contract.

``timeit`` is the one timing idiom for host-side measurement (the
benchmarks use it instead of ad-hoc ``time.monotonic()`` pairs)::

    with timeit() as t:
        work()
    print(t.seconds)
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Span", "Tracer", "timeit", "get_tracer", "set_tracer",
    "start_tracing", "stop_tracing", "tracing", "span", "traced",
]


class timeit:
    """Minimal elapsed-time context manager: ``with timeit() as t: ...``
    then read ``t.seconds``.  ``clock`` defaults to ``time.perf_counter``
    (monotonic, highest host resolution)."""

    __slots__ = ("_clock", "_t0", "seconds")

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.seconds = 0.0

    def __enter__(self) -> "timeit":
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = self._clock() - self._t0


class Span:
    """One recorded interval (times in seconds on the tracer's clock)."""

    __slots__ = ("name", "t0", "dur", "tid", "args")

    def __init__(self, name: str, t0: float, dur: float, tid: int,
                 args: Optional[dict]):
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.args = args

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, t0={self.t0:.6f}, "
                f"dur={self.dur * 1e3:.3f}ms)")


class _NopSpan:
    """Shared do-nothing context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NOP = _NopSpan()


class _LiveSpan:
    """An open span: a profiler annotation plus its ring-buffer record,
    written at exit with the arguments given at entry and by ``set``."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def set(self, **args) -> None:
        """Add arguments to the span before it closes."""
        self._args.update(args)

    def __enter__(self):
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._t0 = self._tracer.now()
        return self

    def __exit__(self, *exc):
        t = self._tracer
        dur = t.now() - self._t0
        self._annotation.__exit__(*exc)
        t.record(self._name, self._t0, dur, **self._args)
        return False


class Tracer:
    """Thread-safe ring buffer of spans with Chrome-trace export.

    ``capacity`` bounds memory: the buffer keeps the newest spans and
    silently drops the oldest (``dropped`` counts them), so a tracer
    left on for a long serve run cannot grow without bound.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 clock=time.perf_counter, pid: Optional[int] = None):
        self.enabled = enabled
        self.capacity = capacity
        self._clock = clock
        self._epoch = clock()
        self._buf: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self.pid = os.getpid() if pid is None else pid

    # ---- clock -----------------------------------------------------------
    def now(self) -> float:
        """Seconds since the tracer's epoch (cheap even when disabled)."""
        return self._clock() - self._epoch

    # ---- recording -------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager recording one complete span on this thread."""
        if not self.enabled:
            return _NOP
        return _LiveSpan(self, name, args)

    def record(self, name: str, t0: float, dur: float, **args) -> None:
        """Record an interval measured by the caller (tracer-clock t0)."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._buf) == self.capacity:
                self.dropped += 1
            self._buf.append(("X", name, t0, dur,
                              threading.get_ident(), args or None))

    def instant(self, name: str, **args) -> None:
        """Point annotation ("ph": "i") at the current time."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._buf) == self.capacity:
                self.dropped += 1
            self._buf.append(("i", name, self.now(), 0.0,
                              threading.get_ident(), args or None))

    def sync(self, x) -> None:
        """``jax.block_until_ready(x)`` only when tracing is enabled, so
        spans around device programs measure the program, not the
        dispatch — and the untraced hot path never syncs."""
        if self.enabled and x is not None:
            import jax
            jax.block_until_ready(x)

    # ---- reading ---------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Span]:
        """Snapshot of recorded "X" spans (optionally filtered by name)."""
        with self._lock:
            rows = list(self._buf)
        return [Span(n, t0, dur, tid, args)
                for ph, n, t0, dur, tid, args in rows
                if ph == "X" and (name is None or n == name)]

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    # ---- export ----------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome trace format: {"traceEvents": [...]} in microseconds."""
        with self._lock:
            rows = list(self._buf)
        events = []
        tids = {}
        for ph, name, t0, dur, tid, args in rows:
            tids.setdefault(tid, len(tids))
            ev = dict(name=name, ph=ph, ts=round(t0 * 1e6, 3),
                      pid=self.pid, tid=tids[tid], cat="repro")
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            events.append(ev)
        # thread-name metadata so Perfetto labels the tracks
        meta = [dict(name="thread_name", ph="M", pid=self.pid, tid=i,
                     args={"name": f"thread-{i}"})
                for i in sorted(tids.values())]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


def _jsonable(v):
    """Coerce numpy/jax scalars so the trace always json-serializes."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    try:
        return v.item()          # 0-d numpy / jax scalar
    except Exception:
        return str(v)


# ---------------------------------------------------------------------------
# process-global tracer: disabled by default (zero overhead); the launch
# drivers enable it behind --trace
# ---------------------------------------------------------------------------

_TRACER = Tracer(enabled=False)
_TRACE_PATH: Optional[str] = None


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer; returns the previous one."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


def start_tracing(path: Optional[str] = None,
                  capacity: int = 65536) -> Tracer:
    """Enable the global tracer (fresh buffer); remember ``path`` for
    ``stop_tracing`` to write the Chrome-trace JSON to."""
    global _TRACE_PATH
    _TRACE_PATH = path
    set_tracer(Tracer(capacity=capacity, enabled=True))
    return _TRACER


def stop_tracing(write: bool = True) -> Optional[str]:
    """Disable the global tracer; write the trace if a path was given."""
    global _TRACE_PATH
    tracer, path = _TRACER, _TRACE_PATH
    out = None
    if write and path is not None and tracer.enabled:
        out = tracer.write(path)
    tracer.enabled = False
    _TRACE_PATH = None
    return out


@contextmanager
def tracing(path: Optional[str] = None,
            capacity: int = 65536) -> Iterator[Tracer]:
    """``with tracing("t.json") as tr: ...`` — scoped global tracing."""
    prev = set_tracer(Tracer(capacity=capacity, enabled=True))
    try:
        yield _TRACER
    finally:
        if path is not None:
            _TRACER.write(path)
        set_tracer(prev)


def span(name: str, **args):
    """Span on the process-global tracer (no-op unless tracing is on)."""
    return _TRACER.span(name, **args)


def traced(name: Optional[str] = None):
    """Decorator: trace every call of ``fn`` as one span."""

    def wrap(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*a, **kw):
            tr = _TRACER
            if not tr.enabled:
                return fn(*a, **kw)
            with tr.span(label):
                return fn(*a, **kw)

        return inner

    return wrap
