"""Reduce a JAX profiler trace to device busy time, device time per
program and per kernel, and idle gaps labelled with what the host was
doing.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU each ``/device:`` plane
has an ``XLA Ops`` line (one event per HLO instruction run, named by its
HLO text, so a Pallas kernel shows as ``%<kernel name>.N = ...
custom-call``) and an ``XLA Modules`` line (one event per jitted program
run, ``jit_<function>(<fingerprint>)``).  Host and device events share
the trace's clock; host-clock spans are put on it through one
annotation (``chipbench.mark``) whose host-clock start the run recorded.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

MARK = "chipbench.mark"
KERNELS = ("frontier_spmv",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _mark_ns(planes) -> int:
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARK:
                    return ev.start_ns
    raise ValueError(f"no {MARK} annotation in the trace")


def _kernel_of(name: str):
    """The named kernel an op runs: its HLO instruction is named after
    the kernel (``%frontier_spmv.15 = ... custom-call(...)``)."""
    head = name.split(" = ", 1)[0]
    for k in KERNELS:
        if k in head:
            return k
    return None


def _module_of(name: str) -> str:
    """``jit__fused_update_loop(123)`` -> ``jit__fused_update_loop``."""
    return name.split("(", 1)[0]


def device_events(planes) -> dict:
    """{device plane: (ops, modules)}, each a list of (name, start_ns,
    end_ns); an op's name is its kernel's where it runs one, else its
    HLO instruction, and a module's is its program's."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    ops.append((_kernel_of(ev.name) or ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    modules.append((_module_of(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
        if ops:
            out[plane.name] = (ops, modules)
    return out


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, t0: int, t1: int) -> list:
    """[start, end) stretches of [t0, t1) that no busy interval covers."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def label(t: int, spans_ns: list) -> str:
    """Name of the innermost host span open at ``t``, else ``host.idle``."""
    best = None
    for name, s, e in spans_ns:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host.idle"


def _clip(events: list, t0: int, t1: int) -> list:
    return [(name, max(s, t0), min(e, t1)) for name, s, e in events
            if e > t0 and s < t1]


def reduce(by_device: dict, t0_ns: int, t1_ns: int, spans_ns: list
           ) -> dict:
    """Busy and idle time, device time per program and per kernel, all
    clipped to the window [t0_ns, t1_ns) and averaged over devices."""
    busy_total = 0.0
    modules = defaultdict(float)
    kernels = defaultdict(float)
    idle = defaultdict(float)
    for ops, mods in by_device.values():
        ops = _clip(ops, t0_ns, t1_ns)
        for name, s, e in ops:
            if name in KERNELS:
                kernels[name] += (e - s) * 1e-9
        for name, s, e in _clip(mods, t0_ns, t1_ns):
            modules[name] += (e - s) * 1e-9
        busy = union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e in gaps(busy, t0_ns, t1_ns):
            idle[label((s + e) // 2, spans_ns)] += (e - s) * 1e-9
    n = max(1, len(by_device))

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return dict(busy_s=busy_total / n, window_s=(t1_ns - t0_ns) * 1e-9,
                kernels={k: v / n for k, v in kernels.items()},
                device_ops=top(modules), idle_gaps=top(idle))


def reduce_dir(log_dir: str, mark_t: float, w0: float, w1: float,
               spans: list) -> dict:
    """``reduce`` of the trace under ``log_dir``, with the window and the
    host spans given on the host clock (``mark_t`` is when the mark
    annotation started on it)."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(find_xplane(log_dir)).planes)
    mark = _mark_ns(planes)
    to_ns = lambda t: mark + int(round((t - mark_t) * 1e9))
    spans_ns = [(s.name, to_ns(s.t0), to_ns(s.t0 + s.dur)) for s in spans]
    return reduce(device_events(planes), to_ns(w0), to_ns(w1), spans_ns)
