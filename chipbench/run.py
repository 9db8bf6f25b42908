"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload wiki-talk.paced --seed 7 \
        --seconds 40 --trace 0

The cell (``BENCHMARK.json``) names a configuration
(``chipbench/configs/``) and a traffic mix (``chipbench/traffic/``).
The run builds the deployment from the seed, serves it through the
program's serving entry, warms up every shape it uses, measures a window
of ``--seconds``, checks what was served against the plain reference,
and prints one JSON line: the cell's end-to-end metrics (``--trace 0``)
or its per-layer metrics read from the program's spans and a device
trace (``--trace 1``).  It exits non-zero, and prints no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))   # the program under test

from harness import registry  # noqa: E402
from harness.window import freshness, nearest_rank, \
    throughput_window  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="serve with the program's float32 path (no "
                         "float64 polish) and print the readings of the "
                         "float32 reference and of altered answers: the "
                         "controls the limits are set against")
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, every
    program in it, so that only a checkout's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(require_tpu: bool, chips: int):
    """(devices, description) or raise SystemExit when the chip is
    missing."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if require_tpu:
        if dev.platform != "tpu":
            print("chipbench: JAX found no TPU", file=sys.stderr)
            raise SystemExit(1)
        if len(devices) < chips:
            print(f"chipbench: the cell needs {chips} chips, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            raise SystemExit(1)
        from harness.peaks import peaks_for
        peaks_for(dev.device_kind)
    return devices[:chips]


def end_to_end(run, wanted: list, setup_s: float) -> dict:
    values = {"setup_s": setup_s}
    pubs = [p for p in run.publishes if p["window"]]
    window = throughput_window(pubs, run.w0, run.seconds)
    if window is not None:
        events, span, _ = window
        values["events_per_s"] = events / span
    seqs = sorted(run.due)
    fresh = [f for f in freshness([run.due[s] for s in seqs], seqs, pubs)
             if f is not None]
    if fresh:
        values["freshness_p95_s"] = nearest_rank(fresh, 0.95)
    lat = [q["done"] - q["due"] for q in run.queries
           if q.get("done") is not None]
    if lat:
        values["query_p95_ms"] = 1e3 * nearest_rank(lat, 0.95)
    out = {}
    for m in wanted:
        if m["name"] not in values:
            raise RuntimeError(f"the run gave no reading of {m['name']}")
        out[m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
    return out


def per_layer(record, wanted: list) -> dict:
    out = {}
    for m in wanted:
        value = registry.metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = dict(value=value, unit=m["unit"])
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             control: bool = False, require_tpu: bool = True,
             root: str = ROOT, here: str = HERE) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``root`` holds BENCHMARK.json and ``here`` the configs/ and traffic/
    directories (the tests point both at small copies)."""
    bench = registry.load_benchmark(root)
    cell = registry.find_cell(bench, workload)
    cfg = registry.load_config(cell["config"], here)
    if control:
        # the program's own lower-precision path: DF-P in float32 alone,
        # without its float64 polish
        cfg["served"]["kernel_opts"] = {"polish": False}
    mix = registry.load_traffic(cell["traffic"], here)
    wanted = registry.cell_metrics(bench, workload, trace)
    devices = device_info(require_tpu, cell["chips"])

    import jax
    import repro  # noqa: F401  (the program: float64 on)
    from repro import obs
    from harness import check
    from harness.driver import CellRun, CompileCounter
    from harness.record import Record, Span

    counter = CompileCounter()
    tracer = None
    if trace:
        tracer = obs.start_tracing(None, capacity=1 << 20)
    run = CellRun(cfg, mix, seed, seconds, trace, log)
    run.setup()
    profile = {}

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # annotations only, no Python calls

    def start_profile():
        profile["dir"] = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(
            profile["dir"], profiler_options=options)
        with jax.profiler.TraceAnnotation("chipbench.mark"):
            profile["mark"] = time.perf_counter()

    run.warm_and_measure(start_profile if trace else None)
    setup_s = run.w0 - T_START
    if trace:
        jax.profiler.stop_trace()
    if run.w1 is None:
        raise RuntimeError("the window never closed: no publish after it")
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    inside = [name for t, name in counter.times if run.w0 <= t <= run.w1]
    window_compiles = len(inside)
    if inside:
        log(f"compiled inside the window: {inside}")
    log(f"window {run.w1 - run.w0:.3f}s publishes="
        f"{sum(p['window'] for p in run.publishes)} "
        f"memory_peak_bytes={peak} bytes_limit={stats.get('bytes_limit')} "
        f"compiles_in_window={window_compiles} setup_s={setup_s:.3f}")
    now = run.counts()
    log("window counts " + json.dumps(
        {k: now[k] - v for k, v in run.counts_at_w0.items()}))
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=peak)

    # attempted: the window's events (each one due on the open-loop
    # schedule, or each one in a published batch of a backlog) and its
    # queries; failed: those never answered, and events load-shed
    pubs = [p for p in run.publishes if p["window"]]
    last_pub = max((p["last_seq"] for p in pubs), default=-1)
    unanswered = sum(1 for q in run.queries if q.get("done") is None) + \
        sum(1 for s in run.due if s > last_pub)
    events = len(run.due) if mix["feed"] == "open_loop" else \
        sum(p["events"] for p in pubs)
    attempted = events + len(run.queries)
    failed = unanswered + run.rejected

    result = dict(correct=False, attempted=attempted, failed=failed)
    if trace:
        from harness import xtrace
        spans = []
        offset = time.perf_counter() - tracer.now()
        for s in tracer.spans():
            spans.append(Span(s.name, s.t0 + offset, s.dur, s.args or {}))
        obs.stop_tracing(write=False)
        repairs = [s.args for s in spans if s.name == "ppr.repair"]
        if repairs:
            log(f"walk repairs: {repairs}")
        reduced = xtrace.reduce_dir(profile["dir"], profile["mark"], run.w0,
                                    run.w1, spans)
        record = Record(
            cell=workload, w0=run.w0, w1=run.w1, publishes=pubs,
            spans=spans, feed_lags=run.feed_lags,
            queries=[q for q in run.queries if q.get("done") is not None],
            window_compiles=window_compiles, trace=reduced)
        result["metrics"] = per_layer(record, wanted)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                   idle_gaps=reduced["idle_gaps"])
        shutil.rmtree(profile["dir"], ignore_errors=True)
    else:
        result["metrics"] = end_to_end(run, wanted, setup_s)
    result["device"] = device

    served = check.collect(run)
    check.release(run)
    checks = check.compare(run, served, cfg["limits"], log, control)
    checks["unanswered"] = dict(value=unanswered, limit=0)
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    use_compile_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), bool(args.control))
    except registry.RegistryError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"chipbench: check {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
