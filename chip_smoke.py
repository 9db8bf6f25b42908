"""Smoke test of the serving path on a TPU: wiki-talk-temporal at paper
scale (1,140,149 vertices, 7,833,140 events) through
``repro.launch.serve`` (``build_parser`` and ``run``), once with the
Pallas kernel engine and once with the XLA engine, each served snapshot
checked against the f64 reference of ``repro.core.reference``.

    python3 chip_smoke.py              # one chip: kernel and XLA engines
    python3 chip_smoke.py --chips 4    # kernel engine + sharded PPR on a
                                       # (data, model) = (1, 4) mesh

Fails (non-zero exit, no result line) when JAX finds no TPU, when a
Pallas call was traced in interpret mode, when the kernel engine resolved
``use_kernel`` to False, when any batch fell back to a static solve
instead of DF-P, when a served snapshot is further than L1 1e-4
from the reference or the two engines differ by more than L1 1e-6, and
when any phase raises.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# No flush deadline: every micro-batch holds exactly 256 events, so both
# engines serve the same 8 batches whatever the host's speed.  No static
# fallback: at 256 events a batch's initial frontier often covers more
# than the default 0.25 of the vertices, and a batch that falls back is
# solved by the same XLA static solve in both engines, which would hide
# the kernel from the comparison.  Every batch runs DF-P instead.
SERVE_ARGS = ["--dataset", "wiki-talk-temporal", "--scale", "paper",
              "--events", "2048", "--flush-size", "256",
              "--flush-interval-ms", "inf", "--static-fallback-frac", "1.0",
              "--ppr-walks", "16"]
REF_L1 = 1e-4        # the shadow verifier's L1 budget (obs.shadow)
ENGINE_L1 = 1e-6     # kernel engine against XLA engine


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Recorder:
    """Counts compiles (JAX monitoring events) and every Pallas call
    traced, with its ``interpret`` flag."""

    def __init__(self):
        import jax
        from jax.experimental import pallas as pl

        self.compiles = 0
        self.cache_hits = 0
        self.pallas = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        inner = pl.pallas_call

        def pallas_call(*args, **kwargs):
            self.pallas.append((kwargs.get("name"),
                                bool(kwargs.get("interpret", False))))
            return inner(*args, **kwargs)

        pl.pallas_call = pallas_call

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.cache_hits, len(self.pallas)


def serve(engine: str, serve_args: list, rec: Recorder, extra=()) -> dict:
    """One ``repro.launch.serve`` run; returns its snapshot and engine.
    Every batch must have run DF-P: a static fallback would replace the
    engine's own solve with the shared XLA static one."""
    from repro.launch import serve as serve_cli

    c0, h0, p0 = rec.mark()
    t0 = time.perf_counter()
    args = serve_cli.build_parser().parse_args(
        serve_args + ["--engine", engine, *extra])
    served = serve_cli.run(args)
    wall = time.perf_counter() - t0
    check(served is not None, f"serve --engine {engine} did not start")
    eng, store, metrics = served
    check(metrics["batches"] > 0 and metrics["static_fallbacks"] == 0,
          f"{engine}: {metrics['static_fallbacks']} of "
          f"{metrics['batches']} batches fell back to a static solve")
    traced = rec.pallas[p0:]
    check(not any(interp for _, interp in traced),
          f"{engine}: a Pallas call ran with interpret=True: {traced}")
    if engine == "kernel":
        check(eng.use_kernel, "kernel engine resolved use_kernel=False")
        check(any(name == "frontier_spmv" for name, _ in traced),
              "kernel engine traced no frontier_spmv Pallas call")
        geom = eng.kernel_geometry.describe()
        print(f"smoke: kernel geometry {geom}", flush=True)
    print(f"smoke: phase serve[{engine}] wall_s={wall:.3f} "
          f"compiles={rec.compiles - c0} "
          f"cache_hits={rec.cache_hits - h0} "
          f"pallas_calls_traced={len(traced)} "
          f"batches={metrics['batches']} "
          f"static_fallbacks={metrics['static_fallbacks']} "
          f"affected_mean={metrics['affected_mean']}", flush=True)
    return dict(snapshot=store.snapshot(), engine=eng)


def reference_l1(snapshot) -> float:
    """L1 of the served ranks against the f64 NumPy reference solved on
    the snapshot's own graph."""
    import numpy as np

    from repro.core.reference import l1_error, static_pagerank_ref

    t0 = time.perf_counter()
    g = snapshot.graph
    valid = np.asarray(g.valid)
    ref, iters = static_pagerank_ref(np.asarray(g.src)[valid],
                                     np.asarray(g.dst)[valid],
                                     g.num_vertices)
    l1 = l1_error(np.asarray(snapshot.ranks), ref)
    print(f"smoke: phase reference wall_s={time.perf_counter() - t0:.3f} "
          f"iterations={iters} L1={l1:.3e} (limit {REF_L1:.0e})",
          flush=True)
    return l1


def on_devices(tree) -> int:
    """Number of distinct devices holding the leaves of ``tree``."""
    import jax
    return len({d for leaf in jax.tree_util.tree_leaves(tree)
                for d in leaf.sharding.device_set})


def smoke(serve_args: list, four_chips: bool) -> None:
    import numpy as np

    from repro.data.snap import load_temporal
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"smoke: compile cache {cache}", flush=True)
    rec = Recorder()
    t0 = time.perf_counter()
    opts = dict(zip(serve_args[::2], serve_args[1::2]))
    ds = load_temporal(opts["--dataset"], scale=opts.get("--scale", "small"))
    print(f"smoke: phase dataset wall_s={time.perf_counter() - t0:.3f} "
          f"|V|={ds.num_vertices} events={len(ds.edges)}", flush=True)

    if four_chips:
        run = serve("kernel", serve_args, rec, extra=("--mesh", "model"))
        packed, index = run["engine"].packed, run["snapshot"].ppr_index
        n_packed, n_index = on_devices(packed), on_devices(index.steps)
        print(f"smoke: sharded pack on {n_packed} devices, walk index on "
              f"{n_index} devices", flush=True)
        check(n_packed == 4 and n_index == 4,
              f"shards span {n_packed}/{n_index} devices, expected 4")
        l1 = reference_l1(run["snapshot"])
        check(l1 <= REF_L1, f"sharded kernel engine L1 {l1:.3e} > {REF_L1}")
        return

    kern = serve("kernel", serve_args, rec)
    l1 = reference_l1(kern["snapshot"])
    check(l1 <= REF_L1, f"kernel engine L1 {l1:.3e} > {REF_L1}")
    xla = serve("xla", serve_args, rec)
    l1 = reference_l1(xla["snapshot"])
    check(l1 <= REF_L1, f"xla engine L1 {l1:.3e} > {REF_L1}")
    gk, gx = kern["snapshot"].graph, xla["snapshot"].graph
    check(int(gk.num_valid_edges()) == int(gx.num_valid_edges()),
          "the two engines served different graphs")
    diff = float(np.sum(np.abs(np.asarray(kern["snapshot"].ranks)
                               - np.asarray(xla["snapshot"].ranks))))
    print(f"smoke: kernel vs xla L1={diff:.3e} (limit {ENGINE_L1:.0e})",
          flush=True)
    check(diff <= ENGINE_L1, f"kernel vs xla L1 {diff:.3e} > {ENGINE_L1}")
    print(f"smoke: total compiles={rec.compiles} "
          f"cache_hits={rec.cache_hits}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        import jax
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    print(f"smoke: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    try:
        smoke(SERVE_ARGS, four_chips=args.chips == 4)
    except Exception as e:                  # any phase failing fails the run
        import traceback
        traceback.print_exc()
        print(f"smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"smoke: total wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
