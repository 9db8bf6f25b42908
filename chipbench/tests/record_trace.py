"""Record the small device trace that test_trace.py reduces.

Run on a TPU (it exits 1 elsewhere):

    python3 chipbench/tests/record_trace.py

Serves two micro-batches of a 16k-vertex graph on the kernel engine under
the profiler, as a benchmark run traces its window, and writes
``tests/data/small.xplane.pb`` and ``tests/data/small.json`` (the host-
clock mark, window and program spans the reduction needs).
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 1
    from harness.gen import kronecker
    from repro import obs
    from repro.graph.structure import from_coo
    from repro.serve import IngestQueue, RankStore, ServeEngine

    edges = kronecker(14, 8, seed=1)
    n = 1 << 14
    graph = from_coo(edges[:, 0], edges[:, 1], n,
                     edge_capacity=len(edges) + 1024)
    ingest = IngestQueue(flush_size=64, flush_interval=1e9)
    engine = ServeEngine(graph, ingest, RankStore(), engine="kernel",
                         telemetry=False)
    tracer = obs.start_tracing(None)
    engine.bootstrap()
    rng = np.random.default_rng(0)

    def step():
        for u, v in rng.integers(0, n, size=(64, 2)):
            ingest.submit_insert(int(u), int((v + (u == v)) % n))
        engine.step(force=True)

    step()                                    # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("chipbench.mark"):
        mark = time.perf_counter()
    w0 = time.perf_counter()
    step()
    step()
    w1 = time.perf_counter()
    jax.profiler.stop_trace()
    offset = time.perf_counter() - tracer.now()
    spans = [dict(name=s.name, t0=s.t0 + offset, dur=s.dur)
             for s in tracer.spans() if s.t0 + offset >= mark]
    out = os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.json"), "w") as f:
        json.dump(dict(mark=mark, w0=w0, w1=w1, spans=spans), f, indent=1)
    shutil.rmtree(tmp)
    print(f"record_trace: wrote {out}/small.xplane.pb", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
