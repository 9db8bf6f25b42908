"""CLI driver smoke tests (subprocess; tiny workloads)."""
import os
import subprocess
import sys

import pytest


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, env=env, cwd=_REPO,
                       timeout=timeout)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


@pytest.mark.slow
def test_pagerank_driver(tmp_path):
    out = _run(["-m", "repro.launch.pagerank", "--dataset",
                "sx-mathoverflow", "--method", "frontier_prune",
                "--batch-frac", "1e-3", "--batches", "3",
                "--ckpt-every", "2", "--ckpt-dir", str(tmp_path)])
    assert "stream complete" in out
    assert "batch   2" in out
    # checkpoint written
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


@pytest.mark.slow
def test_train_driver_restart(tmp_path):
    out1 = _run(["-m", "repro.launch.train", "--arch", "qwen2.5-3b",
                 "--smoke", "--steps", "12", "--batch", "4", "--seq", "32",
                 "--ckpt-every", "5", "--ckpt-dir", str(tmp_path),
                 "--log-every", "5"])
    assert "final loss" in out1
    out2 = _run(["-m", "repro.launch.train", "--arch", "qwen2.5-3b",
                 "--smoke", "--steps", "14", "--batch", "4", "--seq", "32",
                 "--ckpt-every", "5", "--ckpt-dir", str(tmp_path),
                 "--log-every", "5"])
    assert "restored checkpoint at step 10" in out2


def test_quickstart_example():
    out = _run(["examples/quickstart.py"])
    assert "frontier_prune" in out


@pytest.mark.slow
def test_serve_driver(tmp_path):
    out = _run(["-m", "repro.launch.serve", "--dataset", "sx-mathoverflow",
                "--events", "200", "--flush-size", "32",
                "--flush-interval-ms", "20", "--query-every", "50",
                "--min-queries", "1",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    assert "serve complete" in out
    assert "queries served" in out
    # generations printed at each query burst are monotone non-decreasing
    gens = [int(line.split("gen=")[1].split()[0])
            for line in out.splitlines() if "gen=" in line]
    assert gens and gens == sorted(gens)
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))
    # restart resumes the event feed and the generation clock
    out2 = _run(["-m", "repro.launch.serve", "--dataset", "sx-mathoverflow",
                 "--events", "300", "--flush-size", "32",
                 "--flush-interval-ms", "20", "--query-every", "50",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    assert "restored generation" in out2
    assert "serve complete" in out2


def test_serve_run_returns_engine_store_metrics():
    """``launch.serve.run`` (what ``main`` and chip_smoke.py call) serves
    the whole feed in process and hands back the engine, the store and
    the metrics; with no flush deadline and no static fallback every
    batch is a full DF-P batch."""
    import jax

    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--dataset", "sx-mathoverflow", "--events", "96",
         "--flush-size", "32", "--flush-interval-ms", "inf",
         "--static-fallback-frac", "1.0", "--query-every", "48"])
    engine, store, metrics = serve.run(args)
    assert metrics["batches"] == 3
    assert metrics["static_fallbacks"] == 0
    assert metrics["queries_served"] > 0
    assert store.snapshot().last_seq == 95
    assert engine.engine == "xla" and engine.packed is None
    mesh = serve._resolve_mesh("model")
    assert dict(mesh.shape) == {"data": 1, "model": len(jax.devices())}
