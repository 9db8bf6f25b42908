"""A run without a TPU, or without the program, exits non-zero and
prints no result."""
import os
import shutil
import subprocess
import sys

from harness.registry import HERE, ROOT


def run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "wiki-talk.paced",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run(ROOT, env)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr
    assert "platform=cpu" in p.stdout        # the run names its device


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, os.path.join(tmp_path, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = run(str(tmp_path), env)
    assert p.returncode != 0
    assert no_result(p.stdout)
