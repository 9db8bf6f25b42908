import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
for path in (os.path.join(ROOT, "src"), CHIPBENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

TINY = os.path.join(HERE, "tiny")
# a cell the CPU tests add, on a configuration that only tiny/ holds
EXTRA_CELLS = [dict(name="graph500-s19.backlog", config="graph500-s19",
                    traffic="backlog", chips=1,
                    why="random updates at CPU-test size")]


def merged(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out


@pytest.fixture(scope="session")
def tiny(tmp_path_factory) -> tuple:
    """(root, here) of the committed benchmark cut to CPU-test size.

    ``root`` holds the committed BENCHMARK.json with ``EXTRA_CELLS``
    added; ``here`` holds every committed configuration and mix, each
    with the keys of its namesake under ``tiny/`` laid over it (a file
    under ``tiny/`` with no namesake is taken whole)."""
    root = tmp_path_factory.mktemp("tiny")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += EXTRA_CELLS
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    here = root / "chipbench"
    for kind in ("configs", "traffic"):
        os.makedirs(here / kind)
        names = set(os.listdir(os.path.join(CHIPBENCH, kind))) | \
            set(os.listdir(os.path.join(TINY, kind)))
        for name in names:
            parts = []
            for d in (CHIPBENCH, TINY):
                path = os.path.join(d, kind, name)
                if os.path.exists(path):
                    with open(path) as f:
                        parts.append(json.load(f))
            out = merged(parts[0], parts[1]) if len(parts) == 2 else parts[0]
            with open(here / kind / name, "w") as f:
                json.dump(out, f)
    return str(root), str(here)
