"""Everything a cell needs, found by name.

``BENCHMARK.json`` (the checkout's root) names each cell's configuration
and traffic mix.  A configuration is ``configs/<name>.json``, a mix is
``traffic/<name>.json`` and a per-layer metric ``<base>.<suffix>`` is
read by ``metrics/<base>.py``, whose ``read(record)`` returns a number,
or None when the run holds nothing for it to read.  Adding a cell,
mix, configuration or metric adds a file; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class RegistryError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise RegistryError(f"missing {os.path.relpath(path, ROOT)}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise RegistryError(f"no workload {name!r} in BENCHMARK.json; cells: "
                        f"{[c['name'] for c in bench['workloads']]}")


def load_config(name: str, here: str = HERE) -> dict:
    cfg = _load_json(os.path.join(here, "configs", name + ".json"))
    cfg["name"] = name
    return cfg


def load_traffic(name: str, here: str = HERE) -> dict:
    mix = _load_json(os.path.join(here, "traffic", name + ".json"))
    mix["name"] = name
    return mix


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: the end-to-end
    metrics untraced, the per-layer ones traced; a metric with a
    ``workloads`` list applies to those cells only."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_reader(name: str, here: str = HERE):
    """``read`` of ``metrics/<base>.py`` for the metric ``<base>.<suffix>``
    (the suffix names the end-to-end metric it moves, not what it reads)."""
    base = name.split(".")[0]
    path = os.path.join(here, "metrics", base + ".py")
    if not os.path.exists(path):
        raise RegistryError(f"no reader metrics/{base}.py for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
