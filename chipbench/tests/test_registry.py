"""Configurations, mixes and metrics are found by the names in
BENCHMARK.json; adding one is adding a file."""
import json
import os
import shutil

import pytest

from harness import registry
from harness.record import Record


def test_every_cell_resolves_to_files():
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        cfg = registry.load_config(cell["config"])
        mix = registry.load_traffic(cell["traffic"])
        assert cfg["chips"] == cell["chips"]
        assert mix["feed"] in ("open_loop", "backlog")
        for m in registry.cell_metrics(bench, cell["name"], True):
            assert callable(registry.metric_reader(m["name"]))


def test_every_config_file_is_named_in_the_benchmark():
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert registry.load_config(c["name"])["reduced"] == c["reduced"]


def test_cell_metrics_follow_the_workloads_lists():
    bench = registry.load_benchmark()
    paced = {m["name"] for m in
             registry.cell_metrics(bench, "wiki-talk.paced", False)}
    assert paced == {"freshness_p95_s", "query_p95_ms", "setup_s"}
    backlog = {m["name"] for m in
               registry.cell_metrics(bench, "wiki-talk.backlog", True)}
    assert "ppr_repair_ms.tput" in backlog and "step_ms.tput" in backlog
    assert not any(name.endswith(".fresh") for name in backlog)


@pytest.fixture
def copy(tmp_path):
    """A copy of the benchmark's files to add to."""
    here = os.path.join(tmp_path, "chipbench")
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(registry.HERE, d),
                        os.path.join(here, d))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    return str(tmp_path), here


def test_a_new_cell_config_mix_and_metric_are_files(copy):
    root, here = copy
    cfg = registry.load_config("graph500-s19",
                               os.path.join(registry.HERE, "tests", "tiny"))
    cfg["graph"]["scale"] = 22
    with open(os.path.join(here, "configs", "graph500-s22.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(here, "traffic", "bursts.json"), "w") as f:
        json.dump({"feed": "backlog", "query_rate": 0}, f)
    with open(os.path.join(here, "metrics", "publishes.py"), "w") as f:
        f.write("def read(record):\n    return len(record.publishes)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(dict(name="graph500-s22.bursts",
                                   config="graph500-s22", traffic="bursts",
                                   chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="publishes.tput", unit="batches", better="higher",
        source="program_counter", layer="serve step", moves="events_per_s",
        workloads=["graph500-s22.bursts"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    bench = registry.load_benchmark(root)
    cell = registry.find_cell(bench, "graph500-s22.bursts")
    assert registry.load_config(cell["config"], here)["graph"]["scale"] == 22
    assert registry.load_traffic(cell["traffic"], here)["feed"] == "backlog"
    wanted = registry.cell_metrics(bench, cell["name"], True)
    assert [m["name"] for m in wanted] == ["publishes.tput"]
    read = registry.metric_reader("publishes.tput", here)
    assert read(Record("x", 0.0, 1.0, [{"events": 1}] * 3)) == 3


def test_missing_names_are_errors(copy):
    root, here = copy
    bench = registry.load_benchmark(root)
    with pytest.raises(registry.RegistryError):
        registry.find_cell(bench, "no-such.cell")
    with pytest.raises(registry.RegistryError):
        registry.load_config("no-such-config", here)
    with pytest.raises(registry.RegistryError):
        registry.metric_reader("no_such_metric.tput", here)


def test_readers_return_nothing_without_a_reading():
    empty = Record("x", 0.0, 1.0, [])
    for name in ("step_ms", "spmv_kernel_ms", "device_idle", "query_p50_ms",
                 "feed_lag_p95_ms", "batch_events", "ppr_repair_ms"):
        assert registry.metric_reader(name)(empty) is None
