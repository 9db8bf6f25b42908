"""Trace reduction, on a small trace recorded on a TPU v5e
(``record_trace.py``: two serve steps of a 16k-vertex graph on the
kernel engine) and on hand-made intervals."""
import json
import os

import pytest

from harness import xtrace
from harness.record import Span

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small.json")) as f:
        meta = json.load(f)
    spans = [Span(s["name"], s["t0"], s["dur"], {}) for s in meta["spans"]]
    return meta, xtrace.reduce_dir(DATA, meta["mark"], meta["w0"],
                                   meta["w1"], spans)


def test_recorded_trace_busy_and_idle(recorded):
    meta, r = recorded
    assert r["window_s"] == pytest.approx(meta["w1"] - meta["w0"], abs=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    names = {s["name"] for s in meta["spans"]} | {"host.idle"}
    assert {label for label, _ in r["idle_gaps"]} <= names


def test_recorded_trace_kernel_and_programs(recorded):
    _, r = recorded
    assert 0 < r["kernels"]["frontier_spmv"] < r["busy_s"]
    programs = dict(r["device_ops"])
    assert "jit__fused_update_loop" in programs
    assert "jit_apply_batch" in programs
    assert all("(" not in name for name in programs)
    assert sum(programs.values()) <= r["window_s"] * 1.001


def test_kernel_is_named_by_its_instruction():
    op = ("%frontier_spmv.15 = f32[32,1,512]{2,1,0} custom-call(s32[77] "
          "%a), custom_call_target=\"tpu_custom_call\"")
    assert xtrace._kernel_of(op) == "frontier_spmv"
    assert xtrace._kernel_of("%fusion.3 = f32[8] fusion(%frontier_spmv.2)") \
        is None


def test_union_gaps_and_labels():
    busy = xtrace.union([(5, 10), (0, 2), (8, 12), (20, 25)])
    assert busy == [[0, 2], [5, 12], [20, 25]]
    assert xtrace.gaps(busy, 1, 22) == [(2, 5), (12, 20)]
    assert xtrace.gaps([], 0, 4) == [(0, 4)]
    spans = [("serve.step", 0, 100), ("polish.f64", 10, 20)]
    assert xtrace.label(15, spans) == "polish.f64"
    assert xtrace.label(50, spans) == "serve.step"
    assert xtrace.label(150, spans) == "host.idle"


def test_reduce_clips_to_the_window_and_averages_devices():
    ops = [("frontier_spmv", 0, 40), ("%fusion.1 = x", 60, 120)]
    mods = [("jit_step", 0, 120)]
    r = xtrace.reduce({"/device:TPU:0": (ops, mods),
                       "/device:TPU:1": ([("%copy.2 = y", 50, 100)], [])},
                      20, 100, [("route_update", 0, 50)])
    assert r["window_s"] == pytest.approx(80e-9)
    # device 0 busy 20..40 and 60..100 = 60, device 1 busy 50..100 = 50
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["kernels"]["frontier_spmv"] == pytest.approx(10e-9)
    # idle: device 0 40..60 (midpoint 50, after route_update closed),
    # device 1 20..50 (midpoint 35, inside route_update)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"route_update": 15e-9, "host.idle": 10e-9})
