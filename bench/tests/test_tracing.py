"""The reduction from trace events to device times: on hand-made
events, and on a stretch of a trace recorded on a TPU v5e."""
import gzip
import json
import os

import numpy as np
import pytest

from bench import network, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_union_merges_overlaps_and_touching():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4),
                                                                (5, 7)]


def test_reduce_counts_busy_kernel_and_glue():
    evs = [ev("fusion.1", 0, 100), ev("custom-call.3", 100, 300),
           ev("fusion.2", 350, 100),          # overlaps the kernel by 50
           ev("custom-call.4", 1000, 200),
           ev("bench.infer", 0, 2000, plane="/host:CPU", line="python")]
    red = tracing.reduce(evs, ["custom-call"])
    assert red["devices"] == 1 and red["kernel_calls"] == 2
    assert red["busy_s"] == pytest.approx(650e-9)
    assert red["kernel_s"] == pytest.approx(500e-9)
    assert red["glue_s"] == pytest.approx(150e-9)


def test_reduce_averages_over_devices():
    evs = [ev("k", 0, 100), ev("k", 0, 300, plane="/device:TPU:1")]
    red = tracing.reduce(evs, ["^k$"])
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(200e-9)


def test_idle_gaps_are_named_by_the_host_span_open():
    evs = [ev("a", 0, 100), ev("b", 400, 100), ev("c", 600, 10),
           ev("bench.between", 150, 200, plane="/host:CPU", line="py")]
    gaps = tracing.idle_gaps(evs)
    assert gaps[0][0] == "host:bench.between"
    assert gaps[0][1] == pytest.approx(300e-9)
    assert gaps[1] == ["host:none", pytest.approx(100e-9)]


def test_top_ops_sums_by_name():
    evs = [ev("x", 0, 10), ev("y", 20, 5), ev("x", 30, 10)]
    assert tracing.top_ops(evs, 1) == [["x", pytest.approx(20e-9)]]


def _chip_trace():
    """60 ms of a ``resnet50-heana.poisson-b1`` run's trace on one v5e:
    the device's "XLA Ops" events and the benchmark's host spans."""
    with gzip.open(os.path.join(DATA, "chip-trace-poisson-b1.json.gz"),
                   "rt") as f:
        return json.load(f)["events"]


def _covered_s(evs, step_ns=10.0):
    """Busy time by painting each event onto a timeline of ``step_ns``
    cells: a reckoning independent of ``tracing.union``."""
    lo = min(e["start_ns"] for e in evs)
    hi = max(e["start_ns"] + e["dur_ns"] for e in evs)
    cells = np.zeros(int((hi - lo) / step_ns) + 2, bool)
    for e in evs:
        a = int(round((e["start_ns"] - lo) / step_ns))
        b = int(round((e["start_ns"] + e["dur_ns"] - lo) / step_ns))
        cells[a:b] = True
    return cells.sum() * step_ns * 1e-9


def test_reduce_on_a_recorded_chip_trace():
    evs = _chip_trace()
    patterns = network.load_json(
        os.path.join(network.BENCH_DIR, "kernels.json"))["taom"]
    dev = [e for e in evs if e["plane"] == DEV and e["line"] == "XLA Ops"]
    kern = [e for e in dev if "tpu_custom_call" in e["name"]]
    red = tracing.reduce(evs, patterns)
    assert red["devices"] == 1
    assert red["kernel_calls"] == len(kern) == 102
    assert red["busy_s"] == pytest.approx(_covered_s(dev), rel=2e-3)
    assert red["kernel_s"] == pytest.approx(_covered_s(kern), rel=2e-3)
    assert red["glue_s"] == pytest.approx(red["busy_s"] - red["kernel_s"])
    assert red["busy_s"] == pytest.approx(0.022022849, rel=1e-9)
    assert red["kernel_s"] == pytest.approx(0.003925796, rel=1e-9)


def test_idle_gaps_of_a_recorded_chip_trace():
    evs = _chip_trace()
    gaps = tracing.idle_gaps(evs)
    assert len(gaps) == 10
    assert all(name.startswith("host:bench.") for name, _ in gaps[:3])
    lengths = [g for _, g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert lengths[0] == pytest.approx(0.00413106, rel=1e-6)
    dev = [e for e in evs if e["plane"] == DEV]
    span = (max(e["start_ns"] + e["dur_ns"] for e in dev)
            - min(e["start_ns"] for e in dev)) * 1e-9
    busy = tracing.reduce(evs, [])["busy_s"]
    assert sum(lengths) <= span - busy + 1e-12
