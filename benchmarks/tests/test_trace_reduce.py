"""The reduction from a trace to busy share, module times and named gaps."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks import roofline, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes(doc):
    return [
        NS(name=p["name"], lines=[
            NS(name=ln["name"], events=[NS(name=e[0], start_ns=e[1], duration_ns=e[2]) for e in ln["events"]])
            for ln in p["lines"]])
        for p in doc
    ]


def test_synthetic_busy_modules_and_gaps():
    doc = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [[tr.MARKER, 1000.0, 9000.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_resident_run(123)", 2000.0, 3000.0], ["jit_usage_checksum(9)", 6000.0, 500.0]]},
            {"name": "XLA Ops", "events": [["%fusion.1 = u32[] fusion()", 2000.0, 1000.0], ["%while.2 = () while()", 2500.0, 2500.0],
                                           ["%copy.3 = u32[] copy()", 6000.0, 500.0]]},
        ]},
    ]
    red = tr.reduce_planes(_planes(doc))
    # host clock: marker opened at host t=10 s -> trace ns = host*1e9 + (1000 - 10e9)
    s = tr.summarize(red, marker_host_t0=10.0, window=(10.0, 10.000009),
                     host_spans=[("bind", 10.000004, 10.000005), ("queue_pop", 10.0, 10.000001)])
    assert s["aligned"] and s["chips"] == 1
    assert s["window_s"] == pytest.approx(9000e-9)
    assert s["busy_s"] == pytest.approx(3500e-9)  # [2000,5000) U [6000,6500)
    assert s["modules"]["jit_resident_run"] == {"seconds": pytest.approx(3000e-9), "events": 1}
    assert dict(s["device_ops"])["while.2"] == pytest.approx(2500e-9)
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(5500e-9)
    assert gaps["queue_pop"] == pytest.approx(1000e-9)  # the gap [1000,2000) is all queue_pop
    assert gaps["bind"] == pytest.approx(1000e-9)  # the gap [5000,6000)


def test_union_and_gaps():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.gaps([(1, 4), (5, 6)], 0, 10) == [(0, 1), (4, 5), (6, 10)]
    assert tr.module_name("jit_wave_run(42)") == "jit_wave_run"


def test_no_device_op_reads_nothing():
    red = tr.reduce_planes(_planes([{"name": "/host:CPU", "lines": []}]))
    assert tr.summarize(red) is None


def test_recorded_chip_trace():
    """A trace of a 10,000-pod resident drain recorded on the chip (PR 26),
    cut to the device lines and the marker: known busy seconds (counted by a
    separate sweep when the file was cut) and module time."""
    path = os.path.join(HERE, "recorded_trace.json")
    doc = json.load(open(path))
    s = tr.summarize(tr.reduce_planes(_planes(doc["planes"])), marker_host_t0=doc["marker_host_t0"],
                     window=tuple(doc["window"]))
    assert s["busy_s"] == pytest.approx(doc["expect"]["busy_s"], rel=1e-9)
    assert s["modules"]["jit_resident_run"]["seconds"] == pytest.approx(
        doc["expect"]["resident_run_s"], rel=1e-9)
    assert s["busy_s"] <= s["modules"]["jit_resident_run"]["seconds"] + s["modules"]["jit_usage_checksum"]["seconds"] + 1e-6


def test_peaks_and_floor():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("source")
    b = roofline.floor_bytes(n_nodes=5000, n_lanes=3, dispatches=1, pods=10000, nodes_touched=5000)
    assert b == 2 * 5000 * 3 * 4 + 5000 * 3 * 4 + 10000 * 4
