"""Drives a whole run on the CPU at a size a test can hold (512 nodes,
2,048 pods: the smallest backlog the resident engine takes), skipping only
the look for a chip."""

import copy
import json
import shutil
import time

import pytest

from benchmarks import cells, control, runner

SIZES = (512, 2048, 100)  # nodes, measured pods, init pods


def _cell(name="basic-5k.backlog", bench=None):
    return cells.cut(cells.cell(name, bench or cells.benchmark()), *SIZES)


def _run(cell, bench=None, **kw):
    return runner.run_cell(cell, bench or cells.benchmark(), kw.pop("seed", 5), 20.0,
                           kw.pop("trace", False), time.perf_counter(), require_chip=False, **kw)


def test_sound_run_is_correct_and_the_stale_control_is_not():
    """The program's run passes every check; the control (the reference
    deciding against a state one commit stale) read at the same positions
    would not."""
    ctl = control.Controls(lag=1)
    res = _run(_cell(), on_identity_position=ctl)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 2048
    assert set(res["metrics"]) == {"pods_per_s", "setup_s"}
    r = ctl.readings()
    assert r["positions"] == 48 and r["stale_mismatches"] >= 1


def test_a_decision_altered_where_it_is_produced_is_not_correct():
    """The timed path broken underneath: one pod of the window is bound to
    another node than the scheduler decided."""
    def tamper(cluster):
        inner = cluster.sched.binding_sink_many

        def sink_many(items):
            items = list(items)
            for i, (pod, node) in enumerate(items):
                if pod.name == "load-7":
                    other = "scheduler-perf-0" if node != "scheduler-perf-0" else "scheduler-perf-1"
                    items[i] = (pod, other)
            return inner(items)

        cluster.sched.binding_sink_many = sink_many

    res = _run(_cell(), tamper=tamper, identity_positions=list(range(2048)))
    assert res["correct"] is False


def test_traced_run_reports_layer_metrics():
    res = _run(_cell(), trace=True)
    assert res["correct"] is True
    # no device plane on the CPU: the trace readers find nothing and say nothing
    assert set(res["metrics"]) == {
        "served.bind_s_per_kpod.backlog", "loop.host_s_per_kpod.backlog",
        "boundary.device_wait_s_per_kpod.backlog", "runtime.gc_s_per_kpod.backlog"}
    assert "busy_s" not in res["device"]


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A configuration, a traffic mix and a per-layer metric dropped in as
    new files, with their entries in BENCHMARK.json, run with no code
    edited."""
    here = tmp_path / "benchmarks"
    shutil.copytree(cells.HERE, here, ignore=shutil.ignore_patterns("_trace", "__pycache__", "tests"))
    cfg = json.load(open(here / "configs" / "sched-perf-basic-5k.json"))
    cfg["name"] = "tiny-basic"
    cfg["nodes"]["count"], cfg["init_pods"]["count"], cfg["measure_pods"]["count"] = 64, 8, 96
    cfg["expect_kernels"], cfg["identity_sample"] = ["fastpath.static_eval"], 12
    json.dump(cfg, open(here / "configs" / "tiny-basic.json", "w"))
    mix = json.load(open(here / "traffic" / "backlog.json"))
    mix["what"] = "the same mix under another name"
    json.dump(mix, open(here / "traffic" / "backlog-k12.json", "w"))
    metric = {"name": "loop.commit_s_per_kpod.backlog", "layer": "scheduling loop", "unit": "s/kpod",
              "better": "lower", "moves": "pods_per_s", "source": "program_span",
              "reader": "phase", "params": {"phases": ["commit"]}}
    json.dump(metric, open(here / "layer_metrics" / "loop.commit_s_per_kpod.backlog.json", "w"))
    bench = copy.deepcopy(cells.benchmark())
    bench["workloads"].append({"name": "tiny.backlog-k12", "config": "tiny-basic",
                               "traffic": "backlog-k12", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.backlog-k12")
    bench["per_layer"].append({k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
                              | {"workloads": ["tiny.backlog-k12"]})
    monkeypatch.setattr(cells, "HERE", str(here))
    cell = cells.cell("tiny.backlog-k12", bench)
    assert cell["config"]["name"] == "tiny-basic" and cell["traffic"]["what"].startswith("the same mix")
    res = runner.run_cell(cell, bench, 4, 10.0, True, time.perf_counter(), require_chip=False)
    assert res["correct"] is True and res["attempted"] == 96
    assert set(res["metrics"]) == {"loop.commit_s_per_kpod.backlog"}
    with pytest.raises(FileNotFoundError):
        cells.cell("x", {"workloads": [{"name": "x", "config": "nope", "traffic": "backlog", "chips": 1}]})
    with pytest.raises(FileNotFoundError):
        cells.traffic_kind("nope")
    with pytest.raises(KeyError):
        cells.cell("nope", bench)
