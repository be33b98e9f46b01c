"""A traced CPU rehearsal of each cell reports every per-layer metric that
reads the program's spans: the ``phase`` ones from the accumulator's window
diff, the ``xspan`` ones from the profiler's host plane.  Without a device
plane the ``scope`` metrics and the two ``idle_*`` ones find nothing and
say nothing, by design."""

import time

import pytest

from benchmarks import cells, runner

SIZES = (512, 2048, 100)  # nodes, measured pods, init pods

SPAN_METRICS = {
    "served.bind_sink_s_per_kpod.backlog",
    "served.bind_queue_wait_s_per_kpod.backlog",
    "served.bind_lock_wait_s_per_kpod.backlog",
    "loop.lock_wait_s_per_kpod.backlog",
    "served.apiserver_bindings_s_per_kpod.backlog",
    "served.bind_wall_s_per_kpod.backlog",
    "served.drain_tail_s_per_kpod.backlog",
}
NEED_A_DEVICE_PLANE = ("kernels.scoped_share", "kernels.stage_ms_per_kpod", "device.idle_")


@pytest.mark.parametrize("name", ["basic-5k.backlog", "spread-5k.backlog"])
def test_traced_rehearsal_reports_the_span_metrics(name):
    bench = cells.benchmark()
    cell = cells.cut(cells.cell(name, bench), *SIZES)
    res = runner.run_cell(cell, bench, 7, 30.0, True, time.perf_counter(), require_chip=False)
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert SPAN_METRICS <= set(got), sorted(SPAN_METRICS - set(got))
    assert not [m for m in got if m.startswith(NEED_A_DEVICE_PLANE)]
    v = {m: got[m]["value"] for m in got}
    # the parts of bind fit in bind; the server's side of the sink fits in the sink
    assert v["served.bind_sink_s_per_kpod.backlog"] + v["served.bind_lock_wait_s_per_kpod.backlog"] \
        <= v["served.bind_s_per_kpod.backlog"]
    assert 0 < v["served.apiserver_bindings_s_per_kpod.backlog"] <= v["served.bind_sink_s_per_kpod.backlog"]
    assert 0 < v["served.bind_wall_s_per_kpod.backlog"] <= v["served.bind_s_per_kpod.backlog"] * 1.001
    assert v["served.drain_tail_s_per_kpod.backlog"] > 0


def test_every_listed_metric_has_its_files():
    bench = cells.benchmark()
    for w in bench["workloads"]:
        specs = cells.layer_metrics(w["name"], bench)
        assert {s["name"] for s in specs} == {
            m["name"] for m in bench["per_layer"] if w["name"] in m.get("workloads", [w["name"]])}
        for s in specs:
            entry = next(m for m in bench["per_layer"] if m["name"] == s["name"])
            assert {k: s[k] for k in ("unit", "better", "source", "layer", "moves")} == {
                k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
