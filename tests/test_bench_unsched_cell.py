"""``unsched-5k.backlog-pending-first`` at cut counts, whole, on the CPU: the
configuration ``benchmarks/configs/sched-perf-unschedulable-5k.json``
(upstream's ``:724`` Unschedulable) under the traffic kind
``backlog_pending_first`` through ``runner.run_cell``, EVERY position of the
window compared with the frozen reference (``benchmarks/reference/``).

What the cell is there to see is pinned here: pods that fit nowhere sit at the
head of the queue, are attempted once inside the window, fail, and stay
parked and unbound while every pod behind them binds; ``correct`` holds the
pending pods as it holds the bound ones (in the store, unbound, never
acknowledged, decided none where the reference decides none); the failure
path is visible as the span ``post_filter`` and the counter
``sched.unschedulable``, which the two per-layer metrics this cell adds read.

One run serves every case but the last: the counts are cut so that one
resident dispatch still takes the whole queue (24 + 1,024 pods, over
``fast_device_min``), as at the source's counts one takes 200 + 10,000.
"""

import time

import pytest

from benchmarks import cells, runner

CELL = "unsched-5k.backlog-pending-first"
NODES, PODS, PENDING = 256, 1024, 24
NEW_METRICS = ("loop.post_filter_s_per_kpod.backlog", "loop.unschedulable_per_kpod.backlog")


def _cell(bench):
    cell = cells.cut(cells.cell(CELL, bench), NODES, PODS, 0)
    cell["config"]["pending_pods"]["count"] = PENDING
    return cell


def _watch(seen):
    """A ``tamper`` that reads, from outside the runner, the window's own
    phase totals (the kind's ``window`` releases the loop once and holds it
    once) and the store as the runner read it back."""
    def tamper(cluster):
        seen["cluster"] = cluster
        phases = cluster.sched.phases
        release, hold, read_back = cluster.release_loop, cluster.hold_loop, cluster.read_back

        def release_loop():
            seen["phases0"] = phases.snapshot()
            release()

        def hold_loop():
            hold()
            seen.setdefault("phases1", phases.snapshot())

        def read():
            seen["store"] = read_back()
            return seen["store"]

        cluster.release_loop, cluster.hold_loop, cluster.read_back = release_loop, hold_loop, read

    return tamper


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    seen = {"at": []}

    def pending_positions(replay, pos, spec, decided, want):
        if spec["name"].startswith("pending-"):
            one_cpu = {**spec, "requests": {**spec["requests"], "cpu": "1"}}
            seen["at"].append((pos, decided, want, replay.choose(one_cpu)))

    res = runner.run_cell(
        _cell(bench), bench, 3400000007, 120.0, False, time.perf_counter(), require_chip=False,
        tamper=_watch(seen), identity_positions=list(range(PENDING + PODS)),
        on_identity_position=pending_positions,
    )
    cluster = seen.pop("cluster")  # the scheduler itself is let go
    seen["acked"] = cluster.snapshot_acked()
    seen["popped"] = cluster.snapshot_order()[1]
    seen["events"] = [e.regarding.name for e in cluster.api.list_events("FailedScheduling")]
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    return res, seen, bench


def test_the_cut_cell_runs_whole_binds_every_measured_pod_and_is_correct(run):
    res, _seen, _bench = run
    assert res["attempted"] == PODS and res["failed"] == 0
    assert set(res["metrics"]) == {"pods_per_s", "setup_s"}
    assert res["correct"] is True, {k: v for k, v in res["compared"].items() if not v["ok"]}


def test_the_program_equals_the_frozen_reference_at_every_position_pending_ones_too(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["identity.positions_compared"]["value"] == PENDING + PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    # the pending pods are the window's first positions: decided none, wanted none
    assert [(pos, decided, want) for pos, decided, want, _cut in seen["at"]] == \
        [(i, None, None) for i in range(PENDING)]


def test_every_pending_pod_is_in_the_store_unbound_and_never_acknowledged(run):
    res, seen, _bench = run
    uids = [f"default/pending-{i}" for i in range(PENDING)]
    assert all(u in seen["store"] and not seen["store"][u] for u in uids)
    assert not [u for u in uids if u in seen["acked"]]
    assert len(seen["acked"]) == PODS and sum(bool(n) for n in seen["store"].values()) == PODS
    got = res["compared"]
    assert got["guarantee.pods_missing_from_store"]["value"] == 0
    assert got["guarantee.decisions_not_read_back"] == {"value": 0, "limit": 0, "ok": True}


def test_the_pending_pods_pop_before_every_measured_pod_and_are_popped_once(run):
    _res, seen, _bench = run
    popped = seen["popped"]
    assert popped[:PENDING] == [f"default/pending-{i}" for i in range(PENDING)]
    assert len(popped) == PENDING + PODS == len(set(popped))  # no parked pod came back


def test_one_resident_dispatch_takes_the_queue_and_the_window_compiles_nothing(run):
    res, _seen, _bench = run
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]
    assert got["device.breaker_faults"]["value"] == got["device.device_faults_logged"]["value"] == 0


def test_the_window_books_the_failure_path_as_a_span_inside_commit_and_a_count(run):
    _res, seen, _bench = run
    window = seen["window"]
    assert window["sched.unschedulable"] == PENDING
    assert 0 < window["post_filter"] <= window["commit"]
    assert window.get("post_filter.lock_wait", 0.0) <= window["post_filter"]


def test_each_pending_pod_of_the_window_leaves_one_failed_scheduling_event(run):
    """What a failing pod sends to the API server: ONE ``FailedScheduling``
    event through the broadcaster's sink (``api.record_event``, in process);
    the warm-up's pending pods left theirs before they were deleted."""
    _res, seen, _bench = run
    for role in ("pending", "warm-pending"):
        assert sorted(n for n in seen["events"] if n.startswith(f"{role}-")) == \
            sorted(f"{role}-{i}" for i in range(PENDING))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_metrics_read_the_windows_phases_through_the_phase_reader(run, name):
    _res, seen, bench = run
    listed = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}
    others = {m["name"] for m in bench["per_layer"] if "basic-5k.backlog" in m.get("workloads", ())}
    assert set(listed) == others | set(NEW_METRICS)  # all of basic-5k's, and these two here only
    spec = listed[name]
    assert spec["reader"] == "phase" and spec["layer"] == "scheduling loop"
    ctx = {"phases": seen["window"], "pods_in_window": PODS}
    value = spec["read"](ctx, spec["params"])
    if name == "loop.unschedulable_per_kpod.backlog":
        assert value == 1000.0 * PENDING / PODS
        # at the source's counts, while no parked pod is retried in a window
        assert spec["read"]({"phases": {"sched.unschedulable": 200.0}, "pods_in_window": 10000},
                            spec["params"]) == 20.0
    else:
        assert value == seen["window"]["post_filter"] / (PODS / 1000.0) > 0
    # no window: nothing said.  A program without the span and the counter (the
    # parent) reads 0.0 through this reader and raises nothing
    assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None
    assert spec["read"]({"phases": {"commit": 1.0}, "pods_in_window": PODS}, spec["params"]) == 0.0


def test_control_the_reference_would_place_the_same_pending_pod_at_one_cpu(run):
    """The check tells the two faults apart: asked about the same pod with
    its request cut to 1 cpu, the reference wants a node at every pending
    position where the program decided none, so a program that fails what
    it must bind is seen, as one that binds what it must not (below)."""
    _res, seen, _bench = run
    assert len(seen["at"]) == PENDING
    assert all(cut and decided is None for _pos, decided, _want, cut in seen["at"])


def test_control_a_pending_pod_bound_in_the_store_is_not_correct():
    """One pending pod reads back bound (the read-back patched after the
    window): 9 cpu on a 4-cpu node can never pass feasibility, and the
    decision of none no longer equals the store."""
    bench = cells.benchmark()

    def bound_by_hand(cluster):
        read_back = cluster.read_back

        def read():
            return {**read_back(), "default/pending-3": cluster.nodes[0]["name"]}

        cluster.read_back = read

    res = runner.run_cell(
        _cell(bench), bench, 3400000011, 120.0, False, time.perf_counter(), require_chip=False,
        tamper=bound_by_hand, identity_positions=[0, PENDING],
    )
    assert res["attempted"] == PODS and res["failed"] == 0
    got = res["compared"]
    assert got["feasibility.overcommitted_node_resources"]["ok"] is False
    assert got["feasibility.overcommitted_node_resources"]["value"] >= 1
    assert got["guarantee.decisions_not_read_back"]["value"] == 1
    assert res["correct"] is False
