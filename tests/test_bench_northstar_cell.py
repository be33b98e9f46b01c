"""``northstar-10k.backlog`` at cut counts, whole, on the CPU: the
configuration ``benchmarks/configs/northstar-basic-10k.json`` (this
repository's ``BASELINE.json`` north star: 100,000 pending pods onto 10,000
nodes, ``pod-default`` / ``node-default`` of upstream's ``:51``) under the
traffic kind ``backlog`` through ``runner.run_cell``, EVERY position of the
window compared with the frozen reference (``benchmarks/reference/``).

What the cell is there to see is pinned here: ONE drain that is MORE THAN ONE
resident run.  At the cell's counts ``residentRunMax`` = 16,384 cuts the
65,536 measured pods into four runs (the source's 100,000 into seven; the
configuration's one cut); here the same option is set to 256 in the
test's scheduler and 1,024 pods make four too, so the resident usage state is
carried across three run boundaries while the earlier runs' binds are in
flight, and the loop pops, packs and commits beside binding.  The counter
``resident.runs`` (booked where a ``resident_run`` is dispatched) is what
the per-layer metric this cell adds reads.
"""

import time

import pytest

from benchmarks import cells, runner
from tests.test_bench_unsched_cell import _watch  # the window's own phase totals and the store as read back

CELL = "northstar-10k.backlog"
NODES, PODS, INIT = 256, 1024, 48
BATCH, RUN = 64, 256  # a pop batch of 64 (of 512), a resident run of at most 256 pods (of 16,384)
RUNS = PODS // RUN
NEW_METRIC = "loop.resident_runs_per_kpod.backlog"
KERNEL = "resident.resident_run"
# positions at which the control asks: the four after every run boundary
CONTROL_AT = [r * RUN + i for r in range(1, RUNS) for i in range(4)]


def _dispatches(sched):
    return {r["kernel"]: r["dispatches"] for r in sched.kernels.table(cost=False)}.get(KERNEL, 0)


class _RunNotCarried:
    """``on_position`` hook of ``correct.check_identity``: what the plain
    reference chooses at a position of run r when the placements of the
    runs BEFORE r are taken out of its state — a usage state that was not
    carried across the run boundary (the device's ``used`` / ``num_pods``
    rows re-read from the window's start instead of from the run before)."""

    def __init__(self) -> None:
        self.readings = []  # (position, decided, want, what the uncarried state chooses)

    def __call__(self, replay, pos, spec, decided, want) -> None:
        if pos not in CONTROL_AT:
            return
        held = replay.trail[: (pos // RUN) * RUN]
        for pod in held:
            replay.state.unplace(pod)
        try:
            self.readings.append((pos, decided, want, replay.choose(spec)))
        finally:
            for pod in held:
                replay.state.place(pod)


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    seen = {}
    control = _RunNotCarried()
    watch = _watch(seen)

    def tamper(cluster):
        cfg = cluster.sched.config
        cfg.batch_size = cfg.fast_device_min = BATCH
        cfg.resident_run_max = RUN  # the existing residentRunMax option, nothing new
        watch(cluster)
        release, hold = cluster.release_loop, cluster.hold_loop

        def release_loop():
            seen["dispatches0"] = _dispatches(cluster.sched)
            release()

        def hold_loop():
            hold()
            seen.setdefault("dispatches1", _dispatches(cluster.sched))

        cluster.release_loop, cluster.hold_loop = release_loop, hold_loop

    res = runner.run_cell(
        cells.cut(cells.cell(CELL, bench), NODES, PODS, INIT), bench, 4400000007, 120.0, False,
        time.perf_counter(), require_chip=False, tamper=tamper,
        identity_positions=list(range(PODS)), on_identity_position=control,
    )
    cluster = seen.pop("cluster")  # the scheduler itself is let go
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    seen["popped"] = cluster.snapshot_order()[1]
    seen["acked"] = cluster.snapshot_acked()
    seen["control"] = control.readings
    return res, seen, bench


def test_the_configuration_is_the_north_stars_with_its_one_cut():
    cfg = cells.cell(CELL)["config"]
    basic = cells.cell("basic-5k.backlog")["config"]
    assert (cfg["nodes"]["count"], cfg["measure_pods"]["count"], cfg["init_pods"]["count"]) == (10000, 4 * 16384, 2000)
    assert cfg["reduced"] == ["measure_pods"] and cfg["reduced_why"]  # the measured reason: the parent's window at 100,000
    assert cfg["identity_sample"] == 48 and cfg["expect_kernels"] == [KERNEL]
    # the one upstream row that is resource-only: its templates and guarantees, word for word
    for key in ("pod_templates", "guarantees"):
        assert cfg[key] == basic[key]
    assert {**cfg["nodes"], "count": 0} == {**basic["nodes"], "count": 0}


def test_the_cut_cell_runs_whole_binds_every_measured_pod_and_is_correct(run):
    res, seen, _bench = run
    assert res["attempted"] == PODS and res["failed"] == 0
    assert set(res["metrics"]) == {"pods_per_s", "setup_s"}
    assert len(seen["acked"]) == PODS and len(seen["store"]) == PODS + INIT
    assert res["correct"] is True, {k: v for k, v in res["compared"].items() if not v["ok"]}


def test_the_program_equals_the_frozen_reference_at_every_position_across_every_run_boundary(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["identity.positions_compared"]["value"] == PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert got["guarantee.decisions_not_read_back"] == {"value": 0, "limit": 0, "ok": True}
    assert len(seen["popped"]) == PODS == len(set(seen["popped"]))


def test_the_drain_spans_at_least_three_resident_runs_and_the_counter_equals_the_runs_dispatched(run):
    res, seen, _bench = run
    window = seen["window"]
    dispatched = seen["dispatches1"] - seen["dispatches0"]
    assert dispatched == RUNS >= 3
    assert window["resident.runs"] == dispatched
    assert window["route.fast"] == PODS and "route.chained" not in window
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]
    assert got["device.breaker_faults"]["value"] == got["device.device_faults_logged"]["value"] == 0


def test_control_a_usage_state_not_carried_across_a_run_boundary_differs_from_the_reference(run):
    """Had a run started from the usage the window opened on instead of the
    run before's, its first pods would go where the run before already put
    pods: at every position behind a boundary the uncarried state chooses
    another node than the reference — and than the program, which equals
    it — so ``correct`` would count it against the limit 0."""
    _res, seen, _bench = run
    assert [pos for pos, *_ in seen["control"]] == CONTROL_AT
    assert all(decided == want for _pos, decided, want, _stale in seen["control"])
    assert all(stale != want for _pos, _decided, want, stale in seen["control"])


def test_the_new_metric_reads_the_counter_through_the_phase_reader(run):
    _res, seen, bench = run
    listed = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}
    others = {m["name"] for m in bench["per_layer"] if "basic-5k.backlog" in m.get("workloads", ())}
    assert set(listed) == others | {NEW_METRIC}  # the resident set: all of basic-5k's
    spec = listed[NEW_METRIC]
    assert spec["reader"] == "phase" and spec["layer"] == "scheduling loop"
    assert spec["params"] == {"phases": ["resident.runs"]}
    value = spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"])
    assert value == RUNS / (PODS / 1000.0)
    # at the cell's counts: four runs for 65,536 pods (seven for the source's 100,000); one for basic-5k's 10,000
    assert spec["read"]({"phases": {"resident.runs": 4.0}, "pods_in_window": 65536}, spec["params"]) == 0.06103515625
    assert spec["read"]({"phases": {"resident.runs": 7.0}, "pods_in_window": 100000}, spec["params"]) == 0.07
    assert spec["read"]({"phases": {"resident.runs": 1.0}, "pods_in_window": 10000}, spec["params"]) == 0.1
    # no window: nothing said.  A program without the counter (the parent) reads 0.0 and raises nothing
    assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None
    assert spec["read"]({"phases": {"commit": 1.0}, "pods_in_window": PODS}, spec["params"]) == 0.0


@pytest.mark.parametrize("cell", ["basic-5k.backlog", "unsched-5k.backlog-pending-first",
                                  "mixedbase-5k.backlog-on-base", CELL])
def test_the_new_metric_is_listed_in_the_four_resident_cells_and_no_other(cell):
    bench = cells.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NEW_METRIC)
    assert cell in entry["workloads"] and len(entry["workloads"]) == 4
    assert entry["moves"] == "pods_per_s" and entry["source"] == "program_counter"
    assert NEW_METRIC in {s["name"] for s in cells.layer_metrics(cell, bench)}


# ---- what 100,000 pods broke in the program (PR 44) ------------------------------
# (1) The warm-up's 100,000 bound pods are deleted at once: 100,000 DELETED events past a
# watch window that held 4,096.  The reflector re-LISTs, and a re-LIST reports a vanished
# object AS IT LAST SAW IT — for a pod whose bind update it had not read yet, unassigned.
# (2) The same window sent a reflector a second behind the drain's bind updates to re-LIST
# the whole cluster in the middle of the measured drain; it now holds what upstream's
# watch cache grows to under a burst, 100 * 1024 events (the last case below).


def _assumed_and_bound(n_pods=6):
    """A scheduler whose pods are assumed with their binding finished, as
    after a drain; the informer has confirmed none of them."""
    from kubernetes_tpu.api.types import Container, Pod
    from tests.test_fast_gate import _mk

    sched, bindings = _mk(4)
    pods = [Pod(name=f"w{i}", containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})])
            for i in range(n_pods)]
    for p in pods:
        sched.on_pod_add(p)
    sched.schedule_pending()
    sched.wait_for_bindings()
    assert len(bindings) == n_pods and set(sched.cache.assumed) == {p.uid for p in pods}
    return sched, pods, bindings


@pytest.mark.parametrize("last_seen", ["unassigned", "bound"])
def test_an_assumed_pod_deleted_in_either_last_known_state_leaves_the_cache(last_seen):
    """Until PR 44 a DELETE whose object carried no node name only left the
    queue: the assumed pod stayed in the cache, holding its node's cpu and
    pod slot for good (assumed pods do not expire), and the harness's wait
    for the warm-up to go never ended."""
    import copy

    sched, pods, bindings = _assumed_and_bound()
    used = sum(len(cn.pods) for cn in sched.cache.nodes.values())
    assert used == len(pods)
    for p in pods:
        gone = copy.copy(p)
        gone.node_name = bindings[p.name] if last_seen == "bound" else ""
        sched.on_pod_delete(gone)
    assert not sched.cache.pod_states and not sched.cache.assumed
    assert sum(len(cn.pods) for cn in sched.cache.nodes.values()) == 0
    assert all(cn.requested.milli_cpu == 0 for cn in sched.cache.nodes.values())
    assert len(sched.queue) == 0


def test_a_pending_pod_deleted_unassigned_only_leaves_the_queue():
    """The other side of the same branch: a pod the cache does not hold."""
    from kubernetes_tpu.api.types import Container, Pod

    sched, _pods, _bindings = _assumed_and_bound(2)
    before = sched._external_mutations
    waiting = Pod(name="q0", containers=[Container(name="c", requests={"cpu": "100m"})])
    sched.on_pod_add(waiting)
    assert len(sched.queue) == 1
    sched.on_pod_delete(waiting)
    assert len(sched.queue) == 0 and len(sched.cache.pod_states) == 2
    assert sched._external_mutations == before  # nothing the device state rests on moved


def test_the_cut_cell_is_correct_when_the_warm_ups_deletion_overruns_the_watch_window(monkeypatch):
    """The served path at a watch window of 64 events (4,096 at the source's
    counts, where 100,000 deletions overrun it): the reflector re-LISTs while
    the warm-up's 4,096 bound pods go, every one of them leaves the
    scheduler's cache, and the window that follows is ``correct``.  The
    loop's sizes are left as they are: ONE run binds the warm-up faster than
    the reflector reads.  (On the program before PR 44 about a quarter of
    them stayed, assumed for good, and the harness's wait for them to go ran
    out: a race, so the two cases above are the ones that pin the repair.)"""
    import kubernetes_tpu.client.api_server as api_server

    pods = 4 * PODS
    bench = cells.benchmark()
    seen = {}
    monkeypatch.setattr(api_server._WatchCache.__init__, "__defaults__", (64,))
    res = runner.run_cell(
        cells.cut(cells.cell(CELL, bench), NODES, pods, INIT), bench, 4400000019, 120.0, False,
        time.perf_counter(), require_chip=False, tamper=lambda cluster: seen.update(cluster=cluster),
    )
    cluster = seen["cluster"]
    assert cluster.apiserver.caches["pods"].events.maxlen == 64
    assert cluster.source.informers["pods"]._reflector.relists > 1  # the window WAS overrun
    assert res["attempted"] == pods and res["failed"] == 0
    assert res["correct"] is True, {k: v for k, v in res["compared"].items() if not v["ok"]}
    assert len(cluster.sched.cache.pod_states) == pods + INIT  # no warm-up pod left behind


def test_a_watcher_a_whole_drain_behind_is_served_from_the_watch_window_not_sent_to_re_list():
    """The window holds a 65,536-pod drain's bind updates (upstream's cache
    grows to 100 * 1024 events under a burst): a reflector that has read
    none of them yet gets them all, in order; at the 4,096 the window used
    to hold it got 410 Gone and re-LISTed every pod mid-drain."""
    from kubernetes_tpu.client.api_server import WATCH_WINDOW, _WatchCache

    assert WATCH_WINDOW == 100 * 1024
    burst = 4 * 16384
    cache = _WatchCache()
    cache.record_many("MODIFIED", [({"kind": "Pod", "uid": f"p{i}"}, f"p{i}") for i in range(burst)])
    got = cache.since(0, timeout=0.0)
    assert got is not None and [e.rv for e in got] == list(range(1, burst + 1))
    assert cache.gone_total == 0
    small = _WatchCache(window=4096)
    small.record_many("MODIFIED", [({"kind": "Pod", "uid": f"p{i}"}, f"p{i}") for i in range(burst)])
    assert small.since(0, timeout=0.0) is None and small.gone_total == 1
