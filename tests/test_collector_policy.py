"""The serving loop owns the collector (kubernetes_tpu/util/collector.py).

While a ``SchedulerServer`` leads, the cluster state is frozen out of the
garbage collector's walk, the young generation is sized for a batch, a full
collection runs at the loop's idle point and otherwise only past a ceiling;
``stop()`` puts the interpreter back as it was.  The pytest worker that
runs this file runs hundreds of other tests: every case leaves
``gc.get_threshold()`` and ``gc.get_freeze_count()`` as a released process
reads them.
"""

import gc
import threading
import time
import weakref

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.metrics import PhaseAccumulator, SchedulerMetrics
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.server import LeaseElector, SchedulerServer
from kubernetes_tpu.testing.fake_cluster import FakeCluster
from kubernetes_tpu.util import collector
from kubernetes_tpu.util.collector import (
    FULL_CEILING,
    UNFREEZE_EVERY,
    YOUNG_THRESHOLD,
    LoopCollector,
)


class Seen:
    """The test's own ``gc.callbacks`` entry."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.collections[info["generation"]] += 1
            self.seconds[info["generation"]] += time.perf_counter() - self._t0


@pytest.fixture
def seen():
    before, frozen = gc.get_threshold(), gc.get_freeze_count()
    s = Seen()
    gc.callbacks.append(s)
    try:
        yield s
    finally:
        gc.callbacks.remove(s)
        # whatever a failing case left engaged must not reach the next test
        while collector.engaged():
            collector._release()
        assert gc.get_threshold() == before
        # 0 once a policy was released; a fresh interpreter starts with a
        # few hundred objects of its own frozen
        assert gc.get_freeze_count() in (0, frozen)


def _env(n_nodes=4):
    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for i in range(n_nodes):
        api.create_node(
            Node(
                name=f"n{i}",
                labels={"kubernetes.io/hostname": f"n{i}"},
                capacity=Resource.from_map({"cpu": "8", "memory": "16Gi"}),
            )
        )
    return api, sched


def _pods(api, n, prefix="p"):
    for i in range(n):
        api.create_pod(
            Pod(name=f"{prefix}{i}", containers=[Container(requests={"cpu": "100m"})])
        )


@pytest.fixture(scope="module", autouse=True)
def warm_process():
    """The cases below wait, ten seconds at most, on a drain and count the
    collections and idle passes around it.  A process's FIRST drain loads or
    compiles its programs; where this file opens a worker's run beside five
    other cold workers that takes longer than the waits allow and allocates
    enough for passes of its own.  One drain of each size the cases use,
    before any of them, makes them independent of where the file falls."""
    for n in (20, 8, 10):
        api, sched = _env()
        _pods(api, n)
        sched.schedule_pending()
        sched.wait_for_bindings()
        assert len(api.bindings) == n


def _churn():
    """More than a young generation's worth of cyclic garbage: the
    interpreter collects at least once, so an idle pass is due."""
    for _ in range(YOUNG_THRESHOLD + YOUNG_THRESHOLD // 4):
        a = []
        a.append(a)


def _wait(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _passes(sched):
    return sched.phases.snapshot().get("gc.idle_passes", 0)


def test_engage_and_release_restore_thresholds_and_unfreeze(seen):
    before = gc.get_threshold()
    api, sched = _env()
    server = SchedulerServer(sched)
    assert collector.engaged() == 0
    server.start()
    try:
        assert collector.engaged() == 1
        assert gc.get_threshold() == (YOUNG_THRESHOLD, before[1], FULL_CEILING)
        assert gc.get_freeze_count() > 0  # the cluster state, off the walk
        assert gc.isenabled()
    finally:
        server.stop()
    assert collector.engaged() == 0
    assert gc.get_threshold() == before
    assert gc.get_freeze_count() == 0
    assert collector._on_gc not in gc.callbacks
    server.collector.release()  # idempotent
    assert collector.engaged() == 0


def test_two_servers_in_one_process_share_one_engagement(seen):
    before = gc.get_threshold()
    _, s1 = _env()
    _, s2 = _env()
    srv1, srv2 = SchedulerServer(s1), SchedulerServer(s2)
    srv1.start()
    srv2.start()
    try:
        assert collector.engaged() == 2
        raised = gc.get_threshold()
        srv1.stop()
        # the other loop still leads: nothing is put back yet
        assert collector.engaged() == 1
        assert gc.get_threshold() == raised != before
        assert gc.get_freeze_count() > 0
    finally:
        srv1.stop()
        srv2.stop()
    assert collector.engaged() == 0
    assert gc.get_threshold() == before
    assert gc.get_freeze_count() == 0


def test_only_the_leader_engages_and_a_lost_lease_releases(seen):
    api, s1 = _env()
    s2 = Scheduler()
    api.watch_nodes(s2.on_node_add, s2.on_node_update, s2.on_node_delete)
    api.watch_pods(s2.on_pod_add, s2.on_pod_update, s2.on_pod_delete)
    s2.binding_sink = api.bind
    e1 = LeaseElector(api.lease_store, "r1", lease_duration_s=3.0, retry_period_s=0.05)
    e2 = LeaseElector(api.lease_store, "r2", lease_duration_s=3.0, retry_period_s=0.05)
    srv1, srv2 = SchedulerServer(s1, elector=e1), SchedulerServer(s2, elector=e2)
    srv1.start()
    try:
        assert _wait(lambda: srv1.collector._engaged)
        srv2.start()
        time.sleep(0.2)
        assert not srv2.collector._engaged and collector.engaged() == 1
        srv1.stop()  # the leader goes: its engagement goes with it
        assert not srv1.collector._engaged
        assert _wait(lambda: srv2.collector._engaged)
        assert collector.engaged() == 1
        # the lease is taken away under the new leader: it releases
        srv2._is_leader.clear()
        e2.try_acquire_or_renew = lambda: False
        assert _wait(lambda: not srv2.collector._engaged)
        assert collector.engaged() == 0 and gc.get_freeze_count() == 0
    finally:
        srv1.stop()
        srv2.stop()


def test_no_full_collection_and_no_idle_pass_over_a_queued_backlog(seen):
    api, sched = _env()
    server = SchedulerServer(sched, poll_interval_s=0.005)
    real = sched.schedule_pending
    sched.schedule_pending = lambda: []  # a loop that cannot get to its queue
    _pods(api, 20)
    server.start()
    try:
        _churn()
        time.sleep(4 * collector.IDLE_SETTLE_S)
        assert sched.queue._active, "the backlog is still queued"
        assert seen.collections[0] >= 1, "a pass would have been due"
        assert seen.collections[2] == 0
        assert _passes(sched) == 0
        # the loop reaches its queue: the backlog drains, then the pass runs
        sched.schedule_pending = real
        assert _wait(lambda: len(api.bindings) == 20)
        assert _wait(lambda: _passes(sched) == 1)
        assert "gc.idle_pass" in sched.phases.snapshot()
        assert seen.collections[2] == 1
        assert "gc.full_under_load" not in sched.phases.snapshot()
    finally:
        server.stop()


def test_binds_in_flight_are_not_idle(seen):
    api, sched = _env()
    gate, entered = threading.Event(), threading.Event()

    def blocking_sink(pod, node_name):
        entered.set()
        gate.wait(timeout=30)
        api.bind(pod, node_name)

    sched.binding_sink = blocking_sink
    sched.binding_sink_many = None
    server = SchedulerServer(sched, poll_interval_s=0.005)
    server.start()
    try:
        _pods(api, 8)
        assert entered.wait(timeout=10)
        _churn()
        time.sleep(4 * collector.IDLE_SETTLE_S)
        assert not sched.queue._active and not api.bindings
        assert seen.collections[0] >= 1
        assert seen.collections[2] == 0
        assert _passes(sched) == 0
        gate.set()
        assert _wait(lambda: len(api.bindings) == 8)
        # queue and _inflight_binds empty: the idle pass runs, once
        assert _wait(lambda: _passes(sched) == 1)
        time.sleep(3 * collector.IDLE_SETTLE_S)
        assert _passes(sched) == 1, "no pass without allocations since the last"
        assert seen.collections[2] == 1
    finally:
        gate.set()
        server.stop()


def _direct(monkeypatch):
    """The policy driven without a server, every idle poll settled."""
    monkeypatch.setattr(collector, "IDLE_SETTLE_S", 0.0)
    phases = PhaseAccumulator()
    return LoopCollector(phases), phases


class _Knot:
    def __init__(self):
        self.me = self


def test_a_cycle_frozen_and_then_dropped_is_reclaimed_within_unfreeze_every_passes(
    seen, monkeypatch
):
    lc, phases = _direct(monkeypatch)
    at_engage = _Knot()
    ref_engage = weakref.ref(at_engage)
    lc.engage()
    try:
        del at_engage  # start-up garbage, frozen at engage
        assert ref_engage() is not None
        _churn()
        assert lc.poll(busy=False)  # the first pass unfreezes
        assert ref_engage() is None
        # the worst case: frozen by the first pass, dropped right after it
        knot = _Knot()
        ref = weakref.ref(knot)
        _churn()
        assert lc.poll(busy=False)
        del knot
        for n in range(3, UNFREEZE_EVERY + 2):
            assert ref() is not None, f"frozen: pass {n - 1} did not walk it"
            _churn()
            assert lc.poll(busy=False)
        assert ref() is None
        assert phases.snapshot()["gc.idle_passes"] == UNFREEZE_EVERY + 1
    finally:
        lc.release()


def test_a_pass_is_abandoned_when_work_arrived_and_waits_for_the_loop_to_settle(seen):
    phases = PhaseAccumulator()
    lc = LoopCollector(phases)
    lc.engage()
    try:
        _churn()
        assert not lc.poll(busy=False)  # idle, but not for IDLE_SETTLE_S yet
        time.sleep(1.5 * collector.IDLE_SETTLE_S)
        assert not lc.poll(busy=True)  # work arrived: the wait starts over
        assert not lc.poll(busy=False)
        time.sleep(1.5 * collector.IDLE_SETTLE_S)
        assert lc.poll(busy=False)
        assert not lc.poll(busy=False)  # nothing allocated since
        assert phases.snapshot()["gc.idle_passes"] == 1
        assert seen.collections[2] == 1
    finally:
        lc.release()


def test_a_loop_that_is_never_idle_still_collects_past_the_ceiling(seen):
    gc.collect()  # the interpreter's own quarter rule starts from this heap
    full_before = seen.collections[2]
    phases = PhaseAccumulator()
    lc = LoopCollector(phases)
    lc.engage()
    kept = []
    try:
        frozen = gc.get_freeze_count()
        # the ceiling: FULL_CEILING + 1 middle passes, each after 12 young ones
        ceiling = (FULL_CEILING + 1) * 12 * YOUNG_THRESHOLD
        while seen.collections[2] == full_before and len(kept) < 4 * ceiling:
            kept.extend([] for _ in range(YOUNG_THRESHOLD))
            lc.poll(busy=True)
        assert seen.collections[2] == full_before + 1, len(kept)
        assert len(kept) >= ceiling - 2 * YOUNG_THRESHOLD, "not before the ceiling"
        # it walked what was allocated since, not the frozen state
        assert 0 < gc.get_freeze_count() <= frozen
        lc.poll(busy=True)
        assert phases.snapshot()["gc.full_under_load"] == 1
        assert "gc.idle_passes" not in phases.snapshot()
    finally:
        del kept
        lc.release()


def test_counters_add_up_to_what_the_callback_saw(monkeypatch):
    lc, phases = _direct(monkeypatch)
    prom = SchedulerMetrics()
    seen = Seen()  # registered while engaged only: it sees what the policy sees
    lc.engage()
    gc.callbacks.append(seen)
    try:
        _churn()
        assert lc.poll(busy=False)
        _churn()
        lc.sync_registry(prom)
        # frozen objects still die by refcount, so not to the object
        assert prom.gc_frozen_objects.value() == pytest.approx(
            gc.get_freeze_count(), rel=0.01
        )
        assert prom.gc_frozen_objects.value() > 0
    finally:
        gc.callbacks.remove(seen)
        lc.release()
    lc.sync_registry(prom)
    lc.sync_registry(prom)  # a second scrape adds nothing
    for g in (0, 1, 2):
        load = prom.gc_collections.value(generation=g, when="load")
        idle = prom.gc_collections.value(generation=g, when="idle")
        assert load + idle == seen.collections[g], (g, load, idle, seen.collections)
        pause = prom.gc_pause_seconds.value(generation=g)
        assert pause == pytest.approx(seen.seconds[g], rel=0.25, abs=0.005)
    assert prom.gc_collections.value(generation=2, when="idle") == 1
    assert prom.gc_collections.value(generation=2, when="load") == 0
    assert prom.gc_collections.value(generation=0, when="load") >= 2
    assert prom.gc_frozen_objects.value() == 0
    assert phases.snapshot() == {
        "gc.idle_pass": pytest.approx(seen.seconds[2], rel=0.25, abs=0.005),
        "gc.idle_passes": 1,
    }
    assert gc.get_freeze_count() == 0


def test_the_scrape_serves_the_three_series(seen):
    import urllib.request

    api, sched = _env()
    server = SchedulerServer(sched)
    server.start()
    try:
        _churn()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=5
        ) as r:
            body = r.read().decode()
    finally:
        server.stop()
    assert 'scheduler_tpu_gc_collections_total{generation="0",when="load"}' in body
    assert 'scheduler_tpu_gc_pause_seconds_total{generation="0"}' in body
    frozen = [
        ln for ln in body.splitlines() if ln.startswith("scheduler_tpu_gc_frozen_objects ")
    ]
    assert frozen and float(frozen[0].split()[1]) > 0


def test_a_scheduler_without_a_server_leaves_the_interpreter_alone(seen):
    before, frozen = gc.get_threshold(), gc.get_freeze_count()
    api, sched = _env()
    _pods(api, 10)
    sched.schedule_pending()
    sched.wait_for_bindings()
    assert len(api.bindings) == 10
    assert gc.get_threshold() == before
    assert gc.get_freeze_count() == frozen
    assert collector.engaged() == 0


def test_gc_disable_appears_nowhere_in_the_package():
    import pathlib

    import kubernetes_tpu

    root = pathlib.Path(kubernetes_tpu.__file__).parent
    hits = [
        str(p) for p in root.rglob("*.py")
        if "gc.disable" in p.read_text()
    ]
    assert hits == []
