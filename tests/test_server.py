"""Server tier: serving endpoints, leader election, cache debugger.

Matches cmd/kube-scheduler/app/server.go:163-318 (healthz/readyz/metrics/
configz serving, Lease-based leader election where exactly ONE replica
schedules and a lost lease hands over) and backend/cache/debugger (dump +
cache-vs-informer comparer).
"""

import gc
import time
import urllib.request


from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.server import LeaseElector, SchedulerServer
from kubernetes_tpu.testing.fake_cluster import FakeCluster


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return r.status, r.read().decode()


def _env():
    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for i in range(4):
        api.create_node(
            Node(
                name=f"n{i}",
                labels={"kubernetes.io/hostname": f"n{i}"},
                capacity=Resource.from_map({"cpu": "4", "memory": "8Gi"}),
            )
        )
    return api, sched


def test_endpoints_serve():
    api, sched = _env()
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        assert _get(server.port, "/healthz") == (200, "ok")
        assert _get(server.port, "/readyz") == (200, "ok")
        code, body = _get(server.port, "/metrics")
        assert code == 200 and "scheduler_" in body
        code, body = _get(server.port, "/configz")
        assert code == 200 and "batchSize" in body
        # schedule something through the running loop
        api.create_pod(
            Pod(name="p1", containers=[Container(requests={"cpu": "100m"})])
        )
        deadline = time.time() + 10
        while time.time() < deadline and "default/p1#" not in str(api.bindings):
            if any(True for _ in api.bindings):
                break
            time.sleep(0.05)
        assert api.bindings, "server loop did not schedule"
        code, body = _get(server.port, "/debug/cache")
        assert code == 200 and "cache dump" in body
    finally:
        server.stop()


def _served_backlog_bindings():
    """A backlog of mixed requests queued before the loop starts, drained
    by the served loop: {pod name: node}."""
    api, sched = _env()
    for i in range(48):
        api.create_pod(
            Pod(
                name=f"b{i}",
                uid=f"default/b{i}",
                containers=[
                    Container(
                        requests={
                            "cpu": f"{100 + 50 * (i % 5)}m",
                            "memory": f"{64 * (1 + i % 3)}Mi",
                        }
                    )
                ],
            )
        )
    server = SchedulerServer(sched, poll_interval_s=0.005)
    server.start()
    try:
        deadline = time.time() + 20
        while time.time() < deadline and len(api.bindings) < 48:
            time.sleep(0.01)
    finally:
        server.stop()
    assert len(api.bindings) == 48
    return dict(api.bindings)


def test_collector_policy_is_invisible_to_decisions(monkeypatch):
    from kubernetes_tpu.util.collector import YOUNG_THRESHOLD, LoopCollector

    thresholds = []
    engage = LoopCollector.engage

    def engage_and_note(self):
        engage(self)
        thresholds.append(gc.get_threshold())

    monkeypatch.setattr(LoopCollector, "engage", engage_and_note)
    engaged = _served_backlog_bindings()
    assert thresholds and thresholds[0][0] == YOUNG_THRESHOLD
    # the same backlog with the policy released: the interpreter's defaults
    monkeypatch.setattr(
        LoopCollector, "engage", lambda self: thresholds.append(gc.get_threshold())
    )
    released = _served_backlog_bindings()
    assert thresholds[-1][0] != YOUNG_THRESHOLD and gc.get_freeze_count() == 0
    assert engaged == released


def test_leader_election_exactly_one_schedules():
    api, s1 = _env()
    s2 = Scheduler()
    api.watch_nodes(s2.on_node_add, s2.on_node_update, s2.on_node_delete)
    api.watch_pods(s2.on_pod_add, s2.on_pod_update, s2.on_pod_delete)
    s2.binding_sink = api.bind

    e1 = LeaseElector(api.lease_store, "replica-1", retry_period_s=0.05)
    e2 = LeaseElector(api.lease_store, "replica-2", retry_period_s=0.05)
    srv1 = SchedulerServer(s1, elector=e1)
    srv2 = SchedulerServer(s2, elector=e2)
    srv1.start()
    time.sleep(0.2)  # let replica-1 take the lease
    srv2.start()
    try:
        for i in range(6):
            api.create_pod(
                Pod(
                    name=f"p{i}",
                    containers=[Container(requests={"cpu": "100m"})],
                )
            )
        deadline = time.time() + 10
        while time.time() < deadline and len(api.bindings) < 6:
            time.sleep(0.05)
        assert len(api.bindings) == 6
        leaders = [srv1.is_leading(), srv2.is_leading()]
        assert leaders.count(True) == 1, leaders
        # only the leader performed scheduling work
        assert (s1.metrics["scheduled"] > 0) != (s2.metrics["scheduled"] > 0)
    finally:
        srv1.stop()
        srv2.stop()


def test_leader_failover():
    api, s1 = _env()
    s2 = Scheduler()
    api.watch_nodes(s2.on_node_add, s2.on_node_update, s2.on_node_delete)
    api.watch_pods(s2.on_pod_add, s2.on_pod_update, s2.on_pod_delete)
    s2.binding_sink = api.bind
    e1 = LeaseElector(
        api.lease_store, "replica-1", lease_duration_s=0.3, retry_period_s=0.05
    )
    e2 = LeaseElector(
        api.lease_store, "replica-2", lease_duration_s=0.3, retry_period_s=0.05
    )
    srv1 = SchedulerServer(s1, elector=e1)
    srv2 = SchedulerServer(s2, elector=e2)
    srv1.start()
    time.sleep(0.2)
    srv2.start()
    try:
        assert srv1.is_leading()
        srv1.stop()  # leader exits (releases the lease)
        deadline = time.time() + 5
        while time.time() < deadline and not srv2.is_leading():
            time.sleep(0.05)
        assert srv2.is_leading()
        api.create_pod(
            Pod(name="after", containers=[Container(requests={"cpu": "100m"})])
        )
        deadline = time.time() + 10
        while time.time() < deadline and not api.bindings:
            time.sleep(0.05)
        assert api.bindings and s2.metrics["scheduled"] >= 1
    finally:
        srv2.stop()


def test_cache_debugger_compare_finds_divergence():
    api, sched = _env()
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    # inject a ghost node directly into the cache (bypassing the informer)
    sched.cache.add_node(
        Node(name="ghost", capacity=Resource.from_map({"cpu": "1"}))
    )
    problems = server.debugger.compare()
    assert any("ghost" in p for p in problems), problems
    dump = server.debugger.dump()
    assert "cache dump" in dump and "n0" in dump
