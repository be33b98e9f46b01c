"""A wave batch commits and binds as ONE run (PR 50).

Where a wave batch's commits may skip the per-pod walk (``_wave_resolve``'s
verdict: lean binds, no host Filter relevant to a batch pod, no Reserve or
Permit that could act, no extender), ALL its placed pods are one
``_commit_fast_bulk`` run and one ``_BulkBindTask``, which ``_submit_binds``
slices across the workers: at least 1,024 pods a slice where the API tier
installed ``binding_sink_many``, at most 64 where the sink is per pod.  No
sweep of the batch's own terms is made, so a batch of many disjoint
Deployments (until PR 50: one run and one task a Deployment) costs what a
batch of one template costs.  The decisions are final before the commit:
what is held here is that the cache, the binds and the ``Scheduled`` events
are what the per-pod path leaves.
"""

import copy

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, LabelSelector, Node, Pod, TopologySpreadConstraint
from kubernetes_tpu.events import EventBroadcaster
from kubernetes_tpu.framework import config as cfg
from kubernetes_tpu.oracle.pipeline import schedule_one
from kubernetes_tpu.oracle.state import OracleState
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.fake_cluster import FakeCluster

BATCH, DEPLOYMENTS, NODES = 48, 24, 12


def _nodes():
    return [
        Node(
            name=f"n{i}",
            labels={"kubernetes.io/hostname": f"n{i}", "topology.kubernetes.io/zone": f"z{i % 3}"},
            capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110}),
        )
        for i in range(NODES)
    ]


def _pod(name, owner, cpu="100m"):
    """A replica of the Deployment ``owner`` under the two default SOFT
    spread constraints over its own selector (``cl2load-5k``'s pod)."""
    sel = LabelSelector(match_labels={"name": owner})
    return Pod(
        name=name,
        labels={"name": owner},
        topology_spread_constraints=(
            TopologySpreadConstraint(max_skew=3, topology_key="kubernetes.io/hostname",
                                     when_unsatisfiable="ScheduleAnyway", label_selector=sel),
            TopologySpreadConstraint(max_skew=5, topology_key="topology.kubernetes.io/zone",
                                     when_unsatisfiable="ScheduleAnyway", label_selector=sel),
        ),
        containers=[Container(name="c", requests={"cpu": cpu, "memory": "64Mi"})],
    )


def _pods(n, too_large=()):
    """``n`` pods of 24 disjoint Deployments in queue order; those at the
    positions ``too_large`` fit no node."""
    return [_pod(f"p{i}", f"d{i % DEPLOYMENTS}", cpu="64" if i in too_large else "100m") for i in range(n)]


class _Drain:
    """One scheduler against a FakeCluster, with what the commit and the
    binding workers were handed written down."""

    def __init__(self, sink, wave=True, fail_bind=()):
        conf = cfg.SchedulerConfiguration(batch_size=BATCH)
        conf.wave_dispatch = wave
        self.api = api = FakeCluster()
        self.sched = sched = Scheduler(configuration=conf, event_broadcaster=EventBroadcaster())
        api.connect(sched)
        if sink == "bulk":
            def bind_many(items):
                res = api.bind_many([("missing" if p.name in fail_bind else p.uid, nn) for p, nn in items])
                return [None if r is None else r["error"] for r in res]

            sched.binding_sink_many = bind_many
        elif fail_bind:
            def bind(pod, node_name):
                if pod.name in fail_bind:
                    raise RuntimeError("apiserver 500")
                api.bind(pod, node_name)

            sched.binding_sink = bind
        self.runs, self.offered, self.slices, self.chunks, self.failed = [], [], [], [], []
        self._wrap("_commit_fast_bulk", lambda *a, idxs=None, **k: self.runs.append(list(idxs if idxs is not None else range(a[4], a[5]))))
        self._wrap("_submit_binds", lambda *a, **k: self.offered.append([len(t.items) for t in sched._bulk_bind_buffer]))
        self._wrap("_binding_bulk", lambda t: self.slices.append(len(t.items)))
        self._wrap("_binding_chunk", lambda part: self.chunks.append(len(part)))
        self._wrap("_post_filter_or_fail", lambda fwk, state, qp, *a, **k: self.failed.append(qp.pod.name))
        for n in _nodes():
            api.create_node(n)

    def _wrap(self, name, note):
        inner = getattr(self.sched, name)

        def wrapped(*a, **k):
            note(*a, **k)
            return inner(*a, **k)

        setattr(self.sched, name, wrapped)

    def drain(self, pods):
        for p in copy.deepcopy(pods):
            self.api.create_pod(p)
        return self.sched.schedule_pending()

    def left(self):
        """The state a drain leaves: the cache's pods by node, what is still
        only assumed, the store's bindings and the Scheduled events."""
        cache = self.sched.cache
        by_uid = {p.uid: p.name for p in self.api.pods.values()}
        return {
            "cache": {name: sorted(p.name for p in cn.pods.values()) for name, cn in cache.nodes.items()},
            "assumed": sorted(cache.assumed),
            "bindings": {by_uid[uid]: node for uid, node in self.api.bindings.items()},
            "scheduled": sorted(e.note for e in self.api.list_events("Scheduled")),
        }


def _serial_oracle(pods):
    state = OracleState.build(_nodes())
    want = {}
    for pod in copy.deepcopy(pods):
        r = schedule_one(pod, state)
        want[pod.name] = r.node
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    return want


def _slices(n, workers):
    per = min(64, max(1, -(-n // workers)))
    return [min(per, n - lo) for lo in range(0, n, per)]


@pytest.mark.parametrize("sink", ["bulk", "per_pod"])
@pytest.mark.parametrize("n_pods", [BATCH, 2 * BATCH], ids=["the-direct-wave", "a-direct-then-a-chained-wave"])
def test_a_batch_of_many_disjoint_deployments_is_one_commit_run_and_one_bind_task(sink, n_pods):
    """48 pods of 24 selectors: 24 probes x 48 pods is far under the cap at
    which the interaction sweep gave up, so until PR 50 this batch was 24
    runs and 24 bind tasks of two pods."""
    pods = _pods(n_pods)
    d = _Drain(sink)
    outs = d.drain(pods)
    batches = n_pods // BATCH
    window = d.sched.phases.snapshot()
    assert window["route.direct"] == BATCH and window.get("route.chained", 0) == n_pods - BATCH
    assert d.runs == [list(range(BATCH))] * batches  # every placed pod of the batch, in batch order
    assert d.offered == [[BATCH]] * batches  # ONE task a flush ...
    workers = d.sched.config.parallelism
    # ... cut for the workers: one slice under a bulk sink, slices of <= 64 (here 3) under a per-pod one
    assert d.slices == ([BATCH] if sink == "bulk" else _slices(BATCH, workers)) * batches and not d.chunks
    assert window["bind.tasks"] == len(d.slices)
    assert "wave_groups" not in d.sched.metrics
    # the same decisions, cache, binds and events as the serial oracle and as the per-pod commit path
    want = _serial_oracle(pods)
    assert {o.pod.name: o.node for o in outs} == want and None not in want.values()
    got = d.left()
    assert got["bindings"] == want and not got["assumed"]
    assert got["cache"] == {n.name: sorted(p for p, node in want.items() if node == n.name) for n in _nodes()}
    assert got["scheduled"] == sorted(f"Successfully assigned default/{p} to {node}" for p, node in want.items())
    per_pod = _Drain("per_pod", wave=False)
    per_pod.drain(pods)
    assert not per_pod.runs and per_pod.chunks and per_pod.left() == got


def test_failures_walk_the_per_pod_path_in_batch_order_and_the_successes_are_one_run():
    too_large = (3, 17, 18, 40)
    pods = _pods(BATCH, too_large)
    d = _Drain("bulk")
    outs = d.drain(pods)
    assert d.failed == [f"p{i}" for i in too_large]  # one walk a failing pod, in batch order
    placed = [i for i in range(BATCH) if i not in too_large]
    assert d.runs == [placed] and d.offered == [[len(placed)]] and d.slices == [len(placed)]
    want = _serial_oracle(pods)
    assert {o.pod.name: o.node for o in outs} == want
    assert [name for name, node in want.items() if node is None] == d.failed
    got = d.left()
    assert got["bindings"] == {p: node for p, node in want.items() if node is not None} and not got["assumed"]
    assert d.sched.metrics["unschedulable"] == len(too_large)
    assert d.sched.phases.snapshot()["bind.tasks"] == 1


@pytest.mark.parametrize("sink", ["bulk", "per_pod"])
def test_a_bind_failure_of_one_pod_in_the_single_task_unwinds_that_pod_alone(sink):
    pods = _pods(BATCH)
    d = _Drain(sink, fail_bind={"p7"})
    outs = {o.pod.name: o for o in d.drain(pods)}
    want = _serial_oracle(pods)
    assert d.runs == [list(range(BATCH))] and d.offered == [[BATCH]]
    assert outs["p7"].node is None and not outs["p7"].status.ok
    assert {name: o.node for name, o in outs.items() if name != "p7"} == {p: n for p, n in want.items() if p != "p7"}
    got = d.left()
    # forgotten by the cache and requeued; every other pod is bound, finished and has its event
    assert "p7" not in got["bindings"] and not got["assumed"]
    assert "p7" not in [p for names in got["cache"].values() for p in names]
    assert sum(len(names) for names in got["cache"].values()) == BATCH - 1 == len(got["scheduled"])
    assert d.sched.metrics["scheduled"] == BATCH - 1 and d.sched.metrics["errors"] == 1
    assert len(d.sched.queue) == 1


@pytest.mark.parametrize("sink,wave,n_pods", [
    ("bulk", True, 2 * BATCH), ("per_pod", True, 2 * BATCH), ("per_pod", True, BATCH + 5), ("per_pod", False, 2 * BATCH),
], ids=["bulk-sink", "per-pod-sink", "a-short-last-batch", "per-pod-commits"])
def test_bind_tasks_counts_the_futures_of_every_flush(sink, wave, n_pods):
    """``bind.tasks``: what ``_submit_binds`` handed to the pool, bulk
    slices and per-pod chunks alike, booked once a flush."""
    d = _Drain(sink, wave=wave)
    before = d.sched.phases.snapshot()
    d.drain(_pods(n_pods))
    window = d.sched.phases.diff(d.sched.phases.snapshot(), before)
    assert "bind.tasks" not in before and len(d.offered) == 2
    assert window["bind.tasks"] == len(d.slices) + len(d.chunks) > 0
    if wave:
        workers = d.sched.config.parallelism
        last = n_pods - BATCH
        assert d.slices == ([BATCH, last] if sink == "bulk" else _slices(BATCH, workers) + _slices(last, workers))
    else:
        assert not d.slices and sum(d.chunks) == n_pods
