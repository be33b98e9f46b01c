"""The fast path's committer is as old as the MIRROR it was built from.

``Scheduler._try_dispatch_fast`` syncs the mirror under ``_mu``, then prepares
the batch OUTSIDE the lock (signature rows: a static eval, on a first batch a
compile) and only then builds the lineage (``_fast_dispatch``).  An informer
event that lands in between moves the counters the lineage key reads and not
the mirror; keyed by the counters, a committer that never saw the event passed
for current at every later batch — twelve pods parked on ``NodeResourcesFit``
beside four empty nodes, for good (the one-in-twenty stall of
``tests/test_shared_informer.py::test_two_consumers_one_stream_and_node_index``
beside busy processes, PR 45).  The event is delivered here at that very point.
"""

import time

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.scheduler import Scheduler


def _node(name, cpu):
    return Node(
        name=name,
        labels={"kubernetes.io/hostname": name},
        capacity=Resource.from_map({"cpu": cpu, "memory": "32Gi", "pods": 50}),
    )


def _drain_until(sched, done, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sched.schedule_pending()
        sched.wait_for_bindings()
        if done():
            return True
        time.sleep(0.05)
    return done()


@pytest.mark.parametrize("standing", [0, 1], ids=["empty-cluster", "one-full-node"])
def test_an_event_between_the_mirror_sync_and_the_lineage_build_is_not_lost(standing):
    sched = Scheduler()
    bound = {}
    sched.binding_sink = lambda pod, node: bound.__setitem__(pod.uid, node)
    for i in range(standing):
        sched.on_node_add(_node(f"small-{i}", "2"))
    for i in range(12):
        sched.on_pod_add(Pod(name=f"p{i}", containers=[Container(requests={"cpu": "1"})]))

    prepare, arrived = sched._fast_sig_rows, []

    def prepare_then_the_event_arrives(*args, **kwargs):
        rows = prepare(*args, **kwargs)
        if not arrived:  # once: the reflector's thread, between the two halves
            arrived.append(sched._external_mutations)
            for i in range(4):
                sched.on_node_add(_node(f"n{i}", "8"))
        return rows

    sched._fast_sig_rows = prepare_then_the_event_arrives
    sched.schedule_pending()
    sched.wait_for_bindings()
    assert arrived, "the batch did not take the pipelined fast path"
    # the first batch decided against the cluster as its mirror saw it
    assert len(bound) == 2 * standing
    assert set(bound.values()) <= {f"small-{i}" for i in range(standing)}
    # ... and the committer it built is known to be that old
    assert sched._fc_key[0] == arrived[0] < sched._external_mutations
    # the pods it could not place come back on the nodes' ADD and fit
    assert _drain_until(sched, lambda: len(bound) == 12), sched.metrics
    assert sched.metrics["fast_batches"] >= 2
