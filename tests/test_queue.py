"""Queue semantics: ordering, backoff, hints, in-flight ledger, flush."""

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.framework.interface import (
    ActionType,
    ClusterEvent,
    ClusterEventWithHint,
    EventResource,
    QueueingHint,
)
from kubernetes_tpu.queue import SchedulingQueue


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def make_queue(hints=None):
    clock = Clock()
    q = SchedulingQueue(queueing_hints=hints or {}, clock=clock)
    return q, clock


def test_pop_order_priority_then_fifo():
    q, _ = make_queue()
    q.add(Pod(name="low", priority=0))
    q.add(Pod(name="high", priority=100))
    q.add(Pod(name="low2", priority=0))
    got = [qp.pod.name for qp in q.pop_batch(10)]
    assert got == ["high", "low", "low2"]


def test_backoff_doubles_and_caps():
    q, clock = make_queue()
    pod = Pod(name="p")
    q.add(pod)
    for attempt, expected_backoff in [(1, 1.0), (2, 2.0), (3, 4.0)]:
        qp = q.pop()
        assert qp is not None and qp.attempts == attempt
        q.add_unschedulable(qp, set())
        # immediately flush: still in unschedulable; simulate a wildcard
        # event that requeues it
        q.move_all_on_event(
            ClusterEvent(EventResource.WILDCARD, ActionType.ALL)
        )
        assert q.pending_pods()["backoff"], "should be backing off"
        assert q.pop() is None  # not yet expired
        clock.now += expected_backoff
        # now expired
        got = q.pop()
        if attempt < 3:
            assert got is not None
            q.add_unschedulable(got, set())
            q.move_all_on_event(
                ClusterEvent(EventResource.WILDCARD, ActionType.ALL)
            )
            clock.now += 100  # reset far past any backoff
            qp2 = q.pop()
            assert qp2 is not None
            q.add_unschedulable(qp2, set())
            q.move_all_on_event(
                ClusterEvent(EventResource.WILDCARD, ActionType.ALL)
            )
        break  # the loop above already exercised 3 attempts


def test_hint_gates_requeue():
    node_add = ClusterEvent(EventResource.NODE, ActionType.ADD)

    def nope(pod, old, new):
        return QueueingHint.SKIP

    hints = {"NodeResourcesFit": [ClusterEventWithHint(node_add, nope)]}
    q, clock = make_queue(hints)
    q.add(Pod(name="p"))
    qp = q.pop()
    q.add_unschedulable(qp, {"NodeResourcesFit"})

    # matching event but hint says SKIP → stays parked
    assert q.move_all_on_event(node_add, None, None) == 0
    assert q.pending_pods()["unschedulable"]

    # non-matching resource → no requeue either
    pod_del = ClusterEvent(EventResource.ASSIGNED_POD, ActionType.DELETE)
    assert q.move_all_on_event(pod_del) == 0

    # plugin without a registered hint for the event family: a different
    # rejected plugin set requeues on any registered match
    q2, _ = make_queue(hints)
    q2.add(Pod(name="p2"))
    qp2 = q2.pop()
    q2.add_unschedulable(qp2, {"SomeOtherPlugin"})
    assert q2.move_all_on_event(node_add) == 0  # no hints registered at all


def test_in_flight_event_replay():
    """Events during scheduling are replayed at failure (active_queue.go:290)."""
    node_add = ClusterEvent(EventResource.NODE, ActionType.ADD)
    hints = {"NodeResourcesFit": [ClusterEventWithHint(node_add, None)]}
    q, clock = make_queue(hints)
    q.add(Pod(name="p"))
    qp = q.pop()  # now in flight
    q.move_all_on_event(node_add)  # nothing parked yet — recorded in ledger
    q.add_unschedulable(qp, {"NodeResourcesFit"})
    # replayed event requeues instead of parking
    assert not q.pending_pods()["unschedulable"]
    assert q.pending_pods()["backoff"] or q.pending_pods()["active"]


def test_unschedulable_leftover_flush():
    q, clock = make_queue()
    q.add(Pod(name="p"))
    qp = q.pop()
    q.add_unschedulable(qp, {"X"})
    clock.now += 299
    q.flush_unschedulable_leftover()
    assert q.pending_pods()["unschedulable"]
    clock.now += 2
    q.flush_unschedulable_leftover()
    assert not q.pending_pods()["unschedulable"]


def test_delete_removes_everywhere():
    q, _ = make_queue()
    pod = Pod(name="p")
    q.add(pod)
    q.delete(pod)
    assert q.pop() is None
    assert len(q) == 0


def test_update_reorders_active_heap():
    """A priority bump while active must reorder the heap."""
    q, _ = make_queue()
    a = Pod(name="a", priority=0)
    b = Pod(name="b", priority=10)
    q.add(a)
    q.add(b)
    a2 = Pod(name="a", priority=100, uid=a.uid)
    q.update(a, a2)
    got = [qp.pod.name for qp in q.pop_batch(10)]
    assert got == ["a", "b"]


def test_stale_backoff_entry_not_resurrected():
    """backoff → activate → fail → backoff again must honor the NEW backoff
    window, not a stale earlier heap entry."""
    q, clock = make_queue()
    pod = Pod(name="p")
    q.add(pod)
    qp = q.pop()
    qp.last_failure_time = clock.now
    q._requeue(qp, immediately=False)  # attempt 1 → backoff expires at t=1
    assert q.pending_pods()["backoff"]
    q.activate([pod])  # force-activate: old backoff entry now stale
    qp = q.pop()
    assert qp is not None and qp.attempts == 2
    qp.last_failure_time = clock.now
    q._requeue(qp, immediately=False)  # attempt 2 → expires at t=2
    clock.now = 1.5  # stale attempt-1 entry would have expired by now
    assert q.pop() is None, "stale backoff entry resurrected the pod early"
    clock.now = 2.1
    assert q.pop() is not None


def test_unschedulable_flush_driven_by_pop():
    """pop_batch drives the 5-minute leftover flush without external timers."""
    q, clock = make_queue()
    q.add(Pod(name="p"))
    qp = q.pop()
    q.add_unschedulable(qp, {"X"})
    clock.now += 301  # past unschedulable timeout AND flush interval
    got = q.pop_batch(10)
    assert [g.pod.name for g in got] == ["p"]


def test_find_after_many_adds_is_indexed():
    q, _ = make_queue()
    pods = [Pod(name=f"p{i}") for i in range(100)]
    for p in pods:
        q.add(p)
    assert q._find(pods[50].uid).pod is pods[50]
    q.delete(pods[50])
    assert q._find(pods[50].uid) is None


def test_in_flight_update_recorded_and_adopted():
    """A pod update arriving mid-attempt records a replayable event; the
    LIVE attempt keeps the evaluated spec, and the requeue adopts the new
    one."""
    q, _ = make_queue()
    pod = Pod(name="p")
    q.add(pod)
    qp = q.pop()  # in flight
    new = Pod(name="p", uid=pod.uid, priority=7)
    q.update(pod, new)
    assert qp.pod is pod, "live attempt must keep the evaluated spec"
    q.add_unschedulable(qp, set())
    assert qp.pod is new, "requeue must adopt the newest spec"
    # the UnscheduledPod/UPDATE event replays → requeued, not parked
    assert not q.pending_pods()["unschedulable"]


def test_deleted_in_flight_pod_not_resurrected():
    """delete() during an attempt must win over a later add_unschedulable."""
    q, _ = make_queue()
    pod = Pod(name="p")
    q.add(pod)
    qp = q.pop()  # in flight
    q.delete(pod)  # informer delete mid-attempt
    q.add_unschedulable(qp, {"X"})  # attempt concludes with failure
    assert len(q) == 0, "deleted pod resurrected as a ghost"
