"""Per-batch fast-path eligibility: nominations and placed term pods only
poison the pods they can actually touch (round-3 weak #7) — one gang pod
in a big plain drain must NOT degrade every batch to the scan path."""

import random

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    PodAntiAffinity,
)
from kubernetes_tpu.scheduler import Scheduler


def _nodes(n):
    return [
        Node(
            name=f"n{i}",
            labels={
                "topology.kubernetes.io/zone": f"z{i % 3}",
                "kubernetes.io/hostname": f"n{i}",
            },
            capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110}),
        )
        for i in range(n)
    ]


def _plain(i):
    return Pod(
        name=f"p{i}",
        labels={"app": f"app-{i % 5}"},
        containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})],
    )


def _anti_pod(name, group="solo", node_name=""):
    return Pod(
        name=name,
        labels={"g": group},
        node_name=node_name,
        affinity=Affinity(
            pod_anti_affinity=PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=(
                    PodAffinityTerm(
                        topology_key="kubernetes.io/hostname",
                        label_selector=LabelSelector(match_labels={"g": group}),
                    ),
                )
            )
        ),
        containers=[Container(name="c", requests={"cpu": "50m"})],
    )


def _mk(n_nodes=20):
    sched = Scheduler()
    bindings = {}
    sched.binding_sink = lambda pod, node: bindings.__setitem__(pod.name, node)
    for n in _nodes(n_nodes):
        sched.on_node_add(n)
    return sched, bindings


def test_placed_term_pod_does_not_poison_unrelated_batches():
    sched, bindings = _mk()
    # one placed gang pod with anti-affinity (the poison of round 3)
    sched.on_pod_add(_anti_pod("gang", node_name="n0"))
    assert sched.cache.n_term_pods == 1
    for i in range(64):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 64
    assert sched.metrics["fast_batches"] >= 1, sched.metrics


def test_term_matching_batch_pods_still_take_the_exact_path():
    sched, bindings = _mk()
    sched.on_pod_add(_anti_pod("gang", node_name="n0"))
    # batch pods the placed term ADMITS (labels g=solo): the fast gate
    # must refuse, and anti-affinity must be honored exactly
    for i in range(4):
        sched.on_pod_add(
            Pod(
                name=f"s{i}",
                labels={"g": "solo"},
                containers=[Container(name="c", requests={"cpu": "50m"})],
            )
        )
    sched.schedule_pending()
    assert sched.metrics["fast_batches"] == 0, sched.metrics
    # n0 hosts the placed anti pod — no solo-labeled pod may land there
    assert all(bindings[f"s{i}"] != "n0" for i in range(4)), bindings


def test_low_priority_nomination_does_not_poison_higher_priority_batch():
    sched, bindings = _mk()
    nominated = Pod(
        name="nom",
        priority=0,
        containers=[Container(name="c", requests={"cpu": "100m"})],
    )
    nominated.nominated_node_name = "n0"
    sched.nominator.add(nominated, "n0")
    for i in range(32):
        p = _plain(i)
        p.priority = 100  # outranks the nomination -> it never counts
        sched.on_pod_add(p)
    sched.schedule_pending()
    assert len(bindings) == 32
    assert sched.metrics["fast_batches"] >= 1, sched.metrics


def test_equal_priority_nomination_poisons_the_batch():
    sched, bindings = _mk()
    nominated = Pod(
        name="nom",
        priority=50,
        containers=[Container(name="c", requests={"cpu": "100m"})],
    )
    nominated.nominated_node_name = "n0"
    sched.nominator.add(nominated, "n0")
    for i in range(8):
        p = _plain(i)
        p.priority = 50  # nomination counts as present for these
        sched.on_pod_add(p)
    sched.schedule_pending()
    assert len(bindings) == 8
    assert sched.metrics["fast_batches"] == 0, sched.metrics


def test_mixed_drain_decisions_match_serial():
    """Decisions with the per-batch gate active must equal pod-at-a-time
    scheduling on the same mixed workload."""
    rng = random.Random(3)

    def workload():
        pods = [_anti_pod(f"g{i}", group=f"grp{i % 3}") for i in range(6)]
        pods += [_plain(i) for i in range(40)]
        rng.shuffle(pods)
        return pods

    def run(batch_size, pods):
        from kubernetes_tpu.framework.config import SchedulerConfiguration

        cfg = SchedulerConfiguration()
        cfg.batch_size = batch_size
        s = Scheduler(configuration=cfg)
        got = {}
        s.binding_sink = lambda pod, node: got.__setitem__(pod.name, node)
        for n in _nodes(20):
            s.on_node_add(n)
        for p in pods:
            s.on_pod_add(p)
        s.schedule_pending()
        return got

    import copy

    pods = workload()
    batched = run(64, copy.deepcopy(pods))
    serial = run(1, copy.deepcopy(pods))
    assert batched == serial


def test_bulk_commit_charges_exact_bytes_within_quantized_signature():
    """Two pods whose memory requests differ in raw bytes but ceil to the
    same MiB lane share a SIGNATURE, not a request: the bulk commit's memo
    seeding must charge each pod's exact bytes to the cache (sharing the
    representative's Resource objects across the quantization boundary
    drifted the authoritative accounting for the placement's lifetime)."""
    sched, bindings = _mk()
    mem_a, mem_b = 268435455, 268000000  # both ceil to 256 MiB lanes
    pods = [
        Pod(
            name="exact-a",
            containers=[Container(name="c", requests={"cpu": "100m", "memory": mem_a})],
        ),
        Pod(
            name="exact-b",
            containers=[Container(name="c", requests={"cpu": "100m", "memory": mem_b})],
        ),
    ]
    for p in pods:
        sched.on_pod_add(p)
    sched.schedule_pending()
    assert len(bindings) == 2
    got = sum(
        cn.requested.memory for cn in sched.cache.nodes.values()
    )
    assert got == mem_a + mem_b, f"cache charged {got}, want {mem_a + mem_b}"


# ---- the gate on both sides of the COUNT of placed term pods, and its reasons (PR 41) ----
# Today's behaviour, pinned as it is: past 64 placed term-carrying pods the
# gate gives up without asking one probe.  The PR that repairs the gate (probes
# kept per distinct term) changes these cases knowingly.


def _place_term_pods(sched, n):
    """``n`` placed pods that each carry the term, one to a node (the term
    allows nothing else); none admits a ``_plain`` pod (labels ``app=…``)."""
    for i in range(n):
        sched.on_pod_add(_anti_pod(f"placed-{i}", node_name=f"n{i}"))
    assert sched.cache.n_term_pods == n


def _routes(sched):
    return {k: v for k, v in sched.phases.snapshot().items() if k.startswith(("route.", "fast_gate."))}


def test_64_placed_term_pods_that_admit_nobody_leave_the_batch_on_the_fast_path():
    sched, bindings = _mk(80)
    _place_term_pods(sched, 64)
    for i in range(64):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 64
    assert sched.metrics["fast_batches"] >= 1, sched.metrics
    assert _routes(sched) == {"route.fast": 64.0}  # the gate said yes: no reason booked


def test_65_placed_term_pods_send_the_same_batch_to_the_chained_path_on_the_count_alone():
    sched, bindings = _mk(80)
    _place_term_pods(sched, 65)
    for i in range(64):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 64
    assert sched.metrics["fast_batches"] == 0, sched.metrics
    # the process's first batch has no mirror packed yet, so the loop's quick check
    # hands it to the direct path, whose own call of the gate is the verdict booked
    assert _routes(sched) == {"route.direct": 64.0, "fast_gate.refused.term_count": 64.0}
    # the second batch meets a packed mirror: the chained path takes it
    for i in range(64, 96):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 96 and sched.metrics["fast_batches"] == 0
    assert sched.metrics.get("chain_batches", 0) == 1 and sched.metrics["wave_batches"] == 0
    assert _routes(sched) == {
        "route.direct": 64.0, "route.chained": 32.0, "fast_gate.refused.term_count": 96.0,
    }


@pytest.mark.parametrize("placed", [1, 64, 65], ids=["one-placed-term-pod", "at-the-count", "past-the-count"])
def test_a_batch_pod_that_a_placed_term_admits_is_refused_whatever_the_count(placed):
    """Up to the count the gate asks its probes and says ``term_admits``;
    past it the count answers first (``term_count``): refused either way,
    and the term is honoured exactly."""
    sched, bindings = _mk(80)
    _place_term_pods(sched, placed)
    for i in range(4):
        sched.on_pod_add(
            Pod(name=f"s{i}", labels={"g": "solo"},
                containers=[Container(name="c", requests={"cpu": "50m"})])
        )
    sched.schedule_pending()
    assert sched.metrics["fast_batches"] == 0, sched.metrics
    taken = {f"n{i}" for i in range(placed)}
    assert len(bindings) == 4 and not taken & set(bindings.values()), bindings
    reason = "term_admits" if placed <= 64 else "term_count"
    got = _routes(sched)
    assert got.pop(f"fast_gate.refused.{reason}") == 4.0
    assert [k for k in got if k.startswith("fast_gate.")] == []
    assert sum(got.values()) == 4.0  # one route took the batch


@pytest.mark.parametrize("reason", ["nomination", "gang"])
def test_the_gates_other_reasons_are_booked_under_their_names(reason):
    sched, bindings = _mk()
    pods = [_plain(i) for i in range(8)]
    if reason == "nomination":
        nominated = Pod(name="nom", priority=50, containers=[Container(name="c", requests={"cpu": "100m"})])
        nominated.nominated_node_name = "n0"
        sched.nominator.add(nominated, "n0")
        for p in pods:
            p.priority = 50
    else:
        from kubernetes_tpu.workloads import gang as wlg

        for p in pods:
            p.labels[wlg.GROUP_LABEL] = "g1"
        assert wlg.group_key_of(pods[0]) is not None
    for p in pods:
        sched.on_pod_add(p)
    sched.schedule_pending()
    assert sched.metrics["fast_batches"] == 0
    got = _routes(sched)
    assert got.get(f"fast_gate.refused.{reason}") == 8.0, got
    assert [k for k in got if k.startswith("fast_gate.")] == [f"fast_gate.refused.{reason}"]


def test_terms_placed_by_a_waves_bulk_commit_are_known_to_the_gate_and_honoured():
    """A wave's successes are committed through ``cache.assume_pods_bulk``;
    until PR 41 that path registered no term-carrying pod, so after such a
    drain the gate saw NO placed term (``n_term_pods`` 0, an empty registry),
    let pods that the placed REQUIRED anti-affinity terms admit onto the
    fast path, which looks at no term — three of six landed beside a pod
    whose term forbids them — and each later removal took the count below
    zero (−1,000 in ``antiaffinity-5k``'s window, 0 in ``interpod-5k``'s)."""
    sched, bindings = _mk(8)
    sched.on_pod_add(_plain(0))  # the process's first batch packs the mirror
    sched.schedule_pending()
    placed = [_anti_pod(f"a{i}") for i in range(6)]
    for p in placed:
        sched.on_pod_add(p)
    sched.schedule_pending()
    assert sched.metrics["wave_batches"] == 1  # committed in bulk, behind a wave
    assert sched.cache.n_term_pods == len(sched.cache.term_pods) == 6
    taken = {bindings[p.name] for p in placed}
    assert len(taken) == 6
    for i in range(6):  # plain pods the placed terms admit: no term of their own
        sched.on_pod_add(Pod(name=f"s{i}", labels={"g": "solo"},
                             containers=[Container(name="c", requests={"cpu": "50m"})]))
    sched.schedule_pending()
    assert not taken & {bindings[f"s{i}"] for i in range(6)}, bindings
    assert _routes(sched)["fast_gate.refused.term_admits"] == 6.0
    for p in placed:  # the pods go (as the informer reports them, bound): the count returns to zero, never below
        sched.on_pod_delete(sched.cache.pod_states[p.uid].pod)
    assert sched.cache.n_term_pods == 0 and not sched.cache.term_pods
