"""Per-batch fast-path eligibility: nominations and placed term pods only
poison the pods they can actually touch (round-3 weak #7) — one gang pod
in a big plain drain must NOT degrade every batch to the scan path."""

import random
import sys
import threading
from types import SimpleNamespace

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    LabelSelector,
    LabelSelectorRequirement,
    Node,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.cache.term_probes import MAX_PROBES_ASKED, _pod_probes
from kubernetes_tpu.scheduler import Scheduler
from tests.test_fast_gate_registry_equivalence import (
    _registry_counts,
    _walk_admits,  # the gate before PR 42, as the oracle
)


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


@pytest.mark.parametrize("placed", [0, 100], ids=["no-placed-term-pod", "100-placed-term-pods-that-admit-nobody"])
def test_mixed_drain_decisions_match_serial(placed):
    """Decisions with the per-batch gate active must equal pod-at-a-time
    scheduling on the same mixed workload — and, past 64 placed term pods
    that admit none of the plain pods (where the gate used to give up and
    now asks), the serial reference's own, pod for pod."""
    import copy

    from kubernetes_tpu.oracle import OracleState, schedule_one

    rng = random.Random(3)
    n_nodes = 20 if not placed else 120
    base = [_anti_pod(f"placed-{i}", node_name=f"n{i}") for i in range(placed)]

    def workload():
        # past the placed terms the queue holds plain pods only: a queued
        # term pod would send its whole batch to the chained path
        pods = [] if placed else [_anti_pod(f"g{i}", group=f"grp{i % 3}") for i in range(6)]
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
        for n in _nodes(n_nodes):
            s.on_node_add(n)
        for p in copy.deepcopy(base):
            s.on_pod_add(p)
        for p in pods:
            s.on_pod_add(p)
        s.schedule_pending()
        return got, s

    pods = workload()
    batched, sched = run(64, copy.deepcopy(pods))
    serial, _ = run(1, copy.deepcopy(pods))
    assert batched == serial
    if placed:
        # the plain pods took the fast route past the placed terms ...
        assert sched.phases.snapshot().get("route.fast") == 40
        # ... and decided what the serial reference decides
        state = OracleState.build(_nodes(n_nodes))
        for p in copy.deepcopy(base):
            state.place(p)
        for p in copy.deepcopy(pods):
            p.node_name = schedule_one(p, state).node
            assert batched[p.name] == p.node_name, p.name
            state.place(p)


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


# ---- the gate at any COUNT of placed term pods, and its reasons (PR 41, PR 42) ----
# Until PR 42 the gate gave up past 64 placed term-carrying pods without asking
# one probe (``term_count`` on the count alone).  It now asks the cache's
# registry of DISTINCT placed terms, whatever the count.


def _place_term_pods(sched, n):
    """``n`` placed pods that each carry the term, one to a node (the term
    allows nothing else); none admits a ``_plain`` pod (labels ``app=…``)."""
    for i in range(n):
        sched.on_pod_add(_anti_pod(f"placed-{i}", node_name=f"n{i}"))
    assert sched.cache.n_term_pods == n


def _term(color, topology_key="kubernetes.io/hostname"):
    return PodAffinityTerm(
        topology_key=topology_key,
        label_selector=LabelSelector(match_labels={"color": color}),
        namespaces=("sched-1", "sched-0"),
    )


def _cell_template_pod(i, node_name):
    """The four term-carrying templates of ``mixedbase-5k``, in turn: required
    zone affinity (blue), required hostname anti-affinity (green), preferred
    hostname affinity (red), preferred hostname anti-affinity (yellow), every
    term over ``sched-1``, ``sched-0``."""
    kind = i % 4
    if kind == 0:
        labels = {"color": "blue"}
        aff = Affinity(pod_affinity=PodAffinity(
            required_during_scheduling_ignored_during_execution=(_term("blue", "topology.kubernetes.io/zone"),)))
    elif kind == 1:
        labels = {"color": "green", "name": "test"}
        aff = Affinity(pod_anti_affinity=PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=(_term("green"),)))
    elif kind == 2:
        labels = {"color": "red"}
        aff = Affinity(pod_affinity=PodAffinity(
            preferred_during_scheduling_ignored_during_execution=(WeightedPodAffinityTerm(1, _term("red")),)))
    else:
        labels = {"color": "yellow"}
        aff = Affinity(pod_anti_affinity=PodAntiAffinity(
            preferred_during_scheduling_ignored_during_execution=(WeightedPodAffinityTerm(1, _term("yellow")),)))
    return Pod(name=f"base-{i}", namespace="sched-0", labels=labels, node_name=node_name, affinity=aff,
               containers=[Container(name="c", requests={"cpu": "10m"})])


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


@pytest.mark.parametrize("placed", [65, 1000, 8000])
def test_placed_term_pods_that_admit_nobody_leave_the_batch_on_the_fast_path_at_any_count(placed):
    """The cell's shape: ``placed`` pods of the four term-carrying templates,
    two namespaces the batch is not in, four DISTINCT terms whatever the
    count.  The same batch of plain pods takes the fast route, and the gate
    asks nothing: no placed term is filed under a label the pods carry."""
    sched, bindings = _mk(80)
    for i in range(placed):
        sched.on_pod_add(_cell_template_pod(i, f"n{i % 80}"))
    assert sched.cache.n_term_pods == placed
    counts = _registry_counts(sched.cache)
    assert len(counts) == 4 and sum(counts.values()) == placed
    for i in range(64):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 64
    assert sched.metrics["fast_batches"] == 1, sched.metrics
    assert _routes(sched) == {"route.fast": 64.0}  # no fast_gate.* count: not refused, nothing asked
    # the second batch meets a packed mirror: the fast path still takes it
    for i in range(64, 96):
        sched.on_pod_add(_plain(i))
    sched.schedule_pending()
    assert len(bindings) == 96 and sched.metrics["fast_batches"] == 2
    assert sched.metrics.get("chain_batches", 0) == 0 and sched.metrics["wave_batches"] == 0
    assert _routes(sched) == {"route.fast": 96.0}


@pytest.mark.parametrize("placed", [1, 64, 65], ids=["one-placed-term-pod", "at-the-old-count", "past-the-old-count"])
def test_a_batch_pod_that_a_placed_term_admits_is_refused_whatever_the_count(placed):
    """At any count the gate asks its probes and says ``term_admits``: the
    batch is refused, and the term is honoured exactly."""
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
    got = _routes(sched)
    assert got.pop("fast_gate.refused.term_admits") == 4.0
    # ONE distinct term whatever the count: the first pod's label finds it, it admits
    assert got.pop("fast_gate.probes_asked") == 1.0
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


# ---- the registry of DISTINCT placed terms behind the gate (PR 42) ----


def _qp(pod):
    return SimpleNamespace(pod=pod)


def _sel_pod(name, selector, *, namespace="default", node_name="n0", namespaces=(), namespace_selector=None):
    return Pod(
        name=name, namespace=namespace, node_name=node_name,
        affinity=Affinity(pod_anti_affinity=PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=(
                PodAffinityTerm(topology_key="kubernetes.io/hostname", label_selector=selector,
                                namespaces=namespaces, namespace_selector=namespace_selector),))),
        containers=[Container(name="c", requests={"cpu": "10m"})],
    )


def _counts(sched):
    got = _registry_counts(sched.cache)
    assert all(v > 0 for v in got.values()), got  # an entry at 0 is dropped, none goes below
    assert sum(got.values()) == sum(len(_pod_probes(p)) for p in sched.cache.term_pods.values())
    return sorted(got.values())


def test_the_registrys_reference_counts_follow_every_route_of_count_pod():
    sched, _ = _mk(8)
    cache = sched.cache
    assert _counts(sched) == [] and not cache.term_probes.view()
    # informer add: two pods of one template share ONE entry
    a0, a1 = _anti_pod("a0", node_name="n0"), _anti_pod("a1", node_name="n1")
    sched.on_pod_add(a0)
    sched.on_pod_add(a1)
    assert _counts(sched) == [2] and cache.n_term_pods == 2
    # a second distinct term
    b0 = _anti_pod("b0", group="other", node_name="n2")
    sched.on_pod_add(b0)
    assert _counts(sched) == [1, 2]
    # informer update with a CHANGED affinity: the old term loses one, a new entry appears
    a1_new = _anti_pod("a1", group="third", node_name="n1")
    a1_new.uid = a1.uid
    sched.on_pod_update(a1, a1_new)
    assert _counts(sched) == [1, 1, 1] and cache.n_term_pods == 3
    # ... and back: the entry it alone held is dropped
    sched.on_pod_update(a1_new, a1)
    assert _counts(sched) == [1, 2]
    # an update that changes no term (labels only) changes no count
    a0_new = _anti_pod("a0", node_name="n0")
    a0_new.uid, a0_new.labels = a0.uid, {"g": "solo", "extra": "1"}
    sched.on_pod_update(a0, a0_new)
    assert _counts(sched) == [1, 2]
    # assume (one pod) and forget
    c0 = _anti_pod("c0")
    cache.assume_pod(c0, "n3")
    assert _counts(sched) == [1, 3]
    cache.forget_pod(c0)
    assert _counts(sched) == [1, 2]
    # bulk assume: term pods among plain ones, one of them refused (already assumed)
    cache.assume_pod(c0, "n3")
    bulk = [(_anti_pod("d0"), "n4"), (_plain(0), "n4"), (_anti_pod("d1", group="other"), "n5"), (c0, "n6")]
    with sched._mu:
        out = cache.assume_pods_bulk(bulk)
    assert isinstance(out[3], str) and cache.n_term_pods == 6
    assert _counts(sched) == [2, 4]
    # the informer confirms an assumed pod on its node: adopted, not counted again
    confirmed = _anti_pod("d0", node_name="n4")
    confirmed.uid = bulk[0][0].uid
    sched.on_pod_add(confirmed)
    assert _counts(sched) == [2, 4]
    # ... on ANOTHER node than assumed: removed and added, the count stands
    moved = _anti_pod("d1", group="other", node_name="n7")
    moved.uid = bulk[2][0].uid
    sched.on_pod_add(moved)
    assert _counts(sched) == [2, 4]
    # every removal, by the route that fits the pod's state: back to empty
    cache.forget_pod(c0)
    for p in (a0_new, a1, b0, confirmed, moved):
        sched.on_pod_delete(p)
    assert _counts(sched) == [] and cache.n_term_pods == 0 and not cache.term_pods
    assert not cache.term_probes.view()
    # removing what is not there changes nothing: never negative
    cache.term_probes.remove(a0)
    assert _counts(sched) == []


def test_what_was_derived_once_for_a_batch_pod_is_what_the_registry_counts_at_its_commit():
    """The registry's upkeep derives nothing a second time: ``probe_entries`` keys a batch pod's probes
    once, for whoever asks first, the memo rides the assumed copy, and ``add`` / ``remove`` read it."""
    from kubernetes_tpu.cache.term_probes import probe_entries

    sched, _ = _mk(4)
    cache = sched.cache
    batch = [_anti_pod("w0"), _anti_pod("w1"), _anti_pod("w2", group="other")]
    keys = [[key for key, _pr in probe_entries(p)] for p in batch]
    assert keys[0] == keys[1] != keys[2] and len(keys[0]) == 1  # one template, one key: keyed by content
    memos = [p.__dict__["_probe_entries_memo"] for p in batch]
    assert all(probe_entries(p) is m for p, m in zip(batch, memos))  # asked again: the memo, not a second derivation
    with sched._mu:
        assumed = cache.assume_pods_bulk([(p, f"n{i}") for i, p in enumerate(batch)])
    assert [a.__dict__["_probe_entries_memo"] for a in assumed] == memos
    assert all(a.__dict__["_probe_entries_memo"] is m for a, m in zip(assumed, memos))
    assert _counts(sched) == [1, 2]
    view = cache.term_probes.view()
    assert {id(pr) for pr in view.by_pair[("g", "solo")]} == {id(memos[0][0][1])}  # the first adder's probe
    # a pod no sweep saw is derived on its way in, with the same key
    sched.on_pod_add(_anti_pod("late", node_name="n3"))
    assert _counts(sched) == [1, 3] and cache.term_probes.view() is view  # a count moved: same view
    for p in batch:
        cache.forget_pod(p)
    assert _counts(sched) == [1]


def test_a_removal_takes_out_what_the_pods_addition_put_in_whatever_object_reports_it():
    """``_count_pod(-1)`` removes by the object ``term_pods`` registered, not by the one that reports the
    removal: an API object standing in for the assumed copy cannot take another term's count down."""
    sched, _ = _mk(4)
    cache = sched.cache
    mine, other = _anti_pod("m0", node_name="n0"), _anti_pod("o0", group="other", node_name="n1")
    sched.on_pod_add(mine)
    sched.on_pod_add(other)
    reported = _anti_pod("m0", group="other", node_name="n0")  # same pod, reported with the OTHER term
    reported.uid = mine.uid
    cache._count_pod(reported, -1)
    assert {pr.sel.match_labels["g"] for pr in cache.term_probes.view().by_pair[("g", "other")]} == {"other"}
    assert _registry_counts(cache) and sorted(_registry_counts(cache).values()) == [1]
    assert ("g", "solo") not in cache.term_probes.view().by_pair
    cache._count_pod(reported, -1)  # twice: nothing is registered under that uid any more
    assert sorted(_registry_counts(cache).values()) == [1]


@pytest.mark.parametrize(
    "case",
    ["namespace-scope", "namespaces-listed", "namespace-selector", "empty-selector", "nil-selector",
     "expressions-only", "labels-and-expressions", "unhashable-selector"],
)
def test_what_a_placed_term_admits_and_where_the_registry_files_it(case):
    sched, _ = _mk(2)
    view = lambda: sched.cache.term_probes.view()  # noqa: E731
    red = LabelSelector(match_labels={"color": "red"})

    def verdict(pod):
        ok = sched._fast_gate_ok([_qp(pod)])
        assert ok != _walk_admits(sched.cache, pod)
        return sched._gate.refused

    def pod(labels, namespace="default"):
        return Pod(name="x", namespace=namespace, labels=labels,
                   containers=[Container(name="c", requests={"cpu": "10m"})])

    if case == "namespace-scope":  # no namespaces named: the placed pod's own
        sched.on_pod_add(_sel_pod("t", red, namespace="team-a"))
        assert verdict(pod({"color": "red"}, "team-a")) == "term_admits"
        assert verdict(pod({"color": "red"}, "team-b")) is None
        assert verdict(pod({"color": "blue"}, "team-a")) is None
        assert list(view().by_pair) == [("color", "red")] and not view().unindexed
    elif case == "namespaces-listed":
        sched.on_pod_add(_sel_pod("t", red, namespace="team-a", namespaces=("sched-1", "sched-0")))
        assert verdict(pod({"color": "red"}, "sched-1")) == "term_admits"
        assert verdict(pod({"color": "red"}, "team-a")) is None  # its own namespace is not listed
    elif case == "namespace-selector":  # conservatively any namespace
        sched.on_pod_add(_sel_pod("t", red, namespace="team-a",
                                  namespace_selector=LabelSelector(match_labels={"env": "prod"})))
        assert verdict(pod({"color": "red"}, "anywhere")) == "term_admits"
        assert verdict(pod({}, "anywhere")) is None
    elif case == "empty-selector":  # selects every pod of the scope: unindexed
        sched.on_pod_add(_sel_pod("t", LabelSelector(), namespace="team-a"))
        assert len(view().unindexed) == 1 and not view().by_pair
        assert verdict(pod({}, "team-a")) == "term_admits"
        assert verdict(pod({"any": "thing"}, "team-b")) is None
    elif case == "nil-selector":  # selects nothing
        sched.on_pod_add(_sel_pod("t", None, namespace="team-a"))
        assert verdict(pod({}, "team-a")) is None and verdict(pod({"a": "b"}, "team-a")) is None
    elif case == "expressions-only":
        sel = LabelSelector(match_expressions=(
            LabelSelectorRequirement("tier", "In", ("web", "api")),
            LabelSelectorRequirement("canary", "DoesNotExist"),
        ))
        sched.on_pod_add(_sel_pod("t", sel))
        assert len(view().unindexed) == 1 and not view().by_pair
        assert verdict(pod({"tier": "web"})) == "term_admits"
        assert verdict(pod({"tier": "web", "canary": "1"})) is None
        assert verdict(pod({})) is None
        sched.on_pod_add(_sel_pod("u", LabelSelector(match_expressions=(
            LabelSelectorRequirement("tier", "NotIn", ("web",)),)), node_name="n1"))
        assert verdict(pod({})) == "term_admits"  # NotIn admits a pod without the key
        sched.on_pod_add(_sel_pod("v", LabelSelector(match_expressions=(
            LabelSelectorRequirement("zone", "Near", ("x",)),)), node_name="n1"))
        assert verdict(pod({"tier": "web", "canary": "1"})) == "term_admits"  # unknown operator: conservative
    elif case == "labels-and-expressions":  # filed under its first pair in sorted order, asked in full
        sel = LabelSelector(match_labels={"color": "red", "app": "db"},
                            match_expressions=(LabelSelectorRequirement("canary", "DoesNotExist"),))
        sched.on_pod_add(_sel_pod("t", sel))
        assert list(view().by_pair) == [("app", "db")]
        assert verdict(pod({"app": "db", "color": "red"})) == "term_admits"
        assert verdict(pod({"app": "db"})) is None and sched._gate.asked == 1
        assert verdict(pod({"color": "red"})) is None and sched._gate.asked == 0
        assert verdict(pod({"app": "db", "color": "red", "canary": "1"})) is None
    else:  # a selector that will not hash: under a key of the pod's own, never deduped, still exact
        for i in range(3):
            sel = LabelSelector(match_expressions=(LabelSelectorRequirement("tier", "In", ["web", "api"]),))
            sched.on_pod_add(_sel_pod(f"t{i}", sel, node_name=f"n{i % 2}"))
        assert sorted(_registry_counts(sched.cache).values()) == [1, 1, 1]
        assert verdict(pod({"tier": "api"})) == "term_admits"
        assert verdict(pod({"tier": "db"})) is None and sched._gate.asked == 3
        for p in list(sched.cache.term_pods.values()):
            sched.on_pod_delete(p)
        assert not _registry_counts(sched.cache) and verdict(pod({"tier": "api"})) is None


def test_a_cluster_of_distinct_terms_costs_a_plain_pod_its_own_labels_not_the_terms():
    """2,000 Deployments, each with anti-affinity over its own ``app=<name>``:
    a pod is asked about the terms filed under ITS pairs only."""
    sched, _ = _mk(4)
    for i in range(2000):
        sched.on_pod_add(_anti_pod(f"d{i}", group=f"dep-{i}", node_name=f"n{i % 4}"))
    assert len(_registry_counts(sched.cache)) == 2000
    batch = [_qp(_plain(i)) for i in range(64)] + [_qp(Pod(name="bare"))]
    assert sched._fast_gate_ok(batch) and sched._gate.asked == 0
    mine = Pod(name="mine", labels={"g": "dep-7", "app": "x"})
    assert not sched._fast_gate_ok(batch + [_qp(mine)])
    assert sched._gate.refused == "term_admits" and sched._gate.asked == 1


def test_term_count_is_the_work_bound_of_one_batchs_sweep():
    """``term_count`` is no longer a count of placed pods: it is said where one
    batch's label-groups would ask more than MAX_PROBES_ASKED placed terms."""
    sched, _ = _mk(4)
    n_terms = 2001
    for i in range(n_terms):  # distinct, expressions only: unindexed, every pod's candidates
        sel = LabelSelector(match_expressions=(LabelSelectorRequirement(f"k{i}", "Exists"),))
        sched.on_pod_add(_sel_pod(f"t{i}", sel, node_name=f"n{i % 4}"))
    groups = MAX_PROBES_ASKED // n_terms  # 49 label-groups can be asked in full
    pods = [_qp(Pod(name=f"p{i}", labels={"app": f"a{i}"})) for i in range(groups + 1)]
    assert sched._fast_gate_ok(pods[:groups]) and sched._gate.asked == groups * n_terms
    assert sched._fast_gate_ok(pods[:groups] * 3)  # pods of a group already asked cost nothing
    assert not sched._fast_gate_ok(pods)
    assert sched._gate.refused == "term_count" and sched._gate.asked == groups * n_terms
    # a term that admits is found before the bound is reached
    pods[0].pod.labels["k5"] = "x"
    assert not sched._fast_gate_ok(pods) and sched._gate.refused == "term_admits"
    assert sched._gate.asked == 6
    # the batch extension's predicate shares the bound: past it, it extends no further
    del pods[0].pod.labels["k5"]
    with sched._mu:
        sched._repack_mirror()
    fwk = next(iter(sched.profiles.values()))
    assert sched._fast_gate_ok(pods[:1]) and sched._gate.asked == n_terms
    elig = sched._fast_pod_predicate(fwk, pods[0].pod.scheduler_name)
    assert elig(pods[1]) is True and sched._gate.asked == 2 * n_terms
    assert elig(pods[1]) is True and sched._gate.asked == 2 * n_terms  # its group is remembered
    assert sched._fast_gate_ok(pods[:groups])
    elig = sched._fast_pod_predicate(fwk, pods[0].pod.scheduler_name)
    assert elig(pods[groups]) is False and sched._gate.asked == groups * n_terms


def test_the_gate_reads_a_consistent_view_beside_a_thread_that_counts_term_pods_in_and_out():
    """``_fast_gate_ok`` runs outside ``Scheduler._mu`` (``_chain_quickcheck``)
    while the informer thread adds and removes term pods under it.  The
    writer keeps the admitting term placed throughout (its count moves
    between 1 and 2) and churns DISTINCT other terms, so the view is rebuilt
    all the time: every verdict must be ``term_admits``, none an exception."""
    sched, _ = _mk(4)
    keep = _anti_pod("keep-0", node_name="n0")
    sched.on_pod_add(keep)
    for i in range(300):  # a registry large enough that a rebuild can be interrupted
        sched.on_pod_add(_anti_pod(f"stay-{i}", group=f"stay-{i}", node_name="n2"))
    batch = [_qp(_plain(i)) for i in range(8)] + [_qp(Pod(name="s", labels={"g": "solo"}))]
    plain_only = batch[:8]
    stop = threading.Event()
    errors, verdicts = [], []

    def read():
        try:
            while not stop.is_set():
                sched._fast_gate_ok(batch)
                verdicts.append(sched._gate.refused)
                verdicts.append(sched._fast_gate_ok(plain_only))
                _registry_counts(sched.cache)
        except Exception as e:  # noqa: BLE001 — the test's finding
            errors.append(e)

    reader = threading.Thread(target=read)  # the loop thread's part
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter over often: the race, if there is one, shows
    reader.start()
    try:
        for i in range(1, 600):
            last, keep = keep, _anti_pod(f"keep-{i}", node_name="n0")
            sched.on_pod_add(keep)
            sched.on_pod_delete(last)
            churn = [_anti_pod(f"churn-{i}-{j}", group=f"g{i}-{j}", node_name="n1") for j in range(4)]
            for p in churn:
                sched.on_pod_add(p)
            for p in churn:
                sched.on_pod_delete(p)
    finally:
        stop.set()
        reader.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not reader.is_alive()
    assert not errors, errors
    assert len(verdicts) > 100 and set(verdicts) == {"term_admits", True}, set(verdicts)
    assert sorted(_registry_counts(sched.cache).values()) == [1] * 301
