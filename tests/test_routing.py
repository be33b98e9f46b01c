"""The routing table as a test (``kubernetes_tpu/routing.py``'s docstring
carries the table; one case here is one row of it).

A batch of each property is handed to the scheduling loop on a scheduler
whose mirror is packed: the case asserts the ``route.*`` and ``fast_gate.*``
counts the loop booked for it and the engine metric that moved.  A second
test holds the contract between the batch extension's predicate and the
fast offer's gates: the predicate accepts a pod if and only if a fresh
one-pod batch of it takes the fast route.
"""

from types import SimpleNamespace

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    ContainerPort,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
)
from kubernetes_tpu.extender import Extender
from kubernetes_tpu.framework import config as cfg
from kubernetes_tpu.framework.interface import FilterPlugin, ScorePlugin, Status
from kubernetes_tpu.framework.registry import default_registry
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.workloads import gang as wlg

N_PODS = 4
ENGINES = ("fast_batches", "wave_batches", "chain_batches", "scan_batches", "workload_batches")
MARK = {"routing-test": "marked"}  # the label the test's plugins and extender act on


def _marked(pod) -> bool:
    return pod.labels.get("routing-test") == "marked"


class MarkedFilter(FilterPlugin):
    """A host-backed Filter that is relevant to marked pods only."""

    name = "MarkedFilter"

    def filter(self, state, pod, node_state) -> Status:
        return Status.success()

    def maybe_relevant(self, pod) -> bool:
        return _marked(pod)


class MarkedScore(ScorePlugin):
    """A host-backed Score that is relevant to marked pods only."""

    name = "MarkedScore"

    def score(self, state, pod, node_state) -> int:
        return 1

    def score_relevant(self, pod) -> bool:
        return _marked(pod)


class MarkedNormalizingScore(MarkedScore):
    """The same, with a normalize of its own: a one-pod cycle's business."""

    name = "MarkedNormalizingScore"

    def normalize(self, state, pod, scores):
        return [s * 2 for s in scores]


class MarkedExtender(Extender):
    name = "marked"
    weight = 1
    ignorable = False

    def is_interested(self, pod):
        return _marked(pod)

    def is_filter(self):
        return True

    def is_prioritizer(self):
        return False

    def is_binder(self):
        return False

    def supports_preemption(self):
        return False

    def filter(self, pod, node_names):
        return list(node_names), {}, {}


def _nodes(n=8):
    return [
        Node(
            name=f"n{i}",
            labels={"topology.kubernetes.io/zone": f"z{i % 3}", "kubernetes.io/hostname": f"n{i}"},
            capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110}),
        )
        for i in range(n)
    ]


def _pod(name, labels=None, **kw):
    kw.setdefault("containers", [Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})])
    return Pod(name=name, labels=dict(labels or {"app": "plain"}), **kw)


def _with_plugin(plugin_cls, point):
    reg = default_registry()
    reg.register(plugin_cls.name, lambda args, handle: plugin_cls(args, handle))
    ref = cfg.PluginRef(plugin_cls.name, weight=3) if point == "score" else cfg.PluginRef(plugin_cls.name)
    profile = cfg.Profile(plugins=cfg.Plugins(**{point: cfg.PluginSet(enabled=[ref])}))
    return dict(configuration=cfg.SchedulerConfiguration(profiles=[profile]), registry=reg)


def _solo_anti(name, **kw):
    term = PodAffinityTerm(
        topology_key="kubernetes.io/hostname", label_selector=LabelSelector(match_labels={"g": "solo"})
    )
    return _pod(
        name,
        {"g": "solo-owner"},
        affinity=Affinity(
            pod_anti_affinity=PodAntiAffinity(required_during_scheduling_ignored_during_execution=(term,))
        ),
        **kw,
    )


def _nominate(sched, priority):
    nominated = _pod("nominated-elsewhere", priority=priority)
    nominated.nominated_node_name = "n0"
    sched.nominator.add(nominated, "n0")


def _plain_pods(i):
    return _pod(f"p{i}")


# a row: the Scheduler's arguments, what is placed or nominated first, the
# batch's pods; the route, the gate's reason and asking that the loop books,
# the engine metric that moves

ROWS = {
    "plain": dict(pod=_plain_pods, route="fast", engine="fast_batches"),
    "nominated-node": dict(
        pod=lambda i: _pod(f"p{i}", nominated_node_name="n1"), route="direct", engine=None
    ),
    "gang-member": dict(
        first=lambda sched: sched.gangs.upsert(wlg.PodGroup(name="g1", min_member=N_PODS)),
        pod=lambda i: _pod(f"p{i}", {wlg.GROUP_LABEL: "g1"}),
        route="direct",
        refused="gang",
        engine="workload_batches",
    ),
    "host-port": dict(
        pod=lambda i: _pod(
            f"p{i}",
            containers=[
                Container(name="c", requests={"cpu": "100m"}, ports=[ContainerPort(container_port=80, host_port=8080)])
            ],
        ),
        route="direct",
        engine="wave_batches",
    ),
    "host-filter": dict(
        sched=lambda: _with_plugin(MarkedFilter, "filter"),
        pod=lambda i: _pod(f"p{i}", MARK),
        route="direct",
        engine="scan_batches",
        engine_moves=N_PODS,  # one-pod cycles
    ),
    "extender": dict(
        sched=lambda: dict(extenders=[MarkedExtender()]),
        pod=lambda i: _pod(f"p{i}", MARK),
        route="direct",
        engine=None,
    ),
    "normalizing-score": dict(
        sched=lambda: _with_plugin(MarkedNormalizingScore, "score"),
        pod=lambda i: _pod(f"p{i}", MARK),
        route="direct",
        engine=None,
    ),
    "host-score": dict(
        sched=lambda: _with_plugin(MarkedScore, "score"),
        pod=lambda i: _pod(f"p{i}", MARK),
        route="direct",
        engine="scan_batches",
    ),
    "placed-term-admits": dict(
        first=lambda sched: sched.on_pod_add(_solo_anti("placed", node_name="n0")),
        pod=lambda i: _pod(f"p{i}", {"g": "solo"}),
        route="chained",
        refused="term_admits",
        asked=1,
        engine="chain_batches",
    ),
    "under-a-nomination": dict(
        first=lambda sched: _nominate(sched, 50),
        pod=lambda i: _pod(f"p{i}", priority=50),
        route="chained",
        refused="nomination",
        engine="chain_batches",
    ),
    "inter-pod-term": dict(
        pod=lambda i: _solo_anti(f"p{i}"), route="chained", engine="wave_batches"
    ),
    "spread-term": dict(
        pod=lambda i: _pod(
            f"p{i}",
            {"app": "spread"},
            topology_spread_constraints=(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": "spread"}),
                ),
            ),
        ),
        route="chained",
        engine="wave_batches",
    ),
    "sampling": dict(
        sched=lambda: dict(configuration=cfg.SchedulerConfiguration(percentage_of_nodes_to_score=50)),
        pod=_plain_pods,
        route="direct",
        engine="scan_batches",
        of_the_profile=True,
    ),
    "fit-strategy": dict(
        sched=lambda: dict(
            configuration=cfg.SchedulerConfiguration(
                profiles=[
                    cfg.Profile(
                        plugin_config={
                            "NodeResourcesFit": {
                                "scoringStrategy": {
                                    "type": "MostAllocated",
                                    "resources": [{"name": "cpu", "weight": 1}, {"name": "memory", "weight": 1}],
                                }
                            }
                        }
                    )
                ]
            )
        ),
        pod=_plain_pods,
        route="chained",
        engine="chain_batches",
        of_the_profile=True,
    ),
}


def _scheduler(row):
    """A scheduler of the row's configuration whose mirror is packed (the
    process's first batch meets none and goes its own way), with what the row
    places or nominates first; returns it with the counts so far."""
    sched = Scheduler(**row.get("sched", dict)())
    bindings = {}
    sched.binding_sink = lambda pod, node: bindings.__setitem__(pod.name, node)
    for n in _nodes():
        sched.on_node_add(n)
    sched.on_pod_add(_pod("warm-up", priority=100))
    sched.schedule_pending()
    assert bindings.pop("warm-up")
    row.get("first", lambda sched: None)(sched)
    return sched, bindings, _counts(sched)


def _counts(sched):
    got = {k: v for k, v in sched.phases.snapshot().items() if k.startswith(("route.", "fast_gate."))}
    got.update({k: sched.metrics.get(k, 0) for k in ENGINES})
    return got


def _moved(sched, before):
    now = _counts(sched)
    return {k: now[k] - before.get(k, 0) for k in now if now[k] != before.get(k, 0)}


@pytest.mark.parametrize("case", list(ROWS))
def test_a_batch_of_each_property_takes_the_route_the_table_gives_it(case):
    row = ROWS[case]
    sched, bindings, before = _scheduler(row)
    for i in range(N_PODS):
        sched.on_pod_add(row["pod"](i))
    sched.schedule_pending()
    assert len(bindings) == N_PODS, bindings
    want = {"route." + row["route"]: N_PODS}
    if "refused" in row:
        want["fast_gate.refused." + row["refused"]] = N_PODS
    if "asked" in row:
        want["fast_gate.probes_asked"] = row["asked"]
    if row["engine"] is not None:
        want[row["engine"]] = row.get("engine_moves", 1)
    assert _moved(sched, before) == want


@pytest.mark.parametrize("case", [c for c in ROWS if not ROWS[c].get("of_the_profile")])
def test_the_extension_accepts_a_pod_iff_a_fresh_one_pod_batch_passes_the_fast_offers_gates(case):
    """The contract of ``Scheduler._fast_pod_predicate``: the pods the batch
    extension accepts are exactly the pods the fast offer's gates accept as a
    batch of their own (sampling and the fit strategy are properties of the
    profile, asked before either)."""
    row = ROWS[case]
    sched, bindings, before = _scheduler(row)
    pod = row["pod"](0)
    fwk = sched.profiles[pod.scheduler_name]
    accepted = sched._fast_pod_predicate(fwk, pod.scheduler_name)(SimpleNamespace(pod=pod))
    sched.on_pod_add(pod)
    sched.schedule_pending()
    moved = _moved(sched, before)
    assert sum(v for k, v in moved.items() if k.startswith("route.")) == 1  # one route took the pod
    assert accepted == (moved.get("route.fast", 0) == 1) == (case == "plain")
