"""What a configuration's templates build, inside the tier-1 run.

``benchmarks/tests/test_template_format.py`` (PR 29) pins the format — the
accepted configurations build the objects they always built, a pod crosses
the served path unchanged and is equal in both types modules, a key the
harness does not build raises — but ``benchmarks/tests/`` is not part of the
tier-1 command, so its cases counted nowhere.  They are collected here as
they stand (imported; a benchmark file is not this PR's to move), and the
configuration this PR adds, ``sched-perf-prefaffinity-5k`` (upstream's
``:354``), gets the same pins at the source's counts.
"""

import dataclasses
import functools
import hashlib
import json
import os

import pytest

from benchmarks import cells, workload
from benchmarks.reference import resource as RR
from benchmarks.reference import types as RT
from benchmarks.tests import test_template_format as _pr29
from benchmarks.tests.test_template_format import *  # noqa: F401,F403 — PR 29's cases, collected here

CELL = "interpod-5k.backlog"
HOSTNAME = "kubernetes.io/hostname"
# sha256 over the repr of every node and pod object (both types modules; init
# pods on their seeded nodes, warm-up and measured groups) and every uid, as
# benchmarks/workload.py builds them from the file this PR adds, seed 7
PINNED = "79cb34f282026458487f8052be632c416fcfc9a983882c2dadb3083f178e6d5d"


def _digest(T, R, nodes, init, init_nodes, plan) -> str:
    """sha256 over the repr of every node and pod object, in the program's
    types (``T``, ``R``) and the frozen reference's, and every uid."""
    h = hashlib.sha256()
    for TT, RRR in ((T, R), (RT, RR)):
        for s in nodes:
            h.update(repr(workload.build_node(TT, RRR, s)).encode())
        for s, n in zip(init, init_nodes):
            h.update(repr(workload.build_pod(TT, s, node_name=n)).encode())
        for s in plan["warm"] + plan["measure"]:
            h.update(repr(workload.build_pod(TT, s)).encode())
    for s in init + plan["warm"] + plan["measure"]:
        h.update(workload.uid_of(s).encode())
    return h.hexdigest()


def _built():
    cell = cells.cell(CELL)
    return cell["config"], _pr29._groups(cell["config"], cell["traffic"], cell["kind"], 7)


def test_prefaffinity_config_round_trips_with_no_key_refused():
    """Every key of the file is one the harness builds (no ``KeyError``), at
    the source's counts, with nothing cut."""
    cfg, (nodes, init, init_nodes, plan) = _built()
    assert cfg["reduced"] == [] and cfg["name"] == "sched-perf-prefaffinity-5k"
    assert (len(nodes), len(init), len(plan["warm"]), len(plan["measure"])) == (5000,) * 4
    assert {s["namespace"] for s in init} == {"sched-0"}
    assert {s["namespace"] for s in plan["warm"] + plan["measure"]} == {"sched-1"}
    assert len({workload.uid_of(s) for s in init + plan["measure"]}) == 10000


def test_prefaffinity_config_builds_5000_distinct_hostnames_one_init_pod_each():
    _cfg, (nodes, _init, init_nodes, _plan) = _built()
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    built = [workload.build_node(T, R, s) for s in nodes]
    assert len({n.labels[HOSTNAME] for n in built}) == 5000
    assert all(n.labels == {HOSTNAME: n.name} for n in built)
    assert {str(n.capacity) for n in built} == {str(built[0].capacity)}  # ONE node shape
    assert sorted(init_nodes) == sorted(n.name for n in built)  # one init pod a node


def test_prefaffinity_pods_are_the_sources_template_and_equal_on_both_sides():
    """The one template, as the source has it: label color=red, 100m / 500Mi,
    ONE preferred pod-affinity term of weight 1 over both namespaces on the
    hostname, nothing required, no anti-affinity; equal field by field in the
    program's types and the frozen reference's, and pinned by digest."""
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cfg, (nodes, init, init_nodes, plan) = _built()
    for s, n in ((init[0], init_nodes[0]), (init[-1], init_nodes[-1]), (plan["measure"][0], "")):
        pod = workload.build_pod(T, s, node_name=n)
        assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, s, node_name=n))
        assert pod.labels == {"color": "red"} and pod.affinity.pod_anti_affinity is None
        assert pod.containers[0].requests == {"cpu": "100m", "memory": "500Mi"}
        pa = pod.affinity.pod_affinity
        assert pa.required_during_scheduling_ignored_during_execution == ()
        (wt,) = pa.preferred_during_scheduling_ignored_during_execution
        term = wt.pod_affinity_term
        assert wt.weight == 1 and term.topology_key == HOSTNAME
        assert term.label_selector.match_labels == {"color": "red"}
        assert term.namespaces == ("sched-1", "sched-0")
    assert _digest(T, R, nodes, init, init_nodes, plan) == PINNED


# ---- sched-perf-unschedulable-5k (upstream's :724), PR 34 ----------------------

UNSCHED_CELL = "unsched-5k.backlog-pending-first"
# the same digest over what benchmarks/workload.py builds from the file PR 34
# adds, seed 7: no init pods; warm-up and measured backlogs, pending pods first
UNSCHED_PINNED = "23ed4b59c134434a39b485cc945fde858d5b809afa56aca4775f181ba7dded32"


def _unsched_built():
    cell = cells.cell(UNSCHED_CELL)
    return cell, _pr29._groups(cell["config"], cell["traffic"], cell["kind"], 7)


def test_unschedulable_config_round_trips_with_no_key_refused():
    """Every key of the file is one the harness builds (no ``KeyError``), at
    the source's counts, with nothing cut; the third group rides in the
    plan's two lists, pending pods first."""
    cell, (nodes, init, init_nodes, plan) = _unsched_built()
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["name"] == "sched-perf-unschedulable-5k"
    assert (len(nodes), len(init), len(init_nodes)) == (5000, 0, 0)
    assert (len(plan["warm"]), plan["n_warm_pending"], len(plan["measure"]), plan["n_pending"]) == \
        (10200, 200, 10200, 200)
    assert [s["name"] for s in plan["measure"][198:202]] == ["pending-198", "pending-199", "load-0", "load-1"]
    assert [s["name"] for s in plan["warm"][198:202]] == \
        ["warm-pending-198", "warm-pending-199", "warm-0", "warm-1"]
    assert len({workload.uid_of(s) for s in plan["warm"] + plan["measure"]}) == 20400
    assert cell["kind"].pods_alive(plan) == 10200  # parked pods counted in


def test_pending_group_yields_200_specs_named_pending_i():
    cell, _groups = _unsched_built()
    specs = workload.group_specs(cell["config"], "pending_pods", "pending")
    assert [s["name"] for s in specs] == [f"pending-{i}" for i in range(200)]
    assert {(s["namespace"], tuple(sorted(s["requests"].items()))) for s in specs} == \
        {("default", (("cpu", "9"), ("memory", "500Mi")))}


def test_unschedulable_pods_are_the_sources_templates_and_equal_on_both_sides():
    """``pod-large-cpu`` (cpu 9, memory 500Mi, no labels, no term, priority
    unset) and ``pod-default``, equal field by field in the program's types
    and the frozen reference's; 9 cpu exceeds every node's 4."""
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cell, (nodes, _init, _init_nodes, plan) = _unsched_built()
    for s, requests in ((plan["measure"][0], {"cpu": "9", "memory": "500Mi"}),
                        (plan["warm"][199], {"cpu": "9", "memory": "500Mi"}),
                        (plan["measure"][200], {"cpu": "100m", "memory": "500Mi"})):
        pod = workload.build_pod(T, s)
        assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, s))
        assert pod.labels == {} and pod.affinity is None and pod.topology_spread_constraints == ()
        assert pod.containers[0].requests == requests and pod.node_name == ""
        assert pod.priority == workload.build_pod(T, plan["measure"][-1]).priority
    node = workload.build_node(T, R, nodes[0])
    assert {str(workload.build_node(T, R, s).capacity) for s in nodes} == {str(node.capacity)}  # ONE node shape
    assert R.Resource.from_map({"cpu": "9"}).milli_cpu > node.capacity.milli_cpu


def test_unschedulable_config_builds_the_pinned_objects():
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cell, (nodes, init, init_nodes, plan) = _unsched_built()
    assert _digest(T, R, nodes, init, init_nodes, plan) == UNSCHED_PINNED


# ---- sched-perf-antiaffinity-5k (upstream's :93), PR 37 -------------------------

ANTI_CELL = "antiaffinity-5k.backlog"
ANTI_FIXTURE = "sched-perf-antiaffinity"  # the same source at cut counts, in since PR 29


def _anti_built():
    cell = cells.cell(ANTI_CELL)
    return cell["config"], _pr29._groups(cell["config"], cell["traffic"], cell["kind"], 7)


def _but_count(d: dict) -> dict:
    return {k: v for k, v in d.items() if k != "count"}


@pytest.mark.parametrize("part", [
    "nodes", "pod_templates", "init_pods", "measure_pods", "expect_kernels", "guarantees-but-the-last",
])
def test_antiaffinity_config_is_the_fixture_at_the_sources_counts(part):
    """What PR 29 rehearsed at cut counts is what the cell runs: the same
    node shape, template, namespaces and kernels; only the counts differ (and
    the fourth guarantee, which says here what holds the term)."""
    cfg = cells.cell(ANTI_CELL)["config"]
    with open(os.path.join(os.path.dirname(_pr29.__file__), "fixtures", f"{ANTI_FIXTURE}.json")) as f:
        fixture = json.load(f)
    if part == "guarantees-but-the-last":
        assert cfg["guarantees"][:3] == fixture["guarantees"][:3]
        assert cfg["guarantees"][3].startswith(fixture["guarantees"][3])
    elif part in ("nodes", "init_pods", "measure_pods"):
        assert _but_count(cfg[part]) == _but_count(fixture[part])
        assert cfg[part]["count"] == {"nodes": 5000, "init_pods": 1000, "measure_pods": 2000}[part]
    else:
        assert cfg[part] == fixture[part]


def test_antiaffinity_config_round_trips_with_no_key_refused():
    """Every key of the file is one the harness builds (no ``KeyError``), at
    the source's counts, with nothing cut; one init pod a node on 1,000
    distinct nodes; the sample of identity is 4 and says why."""
    cfg, (nodes, init, init_nodes, plan) = _anti_built()
    assert cfg["reduced"] == [] and cfg["name"] == "sched-perf-antiaffinity-5k"
    assert (len(nodes), len(init), len(plan["warm"]), len(plan["measure"])) == (5000, 1000, 2000, 2000)
    assert {s["namespace"] for s in init} == {"sched-0"}
    assert {s["namespace"] for s in plan["warm"] + plan["measure"]} == {"sched-1"}
    assert len({workload.uid_of(s) for s in init + plan["warm"] + plan["measure"]}) == 5000
    assert len(set(init_nodes)) == 1000 <= len({n["labels"][HOSTNAME] for n in nodes}) == 5000
    assert cfg["identity_sample"] == 4 and "recount" in cfg["identity_sample_why"]
    assert len(cfg["source"]) <= 200 and ":93" in cfg["source"] and "5000Nodes_2000Pods" in cfg["source"]


def test_antiaffinity_pods_are_the_sources_template_and_equal_on_both_sides():
    """The one template, as the source has it: labels color=green and
    name=test, 100m / 500Mi, ONE required pod-anti-affinity term over both
    namespaces on the hostname, nothing preferred, no pod-affinity; equal
    field by field in the program's types and the frozen reference's."""
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cfg, (nodes, init, init_nodes, plan) = _anti_built()
    for s, n in ((init[0], init_nodes[0]), (init[-1], init_nodes[-1]), (plan["warm"][0], ""), (plan["measure"][-1], "")):
        pod = workload.build_pod(T, s, node_name=n)
        assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, s, node_name=n))
        assert pod.labels == {"color": "green", "name": "test"} and pod.affinity.pod_affinity is None
        assert pod.containers[0].requests == {"cpu": "100m", "memory": "500Mi"}
        anti = pod.affinity.pod_anti_affinity
        assert anti.preferred_during_scheduling_ignored_during_execution == ()
        (term,) = anti.required_during_scheduling_ignored_during_execution
        assert term.topology_key == HOSTNAME
        assert term.label_selector.match_labels == {"color": "green"}
        assert term.namespaces == ("sched-1", "sched-0")
    for s in (nodes[0], nodes[-1]):
        node = workload.build_node(T, R, s)
        assert dataclasses.asdict(node) == dataclasses.asdict(workload.build_node(RT, RR, s))
        assert node.labels == {HOSTNAME: node.name}


# ---- sched-perf-mixedbase-5k (upstream's :615), PR 41 ----------------------------

MIXED_CELL = "mixedbase-5k.backlog-on-base"
ZONE = "topology.kubernetes.io/zone"
NAMESPACES = ("sched-1", "sched-0")
# base group -> (role its pods are named after, template, labels, the one term:
# side, required or preferred, topology key, the colour it selects)
MIXED_BASE = {
    "base_affinity": ("base-affinity", "pod-with-pod-affinity", {"color": "blue"},
                      ("pod_affinity", "required", ZONE, "blue")),
    "base_anti_affinity": ("base-anti-affinity", "pod-with-pod-anti-affinity", {"color": "green", "name": "test"},
                           ("pod_anti_affinity", "required", HOSTNAME, "green")),
    "base_preferred_affinity": ("base-preferred-affinity", "pod-with-preferred-pod-affinity", {"color": "red"},
                                ("pod_affinity", "preferred", HOSTNAME, "red")),
    "base_preferred_anti_affinity": ("base-preferred-anti-affinity", "pod-with-preferred-pod-anti-affinity",
                                     {"color": "yellow"}, ("pod_anti_affinity", "preferred", HOSTNAME, "yellow")),
}
# the same digest over what benchmarks/workload.py and the traffic kind
# backlog_on_base build from the files PR 41 adds, seed 7: the init pods and
# the four planted groups on their seeded nodes, warm-up and measured backlogs
MIXED_PINNED = "b49a5c88f677b29b9aacb4b68e40fc4e44ea8035b8c81d96b91ca69721fadc6e"


def _mixed_built():
    cell = cells.cell(MIXED_CELL)
    return cell, _pr29._groups(cell["config"], cell["traffic"], cell["kind"], 7)


def test_mixedbase_config_round_trips_with_no_key_refused():
    """Every key of the file is one the harness or the traffic kind builds
    (no ``KeyError``), at the source's counts, with nothing cut: five placed
    groups of 2,000 in ``sched-0``, two pods a node over ONE seeded node
    order, the measured pods in a namespace no term names."""
    cell, (nodes, init, init_nodes, plan) = _mixed_built()
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["name"] == "sched-perf-mixedbase-5k"
    assert len(cfg["source"]) <= 200 and "performance-config.yaml:615" in cfg["source"]
    assert "MixedSchedulingBasePod/5000Nodes_5000Pods" in cfg["source"]
    assert cfg["identity_sample"] >= 4 and "BLIND" in cfg["identity_sample_why"] and cfg["assumed"]
    assert (len(nodes), len(init), len(plan["base"]), len(plan["warm"]), len(plan["measure"])) == \
        (5000, 2000, 8000, 5000, 5000)
    assert cell["traffic"]["base"] == list(MIXED_BASE)
    assert cell["kind"].pods_alive(plan) == 13000  # beside the 2,000 init pods the harness counts itself
    base_specs = [s for s, _n in plan["base"]]
    assert {s["namespace"] for s in init + base_specs} == {"sched-0"}
    assert {s["namespace"] for s in plan["warm"] + plan["measure"]} == {"namespace-6"}
    assert not {"namespace-6"} & {ns for s in base_specs for side in s["affinity"].values()
                                  for t in side.get("required", []) + [p["term"] for p in side.get("preferred", [])]
                                  for ns in t["namespaces"]}
    assert len({workload.uid_of(s) for s in init + base_specs + plan["warm"] + plan["measure"]}) == 20000
    # ONE node order: the base continues where the harness's init pods ended
    order = workload.init_placement(cfg, 10000, nodes, 7)
    assert order[:2000] == init_nodes and order[2000:] == [n for _s, n in plan["base"]]
    assert sorted(order) == sorted([n["name"] for n in nodes] * 2)  # two base pods a node
    green = [n for s, n in plan["base"] if s["labels"].get("color") == "green"]
    assert len(green) == len(set(green)) == 2000  # the required anti-affinity group on distinct nodes
    built = {n["name"]: n["labels"] for n in nodes}
    assert all(lb == {ZONE: "zone1", HOSTNAME: name} for name, lb in built.items())
    assert cfg["expect_kernels"] == ["resident.resident_run", "chain.chain_dispatch", "wave.wave_run", "gang.gang_run"]
    assert len(cfg["guarantees"]) == 5 and "15,000" in cfg["guarantees"][4]


@pytest.mark.parametrize("group", sorted(MIXED_BASE))
def test_mixedbase_base_groups_resolve_through_group_specs_and_are_the_sources_templates(group):
    """Each of the four term-carrying groups is a group like ``init_pods``
    (``workload.group_specs`` takes any group name): 2,000 specs of its
    template in ``sched-0``, named after the group, carrying the template's
    ONE term over both namespaces; equal field by field in the program's
    types and the frozen reference's."""
    from kubernetes_tpu.api import types as T

    cell, (_nodes, _init, _init_nodes, plan) = _mixed_built()
    role, template, labels, (side, strength, key, colour) = MIXED_BASE[group]
    specs = workload.group_specs(cell["config"], group, role)
    assert cell["config"][group] == {"count": 2000, "template": template, "namespace": "sched-0"}
    assert [s["name"] for s in specs[:2] + specs[-1:]] == [f"{role}-0", f"{role}-1", f"{role}-1999"]
    start = list(MIXED_BASE).index(group) * 2000
    assert [s for s, _n in plan["base"][start:start + 2000]] == specs
    for s, n in (plan["base"][start], plan["base"][start + 1999]):
        pod = workload.build_pod(T, s, node_name=n)
        assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, s, node_name=n))
        assert pod.labels == labels and pod.node_name == n and pod.namespace == "sched-0"
        assert pod.containers[0].requests == {"cpu": "100m", "memory": "500Mi"}
        other = "pod_anti_affinity" if side == "pod_affinity" else "pod_affinity"
        assert getattr(pod.affinity, other) is None
        body = getattr(pod.affinity, side)
        req = body.required_during_scheduling_ignored_during_execution
        pref = body.preferred_during_scheduling_ignored_during_execution
        if strength == "required":
            (term,), weights = req, [w for w in pref]
        else:
            (wt,), weights = pref, [w for w in req]
            term = wt.pod_affinity_term
            assert wt.weight == 1
        assert weights == []  # one term a template, of one kind
        assert term.topology_key == key and term.namespaces == NAMESPACES
        assert term.label_selector.match_labels == {"color": colour}


def test_mixedbase_plain_pods_and_the_pinned_objects():
    """``pod-default`` on both ends (the init group in ``sched-0``, warm-up
    and measured pods in ``namespace-6``): no labels, no term, 100m / 500Mi;
    and everything the cell builds, pinned by digest."""
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cell, (nodes, init, init_nodes, plan) = _mixed_built()
    for s, n in ((init[0], init_nodes[0]), (plan["warm"][0], ""), (plan["measure"][-1], "")):
        pod = workload.build_pod(T, s, node_name=n)
        assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, s, node_name=n))
        assert pod.labels == {} and pod.affinity is None and pod.topology_spread_constraints == ()
        assert pod.containers[0].requests == {"cpu": "100m", "memory": "500Mi"}
    placed = init + [s for s, _n in plan["base"]]
    placed_on = init_nodes + [n for _s, n in plan["base"]]
    assert _digest(T, R, nodes, placed, placed_on, plan) == MIXED_PINNED


# ---- northstar-basic-10k (BASELINE.json's north star on :51's templates), PR 44 ----

NORTHSTAR_CELL = "northstar-10k.backlog"
# the same digest over what benchmarks/workload.py builds from the file PR 44
# adds, seed 7: 10,000 nodes, 2,000 init pods, warm-up and measured backlogs of 65,536
NORTHSTAR_PINNED = "71d3f56398a914b7c5187bc88fa9870243c06d129b527b0aa9b812828bfda49f"


@functools.lru_cache(maxsize=1)  # 143,072 specs: built once for the cases below, which only read them
def _northstar_built():
    cell = cells.cell(NORTHSTAR_CELL)
    return cell, _pr29._groups(cell["config"], cell["traffic"], cell["kind"], 7)


def test_northstar_config_round_trips_with_no_key_refused():
    """Every key of the file is one the harness builds (no ``KeyError``), at
    the north star's node count and with ONE cut, the measured pods (100,000
    -> 65,536, so that the window closes inside ``run_seconds`` on the chip:
    the file's ``reduced_why``): a queue of 65,536 is four resident runs of
    ``residentRunMax`` = 16,384."""
    from kubernetes_tpu.framework.config import SchedulerConfiguration

    cell, (nodes, init, init_nodes, plan) = _northstar_built()
    cfg = cell["config"]
    assert cfg["reduced"] == ["measure_pods"] and "89,088 of 100,000" in cfg["reduced_why"]
    assert cfg["name"] == "northstar-basic-10k"
    assert (len(nodes), len(init), len(plan["warm"]), len(plan["measure"])) == (10000, 2000, 65536, 65536)
    assert len(set(init_nodes)) == 2000  # one init pod a node, on nodes drawn from the seed
    assert len({workload.uid_of(s) for s in init + plan["warm"] + plan["measure"]}) == 133072
    assert cell["kind"].pods_alive(plan) == 65536
    run_max = SchedulerConfiguration().resident_run_max
    assert len(plan["measure"]) == 4 * run_max


@pytest.mark.parametrize("group,index", [("init", 0), ("warm", 0), ("measure", 0), ("measure", 16383),
                                         ("measure", 16384), ("measure", 65535)])
def test_northstar_pods_are_pod_default_and_equal_on_both_sides(group, index):
    """ONE shape before and behind every run boundary: ``pod-default`` (100m /
    500Mi, no labels, no term), equal field by field in the program's types
    and the frozen reference's, built from the same spec."""
    from kubernetes_tpu.api import types as T

    _cell, (_nodes, init, init_nodes, plan) = _northstar_built()
    spec = {"init": init, **plan}[group][index]
    on = init_nodes[index] if group == "init" else ""
    pod = workload.build_pod(T, spec, node_name=on)
    assert dataclasses.asdict(pod) == dataclasses.asdict(workload.build_pod(RT, spec, node_name=on))
    assert pod.labels == {} and pod.affinity is None and pod.topology_spread_constraints == ()
    assert pod.containers[0].requests == {"cpu": "100m", "memory": "500Mi"}
    assert pod.uid == f"default/{ {'init': 'init', 'warm': 'warm', 'measure': 'load'}[group] }-{index}"


def test_northstar_config_builds_the_pinned_objects():
    from kubernetes_tpu.api import resource as R
    from kubernetes_tpu.api import types as T

    _cell, (nodes, init, init_nodes, plan) = _northstar_built()
    node = workload.build_node(T, R, nodes[0])
    assert {str(workload.build_node(T, R, s).capacity) for s in nodes} == {str(node.capacity)}  # ONE node shape
    assert (nodes[0]["name"], nodes[-1]["name"]) == ("scheduler-perf-0", "scheduler-perf-9999")
    assert _digest(T, R, nodes, init, init_nodes, plan) == NORTHSTAR_PINNED
