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
import hashlib

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
    assert h.hexdigest() == PINNED
