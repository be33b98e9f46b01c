"""NodeResourcesFit decision tables ported from the reference's own unit
suite (pkg/scheduler/framework/plugins/noderesources/fit_test.go — the
enoughPodsTests / notEnoughPodsTests / extended-resource / init-container
tables), run against BOTH the host oracle (oracle/filters.py) and the
device kernels (ops/filters.mask_resources + the fast path's
FastCommitter.feasible_int).

This is the start of the reference-ANCHORED parity story: every other
parity check proves device == our own oracle; these cases pin the oracle
itself to the reference's published expectations, as data (inputs +
expected insufficient-resource reasons), not translated code.  Units follow the reference table's spirit: cpu in
whole cores, memory/ephemeral-storage in Mi (exact under the packed MiB
lanes, so all three implementations judge identical quantities).
"""

from typing import Dict, List, Optional

import numpy as np
import pytest

from kubernetes_tpu import fastpath as fp
from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.oracle import filters as OF
from kubernetes_tpu.oracle.state import OracleState
from kubernetes_tpu.ops import filters as KF
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster
from kubernetes_tpu.snapshot.cluster import pack_cluster
from kubernetes_tpu.snapshot.schema import (
    MEM_UNIT,
    N_FIXED_LANES,
    ResourceLanes,
    pack_pod_batch,
)

# ---------------------------------------------------------------------------
# case table — each entry mirrors one fit_test.go case:
#   pod:      containers / init containers / sidecars / overhead requests
#   existing: requests of a pod already placed on the node
#   node:     allocatable (defaults cpu=10, memory=20Mi, pods=32)
#   fits:     expected verdict
#   reasons:  expected insufficient-resource reasons (oracle exact-match)
# ---------------------------------------------------------------------------

FOO = "example.com/foo"
DEFAULT_NODE = {"cpu": "10", "memory": "20Mi", "pods": 32}

CASES = [
    # ----- enoughPodsTests -------------------------------------------------
    dict(
        name="no resources requested always fits",
        pod={},
        existing={"cpu": "10", "memory": "20Mi"},
        fits=True,
    ),
    dict(
        name="too many resources fails",
        pod={"req": {"cpu": "1", "memory": "1Mi"}},
        existing={"cpu": "10", "memory": "20Mi"},
        fits=False,
        reasons=["Insufficient cpu", "Insufficient memory"],
    ),
    dict(
        name="too many resources fails due to init container cpu",
        pod={"req": {"cpu": "1", "memory": "1Mi"}, "init": [{"cpu": "3", "memory": "1Mi"}]},
        existing={"cpu": "8", "memory": "19Mi"},
        fits=False,
        reasons=["Insufficient cpu"],
    ),
    dict(
        name="too many resources fails due to highest init container cpu",
        pod={
            "req": {"cpu": "1", "memory": "1Mi"},
            "init": [{"cpu": "3", "memory": "1Mi"}, {"cpu": "2", "memory": "1Mi"}],
        },
        existing={"cpu": "8", "memory": "19Mi"},
        fits=False,
        reasons=["Insufficient cpu"],
    ),
    dict(
        name="too many resources fails due to init container memory",
        pod={"req": {"cpu": "1", "memory": "1Mi"}, "init": [{"cpu": "1", "memory": "3Mi"}]},
        existing={"cpu": "9", "memory": "19Mi"},
        fits=False,
        reasons=["Insufficient memory"],
    ),
    dict(
        name="too many resources fails due to highest init container memory",
        pod={
            "req": {"cpu": "1", "memory": "1Mi"},
            "init": [{"cpu": "1", "memory": "3Mi"}, {"cpu": "1", "memory": "2Mi"}],
        },
        existing={"cpu": "9", "memory": "19Mi"},
        fits=False,
        reasons=["Insufficient memory"],
    ),
    dict(
        name="init container fits because it's the max, not sum, of containers and init containers",
        pod={"req": {"cpu": "1", "memory": "1Mi"}, "init": [{"cpu": "1", "memory": "1Mi"}]},
        existing={"cpu": "9", "memory": "19Mi"},
        fits=True,
    ),
    dict(
        name="multiple init containers fit because it's the max, not sum",
        pod={
            "req": {"cpu": "1", "memory": "1Mi"},
            "init": [{"cpu": "1", "memory": "1Mi"}, {"cpu": "1", "memory": "1Mi"}],
        },
        existing={"cpu": "9", "memory": "19Mi"},
        fits=True,
    ),
    dict(
        name="both resources fit",
        pod={"req": {"cpu": "1", "memory": "1Mi"}},
        existing={"cpu": "5", "memory": "5Mi"},
        fits=True,
    ),
    dict(
        name="one resource memory fits",
        pod={"req": {"cpu": "2", "memory": "1Mi"}},
        existing={"cpu": "9", "memory": "5Mi"},
        fits=False,
        reasons=["Insufficient cpu"],
    ),
    dict(
        name="one resource cpu fits",
        pod={"req": {"cpu": "1", "memory": "2Mi"}},
        existing={"cpu": "5", "memory": "19Mi"},
        fits=False,
        reasons=["Insufficient memory"],
    ),
    dict(
        name="equal edge case",
        pod={"req": {"cpu": "4", "memory": "1Mi"}},
        existing={"cpu": "6", "memory": "1Mi"},
        fits=True,
    ),
    dict(
        name="equal edge case for init container",
        pod={"init": [{"cpu": "4", "memory": "1Mi"}]},
        existing={"cpu": "6", "memory": "1Mi"},
        fits=True,
    ),
    dict(
        name="extended resource fits",
        pod={"req": {FOO: 1}},
        existing={},
        node={**DEFAULT_NODE, FOO: 4},
        fits=True,
    ),
    dict(
        name="extended resource fits for init container",
        pod={"init": [{FOO: 1}]},
        existing={},
        node={**DEFAULT_NODE, FOO: 4},
        fits=True,
    ),
    dict(
        name="extended resource capacity enforced",
        pod={"req": {FOO: 10}},
        existing={},
        node={**DEFAULT_NODE, FOO: 5},
        fits=False,
        reasons=[f"Insufficient {FOO}"],
    ),
    dict(
        name="extended resource capacity enforced for init container",
        pod={"init": [{FOO: 10}]},
        existing={},
        node={**DEFAULT_NODE, FOO: 5},
        fits=False,
        reasons=[f"Insufficient {FOO}"],
    ),
    dict(
        name="extended resource allocatable enforced",
        pod={"req": {FOO: 1}},
        existing={FOO: 5},
        node={**DEFAULT_NODE, FOO: 5},
        fits=False,
        reasons=[f"Insufficient {FOO}"],
    ),
    dict(
        name="extended resource allocatable enforced for multiple containers",
        pod={"req": {FOO: 3}, "extra_containers": [{FOO: 3}]},
        existing={},
        node={**DEFAULT_NODE, FOO: 5},
        fits=False,
        reasons=[f"Insufficient {FOO}"],
    ),
    dict(
        name="extended resource allocatable admits multiple init containers",
        pod={"init": [{FOO: 3}, {FOO: 2}]},
        existing={FOO: 2},
        node={**DEFAULT_NODE, FOO: 5},
        fits=True,
    ),
    dict(
        name="extended resource allocatable enforced for multiple init containers",
        pod={"init": [{FOO: 4}, {FOO: 2}]},
        existing={FOO: 2},
        node={**DEFAULT_NODE, FOO: 5},
        fits=False,
        reasons=[f"Insufficient {FOO}"],
    ),
    dict(
        name="extended resource allocatable enforced for unknown resource",
        pod={"req": {"example.com/new": 1}},
        existing={},
        fits=False,
        reasons=["Insufficient example.com/new"],
    ),
    dict(
        name="extended resource allocatable enforced for unknown resource for init container",
        pod={"init": [{"example.com/new": 1}]},
        existing={},
        fits=False,
        reasons=["Insufficient example.com/new"],
    ),
    dict(
        name="ignored extended resource via prefix",
        pod={"req": {"example.com/ignored": 2}},
        existing={},
        ignored_prefixes=("example.com/",),
        fits=True,
        oracle_only=True,  # the prefix list is a host-plugin argument
    ),
    # ----- notEnoughPodsTests (allowedPodNumber) ---------------------------
    dict(
        name="even without specified resources, predicate fails when there's no space for additional pod",
        pod={"req": {"cpu": "1", "memory": "1Mi"}},
        existing={"cpu": "5", "memory": "5Mi"},
        node={"cpu": "10", "memory": "20Mi", "pods": 1},
        fits=False,
        reasons=["Too many pods"],
    ),
    dict(
        name="even if both resources fit, predicate fails when there's no space for additional pod",
        pod={"req": {"cpu": "1", "memory": "1Mi"}},
        existing={"cpu": "5", "memory": "5Mi"},
        node={"cpu": "10", "memory": "20Mi", "pods": 1},
        fits=False,
        reasons=["Too many pods"],
    ),
    dict(
        name="even for equal edge case, predicate fails when there's no space for additional pod",
        pod={"req": {"cpu": "4", "memory": "1Mi"}},
        existing={"cpu": "6", "memory": "1Mi"},
        node={"cpu": "10", "memory": "20Mi", "pods": 1},
        fits=False,
        reasons=["Too many pods"],
    ),
    # ----- overhead / ephemeral / sidecars ---------------------------------
    dict(
        name="requests + overhead does not fit for memory",
        pod={"req": {"cpu": "1", "memory": "1Mi"}, "overhead": {"cpu": "1", "memory": "2Mi"}},
        existing={"cpu": "5", "memory": "18Mi"},
        fits=False,
        reasons=["Insufficient memory"],
    ),
    dict(
        name="requests + overhead fits",
        pod={"req": {"cpu": "1", "memory": "1Mi"}, "overhead": {"cpu": "1", "memory": "1Mi"}},
        existing={"cpu": "5", "memory": "5Mi"},
        fits=True,
    ),
    dict(
        name="storage ephemeral local storage request exceeds allocatable",
        pod={"req": {"ephemeral-storage": "25Mi"}},
        existing={},
        node={"cpu": "10", "memory": "20Mi", "pods": 32, "ephemeral-storage": "20Mi"},
        fits=False,
        reasons=["Insufficient ephemeral-storage"],
    ),
    dict(
        name="ephemeral local storage request fits",
        pod={"req": {"ephemeral-storage": "10Mi"}},
        existing={"ephemeral-storage": "5Mi"},
        node={"cpu": "10", "memory": "20Mi", "pods": 32, "ephemeral-storage": "20Mi"},
        fits=True,
    ),
    dict(
        name="restartable init container sums with regular containers",
        pod={"req": {"cpu": "1"}, "sidecar": [{"cpu": "1"}]},
        existing={"cpu": "8"},
        fits=True,
    ),
    dict(
        name="restartable init container over capacity fails",
        pod={"req": {"cpu": "1"}, "sidecar": [{"cpu": "1"}]},
        existing={"cpu": "9"},
        fits=False,
        reasons=["Insufficient cpu"],
    ),
]


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def _pod(spec: Dict, name="test-pod", node_name: Optional[str] = None) -> Pod:
    containers: List[Container] = [Container(name="c0", requests=spec.get("req", {}))]
    for i, req in enumerate(spec.get("extra_containers", [])):
        containers.append(Container(name=f"c{i + 1}", requests=req))
    inits = [
        Container(name=f"init{i}", requests=req)
        for i, req in enumerate(spec.get("init", []))
    ]
    inits += [
        Container(name=f"sidecar{i}", requests=req, restart_policy="Always")
        for i, req in enumerate(spec.get("sidecar", []))
    ]
    return Pod(
        name=name,
        node_name=node_name,
        containers=containers,
        init_containers=inits,
        overhead=spec.get("overhead") or {},
    )


def _node(case) -> Node:
    alloc = dict(case.get("node", DEFAULT_NODE))
    return Node(
        name="test-node",
        labels={"kubernetes.io/hostname": "test-node"},
        capacity=Resource.from_map(alloc),
    )


def _state(case) -> OracleState:
    node = _node(case)
    placed = []
    if case.get("existing"):
        placed.append(_pod({"req": case["existing"]}, name="existing", node_name=node.name))
    return OracleState.build([node], placed)


# ---------------------------------------------------------------------------
# the three implementations under test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", CASES, ids=[c["name"] for c in CASES])
def test_oracle_matches_reference_table(case):
    state = _state(case)
    pod = _pod(case["pod"])
    reasons = OF.filter_node_resources(
        pod, state.nodes["test-node"], case.get("ignored_prefixes", ())
    )
    assert (not reasons) == case["fits"], reasons
    assert sorted(reasons) == sorted(case.get("reasons", [])), reasons


@pytest.mark.parametrize(
    "case",
    [c for c in CASES if not c.get("oracle_only")],
    ids=[c["name"] for c in CASES if not c.get("oracle_only")],
)
def test_device_kernel_matches_reference_table(case):
    state = _state(case)
    pod = _pod(case["pod"])
    pc = pack_cluster(state, pending_pods=[pod])
    pb = pack_pod_batch([pod], pc.vocab, k_cap=pc.nodes.k_cap)
    dc = DeviceCluster.from_host(pc.nodes, pc.existing, pc.vocab)
    db = DeviceBatch.from_host(pb)
    got = bool(np.asarray(KF.mask_resources(dc, db))[0, 0])
    assert got == case["fits"]


@pytest.mark.parametrize(
    "case",
    [c for c in CASES if not c.get("oracle_only")],
    ids=[c["name"] for c in CASES if not c.get("oracle_only")],
)
def test_fast_committer_matches_reference_table(case):
    """The signature fast path's host committer (bit-identical to the
    sig_scan kernel by test_fastpath's property tests) must judge the
    same tables — closing the loop oracle == kernels == fast path."""
    state = _state(case)
    pod = _pod(case["pod"])
    pc = pack_cluster(state, pending_pods=[pod])
    nt = pc.nodes
    lanes = ResourceLanes(pc.vocab)
    R = nt.allocatable.shape[1]
    req = pod.compute_requests()
    row = tuple(int(x) for x in lanes.request_row(req, R))
    # a scalar whose lane exceeds the packed width reads as unsatisfiable
    # on every node (the scheduler's signature path re-keys after interning
    # grows the lane table); model that as an extra over-width lane
    dropped = any(
        lanes.vocab.resources.intern(nm) + N_FIXED_LANES >= R
        for nm in req.scalars
    )
    nz = req.non_zero_defaulted()
    sig = fp.Signature(
        req_row=row,
        nz0=nz.milli_cpu,
        nz1=-(-nz.memory // MEM_UNIT),
        all_zero=all(v == 0 for v in row) and not req.scalars,
        static_ok=np.ones(nt.valid.shape[0], dtype=bool),
    )
    fc = fp.FastCommitter(nt, weights=(0, 0, 0, 0, 1, 1, 0), check_fit=True)
    got = fc.feasible_int(0, sig) and not dropped
    assert got == case["fits"]