"""NodeResourcesFit scoring strategies: MostAllocated and
RequestedToCapacityRatio must steer placement on the batched device path
exactly like the host oracle (noderesources/most_allocated.go,
requested_to_capacity_ratio.go:32).
"""

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.framework import config as cfg
from kubernetes_tpu.oracle.scores import broken_linear
from kubernetes_tpu.scheduler import Scheduler


def _sched(strategy: str, shape=None):
    pc = {"scoringStrategy": {"type": strategy}}
    if shape is not None:
        pc["scoringStrategy"]["requestedToCapacityRatio"] = {"shape": shape}
    profile = cfg.Profile(plugin_config={"NodeResourcesFit": pc})
    sched = Scheduler(configuration=cfg.SchedulerConfiguration(profiles=[profile]))
    bindings = {}
    sched.binding_sink = lambda pod, node: bindings.__setitem__(pod.name, node)
    return sched, bindings


def _add_nodes(sched):
    # n0 pre-loaded (less free), n1 empty
    sched.on_node_add(
        Node(
            name="n0",
            labels={"kubernetes.io/hostname": "n0"},
            capacity=Resource.from_map({"cpu": "4", "memory": "8Gi"}),
        )
    )
    sched.on_node_add(
        Node(
            name="n1",
            labels={"kubernetes.io/hostname": "n1"},
            capacity=Resource.from_map({"cpu": "4", "memory": "8Gi"}),
        )
    )
    sched.on_pod_add(
        Pod(
            name="preload",
            node_name="n0",
            containers=[Container(requests={"cpu": "2", "memory": "4Gi"})],
        )
    )


def test_most_allocated_packs():
    """MostAllocated (bin packing) prefers the fuller node."""
    sched, bindings = _sched("MostAllocated")
    _add_nodes(sched)
    sched.on_pod_add(
        Pod(name="p", containers=[Container(requests={"cpu": "500m", "memory": "512Mi"})])
    )
    outs = sched.schedule_pending()
    assert outs[0].node == "n0", outs[0]
    assert bindings["p"] == "n0"


def test_least_allocated_spreads():
    sched, bindings = _sched("LeastAllocated")
    _add_nodes(sched)
    sched.on_pod_add(
        Pod(name="p", containers=[Container(requests={"cpu": "500m", "memory": "512Mi"})])
    )
    outs = sched.schedule_pending()
    assert outs[0].node == "n1", outs[0]


def test_rtcr_shape_packs():
    """An ascending shape (score grows with utilization) bin-packs."""
    shape = [
        {"utilization": 0, "score": 0},
        {"utilization": 100, "score": 10},
    ]
    sched, bindings = _sched("RequestedToCapacityRatio", shape=shape)
    _add_nodes(sched)
    sched.on_pod_add(
        Pod(name="p", containers=[Container(requests={"cpu": "500m", "memory": "512Mi"})])
    )
    outs = sched.schedule_pending()
    assert outs[0].node == "n0", outs[0]


def test_rtcr_shape_spreads():
    """A descending shape prefers emptier nodes."""
    shape = [
        {"utilization": 0, "score": 10},
        {"utilization": 100, "score": 0},
    ]
    sched, bindings = _sched("RequestedToCapacityRatio", shape=shape)
    _add_nodes(sched)
    sched.on_pod_add(
        Pod(name="p", containers=[Container(requests={"cpu": "500m", "memory": "512Mi"})])
    )
    outs = sched.schedule_pending()
    assert outs[0].node == "n1", outs[0]


def test_broken_linear_matches_reference_semantics():
    pts = ((0, 0), (50, 80), (100, 100))
    assert broken_linear(pts, -5) == 0
    assert broken_linear(pts, 0) == 0
    assert broken_linear(pts, 25) == 40
    assert broken_linear(pts, 50) == 80
    assert broken_linear(pts, 75) == 90
    assert broken_linear(pts, 100) == 100
    assert broken_linear(pts, 150) == 100


def test_extended_resource_spec_scored_host_side():
    """resources beyond cpu/memory are accepted (resource_allocation.go
    handles arbitrary resources) and flip the plugin to host scoring."""
    profile = cfg.Profile(
        plugin_config={
            "NodeResourcesFit": {
                "scoringStrategy": {
                    "type": "MostAllocated",
                    "resources": [{"name": "nvidia.com/gpu", "weight": 1}],
                }
            }
        }
    )
    sched = Scheduler(configuration=cfg.SchedulerConfiguration(profiles=[profile]))
    inst = next(iter(sched.profiles.values()))._instances["NodeResourcesFit"]
    assert inst.device_score is False


class TestExtendedResourceScoring:
    """scoringStrategy.resources beyond cpu/memory
    (resource_allocation.go:37-115 scores arbitrary resources, including
    scalars); such configs route scoring through the exact host path."""

    def _gpu_sched(self, strategy="MostAllocated"):
        pc = {
            "scoringStrategy": {
                "type": strategy,
                "resources": [{"name": "example.com/gpu", "weight": 5}],
            }
        }
        profile = cfg.Profile(plugin_config={"NodeResourcesFit": pc})
        sched = Scheduler(
            configuration=cfg.SchedulerConfiguration(profiles=[profile])
        )
        bindings = {}
        sched.binding_sink = lambda pod, node: bindings.__setitem__(
            pod.name, node
        )
        return sched, bindings

    def test_extended_resource_config_accepted(self):
        sched, _ = self._gpu_sched()
        inst = next(iter(sched.profiles.values()))._instances["NodeResourcesFit"]
        assert inst.device_score is False
        assert ("example.com/gpu", 5) in inst.fit_resources

    def test_most_allocated_packs_onto_fuller_gpu_node(self):
        sched, bindings = self._gpu_sched("MostAllocated")
        for name, used in (("g0", 6), ("g1", 1)):
            sched.on_node_add(
                Node(
                    name=name,
                    labels={"kubernetes.io/hostname": name},
                    capacity=Resource.from_map(
                        {"cpu": "16", "memory": "64Gi", "example.com/gpu": 8}
                    ),
                )
            )
            for v in range(used):
                sched.on_pod_add(
                    Pod(
                        name=f"f-{name}-{v}",
                        node_name=name,
                        containers=[
                            Container(requests={"example.com/gpu": 1})
                        ],
                    )
                )
        sched.on_pod_add(
            Pod(
                name="want-gpu",
                containers=[
                    Container(
                        requests={
                            "cpu": "100m",
                            "memory": "64Mi",
                            "example.com/gpu": 1,
                        }
                    )
                ],
            )
        )
        outs = sched.schedule_pending()
        assert bindings["want-gpu"] == "g0", outs  # MostAllocated packs

    def test_least_allocated_spreads_off_fuller_gpu_node(self):
        sched, bindings = self._gpu_sched("LeastAllocated")
        for name, used in (("g0", 6), ("g1", 1)):
            sched.on_node_add(
                Node(
                    name=name,
                    labels={"kubernetes.io/hostname": name},
                    capacity=Resource.from_map(
                        {"cpu": "16", "memory": "64Gi", "example.com/gpu": 8}
                    ),
                )
            )
            for v in range(used):
                sched.on_pod_add(
                    Pod(
                        name=f"f-{name}-{v}",
                        node_name=name,
                        containers=[
                            Container(requests={"example.com/gpu": 1})
                        ],
                    )
                )
        sched.on_pod_add(
            Pod(
                name="want-gpu",
                containers=[
                    Container(requests={"example.com/gpu": 1})
                ],
            )
        )
        sched.schedule_pending()
        assert bindings["want-gpu"] == "g1"


# ---------------------------------------------------------------------------
# gang.pod_step's fit score, lane by lane, against the serial oracle
# ---------------------------------------------------------------------------
#
# pod_step computes the score through gang.fit_score on the cpu and memory
# lanes as separate [N] vectors; these cases hold that function, fed from
# the PACKED cluster exactly as pod_step feeds it, to the oracle's integer
# score on every node, and gang_schedule's scan (pod_step with commits) to
# the oracle's serial decisions.  Quantities are whole MiB: the packed
# lanes are MiB, the oracle counts bytes.

_RTCR_SHAPE = ((0, 0), (40, 100), (100, 20))  # rises, then falls; 0 at 0
_STRATEGIES = {
    "least": (0, ()),
    "most": (1, ()),
    "rtcr": (2, _RTCR_SHAPE),
}


def _node(name, cpu, memory=None):
    cap = {"cpu": cpu, "pods": "110"}
    if memory is not None:
        cap["memory"] = memory
    return Node(
        name=name,
        labels={"kubernetes.io/hostname": name},
        capacity=Resource.from_map(cap),
    )


def _pod(name, node=None, **requests):
    return Pod(
        name=name,
        node_name=node,
        containers=[Container(requests=dict(requests))] if requests else [Container()],
    )


def _case_zero_allocatable_lane():
    # n0 has no memory lane at all (lane_has false there): only cpu counts
    nodes = [_node("n0", "4"), _node("n1", "4", "8Gi"), _node("n2", "2", "16Gi")]
    placed = [_pod("f0", "n0", cpu="1"), _pod("f1", "n1", cpu="500m", memory="1Gi")]
    return nodes, placed, [_pod(f"p{i}", cpu="300m") for i in range(6)]


def _case_over_allocatable_on_one_lane():
    # the non-zero-defaulted memory (200Mi a pod) passes n0's 256Mi while
    # the REAL request (none) still fits: memory scores 0, cpu still counts
    nodes = [_node("n0", "8", "256Mi"), _node("n1", "2", "4Gi"), _node("n2", "1", "2Gi")]
    placed = [_pod("f0", "n0", cpu="1"), _pod("f1", "n0", cpu="1")]
    return nodes, placed, [_pod(f"p{i}", cpu="250m") for i in range(6)]


def _case_memory_above_2_pow_32_bytes():
    nodes = [
        _node("n0", "64", "512Gi"),
        _node("n1", "96", "1024Gi"),
        _node("n2", "32", "6Gi"),
    ]
    placed = [
        _pod("f0", "n0", cpu="8", memory="300Gi"),
        _pod("f1", "n1", cpu="40", memory="5Gi"),
    ]
    pending = [_pod(f"p{i}", cpu="3", memory=f"{5 + 9 * i}Gi") for i in range(6)]
    return nodes, placed, pending


def _case_one_lane_scores_zero():
    # n0: 1,000 cores → cpu utilisation truncates to 0 (RTCR: frac 0, lane
    # not used, the mean is memory's alone); n2 is full on cpu (Least: 0)
    nodes = [_node("n0", "1000", "8Gi"), _node("n1", "4", "8Gi"), _node("n2", "1", "64Gi")]
    placed = [_pod("f0", "n1", cpu="1", memory="3Gi"), _pod("f2", "n2", cpu="900m", memory="1Gi")]
    return nodes, placed, [_pod(f"p{i}", cpu="100m", memory="1Gi") for i in range(6)]


def _case_mixed_loads():
    nodes = [_node(f"n{i}", str(2 + 3 * i), f"{4 + 5 * i}Gi") for i in range(5)]
    placed = [
        _pod(f"f{i}", f"n{i}", cpu=f"{300 + 700 * i}m", memory=f"{512 + 1536 * i}Mi")
        for i in range(5)
    ]
    pending = [
        _pod(f"p{i}", cpu=f"{150 + 350 * (i % 3)}m", memory=f"{256 + 768 * (i % 4)}Mi")
        for i in range(8)
    ]
    return nodes, placed, pending


_FIT_CASES = {
    "zero_allocatable_lane": (_case_zero_allocatable_lane, (1, 1)),
    "over_allocatable_on_one_lane": (_case_over_allocatable_on_one_lane, (1, 1)),
    "memory_above_2_pow_32_bytes": (_case_memory_above_2_pow_32_bytes, (2, 1)),
    "one_lane_scores_zero": (_case_one_lane_scores_zero, (1, 1)),
    "unequal_lane_weights": (_case_mixed_loads, (1, 3)),
}


def _oracle_fit_score(strategy, weights, pod, ns):
    from kubernetes_tpu.oracle import scores as OS

    resources = (("cpu", weights[0]), ("memory", weights[1]))
    if strategy == "most":
        return OS.score_most_allocated(pod, ns, resources)
    if strategy == "rtcr":
        return OS.score_requested_to_capacity_ratio(pod, ns, _RTCR_SHAPE, resources)
    return OS.score_least_allocated(pod, ns, resources)


def _packed(state, pending):
    import jax.numpy as jnp

    from kubernetes_tpu.oracle.scores import HOSTNAME_LABEL
    from kubernetes_tpu.ops import gang
    from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32
    from kubernetes_tpu.snapshot.cluster import pack_cluster
    from kubernetes_tpu.snapshot.interner import Vocab
    from kubernetes_tpu.snapshot.schema import bucket_cap, pack_pod_batch

    vocab = Vocab()
    pc = pack_cluster(state, vocab, pending_pods=pending)
    pb = pack_pod_batch(pending, vocab, k_cap=pc.nodes.k_cap)
    dc = DeviceCluster.from_host(pc.nodes, pc.existing, vocab)
    db = DeviceBatch.from_host(pb)
    v_cap = bucket_cap(len(vocab.label_vals))
    host_key = vocab.label_keys.lookup(HOSTNAME_LABEL)
    tables = gang.batch_tables(
        pb.tsc_topo_key, pb.aff_topo_key, pc.nodes.label_vals, host_key
    )
    d_cap = tables.pop("d_cap")
    g = gang.precompute(dc, db, jnp.asarray(host_key, I32), v_cap, **tables)
    return dc, db, g, v_cap, d_cap


@pytest.mark.parametrize("strategy", sorted(_STRATEGIES))
@pytest.mark.parametrize("case", sorted(_FIT_CASES))
def test_fit_score_lanes_match_oracle_bit_for_bit(case, strategy):
    """gang.fit_score on the packed lanes == the oracle's integer score on
    every node, for the first pending pod and again after each serial
    placement (the tallies drift as pod_step's carry does)."""
    import numpy as np

    from kubernetes_tpu.oracle.state import OracleState
    from kubernetes_tpu.ops import gang
    from kubernetes_tpu.ops.common import I64
    from kubernetes_tpu.snapshot.schema import LANE_CPU, LANE_MEM

    build, weights = _FIT_CASES[case]
    strat_id, shape = _STRATEGIES[strategy]
    nodes, placed, pending = build()
    state = OracleState.build(nodes, placed)
    dc, db, _, _, _ = _packed(state, pending)
    names = list(state.nodes)
    n = len(names)
    a0 = dc.allocatable[:, LANE_CPU].astype(I64)
    a1 = dc.allocatable[:, LANE_MEM].astype(I64)
    nonzero = np.asarray(dc.nonzero_req).astype(np.int64)
    memory_lane_counted = False
    for i, pod in enumerate(pending):
        nz = np.asarray(db.nonzero_req[i]).astype(np.int64)
        got = np.asarray(
            gang.fit_score(
                (strat_id, shape, weights),
                a0,
                a1,
                nonzero[:, 0] + nz[0],
                nonzero[:, 1] + nz[1],
            )
        )
        assert got.dtype == np.int64
        want = [_oracle_fit_score(strategy, weights, pod, state.nodes[nm]) for nm in names]
        assert got[:n].tolist() == want, (case, strategy, i)
        one = [
            _oracle_fit_score(strategy, (weights[0], 0), pod, state.nodes[nm])
            for nm in names
        ]
        memory_lane_counted |= one != want
        # serial drift: the pod lands on the node the oracle's score prefers
        target = names[max(range(n), key=lambda j: (want[j], -j))]
        pod.node_name = target
        state.place(pod)
        nonzero[names.index(target)] += nz
    assert memory_lane_counted, "the memory lane never moved the score"


@pytest.mark.parametrize("weights", [(1, 1), (1, 3)])
@pytest.mark.parametrize("strategy", sorted(_STRATEGIES))
def test_gang_scan_fit_only_matches_serial_oracle(strategy, weights):
    """pod_step itself (gang_schedule's scan, NodeResourcesFit the only
    score) decides like the oracle scheduling one pod at a time, over the
    union of the edge-case nodes."""
    import numpy as np

    from kubernetes_tpu.oracle.pipeline import feasible_nodes
    from kubernetes_tpu.oracle.state import OracleState
    from kubernetes_tpu.ops import gang

    strat_id, shape = _STRATEGIES[strategy]
    nodes, placed, pending = [], [], []
    for k, (build, _) in enumerate(_FIT_CASES.values()):
        ns_, pl_, pe_ = build()
        for nd in ns_:
            nd.name = f"c{k}-{nd.name}"
            nd.labels = {"kubernetes.io/hostname": nd.name}
        for p in pl_:
            p.name, p.node_name = f"c{k}-{p.name}", f"c{k}-{p.node_name}"
        for p in pe_:
            p.name = f"c{k}-{p.name}"
        nodes += ns_
        placed += pl_
        pending += pe_

    def fresh():
        import copy

        return OracleState.build(copy.deepcopy(nodes), copy.deepcopy(placed))

    state = fresh()
    dc, db, g, v_cap, d_cap = _packed(state, pending)
    only_fit = (0, 0, 0, 0, 1, 0, 0)
    chosen, _, _, _ = gang.gang_schedule(
        dc, db, g, v_cap, weights=only_fit, d_cap=d_cap,
        fit_strategy=(strat_id, shape, weights),
    )
    names = list(state.nodes)
    got = [names[int(c)] if int(c) >= 0 else None for c in np.asarray(chosen)[: len(pending)]]

    serial = fresh()
    want = []
    for pod in pending:
        fit = feasible_nodes(pod, serial).feasible
        if not fit:
            want.append(None)
            continue
        best = max(
            fit,
            key=lambda nm: (
                _oracle_fit_score(strategy, weights, pod, serial.nodes[nm]),
                -names.index(nm),
            ),
        )
        want.append(best)
        pod.node_name = best
        serial.place(pod)
    assert got == want
    assert len(set(want)) > 3  # the scores did steer the pods apart
