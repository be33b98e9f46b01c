"""Decision-parity evidence at scales beyond a pytest budget.

The flagship claim — "binding decisions identical to default-scheduler" —
needs evidence at scales no CI-budget pytest run can afford.  This tool
produces it on the device it is run on; ``chip_smoke.py``'s identity phase
and tests/test_spans.py, test_wave.py and test_tpu_compile.py use its
drains and pod generators:

  * CROSS-BATCH-SIZE identity at 10k nodes / 50k pods: the extended
    device fast path (fastBatchMax=4096, sig_scan pipeline) against a
    64-pod-batch drain (host-greedy committer) — completely different
    machinery whose decisions must be bit-identical because both replay
    the sequential one-pod-at-a-time argmax;
  * SAMPLING-COMPAT vs the serial oracle at 2k nodes / 3k pods over
    3 zones: the device kernel's nodeTree-ordered sampling window,
    rotation cursor, and seeded tie-break against the scalar
    reference-shaped loop (schedule_one semantics).

Writes one JSON artifact {"checks": {...}, "total_diffs": N}.  Run
standalone:

    python -m kubernetes_tpu.tools.paritycheck [--out PARITY.json]
"""

from __future__ import annotations

import argparse
import json
import random
import time
from typing import Dict, List, Optional


def _basic_nodes(n, zones=3):
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node

    return [
        Node(
            name=f"node-{i}",
            labels={
                "topology.kubernetes.io/zone": f"zone-{i % zones}",
                "kubernetes.io/hostname": f"node-{i}",
            },
            capacity=Resource.from_map(
                {"cpu": "8", "memory": "32Gi", "pods": 110}
            ),
        )
        for i in range(n)
    ]


def _basic_pods(n, seed=4242):
    from kubernetes_tpu.api.types import Container, Pod

    rng = random.Random(seed)
    return [
        Pod(
            name=f"pp-{i}",
            labels={"app": f"app-{i % 16}"},
            containers=[
                Container(
                    name="c",
                    requests={
                        "cpu": f"{rng.choice([100, 250, 500])}m",
                        "memory": f"{rng.choice([128, 256, 512])}Mi",
                    },
                )
            ],
        )
        for i in range(n)
    ]


def _drain(nodes, pods, return_sched: bool = False, **cfg_kw):
    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler

    cfg = SchedulerConfiguration()
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    s = Scheduler(configuration=cfg)
    got: Dict[str, Optional[str]] = {}
    s.binding_sink = lambda pod, node: got.__setitem__(pod.name, node)
    s.mirror.e_cap_hint = len(pods) + cfg.batch_size + 128
    for n in nodes:
        s.on_node_add(n)
    for p in pods:
        s.on_pod_add(p)
    outs = s.schedule_pending()
    for o in outs:
        got.setdefault(o.pod.name, o.node)
    if return_sched:
        return got, s
    return got


def device_faults(sched) -> List:
    """Diff rows for a drain whose DEVICE path was abandoned: a booked
    breaker failure, a breaker that is not closed, or a batch handed to a
    breaker fallback.  The fallback engines are bit-identical by design,
    so without this a run in which the chip compiled nothing would still
    certify zero diffs — fail loud instead (empty list = the device
    answered every dispatch)."""
    rows: List = []
    for kernel, b in sorted(sched.kernels.breaker_rows().items()):
        if b["state"] != "closed" or b["failures"] or b["trips"]:
            rows.append(
                (
                    f"__breaker__{kernel}",
                    f"{b['state']} failures={b['failures']} "
                    f"trips={b['trips']} kind={b['last_kind']}",
                    "closed",
                )
            )
    for line in sched.prom.kernel_breaker_failures.expose():
        # the counter never resets on a later success (the row above does)
        if not line.startswith("#") and float(line.rsplit(" ", 1)[1]) > 0:
            rows.append(("__breaker_failures__", line, 0))
    fallbacks = sched.prom.wave_fallback.value(reason="breaker")
    if fallbacks:
        rows.append(("__breaker_fallbacks__", int(fallbacks), 0))
    return rows


def _diff(a: Dict, b: Dict) -> List:
    keys = set(a) | set(b)
    return sorted(
        (k, a.get(k), b.get(k)) for k in keys if a.get(k) != b.get(k)
    )


def check_cross_batch(n_nodes=10000, n_pods=50000) -> dict:
    """Device sig_scan pipeline (4096-extended batches) vs host-greedy
    64-pod batches — identical bindings at north-star scale."""
    import copy

    nodes = _basic_nodes(n_nodes)
    pods = _basic_pods(n_pods)
    t0 = time.perf_counter()
    big, s_big = _drain(nodes, copy.deepcopy(pods), return_sched=True)
    small, s_small = _drain(
        nodes,
        copy.deepcopy(pods),
        return_sched=True,
        batch_size=64,
        fast_batch_max=64,
    )
    diffs = device_faults(s_big) + device_faults(s_small) + _diff(big, small)
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "bound_a": sum(1 for v in big.values() if v),
        "bound_b": sum(1 for v in small.values() if v),
        "diffs": len(diffs),
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def check_compat_vs_oracle(n_nodes=2000, n_pods=3000, seed=77) -> dict:
    """Sampling-compat + seeded-tie device pipeline vs the serial oracle
    (reference-shaped one-pod loop in nodeTree order)."""
    import copy

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.oracle.pipeline import (
        feasible_nodes,
        num_feasible_nodes_to_find,
        prioritize,
    )
    from kubernetes_tpu.oracle.state import OracleState

    nodes = _basic_nodes(n_nodes, zones=3)
    pods = _basic_pods(n_pods, seed=seed)
    t0 = time.perf_counter()
    got = _drain(
        nodes,
        copy.deepcopy(pods),
        reference_sampling_compat=True,
        tie_break_seed=seed,
    )

    state = OracleState.build(nodes)
    key = jax.random.PRNGKey(seed)
    # one device call for ALL attempts' tie-break hashes instead of a
    # dispatch + fetch round trip per pod
    h_all = np.asarray(
        jax.vmap(
            lambda a: jax.random.bits(
                jax.random.fold_in(key, a), (n_nodes,), dtype=jnp.uint32
            )
        )(jnp.arange(n_pods))
    )
    idx_of = {name: i for i, name in enumerate(state.nodes)}
    start = 0
    attempt = 0
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        fit = feasible_nodes(pod, state, sample_pct=0, start_index=start)
        start = (start + fit.processed) % n_nodes
        totals = prioritize(pod, state, fit.feasible)
        if not totals:
            want[pod.name] = None
            continue
        h = h_all[attempt]
        attempt += 1
        node = max(totals, key=lambda m: (totals[m], int(h[idx_of[m]])))
        want[pod.name] = node
        pod.node_name = node
        state.place(pod)
    diffs = _diff(got, want)
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "bound_device": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": len(diffs),
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def _cross_pod_pods(n, seed=99):
    """Mixed spread / anti-affinity / plain pods — the wave path's diet."""
    from kubernetes_tpu.api.types import (
        Affinity,
        Container,
        LabelSelector,
        Pod,
        PodAffinityTerm,
        PodAntiAffinity,
        TopologySpreadConstraint,
    )

    rng = random.Random(seed)
    pods = []
    for i in range(n):
        kw = {}
        if i % 2 == 0:
            app = f"sp-{i % 12}"
            kw["labels"] = {"app": app}
            kw["topology_spread_constraints"] = (
                TopologySpreadConstraint(
                    max_skew=3,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": app}),
                ),
            )
        elif i % 4 == 1:
            grp = f"g{i % 20}"
            kw["labels"] = {"group": grp}
            kw["affinity"] = Affinity(
                pod_anti_affinity=PodAntiAffinity(
                    required_during_scheduling_ignored_during_execution=(
                        PodAffinityTerm(
                            topology_key="kubernetes.io/hostname",
                            label_selector=LabelSelector(
                                match_labels={"group": grp}
                            ),
                        ),
                    )
                )
            )
        else:
            kw["labels"] = {"app": f"plain-{i % 8}"}
        pods.append(
            Pod(
                name=f"wp-{i}",
                containers=[
                    Container(
                        name="c",
                        requests={
                            "cpu": f"{rng.choice([100, 250])}m",
                            "memory": "128Mi",
                        },
                    )
                ],
                **kw,
            )
        )
    return pods


def check_wave_vs_oracle(
    n_nodes=500, n_pods=2000, make_pods=_cross_pod_pods, zones=6
) -> dict:
    """Wave-dispatch drain (speculation + factored conflict resolution,
    ops/wave.py) vs the serial oracle on a mixed spread/anti-affinity
    workload — the wave's bit-identity evidence at scale.
    ``make_pods(n)`` swaps the workload (chip_smoke.py passes the
    TopologySpreading pods: every statics variant of the wave engine is
    minutes of TPU compile, so its drain and its identity check share
    one)."""
    import copy

    from kubernetes_tpu.oracle.pipeline import schedule_one
    from kubernetes_tpu.oracle.state import OracleState

    nodes = _basic_nodes(n_nodes, zones=zones)
    pods = make_pods(n_pods)
    t0 = time.perf_counter()
    got, sched = _drain(nodes, copy.deepcopy(pods), return_sched=True)
    wave_batches = sched.metrics["wave_batches"]

    state = OracleState.build(nodes)
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        r = schedule_one(pod, state)
        want[pod.name] = r.node
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    diffs = device_faults(sched) + _diff(got, want)
    n_diffs = len(diffs)
    if wave_batches == 0:
        # the check exists to certify the WAVE path; a silent fallback to
        # the scan would make its zero-diff claim vacuous — fail loud
        n_diffs += 1
        diffs = [("__wave_batches__", 0, ">=1")] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "wave_batches": wave_batches,
        "kernel_dispatches": {
            r["kernel"]: r["dispatches"]
            for r in sched.kernels.table(cost=False)
            if r["dispatches"]
        },
        "bound_wave": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def _port_heavy_pods(n, seed=13, apps=8, prefix="pp"):
    """Port-contended mix: most pods race a couple of (port, proto) pairs
    (some wildcard-IP, some IP-scoped) alongside spread terms — the wave's
    factored [Tpt, N] port-occupancy carry is the only thing standing
    between this workload and the gang scan.  THE workload definition for
    the de-fallback coverage: tests/test_wave.py imports it, so the check
    and the test exercise one mix, not drifting copies."""
    from kubernetes_tpu.api.types import (
        Container,
        ContainerPort,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )

    rng = random.Random(seed)
    pods = []
    for i in range(n):
        kw = {"labels": {"app": f"srv-{i % apps}"}}
        containers = [
            Container(
                name="c",
                requests={
                    "cpu": f"{rng.choice([100, 250])}m",
                    "memory": "128Mi",
                },
            )
        ]
        if i % 3 != 2:
            containers.append(
                Container(
                    name="srv",
                    ports=(
                        ContainerPort(
                            container_port=8080,
                            host_port=rng.choice([8080, 9090]),
                            protocol=rng.choice(["TCP", "UDP"]),
                            host_ip=rng.choice(["", "", "10.0.0.1"]),
                        ),
                    ),
                )
            )
        if i % 2 == 0:
            app = kw["labels"]["app"]
            kw["topology_spread_constraints"] = (
                TopologySpreadConstraint(
                    max_skew=2,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": app}),
                ),
            )
        pods.append(Pod(name=f"{prefix}-{i}", containers=containers, **kw))
    return pods


def check_port_carry_vs_oracle(n_nodes=400, n_pods=1600) -> dict:
    """Port-contended wave drain (the factored [Tpt, N] port-occupancy
    carry) vs the serial oracle — the de-fallback's bit-identity evidence.
    Fails loud if the wave never engaged or the retired `ports` fallback
    rung was used."""
    import copy

    from kubernetes_tpu.oracle.pipeline import schedule_one
    from kubernetes_tpu.oracle.state import OracleState

    nodes = _basic_nodes(n_nodes, zones=5)
    pods = _port_heavy_pods(n_pods)
    t0 = time.perf_counter()
    got, sched = _drain(nodes, copy.deepcopy(pods), return_sched=True)
    wave_batches = sched.metrics["wave_batches"]
    port_fallbacks = sched.prom.wave_fallback.value(reason="ports")

    state = OracleState.build(nodes)
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        r = schedule_one(pod, state)
        want[pod.name] = r.node
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    diffs = _diff(got, want)
    n_diffs = len(diffs)
    if wave_batches == 0:
        n_diffs += 1
        diffs = [("__wave_batches__", 0, ">=1")] + diffs
    if port_fallbacks:
        n_diffs += 1
        diffs = [("__fallback_ports__", port_fallbacks, 0)] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "wave_batches": wave_batches,
        "bound_wave": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def check_compat_wave_vs_oracle(n_nodes=800, n_pods=1600, seed=47) -> dict:
    """Sampling-compat + seeded-tie drain over a CROSS-POD-constraint
    workload vs the serial oracle: the wave engine replays the adaptive
    window, nodeTree rotation, and seeded tie-break per step, so compat
    drains no longer pay the [C,N,J] gang scan.  Fails loud if the wave
    never engaged or the retired `sampling_compat` rung was used."""
    import copy

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.oracle.pipeline import feasible_nodes, prioritize
    from kubernetes_tpu.oracle.state import OracleState

    nodes = _basic_nodes(n_nodes, zones=3)
    pods = _cross_pod_pods(n_pods, seed=seed)
    t0 = time.perf_counter()
    got, sched = _drain(
        nodes,
        copy.deepcopy(pods),
        return_sched=True,
        reference_sampling_compat=True,
        tie_break_seed=seed,
    )
    wave_batches = sched.metrics["wave_batches"]
    compat_fallbacks = sched.prom.wave_fallback.value(
        reason="sampling_compat"
    )

    state = OracleState.build(nodes)
    key = jax.random.PRNGKey(seed)
    h_all = np.asarray(
        jax.vmap(
            lambda a: jax.random.bits(
                jax.random.fold_in(key, a), (n_nodes,), dtype=jnp.uint32
            )
        )(jnp.arange(n_pods))
    )
    idx_of = {name: i for i, name in enumerate(state.nodes)}
    start = 0
    attempt = 0
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        fit = feasible_nodes(pod, state, sample_pct=0, start_index=start)
        start = (start + fit.processed) % n_nodes
        totals = prioritize(pod, state, fit.feasible)
        h = h_all[attempt]
        attempt += 1
        if not totals:
            want[pod.name] = None
            continue
        node = max(totals, key=lambda m: (totals[m], int(h[idx_of[m]])))
        want[pod.name] = node
        pod.node_name = node
        state.place(pod)
    diffs = _diff(got, want)
    n_diffs = len(diffs)
    if wave_batches == 0:
        n_diffs += 1
        diffs = [("__wave_batches__", 0, ">=1")] + diffs
    if compat_fallbacks:
        n_diffs += 1
        diffs = [("__fallback_sampling_compat__", compat_fallbacks, 0)] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "wave_batches": wave_batches,
        "bound_device": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def check_resident_vs_oracle(n_nodes=1000, n_pods=5000) -> dict:
    """Resident drain loop (ops/resident.py speculation/admission fixed
    point + tail engine) vs the serial oracle AND vs the residentDrain:false
    drain (sig_scan/host-greedy machinery) — the resident path's
    bit-identity evidence at bench scale, kill switch included."""
    import copy

    from kubernetes_tpu.oracle.pipeline import schedule_one
    from kubernetes_tpu.oracle.state import OracleState

    nodes = _basic_nodes(n_nodes)
    pods = _basic_pods(n_pods, seed=31)
    t0 = time.perf_counter()
    got, sched = _drain(nodes, copy.deepcopy(pods), return_sched=True)
    resident_batches = sched.metrics["resident_batches"]
    off, s_off = _drain(
        nodes, copy.deepcopy(pods), return_sched=True, resident_drain=False
    )

    state = OracleState.build(nodes)
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        r = schedule_one(pod, state)
        want[pod.name] = r.node
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    diffs = (
        device_faults(sched)
        + device_faults(s_off)
        + _diff(got, want)
        + _diff(got, off)
    )
    n_diffs = len(diffs)
    if resident_batches == 0:
        # the check certifies the RESIDENT path; a silent fallback would
        # make its zero-diff claim vacuous — fail loud
        n_diffs += 1
        diffs = [("__resident_batches__", 0, ">=1")] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "resident_batches": resident_batches,
        "bound_resident": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def _gang_workload(n_nodes, n_gangs, seed=12):
    """Plain pods + gangs of mixed feasibility on tight nodes — partial
    gangs MUST roll back, so the check exercises the rollback algebra."""
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.workloads.gang import PodGroup

    rng = random.Random(seed)
    nodes = [
        Node(
            name=f"node-{i}",
            labels={
                "topology.kubernetes.io/zone": f"zone-{i % 4}",
                "kubernetes.io/hostname": f"node-{i}",
            },
            capacity=Resource.from_map(
                {"cpu": rng.choice(["2", "4"]), "memory": "8Gi", "pods": 110}
            ),
        )
        for i in range(n_nodes)
    ]
    pods, groups = [], {}
    for gi in range(n_gangs):
        size = rng.randrange(2, 6)
        name = f"gang-{gi}"
        groups[f"default/{name}"] = PodGroup(
            name=name, min_member=rng.randrange(2, size + 1)
        )
        for m in range(size):
            pods.append(
                Pod(
                    name=f"{name}-{m}",
                    pod_group=name,
                    containers=[
                        Container(
                            name="c",
                            requests={
                                "cpu": rng.choice(["200m", "800m", "1800m"]),
                                "memory": "256Mi",
                            },
                        )
                    ],
                )
            )
        if gi % 3 == 0:
            pods.append(
                Pod(
                    name=f"plain-{gi}",
                    containers=[
                        Container(name="c", requests={"cpu": "150m"})
                    ],
                )
            )
    return nodes, pods, groups


def check_gang_vs_oracle(n_nodes=60, n_gangs=120) -> dict:
    """Workloads-tier gang admission (ops/coscheduling.py: all-or-nothing
    checkpoint/rollback over the factored algebra) vs the serial gang
    oracle replaying the same canonical order — zero diffs required."""
    import copy

    from kubernetes_tpu.oracle.state import OracleState
    from kubernetes_tpu.oracle.workloads import WorkloadOracle

    nodes, pods, groups = _gang_workload(n_nodes, n_gangs)
    t0 = time.perf_counter()
    got, sched = _drain_workloads(nodes, pods, groups)
    wl_batches = sched.metrics["workload_batches"]

    oracle = WorkloadOracle(
        state=OracleState.build(nodes), groups=copy.deepcopy(groups)
    )
    res = oracle.schedule(copy.deepcopy(pods))
    diffs = _diff(got, res.placements)
    n_diffs = len(diffs)
    if wl_batches == 0:
        n_diffs += 1
        diffs = [("__workload_batches__", 0, ">=1")] + diffs
    if sched.metrics["gang_rolled_back"] == 0:
        # the check certifies ROLLBACK; a workload where no gang ever
        # rolls back would make the claim vacuous — fail loud
        n_diffs += 1
        diffs = [("__gang_rolled_back__", 0, ">=1")] + diffs
    return {
        "nodes": n_nodes,
        "pods": len(pods),
        "gangs": n_gangs,
        "workload_batches": wl_batches,
        "gangs_rolled_back": sched.metrics["gang_rolled_back"],
        "bound_kernel": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in res.placements.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def _dra_workload(n_nodes, n_pods, seed=9):
    from kubernetes_tpu.api import dra
    from kubernetes_tpu.api.types import Container, Pod

    rng = random.Random(seed)
    nodes = _basic_nodes(n_nodes)
    slices = []
    for i in range(n_nodes):
        if i % 2:
            continue
        slices.append(
            dra.ResourceSlice(
                name=f"sl-{i}",
                node_name=f"node-{i}",
                driver="drv",
                pool=f"pool-{i}",
                devices=tuple(
                    dra.Device(
                        name=f"dev-{i}-{j}",
                        attributes=(
                            ("vendor", "x" if j % 2 else "y"),
                            ("mem", rng.choice(["16", "32"])),
                        ),
                    )
                    for j in range(rng.randrange(1, 5))
                ),
            )
        )
    classes = {
        "gpu": dra.DeviceClass(
            name="gpu",
            selectors=(dra.DeviceSelector("vendor", "In", ("x",)),),
        ),
        "any": dra.DeviceClass(name="any"),
    }
    claims, pods = {}, []
    for i in range(n_pods):
        mode_all = rng.random() < 0.2
        c = dra.ResourceClaim(
            name=f"claim-{i}",
            requests=(
                dra.DeviceRequest(
                    name="r",
                    device_class_name=rng.choice(["gpu", "any"]),
                    count=rng.randrange(1, 3),
                    allocation_mode=(
                        dra.ALLOCATION_MODE_ALL
                        if mode_all
                        else dra.ALLOCATION_MODE_EXACT
                    ),
                    selectors=(
                        (dra.DeviceSelector("mem", "In", ("32",)),)
                        if rng.random() < 0.3
                        else ()
                    ),
                ),
            ),
        )
        claims[c.key] = c
        pods.append(
            Pod(
                name=f"dp-{i}",
                containers=[Container(name="c", requests={"cpu": "100m"})],
                resource_claims=(c.name,),
            )
        )
    return nodes, slices, classes, claims, pods


def check_dra_vs_oracle(n_nodes=200, n_pods=600) -> dict:
    """Batched DRA allocation (ops/dra.py device-matching kernel inside
    the workloads admission scan) vs the serial structured-allocator
    oracle — placements AND claim→node pinnings, zero diffs required."""
    import copy

    from kubernetes_tpu.oracle.state import OracleState
    from kubernetes_tpu.oracle.workloads import WorkloadOracle

    nodes, slices, classes, claims, pods = _dra_workload(n_nodes, n_pods)
    t0 = time.perf_counter()
    got, sched = _drain_workloads(
        nodes, pods, {}, slices=slices, classes=classes, claims=claims
    )
    wl_batches = sched.metrics["workload_batches"]

    oracle = WorkloadOracle(
        state=OracleState.build(nodes),
        slices=copy.deepcopy(slices),
        device_classes=copy.deepcopy(classes),
        claims=copy.deepcopy(claims),
    )
    res = oracle.schedule(copy.deepcopy(pods))
    diffs = _diff(got, res.placements)
    # claim pinning identity through the live claim cache
    for key, want_node in res.claim_nodes.items():
        c = sched.claim_cache.get(key)
        have = (
            c.allocation.node_name
            if c is not None and c.allocation is not None
            else None
        )
        if have != want_node:
            diffs.append((f"claim:{key}", have, want_node))
    n_diffs = len(diffs)
    if wl_batches == 0:
        n_diffs += 1
        diffs = [("__workload_batches__", 0, ">=1")] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "workload_batches": wl_batches,
        "bound_kernel": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in res.placements.values() if v),
        "claims_allocated": len(res.claim_nodes),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def _drain_workloads(
    nodes, pods, groups, slices=(), classes=None, claims=None, **cfg_kw
):
    """A FakeCluster drain wired for the workloads tier (PodGroups +
    DRA objects), returning ({pod: node}, scheduler)."""
    import copy

    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing import FakeCluster

    api = FakeCluster()
    cfg = SchedulerConfiguration(batch_size=4096)
    cfg.feature_gates["DynamicResourceAllocation"] = True
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    s = Scheduler(configuration=cfg)
    api.connect(s)
    for n in nodes:
        api.create_node(n)
    for pg in groups.values():
        api.pod_groups.create(pg)
    for cls in (classes or {}).values():
        api.device_classes.create(cls)
    for sl in slices:
        api.resource_slices.create(sl)
    for c in (claims or {}).values():
        api.resource_claims.create(c)
    for p in pods:
        api.create_pod(copy.deepcopy(p))
    got = {}
    for o in s.schedule_pending():
        got[o.pod.name] = o.node
    return got, s


def check_plan_vs_oracle(
    n_nodes=60, n_fill=1500, n_backlog=32, k=24, seed=991
) -> dict:
    """Counterfactual planner tier vs the serial forked-snapshot oracle
    (PLANNER.md): K mixed forks — clone-adds, cordons, evictions,
    capacity scales, removals — over a spread-constrained backlog with a
    gang, per-fork placements / gang verdicts / admission counts /
    density bit-identical.  Fails loud when the K-vmap kernel path is not
    engaged (kernel must cost exactly ONE dispatch for all K forks)."""
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )
    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.planner import Fork, simulate_forks
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing import FakeCluster
    from kubernetes_tpu.workloads.gang import PodGroup

    rng = random.Random(seed)
    t0 = time.perf_counter()
    api = FakeCluster()
    sched = Scheduler(configuration=SchedulerConfiguration(batch_size=4096))
    api.connect(sched)
    for n in _basic_nodes(n_nodes, zones=3):
        api.create_node(n)
    for p in _basic_pods(n_fill, seed=seed):
        p.priority = 2
        api.create_pod(p)
    sched.schedule_pending()
    backlog = []
    for i in range(n_backlog):
        tsc = ()
        if i % 3 == 0:
            tsc = (
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(
                        match_labels={"app": "plan"}
                    ),
                ),
            )
        backlog.append(
            Pod(
                name=f"plan-{i}",
                labels={"app": "plan"},
                topology_spread_constraints=tsc,
                containers=[
                    Container(
                        name="c",
                        requests={
                            "cpu": f"{rng.choice([500, 900, 1500])}m",
                            "memory": "256Mi",
                        },
                    )
                ],
            )
        )
    with sched._mu:
        sched.gangs.upsert(PodGroup(name="pg", min_member=3))
    backlog += [
        Pod(
            name=f"pg-{m}",
            pod_group="pg",
            containers=[
                Container(name="c", requests={"cpu": "700m", "memory": "128Mi"})
            ],
        )
        for m in range(3)
    ]
    placed = sched.cache.placed_pods()
    names = [f"node-{i}" for i in range(n_nodes)]
    forks = [Fork(label="baseline")]
    while len(forks) < k:
        i = len(forks)
        kind = i % 5
        if kind == 0:
            t = rng.choice(names)
            forks.append(
                Fork(
                    label=f"add{i}",
                    add=tuple(
                        (t, f"{t}~cf{i}-{j}") for j in range(1 + i % 3)
                    ),
                )
            )
        elif kind == 1:
            forks.append(
                Fork(label=f"cordon{i}", cordon=(rng.choice(names),))
            )
        elif kind == 2:
            forks.append(
                Fork(
                    label=f"evict{i}",
                    evict=tuple(
                        p.uid
                        for p in rng.sample(placed, min(6, len(placed)))
                    ),
                )
            )
        elif kind == 3:
            forks.append(
                Fork(
                    label=f"scale{i}",
                    scale=((rng.choice(names), rng.choice([1, 3]), 2),),
                )
            )
        else:
            forks.append(
                Fork(label=f"remove{i}", remove=(rng.choice(names),))
            )
    kern = simulate_forks(sched, forks, backlog, planner="paritycheck")
    serial = simulate_forks(
        sched, forks, backlog, planner="paritycheck", use_kernel=False
    )
    diffs: List = []
    if kern.engine != "kernel" or kern.dispatches != 1:
        diffs.append(
            ("__kernel_engaged__", (kern.engine, kern.dispatches), ("kernel", 1))
        )
    for fk, fs in zip(kern.forks, serial.forks):
        for key in (
            "placements",
            "admitted",
            "unschedulable",
            "density_ppm",
            "gang_admitted",
        ):
            if fk[key] != fs[key]:
                diffs.append((f"{fk['label']}:{key}", fk[key], fs[key]))
    return {
        "nodes": n_nodes,
        "fill": n_fill,
        "backlog": len(backlog),
        "forks": len(forks),
        "kernel_dispatches": kern.dispatches,
        "admitted_baseline": kern.forks[0]["admitted"],
        "diffs": len(diffs),
        "first_diffs": [
            (lbl, str(a)[:120], str(b)[:120]) for lbl, a, b in diffs[:5]
        ],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def check_multichip_vs_singlechip(
    n_nodes=120, n_pods=600, n_cross=240, n_gangs=24
) -> dict:
    """Mesh-partitioned admission engine (ISSUE 14 / MULTICHIP.md) vs the
    single-chip kernels: the SAME mixed workload — resident/fast basics,
    wave-shaped cross-pod constraints, gang coscheduling — drains with
    meshDispatch OFF, then ON over a pods-major mesh (all devices on the
    pods axis) and a nodes-major mesh (all devices on the nodes axis).
    Decisions must be bit-identical in all three modes, and on a
    multi-device backend the mesh runs must PROVE engagement (scheduler
    mesh resolved and still full-width at the end, ledger multi-device
    dispatches whose partitioned arguments span EVERY device — pod-major
    arrays in the pods-major layout, node-major arrays in the nodes-major
    one — and no dispatch abandoned to a breaker fallback), or the check
    fails loud — a silently-replicated, degraded or host-answered run
    would make the parity claim vacuous.
    On a single-device backend the check degrades to a 1x1 mesh identity
    (still zero diffs required) and reports devices=1."""
    import copy

    import jax

    devices = len(jax.devices())
    t0 = time.perf_counter()
    nodes = _basic_nodes(n_nodes)
    pods = _basic_pods(n_pods) + _cross_pod_pods(n_cross)
    gnodes, gpods, groups = _gang_workload(max(n_nodes // 2, 8), n_gangs)

    def drains(**cfg_kw):
        got, s = _drain(
            nodes, copy.deepcopy(pods), return_sched=True, **cfg_kw
        )
        got2, s2 = _drain_workloads(
            gnodes, copy.deepcopy(gpods), copy.deepcopy(groups), **cfg_kw
        )
        return got, got2, s, s2

    base, gbase, s_base, s2_base = drains(mesh_dispatch=False)
    diffs: List = device_faults(s_base) + device_faults(s2_base)
    mesh_runs = {}
    for label, pods_axis in (("pods_major", None), ("nodes_major", 1)):
        got, ggot, s, s2 = drains(
            mesh_dispatch=True, mesh_pods_axis=pods_axis
        )
        diffs += [
            (f"{label}:{k}", a, b) for k, a, b in _diff(base, got)
        ] + [(f"{label}:gang:{k}", a, b) for k, a, b in _diff(gbase, ggot)]
        diffs += [
            (f"{label}:{k}", a, b)
            for k, a, b in device_faults(s) + device_faults(s2)
        ]
        if s.mesh is None or s2.mesh is None:
            # resolved at init, halved/dropped by _degrade_mesh on a fault
            diffs.append((f"__{label}_mesh_resolved__", None, "mesh"))
            mesh_runs[label] = {"mesh": None}
            continue
        mesh_shape = f"{s.mesh.shape['pods']}x{s.mesh.shape['nodes']}"
        multi = (
            s.kernels.stats()["multi_device_dispatches"]
            + s2.kernels.stats()["multi_device_dispatches"]
        )
        # the widest DISTINCT-device set any partitioned (non-replicated)
        # dispatch argument spanned — read off the live arrays' shardings
        # by the ledger at dispatch time
        span = max(
            max(r["devices"])
            for led in (s.kernels, s2.kernels)
            for r in led.table(cost=False)
        )
        mesh_runs[label] = {
            "mesh": mesh_shape,
            "mesh_devices": sorted(int(d.id) for d in s.mesh.devices.flat),
            "multi_device_dispatches": multi,
            "dispatch_device_span": span,
        }
        if int(s.mesh.devices.size) != devices:
            diffs.append(
                (f"__{label}_mesh_width__", int(s.mesh.devices.size), devices)
            )
        if devices > 1 and multi == 0:
            # a mesh run whose dispatches never actually partitioned
            # proves nothing — fail loud rather than certify replication
            diffs.append((f"__{label}_engaged__", 0, ">=1"))
        if devices > 1 and span != devices:
            # everything on the first chip (or a subset) is not a mesh run
            diffs.append((f"__{label}_device_span__", span, devices))
    return {
        "devices": devices,
        "nodes": n_nodes,
        "pods": len(pods),
        "gang_pods": len(gpods),
        "mesh_runs": mesh_runs,
        "diffs": len(diffs),
        "first_diffs": [
            (lbl, str(a)[:80], str(b)[:80]) for lbl, a, b in diffs[:5]
        ],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def check_breaker_open_vs_oracle(n_nodes=300, n_pods=900) -> dict:
    """Breaker-degraded drain vs the serial oracle (ISSUE 15): with the
    wave AND gang-scan breakers latched open, every cross-pod batch
    drains on the one-pod host-oracle fallback — placements must be
    bit-identical to the oracle (that is the entire point of routing an
    open breaker to a parity-certified engine), and the fallback must
    actually ENGAGE (wave_fallback{reason=breaker} > 0, zero device
    batches) or the claim is vacuous."""
    import copy

    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.oracle.pipeline import schedule_one
    from kubernetes_tpu.oracle.state import OracleState
    from kubernetes_tpu.scheduler import Scheduler

    nodes = _basic_nodes(n_nodes, zones=6)
    pods = _cross_pod_pods(n_pods)
    t0 = time.perf_counter()
    s = Scheduler(configuration=SchedulerConfiguration())
    s.kernels.force_breaker_open("wave.wave_run")
    s.kernels.force_breaker_open("gang.gang_run")
    s.kernels.force_breaker_open("chain.chain_dispatch")
    got: Dict[str, Optional[str]] = {}
    s.binding_sink = lambda pod, node: got.__setitem__(pod.name, node)
    s.mirror.e_cap_hint = len(pods) + s.config.batch_size + 128
    for n in nodes:
        s.on_node_add(n)
    for p in copy.deepcopy(pods):
        s.on_pod_add(p)
    outs = s.schedule_pending()
    for o in outs:
        got.setdefault(o.pod.name, o.node)
    breaker_fallbacks = int(
        s.prom.wave_fallback.value(reason="breaker")
    )
    device_batches = (
        s.metrics["wave_batches"] + s.metrics["scan_batches"]
    )

    state = OracleState.build(nodes)
    want: Dict[str, Optional[str]] = {}
    for pod in copy.deepcopy(pods):
        r = schedule_one(pod, state)
        want[pod.name] = r.node
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    diffs = _diff(got, want)
    n_diffs = len(diffs)
    if breaker_fallbacks == 0 or device_batches > 0:
        n_diffs += 1
        diffs = [
            ("__breaker_engaged__", breaker_fallbacks, device_batches)
        ] + diffs
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "breaker_fallbacks": breaker_fallbacks,
        "device_batches": device_batches,
        "bound_degraded": sum(1 for v in got.values() if v),
        "bound_oracle": sum(1 for v in want.values() if v),
        "diffs": n_diffs,
        "first_diffs": diffs[:5],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def run_checks(ns_nodes=10000, ns_pods=50000) -> dict:
    checks = {
        "cross_batch_devfast_vs_hostgreedy": check_cross_batch(
            ns_nodes, ns_pods
        ),
        "sampling_compat_vs_serial_oracle": check_compat_vs_oracle(),
        "wave_dispatch_vs_serial_oracle": check_wave_vs_oracle(),
        "port_carry_vs_serial_oracle": check_port_carry_vs_oracle(),
        "compat_wave_vs_serial_oracle": check_compat_wave_vs_oracle(),
        "resident_drain_vs_serial_oracle": check_resident_vs_oracle(),
        "gang_admission_vs_serial_oracle": check_gang_vs_oracle(),
        "dra_allocation_vs_serial_oracle": check_dra_vs_oracle(),
        "plan_vs_serial_oracle": check_plan_vs_oracle(),
        "multichip_vs_singlechip": check_multichip_vs_singlechip(),
        "breaker_open_vs_serial_oracle": check_breaker_open_vs_oracle(),
    }
    return {
        "checks": checks,
        "total_diffs": sum(c["diffs"] for c in checks.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="paritycheck")
    ap.add_argument("--out", default="PARITY.json")
    ap.add_argument("--ns-nodes", type=int, default=10000)
    ap.add_argument("--ns-pods", type=int, default=50000)
    args = ap.parse_args(argv)
    result = run_checks(args.ns_nodes, args.ns_pods)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"total_diffs": result["total_diffs"], "out": args.out}))
    return 0 if result["total_diffs"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
