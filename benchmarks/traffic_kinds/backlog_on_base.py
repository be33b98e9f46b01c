"""Traffic kind ``backlog_on_base``: a closed drain of a delivered queue onto
a cluster whose placed pods are of several templates.

As ``backlog``, with more pods in place than the harness plants: the harness
creates the configuration's ``init_pods`` (one group, one template); this
kind's warm-up FIRST plants the groups the mix lists under ``base`` — bound,
before anything is scheduled, on nodes from the SAME seeded order the harness
drew for the init pods, continued where those ended — and hands them to the
cluster as init pods, so ``correct`` holds them with the rest (in the store,
feasible, every required term of theirs recounted, bound before the window
for identity).  Then ``backlog``'s two-drain warm-up, its delivery, its
window and its reduction: ``offer``, ``window`` and ``reduce`` are
``backlog``'s own.
"""

from __future__ import annotations

import time

from benchmarks import cells, workload
from benchmarks.harness import ServedCluster, say

_backlog = cells.traffic_kind("backlog")
offer = _backlog.offer
window = _backlog.window
reduce = _backlog.reduce


def plan(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """``backlog``'s plan, and under ``base`` the (spec, node) pairs to
    plant: the mix's ``base`` groups in order, named after their group
    (``base_affinity`` -> ``base-affinity-<i>``), group g's pod j on node
    (init + g·count + j) mod nodes of the init pods' seeded order."""
    plan_ = _backlog.plan(cfg, mix, seed, seconds)
    specs = [s for g in mix["base"] for s in workload.group_specs(cfg, g, g.replace("_", "-"))]
    n_init = cfg["init_pods"]["count"]
    nodes = workload.init_placement(cfg, n_init + len(specs), workload.node_specs(cfg), seed)[n_init:]
    plan_["base"] = list(zip(specs, nodes))
    return plan_


def pods_alive(plan_: dict) -> int:
    """The most pods of this mix that exist at once beside the init pods:
    the planted base, and the warm-up or the measured backlog on top."""
    return len(plan_["base"]) + _backlog.pods_alive(plan_)


def _plant(cluster: ServedCluster, base: list, timeout_s: float = 120.0) -> None:
    """Create the base pods bound, as ``ServedCluster.start`` creates the
    init pods, wait until the scheduler's cache holds them, and list them
    with the init pods."""
    t0 = time.perf_counter()
    for spec, node in base:
        cluster.api.create_pod(workload.build_pod(cluster.T, spec, node_name=node))
        cluster.api.bindings[workload.uid_of(spec)] = node
    want = len(cluster.init_specs) + len(base)
    deadline = time.monotonic() + timeout_s
    while len(cluster.sched.cache.pod_states) < want:
        if time.monotonic() > deadline:
            raise RuntimeError("the base pods never reached the scheduler")
        time.sleep(0.01)
    cluster.init_specs += [spec for spec, _node in base]
    cluster.init_nodes += [node for _spec, node in base]
    say(f"base of {len(base)} pods planted in {time.perf_counter() - t0:.2f}s: "
        f"{cluster.sched.cache.n_term_pods} placed pods carry a term")


def warm_up(cluster: ServedCluster, plan_: dict) -> None:
    """Plant the base, then ``backlog``'s warm-up: the measured backlog
    delivered, drained by the loop body against the whole base and deleted,
    so the window opens on the configuration's own state."""
    _plant(cluster, plan_["base"])
    _backlog.warm_up(cluster, plan_)
