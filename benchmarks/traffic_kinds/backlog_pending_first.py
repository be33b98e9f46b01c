"""Traffic kind ``backlog_pending_first``: a closed drain of a delivered
queue whose head is a group of pods that fit nowhere.

As ``backlog``, with one more group: the configuration's ``pending`` group
is created in the API server's store FIRST and the measured group after it,
all with the scheduling loop held, so the pending pods pop first (equal
priority, older timestamp).  The window opens when the loop is released and
closes at the last acknowledged bind of the MEASURED group, or when
``--seconds`` are spent; the pending pods are attempted inside it, fail, and
stay parked.  ``attempted`` and ``failed`` count the measured group alone;
the pending pods ride in ``plan["measure"]``, so ``correct`` holds them
(in the store, unbound, never acknowledged, decided none as the reference
decides).  ``window`` and ``reduce`` are ``backlog``'s own.
"""

from __future__ import annotations

import time
from typing import List

from benchmarks import cells, workload
from benchmarks.harness import ServedCluster, say

_backlog = cells.traffic_kind("backlog")
window = _backlog.window
reduce = _backlog.reduce


def _groups(cfg: dict, part: dict, pending_role: str, role: str):
    """(the pending specs, the measured specs) of the mix or of its warm-up:
    ``pending`` and ``count`` name the configuration's groups."""
    if not part:
        return [], []
    return (workload.group_specs(cfg, part["pending"], pending_role),
            workload.group_specs(cfg, "measure_pods", role, cfg[part["count"]]["count"]))


def plan(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """Specs of the warm-up backlog and of the measured one, each in the
    order it is created: pending pods first.  ``n_*_pending`` says how many
    of each list's head are pending pods."""
    warm_pending, warm = _groups(cfg, mix.get("warmup", {}), "warm-pending", "warm")
    pending, load = _groups(cfg, mix, "pending", "load")
    return {
        "warm": warm_pending + warm, "n_warm_pending": len(warm_pending),
        "measure": pending + load, "n_pending": len(pending),
    }


def pods_alive(plan_: dict) -> int:
    """The most pods of this mix that exist at once, parked ones included:
    the warm-up is gone before the measured backlog lands."""
    return max(len(plan_["warm"]), len(plan_["measure"]))


def _deliver(cluster: ServedCluster, specs: List[dict], n_pending: int, what: str):
    """Create ``specs`` in order and wait until all are queued; the
    countdown waits for the binds of those after the first ``n_pending``."""
    cd = cluster.expect([workload.uid_of(s) for s in specs[n_pending:]])
    t0 = time.perf_counter()
    cluster.create_in_store(specs)
    if not cluster.wait_queued(len(specs)):
        raise RuntimeError(f"{what} never reached the queue")
    return cd, time.perf_counter() - t0


def _wait_queue_empty(cluster: ServedCluster, timeout_s: float = 120.0) -> None:
    """``ServedCluster.delete_from_store`` waits on the scheduler's cache,
    which never held an unbound pod: a parked pod is gone when the queue
    (its unschedulable map counted in) has let it go."""
    deadline = time.monotonic() + timeout_s
    while len(cluster.sched.queue):
        if time.monotonic() > deadline:
            raise RuntimeError(f"{len(cluster.sched.queue)} warm-up pods never left the queue")
        time.sleep(0.02)


def warm_up(cluster: ServedCluster, plan_: dict) -> None:
    """Deliver a backlog of the measured shape (pending pods first), drain
    it by calling the loop body until its measured part is bound, then
    delete ALL of it, bound and parked pods alike: every shape the window
    meets, the failure path's too, is compiled or loaded here, and the
    window opens on empty nodes, an empty queue and nothing parked."""
    if not plan_["warm"]:
        return
    n = plan_["n_warm_pending"]
    cd, _ = _deliver(cluster, plan_["warm"], n, "warm-up backlog")
    if not cluster.drain_by_loop_body(cd):
        raise RuntimeError(f"warm-up left {cd.left} pods unbound")
    parked = cluster.sched.queue.stats()
    t0 = time.perf_counter()
    for spec in plan_["warm"][:n]:
        cluster.api.delete_pod(workload.uid_of(spec))
    cluster.delete_from_store(plan_["warm"][n:])  # the bound ones; forgets every decision and pop logged
    _wait_queue_empty(cluster)
    say(f"warm-up backlog of {n} + {len(plan_['warm']) - n} deleted in {time.perf_counter() - t0:.2f}s "
        f"(queue after its drain {parked})")


def offer(cluster: ServedCluster, plan_: dict) -> dict:
    """Deliver the pending pods and the measured backlog behind them with
    the loop held.  ``uids`` and the countdown are the measured pods only."""
    n = plan_["n_pending"]
    cd, took = _deliver(cluster, plan_["measure"], n, "measured backlog")
    say(f"backlog of {n} pending + {len(plan_['measure']) - n} delivered in {took:.2f}s, "
        f"process quiet after {cluster.quiesce():.2f}s more")
    return {"uids": [workload.uid_of(s) for s in plan_["measure"][n:]], "countdown": cd}
