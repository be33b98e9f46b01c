"""Traffic kind ``backlog``: a closed drain of a delivered queue.

The mix's pods are created in the API server's store and reach the
scheduler's queue over LIST/WATCH while the scheduling loop is held.  The
window opens when the loop is released and closes at the last acknowledged
bind of the backlog, or when ``--seconds`` are spent.  Nothing arrives
inside it.
"""

from __future__ import annotations

import time
from typing import Dict, List

from benchmarks import workload
from benchmarks.harness import ServedCluster, say


def _count(cfg: dict, part: dict) -> int:
    """``count`` names the configuration's group of pods whose count is meant."""
    return cfg[part["count"]]["count"] if part else 0


def plan(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """Specs of the warm-up backlog and of the measured one."""
    tpl = cfg["measure_pods"]["template"]
    return {
        "warm": workload.pod_specs(cfg, tpl, _count(cfg, mix.get("warmup", {})), "warm"),
        "measure": workload.pod_specs(cfg, tpl, _count(cfg, mix), "load"),
    }


def pods_alive(plan_: dict) -> int:
    """The most pods of this mix that exist at once (sizes the placed-pod
    axes): the warm-up is gone before the measured backlog lands."""
    return max(len(plan_["warm"]), len(plan_["measure"]))


def _deliver(cluster: ServedCluster, specs: List[dict], what: str):
    cd = cluster.expect([workload.uid_of(s) for s in specs])
    t0 = time.perf_counter()
    cluster.create_in_store(specs)
    if not cluster.wait_queued(len(specs)):
        raise RuntimeError(f"{what} never reached the queue")
    return cd, time.perf_counter() - t0


def warm_up(cluster: ServedCluster, plan_: dict) -> None:
    """Deliver a backlog of the measured size and template, drain it by
    calling the loop body (chip_smoke.py's two-drain pattern) and delete it
    again: every shape the window meets is compiled, or loaded from the
    cache, here, and the window opens on the configuration's own state."""
    if not plan_["warm"]:
        return
    cd, _ = _deliver(cluster, plan_["warm"], "warm-up backlog")
    if not cluster.drain_by_loop_body(cd):
        raise RuntimeError(f"warm-up left {cd.left} pods unbound")
    t0 = time.perf_counter()
    cluster.delete_from_store(plan_["warm"])
    say(f"warm-up backlog of {len(plan_['warm'])} deleted in {time.perf_counter() - t0:.2f}s")


def offer(cluster: ServedCluster, plan_: dict) -> dict:
    """Deliver the measured backlog with the loop held."""
    cd, took = _deliver(cluster, plan_["measure"], "measured backlog")
    say(f"backlog of {len(plan_['measure'])} delivered in {took:.2f}s, "
        f"process quiet after {cluster.quiesce():.2f}s more")
    return {"uids": [workload.uid_of(s) for s in plan_["measure"]], "countdown": cd}


def window(cluster: ServedCluster, offered: dict, seconds: float) -> dict:
    """Release the loop, wait for the backlog's last acknowledgement or
    for --seconds, hold the loop.  The loop is left held."""
    t_open = time.perf_counter()
    cluster.release_loop()
    offered["countdown"].done.wait(timeout=seconds)
    t_end = time.perf_counter()
    cluster.hold_loop()
    return {"t_open": t_open, "t_deadline": t_open + seconds, "t_wait_end": t_end}


def reduce(offered: dict, win: dict, acked: Dict[str, tuple], good: set) -> dict:
    """End-to-end numbers from the client's side.  ``good`` is the set of
    uids whose acknowledged bind read back equal."""
    times = sorted(
        acked[u][1] for u in offered["uids"]
        if u in good and acked[u][1] <= win["t_deadline"]
    )
    attempted = len(offered["uids"])
    # all bound: the window closes at the last acknowledgement
    t_close = times[-1] if len(times) == attempted else min(win["t_wait_end"], win["t_deadline"])
    if times:
        t_close = max(t_close, times[-1])
    window_s = max(t_close - win["t_open"], 1e-9)
    return {
        "attempted": attempted,
        "failed": attempted - len(times),
        "window_s": window_s,
        "window": (win["t_open"], t_close),
        "pods_in_window": len(times),
        "metrics": {"pods_per_s": len(times) / window_s},
    }
