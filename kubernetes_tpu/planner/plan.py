"""The counterfactual fleet planners — what the reference outsources to
cluster-autoscaler and descheduler, rebuilt on the batched [K, P, N]
what-if kernel (ops/counterfactual.py; PLANNER.md).

``simulate_forks`` is the shared engine: pack K forked snapshots off the
mirror (planner/forks.py), ride ONE fused dispatch + ONE accounted d2h,
and hand back per-fork outcomes.  The three planners on top differ only
in how they generate forks and read recommendations:

  * ``plan_autoscale``    — which node shape admits the unschedulable
                            backlog cheapest (fork axis = candidate shapes
                            × counts, plus per-empty-node removal forks
                            for scale-down);
  * ``plan_deschedule``   — which node drains raise bin-packing density
                            (fork axis = candidate eviction sets: cordon a
                            node, evict its pods, re-place them);
  * ``plan_preempt_cost`` — expected preemption cascade per pending
                            priority class (fork pairs: class backlog with
                            and without every lower-priority victim
                            evicted).

Everything is READ-ONLY: the planners never touch the cache, queue, or
the hot loop's chained device state (fresh uploads, like /debug/explain).
With ``plannerKernel: false`` (or when the factored algebra is
unavailable) the same fork specs replay through the serial forked-
snapshot oracle (oracle/planner.py) — the bit-identity reference the
paritycheck ``plan_vs_serial_oracle`` gate runs against the kernel.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu import routing
from kubernetes_tpu.planner.forks import Fork, collect_clones, pack_forks

# Steering bonus for target-node what-ifs: large enough to dominate every
# weighted normalized score sum, small enough that score + bonus cannot
# overflow i64.
_TARGET_BONUS = 1 << 40


# Lock-discipline registry: the planners' prep (mirror sync, fork packing,
# batch packing) holds the owning Scheduler's _mu like explain does; the
# device dispatch + d2h run OUTSIDE it against immutable arrays.
_KTPU_GUARDED = {
    "PlanScratch": {
        "external_lock": "Scheduler._mu",
    },
}


class PlanScratch:
    """Marker class for the lock registry — planner state is all local."""


@dataclass
class SimResult:
    """One simulate_forks run: per-fork outcomes + coverage bookkeeping."""

    engine: str  # "kernel" | "serial"
    k: int
    dispatches: int  # device dispatches consumed (kernel: 1)
    batch: List[str] = field(default_factory=list)  # pod names, canonical order
    skipped: Dict[str, str] = field(default_factory=dict)  # pod → reason
    forks: List[dict] = field(default_factory=list)
    wall_s: float = 0.0

    def to_json(self) -> dict:
        return {
            "engine": self.engine,
            "k": self.k,
            "dispatches": self.dispatches,
            "batch": self.batch,
            "skipped": self.skipped,
            "forks": self.forks,
            "wall_s": round(self.wall_s, 4),
        }


def _pod_ineligible(sched, fwk, pod) -> Optional[str]:
    """Why a pod cannot ride the planner kernel (None = eligible).  The
    same spec-level disqualifiers as the workloads dispatch, plus DRA
    claims (the planner's fork planes don't carry the allocation ledger
    yet — see PLANNER.md remainders)."""
    if pod.nominated_node_name:
        return "nominated"
    if pod.host_ports():
        return "host_ports"
    if pod.resource_claims:
        return "resource_claims"
    for e in sched.extenders:
        if e.is_interested(pod):
            return "extender"
    for pl in routing.normalizing_score_plugins(fwk) + routing.weighted_host_scores(fwk):
        if pl.score_relevant(pod):
            return "host_score"
    if pod.pvc_names() and not sched._vol_kernel_ok(pod):
        return "volume_shape"
    return None


def backlog_pods(sched, fwk, max_pods: int = 256) -> Tuple[list, Dict[str, str]]:
    """The pending backlog the planners simulate: unschedulable pods first
    (they ARE the autoscaler's trigger), then backoff, then active, capped.
    Returns (eligible pods, skipped-pod reasons)."""
    with sched._mu:
        pools = sched.queue.pending_pods()
    seen = set()
    ordered = []
    # gated pods are deliberately excluded: a scheduling gate means "do
    # not schedule", so planning capacity for them would mislead
    for pool in ("unschedulable", "backoff", "active"):
        for p in pools.get(pool, ()):
            if p.uid not in seen:
                seen.add(p.uid)
                ordered.append(p)
    eligible, skipped = [], {}
    for p in ordered:
        why = _pod_ineligible(sched, fwk, p)
        if why is None:
            if len(eligible) < max_pods:
                eligible.append(p)
        else:
            skipped[p.name] = why
    return eligible, skipped


def simulate_forks(
    sched,
    forks: Sequence[Fork],
    pods: Sequence,
    target_node: Optional[str] = None,
    planner: str = "custom",
    use_kernel: Optional[bool] = None,
) -> SimResult:
    """K forked snapshots × one pod batch → per-fork outcomes.

    The kernel path packs fork planes off the mirror and runs ONE
    ``counterfactual_run`` dispatch + ONE ``Scheduler._d2h``; the serial
    path (kill switch / factored-algebra unavailable) replays the same
    fork specs through oracle/planner.py.  ``target_node`` (single-pod
    batches ONLY — enforced) steers the pod toward that node with a
    dominating score bonus, so ``chosen == target`` ⟺ the pod is
    feasible there (the K=1 what-if contract /debug/explain rides).
    """
    import jax.numpy as jnp

    from kubernetes_tpu.ops import counterfactual as cf_ops
    from kubernetes_tpu.ops import gang
    from kubernetes_tpu.ops import wave as wave_ops
    from kubernetes_tpu.ops import wire
    from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster
    from kubernetes_tpu.snapshot.interner import PAD
    from kubernetes_tpu.snapshot.schema import bucket_cap, pack_pod_batch
    from kubernetes_tpu.workloads import gang as wlg

    t0 = time.perf_counter()
    fwk = next(iter(sched.profiles.values()))
    kernel_ok = (
        sched.config.planner_kernel
        if use_kernel is None
        else use_kernel
    ) and not sched._sampling_active(fwk)
    # device-fault tier: an open counterfactual breaker routes fork specs
    # through the serial forked-snapshot oracle (the plannerKernel
    # kill-switch engine — decision-identical per fork)
    if kernel_ok and sched._breaker_blocked(
        "counterfactual.counterfactual_run"
    ):
        kernel_ok = False

    forks = list(forks)
    pods = list(pods)
    if target_node is not None and len(pods) != 1:
        # the target-bonus trick judges pods SEQUENTIALLY on the kernel
        # path (earlier steered pods commit usage at the target) but
        # against the initial state on the serial path — only the
        # single-pod what-if contract is well-defined across engines
        raise ValueError(
            "target_node requires a single-pod batch (the K=1 what-if "
            f"contract); got {len(pods)} pods"
        )
    skipped: Dict[str, str] = {}
    live_pods = []
    for p in pods:
        why = _pod_ineligible(sched, fwk, p)
        if why is None:
            live_pods.append(p)
        else:
            skipped[p.name] = why
    pods = live_pods

    with sched._mu:
        vocab = sched.mirror.vocab
        for p in pods:
            for k, v in p.labels.items():
                vocab.intern_label(k, v)
        sched._sync_mirror_external()
        # clone labels intern BEFORE the repack so a grown value bucket
        # forces the full pack the mirror already knows how to do
        node_objs = {cn.node.name: cn.node for cn in sched.cache.real_nodes()}
        clones = collect_clones(forks, node_objs)
        from kubernetes_tpu.snapshot.selectors import METADATA_NAME_KEY

        for node in clones.values():
            for k, v in node.labels.items():
                vocab.intern_label(k, v)
            vocab.intern_label(METADATA_NAME_KEY, node.name)
        sched._repack_mirror()
        if sched.mirror.nodes is None or not any(sched.mirror.nodes.valid):
            return SimResult(engine="none", k=0, dispatches=0,
                             skipped={"__cluster__": "no nodes in snapshot"})

        kernel_ok = kernel_ok and sched.mirror.hostnames_unique

        # canonical order: gang members contiguous (the oracle replays it)
        order, gang_positions = wlg.plan_batch(
            pods, group_of=sched._workloads_group_of
        )
        ordered = [pods[i] for i in order]
        needs = {}
        for key in gang_positions:
            pg = sched.gangs.get(key)
            needs[key] = max(
                0, (pg.min_member if pg else 0) - sched.gangs.bound_count(key)
            )

        serial_snapshot = None
        if not kernel_ok:
            serial_snapshot = _serial_snapshot(sched, gang_positions)
        if serial_snapshot is None:
            p_cap = bucket_cap(max(len(ordered), 1), 1)
            pf = pack_forks(
                sched.mirror,
                sched.cache,
                forks,
                [p.uid for p in ordered],
                p_cap,
                clones=clones,
            )
            pb = pack_pod_batch(
                ordered,
                vocab,
                k_cap=pf.nt.k_cap,
                p_cap=p_cap,
                namespace_labels=sched.namespace_labels,
            )
            from kubernetes_tpu.oracle.scores import HOSTNAME_LABEL

            hk_id = vocab.label_keys.lookup(HOSTNAME_LABEL)
            tables = dict(
                gang.batch_tables(
                    pb.tsc_topo_key, pb.aff_topo_key, pf.nt.label_vals, hk_id
                )
            )
            wt = wave_ops.wave_tables(
                pb, pf.nt.label_vals, hk_id, hostnames_unique=True
            )
            if wt is None:
                serial_snapshot = _serial_snapshot(sched, gang_positions)
        if serial_snapshot is None:
            gid, gfirst, glast, gneed, g_cap, slot_keys = wlg.gang_arrays(
                p_cap, gang_positions, needs
            )
            volt = sched._vol_tables(ordered, p_cap, vocab)
            has_interpod = bool(
                (pb.aff_kind != PAD).any()
                or (sched.mirror.existing.term_kind != PAD).any()
            )
            has_spread = bool((pb.tsc_topo_key != PAD).any())
            has_images = bool((pb.img_ids >= 0).any())
            enabled = fwk.device_enabled()
            weights = tuple(
                fwk.score_weights.get(n, 0) for n in gang.WEIGHT_ORDER
            )
            # a fresh device view off the EXTENDED node tensors —
            # independent of the hot loop's chained/delta-cached state,
            # like explain
            dc = DeviceCluster.from_host(pf.nt, sched.mirror.existing, vocab)
            db = DeviceBatch.from_host(pb)
            hostname_dev = sched._hostname_dev(vocab)
            v_cap = bucket_cap(len(vocab.label_vals))
            extra_score = None
            target_slot = None
            if target_node is not None:
                target_slot = pf.nt.name_to_idx.get(target_node)
                if target_slot is None:
                    target_slot = pf.clone_slots.get(target_node)
                if target_slot is not None:
                    es = np.zeros((p_cap, pf.nt.n_cap), np.int64)
                    es[:, target_slot] = _TARGET_BONUS
                    extra_score = jnp.asarray(es)
            planes = wire.device_put_packed(
                {k: np.asarray(v) for k, v in pf.planes.items()}
            )
            if sched.mesh is not None:
                # mesh-partitioned what-ifs (MULTICHIP.md): the fork axis
                # is embarrassingly parallel — shard KF over the mesh's
                # pods axis (each device simulates its own forks; the
                # shared snapshot/batch replicate, so the vmap body needs
                # ZERO collectives on a pods-major mesh).  Indivisible
                # KF (e.g. the K=1 whatif reroute) replicates instead.
                import jax as _jax
                from jax.sharding import (
                    NamedSharding as _NS,
                    PartitionSpec as _P,
                )

                from kubernetes_tpu.parallel.mesh import place_cluster

                pa = sched.mesh.shape["pods"]

                def _place_fork(x):
                    spec = (
                        _P("pods", *([None] * (x.ndim - 1)))
                        if pa > 1 and x.shape[0] % pa == 0
                        else _P()
                    )
                    return _jax.device_put(x, _NS(sched.mesh, spec))

                planes = {k: _place_fork(v) for k, v in planes.items()}
                dc = place_cluster(sched.mesh, dc)
            d_cap = tables.pop("d_cap")

    if serial_snapshot is not None:
        # serial replay runs OUTSIDE the lock: K forks of oracle replay
        # can take seconds and must not stall the scheduling loop (the
        # same rule the kernel dispatch follows).  The snapshot's object
        # graph is read-stable — cache objects are replaced, not mutated,
        # on informer updates (the oracle_view discipline).
        nodes_snap, placed_snap, groups_snap, pvs_snap, pvcs_snap = (
            serial_snapshot
        )
        t_ser = time.perf_counter()
        sim = _simulate_serial(
            sched,
            forks,
            ordered,
            needs,
            target_node,
            nodes_snap,
            placed_snap,
            groups_snap,
            pvs_snap,
            pvcs_snap,
        )
        sim.skipped.update(skipped)
        sim.wall_s = time.perf_counter() - t0
        tr = sched.tracer
        if tr.enabled:
            tr.complete(
                "plan.serial", t_ser, cat="plan", planner=planner,
                forks=len(forks),
            )
        _observe(sched, planner, sim)
        return sim

    # the fused dispatch + its d2h run OUTSIDE the lock (device-path rule:
    # a first-shape XLA compile must not stall the scheduling loop)
    from kubernetes_tpu.observability import kernels as kernels_mod

    tr = sched.tracer
    t_disp = time.perf_counter()
    try:
        out_dev = cf_ops.counterfactual_run(
            dc,
            db,
            hostname_dev,
            v_cap,
            g_cap,
            wt["tid_sp"],
            wt["rep_sp_p"],
            wt["rep_sp_c"],
            wt["tid_ip"],
            wt["rep_ip_p"],
            wt["rep_ip_u"],
            wt["ip_cdv_tab"],
            jnp.asarray(gid),
            jnp.asarray(gfirst),
            jnp.asarray(glast),
            jnp.asarray(gneed),
            **planes,
            **(volt or {}),
            has_interpod=has_interpod,
            has_spread=has_spread,
            has_images=has_images,
            enabled=enabled,
            weights=weights,
            extra_score=extra_score,
            d_cap=d_cap,
            d2_cap=wt["d2_cap"],
            fit_strategy=fwk.fit_strategy(),
            **tables,
        )
        # planner dispatches are host-tracer-visible like every scheduling
        # path: dispatch/harvest halves as spans, alongside the
        # scheduler_tpu_plan_* metrics and the `plan` flight event (_observe)
        if tr.enabled:
            tr.complete(
                "dispatch.plan", t_disp, cat="plan", planner=planner,
                forks=len(forks), pods=len(ordered),
            )
        t_harvest = time.perf_counter()
        fetched = {
            k: np.asarray(v)
            for k, v in sched._d2h_guarded(
                out_dev, kernel="counterfactual.counterfactual_run"
            ).items()
        }
    except kernels_mod.DispatchFailed as e:
        # abandoned kernel dispatch: the same fork specs replay through
        # the serial forked-snapshot oracle, decision-identically, while
        # the breaker keeps the kernel parked
        sched._note_dispatch_failure(e)
        with sched._mu:
            snap = _serial_snapshot(sched, gang_positions)
        t_ser = time.perf_counter()
        sim = _simulate_serial(
            sched, forks, ordered, needs, target_node, *snap
        )
        sim.skipped.update(skipped)
        sim.wall_s = time.perf_counter() - t0
        if tr.enabled:
            tr.complete(
                "plan.serial", t_ser, cat="plan", planner=planner,
                forks=len(forks),
            )
        _observe(sched, planner, sim)
        return sim
    if tr.enabled:
        tr.complete(
            "harvest.plan", t_harvest, cat="plan", planner=planner,
            forks=len(forks),
        )

    sim = SimResult(
        engine="kernel",
        k=len(forks),
        dispatches=1,
        batch=[p.name for p in ordered],
        skipped=skipped,
    )
    names = pf.names
    diag = list(gang.DIAG_KERNELS)
    for k, f in enumerate(forks):
        chosen = fetched["chosen"][k]
        live_row = pf.planes["fk_pod_live"][k]
        placements = {}
        target_ok = {}
        for i, p in enumerate(ordered):
            if not live_row[i]:
                continue
            c = int(chosen[i])
            placements[p.name] = (
                names[c] if 0 <= c < len(names) else None
            )
            if target_slot is not None:
                target_ok[p.name] = c == target_slot
        gang_admitted = {
            key: int(fetched["gang_admit"][k][slot])
            for slot, key in enumerate(slot_keys)
        }
        fork_out = {
            "label": f.label,
            "placements": placements,
            "admitted": int(fetched["admitted"][k]),
            "unschedulable": int(fetched["unschedulable"][k]),
            "density_ppm": int(fetched["density_ppm"][k]),
            "reasons": {
                name: int(v)
                for name, v in zip(diag, fetched["reasons"][k])
                if int(v)
            },
            "gang_admitted": gang_admitted,
            "meta": dict(f.meta),
        }
        if target_slot is not None:
            fork_out["target_ok"] = target_ok
        sim.forks.append(fork_out)
    sim.wall_s = time.perf_counter() - t0
    _observe(sched, planner, sim)
    return sim


def _observe(sched, planner: str, sim: SimResult) -> None:
    prom = sched.prom
    prom.plan_forks.inc(sim.k)
    prom.recorder.observe(prom.plan_duration, sim.wall_s, planner=planner)
    # the flight-recorder `plan` breadcrumb (queryable at
    # /debug/flightrecorder?pod=planner): one per planner run, both
    # engines, so what-if traffic is visible next to pod lifecycles
    fl = sched.flight
    if fl.enabled:
        fl.record(
            "planner",
            "plan",
            {
                "planner": planner,
                "engine": sim.engine,
                "forks": sim.k,
                "dispatches": sim.dispatches,
                "wall_s": round(sim.wall_s, 6),
            },
        )


def _serial_snapshot(sched, gang_positions):
    """The serial engine's inputs, snapshotted under sched._mu (caller
    holds it) so the replay itself can run outside the lock."""
    return (
        [cn.node for cn in sched.cache.real_nodes()],
        sched.cache.placed_pods(),
        {
            key: sched.gangs.get(key)
            for key in gang_positions
            if sched.gangs.get(key) is not None
        },
        {o.key: o for o in sched.pv_cache.list()},
        {o.key: o for o in sched.pvc_cache.list()},
    )


def _simulate_serial(
    sched, forks, ordered, needs, target_node, nodes, placed, groups, pvs, pvcs
) -> SimResult:
    """The kill-switch / fallback engine: same fork specs, serial forked-
    snapshot oracle, replayed OUTSIDE the scheduler lock over the
    read-stable snapshot _serial_snapshot took under it."""
    from kubernetes_tpu.oracle.planner import serial_plan

    outcomes = serial_plan(
        nodes=nodes,
        placed=placed,
        pods=ordered,
        forks=forks,
        groups=groups,
        needs=needs,
        pvs=pvs,
        pvcs=pvcs,
        namespace_labels=sched.namespace_labels,
        target_node=target_node,
    )
    sim = SimResult(
        engine="serial",
        k=len(forks),
        dispatches=0,
        batch=[p.name for p in ordered],
    )
    for f, o in zip(forks, outcomes):
        fork_out = {
            "label": f.label,
            "placements": o["placements"],
            "admitted": o["admitted"],
            "unschedulable": o["unschedulable"],
            "density_ppm": o["density_ppm"],
            "reasons": {},
            "gang_admitted": o["gang_admitted"],
            "meta": dict(f.meta),
        }
        if target_node is not None:
            fork_out["target_ok"] = o.get("target_ok", {})
        sim.forks.append(fork_out)
    return sim


# ---------------------------------------------------------------------------
# The planner catalogue
# ---------------------------------------------------------------------------


def _distinct_shapes(sched, max_shapes: int = 4) -> List[str]:
    """One representative node per distinct (cpu, mem, pods) allocatable."""
    seen = {}
    with sched._mu:
        for cn in sched.cache.real_nodes():
            r = cn.node.allocatable
            key = (r.milli_cpu, r.memory, r.allowed_pod_number)
            if key not in seen:
                seen[key] = cn.node.name
    return list(seen.values())[:max_shapes]


def plan_autoscale(
    sched,
    shapes: Optional[Sequence[str]] = None,
    max_count: int = 3,
    max_backlog: int = 256,
) -> dict:
    """Scale-up/down planning: which node shape admits the unschedulable
    backlog cheapest (cost = clones × template milli-cpu), and which empty
    nodes are removable without hurting backlog admission."""
    fwk = next(iter(sched.profiles.values()))
    pods, skipped = backlog_pods(sched, fwk, max_pods=max_backlog)
    if not pods:
        return {
            "planner": "autoscale",
            "error": "no eligible pending backlog to plan for",
            "skipped": skipped,
        }
    shapes = list(shapes) if shapes else _distinct_shapes(sched)
    with sched._mu:
        node_alloc = {
            cn.node.name: cn.node.allocatable.milli_cpu
            for cn in sched.cache.real_nodes()
        }
        empty = [
            cn.node.name
            for cn in sched.cache.real_nodes()
            if not cn.pods
        ]
    forks = [Fork(label="baseline")]
    for s in shapes:
        for m in range(1, max_count + 1):
            forks.append(
                Fork(
                    label=f"add:{s}x{m}",
                    add=tuple((s, f"{s}~cf{i}") for i in range(m)),
                    meta=(("shape", s), ("count", m),
                          ("cost_milli", node_alloc.get(s, 0) * m)),
                )
            )
    scale_down_considered = empty[:16]
    for name in scale_down_considered:
        forks.append(
            Fork(label=f"remove:{name}", remove=(name,),
                 meta=(("scale_down", name),))
        )
    sim = simulate_forks(sched, forks, pods, planner="autoscale")
    out = {
        "planner": "autoscale",
        "backlog": len(pods),
        "shapes": shapes,
        "result": sim.to_json(),
    }
    by_label = {f["label"]: f for f in sim.forks}
    base = by_label.get("baseline")
    if base is not None:
        best = None
        for f in sim.forks:
            meta = f.get("meta", {})
            if "shape" not in meta:
                continue
            gain = f["admitted"] - base["admitted"]
            key = (-f["admitted"], meta.get("cost_milli", 0))
            if gain > 0 and (best is None or key < best[0]):
                best = (key, f, gain)
        if best is not None:
            _, f, gain = best
            out["recommendation"] = {
                "action": "scale_up",
                "shape": f["meta"]["shape"],
                "count": f["meta"]["count"],
                "newly_schedulable": gain,
                "cost_milli": f["meta"]["cost_milli"],
            }
        else:
            out["recommendation"] = {
                "action": "none",
                "reason": "no candidate shape admits more of the backlog",
            }
        out["scale_down"] = [
            f["meta"]["scale_down"]
            for f in sim.forks
            if "scale_down" in f.get("meta", {})
            and f["admitted"] >= base["admitted"]
        ]
        # no silent caps: empty nodes beyond the per-dispatch candidate
        # budget were NOT simulated and must not read as "not removable"
        out["scale_down_considered"] = scale_down_considered
        out["scale_down_unevaluated"] = empty[16:]
    return out


def plan_deschedule(sched, max_candidates: int = 8) -> dict:
    """Defragmentation planning: cordon a lightly-loaded node, evict its
    pods, and see whether they re-place elsewhere and what that does to
    bin-packing density — the descheduler's question as K forks."""
    import copy as _copy

    fwk = next(iter(sched.profiles.values()))
    with sched._mu:
        candidates = sorted(
            (
                cn
                for cn in sched.cache.real_nodes()
                if cn.pods
            ),
            key=lambda cn: (len(cn.pods), cn.node.name),
        )[:max_candidates]
        cand = []
        for cn in candidates:
            pods = [
                p
                for p in cn.pods.values()
                if _pod_ineligible(sched, fwk, p) is None
            ]
            if pods and len(pods) == len(cn.pods):
                cand.append((cn.node.name, pods))
    if not cand:
        return {
            "planner": "deschedule",
            "error": "no drainable candidate nodes (occupied + eligible)",
        }
    batch = []
    forks = [Fork(label="baseline", live=())]
    for name, pods in cand:
        copies = []
        for p in pods:
            c = _copy.deepcopy(p)
            c.node_name = ""
            copies.append(c)
        batch.extend(copies)
        forks.append(
            Fork(
                label=f"drain:{name}",
                cordon=(name,),
                evict=tuple(p.uid for p in pods),
                live=tuple(c.uid for c in copies),
                meta=(("node", name), ("pods", len(pods))),
            )
        )
    sim = simulate_forks(sched, forks, batch, planner="deschedule")
    out = {
        "planner": "deschedule",
        "candidates": [name for name, _ in cand],
        "result": sim.to_json(),
    }
    base = next((f for f in sim.forks if f["label"] == "baseline"), None)
    drains = []
    for f in sim.forks:
        meta = f.get("meta", {})
        if "node" not in meta:
            continue
        drains.append(
            {
                "node": meta["node"],
                "evicted": meta["pods"],
                "replaced": f["admitted"],
                "fully_drainable": f["admitted"] == meta["pods"],
                "density_ppm": f["density_ppm"],
                "density_gain_ppm": (
                    f["density_ppm"] - base["density_ppm"]
                    if base is not None
                    else None
                ),
            }
        )
    drains.sort(
        key=lambda d: (not d["fully_drainable"], -(d["density_gain_ppm"] or 0))
    )
    out["drains"] = drains
    best = next((d for d in drains if d["fully_drainable"]), None)
    out["recommendation"] = (
        {"action": "drain", "node": best["node"],
         "density_gain_ppm": best["density_gain_ppm"]}
        if best is not None
        else {"action": "none", "reason": "no candidate drains fully re-place"}
    )
    return out


def plan_preempt_cost(sched, max_backlog: int = 256, max_classes: int = 8) -> dict:
    """Preemption cost forecast per pending priority class: how many class
    members become schedulable if every strictly-lower-priority placed pod
    were evicted (the cascade's upper bound), vs without evictions."""
    fwk = next(iter(sched.profiles.values()))
    pods, skipped = backlog_pods(sched, fwk, max_pods=max_backlog)
    if not pods:
        return {
            "planner": "preempt_cost",
            "error": "no eligible pending backlog",
            "skipped": skipped,
        }
    classes: Dict[int, list] = {}
    for p in pods:
        classes.setdefault(p.priority, []).append(p)
    prios = sorted(classes, reverse=True)[:max_classes]
    with sched._mu:
        placed = sched.cache.placed_pods()
    forks = []
    for c in prios:
        victims = tuple(p.uid for p in placed if p.priority < c)
        live = tuple(p.uid for p in classes[c])
        forks.append(
            Fork(label=f"class:{c}:base", live=live,
                 meta=(("priority", c), ("kind", "base"),))
        )
        forks.append(
            Fork(
                label=f"class:{c}:preempt",
                evict=victims,
                live=live,
                meta=(
                    ("priority", c),
                    ("kind", "preempt"),
                    ("victims", len(victims)),
                ),
            )
        )
    sim = simulate_forks(sched, forks, pods, planner="preempt_cost")
    by_label = {f["label"]: f for f in sim.forks}
    per_class = []
    for c in prios:
        base = by_label.get(f"class:{c}:base")
        pre = by_label.get(f"class:{c}:preempt")
        if base is None or pre is None:
            continue
        per_class.append(
            {
                "priority": c,
                "pending": len(classes[c]),
                "schedulable_now": base["admitted"],
                "schedulable_with_max_preemption": pre["admitted"],
                "cascade_upper_bound": pre["admitted"] - base["admitted"],
                "victims_considered": pre["meta"].get("victims", 0),
            }
        )
    return {
        "planner": "preempt_cost",
        "classes": per_class,
        "result": sim.to_json(),
    }


def whatif_after_evictions(sched, pod, node_name: str, victim_uids) -> dict:
    """The K=1 counterfactual behind /debug/explain?whatif_node=: evict
    ``victim_uids`` and ask whether ``pod`` is then feasible ON
    ``node_name`` (a dominating target-score bonus makes
    ``chosen == target`` ⟺ feasible-at-target).  Same kernel, same fork
    packer as the batched planners — the single-what-if endpoint cannot
    drift from the fleet tier."""
    import copy as _copy

    if pod.nominated_node_name:
        # a live preemptor is USUALLY nominated already — the what-if asks
        # about the pod minus its nomination state (the caller supplies
        # the eviction set explicitly), so simulate a cleared copy rather
        # than skipping
        pod = _copy.deepcopy(pod)
        pod.nominated_node_name = ""
    fork = Fork(
        label=f"whatif:{node_name}", evict=tuple(victim_uids)
    )
    sim = simulate_forks(
        sched, [fork], [pod], target_node=node_name, planner="whatif"
    )
    out = {"engine": sim.engine, "dispatches": sim.dispatches}
    if pod.name in sim.skipped:
        out["skipped_reason"] = sim.skipped[pod.name]
        return out
    if not sim.forks:
        out["error"] = "simulation unavailable"
        return out
    f0 = sim.forks[0]
    t_ok = f0.get("target_ok", {}).get(pod.name)
    if t_ok is None:
        out["error"] = f"unknown node {node_name!r}"
        return out
    out["feasible"] = bool(t_ok)
    out["placement"] = f0["placements"].get(pod.name)
    return out


PLANNERS = {
    "autoscale": plan_autoscale,
    "deschedule": plan_deschedule,
    "preempt_cost": plan_preempt_cost,
}


def run_planner(sched, name: str, params: Optional[dict] = None) -> dict:
    """The /debug/plan dispatcher: planner name + query params → JSON.
    A debug surface must not 500: malformed params and racy state (e.g.
    a victim pod unbinding between the planner's snapshot and the fork
    pack) come back as an ``error`` field, not an exception."""
    params = params or {}
    if name == "list":
        return {
            "planners": sorted(PLANNERS),
            "kernel": bool(sched.config.planner_kernel),
        }
    fn = PLANNERS.get(name)
    if fn is None:
        return {
            "error": f"unknown planner {name!r}",
            "planners": sorted(PLANNERS),
        }
    kw = {}
    try:
        if name == "autoscale":
            if params.get("shapes"):
                kw["shapes"] = [
                    s for s in str(params["shapes"]).split(",") if s
                ]
            if params.get("max_count"):
                kw["max_count"] = int(params["max_count"])
        elif name == "deschedule":
            if params.get("max_candidates"):
                kw["max_candidates"] = int(params["max_candidates"])
    except ValueError as e:
        return {"error": f"bad parameter: {e}"}
    try:
        return fn(sched, **kw)
    except ValueError as e:
        # planner-level input/race errors (unknown shape template, pod
        # unbound mid-plan, …) — report, don't 500
        return {"error": str(e), "planner": name}
