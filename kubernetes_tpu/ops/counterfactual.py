"""Batched [K, P, N] counterfactual simulation — the planner tier's kernel.

The reference scheduler answers every "what would happen if…" question in
satellite projects (cluster-autoscaler, descheduler) that each re-implement
a slow serial simulator over Filter/Score semantics.  Here the question is
a SHAPE: ``counterfactual_run`` vmaps the workloads admission engine
(ops/coscheduling.workloads_run — speculation + the term-factored serial
admission scan) over a leading fork axis K, stepping K mutated snapshots
through ONE fused dispatch.

A fork is a set of per-fork planes over the SHARED packed snapshot:

  * ``fk_alive``      [K, N]      node exists in this fork (removals clear
                                  it; clone slots set it only in the forks
                                  that add them)
  * ``fk_unsched``    [K, N]      cordons
  * ``fk_alloc``      [K, N, Rn]  capacity (scaled per fork)
  * ``fk_req/_nz/_npods``         usage rows with the fork's evictions
                                  subtracted (host-recomputed per touched
                                  node in exact pack arithmetic)
  * ``fk_epod_valid`` [K, E]      evicted / removed-node placed pods
  * ``fk_pod_live``   [K, P]      which batch pods this fork simulates

Inside the vmap each fork materializes a per-fork ``DeviceCluster`` view:
usage/validity planes substituted, and — crucially — the label/taint rows
of non-alive slots neutralized to ABSENT/PAD so a removed (or not-added)
node is EXACTLY equivalent to a node that never existed: it drops out of
spread domain tracking, inter-pod topology membership, and min-match the
same way a repack without the node would.  Everything downstream is the
UNMODIFIED workloads engine — gang checkpoint/rollback, the factored
[T, N] carries committed through ``wave.factored_carry_update``, usage
rows through ``common.usage_carry_update`` — so fork semantics cannot
drift from the production admission path, and every fork is bit-identical
to the serial forked-snapshot oracle (oracle/planner.py) by the same
argument as the workloads tier itself (tools/paritycheck.py
``plan_vs_serial_oracle``).

Per-fork outcomes (placements, unschedulable counts, first-failure reason
sums, bin-packing density, gang admissions) pack into ONE d2h readback
through ``Scheduler._d2h`` — K what-ifs cost one host round trip where
the serial formulation costs K.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import coscheduling as cos
from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32, I64
from kubernetes_tpu.snapshot.interner import ABSENT, PAD
from kubernetes_tpu.snapshot.schema import LANE_CPU, LANE_MEM

# Fixed-point scale of the density readout (parts per million).
DENSITY_SCALE = 1_000_000

# shard-rule roster: the per-fork summary reductions collapse the node
# axis (admitted/unschedulable counts are P-reductions, but density and
# the per-fork workloads engine underneath contract over N).  Under a
# sharded N mesh each is a cross-shard collective; the K axis itself is
# embarrassingly parallel and would shard cleanly (ROADMAP item 1).
_KTPU_N_COLLECTIVES = {
    "counterfactual_run.one_fork": "resolved(local): per-fork "
    "snapshot-view substitution + density/utilization reductions over "
    "the alive N axis — the FORK axis is the sharded one (planner/plan.py "
    "places the fk_* planes P('pods'): each device simulates its own "
    "forks against the replicated snapshot, zero cross-fork collectives); "
    "the admission engine inside is workloads_schedule, whose own roster "
    "entries govern any in-fork N crossings",
}


def fork_cluster_view(dc: DeviceCluster, alive, unsched, alloc, req, nz, npods, epod_valid, n_valid):
    """One fork's DeviceCluster: usage/validity planes substituted and the
    static rows of non-alive slots NEUTRALIZED (labels → ABSENT, taints →
    PAD, visit rank → -1) so absence is indistinguishable from a repack
    without the node — spread/inter-pod domain tracking included."""
    gone = ~alive
    labels = jnp.where(gone[:, None], ABSENT, dc.node_labels)
    return dataclasses.replace(
        dc,
        allocatable=alloc,
        requested=req,
        nonzero_req=nz,
        num_pods=npods,
        node_valid=alive,
        unschedulable=unsched,
        node_labels=labels,
        taint_key=jnp.where(gone[:, None], PAD, dc.taint_key),
        taint_val=jnp.where(gone[:, None], PAD, dc.taint_val),
        taint_effect=jnp.where(gone[:, None], PAD, dc.taint_effect),
        visit_rank=jnp.where(gone, -1, dc.visit_rank),
        epod_valid=epod_valid,
        n_valid_nodes=n_valid,
    )


def fork_density(alive, alloc, used):
    """Mean cpu+mem utilization over alive nodes with nonzero capacity, in
    DENSITY_SCALE fixed point — the descheduler's bin-packing objective as
    one integer per fork."""
    a_cpu = alloc[:, LANE_CPU].astype(I64)
    a_mem = alloc[:, LANE_MEM].astype(I64)
    u_cpu = used[:, LANE_CPU].astype(I64)
    u_mem = used[:, LANE_MEM].astype(I64)
    counted = alive & (a_cpu > 0) & (a_mem > 0)
    util = (
        u_cpu * DENSITY_SCALE // jnp.maximum(a_cpu, 1)
        + u_mem * DENSITY_SCALE // jnp.maximum(a_mem, 1)
    ) // 2
    total = jnp.sum(jnp.where(counted, util, 0))
    n = jnp.sum(counted.astype(I32))
    return total // jnp.maximum(n.astype(I64), 1)


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32)
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(gang_id=i32[P], gang_first=bool[P], gang_last=bool[P], gang_need=i32[P])
# ktpu: axes(fk_alive=bool[KF,N], fk_unsched=bool[KF,N], fk_alloc=i32[KF,N,Rn], fk_req=i32[KF,N,Rn])
# ktpu: axes(fk_nz=i32[KF,N,2], fk_npods=i32[KF,N], fk_epod_valid=bool[KF,E], fk_nvalid=i32[KF])
# ktpu: axes(fk_pod_live=bool[KF,P])
# ktpu: axes(vol_table=DTable[P,PV2,VT], vol_valid=bool[P,PV2], vol_bad=bool[P])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2], extra_score=i64[P,N])
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16, g_cap=4)
@functools.partial(
    jax.jit,
    static_argnames=(
        "v_cap",
        "g_cap",
        "hard_pod_affinity_weight",
        "has_interpod",
        "has_spread",
        "has_images",
        "enabled",
        "weights",
        "d_cap",
        "d2_cap",
        "fit_strategy",
    ),
)
def counterfactual_run(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    v_cap: int,
    g_cap: int,
    tid_sp,
    rep_sp_p,
    rep_sp_c,
    tid_ip,
    rep_ip_p,
    rep_ip_u,
    ip_cdv_tab,
    gang_id,
    gang_first,
    gang_last,
    gang_need,
    fk_alive,
    fk_unsched,
    fk_alloc,
    fk_req,
    fk_nz,
    fk_npods,
    fk_epod_valid,
    fk_nvalid,
    fk_pod_live,
    vol_table=None,
    vol_valid=None,
    vol_bad=None,
    hard_pod_affinity_weight: int = 1,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_images: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    weights: tuple = gang.DEFAULT_WEIGHTS,
    extra_score=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sp_host_cdv=None,
    d_cap: int = 8,
    d2_cap: int = 8,
    fit_strategy: tuple = gang.DEFAULT_FIT_STRATEGY,
):
    """K forked snapshots × one batch through one fused dispatch.

    Returns a dict of per-fork outcomes (everything leads with the KF
    axis; the caller fetches the whole dict in ONE ``Scheduler._d2h``):

      chosen       [KF, P]   post-rollback placements (-1 unschedulable)
      n_feas       [KF, P]   per-pod feasible-node counts
      reasons      [KF, ND]  summed first-failure diagnosis lanes
      admitted     [KF]      live batch pods placed
      unschedulable[KF]      live batch pods left pending
      density_ppm  [KF]      mean cpu+mem utilization after placements
      gang_admit   [KF, G2]  per-gang verdicts (-1/0/1)
      gang_landed  [KF, G2]  members placed per gang
    """

    def one_fork(alive, unsched, alloc, req, nz, npods, epv, n_valid, live):
        dc_k = fork_cluster_view(
            dc, alive, unsched, alloc, req, nz, npods, epv, n_valid
        )
        db_k = dataclasses.replace(db, valid=db.valid & live)
        chosen, n_feas, reason_counts, tallies, wl = cos.workloads_run(
            dc_k,
            db_k,
            hostname_key,
            v_cap,
            g_cap,
            tid_sp,
            rep_sp_p,
            rep_sp_c,
            tid_ip,
            rep_ip_p,
            rep_ip_u,
            ip_cdv_tab,
            gang_id,
            gang_first,
            gang_last,
            gang_need,
            vol_table=vol_table,
            vol_valid=vol_valid,
            vol_bad=vol_bad,
            hard_pod_affinity_weight=hard_pod_affinity_weight,
            has_interpod=has_interpod,
            has_spread=has_spread,
            has_images=has_images,
            enabled=enabled,
            weights=weights,
            extra_mask=None,
            nom_node=None,
            nom_prio=None,
            nom_req=None,
            sp_keys=sp_keys,
            sp_cdv_tab=sp_cdv_tab,
            ip_keys=ip_keys,
            sp_host_cdv=sp_host_cdv,
            d_cap=d_cap,
            d2_cap=d2_cap,
            extra_score=extra_score,
            fit_strategy=fit_strategy,
        )
        is_live = db.valid & live
        admitted = jnp.sum((is_live & (chosen >= 0)).astype(I32))
        unsched_n = jnp.sum((is_live & (chosen < 0)).astype(I32))
        reasons = jnp.sum(
            jnp.where(is_live[:, None], reason_counts, 0), axis=0
        )  # [ND]
        density = fork_density(alive, alloc, tallies["requested"])
        return (
            chosen,
            n_feas,
            reasons,
            admitted,
            unsched_n,
            density,
            wl["gang_admit"],
            wl["gang_landed"],
        )

    outs = jax.vmap(one_fork)(
        fk_alive,
        fk_unsched,
        fk_alloc,
        fk_req,
        fk_nz,
        fk_npods,
        fk_epod_valid,
        fk_nvalid,
        fk_pod_live,
    )
    keys = (
        "chosen",
        "n_feas",
        "reasons",
        "admitted",
        "unschedulable",
        "density_ppm",
        "gang_admit",
        "gang_landed",
    )
    # ktpu: allow(jit-boundary) — static python zip over fixed output names
    return dict(zip(keys, outs))
