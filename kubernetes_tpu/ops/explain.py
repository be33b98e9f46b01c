"""Explain-mode kernel: per-plugin feasibility masks for a pod batch.

The batched filter pipeline (ops/gang.py) computes a per-kernel pass mask
for every (pod, node) pair but returns only the winner and aggregate
rejection counts — the per-node, per-plugin verdicts (the reference's
Diagnosis/NodeToStatusMap, framework/types.go:367) are thrown away on
device.  ``explain_masks`` recomputes exactly those masks for a diagnosed
batch and returns the FULL [N_DIAG, P, N] tensor, so one gated d2h fetch
answers "why is this pod unschedulable on each node" per plugin.

Semantics: verdicts are judged against the CURRENT cluster snapshot with
no in-batch peers and no nominated-pod charges — the state a fresh
one-pod scheduling attempt (and the host oracle's ``feasible_nodes``)
would see.  The mask stack is ordered exactly like ``gang.DIAG_KERNELS``:

    NodeUnschedulable, NodeName, TaintToleration, NodeAffinity, NodePorts,
    HostFilters, NodeResourcesFit, PodTopologySpread, InterPodAffinity

Each row is the kernel's independent pass/fail (NOT first-failure
attributed): a node rejected by three plugins is False in three rows,
matching the oracle's collect-all-reasons walk.

Cost model: this is a separate jitted entry point dispatched only from the
/debug/explain path — the scheduling hot loop never calls it, so its d2h
(the one blocking fetch of the [N_DIAG, P, N] stack) happens exclusively
for diagnosed pods.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32
from kubernetes_tpu.snapshot.schema import N_FIXED_LANES

# shard-rule roster: diagnosis recomputes minMatch over the tracked
# node set per constraint — inherently a full-N reduction
_KTPU_N_COLLECTIVES = {
    "explain_masks._spread_one": "resolved(replicated): per-constraint "
    "min-match over the tracked N axis (filtering.go:313 semantics) — "
    "the explain/debug tier builds its own single-device snapshot view "
    "(one diagnosed pod per d2h, latency-bound not throughput-bound), "
    "so the crossed operand is whole-array by construction; were it "
    "mesh-placed, the min-match would ride a cross-shard min-reduce",
}


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32, extra_mask=bool[P,N])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2])
# ktpu: static(v_cap=16)
@functools.partial(
    jax.jit,
    static_argnames=(
        "v_cap",
        "has_interpod",
        "has_spread",
        "has_ports",
        "enabled",
        "check_fit",
        "d_cap",
    ),
)
def explain_masks(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    v_cap: int,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_ports: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    check_fit: bool = True,
    extra_mask=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sp_host_cdv=None,
    d_cap=None,
):
    """Returns bool [N_DIAG, P, N] per-kernel pass masks (gang.DIAG_KERNELS
    row order) plus the combined feasibility [P, N] as the last element of
    a 2-tuple.  Table kwargs come from ``gang.batch_tables``."""
    g = gang.precompute(
        dc,
        db,
        hostname_key,
        v_cap,
        has_interpod=has_interpod,
        has_spread=has_spread,
        has_ports=has_ports,
        has_images=False,
        enabled=enabled,
        extra_mask=extra_mask,
        sp_keys=sp_keys,
        sp_cdv_tab=sp_cdv_tab,
        ip_keys=ip_keys,
        d_cap=d_cap,
        sp_host_cdv=sp_host_cdv,
    )
    P, N = g.static_mask.shape
    Rn = dc.requested.shape[1]
    Rp = db.requests.shape[1]
    true_pn = jnp.ones((P, N), bool)

    # ---- NodeResourcesFit against the snapshot usage (the state-dependent
    # half of gang_schedule's cheap_body, with zero in-batch commits)
    if check_fit:
        fits = dc.num_pods + 1 <= dc.allowed_pods  # [N]
        req = db.requests  # [P, Rp]
        all_zero = jnp.all(req == 0, axis=1)  # [P]
        avail = dc.allocatable - dc.requested  # [N, Rn]
        if Rp > Rn:
            avail = jnp.concatenate(
                [avail, jnp.zeros((N, Rp - Rn), I32)], axis=1
            )
        conflict = req[:, None, :] > avail[None, :, :]  # [P, N, Rp]
        # extended-resource lanes only count when actually requested
        scalar_lane = jnp.arange(Rp) >= N_FIXED_LANES
        conflict = conflict & (
            ~scalar_lane[None, None, :] | (req[:, None, :] > 0)
        )
        lane_ok = ~jnp.any(conflict, axis=2)  # [P, N]
        m_fit = fits[None, :] & (all_zero[:, None] | lane_ok)
    else:
        m_fit = true_pn

    # ---- PodTopologySpread hard constraints vs existing pods only
    C = g.sp_dv.shape[1]
    if C:
        big32 = jnp.iinfo(jnp.int32).max

        def _spread_one(hard, dv, te, dom_cnt, dom_pres, ndom, selfm, mind, mskew):
            total = dom_cnt  # [C, N] — no batch-peer contributions
            min_match = jnp.min(jnp.where(te, total, big32), axis=1)  # [C]
            min_match = jnp.where((mind > 0) & (ndom < mind), 0, min_match)
            skew = total + selfm.astype(I32)[:, None] - min_match[:, None]
            c_ok = (dv >= 0) & (~dom_pres | (skew <= mskew[:, None]))
            return jnp.all(~hard[:, None] | c_ok, axis=0)  # [N]

        m_spread = jax.vmap(_spread_one)(
            g.sp_hard,
            g.sp_dv,
            g.sp_te,
            g.sp_dom_cnt,
            g.sp_dom_pres,
            g.sp_ndom,
            g.sp_self,
            db.tsc_min_domains,
            db.tsc_max_skew,
        )
    else:
        m_spread = true_pn

    # ---- InterPodAffinity vs existing pods only
    AT = g.ip_dv.shape[1]
    if AT:

        def _interpod_one(dv, dom_cnt, is_aff, is_anti, any_static, self_all):
            topo_present = dv >= 0  # [AT, N]
            total = dom_cnt
            viol2 = jnp.any(
                is_anti[:, None] & topo_present & (total > 0), axis=0
            )
            aff_ok = jnp.all(
                ~is_aff[:, None] | (topo_present & (total > 0)), axis=0
            )
            topo_all = jnp.all(~is_aff[:, None] | topo_present, axis=0)
            escape = jnp.any(is_aff) & ~any_static & self_all
            ok3 = aff_ok | (escape & topo_all)
            return ~viol2 & ok3  # [N]

        m_interpod = ~g.ip_viol_existing & jax.vmap(_interpod_one)(
            g.ip_dv,
            g.ip_dom_cnt,
            g.ip_is_aff,
            g.ip_is_anti,
            g.ip_any_static,
            g.ip_self_all,
        )
    else:
        m_interpod = ~g.ip_viol_existing

    base = dc.node_valid[None, :] & db.valid[:, None]
    stack = jnp.stack(
        [
            g.d_unsched,
            g.d_nodename,
            g.d_taints,
            g.d_nodeaff,
            g.d_ports,  # static port conflicts only: no in-batch peers
            g.d_extra,
            m_fit,
            m_spread,
            m_interpod,
        ]
    )  # [N_DIAG, P, N]
    feasible = base & jnp.all(stack, axis=0)
    return stack, feasible
