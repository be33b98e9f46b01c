"""Gang/coscheduling admission + DRA allocation — the workloads tier.

One fused device dispatch schedules batches that carry PodGroup gangs
and/or DRA resource claims (and volume-topology-masked pods via the static
extra mask), riding the wave dispatch's two-pass shape (ops/wave.py):

  1. **Speculation** — every pod is evaluated in one parallel ``(P × N)``
     pass against the frozen snapshot (zero intra-batch deltas, the
     pre-batch DRA allocation state), exactly the wave's first pass.

  2. **Admission** — a serial scan replays the exact recurrence
     ``choice_i = F_i(S + Σ_{j<i} Δ(choice_j))`` over the TERM-FACTORED
     delta algebra (wave.factored_*: per-term [T, N] spread/inter-pod
     carries) EXTENDED with two allocation carries — ``free [N, DD]``
     device availability and ``claim_node [CL]`` claim pinning
     (ops/dra.py) — so DRA claims participate in conflict resolution like
     any other usage row, with in-batch contention resolved in queue
     order.

  **All-or-nothing gangs.**  The batch planner (workloads/gang.py) lays
  each gang's members out contiguously; the scan snapshots its ENTIRE
  carried state (usage + factored counts + allocation carries + the
  assignment row) at a gang's first member and, at its last member,
  admits the gang only when the members placed this batch cover the
  gang's remaining ``minMember`` need — otherwise the checkpoint is
  restored wholesale: usage rows, topology counts, device grants, and
  the members' own assignments all roll back, and later pods in the
  batch see a state in which the gang never happened.  This is the
  coscheduling plugin's Permit-barrier semantics collapsed into the
  dispatch: members land together or not at all, bit-identically to the
  serial gang/DRA oracle (oracle/workloads.py) replaying the same
  canonical order.

The verdict itself is gang.pod_step — the SAME code as the scan/wave
paths — and the factored dyn builders are imported from ops/wave.py, so
the three serial-recurrence replayers cannot drift.  Routing lives in
scheduler.py behind the ``gangDispatch`` kill-switch; with it off, gang
pods schedule individually and DRA/volume pods fall back to the serial
one-pod host-plugin path (decision-identical — kill-switch identity is
property-tested in tests/test_coscheduling.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import dra as dra_ops
from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.gang import N_DIAG
from kubernetes_tpu.ops import wave
from kubernetes_tpu.ops.common import (
    DeviceBatch,
    DeviceCluster,
    I32,
    I64,
    dnf_any,
    eval_table,
)
from kubernetes_tpu.snapshot.interner import ABSENT


def volume_topology_mask(dc: DeviceCluster, vol_table, vol_valid, vol_bad):
    """The volume-topology filter as a kernel mask: [P, N] bool — every
    bound PV's node-affinity DNF (packed one PV per ``PV2`` slot, ORed
    terms on the DTable term axis) must admit the node; a PV with nil
    affinity is packed invalid (matches everywhere); ``vol_bad`` marks
    pods whose bound PVC points at a missing PV (infeasible everywhere —
    binder.go:868 checkBoundClaims).  Reuses the conjunction evaluator the
    spread/affinity topology terms ride (ops/common.eval_table)."""
    vm = eval_table(vol_table, dc.node_labels, dc.val_ints)  # [P, PV2, T, N]
    per_pv = dnf_any(vm)  # [P, PV2, N]
    vol_mask = jnp.all(
        jnp.where(vol_valid[:, :, None], per_pv, True), axis=1
    )  # [P, N]
    return vol_mask & ~vol_bad[:, None]

# shard-rule roster: like the wave admission scan, the workloads scan
# contracts the factored [T, N] carries over N, and additionally reduces
# the [N, DD] device-availability plane per node (match counts, greedy
# ranks) and gathers the chosen node's take row.  Under a sharded N mesh
# each is a cross-shard collective (ROADMAP item 2 worklist).
_KTPU_N_COLLECTIVES = {
    "workloads_schedule.step": "resolved(collective): term-factored "
    "domain compare+reduce over N + per-node DRA match/take reductions + "
    "chosen-node row gathers (allocation commit, gang checkpoint "
    "restore) — same algebra as wave_schedule.step: per-term counts "
    "psum across node shards at the conflict compare, the chosen-node "
    "row gather is an owning-shard broadcast, rank-1 usage/DRA commits "
    "stay shard-local, and the gang checkpoint save/restore is "
    "elementwise over the carried state (no crossing)",
    "workloads_schedule.spec_one": "resolved(local): frozen-snapshot "
    "speculation — the vmap shards the POD axis (pods-major mesh: each "
    "device speculates its own pods against the replicated/node-sharded "
    "snapshot); the per-node DRA match counts reduce the device axis "
    "(DD), not N, so the reduction is shard-local until the final "
    "rostered argmax",
}

# carried state snapshotted at a gang's first member and restored wholesale
# on rollback (the allocation carries join when the batch has claims)
_CK_KEYS = (
    "requested",
    "nonzero",
    "num_pods",
    "assigned",
    "cnt_sp",
    "cnt_ip",
    "rev_cnt",
)
_CK_DRA_KEYS = ("free", "claim_node")


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, g=GangStatics, hostname_key=i32)
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(gang_id=i32[P], gang_first=bool[P], gang_last=bool[P], gang_need=i32[P])
# ktpu: axes(dev_key=i32[N,DD,DA], dev_val=i32[N,DD,DA], dev_valid=bool[N,DD], free0=bool[N,DD])
# ktpu: axes(sel_key=i32[P,DQ,DS], sel_op=i32[P,DQ,DS], sel_vals=i32[P,DQ,DS,DV])
# ktpu: axes(req_count=i32[P,DQ], req_all=bool[P,DQ], req_cl=i32[P,DQ], req_bad=bool[P,DQ])
# ktpu: axes(q_valid=bool[P,DQ], ref_cl=i32[P,CQ], claim_node0=i32[CL])
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16, g_cap=4)
@functools.partial(
    jax.jit,
    static_argnames=(
        "v_cap",
        "g_cap",
        "weights",
        "check_fit",
        "d_cap",
        "d2_cap",
        "fit_strategy",
    ),
)
def workloads_schedule(
    dc: DeviceCluster,
    db: DeviceBatch,
    g: gang.GangStatics,
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
    dev_key=None,
    dev_val=None,
    dev_valid=None,
    free0=None,
    sel_key=None,
    sel_op=None,
    sel_vals=None,
    req_count=None,
    req_all=None,
    req_cl=None,
    req_bad=None,
    q_valid=None,
    ref_cl=None,
    claim_node0=None,
    weights: tuple = gang.DEFAULT_WEIGHTS,
    check_fit: bool = True,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    d_cap: int = 8,
    d2_cap: int = 8,
    extra_score=None,
    fit_strategy: tuple = gang.DEFAULT_FIT_STRATEGY,
):
    """One fused workloads dispatch: speculation + gang/DRA admission scan.

    Returns (chosen [P], n_feas [P], reason_counts [P, ND], tallies,
    wl) where ``chosen`` is the POST-ROLLBACK assignment (-1 for failed
    and rolled-back pods) and ``wl`` is a dict of workload stats:
    spec [P] speculative choices, raw [P] pre-rollback admission choices,
    gang_admit [G2] (-1 unjudged / 0 rolled back / 1 admitted),
    gang_landed [G2] members placed this batch, claim_node [CL] (or the
    untouched input when the batch has no claims)."""
    P, N = g.static_mask.shape
    C = g.sp_dv.shape[1]
    AT = g.ip_dv.shape[1]
    Tsp = rep_sp_p.shape[0]
    Tip = rep_ip_p.shape[0]
    has_dra = dev_key is not None

    if nom_node is not None:
        nom_oh = (
            nom_node[:, None] == jnp.arange(N, dtype=I32)[None, :]
        ).astype(I32)  # [G, N]
    else:
        nom_oh = None

    true_n = jnp.ones((N,), bool)

    # batch-peer match tensors from the statics (the wave's gathers)
    m_sp_all, m_ip_all, t_anti, t_w = wave.term_match_rows(
        g, rep_sp_p, rep_sp_c, rep_ip_p, rep_ip_u
    )

    # the batched device-matching pass: selectors are static per batch, so
    # the full [P, DQ, N, DD] match tensor is built ONCE outside the scan
    if has_dra:
        match = dra_ops.selector_match(
            dev_key, dev_val, dev_valid, sel_key, sel_op, sel_vals
        )
    else:
        match = None

    def zero_sdyn():
        z = jnp.zeros((C, N), I32)
        return gang.SpreadDyn(z, z, z)

    def zero_idyn():
        return gang.InterpodDyn(
            jnp.zeros((AT, N), I32),
            jnp.zeros((N,), bool),
            jnp.zeros((N,), I64),
            jnp.asarray(False),
        )

    def build_hv(p, sdyn, idyn, m_extra):
        if C:
            m_spread, sp_cnt, _ = gang.spread_constraints(db, g, p, sdyn)
        else:
            m_spread = true_n
            sp_cnt = jnp.zeros((C, N), I32)
        if AT:
            m_interpod, ip_raw, _ = gang.interpod_constraints(g, p, idyn)
        else:
            m_interpod = true_n
            ip_raw = g.ip_sym[p]
        return dict(
            m_portb=m_extra,
            m_spread=m_spread,
            sp_cnt=sp_cnt,
            m_interpod=m_interpod,
            ip_raw=ip_raw,
        )

    step_kw = dict(
        check_fit=check_fit,
        weights=weights,
        d_cap=d_cap,
        fit_strategy=fit_strategy,
        extra_score=extra_score,
        nom_oh=nom_oh,
        nom_prio=nom_prio,
        nom_req=nom_req,
    )

    base = dict(
        requested=dc.requested,
        nonzero=dc.nonzero_req,
        num_pods=dc.num_pods,
        assigned=jnp.full((P,), ABSENT, I32),
    )

    def dra_mask_take(p, free, claim_node):
        if not has_dra:
            return true_n, None
        ok, take = dra_ops.node_feasible(
            match[p],
            free,
            claim_node,
            req_count[p],
            req_all[p],
            req_cl[p],
            q_valid[p],
            req_bad[p],
            ref_cl[p],
        )
        return ok, take

    # ---- pass 1: speculation against the frozen snapshot ------------------
    def spec_one(p):
        m_extra, _ = dra_mask_take(p, free0, claim_node0)
        hv = build_hv(p, zero_sdyn(), zero_idyn(), m_extra)
        _, (choice, _, _) = gang.pod_step(
            dc, db, g, p, base, hv, jnp.asarray(True), commit=False, **step_kw
        )
        return choice

    c0 = jax.vmap(spec_one)(jnp.arange(P, dtype=I32))

    # ---- pass 2: gang/DRA admission over the factored deltas ---------------
    init = dict(
        base,
        **wave.factored_carry_init(Tsp, Tip, N),
        gang_landed=jnp.asarray(0, I32),
        gang_admit=jnp.full((g_cap,), -1, I32),
        gang_landed_out=jnp.zeros((g_cap,), I32),
        # Per-pod outputs ride CARRY buffers (not scan-stacked ys):
        # jaxlib 0.4.37's SPMD partitioner mis-clamps the ys-stacking
        # dynamic_update_slice (s64 scan counter vs its s32 shard
        # arithmetic) when propagation shards the stacking axis; carry
        # scatter writes at an i32 index partition correctly.  NOT in
        # ck_keys: a rolled-back gang keeps its RAW choices recorded,
        # exactly like the ys did.
        out_raw=jnp.full((P,), ABSENT, I32),
        out_nfeas=jnp.zeros((P,), I64),
        out_rc=jnp.zeros((P, N_DIAG), I64),
    )
    ck_keys = _CK_KEYS + (_CK_DRA_KEYS if has_dra else ())
    if has_dra:
        init["free"] = free0
        init["claim_node"] = claim_node0
    for k in ck_keys:
        init["ck_" + k] = init[k]

    def step(state, p):
        in_gang = gang_id[p] >= 0
        is_first = gang_first[p] & in_gang
        # gang checkpoint: snapshot the ENTIRE carried state at the first
        # member so a failed gang restores wholesale (usage, topology
        # counts, allocation carries, assignments)
        ck = {
            k: jnp.where(is_first, state[k], state["ck_" + k])
            for k in ck_keys
        }

        if C:
            sdyn = wave.factored_spread_dyn(
                g, p, tid_sp, state["cnt_sp"], d_cap
            )
        else:
            sdyn = zero_sdyn()
        if AT:
            idyn, ip_aux = wave.factored_interpod_dyn(
                g,
                db,
                p,
                tid_ip,
                ip_cdv_tab,
                d2_cap,
                hostname_key,
                state["cnt_ip"],
                state["rev_cnt"],
                m_ip_all,
                t_anti,
                t_w,
            )
        else:
            idyn = zero_idyn()
            ip_aux = None

        if has_dra:
            m_extra, take_p = dra_mask_take(
                p, state["free"], state["claim_node"]
            )
        else:
            m_extra, take_p = true_n, None
        hv = build_hv(p, sdyn, idyn, m_extra)
        new_state, (choice, n_feas, reason_counts) = gang.pod_step(
            dc, db, g, p, state, hv, jnp.asarray(True), **step_kw
        )

        new_state.update(
            wave.factored_carry_update(
                {k: state[k] for k in ("cnt_sp", "cnt_ip", "rev_cnt")},
                p,
                choice,
                m_sp_all,
                m_ip_all,
                ip_aux,
            )
        )
        if has_dra:
            new_state["free"], new_state["claim_node"] = dra_ops.dra_commit(
                state["free"],
                state["claim_node"],
                choice,
                take_p,
                ref_cl[p],
            )

        # gang bookkeeping: landed counter resets at the first member; the
        # last member's verdict admits or rolls back the whole gang
        landed = jnp.where(is_first, 0, state["gang_landed"]) + (
            (choice >= 0) & in_gang
        ).astype(I32)
        is_last = gang_last[p] & in_gang
        fail = is_last & (landed < gang_need[p])
        for k in ck_keys:
            new_state[k] = jnp.where(fail, ck[k], new_state[k])
            new_state["ck_" + k] = ck[k]
        gid_oh = (jnp.arange(g_cap, dtype=I32) == gang_id[p]) & is_last
        new_state["gang_admit"] = jnp.where(
            gid_oh, jnp.where(fail, 0, 1), state["gang_admit"]
        )
        new_state["gang_landed_out"] = jnp.where(
            gid_oh, landed, state["gang_landed_out"]
        )
        new_state["gang_landed"] = landed
        # p in range by construction; mode="drop" for the clamp rule
        new_state["out_raw"] = state["out_raw"].at[p].set(choice, mode="drop")
        new_state["out_nfeas"] = (
            state["out_nfeas"].at[p].set(n_feas, mode="drop")
        )
        new_state["out_rc"] = (
            state["out_rc"].at[p].set(reason_counts, mode="drop")
        )
        return new_state, None

    state, _ = jax.lax.scan(step, init, jnp.arange(P, dtype=I32))
    raw = state["out_raw"]
    n_feas = state["out_nfeas"]
    reason_counts = state["out_rc"]
    tallies = {
        "requested": state["requested"],
        "nonzero": state["nonzero"],
        "num_pods": state["num_pods"],
    }
    wl = {
        "spec": c0,
        "raw": raw,
        "gang_admit": state["gang_admit"],
        "gang_landed": state["gang_landed_out"],
        "claim_node": state["claim_node"] if has_dra else claim_node0,
    }
    return state["assigned"], n_feas, reason_counts, tallies, wl


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32, extra_mask=bool[P,N])
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(gang_id=i32[P], gang_first=bool[P], gang_last=bool[P], gang_need=i32[P])
# ktpu: axes(dev_key=i32[N,DD,DA], dev_val=i32[N,DD,DA], dev_valid=bool[N,DD], free0=bool[N,DD])
# ktpu: axes(sel_key=i32[P,DQ,DS], sel_op=i32[P,DQ,DS], sel_vals=i32[P,DQ,DS,DV])
# ktpu: axes(req_count=i32[P,DQ], req_all=bool[P,DQ], req_cl=i32[P,DQ], req_bad=bool[P,DQ])
# ktpu: axes(q_valid=bool[P,DQ], ref_cl=i32[P,CQ], claim_node0=i32[CL])
# ktpu: axes(vol_table=DTable[P,PV2,VT], vol_valid=bool[P,PV2], vol_bad=bool[P])
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2])
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
def workloads_run(
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
    dev_key=None,
    dev_val=None,
    dev_valid=None,
    free0=None,
    sel_key=None,
    sel_op=None,
    sel_vals=None,
    req_count=None,
    req_all=None,
    req_cl=None,
    req_bad=None,
    q_valid=None,
    ref_cl=None,
    claim_node0=None,
    vol_table=None,
    vol_valid=None,
    vol_bad=None,
    hard_pod_affinity_weight: int = 1,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_images: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    weights: tuple = gang.DEFAULT_WEIGHTS,
    extra_mask=None,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sp_host_cdv=None,
    d_cap: int = 8,
    d2_cap: int = 8,
    extra_score=None,
    fit_strategy: tuple = gang.DEFAULT_FIT_STRATEGY,
):
    """Fused precompute + workloads admission: ONE device dispatch per
    batch (the workloads counterpart of wave.wave_run — eligibility
    guarantees no in-batch host ports, so the port axis is compiled out).
    The volume-topology kernel mask evaluates in-dispatch and folds into
    the precompute's extra mask, so volume rejections carry the host-veto
    diagnosis lane like any stateful-plugin veto."""
    if vol_table is not None:
        vmask = volume_topology_mask(dc, vol_table, vol_valid, vol_bad)
        extra_mask = vmask if extra_mask is None else (extra_mask & vmask)
    g = gang.precompute(
        dc,
        db,
        hostname_key,
        v_cap,
        hard_pod_affinity_weight,
        has_interpod=has_interpod,
        has_spread=has_spread,
        has_ports=False,
        has_images=has_images,
        enabled=enabled,
        extra_mask=extra_mask,
        sp_keys=sp_keys,
        sp_cdv_tab=sp_cdv_tab,
        ip_keys=ip_keys,
        d_cap=d_cap,
        sp_host_cdv=sp_host_cdv,
    )
    return workloads_schedule(
        dc,
        db,
        g,
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
        dev_key=dev_key,
        dev_val=dev_val,
        dev_valid=dev_valid,
        free0=free0,
        sel_key=sel_key,
        sel_op=sel_op,
        sel_vals=sel_vals,
        req_count=req_count,
        req_all=req_all,
        req_cl=req_cl,
        req_bad=req_bad,
        q_valid=q_valid,
        ref_cl=ref_cl,
        claim_node0=claim_node0,
        weights=weights,
        check_fit="NodeResourcesFit" in enabled,
        nom_node=nom_node,
        nom_prio=nom_prio,
        nom_req=nom_req,
        d_cap=d_cap,
        d2_cap=d2_cap,
        extra_score=extra_score,
        fit_strategy=fit_strategy,
    )
