"""Chained batch dispatch: gang + on-device self-append of placements.

The throughput ceiling of the batched scheduler is host↔device round
trips — with a naive loop every batch pays upload + sync +
dispatch + fetch latencies.  `chain_dispatch` removes the host from the
inter-batch critical path: one jit call runs the gang pipeline AND splices
the batch's own committed pods (rows + flattened affinity terms, the device
analogue of schema.append_existing_pods) into the donated DeviceCluster, so
the NEXT batch can dispatch against the returned cluster immediately —
before this batch's results have even been fetched.  The scheduling loop
becomes a software pipeline: dispatch batch k+1, then harvest batch k.

Consistency model (matches the reference's assume-until-forget,
cache.go:360-422): in-flight batches see every earlier batch's placements
as assumed pods.  Anything the device can't see — informer events, bind
failures (forget), fast-path or one-pod commits — breaks the chain via the
scheduler's epoch check, forcing a fresh host upload; decisions made by
batches already in flight used the pre-event snapshot, exactly like
reference scheduling cycles racing an informer update.

Layout note: unlike the host packer, the device append keeps each pod's
term rows at a fixed stride (P·AT rows per batch, PAD rows for empty term
slots).  Term evaluation is row-order independent and gated on
term_kind/epod_valid, so PAD gaps are inert; they only consume term-row
capacity, which the capacity check in the scheduler guards.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.common import DTable, DeviceBatch, DeviceCluster, I32
from kubernetes_tpu.snapshot.interner import ABSENT, PAD


def _dus(full, delta, start):
    start = jnp.asarray(start, I32)
    zero = jnp.zeros((), I32)
    starts = (start,) + (zero,) * (full.ndim - 1)
    # ktpu: allow(slice-clamp) — e_cursor/m_cursor are host ints checked
    # against the CHAINED cluster's own capacity before every dispatch
    # (scheduler._chain_dispatch: `ch["e"] + P > E or ch["m"] + P*AT > M`
    # compacts-and-grows or falls back to the direct path), so start +
    # delta rows <= len(full) holds for every splice XLA ever sees
    return jax.lax.dynamic_update_slice(full, delta, starts)


def _pad_axis(x, axis, target, fill):
    cur = x.shape[axis]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=fill)


def caps_compatible(dc_shapes, pb) -> bool:
    """Host-side check that the batch's term tables fit the cluster's row
    width (else the append would truncate selector conjunctions)."""
    (Rc, Vc, NSc, Kc) = dc_shapes
    bt = pb.aff_table
    return (
        bt.req_key.shape[2] <= Rc
        and bt.req_vals.shape[3] <= Vc
        and pb.aff_ns_ids.shape[2] <= NSc
        and pb.label_vals.shape[1] == Kc
    )


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32, e_cursor=i32, m_cursor=i32)
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2])
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(tid_pt=i32[P,UP], port_conf=bool[Tpt,Tpt])
# ktpu: axes(sig=i32[P], rep_pod=i32[U])
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16)
# ktpu: noinstantiate — donates and splices the cluster at host-checked
#   cursors; the representative instantiation would need a consistent
#   (e_cursor, m_cursor, capacity) triple the schema cannot express
@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=(
        "v_cap",
        "hard_pod_affinity_weight",
        "has_interpod",
        "has_spread",
        "has_ports",
        "has_images",
        "enabled",
        "weights",
        "d_cap",
        "d2_cap",
        "append_terms",
        "fit_strategy",
        "wave",
        "wave_ports",
    ),
)
def chain_dispatch(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    e_cursor,
    m_cursor,
    v_cap: int,
    hard_pod_affinity_weight: int = 1,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_ports: bool = True,
    has_images: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    weights: tuple = gang.DEFAULT_WEIGHTS,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sp_host_cdv=None,
    d_cap: int = 8,
    append_terms: bool = True,
    fit_strategy: tuple = gang.DEFAULT_FIT_STRATEGY,
    wave: bool = False,
    tid_sp=None,
    rep_sp_p=None,
    rep_sp_c=None,
    tid_ip=None,
    rep_ip_p=None,
    rep_ip_u=None,
    ip_cdv_tab=None,
    d2_cap: int = 8,
    wave_ports: bool = False,
    tid_pt=None,
    port_conf=None,
    sig=None,
    rep_pod=None,
):
    """One fused dispatch: gang schedule the batch, then append its
    committed pods into the (donated) cluster at the given cursors.

    ``append_terms=False`` skips the term-row splice for batches with no
    affinity terms — the bucketed AT axis would otherwise burn P·AT PAD
    rows of term capacity per batch.

    ``wave=True`` schedules via the speculative wave (ops/wave.py: one
    parallel speculation pass + the term-factored admission pass) instead
    of the gang scan — same decisions, a fraction of the per-step cost —
    and appends a fourth output: the [3, P] wave stats block.
    ``wave_ports`` compiles in the wave's [Tpt, N] port-occupancy carry
    for batches with in-batch host ports (tid_pt/port_conf from
    wave_tables).  NOT YET REACHABLE from the scheduler: the chained
    router refuses port batches outright because the device append below
    does not splice committed pods' port rows into used_ppk, so a LATER
    chained batch would miss their conflicts (scheduler._chain_quickcheck)
    — port batches take the direct wave instead.  The plumbing keeps the
    wave call signature uniform and is the landing slot for a future
    port-row splice.

    ``sig`` / ``rep_pod`` (wave.static_signatures; None = every pod its
    own row, the per-pod program): the statics are computed once a
    distinct pod row and read per pod (gang.precompute).

    Returns (next_dc, stacked [2, P] (chosen, n_feas), reason_counts
    [, wave_stats])."""
    g = gang.precompute(
        dc,
        db,
        hostname_key,
        v_cap,
        hard_pod_affinity_weight,
        has_interpod=has_interpod,
        has_spread=has_spread,
        # the wave never reads the scan's pod×pod port matrix — in-batch
        # ports ride its factored [Tpt, N] occupancy carry instead
        has_ports=has_ports and not wave,
        has_images=has_images,
        enabled=enabled,
        sp_keys=sp_keys,
        sp_cdv_tab=sp_cdv_tab,
        ip_keys=ip_keys,
        d_cap=d_cap,
        sp_host_cdv=sp_host_cdv,
        sig=sig,
        rep_pod=rep_pod,
    )
    wave_stats = None
    if wave:
        from kubernetes_tpu.ops import wave as wave_ops

        chosen, n_feas, reason_counts, tallies, wave_stats = (
            wave_ops.wave_schedule(
                dc,
                db,
                g,
                hostname_key,
                v_cap,
                tid_sp,
                rep_sp_p,
                rep_sp_c,
                tid_ip,
                rep_ip_p,
                rep_ip_u,
                ip_cdv_tab,
                weights=weights,
                check_fit="NodeResourcesFit" in enabled,
                nom_node=nom_node,
                nom_prio=nom_prio,
                nom_req=nom_req,
                d_cap=d_cap,
                d2_cap=d2_cap,
                fit_strategy=fit_strategy,
                has_ports=wave_ports,
                tid_pt=tid_pt,
                port_conf=port_conf,
            )
        )
    else:
        chosen, n_feas, reason_counts, tallies = gang.gang_schedule(
            dc,
            db,
            g,
            v_cap,
            weights=weights,
            check_fit="NodeResourcesFit" in enabled,
            nom_node=nom_node,
            nom_prio=nom_prio,
            nom_req=nom_req,
            d_cap=d_cap,
            fit_strategy=fit_strategy,
        )
    with jax.named_scope("ktpu/chain/append"):
        P = db.valid.shape[0]
        committed = (chosen >= 0) & db.valid
        upd = dict(
            requested=tallies["requested"],
            nonzero_req=tallies["nonzero"],
            num_pods=tallies["num_pods"],
            epod_node=_dus(
                dc.epod_node, jnp.where(committed, chosen, ABSENT), e_cursor
            ),
            epod_ns=_dus(dc.epod_ns, db.ns_id, e_cursor),
            epod_labels=_dus(dc.epod_labels, db.labels, e_cursor),
            epod_valid=_dus(dc.epod_valid, committed, e_cursor),
            epod_deleting=_dus(dc.epod_deleting, jnp.zeros((P,), bool), e_cursor),
        )
        AT = db.aff_kind.shape[1]
        if AT and append_terms:
            real = db.aff_kind != PAD  # [P, AT]
            pod_idx = e_cursor + jnp.arange(P, dtype=I32)[:, None]
            term_pod = jnp.where(real, pod_idx, ABSENT).reshape(P * AT)
            tt = dc.term_table
            Rc = tt.req_key.shape[2]
            Vc = tt.req_vals.shape[3]
            NSc = dc.term_ns_ids.shape[1]
            bt = db.aff_table
            rk = _pad_axis(bt.req_key.reshape(P * AT, 1, -1), 2, Rc, PAD)
            ro = _pad_axis(bt.req_op.reshape(P * AT, 1, -1), 2, Rc, PAD)
            rr = _pad_axis(bt.req_rhs.reshape(P * AT, 1, -1), 2, Rc, 0)
            rv = bt.req_vals.reshape(
                P * AT, 1, bt.req_vals.shape[2], bt.req_vals.shape[3]
            )
            rv = _pad_axis(_pad_axis(rv, 3, Vc, PAD), 2, Rc, PAD)
            upd.update(
                term_pod=_dus(dc.term_pod, term_pod, m_cursor),
                term_kind=_dus(dc.term_kind, db.aff_kind.reshape(P * AT), m_cursor),
                term_topo=_dus(dc.term_topo, db.aff_topo.reshape(P * AT), m_cursor),
                term_weight=_dus(
                    dc.term_weight, db.aff_weight.reshape(P * AT), m_cursor
                ),
                term_ns_all=_dus(
                    dc.term_ns_all, db.aff_ns_all.reshape(P * AT), m_cursor
                ),
                term_ns_ids=_dus(
                    dc.term_ns_ids,
                    _pad_axis(db.aff_ns_ids.reshape(P * AT, -1), 1, NSc, PAD),
                    m_cursor,
                ),
                term_table=DTable(
                    req_key=_dus(tt.req_key, rk, m_cursor),
                    req_op=_dus(tt.req_op, ro, m_cursor),
                    req_vals=_dus(tt.req_vals, rv, m_cursor),
                    req_rhs=_dus(tt.req_rhs, rr, m_cursor),
                    term_valid=_dus(
                        tt.term_valid, bt.term_valid.reshape(P * AT, 1), m_cursor
                    ),
                ),
            )
    next_dc = replace(dc, **upd)
    results = jnp.stack([chosen, n_feas])
    if wave:
        return next_dc, results, reason_counts, wave_stats
    return next_dc, results, reason_counts
