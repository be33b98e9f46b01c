"""Speculative wave dispatch for cross-pod-constraint batches.

Pods with PodTopologySpread / inter-pod-affinity terms pay a serial data
dependency: each placement mutates the topology counts the next pod's
verdict reads, so the gang scan (ops/gang.py) re-derives every pod's
batch-peer counts from the full ``[C, N, J]`` / ``[AT, N, J]`` peer
contractions, once per scan step.  That per-step volume — not the verdict
math — is what makes the spread/inter-pod configs the slowest lines in the
bench.  This module replaces it with a two-pass wave:

  1. **Speculation** — the entire wave is evaluated as one parallel
     ``(P × N)`` pass against the FROZEN snapshot (zero intra-batch
     deltas): a vmap of the shared per-pod verdict (gang.pod_step), giving
     every pod a candidate placement as if it were first in line.

  2. **Conflict resolution / admission** — a device-side pass that
     recomputes each pod's verdict and argmax under the wave's combined
     usage + topology-count deltas, in queue order.  Its carried state is
     NOT the peer list but a **term-factored delta algebra**: the host
     interaction partitioner dedups the batch's constraint terms into
     ``T ≪ P`` distinct (selector, namespace, topology-key) terms, and the
     pass carries per-term per-node counts ``[T, N]`` (+ per-term
     domain-spread rows for the symmetric inter-pod direction).  Each
     step's batch-peer counts come from ``[C, N, d_cap]``-shaped dense
     compare+reduce over those carries — O(T·N + C·N·D) per pod instead of
     O((C+AT)·N·J) — and commits update the carries with dense rank-1
     outer products (no scatters).

**Admission invariant.**  The admission pass replays the exact serial
recurrence ``choice_i = F_i(S + Σ_{j<i} Δ(choice_j))`` — the unique fixed
point of the wave's combined-delta re-evaluation — so its placements are
bit-identical to processing the wave's pods one at a time in queue order
(the parity oracle's order).  A pod whose speculative candidate survives
the recomputation is **admitted as speculated**; a pod whose candidate is
invalidated by the wave's combined deltas is **demoted** — its corrected
placement still lands in the same dispatch (the next "wave" of the fixed
point is evaluated in place), and the demotion is surfaced to the host
with the conflicting constraint kind + term for the flight recorder /
wave-conflict metrics.  Fully disjoint footprints admit the whole wave at
its speculative placements; fully shared footprints degenerate to the
serial recurrence — exactly the gang scan's semantics at a fraction of its
per-step cost.

**Fallback ladder.**  The factored algebra expresses the whole hot path:
in-batch host-port users ride a dedicated ``[Tpt, N]`` port-occupancy
carry (distinct (proto, port, hostIP-class) tuples dedup into ``Tpt ≪ P``
port terms whose pairwise conflicts are a static host-built matrix), and
sampling-compat / seeded-tie drains replay ``numFeasibleNodesToFind``'s
adaptive window and nodeTree rotation per step (the sampling cut lives in
gang.pod_step and is carry-state, not peer-state, so the factored pass
reproduces it bit-exactly).  What remains off the wave: host-filter-
relevant, extender, and nominated pods take the one-pod paths;
resource-only batches never get here (the signature fast path owns them);
duplicate hostname label values (two nodes claiming one hostname)
disqualify the wave — the factored hostname-topology counts assume node
identity ≡ hostname domain (the uniqueness bit is computed once per
snapshot by the mirror, not per batch).  Every fallback bumps
``scheduler_tpu_wave_fallback_total{reason=}``.

The verdict itself — filters, scores, normalization, tie-break — is the
SAME code as the scan path (gang.pod_step + gang.spread_constraints +
gang.interpod_constraints), so the paths cannot drift: only the production
of the batch-peer count tensors differs.  Equivalence is property-tested
against both gang_schedule and the serial oracle in tests/test_wave.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.gang import N_DIAG
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32, I64
from kubernetes_tpu.snapshot.interner import ABSENT, PAD
from kubernetes_tpu.snapshot.schema import N_FIXED_LANES, bucket_cap

# demote_kind codes in the wave stats row (host side maps to labels)
DEMOTE_NONE = 0
DEMOTE_SPREAD = 1
DEMOTE_AFFINITY = 2
DEMOTE_SCORE = 3
DEMOTE_FIT = 4
# not a demotion: infeasible in speculation, PLACED by the admission pass
# (a batch peer's commit satisfied a required affinity) — the wave upgraded
# the pod; reported separately, never as a conflict
DEMOTE_UPGRADE = 5
DEMOTE_PORTS = 6
DEMOTE_KINDS = {
    DEMOTE_SPREAD: "spread",
    DEMOTE_AFFINITY: "affinity",
    DEMOTE_SCORE: "score",
    DEMOTE_FIT: "fit",
    DEMOTE_PORTS: "ports",
}

# shard-rule roster: the admission scan's per-step work contracts the
# factored [T, N] carries over N ([C, N, d_cap] compare+reduce) and
# gathers the speculative node's row for demotion attribution.  These
# are the per-term reductions ROADMAP item 2 reduces ACROSS shards —
# the roster is the inventory of exactly where those collectives go.
_KTPU_N_COLLECTIVES = {
    "wave_schedule.step": "resolved(collective): term-factored domain "
    "compare+reduce over N + port-occupancy [Tpt, N] conflict reduce + "
    "speculative-node row gathers (demotion attribution) — the per-term "
    "[T,N]/[Tpt,N] carry counts are per-node integers that reduce "
    "cleanly across a sharded N axis: per-shard partial compare+psum at "
    "the conflict check, owning-shard gather for the speculative row, "
    "and rank-1 carry commits stay local to the shard that owns the "
    "committed node",
    "factored_port_mask": "resolved(collective): port-term occupancy "
    "conflict reduce over the carried [Tpt, N] rows — per-shard partial "
    "conflict bits + cross-shard or-reduce",
}


# ---------------------------------------------------------------------------
# Host-side interaction partitioner
# ---------------------------------------------------------------------------


def _dedup_slots(mat, live):
    """Row-dedup of a [S, W] content matrix over live slots.

    Returns (tid [S] i64 with -1 for dead slots, rep [T] flat indices of
    one representative live slot per distinct row).  Term ids follow
    np.unique's sorted row order — deterministic across hosts."""
    import numpy as np

    tid = np.full(mat.shape[0], -1, np.int64)
    if not live.any():
        return tid, np.zeros((0,), np.int64)
    rows = np.ascontiguousarray(mat[live])
    _, first, inv = np.unique(
        rows, axis=0, return_index=True, return_inverse=True
    )
    live_idx = np.nonzero(live)[0]
    tid[live_idx] = inv.reshape(-1)
    return tid, live_idx[first]


def _slot_content(n_slots, parts):
    """Stack per-slot content columns into one [n_slots, W] i64 matrix."""
    import numpy as np

    cols = [np.asarray(p, np.int64).reshape(n_slots, -1) for p in parts]
    return np.concatenate(cols, axis=1)


def wave_tables(
    pb, node_label_vals, hostname_id: int, hostnames_unique=None, t_floor=(1, 1, 1)
):
    """Dedup the batch's constraint terms into distinct-term tables — the
    host half of the interaction partitioner.

    Two pods share a spread term when (topology key, namespace, packed
    selector) coincide — then their batch-peer counts are the same counter;
    inter-pod terms additionally key on (kind, weight, namespace scope), so
    a term's symmetric weight and violation polarity are term constants.
    In-batch host ports dedup the same way: distinct (proto-port key,
    hostIP, wildcard) tuples become ``Tpt`` port terms with a static
    pairwise conflict matrix, so the admission pass carries per-term
    occupancy instead of the gang scan's pod×pod conflict matrix.

    Returns None only when the batch is not wave-eligible: duplicate
    hostname label values among nodes (the factored hostname-domain counts
    assume hostname ≡ node identity).  ``hostnames_unique`` is the
    once-per-snapshot bit from SnapshotMirror.hostnames_unique; None
    re-derives it here (standalone/test callers).  ``t_floor``: the least
    (spread, inter-pod, port) term buckets — a caller that keeps each at the
    largest it has met (``t_caps`` of the batches before) compiles ONE
    admission program a bucket it grows into, not one a batch whose count of
    distinct terms falls in a smaller bucket (a drain of many Deployments:
    WAVE.md "Many terms a batch").  Otherwise a dict of device-ready arrays
    + static caps:

      tid_sp  i32 [P, C]   distinct spread-term id per slot (-1 empty)
      rep_sp_p/rep_sp_c  i32 [Tsp]  a representative slot per term
      tid_ip  i32 [P, AT]  distinct inter-pod-term id per slot
      rep_ip_p/rep_ip_u  i32 [Tip]
      ip_cdv_tab i32 [Kd2, N]  compact domain ids per inter-pod topology
                 key (row of -1 for the hostname key: identity domains)
      d2_cap  int  static bucket over inter-pod distinct-domain counts
      tid_pt  i32 [P, W]   distinct port-term id per want slot (-1 empty)
      port_conf bool [Tpt, Tpt]  static term-pair conflict matrix
      has_ports bool       batch carries in-batch host ports
      n_terms int  total distinct terms (spread + inter-pod + port)
      t_caps  (Tsp, Tip, Tpt)  the term buckets the tables were built at
    """
    import numpy as np

    lv = np.asarray(node_label_vals)
    n_cap, K = lv.shape
    if hostnames_unique is None and 0 <= hostname_id < K:
        col = lv[:, hostname_id]
        vals = col[col >= 0]
        hostnames_unique = len(vals) == len(np.unique(vals))
    if hostnames_unique is False:
        return None  # duplicate hostname labels: identity trick invalid

    P, C = np.asarray(pb.tsc_topo_key).shape
    AT = np.asarray(pb.aff_kind).shape[1]
    ns_id = np.asarray(pb.ns_id)
    tsc_topo = np.asarray(pb.tsc_topo_key)
    aff_kind = np.asarray(pb.aff_kind)
    valid = np.asarray(pb.valid)

    # distinct spread terms: (topology key, pod namespace, packed selector)
    if C:
        sp_content = _slot_content(
            P * C,
            [
                tsc_topo,
                np.broadcast_to(ns_id[:, None], (P, C)),
                pb.tsc_table.req_key,
                pb.tsc_table.req_op,
                pb.tsc_table.req_vals,
                pb.tsc_table.req_rhs,
                pb.tsc_table.term_valid,
            ],
        )
        sp_live = (tsc_topo != PAD).reshape(-1) & np.repeat(valid, C)
        tid_flat, rep_flat = _dedup_slots(sp_content, sp_live)
    else:
        tid_flat = np.zeros((0,), np.int64)
        rep_flat = np.zeros((0,), np.int64)
    tid_sp = tid_flat.reshape(P, C).astype(np.int32)
    t_sp = bucket_cap(len(rep_flat), t_floor[0])
    rep_sp_p = np.full(t_sp, -1, np.int32)
    rep_sp_c = np.zeros(t_sp, np.int32)
    rep_sp_p[: len(rep_flat)] = rep_flat // C if C else 0
    rep_sp_c[: len(rep_flat)] = rep_flat % C if C else 0
    n_sp = len(rep_flat)

    # distinct inter-pod terms: kind/weight/ns-scope are part of the
    # identity so a term's symmetric weight and polarity are constants
    if AT:
        ip_content = _slot_content(
            P * AT,
            [
                aff_kind,
                pb.aff_topo_key,
                pb.aff_weight,
                pb.aff_ns_all,
                pb.aff_ns_ids,
                pb.aff_table.req_key,
                pb.aff_table.req_op,
                pb.aff_table.req_vals,
                pb.aff_table.req_rhs,
                pb.aff_table.term_valid,
            ],
        )
        ip_live = (aff_kind != PAD).reshape(-1) & np.repeat(valid, AT)
        tid_flat, rep_flat = _dedup_slots(ip_content, ip_live)
    else:
        tid_flat = np.zeros((0,), np.int64)
        rep_flat = np.zeros((0,), np.int64)
    tid_ip = tid_flat.reshape(P, AT).astype(np.int32)
    t_ip = bucket_cap(len(rep_flat), t_floor[1])
    rep_ip_p = np.full(t_ip, -1, np.int32)
    rep_ip_u = np.zeros(t_ip, np.int32)
    rep_ip_p[: len(rep_flat)] = rep_flat // AT if AT else 0
    rep_ip_u[: len(rep_flat)] = rep_flat % AT if AT else 0
    n_ip = len(rep_flat)

    # distinct port terms: (proto-port key, hostIP, wildcard) — the same
    # content identity node_ports.go compares; the pairwise conflict rule
    # (same proto-port ∧ (same IP ∨ either wildcard)) is evaluated ONCE
    # over the Tpt ≪ P·W distinct tuples instead of per pod pair
    want_ppk = np.asarray(pb.want_ppk)
    W = want_ppk.shape[1]
    n_pt = 0
    t_pt = 1
    if W and (want_ppk != PAD).any():
        pt_content = _slot_content(
            P * W, [want_ppk, pb.want_ip, pb.want_wild]
        )
        pt_live = (want_ppk != PAD).reshape(-1) & np.repeat(valid, W)
        tid_flat, rep_flat = _dedup_slots(pt_content, pt_live)
        tid_pt = tid_flat.reshape(P, W).astype(np.int32)
        n_pt = len(rep_flat)
        t_pt = bucket_cap(n_pt, t_floor[2])
        r_ppk = want_ppk.reshape(-1)[rep_flat]
        r_ip = np.asarray(pb.want_ip).reshape(-1)[rep_flat]
        r_wild = np.asarray(pb.want_wild).reshape(-1)[rep_flat]
        port_conf = np.zeros((t_pt, t_pt), bool)
        port_conf[:n_pt, :n_pt] = (r_ppk[:, None] == r_ppk[None, :]) & (
            (r_ip[:, None] == r_ip[None, :])
            | r_wild[:, None]
            | r_wild[None, :]
        )
    else:
        tid_pt = np.full((P, W), -1, np.int32)
        port_conf = np.zeros((1, 1), bool)

    # Compact per-key domain ids for the inter-pod keys, batch_tables-style
    # (same distinct-key ordering as gang.batch_tables so g.ip_key_idx rows
    # index both tables).  The hostname key keeps a -1 row: its domains are
    # node identities and never ride the [.., d2_cap] compare+reduce.
    ip_keys = np.unique(np.asarray(pb.aff_topo_key).reshape(-1))
    ip_keys = [int(k) for k in ip_keys if 0 <= int(k) < K]
    kd2 = bucket_cap(max(len(ip_keys), 1), 1)
    ip_cdv_tab = np.full((kd2, n_cap), -1, np.int32)
    d2_max = 1
    for i, k in enumerate(ip_keys):
        if k == hostname_id:
            continue
        col = lv[:, k]
        pos = col >= 0
        if pos.any():
            uniq, inv = np.unique(col[pos], return_inverse=True)
            ip_cdv_tab[i, pos] = inv.astype(np.int32)
            d2_max = max(d2_max, len(uniq))

    return dict(
        tid_sp=jnp.asarray(tid_sp),
        rep_sp_p=jnp.asarray(rep_sp_p),
        rep_sp_c=jnp.asarray(rep_sp_c),
        tid_ip=jnp.asarray(tid_ip),
        rep_ip_p=jnp.asarray(rep_ip_p),
        rep_ip_u=jnp.asarray(rep_ip_u),
        ip_cdv_tab=jnp.asarray(ip_cdv_tab),
        d2_cap=bucket_cap(d2_max, 8),
        tid_pt=jnp.asarray(tid_pt),
        port_conf=jnp.asarray(port_conf),
        has_ports=n_pt > 0,
        n_terms=n_sp + n_ip + n_pt,
        t_caps=(t_sp, t_ip, t_pt),
    )


# The ONE bucket of distinct pod rows a batch's statics are computed for
# (bucket_cap's minimum).  Not 1: a drain's last batch is padded, and the
# padding rows (valid False) are a second row.  One bucket = at most one
# more program a batch size; past it the dispatch is the per-pod program.
STATIC_SIG_CAP = 8


def batch_leaves(obj):
    """Every numpy leaf of a packed batch, nested tables included, in
    field order (the ``pods`` list is not an array and is skipped)."""
    import dataclasses

    import numpy as np

    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if isinstance(v, np.ndarray):
            yield v
        elif dataclasses.is_dataclass(v):
            yield from batch_leaves(v)


def static_signatures(pb, u_cap: int = STATIC_SIG_CAP):
    """Dedup the batch's PODS by everything gang.precompute reads of one:
    the pod's row of EVERY array leaf of the packed batch (not a
    hand-picked list: a forgotten field is a wrong decision, one too many
    only costs a signature).  Two pods with equal rows have equal statics,
    so the device computes them for one representative row a signature.

    Returns None when the batch has more than ``u_cap`` distinct rows
    (the caller dispatches the per-pod program), else

      sig      i32 [P]      each pod's distinct-row id
      rep_pod  i32 [u_cap]  one representative pod a signature (-1 padded)
      n_valid  int          distinct rows among the VALID pods
    """
    import numpy as np

    valid = np.asarray(pb.valid, bool)
    P = valid.shape[0]
    # rows compared as BYTES, each leaf in its own dtype (one memcmp a
    # pair; _dedup_slots' sorted i64 rows cost ten times as much, and a
    # signature id, unlike a term id, is never shown to anyone)
    rows = np.concatenate(
        [
            np.ascontiguousarray(a).reshape(P, -1).view(np.uint8)
            for a in batch_leaves(pb)
        ],
        axis=1,
    )
    keys = rows.view(np.dtype((np.void, rows.shape[1]))).reshape(-1)
    _, rep, sig = np.unique(keys, return_index=True, return_inverse=True)
    if len(rep) > u_cap:
        return None
    rep_pod = np.full(u_cap, -1, np.int32)
    rep_pod[: len(rep)] = rep
    return dict(
        sig=jnp.asarray(sig.astype(np.int32)),
        rep_pod=jnp.asarray(rep_pod),
        n_valid=len(np.unique(sig[valid])),
    )


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _rep_rows(mat, rp, rc):
    """mat[rp, rc] with -1 representatives masked to zeros/False."""
    safe_p = jnp.clip(rp, 0, mat.shape[0] - 1)
    safe_c = jnp.clip(rc, 0, mat.shape[1] - 1)
    rows = mat[safe_p, safe_c]
    live = rp >= 0
    if rows.dtype == jnp.bool_:
        return rows & live.reshape(live.shape + (1,) * (rows.ndim - 1))
    return rows * live.reshape(live.shape + (1,) * (rows.ndim - 1)).astype(
        rows.dtype
    )


# ---------------------------------------------------------------------------
# The term-factored delta algebra, factored out of the admission scan so
# every serial-recurrence replayer shares ONE definition: wave_schedule's
# conflict-resolution pass below and the workloads tier's gang/DRA
# admission scan (ops/coscheduling.py) produce pod p's batch-peer count
# tensors from the SAME [T, N] carries and commit them through the SAME
# factored_carry_update entry point (whose usage-row twin is
# common.usage_carry_update, called from gang.pod_step) — the paths
# cannot drift.
# ---------------------------------------------------------------------------


def term_match_rows(g, rep_sp_p, rep_sp_c, rep_ip_p, rep_ip_u):
    """Per-dispatch gathers from the statics: which batch pods each
    distinct term matches (the forward AND reverse match matrix —
    ip_bmatch[p,u,j] reads "pod j matches p's term u", so one gather
    serves both sides).  Shared by the wave and workloads admission
    scans.  Returns (m_sp_all [Tsp,P], m_ip_all [Tip,P], t_anti [Tip],
    t_w [Tip] i64)."""
    P = g.static_mask.shape[0]
    C = g.sp_dv.shape[1]
    AT = g.ip_dv.shape[1]
    Tsp = rep_sp_p.shape[0]
    Tip = rep_ip_p.shape[0]
    if C:
        m_sp_all = _rep_rows(g.sp_bmatch, rep_sp_p, rep_sp_c)
    else:
        m_sp_all = jnp.zeros((Tsp, P), bool)
    if AT:
        m_ip_all = _rep_rows(g.ip_bmatch, rep_ip_p, rep_ip_u)
        t_anti = _rep_rows(g.ip_is_anti, rep_ip_p, rep_ip_u)
        t_w = _rep_rows(g.ip_sym_w, rep_ip_p, rep_ip_u)
    else:
        m_ip_all = jnp.zeros((Tip, P), bool)
        t_anti = jnp.zeros((Tip,), bool)
        t_w = jnp.zeros((Tip,), I64)
    return m_sp_all, m_ip_all, t_anti, t_w


def factored_carry_init(Tsp, Tip, N, Tpt=0):
    """Zero factored carries for one admission scan.  Keys present in the
    returned dict are exactly the keys factored_carry_update advances —
    callers thread them through their scan state wholesale."""
    out = dict(
        cnt_sp=jnp.zeros((Tsp, N), I32),
        cnt_ip=jnp.zeros((Tip, N), I32),
        rev_cnt=jnp.zeros((Tip, N), I32),
    )
    if Tpt:
        out["occ_pt"] = jnp.zeros((Tpt, N), I32)
    return out


FACTORED_CARRY_KEYS = ("cnt_sp", "cnt_ip", "rev_cnt", "occ_pt")


def factored_port_mask(tid_pt, port_conf, occ_pt, p):
    """NodePorts verdict for pod p from the factored port-occupancy carry.

    tid_pt [P, W] maps p's want slots onto distinct port-term ids;
    port_conf [Tpt, Tpt] is the static term-pair conflict matrix;
    occ_pt [Tpt, N] carries committed-peer port occupancy.  Returns
    (m_portb [N], pt_cnt [Tpt] — p's own per-term slot counts, the aux
    factored_carry_update commits)."""
    Tpt = occ_pt.shape[0]
    tidw = tid_pt[p]  # [W]
    ohw = (
        (tidw[:, None] == jnp.arange(Tpt, dtype=I32)[None, :])
        & (tidw >= 0)[:, None]
    )  # [W, Tpt]
    mine = jnp.any(ohw, axis=0)  # [Tpt] terms p requests
    conf_p = jnp.any(mine[:, None] & port_conf, axis=0)  # [Tpt]
    blocked = jnp.any(conf_p[:, None] & (occ_pt > 0), axis=0)  # [N]
    # dtype pinned: an i32 sum promotes to i64 under x64, which would
    # drift the occ_pt carry's dtype across scan steps
    return ~blocked, jnp.sum(ohw.astype(I32), axis=0).astype(I32)


def factored_spread_dyn(g, p, tid_sp, cnt_sp, d_cap: int):
    """SpreadDyn for pod p from the factored spread carries.

    tid_sp [P, C] maps p's constraint slots onto distinct-term ids;
    cnt_sp [Tsp, N] carries per-term committed-peer counts."""
    Tsp = cnt_sp.shape[0]
    d_ids = jnp.arange(d_cap, dtype=I32)
    tid = tid_sp[p]  # [C]
    ohc = (
        (tid[:, None] == jnp.arange(Tsp, dtype=I32)[None, :])
        & (tid >= 0)[:, None]
    ).astype(I32)
    cnt_rows = jnp.einsum("ct,tn->cn", ohc, cnt_sp)  # [C,N]
    te = g.sp_te[p].astype(I32)
    cting = g.sp_counting[p].astype(I32)
    cdv = g.sp_cdv[p]
    dom_oh = (
        (cdv[:, :, None] == d_ids[None, None, :])
        & (cdv >= 0)[:, :, None]
    ).astype(I32)  # [C, N, D]
    g1 = jnp.einsum("cn,cnd->cd", cnt_rows * te, dom_oh)
    g2 = jnp.einsum("cn,cnd->cd", cnt_rows * cting, dom_oh)
    dyn_f_dom = jnp.einsum("cd,cnd->cn", g1, dom_oh)
    dyn_dom = jnp.einsum("cd,cnd->cn", g2, dom_oh)
    present = (g.sp_dv[p] >= 0).astype(I32)
    dyn_f = jnp.where(
        g.sp_is_host[p][:, None], cnt_rows * te * present, dyn_f_dom
    )
    return gang.SpreadDyn(dyn_f, cnt_rows, dyn_dom)


def factored_interpod_dyn(
    g,
    db,
    p,
    tid_ip,
    ip_cdv_tab,
    d2_cap: int,
    hostname_key,
    cnt_ip,
    rev_cnt,
    m_ip_all,
    t_anti,
    t_w,
):
    """InterpodDyn for pod p from the factored inter-pod carries, plus the
    aux tuple factored_carry_update needs to spread p's own committed terms
    over their topology domains (ohu, cdv2, dvip, is_host_u, ki)."""
    Tip = cnt_ip.shape[0]
    Kd2 = ip_cdv_tab.shape[0]
    d2_ids = jnp.arange(d2_cap, dtype=I32)
    tidu = tid_ip[p]  # [AT]
    ohu = (
        (tidu[:, None] == jnp.arange(Tip, dtype=I32)[None, :])
        & (tidu >= 0)[:, None]
    ).astype(I32)
    fcnt = jnp.einsum("ut,tn->un", ohu, cnt_ip)  # [AT,N]
    ki = g.ip_key_idx[p]  # [AT]
    cdv2 = ip_cdv_tab[jnp.clip(ki, 0, Kd2 - 1)]  # [AT, N]
    cdv2 = jnp.where((ki >= 0)[:, None], cdv2, -1)
    dom2 = (
        (cdv2[:, :, None] == d2_ids[None, None, :])
        & (cdv2 >= 0)[:, :, None]
    ).astype(I32)  # [AT, N, D2]
    gf = jnp.einsum("un,und->ud", fcnt, dom2)
    ip_dyn_dom = jnp.einsum("ud,und->un", gf, dom2)
    dvip = g.ip_dv[p]
    is_host_u = db.aff_topo[p] == hostname_key  # [AT]
    ip_dyn = jnp.where(
        is_host_u[:, None], fcnt * (dvip >= 0).astype(I32), ip_dyn_dom
    )
    any_dyn = jnp.any(g.ip_is_aff[p] & (jnp.sum(fcnt, axis=1) > 0))
    m_rev = m_ip_all[:, p]  # [Tip]
    viol_b = jnp.any(
        (m_rev & t_anti)[:, None] & (rev_cnt > 0), axis=0
    )
    sym_b = jnp.sum(
        jnp.where(
            m_rev[:, None],
            t_w[:, None] * rev_cnt.astype(I64),
            0,
        ),
        axis=0,
    )
    idyn = gang.InterpodDyn(ip_dyn, viol_b, sym_b, any_dyn)
    return idyn, (ohu, cdv2, dvip, is_host_u, ki)


def factored_carry_update(
    carries, p, choice, m_sp_all, m_ip_all, ip_aux, pt_cnt=None
):
    """Commit pod p's placement into the factored carries — THE shared
    carry-update entry point of every factored admission scan (the wave's
    conflict-resolution pass and the workloads gang/DRA scan): dense
    rank-1 outer products, no scatters.  ``carries`` holds the keys
    factored_carry_init produced; ``ip_aux`` is factored_interpod_dyn's
    aux tuple (None when the batch carries no inter-pod terms) and
    ``pt_cnt`` factored_port_mask's per-term slot counts (None when the
    batch carries no in-batch host ports)."""
    cnt_sp = carries["cnt_sp"]
    cnt_ip = carries["cnt_ip"]
    rev_cnt = carries["rev_cnt"]
    N = cnt_sp.shape[1]
    n_ids = jnp.arange(N, dtype=I32)
    committed = choice >= 0
    onehot_n = ((n_ids == choice) & committed).astype(I32)
    out = dict(
        cnt_sp=cnt_sp + m_sp_all[:, p, None].astype(I32) * onehot_n[None, :],
        cnt_ip=cnt_ip + m_ip_all[:, p, None].astype(I32) * onehot_n[None, :],
        rev_cnt=rev_cnt,
    )
    if pt_cnt is not None:
        out["occ_pt"] = carries["occ_pt"] + pt_cnt[:, None] * onehot_n[None, :]
    if ip_aux is None:
        return out
    ohu, cdv2, dvip, is_host_u, ki = ip_aux
    # p's own terms spread over their topology domains (the
    # reverse/symmetric direction future steps read back)
    val2_at = jnp.sum(
        jnp.where(onehot_n[None, :] > 0, cdv2, 0), axis=1
    )  # [AT] compact id at the chosen node
    dval_at = jnp.sum(
        jnp.where(onehot_n[None, :] > 0, dvip, 0), axis=1
    )  # [AT] label value at the chosen node
    dom_row = jnp.where(
        is_host_u[:, None],
        (onehot_n > 0)[None, :] & (dval_at >= 0)[:, None],
        (cdv2 == val2_at[:, None])
        & (cdv2 >= 0)
        & (val2_at >= 0)[:, None],
    )
    dom_row = dom_row & committed & (ki >= 0)[:, None]
    out["rev_cnt"] = rev_cnt + jnp.einsum(
        "ut,un->tn", ohu, dom_row.astype(I32)
    )
    return out


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, g=GangStatics, hostname_key=i32)
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(tid_pt=i32[P,UP], port_conf=bool[Tpt,Tpt])
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: axes(sample_k=i32, sample_start=i32, tie_key=key, attempt_base=i32)
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16)
@functools.partial(
    jax.jit,
    static_argnames=(
        "v_cap",
        "weights",
        "check_fit",
        "d_cap",
        "d2_cap",
        "fit_strategy",
        "has_ports",
    ),
)
def wave_schedule(
    dc: DeviceCluster,
    db: DeviceBatch,
    g: gang.GangStatics,
    hostname_key,
    v_cap: int,
    tid_sp,
    rep_sp_p,
    rep_sp_c,
    tid_ip,
    rep_ip_p,
    rep_ip_u,
    ip_cdv_tab,
    weights: tuple = gang.DEFAULT_WEIGHTS,
    check_fit: bool = True,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    d_cap: int = 8,
    d2_cap: int = 8,
    extra_score=None,
    fit_strategy: tuple = gang.DEFAULT_FIT_STRATEGY,
    has_ports: bool = False,
    tid_pt=None,
    port_conf=None,
    sample_k=None,
    sample_start=None,
    tie_key=None,
    attempt_base=None,
):
    """One fused wave dispatch: speculation + factored admission pass.

    ``has_ports`` (static) compiles in the [Tpt, N] port-occupancy carry
    for in-batch host-port users; ``sample_k``/``sample_start``/
    ``tie_key``/``attempt_base`` opt into the bit-compat sampling and
    seeded-tie modes exactly as gang_schedule does — the sampling window,
    nodeTree rotation cursor, and tie-break live in gang.pod_step and read
    only carried state, so the factored pass replays them bit-exactly
    (``tallies["sample_start"]`` returns the advanced cursor).

    Returns (chosen [P], n_feas [P], reason_counts [P, ND], tallies,
    stats [3, P]) where stats rows are (speculative choice, demote kind,
    conflicting term slot) — ``chosen == stats[0]`` per pod is the
    admitted-as-speculated mask the host turns into wave metrics."""
    P, N = g.static_mask.shape
    C = g.sp_dv.shape[1]
    AT = g.ip_dv.shape[1]
    Tsp = rep_sp_p.shape[0]
    Tip = rep_ip_p.shape[0]
    Kd2 = ip_cdv_tab.shape[0]
    Tpt = port_conf.shape[0] if has_ports else 0

    if nom_node is not None:
        nom_oh = (
            nom_node[:, None] == jnp.arange(N, dtype=I32)[None, :]
        ).astype(I32)  # [G, N]
    else:
        nom_oh = None

    true_n = jnp.ones((N,), bool)
    d_ids = jnp.arange(d_cap, dtype=I32)
    d2_ids = jnp.arange(d2_cap, dtype=I32)
    n_ids = jnp.arange(N, dtype=I32)

    m_sp_all, m_ip_all, t_anti, t_w = term_match_rows(
        g, rep_sp_p, rep_sp_c, rep_ip_p, rep_ip_u
    )

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

    def build_hv(p, sdyn, idyn, m_portb):
        """hv dict for pod_step + attribution tensors (c_ok, anti_viol)."""
        if C:
            m_spread, sp_cnt, c_ok = gang.spread_constraints(db, g, p, sdyn)
        else:
            m_spread = true_n
            sp_cnt = jnp.zeros((C, N), I32)
            c_ok = jnp.ones((C, N), bool)
        if AT:
            m_interpod, ip_raw, anti_viol = gang.interpod_constraints(
                g, p, idyn
            )
        else:
            m_interpod = true_n
            ip_raw = g.ip_sym[p]
            anti_viol = jnp.zeros((AT, N), bool)
        hv = dict(
            m_portb=m_portb,
            m_spread=m_spread,
            sp_cnt=sp_cnt,
            m_interpod=m_interpod,
            ip_raw=ip_raw,
        )
        return hv, c_ok, anti_viol

    step_kw = dict(
        check_fit=check_fit,
        weights=weights,
        d_cap=d_cap,
        fit_strategy=fit_strategy,
        extra_score=extra_score,
        nom_oh=nom_oh,
        nom_prio=nom_prio,
        nom_req=nom_req,
        sample_k=sample_k,
        tie_key=tie_key,
        attempt_base=attempt_base,
    )

    base = dict(
        requested=dc.requested,
        nonzero=dc.nonzero_req,
        num_pods=dc.num_pods,
        assigned=jnp.full((P,), ABSENT, I32),
    )
    if sample_k is not None:
        base["sample_start"] = jnp.asarray(sample_start, I32)

    # ---- pass 1: speculation — the whole wave against the frozen snapshot
    # (in sampling mode every pod speculates from the INITIAL rotation
    # cursor — the admission pass alone carries the advancing cursor, and
    # speculation feeds only the stats/attribution outputs)
    def spec_one(p):
        with jax.named_scope("ktpu/wave/speculation"):
            hv, _, _ = build_hv(p, zero_sdyn(), zero_idyn(), true_n)
            _, (choice, _, _) = gang.pod_step(
                dc, db, g, p, base, hv, jnp.asarray(True), commit=False, **step_kw
            )
            return choice

    c0 = jax.vmap(spec_one)(jnp.arange(P, dtype=I32))

    # ---- pass 2: conflict resolution / admission over factored deltas
    init = dict(base, **factored_carry_init(Tsp, Tip, N, Tpt))
    # Per-pod outputs ride CARRY buffers written at the pod's own slot
    # instead of scan-stacked ys: jaxlib 0.4.37's SPMD partitioner
    # mis-clamps the ys-stacking dynamic_update_slice (the scan's s64
    # loop counter meets the partitioner's own s32 shard arithmetic in
    # one compare — hlo-verifier rejection after spmd-partitioning)
    # whenever sharding propagation partitions the stacking axis, and
    # replicated constraints on the scan outputs do not reach the
    # in-loop buffers.  Scatter-style carry writes at an i32 index
    # partition correctly — `assigned` has always used this pattern.
    init.update(
        out_choice=jnp.full((P,), ABSENT, I32),
        out_nfeas=jnp.zeros((P,), I64),
        out_rc=jnp.zeros((P, N_DIAG), I64),
        out_kind=jnp.zeros((P,), I32),
        out_cterm=jnp.full((P,), -1, I32),
    )
    carry_keys = FACTORED_CARRY_KEYS[:3] + (("occ_pt",) if Tpt else ())

    def step(state, p):
        with jax.named_scope("ktpu/wave/admission"):
            if C:
                sdyn = factored_spread_dyn(g, p, tid_sp, state["cnt_sp"], d_cap)
            else:
                sdyn = zero_sdyn()

            if AT:
                idyn, ip_aux = factored_interpod_dyn(
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

            if has_ports:
                m_portb, pt_cnt = factored_port_mask(
                    tid_pt, port_conf, state["occ_pt"], p
                )
            else:
                m_portb, pt_cnt = true_n, None

            hv, c_ok, anti_viol = build_hv(p, sdyn, idyn, m_portb)
            new_state, (choice, n_feas, reason_counts) = gang.pod_step(
                dc, db, g, p, state, hv, jnp.asarray(True), **step_kw
            )

            # carry updates: dense rank-1 outer products, no scatters
            new_state.update(
                factored_carry_update(
                    {k: state[k] for k in carry_keys},
                    p,
                    choice,
                    m_sp_all,
                    m_ip_all,
                    ip_aux,
                    pt_cnt=pt_cnt,
                )
            )

            # demotion attribution vs the speculative candidate: evaluated at
            # the pod's own step, where the carries are exactly the serial
            # prefix — "why this speculation failed in the serial order"
            spec = c0[p]
            spec_live = spec >= 0
            at = jnp.clip(spec, 0, N - 1)
            pt_bad = spec_live & ~m_portb[at]
            sp_bad = spec_live & ~hv["m_spread"][at]
            ip_bad = spec_live & ~hv["m_interpod"][at]
            # resource-contention demotion: earlier wave commits consumed the
            # speculative node (the dominant cause on tight clusters) —
            # checked against the PRE-commit state this pod's verdict saw.
            # Nominated-pod charges are not replayed here (attribution only;
            # a nomination-induced fit failure reports as "score").
            if check_fit:
                Rn = dc.requested.shape[1]
                Rp = db.requests.shape[1]
                req = db.requests[p]
                avail = dc.allocatable[at] - state["requested"][at]  # [Rn]
                if Rp > Rn:
                    avail = jnp.concatenate(
                        [avail, jnp.zeros((Rp - Rn,), I32)]
                    )
                scalar_lane = jnp.arange(Rp) >= N_FIXED_LANES
                conflict = (req > avail) & (~scalar_lane | (req > 0))
                lane_bad = jnp.any(conflict) & ~jnp.all(req == 0)
                pods_bad = state["num_pods"][at] + 1 > dc.allowed_pods[at]
                fit_bad = spec_live & (lane_bad | pods_bad)
            else:
                fit_bad = jnp.asarray(False)
            demoted = choice != spec
            kind = jnp.where(
                ~demoted,
                DEMOTE_NONE,
                jnp.where(
                    ~spec_live,
                    DEMOTE_UPGRADE,
                    jnp.where(
                        pt_bad,
                        DEMOTE_PORTS,
                        jnp.where(
                            sp_bad,
                            DEMOTE_SPREAD,
                            jnp.where(
                                ip_bad,
                                DEMOTE_AFFINITY,
                                jnp.where(fit_bad, DEMOTE_FIT, DEMOTE_SCORE),
                            ),
                        ),
                    ),
                ),
            ).astype(I32)
            if C:
                sp_viol = g.sp_hard[p] & ~c_ok[:, at]  # [C]
                sp_term = jnp.argmax(sp_viol).astype(I32)
                sp_term = jnp.where(jnp.any(sp_viol), sp_term, -1)
            else:
                sp_term = jnp.asarray(-1, I32)
            if AT:
                ip_viol = anti_viol[:, at]  # [AT]
                ip_term = jnp.argmax(ip_viol).astype(I32)
                ip_term = jnp.where(jnp.any(ip_viol), ip_term, -1)
            else:
                ip_term = jnp.asarray(-1, I32)
            cterm = jnp.where(
                kind == DEMOTE_SPREAD,
                sp_term,
                jnp.where(kind == DEMOTE_AFFINITY, ip_term, -1),
            )
            # p is the scan index over the batch axis — in range by
            # construction; mode="drop" spells it for the slice-clamp rule
            new_state["out_choice"] = (
                state["out_choice"].at[p].set(choice, mode="drop")
            )
            new_state["out_nfeas"] = (
                state["out_nfeas"].at[p].set(n_feas, mode="drop")
            )
            new_state["out_rc"] = (
                state["out_rc"].at[p].set(reason_counts, mode="drop")
            )
            new_state["out_kind"] = state["out_kind"].at[p].set(kind, mode="drop")
            new_state["out_cterm"] = (
                state["out_cterm"].at[p].set(cterm, mode="drop")
            )
            return new_state, None

    # the scan's own loop machinery belongs to the admission pass too
    with jax.named_scope("ktpu/wave/admission"):
        state, _ = jax.lax.scan(step, init, jnp.arange(P, dtype=I32))
    chosen = state["out_choice"]
    n_feas = state["out_nfeas"]
    reason_counts = state["out_rc"]
    kinds = state["out_kind"]
    cterms = state["out_cterm"]
    tallies = {
        "requested": state["requested"],
        "nonzero": state["nonzero"],
        "num_pods": state["num_pods"],
    }
    if sample_k is not None:
        tallies["sample_start"] = state["sample_start"]
    stats = jnp.stack([c0, kinds, cterms])  # [3, P]
    return chosen, n_feas, reason_counts, tallies, stats


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32, extra_mask=bool[P,N])
# ktpu: axes(tid_sp=i32[P,C], rep_sp_p=i32[Tsp], rep_sp_c=i32[Tsp])
# ktpu: axes(tid_ip=i32[P,A], rep_ip_p=i32[Tip], rep_ip_u=i32[Tip], ip_cdv_tab=i32[Kd2,N])
# ktpu: axes(tid_pt=i32[P,UP], port_conf=bool[Tpt,Tpt])
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2])
# ktpu: axes(sample_k=i32, sample_start=i32, tie_key=key, attempt_base=i32)
# ktpu: axes(sig=i32[P], rep_pod=i32[U])
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16)
@functools.partial(
    jax.jit,
    static_argnames=(
        "v_cap",
        "hard_pod_affinity_weight",
        "has_interpod",
        "has_spread",
        "has_images",
        "enabled",
        "weights",
        "d_cap",
        "d2_cap",
        "fit_strategy",
        "has_ports",
    ),
)
def wave_run(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    v_cap: int,
    tid_sp,
    rep_sp_p,
    rep_sp_c,
    tid_ip,
    rep_ip_p,
    rep_ip_u,
    ip_cdv_tab,
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
    has_ports: bool = False,
    tid_pt=None,
    port_conf=None,
    sample_k=None,
    sample_start=None,
    tie_key=None,
    attempt_base=None,
    sig=None,
    rep_pod=None,
):
    """Fused precompute + wave: ONE device dispatch per batch (the wave
    counterpart of gang.gang_run).  The gang scan's pod×pod port matrix
    stays compiled out (precompute has_ports=False): in-batch host ports
    ride the factored [Tpt, N] occupancy carry instead (``has_ports`` here
    gates THAT carry).  ``sig`` / ``rep_pod``: the statics by distinct pod
    row (static_signatures; not used with ``extra_mask``, which is per pod)."""
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
        sig=sig,
        rep_pod=rep_pod,
    )
    return wave_schedule(
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
        extra_score=extra_score,
        fit_strategy=fit_strategy,
        has_ports=has_ports,
        tid_pt=tid_pt,
        port_conf=port_conf,
        sample_k=sample_k,
        sample_start=sample_start,
        tie_key=tie_key,
        attempt_base=attempt_base,
    )
