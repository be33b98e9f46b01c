"""Sequential-equivalent gang scheduling: one lax.scan step per pod.

The reference schedules strictly one pod at a time, each cycle seeing all
previous placements through the assume-cache (schedule_one.go:65,
cache.go:360).  Batch evaluation must reproduce those semantics or decisions
diverge (SURVEY.md §7 "intra-batch conflicts").  The design:

  * everything state-INdependent is computed batched up front — all
    selector/term matching, the pod×existing quadratic terms, and the
    pod×pod batch-cross match matrices (the expensive MXU work);
  * a lax.scan walks the batch in queue order; each step is an [N]-wide
    vectorized re-evaluation of only the state-DEPENDENT pieces (resource
    tallies, spread/inter-pod counts contributed by batch placements, score
    normalization over the current feasible set) followed by argmax commit.

The scan step is built for TPU op latency: NO scatters, segment-sums or
vocab-wide gathers in the loop body.  Every state-dependent count is a fused
dense equality-contraction over small axes ([C,N,J]-shaped compare+reduce
against the assigned-node domain values), so the per-step cost is a handful
of VPU/MXU passes over row slices instead of serialized scatter ops.  The
only per-step dynamic indexing is row slices of the per-pod statics and
[C,J]-sized gathers of the assigned nodes' domain values.  Scan-step
arithmetic keeps the NODE axis minor and resource lanes as separate [N]
vectors: an int64 [N, 2] operand fills the register tiles of [N, 128], and
the fit score's emulated divisions cost 227 us a step on a v5e stacked as
[N, 2] and about 11 us as two [N] lanes, N = 5,120 (PERF.md, PR 28).

The scan step mirrors, piece by piece, what the serial oracle recomputes
between pods, so gang results are identical to scheduling the pods one by
one — property-tested against the serial oracle in tests/test_gang.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import scores as S
from kubernetes_tpu.ops.common import (
    DeviceBatch,
    DeviceCluster,
    I32,
    I64,
    compact_domain_stats,
    domain_stats,
    eval_table,
    gather_at,
    ns_member,
    per_node_counts,
    usage_carry_update,
)
from kubernetes_tpu.snapshot.interner import ABSENT, PAD
from kubernetes_tpu.snapshot.schema import (
    LANE_CPU,
    LANE_MEM,
    N_FIXED_LANES,
    TERM_PREFERRED_AFFINITY,
    TERM_PREFERRED_ANTI,
    TERM_REQUIRED_AFFINITY,
    TERM_REQUIRED_ANTI,
    bucket_cap,
)

MAX = S.MAX_NODE_SCORE
_FX = S._FX

# Named-axis schema of the precompute product (analyzer shape rules).
# J — the batch-peer view of the P axis — is spelled P here: the two are
# the same size by construction and must unify in the shape algebra
# (ANALYSIS.md glossary).
_KTPU_AXES = {
    "GangStatics": {
        "static_mask": "bool[P,N]",
        "sp_hard": "bool[P,C]",
        "sp_soft": "bool[P,C]",
        "sp_dv": "i32[P,C,N]",
        "sp_te": "bool[P,C,N]",
        "sp_dom_cnt": "i32[P,C,N]",
        "sp_dom_pres": "bool[P,C,N]",
        "sp_ndom": "i32[P,C]",
        "sp_self": "bool[P,C]",
        "sp_bmatch": "bool[P,C,P]",
        "sp_is_host": "bool[P,C]",
        "sp_counting": "bool[P,C,N]",
        "sp_node_cnt": "i32[P,C,N]",
        "sp_sc_dom": "i32[P,C,N]",
        "sp_all_keys": "bool[P,N]",
        "sp_cdv": "i32[P,C,N]",
        "ip_dv": "i32[P,A,N]",
        "ip_dom_cnt": "i32[P,A,N]",
        "ip_viol_existing": "bool[P,N]",
        "ip_sym": "i64[P,N]",
        "ip_any_static": "bool[P]",
        "ip_self_all": "bool[P]",
        "ip_bmatch": "bool[P,A,P]",
        "ip_is_aff": "bool[P,A]",
        "ip_is_anti": "bool[P,A]",
        "ip_pref_w": "i64[P,A]",
        "ip_sym_w": "i64[P,A]",
        "ip_key_idx": "i32[P,A]",
        "ip_key_cols": "i32[Kd2,N]",
        "sc_taint": "i64[P,N]",
        "sc_nodeaff": "i64[P,N]",
        "sc_image": "i64[P,N]",
        "port_b": "bool[P,P]",
        "d_nodename": "bool[P,N]",
        "d_unsched": "bool[P,N]",
        "d_taints": "bool[P,N]",
        "d_nodeaff": "bool[P,N]",
        "d_ports": "bool[P,N]",
        "d_extra": "bool[P,N]",
    },
}

# shard-rule roster: the serial verdict core and its per-pod helpers are
# full-node-width by design.  Every entry carries its resolved sharding
# story (MULTICHIP.md inventory): under meshDispatch the DeviceCluster's
# node-major tensors are partitioned over the mesh's 'nodes' axis and
# GSPMD lowers each rostered op to per-shard work + the named collective;
# integer-exact arithmetic makes every reduction order-free, so the
# partitioned result is bit-identical to the single-chip kernel.
_KTPU_N_COLLECTIVES = {
    "pod_step": "resolved(collective): per-pod argmax/select over all N "
    "nodes + sampling-window rotation gathers (selectHost / nodeTree "
    "order semantics) — GSPMD all-reduces the packed (key, first-index) "
    "max across node shards; the index tiebreak in the packed key keeps "
    "first-max semantics exact, and the chosen row gather is an "
    "owning-shard broadcast",
    "spread_constraints": "resolved(collective): min-match over the "
    "tracked N axis (filtering.go:313 minMatch) — per-shard partial min "
    "+ cross-shard min-reduce",
    "interpod_constraints": "resolved(collective): per-term verdicts "
    "collapse over N-wide rows — per-shard partial any/all + cross-shard "
    "reduce",
    "_spread_raw": "resolved(collective): counted-node totals + "
    "per-domain [C,N,d_cap] compare+reduce over N — per-shard partial "
    "sums psum across node shards (integer counts, order-free)",
    "_norm_default": "resolved(collective): score normalization max over "
    "the feasible N axis — cross-shard max-reduce",
    "_norm_minmax": "resolved(collective): score normalization min+max "
    "over the feasible N axis — cross-shard min/max-reduce",
    "_norm_spread": "resolved(collective): spread normalization min+max "
    "over the valid N axis — cross-shard min/max-reduce",
    "gang_schedule.heavy_parts": "resolved(collective): peer-count einsum "
    "contractions over N (the [C,N,J]/[AT,N,J] dense compare+reduce) — "
    "per-shard partial contractions + psum of the [C,J] partials",
}


class GangStatics(NamedTuple):
    """State-independent precompute for one (cluster, batch) pair."""

    static_mask: jnp.ndarray  # bool [P, N]
    # spread filter (hard constraints, filtering.go:236-362)
    sp_hard: jnp.ndarray  # bool [P, C]
    sp_soft: jnp.ndarray  # bool [P, C]
    sp_dv: jnp.ndarray  # i32 [P, C, N]
    sp_te: jnp.ndarray  # bool [P, C, N] tracked & eligible (filter counting)
    sp_dom_cnt: jnp.ndarray  # i32 [P, C, N] per-domain counts (existing pods)
    sp_dom_pres: jnp.ndarray  # bool [P, C, N]
    sp_ndom: jnp.ndarray  # i32 [P, C]
    sp_self: jnp.ndarray  # bool [P, C]
    sp_bmatch: jnp.ndarray  # bool [P, C, J]
    # spread score (scoring.go)
    sp_is_host: jnp.ndarray  # bool [P, C]
    sp_counting: jnp.ndarray  # bool [P, C, N] all-keys ∧ eligible (score gate)
    sp_node_cnt: jnp.ndarray  # i32 [P, C, N] raw per-node matching counts
    sp_sc_dom: jnp.ndarray  # i32 [P, C, N] score-gated per-domain counts
    sp_all_keys: jnp.ndarray  # bool [P, N] node has every soft topo key
    sp_cdv: jnp.ndarray  # i32 [P, C, N] compact domain ids (<0: host/absent)
    # inter-pod
    ip_dv: jnp.ndarray  # i32 [P, AT, N]
    ip_dom_cnt: jnp.ndarray  # i32 [P, AT, N] matching existing in node's domain
    ip_viol_existing: jnp.ndarray  # bool [P, N]
    ip_sym: jnp.ndarray  # i64 [P, N] symmetric score from existing terms
    ip_any_static: jnp.ndarray  # bool [P]
    ip_self_all: jnp.ndarray  # bool [P]
    ip_bmatch: jnp.ndarray  # bool [P, AT, J]  (read [j,u,p]: p matches j's term u)
    ip_is_aff: jnp.ndarray  # bool [P, AT]
    ip_is_anti: jnp.ndarray  # bool [P, AT]
    ip_pref_w: jnp.ndarray  # i64 [P, AT]
    ip_sym_w: jnp.ndarray  # i64 [P, AT] weight of p's terms once p is placed
    ip_key_idx: jnp.ndarray  # i32 [P, AT] index into ip_key_cols (<0 absent)
    ip_key_cols: jnp.ndarray  # i32 [Kd, N] node label value per distinct key
    # static raw scores
    sc_taint: jnp.ndarray  # i64 [P, N]
    sc_nodeaff: jnp.ndarray  # i64 [P, N]
    sc_image: jnp.ndarray  # i64 [P, N]
    # batch port conflicts
    port_b: jnp.ndarray  # bool [P, J]
    # per-kernel masks kept separate for failure diagnosis (FitError reason
    # counts, framework/types.go:367-465).  All-True when a kernel is
    # disabled so it is never blamed.
    d_nodename: jnp.ndarray  # bool [P, N]
    d_unsched: jnp.ndarray  # bool [P, N]
    d_taints: jnp.ndarray  # bool [P, N]
    d_nodeaff: jnp.ndarray  # bool [P, N]
    d_ports: jnp.ndarray  # bool [P, N]
    d_extra: jnp.ndarray  # bool [P, N] (host-filter veto mask)


def batch_tables(
    tsc_topo, aff_topo, node_label_vals, hostname_id: int, hostnames_unique=None
):
    """Host-side per-batch key tables for the dense domain math of the
    statics and the scan.

    tsc_topo/aff_topo: numpy [P, C]/[P, AT] interned topology-key ids of the
    batch (PAD in empty slots); node_label_vals: numpy [N, K] interned node
    label values (the mirror's column-per-key layout).  ``hostnames_unique``
    is the once-per-snapshot bit from SnapshotMirror.hostnames_unique; None
    re-derives it here (standalone/test callers).

    Returns a dict of gang_run kwargs:
      sp_keys    i32 [Kd]   distinct NON-hostname spread topology keys
      sp_cdv_tab i32 [Kd,N] per-key compact domain id per node (-1: absent)
      sp_host_cdv i32 [N]   the hostname key's compact map, for precompute
                 alone; None (no argument of the program) unless a spread
                 slot of the batch is on the hostname key AND two nodes
                 share a hostname value
      ip_keys    i32 [Kd2]  distinct inter-pod topology keys (incl hostname)
      d_cap      int        static bucket over the max distinct-domain count

    Compact ids let the statics sum, and the scan count, domains as a
    [C, N, d_cap] fused compare+reduce instead of a vocab-wide segment op
    (the TPU-hostile pattern this file avoids); hostname-topology constraints
    use node identity directly so their domain count never inflates d_cap —
    where hostnames repeat, identity does not hold and precompute sums the
    key by ``sp_host_cdv``, whose ids the node count bounds.
    """
    import numpy as np

    lv = np.asarray(node_label_vals)
    n_cap, K = lv.shape

    def _compact(col):
        """(compact id per node, -1 absent; the number of distinct values)"""
        cdv = np.full(n_cap, -1, np.int32)
        pos = col >= 0
        n_uniq = 0
        if pos.any():
            uniq, inv = np.unique(col[pos], return_inverse=True)
            cdv[pos] = inv.astype(np.int32)
            n_uniq = len(uniq)
        return cdv, n_uniq

    def _distinct(keys_arr, exclude_host: bool):
        ids = np.unique(np.asarray(keys_arr).reshape(-1))
        out = []
        for k in ids:
            k = int(k)
            if k < 0 or k >= K:
                continue
            if exclude_host and k == hostname_id:
                continue
            out.append(k)
        return out

    sp_ids = _distinct(tsc_topo, exclude_host=True)
    d_max = 1
    rows = []
    for k in sp_ids:
        cdv, n_uniq = _compact(lv[:, k])
        d_max = max(d_max, n_uniq)
        rows.append(cdv)
    kd = bucket_cap(max(len(sp_ids), 1), 1)
    sp_keys = np.full(kd, -1, np.int32)
    sp_keys[: len(sp_ids)] = sp_ids
    sp_cdv_tab = np.full((kd, n_cap), -1, np.int32)
    for i, r in enumerate(rows):
        sp_cdv_tab[i] = r

    sp_host_cdv = None
    if (
        not hostnames_unique
        and 0 <= hostname_id < K
        and (np.asarray(tsc_topo) == hostname_id).any()
    ):
        cdv, n_uniq = _compact(lv[:, hostname_id])
        if n_uniq < int((cdv >= 0).sum()):
            sp_host_cdv = jnp.asarray(cdv)

    ip_ids = _distinct(aff_topo, exclude_host=False)
    kd2 = bucket_cap(max(len(ip_ids), 1), 1)
    ip_keys = np.full(kd2, -1, np.int32)
    ip_keys[: len(ip_ids)] = ip_ids

    return dict(
        sp_keys=jnp.asarray(sp_keys),
        sp_cdv_tab=jnp.asarray(sp_cdv_tab),
        sp_host_cdv=sp_host_cdv,
        ip_keys=jnp.asarray(ip_keys),
        d_cap=bucket_cap(d_max, 8),
    )


def precompute(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    v_cap: int,
    hard_pod_affinity_weight: int = 1,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_ports: bool = True,
    has_images: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    extra_mask=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sig=None,
    rep_pod=None,
    d_cap=None,
    sp_host_cdv=None,
) -> GangStatics:
    """When a has_* flag is False the corresponding statics are built with a
    ZERO-width constraint axis; the scan step's reductions over that axis
    vanish at compile time (the PreFilter-Skip of the gang path — shape-
    driven rather than flag-plumbed).  ``enabled`` reflects the profile's
    Filter plugin set.  sp_keys/sp_cdv_tab/ip_keys come from batch_tables();
    they are required whenever the matching has_* flag is set.

    The spread aggregates are summed by topology KEY over the key's compact
    node→domain map (``sp_cdv_tab``; every row of a key reads the same map),
    a [P, C, d_cap, N] compare+reduce (``compact_domain_stats``) — never
    with a segment-id vector private to a (pod, slot) row, which lowers to
    P·C·N scalar scatters and gathers.  ``d_cap`` (static) is batch_tables'
    bucket; None: the node count, which bounds every compact id.  The
    hostname key is in no table: with ``sp_host_cdv`` None every node is its
    own domain (no two share a hostname value) and the aggregate is the
    identity; else it is summed like any other key by that map.

    ``sig`` i32 [P] / ``rep_pod`` i32 [U] (wave.static_signatures): the
    statics are computed for the batch's U representative rows instead of
    its P pods and read back per pod through ``sig`` — every field is a
    function of (dc, the pod's own row; the peer's row too on a trailing
    batch axis), so the result is the same integers.  ``extra_mask`` is
    per pod by nature: with it the table is not used."""
    if extra_mask is not None:
        sig = None
    with jax.named_scope("ktpu/gang/precompute"):
        if sig is not None:
            # padding entries (-1) read row 0; no pod's sig points at them
            rows = jnp.maximum(rep_pod, 0)
            db = jax.tree_util.tree_map(lambda x: x[rows], db)
        P = db.valid.shape[0]
        N = dc.node_valid.shape[0]
        tolerated = F._tolerated(dc, db)
        node_affinity = F.mask_node_affinity(dc, db)
        taints = F.mask_taints(dc, db, tolerated)
        base = dc.node_valid[None, :] & db.valid[:, None]
        true_pn = jnp.ones((P, N), bool)
        # host-plugin vetoes (run_host_filters) fold in as a static [P, N]
        # feasibility contribution
        d_extra = extra_mask if extra_mask is not None else true_pn
        d_nodename = F.mask_node_name(dc, db) if "NodeName" in enabled else true_pn
        d_unsched = (
            F.mask_unschedulable(dc, db) if "NodeUnschedulable" in enabled else true_pn
        )
        d_taints = taints if "TaintToleration" in enabled else true_pn
        d_nodeaff = node_affinity if "NodeAffinity" in enabled else true_pn
        d_ports = F.mask_ports(dc, db) if "NodePorts" in enabled else true_pn
        static_mask = (
            base & d_extra & d_nodename & d_unsched & d_taints & d_nodeaff & d_ports
        )
        has_interpod = has_interpod and "InterPodAffinity" in enabled
        has_spread = has_spread and "PodTopologySpread" in enabled

        # ---- spread ----
        if has_spread:
            spre = F.spread_precompute(dc, db, node_affinity, taints)
            _, C, _ = spre.dv.shape
            cnt_n = per_node_counts(spre.sel_match.astype(I32), dc.epod_node, N)
            te = spre.tracked[:, None, :] & spre.eligible
            soft = spre.exists & ~db.tsc_hard
            topo_present = spre.dv >= 0
            all_keys = jnp.all(~soft[:, :, None] | topo_present, axis=1)  # [P, N]
            counting = all_keys[:, None, :] & spre.eligible
            b_sel = eval_table(db.tsc_table, db.labels, dc.val_ints)  # [P, C, J]
            same_ns = db.ns_id[:, None] == db.ns_id[None, :]
            sp_bmatch = b_sel & same_ns[:, None, :] & db.valid[None, None, :]
            if sp_keys is None:
                # Missing tables would silently zero every non-host domain
                # aggregate (and the topologyNormalizingWeight) — fail loud.
                raise ValueError(
                    "precompute: sp_keys/sp_cdv_tab (from batch_tables) are "
                    "required when has_spread is set"
                )
            k_eq = (db.tsc_topo[:, :, None] == sp_keys[None, None, :]) & (
                sp_keys >= 0
            )[None, None, :]  # [P, C, Kd]
            any_k = jnp.any(k_eq, axis=-1)
            ki = jnp.argmax(k_eq, axis=-1)
            sp_cdv = jnp.where(any_k[:, :, None], sp_cdv_tab[ki], -1)  # [P, C, N]
            is_host = db.tsc_topo == hostname_key  # [P, C]
            host_pc = is_host[:, :, None]
            if sp_host_cdv is None:
                agg_cdv, agg_cap = sp_cdv, (N if d_cap is None else d_cap)
            else:
                agg_cdv, agg_cap = jnp.where(host_pc, sp_host_cdv, sp_cdv), N

            def by_domain(gate):
                """(total, present, n_domains) of the gated matching counts
                over each node's domain; 0 / False where the key is absent"""
                cnt = jnp.where(gate, cnt_n, 0)
                tot, pres, n = compact_domain_stats(cnt, gate, agg_cdv, agg_cap)
                if sp_host_cdv is None:
                    # a hostname slot's compact ids are all absent: every
                    # node that carries the label is its own domain
                    own = host_pc & topo_present & gate
                    tot = jnp.where(own, cnt, tot)
                    pres = pres | own
                    n = n + jnp.sum(own.astype(I32), axis=-1)
                return tot, pres, n

            dom_tot, dom_pres, n_dom = by_domain(te)
            sc_dom, _, _ = by_domain(counting)
            sp = dict(
                sp_hard=spre.exists & db.tsc_hard,
                sp_soft=soft,
                sp_dv=spre.dv,
                sp_te=te,
                sp_dom_cnt=dom_tot,
                sp_dom_pres=dom_pres,
                sp_ndom=n_dom,
                sp_self=spre.self_match,
                sp_bmatch=sp_bmatch,
                sp_is_host=is_host,
                sp_counting=counting,
                sp_node_cnt=cnt_n,
                sp_sc_dom=sc_dom,
                sp_all_keys=all_keys,
                sp_cdv=sp_cdv,
            )
        else:
            z2 = jnp.zeros((P, 0), bool)
            z3b = jnp.zeros((P, 0, N), bool)
            z3i = jnp.zeros((P, 0, N), I32)
            sp = dict(
                sp_hard=z2,
                sp_soft=z2,
                sp_dv=z3i,
                sp_te=z3b,
                sp_dom_cnt=z3i,
                sp_dom_pres=z3b,
                sp_ndom=jnp.zeros((P, 0), I32),
                sp_self=z2,
                sp_bmatch=jnp.zeros((P, 0, P), bool),
                sp_is_host=z2,
                sp_counting=z3b,
                sp_node_cnt=z3i,
                sp_sc_dom=z3i,
                sp_all_keys=jnp.ones((P, N), bool),
                sp_cdv=z3i,
            )

        # ---- inter-pod ----
        if has_interpod:
            ipre = F.interpod_precompute(dc, db)
            viol_existing = F.interpod_existing_violation(dc, ipre)
            sym = S.interpod_symmetric_score(dc, ipre, hard_pod_affinity_weight)
            ip_dom_cnt, _, _, _ = domain_stats(
                ipre.inc_cnt, jnp.zeros_like(ipre.inc_cnt, bool), ipre.inc_dv, v_cap
            )
            ip_dom_cnt = jnp.where(ipre.inc_dv >= 0, ip_dom_cnt, 0)
            is_aff = db.aff_kind == TERM_REQUIRED_AFFINITY
            is_anti = db.aff_kind == TERM_REQUIRED_ANTI
            any_static = jnp.any(is_aff[:, :, None] & ipre.inc_match, axis=(1, 2))
            self_sel = jax.vmap(
                lambda tbl, lbl: eval_table(tbl, lbl[None, :], dc.val_ints)[..., 0]
            )(db.aff_table, db.labels)
            self_ns = jax.vmap(
                lambda a, ids, ns: ns_member(a, ids, ns[None])[..., 0]
            )(db.aff_ns_all, db.aff_ns_ids, db.ns_id)
            self_all = jnp.all(~is_aff | (self_sel & self_ns), axis=1)
            b_aff_sel = eval_table(db.aff_table, db.labels, dc.val_ints)
            b_aff_ns = ns_member(db.aff_ns_all, db.aff_ns_ids, db.ns_id)
            ip_bmatch = b_aff_sel & b_aff_ns & db.valid[None, None, :]
            pref_w = jnp.where(
                db.aff_kind == TERM_PREFERRED_AFFINITY,
                db.aff_weight,
                jnp.where(db.aff_kind == TERM_PREFERRED_ANTI, -db.aff_weight, 0),
            ).astype(I64)
            sym_w = jnp.where(
                db.aff_kind == TERM_REQUIRED_AFFINITY,
                hard_pod_affinity_weight,
                pref_w.astype(I32),
            ).astype(I64)
            AT = is_aff.shape[1]
            if ip_keys is None:
                # Without the key table the batch-cross (pod vs already-committed
                # batch peer) term evaluation has nothing to factor over and
                # anti-affinity between batch members would silently vanish.
                raise ValueError(
                    "precompute: ip_keys (from batch_tables) is required when "
                    "has_interpod is set"
                )
            else:
                k_eq = (db.aff_topo[:, :, None] == ip_keys[None, None, :]) & (
                    ip_keys >= 0
                )[None, None, :]
                any_k = jnp.any(k_eq, axis=-1)
                ip_key_idx = jnp.where(
                    any_k, jnp.argmax(k_eq, axis=-1).astype(I32), -1
                )
                ip_key_cols = gather_at(dc.node_labels.T, ip_keys)  # [Kd2, N]
            ip = dict(
                ip_dv=ipre.inc_dv,
                ip_dom_cnt=ip_dom_cnt,
                ip_viol_existing=viol_existing,
                ip_sym=sym,
                ip_any_static=any_static,
                ip_self_all=self_all,
                ip_bmatch=ip_bmatch,
                ip_is_aff=is_aff,
                ip_is_anti=is_anti,
                ip_pref_w=pref_w,
                ip_sym_w=sym_w,
                ip_key_idx=ip_key_idx,
                ip_key_cols=ip_key_cols,
            )
        else:
            ip = dict(
                ip_dv=jnp.zeros((P, 0, N), I32),
                ip_dom_cnt=jnp.zeros((P, 0, N), I32),
                ip_viol_existing=jnp.zeros((P, N), bool),
                ip_sym=jnp.zeros((P, N), I64),
                ip_any_static=jnp.zeros((P,), bool),
                ip_self_all=jnp.ones((P,), bool),
                ip_bmatch=jnp.zeros((P, 0, P), bool),
                ip_is_aff=jnp.zeros((P, 0), bool),
                ip_is_anti=jnp.zeros((P, 0), bool),
                ip_pref_w=jnp.zeros((P, 0), I64),
                ip_sym_w=jnp.zeros((P, 0), I64),
                ip_key_idx=jnp.zeros((P, 0), I32),
                ip_key_cols=jnp.full((1, N), ABSENT, I32),
            )

        # ---- batch port conflicts (node_ports.go semantics, pod×pod) ----
        if has_ports:
            W = db.want_ppk.shape[1]
            port_b = jnp.zeros((P, P), bool)
            for w in range(W):
                wk = db.want_ppk[:, w][:, None]
                wi = db.want_ip[:, w][:, None]
                ww = db.want_wild[:, w][:, None]
                wv = wk != PAD
                for u in range(W):
                    uk = db.want_ppk[:, u][None, :]
                    ui = db.want_ip[:, u][None, :]
                    uw = db.want_wild[:, u][None, :]
                    uv = uk != PAD
                    port_b = port_b | (
                        wv & uv & (wk == uk) & ((wi == ui) | ww | uw)
                    )
        else:
            port_b = jnp.zeros((P, 0), bool)

        if has_images:
            sc_image = S.score_image_locality(dc, db)
        else:
            sc_image = jnp.zeros((P, N), I64)

        g = GangStatics(
            static_mask=static_mask,
            **sp,
            **ip,
            sc_taint=S.score_taint_toleration(dc, db),
            sc_nodeaff=S.score_node_affinity(dc, db),
            sc_image=sc_image,
            port_b=port_b,
            d_nodename=d_nodename,
            d_unsched=d_unsched,
            d_taints=d_taints,
            d_nodeaff=d_nodeaff,
            d_ports=d_ports,
            d_extra=d_extra,
        )
        return g if sig is None else _statics_by_sig(g, sig)


# GangStatics fields with no pod axis / with a trailing batch-peer axis
_NO_POD_AXIS = ("ip_key_cols",)
_PEER_AXIS = ("sp_bmatch", "ip_bmatch", "port_b")


def _select_rows(x_u, sig, axis):
    """``x_u`` indexed by ``sig`` along ``axis`` as a chain of selects over
    the U rows: elementwise, so it fuses into what reads it.  (A gather
    ``x_u[sig]`` is the same values; this chip's gathers are the ops this
    table exists to remove.)"""
    P = sig.shape[0]
    sel = sig.reshape([P if a == axis else 1 for a in range(x_u.ndim)])
    out = jnp.broadcast_to(
        jax.lax.slice_in_dim(x_u, 0, 1, axis=axis),
        [P if a == axis else n for a, n in enumerate(x_u.shape)],
    )
    for u in range(1, x_u.shape[axis]):
        out = jnp.where(
            sel == u, jax.lax.slice_in_dim(x_u, u, u + 1, axis=axis), out
        )
    return out


def _statics_by_sig(g_u: GangStatics, sig) -> GangStatics:
    """Expand [U, …] statics to [P, …]: each pod reads its signature's row
    (and, on a trailing batch axis, each peer its signature's column)."""
    out = {}
    for name in GangStatics._fields:
        x = getattr(g_u, name)
        if name not in _NO_POD_AXIS:
            x = _select_rows(x, sig, 0)
            # a zero-width trailing axis is a compiled-out term axis
            # (port_b [P, 0]), not a peer axis
            if name in _PEER_AXIS and x.shape[-1]:
                x = _select_rows(x, sig, x.ndim - 1)
        out[name] = x
    return GangStatics(**out)


# ---------------------------------------------------------------------------
# Per-step helpers (single pod, [N]-wide)
# ---------------------------------------------------------------------------


def _norm_default(raw, feas, reverse=False):
    raw = raw.astype(I64)
    mx = jnp.max(jnp.where(feas, raw, 0))
    out = jnp.where(mx > 0, MAX * raw // jnp.maximum(mx, 1), raw)
    if reverse:
        out = jnp.where(mx > 0, MAX - out, MAX)
    return out


def _norm_minmax(raw, feas):
    raw = raw.astype(I64)
    big = jnp.iinfo(jnp.int64).max
    mn = jnp.min(jnp.where(feas, raw, big))
    mx = jnp.max(jnp.where(feas, raw, -big))
    diff = mx - mn
    return jnp.where(diff > 0, MAX * (raw - mn) // jnp.maximum(diff, 1), 0)


def _norm_spread(raw, valid, feas):
    raw = raw.astype(I64)
    use = valid & feas
    big = jnp.iinfo(jnp.int64).max
    mn = jnp.min(jnp.where(use, raw, big))
    mx = jnp.max(jnp.where(use, raw, -big))
    any_valid = jnp.any(use)
    out = jnp.where(
        mx == 0, MAX, MAX * (mx + mn - raw) // jnp.maximum(mx, 1)
    )
    return jnp.where(use & any_valid, out, 0)


# Diagnosis rows of the [P, N_DIAG] reason-count output, in chain order.
DIAG_KERNELS = (
    "NodeUnschedulable",
    "NodeName",
    "TaintToleration",
    "NodeAffinity",
    "NodePorts",
    "HostFilters",
    "NodeResourcesFit",
    "PodTopologySpread",
    "InterPodAffinity",
)
# literal so the shape interpreter resolves [P, N_DIAG] buffers concretely
N_DIAG = 9
assert N_DIAG == len(DIAG_KERNELS)

# Positional weight order for the gang scan's static `weights` tuple — the
# single source of truth is scores.DEFAULT_SCORE_WEIGHTS.
WEIGHT_ORDER = (
    "TaintToleration",
    "NodeAffinity",
    "PodTopologySpread",
    "InterPodAffinity",
    "NodeResourcesFit",
    "NodeResourcesBalancedAllocation",
    "ImageLocality",
)
DEFAULT_WEIGHTS = tuple(S.DEFAULT_SCORE_WEIGHTS[n] for n in WEIGHT_ORDER)


def _trunc_div(num, den):
    """Go-style truncation toward zero (den > 0)."""
    return jnp.where(num >= 0, num // den, -((-num) // den))


def _broken_linear_dev(points: tuple, x):
    """BuildBrokenLinearFunction (helper/shape_score.go:40) over an [N]
    integer array; ``points`` is a static ((utilization, score), ...)."""
    out = jnp.full_like(x, points[0][1])
    for (x0, y0), (x1, y1) in zip(points, points[1:]):
        seg = y0 + _trunc_div((y1 - y0) * (x - x0), x1 - x0)
        out = jnp.where((x > x0) & (x <= x1), seg, out)
    return jnp.where(x > points[-1][0], points[-1][1], out)


# (strategy id, shape, per-lane weights) defaults — LeastAllocated with
# cpu/memory weight 1, matching resource_allocation.go defaults.
DEFAULT_FIT_STRATEGY = (0, (), (1, 1))


def fit_score(fit_strategy: tuple, a0, a1, c0, c1):
    """NodeResourcesFit score (resource_allocation.go:37-115) from the cpu
    and memory lanes as SEPARATE same-shaped i64 arrays: ``a0/a1``
    allocatable, ``c0/c1`` non-zero-defaulted requests (node + pod).
    Never stacked into a trailing axis of 2 (module docstring): the two
    lanes meet only in the final two-term sums."""
    strat_id, fit_shape, (w0, w1) = fit_strategy

    def lane(a, c):
        has = a > 0
        if strat_id == 1:  # MostAllocated (most_allocated.go)
            f = jnp.where(c > a, 0, c * MAX // jnp.maximum(a, 1))
        elif strat_id == 2:  # RequestedToCapacityRatio
            util = jnp.where(
                ~has | (c > a), MAX, c * MAX // jnp.maximum(a, 1)
            )
            f = _broken_linear_dev(fit_shape, util)
            # RTCR only counts resources whose score is positive
            # (requested_to_capacity_ratio.go:46-52)
            has = has & (f > 0)
        else:  # LeastAllocated (least_allocated.go:29-60)
            f = jnp.where(c > a, 0, (a - c) * MAX // jnp.maximum(a, 1))
        return f, has

    f0, u0 = lane(a0, c0)
    f1, u1 = lane(a1, c1)
    zero = jnp.zeros_like(f0)
    wsum = jnp.where(u0, w0, zero) + jnp.where(u1, w1, zero)
    total = jnp.where(u0, f0 * w0, zero) + jnp.where(u1, f1 * w1, zero)
    if strat_id == 2:  # math.Round of the weighted mean
        q = (2 * total + wsum) // jnp.maximum(2 * wsum, 1)
    else:
        q = total // jnp.maximum(wsum, 1)
    return jnp.where(wsum > 0, q, 0)


# ---------------------------------------------------------------------------
# Shared count→constraint algebra (one definition for every dispatch path)
#
# The scan step (heavy_parts), the wave kernels (ops/wave.py), and any other
# batch-dynamic evaluator differ ONLY in how they produce the per-pod
# BATCH-PEER count tensors; everything downstream of the counts — skew
# checks, min-match, the inter-pod violation/escape ladder, preferred-term
# scoring — is defined once here so the paths cannot drift apart.
# ---------------------------------------------------------------------------


class SpreadDyn(NamedTuple):
    """Batch-peer contributions to pod p's spread counts (all [C, N] i32)."""

    dyn_f: jnp.ndarray  # filter-side counts (bm ∧ te-at-peer ∧ same-domain)
    dyn_host: jnp.ndarray  # score-side per-node counts (bm only)
    dyn_dom: jnp.ndarray  # score-side domain counts (bm ∧ counting-at-peer)


class InterpodDyn(NamedTuple):
    """Batch-peer contributions to pod p's inter-pod state."""

    ip_dyn: jnp.ndarray  # i32 [AT, N] incoming matches per term domain
    viol_b: jnp.ndarray  # bool [N] anti-affinity of committed peers' terms
    sym_b: jnp.ndarray  # i64 [N] symmetric score from committed peers' terms
    any_dyn: jnp.ndarray  # bool [] any committed peer matches an aff term


def spread_constraints(db: DeviceBatch, g: "GangStatics", p, sd: SpreadDyn):
    """Filter verdict + score counts for pod p's spread constraints given
    the batch-peer count contributions (filtering.go:236-362 semantics on
    static existing counts + ``sd``).  Returns (m_spread [N], sp_cnt [C,N],
    c_ok [C,N]) — c_ok per constraint for failure attribution."""
    with jax.named_scope("ktpu/gang/spread_constraints"):
        total = g.sp_dom_cnt[p] + sd.dyn_f  # [C, N]
        big32 = jnp.iinfo(jnp.int32).max
        min_match = jnp.min(jnp.where(g.sp_te[p], total, big32), axis=1)
        min_match = jnp.where(
            (db.tsc_min_domains[p] > 0) & (g.sp_ndom[p] < db.tsc_min_domains[p]),
            0,
            min_match,
        )
        skew = total + g.sp_self[p].astype(I32)[:, None] - min_match[:, None]
        c_ok = (g.sp_dv[p] >= 0) & (
            ~g.sp_dom_pres[p] | (skew <= db.tsc_max_skew[p][:, None])
        )
        m_spread = jnp.all(~g.sp_hard[p][:, None] | c_ok, axis=0)
        sp_cnt = jnp.where(
            g.sp_is_host[p][:, None],
            g.sp_node_cnt[p] + sd.dyn_host,
            g.sp_sc_dom[p] + sd.dyn_dom,
        )  # [C, N]
        return m_spread, sp_cnt, c_ok


def interpod_constraints(g: "GangStatics", p, idyn: InterpodDyn):
    """Filter verdict + raw score for pod p's inter-pod terms given the
    batch-peer contributions (interpodaffinity filtering/scoring over
    static existing counts + ``idyn``).  Returns (m_interpod [N],
    ip_raw [N], anti_viol [AT, N]) — anti_viol per term for attribution."""
    with jax.named_scope("ktpu/gang/interpod_constraints"):
        ip_total = g.ip_dom_cnt[p] + idyn.ip_dyn  # [AT, N]
        topo_present = g.ip_dv[p] >= 0
        anti_viol = g.ip_is_anti[p][:, None] & topo_present & (ip_total > 0)
        viol2 = jnp.any(anti_viol, axis=0)
        aff_ok = jnp.all(
            ~g.ip_is_aff[p][:, None] | (topo_present & (ip_total > 0)), axis=0
        )
        any_match = g.ip_any_static[p] | idyn.any_dyn
        topo_all = jnp.all(~g.ip_is_aff[p][:, None] | topo_present, axis=0)
        escape = jnp.any(g.ip_is_aff[p]) & ~any_match & g.ip_self_all[p]
        ok3 = aff_ok | (escape & topo_all)
        m_interpod = ~g.ip_viol_existing[p] & ~viol2 & ok3 & ~idyn.viol_b
        pref = jnp.sum(
            jnp.where(
                topo_present,
                ip_total.astype(I64) * g.ip_pref_w[p][:, None],
                0,
            ),
            axis=0,
        )
        ip_raw = g.ip_sym[p] + pref + idyn.sym_b.astype(I64)
        return m_interpod, ip_raw, anti_viol


def pod_step(
    dc: DeviceCluster,
    db: DeviceBatch,
    g: "GangStatics",
    p,
    state,
    hv,
    active,
    *,
    check_fit: bool,
    weights: tuple,
    d_cap: int,
    fit_strategy: tuple,
    extra_score=None,
    nom_oh=None,
    nom_prio=None,
    nom_req=None,
    sample_k=None,
    tie_key=None,
    attempt_base=None,
    commit: bool = True,
):
    """One pod's full Filter→Score→Select→commit against ``state`` — the
    single definition of the per-pod decision shared by the gang scan, the
    wave admission scan, and the wave speculation pass (ops/wave.py).  The
    state-dependent constraint tensors arrive in ``hv`` (m_portb, m_spread,
    sp_cnt, m_interpod, ip_raw); how they were produced is the caller's
    business.  ``state`` carries requested [N,Rn] / nonzero [N,2] /
    num_pods [N] / assigned [P] (+ sample_start in sampling mode).  With
    ``commit=False`` the returned state is the input untouched (speculation
    evaluates without placing).  Returns
    (new_state, (choice, n_feas, reason_counts))."""
    P, N = g.static_mask.shape
    Rn = dc.requested.shape[1]
    Rp = db.requests.shape[1]
    C = g.sp_dv.shape[1]
    true_n = jnp.ones((N,), bool)

    with jax.named_scope("ktpu/gang/filter"):
        # ---------------- dynamic filters ----------------
        req = db.requests[p]  # [Rp]
        mask = g.static_mask[p] & hv["m_portb"]
        m_fit = true_n
        if check_fit:
            nom_cnt = 0
            nom_delta = 0
            if nom_oh is not None:
                gate = (nom_prio >= db.priority[p]).astype(I32)  # [G]
                nom_cnt = jnp.einsum("g,gn->n", gate, nom_oh)
                nom_delta = jnp.einsum(
                    "gr,gn->nr", nom_req * gate[:, None], nom_oh
                )  # [N, Rn]
            fits = state["num_pods"] + nom_cnt + 1 <= dc.allowed_pods
            all_zero = jnp.all(req == 0)
            avail = dc.allocatable - state["requested"] - nom_delta  # [N, Rn]
            if Rp > Rn:
                avail = jnp.concatenate(
                    [avail, jnp.zeros((N, Rp - Rn), I32)], axis=1
                )
            conflict = req[None, :] > avail  # [N, Rp]
            # extended-resource lanes only count when actually requested
            scalar_lane = jnp.arange(Rp) >= N_FIXED_LANES
            conflict = conflict & (~scalar_lane | (req > 0))[None, :]
            lane_ok = ~jnp.any(conflict, axis=1)
            m_fit = fits & (all_zero | lane_ok)
            mask = mask & m_fit

        m_portb = hv["m_portb"]
        m_spread = hv["m_spread"]
        m_interpod = hv["m_interpod"]
        mask = mask & m_spread & m_interpod
        feas = mask
        if sample_k is not None:
            # adaptive-sampling cut: keep the first sample_k feasible nodes
            # in ZONE-ROUND-ROBIN rotation order from the carried start
            # index — dc.visit_rank is the nodeTree order
            # (node_tree.go:119-143) that the reference's sampling,
            # rotation, and tie-breaks all ride
            nv = jnp.maximum(dc.n_valid_nodes, 1)
            start = state["sample_start"]
            vr = dc.visit_rank
            valid_vr = vr >= 0
            rank = jnp.where(valid_vr, (vr - start) % nv, N)
            rot = (
                jnp.zeros((N + 1,), bool)
                .at[rank]
                .set(feas & valid_vr, mode="drop")[:N]
            )
            cum = jnp.cumsum(rot.astype(I32))
            keep_rot = rot & (cum <= sample_k)
            feas = (
                jnp.concatenate([keep_rot, jnp.zeros((1,), bool)])[rank]
                & feas
            )
            total_feas = cum[N - 1]
            processed = jnp.where(
                total_feas >= sample_k,
                jnp.sum((cum < sample_k).astype(I32)) + 1,
                nv,
            )
        n_feas = jnp.sum(feas.astype(I32))

        # ---------------- failure diagnosis ----------------
        # Per-kernel rejected-node counts with first-failure attribution in
        # the reference's filter chain order (findNodesThatPassFilters
        # early-exits per node; FitError aggregates counts per reason).
        remaining = dc.node_valid & db.valid[p]
        reason_counts = []
        for comp in (
            g.d_unsched[p],
            g.d_nodename[p],
            g.d_taints[p],
            g.d_nodeaff[p],
            g.d_ports[p] & m_portb,
            g.d_extra[p],
            m_fit,
            m_spread,
            m_interpod,
        ):
            rejected = remaining & ~comp
            reason_counts.append(jnp.sum(rejected.astype(I32)))
            remaining = remaining & comp
        reason_counts = jnp.stack(reason_counts)  # [N_DIAG]

    with jax.named_scope("ktpu/gang/score"):
        # ---------------- scores ----------------
        # NodeResourcesFit scoring strategy on non-zero-defaulted requests
        # (resource_allocation.go:37-115): LeastAllocated (default),
        # MostAllocated, or RequestedToCapacityRatio over cpu/memory.
        # Per resource lane on [N] vectors (module docstring); a0/a1 also
        # feed BalancedAllocation below.
        a0 = dc.allocatable[:, LANE_CPU].astype(I64)
        a1 = dc.allocatable[:, LANE_MEM].astype(I64)
        nz_req = db.nonzero_req[p].astype(I64)
        least = fit_score(
            fit_strategy,
            a0,
            a1,
            state["nonzero"][:, 0].astype(I64) + nz_req[0],
            state["nonzero"][:, 1].astype(I64) + nz_req[1],
        )

        # BalancedAllocation on real requests
        r0 = jnp.minimum(
            state["requested"][:, LANE_CPU].astype(I64)
            + db.requests[p, LANE_CPU].astype(I64),
            a0,
        )
        r1 = jnp.minimum(
            state["requested"][:, LANE_MEM].astype(I64)
            + db.requests[p, LANE_MEM].astype(I64),
            a1,
        )
        d = jnp.abs(r0 * a1 - r1 * a0)
        den = jnp.maximum(a0 * a1, 1)
        balanced = jnp.where(
            (a0 > 0) & (a1 > 0), MAX - (50 * d + den - 1) // den, MAX
        )

        # InterPodAffinity: static symmetric + incoming preferred (with batch
        # contributions) + symmetric from batch-assigned pods' terms —
        # carried in hv.
        ip_raw = hv["ip_raw"]

        # PodTopologySpread score: the count rows come from hv; the
        # log-weight normalization depends on the LIVE feasible set, so it
        # runs here per pod.
        if C:
            sp_raw, sp_valid = _spread_raw(
                dc, db, g, p, feas, hv["sp_cnt"], d_cap
            )
        else:
            sp_raw = jnp.zeros((N,), I64)
            sp_valid = feas

        w_taint, w_naff, w_spread, w_ip, w_fit, w_bal, w_img = weights
        total_score = jnp.zeros((N,), I64)
        if w_taint:
            total_score += w_taint * _norm_default(
                g.sc_taint[p], feas, reverse=True
            )
        if w_naff:
            total_score += w_naff * _norm_default(g.sc_nodeaff[p], feas)
        if w_spread:
            total_score += w_spread * _norm_spread(sp_raw, sp_valid, feas)
        if w_ip:
            total_score += w_ip * _norm_minmax(ip_raw, feas)
        if w_fit:
            total_score += w_fit * least
        if w_bal:
            total_score += w_bal * balanced
        if w_img:
            total_score += w_img * g.sc_image[p]
        if extra_score is not None:
            total_score += extra_score[p]

    with jax.named_scope("ktpu/gang/select"):
        neg = jnp.iinfo(jnp.int64).min
        if tie_key is not None:
            # seeded uniform tie-break: lexicographic (score, hash) argmax
            # — every max-score node equally likely, deterministic per
            # (seed, attempt) (selectHost reservoir analogue)
            k_p = jax.random.fold_in(tie_key, attempt_base + p)
            h = jax.random.bits(k_p, (N,), dtype=jnp.uint32).astype(I64)
            ranked = jnp.where(feas, total_score * (1 << 33) + h, neg)
            choice = jnp.argmax(ranked).astype(I32)
        elif sample_k is not None:
            # compat first-max: among max-score nodes, pick the first in
            # the zone-round-robin VISIT order (the reference appends
            # feasible nodes in nodeTree walk order, so "first max" means
            # first visited, not lowest packed slot)
            ranked = jnp.where(feas, total_score, neg)
            best = jnp.max(ranked)
            tie_rank = jnp.where(feas & (ranked == best), rank, N + 1)
            choice = jnp.argmin(tie_rank).astype(I32)
        else:
            ranked = jnp.where(feas, total_score, neg)
            choice = jnp.argmax(ranked).astype(I32)
        choice = jnp.where((n_feas > 0) & active, choice, ABSENT)
        n_feas = jnp.where(active, n_feas, 0)

    if not commit:
        return state, (choice, n_feas, reason_counts)

    with jax.named_scope("ktpu/gang/commit"):
        # ---------------- commit ----------------
        committed = choice >= 0
        new_state = dict(
            state,
            **usage_carry_update(
                {k: state[k] for k in ("requested", "nonzero", "num_pods")},
                {
                    "requested": db.requests[p][:Rn],
                    "nonzero": db.nonzero_req[p],
                    "num_pods": 1,
                },
                choice,
                committed,
            ),
            # inactive (pad) slots must not clobber row p's assignment.
            # p is the scan/vmap index over the batch axis — in range by
            # construction; mode="drop" (the default, spelled out) documents
            # the out-of-bounds semantics for the slice-clamp rule
            assigned=state["assigned"]
            .at[p]
            .set(jnp.where(active, choice, state["assigned"][p]), mode="drop"),
        )
        if sample_k is not None:
            # nextStartNodeIndex advances by nodes visited, per attempt
            # (schedule_one.go:625), padded batch rows included like the
            # reference's no-op cycles would be skipped: only real pods
            # advance the rotation
            new_state["sample_start"] = jnp.where(
                db.valid[p],
                (state["sample_start"] + processed) % nv,
                state["sample_start"],
            ).astype(I32)
    return new_state, (choice, n_feas, reason_counts)


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, g=GangStatics)
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: axes(sample_k=i32, sample_start=i32, tie_key=key, attempt_base=i32)
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16)
@functools.partial(
    jax.jit,
    static_argnames=("v_cap", "weights", "check_fit", "d_cap", "fit_strategy"),
)
def gang_schedule(
    dc: DeviceCluster,
    db: DeviceBatch,
    g: GangStatics,
    v_cap: int,
    weights: tuple = DEFAULT_WEIGHTS,
    check_fit: bool = True,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    d_cap: int = 8,
    extra_score=None,
    fit_strategy: tuple = DEFAULT_FIT_STRATEGY,
    sample_k=None,
    sample_start=None,
    tie_key=None,
    attempt_base=None,
):
    """Scan the batch in order; each pod sees all prior in-batch placements.

    Bit-compat sampling mode (schedule_one.go:588-699,870-917): when
    sample_k (traced scalar) is given, each pod's Filter result is cut to
    the first sample_k feasible nodes in rotation order from the carried
    start index (nextStartNodeIndex semantics — the carry advances by the
    number of nodes "visited" per pod and is returned in the tallies dict
    under "sample_start").  When tie_key (a jax PRNG key) is given,
    max-score ties break by a per-attempt seeded hash instead of
    first-index — the deterministic, device-reproducible analogue of
    selectHost's reservoir sampling (the host oracle draws the same hash).

    extra_score (optional i64 [P, N]) carries host-plugin Score
    contributions, already normalized and weighted (run_host_scores) — the
    post-device merge point of RunScorePlugins (runtime/framework.go:1177)
    for plugins without kernels.

    nom_* (optional [G] / [G, Rn] arrays) carry NOMINATED pods — preemptors
    whose victims are still terminating.  Their resources are charged to
    their nominated node for every pod of lower-or-equal priority
    (RunFilterPluginsWithNominatedPods, runtime/framework.go:973: nominated
    pods with priority >= the evaluated pod count as present).

    Returns (chosen [P] i32 node index or -1, n_feasible [P] i32).
    """
    P, N = g.static_mask.shape
    Rn = dc.requested.shape[1]
    Rp = db.requests.shape[1]
    C = g.sp_dv.shape[1]
    AT = g.ip_dv.shape[1]
    Kd2 = g.ip_key_cols.shape[0]
    # Nominated-pod node charge matrix, built once outside the scan: per-step
    # work is a tiny [G]·[G,N] contraction instead of a segment scatter.
    if nom_node is not None:
        nom_oh = (
            nom_node[:, None] == jnp.arange(N, dtype=I32)[None, :]
        ).astype(I32)  # [G, N]

    init = dict(
        requested=dc.requested,
        nonzero=dc.nonzero_req,
        num_pods=dc.num_pods,
        assigned=jnp.full((P,), ABSENT, I32),
        # Per-pod outputs ride CARRY buffers written at the pod's own
        # slot instead of scan-stacked ys: jaxlib 0.4.37's SPMD
        # partitioner mis-clamps the ys-stacking dynamic_update_slice
        # (s64 scan counter vs its own s32 shard arithmetic) whenever
        # propagation shards the stacking axis — carry scatter writes at
        # an i32 index partition correctly (`assigned` always has).
        out_choice=jnp.full((P,), ABSENT, I32),
        out_nfeas=jnp.zeros((P,), I64),
        out_rc=jnp.zeros((P, N_DIAG), I64),
    )
    if sample_k is not None:
        init["sample_start"] = jnp.asarray(sample_start, I32)

    true_n = jnp.ones((N,), bool)

    def peer_view(assigned):
        """Shared per-state tensors describing already-placed batch peers."""
        assigned_valid = assigned >= 0  # [J]
        a_clip = jnp.clip(assigned, 0, N - 1)
        # [J, N] node-identity of each assigned batch peer — shared by the
        # port-conflict check and the hostname-topology spread counts.
        eqJ = (a_clip[:, None] == jnp.arange(N, dtype=I32)[None, :]) & (
            assigned_valid[:, None]
        )
        return assigned_valid, eqJ

    def heavy_parts(p, assigned_valid, eqJ):
        """State-dependent tensors whose value cannot change while no
        INTERACTING peer commits: spread/inter-pod masks, count rows, and
        port conflicts.  The per-pod scan calls this every step."""
        with jax.named_scope("ktpu/gang/heavy_parts"):
            av = assigned_valid[None, :]
            m_portb = true_n
            if g.port_b.shape[1]:
                port_conf = jnp.any(g.port_b[p][:, None] & eqJ, axis=0)
                m_portb = ~port_conf

            if C:
                dv = g.sp_dv[p]  # [C, N]
                # value-at-assigned-node via one-hot matmul instead of a gather
                # (TPU gathers serialize; einsum rides the MXU).  Invalid peers
                # produce 0 rows — every consumer is gated on av/bm.
                eqJ_i = eqJ.astype(I32)
                dv_at = jnp.einsum("cn,jn->cj", dv, eqJ_i)  # [C, J]
                te_at = jnp.einsum("cn,jn->cj", g.sp_te[p].astype(I32), eqJ_i) > 0
                bm = g.sp_bmatch[p] & av  # [C, J]
                # Same-domain indicator of each node vs each assigned peer's
                # node, as a fused dense compare (dv space): [C, N, J].
                eq_dom = (
                    (dv[:, :, None] >= 0)
                    & (dv_at[:, None, :] >= 0)
                    & (dv[:, :, None] == dv_at[:, None, :])
                )
                dyn_f = jnp.sum(
                    (eq_dom & (bm & te_at)[:, None, :]).astype(I32), axis=2
                )  # [C, N]
                # score-side counts: _spread_cnt
                dyn_host = jnp.einsum("cj,jn->cn", bm.astype(I32), eqJ_i)
                cg_at = (
                    jnp.einsum(
                        "cn,jn->cj", g.sp_counting[p].astype(I32), eqJ_i
                    )
                    > 0
                )
                dyn_dom = jnp.sum(
                    (eq_dom & (bm & cg_at)[:, None, :]).astype(I32), axis=2
                )
                m_spread, sp_cnt, _ = spread_constraints(
                    db, g, p, SpreadDyn(dyn_f, dyn_host, dyn_dom)
                )
            else:
                m_spread = true_n
                sp_cnt = jnp.zeros((C, N), I32)

            if AT:
                ip_dv = g.ip_dv[p]  # [AT, N]
                ip_dv_at = jnp.einsum("tn,jn->tj", ip_dv, eqJ.astype(I32))
                ip_eq = (
                    (ip_dv[:, :, None] >= 0)
                    & (ip_dv_at[:, None, :] >= 0)
                    & (ip_dv[:, :, None] == ip_dv_at[:, None, :])
                )  # [AT, N, J]
                ip_bm = g.ip_bmatch[p] & av  # [AT, J]
                ip_dyn = jnp.sum((ip_eq & ip_bm[:, None, :]).astype(I32), axis=2)
                any_dyn = jnp.any(g.ip_is_aff[p][:, None] & ip_bm)

                # Batch-assigned peers' terms vs p, factored by distinct topology
                # key so the contraction reads [Kd2, N] columns instead of the
                # full [P, AT, N] domain tensor each step.  dv_ju[j, u] = the
                # topology value at j's assigned node for j's term u.
                m_jp = g.ip_bmatch[:, :, p] & assigned_valid[:, None]  # [J, AT]
                cols_at_a = jnp.einsum(
                    "kn,jn->kj", g.ip_key_cols, eqJ.astype(I32)
                )  # [Kd2, J]
                ki = g.ip_key_idx  # [J, AT]
                ki_clip = jnp.clip(ki, 0, Kd2 - 1)
                ki_oh = (
                    ki_clip[:, :, None] == jnp.arange(Kd2, dtype=I32)[None, None, :]
                ).astype(I32)  # [J, AT, Kd2]
                dv_ju = jnp.einsum("jk,juk->ju", cols_at_a.T, ki_oh)  # [J, AT]
                term_live = m_jp & (ki >= 0) & (dv_ju >= 0)
                g_anti = (term_live & g.ip_is_anti).reshape(-1)  # [J·AT]
                w_sym = jnp.where(term_live, g.ip_sym_w, 0).astype(I32).reshape(-1)
                ki_f = ki_clip.reshape(-1)
                live_f = (ki >= 0).reshape(-1)
                dvf = dv_ju.reshape(-1)
                viol_b = jnp.zeros((N,), bool)
                sym_b = jnp.zeros((N,), I32)
                for k in range(Kd2):
                    in_k = live_f & (ki_f == k)
                    eqk = (dvf[:, None] == g.ip_key_cols[k][None, :]) & (
                        g.ip_key_cols[k] >= 0
                    )[None, :]  # [J·AT, N]
                    viol_b = viol_b | jnp.any(
                        (g_anti & in_k)[:, None] & eqk, axis=0
                    )
                    sym_b = sym_b + jnp.einsum(
                        "t,tn->n",
                        jnp.where(in_k, w_sym, 0),
                        eqk.astype(I32),
                    )
                m_interpod, ip_raw, _ = interpod_constraints(
                    g, p, InterpodDyn(ip_dyn, viol_b, sym_b.astype(I64), any_dyn)
                )
            else:
                m_interpod = true_n
                ip_raw = g.ip_sym[p]
            return dict(
                m_portb=m_portb,
                m_spread=m_spread,
                sp_cnt=sp_cnt,
                m_interpod=m_interpod,
                ip_raw=ip_raw,
            )

    def step(state, p):
        assigned_valid, eqJ = peer_view(state["assigned"])
        hv = heavy_parts(p, assigned_valid, eqJ)
        new_state, (choice, n_feas, reason_counts) = cheap_body(
            state, p, hv, jnp.asarray(True)
        )
        # p in range by construction; mode="drop" for the clamp rule
        new_state["out_choice"] = (
            state["out_choice"].at[p].set(choice, mode="drop")
        )
        new_state["out_nfeas"] = (
            state["out_nfeas"].at[p].set(n_feas, mode="drop")
        )
        new_state["out_rc"] = (
            state["out_rc"].at[p].set(reason_counts, mode="drop")
        )
        return new_state, None

    def cheap_body(state, p, hv, active):
        return pod_step(
            dc,
            db,
            g,
            p,
            state,
            hv,
            active,
            check_fit=check_fit,
            weights=weights,
            d_cap=d_cap,
            fit_strategy=fit_strategy,
            extra_score=extra_score,
            nom_oh=nom_oh if nom_node is not None else None,
            nom_prio=nom_prio,
            nom_req=nom_req,
            sample_k=sample_k,
            tie_key=tie_key,
            attempt_base=attempt_base,
        )

    state, _ = jax.lax.scan(step, init, jnp.arange(P, dtype=I32))
    chosen = state["out_choice"]
    n_feas = state["out_nfeas"]
    reason_counts = state["out_rc"]
    # Final node tallies let the caller chain batches without a host round
    # trip: feed them back as the next DeviceCluster's requested/nonzero/
    # num_pods (the across-batch analogue of the assume cache).
    tallies = {
        "requested": state["requested"],
        "nonzero": state["nonzero"],
        "num_pods": state["num_pods"],
    }
    if sample_k is not None:
        tallies["sample_start"] = state["sample_start"]
    return chosen, n_feas, reason_counts, tallies


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch, hostname_key=i32, extra_mask=bool[P,N])
# ktpu: axes(nom_node=i32[G], nom_prio=i32[G], nom_req=i32[G,Rn], extra_score=i64[P,N])
# ktpu: axes(sp_keys=i32[Kd], sp_cdv_tab=i32[Kd,N], sp_host_cdv=i32[N], ip_keys=i32[Kd2])
# ktpu: axes(sample_k=i32, sample_start=i32, tie_key=key, attempt_base=i32)
# ktpu: accum(i64, i32, bool)
# ktpu: static(v_cap=16)
@functools.partial(
    jax.jit,
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
        "fit_strategy",
    ),
)
def gang_run(
    dc: DeviceCluster,
    db: DeviceBatch,
    hostname_key,
    v_cap: int,
    hard_pod_affinity_weight: int = 1,
    has_interpod: bool = True,
    has_spread: bool = True,
    has_ports: bool = True,
    has_images: bool = True,
    enabled: frozenset = F.ALL_FILTER_KERNELS,
    weights: tuple = DEFAULT_WEIGHTS,
    extra_mask=None,
    nom_node=None,
    nom_prio=None,
    nom_req=None,
    sp_keys=None,
    sp_cdv_tab=None,
    ip_keys=None,
    sp_host_cdv=None,
    d_cap: int = 8,
    extra_score=None,
    fit_strategy: tuple = DEFAULT_FIT_STRATEGY,
    sample_k=None,
    sample_start=None,
    tie_key=None,
    attempt_base=None,
):
    """Fused precompute + scan: ONE device dispatch per batch."""
    g = precompute(
        dc,
        db,
        hostname_key,
        v_cap,
        hard_pod_affinity_weight,
        has_interpod=has_interpod,
        has_spread=has_spread,
        has_ports=has_ports,
        has_images=has_images,
        enabled=enabled,
        extra_mask=extra_mask,
        sp_keys=sp_keys,
        sp_cdv_tab=sp_cdv_tab,
        ip_keys=ip_keys,
        d_cap=d_cap,
        sp_host_cdv=sp_host_cdv,
    )
    return gang_schedule(
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
        extra_score=extra_score,
        fit_strategy=fit_strategy,
        sample_k=sample_k,
        sample_start=sample_start,
        tie_key=tie_key,
        attempt_base=attempt_base,
    )


def _spread_raw(dc, db, g, p, feas, cnt, d_cap):
    """ScheduleAnyway scoring for one pod (podtopologyspread/scoring.go,
    fixed-point log weights), given the per-constraint count rows ``cnt``
    [C, N] (static existing-pod counts + batch contributions — computed in
    heavy_parts; hostname constraints count per assigned node directly, the
    ungated path, domain constraints are gated by the score-counting mask
    at the assigned node).

    The per-domain machinery of the original formulation is replaced by
    dense equivalents:
      * domain presence (``pair_pres``) is dropped outright — a node whose
        score is ever consumed is ``counted`` (feasible ∧ has all soft topo
        keys), and a counted node's own domain trivially contains it, so the
        where(pair_pres, ., 0) gate was a no-op at every consumed node;
      * the count of domains containing counted nodes uses the host-built
        compact domain ids (g.sp_cdv, batch_tables()) as a [C, N, d_cap]
        compare+reduce.
    This half stays per pod in the scan: ``counted`` (and so the
    topologyNormalizingWeight) depends on the LIVE feasible set.
    """
    soft = g.sp_soft[p]  # [C]
    has_soft = jnp.any(soft)

    ignored = feas & ~g.sp_all_keys[p]
    counted = feas & g.sp_all_keys[p]  # filtered, non-ignored
    n_counted = jnp.sum(counted.astype(I32))

    cdv = g.sp_cdv[p]  # [C, N]
    dom_hit = (cdv[:, :, None] == jnp.arange(d_cap, dtype=I32)[None, None, :]) & (
        counted[None, :, None]
    )  # [C, N, D]
    n_dom = jnp.sum(jnp.any(dom_hit, axis=1).astype(I32), axis=1)  # [C]
    size = jnp.where(g.sp_is_host[p], n_counted, n_dom)  # [C]
    w_fx = dc.log_tab[jnp.clip(size, 0, dc.log_tab.shape[0] - 1)]  # [C] i64

    contrib_fx = cnt.astype(I64) * w_fx[:, None] + (
        (db.tsc_max_skew[p].astype(I64) - 1)[:, None] << _FX
    )
    total_fx = jnp.sum(jnp.where(soft[:, None], contrib_fx, 0), axis=0)  # [N]
    k = total_fx >> _FX
    frac = total_fx & ((1 << _FX) - 1)
    half = 1 << (_FX - 1)
    up = (frac > half) | ((frac == half) & ((k & 1) == 1))
    raw = k + up.astype(I64)
    raw = jnp.where(has_soft, raw, 0)
    valid = jnp.where(has_soft, ~ignored, feas)
    return raw, valid
