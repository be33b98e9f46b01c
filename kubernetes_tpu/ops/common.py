"""Shared kernel machinery: device containers and primitive evaluators.

The conjunction-table evaluator here is the device analogue of
labels.Selector.Matches / nodeaffinity.RequiredNodeAffinity.Match in the
reference (staging/src/k8s.io/apimachinery/pkg/labels/selector.go,
component-helpers/scheduling/corev1/nodeaffinity) — one vectorized pass
instead of per-object interpreter loops.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.snapshot.interner import ABSENT, INT_INVALID, PAD
from kubernetes_tpu.snapshot.schema import (
    ConjunctionTable,
    ExistingPodTensors,
    NodeTensors,
    PodBatch,
)
from kubernetes_tpu.snapshot.selectors import (
    OP_DOES_NOT_EXIST,
    OP_EXISTS,
    OP_GT,
    OP_IN,
    OP_LT,
    OP_NOT_IN,
)

I32 = jnp.int32
I64 = jnp.int64


def _register_pytree(cls):
    """Register a plain dataclass of arrays as a JAX pytree."""
    names = [f.name for f in fields(cls)]

    def flatten(x):
        return tuple(getattr(x, n) for n in names), None

    def unflatten(_, children):
        return cls(*children)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@_register_pytree
@dataclass
class DTable:
    """Device copy of a ConjunctionTable."""

    req_key: Any  # i32 [..., R]
    req_op: Any  # i32 [..., R]
    req_vals: Any  # i32 [..., R, V]
    req_rhs: Any  # i32 [..., R]
    term_valid: Any  # bool [...]

    @classmethod
    def host_tree(cls, t: ConjunctionTable) -> "DTable":
        """numpy-leaved instance — callers device_put whole pytrees at once
        (ONE transfer instead of one per field)."""
        return cls(
            req_key=np.asarray(t.req_key, np.int32),
            req_op=np.asarray(t.req_op, np.int32),
            req_vals=np.asarray(t.req_vals, np.int32),
            req_rhs=np.asarray(t.req_rhs, np.int32),
            term_valid=np.asarray(t.term_valid, bool),
        )

    @classmethod
    def from_host(cls, t: ConjunctionTable) -> "DTable":
        from kubernetes_tpu.ops import wire

        return wire.device_put_packed(cls.host_tree(t))


@_register_pytree
@dataclass
class DeviceCluster:
    """HBM-resident cluster snapshot (nodes + placed pods + their terms)."""

    # nodes
    allocatable: Any  # i32 [N, R]
    requested: Any  # i32 [N, R]
    nonzero_req: Any  # i32 [N, 2]
    num_pods: Any  # i32 [N]
    allowed_pods: Any  # i32 [N]
    node_labels: Any  # i32 [N, K]
    val_ints: Any  # i32 [V]
    taint_key: Any  # i32 [N, T]
    taint_val: Any  # i32 [N, T]
    taint_effect: Any  # i32 [N, T]
    unschedulable: Any  # bool [N]
    node_valid: Any  # bool [N]
    used_ppk: Any  # i32 [N, U]
    used_ip: Any  # i32 [N, U]
    used_wild: Any  # bool [N, U]
    img_sizes: Any  # i64 [N, IMG]
    # zone-round-robin visit rank (node_tree.go order; -1 invalid) — the
    # sampling-compat window/rotation and compat tie-breaks read this
    visit_rank: Any  # i32 [N]
    # placed pods
    epod_node: Any  # i32 [E]
    epod_ns: Any  # i32 [E]
    epod_labels: Any  # i32 [E, K]
    epod_valid: Any  # bool [E]
    epod_deleting: Any  # bool [E]
    # flattened (anti-)affinity terms of placed pods
    term_pod: Any  # i32 [M]
    term_kind: Any  # i32 [M]
    term_topo: Any  # i32 [M]
    term_weight: Any  # i32 [M]
    term_table: DTable  # [M, 1, ...]
    term_ns_all: Any  # bool [M]
    term_ns_ids: Any  # i32 [M, NS]
    # scalar ids resolved from the vocab (traced so vocab growth ≠ recompile)
    name_key: Any  # i32  label-key id of metadata.name
    unsched_key: Any  # i32  label-key id of node.kubernetes.io/unschedulable
    empty_val: Any  # i32  label-val id of ""
    n_valid_nodes: Any  # i32  number of real nodes
    log_tab: Any  # i64 [N+2]  fixed-point round(log(i+2)·2^32) table

    @classmethod
    def from_host(cls, nt: NodeTensors, ep: ExistingPodTensors, vocab) -> "DeviceCluster":
        from kubernetes_tpu.ops import wire
        from kubernetes_tpu.snapshot.selectors import METADATA_NAME_KEY

        n = int(nt.valid.sum())
        log_tab = np.round(
            np.log(np.arange(nt.n_cap + 2, dtype=np.float64) + 2.0) * (1 << 32)
        ).astype(np.int64)
        return wire.device_put_packed(cls(
            allocatable=np.asarray(nt.allocatable, np.int32),
            requested=np.asarray(nt.requested, np.int32),
            nonzero_req=np.asarray(nt.nonzero_req, np.int32),
            num_pods=np.asarray(nt.num_pods, np.int32),
            allowed_pods=np.asarray(nt.allowed_pods, np.int32),
            node_labels=np.asarray(nt.label_vals, np.int32),
            val_ints=np.asarray(nt.val_ints, np.int32),
            taint_key=np.asarray(nt.taint_key, np.int32),
            taint_val=np.asarray(nt.taint_val, np.int32),
            taint_effect=np.asarray(nt.taint_effect, np.int32),
            unschedulable=np.asarray(nt.unschedulable, bool),
            node_valid=np.asarray(nt.valid, bool),
            used_ppk=np.asarray(nt.used_ppk, np.int32),
            used_ip=np.asarray(nt.used_ip, np.int32),
            used_wild=np.asarray(nt.used_wild, bool),
            img_sizes=np.asarray(nt.img_sizes, np.int64),
            visit_rank=np.asarray(nt.visit_rank, np.int32),
            epod_node=np.asarray(ep.node_idx, np.int32),
            epod_ns=np.asarray(ep.ns_id, np.int32),
            epod_labels=np.asarray(ep.label_vals, np.int32),
            epod_valid=np.asarray(ep.valid, bool),
            epod_deleting=np.asarray(ep.deleting, bool),
            term_pod=np.asarray(ep.term_pod, np.int32),
            term_kind=np.asarray(ep.term_kind, np.int32),
            term_topo=np.asarray(ep.term_topo_key, np.int32),
            term_weight=np.asarray(ep.term_weight, np.int32),
            term_table=DTable.host_tree(ep.term_table),
            term_ns_all=np.asarray(ep.term_ns_all, bool),
            term_ns_ids=np.asarray(ep.term_ns_ids, np.int32),
            name_key=np.asarray(vocab.label_keys.lookup(METADATA_NAME_KEY), np.int32),
            unsched_key=np.asarray(
                vocab.label_keys.lookup("node.kubernetes.io/unschedulable"), I32
            ),
            empty_val=np.asarray(vocab.label_vals.lookup(""), np.int32),
            n_valid_nodes=np.asarray(n, np.int32),
            log_tab=np.asarray(log_tab),
        ))


@_register_pytree
@dataclass
class DeviceBatch:
    """Pending-pod batch on device."""

    requests: Any  # i32 [P, R]
    nonzero_req: Any  # i32 [P, 2]
    ns_id: Any  # i32 [P]
    priority: Any  # i32 [P]
    labels: Any  # i32 [P, K]
    valid: Any  # bool [P]
    node_sel: DTable  # [P, T, ...]
    pref_node: DTable  # [P, PT, ...]
    pref_weight: Any  # i32 [P, PT]
    tol_key: Any  # i32 [P, TL]
    tol_op: Any  # i32 [P, TL]
    tol_val: Any  # i32 [P, TL]
    tol_effect: Any  # i32 [P, TL]
    tsc_table: DTable  # [P, C, ...]
    tsc_topo: Any  # i32 [P, C]
    tsc_max_skew: Any  # i32 [P, C]
    tsc_hard: Any  # bool [P, C]
    tsc_min_domains: Any  # i32 [P, C]
    tsc_honor_affinity: Any  # bool [P, C]
    tsc_honor_taints: Any  # bool [P, C]
    aff_table: DTable  # [P, AT, ...]
    aff_kind: Any  # i32 [P, AT]
    aff_topo: Any  # i32 [P, AT]
    aff_weight: Any  # i32 [P, AT]
    aff_ns_all: Any  # bool [P, AT]
    aff_ns_ids: Any  # i32 [P, AT, NS]
    target_name_val: Any  # i32 [P]
    want_ppk: Any  # i32 [P, W]
    want_ip: Any  # i32 [P, W]
    want_wild: Any  # bool [P, W]
    img_ids: Any  # i32 [P, I]
    n_containers: Any  # i32 [P]

    @classmethod
    def from_host(cls, pb: PodBatch) -> "DeviceBatch":
        from kubernetes_tpu.ops import wire

        return wire.device_put_packed(cls(
            requests=np.asarray(pb.requests, np.int32),
            nonzero_req=np.asarray(pb.nonzero_req, np.int32),
            ns_id=np.asarray(pb.ns_id, np.int32),
            priority=np.asarray(pb.priority, np.int32),
            labels=np.asarray(pb.label_vals, np.int32),
            valid=np.asarray(pb.valid, bool),
            node_sel=DTable.host_tree(pb.node_sel),
            pref_node=DTable.host_tree(pb.pref_node),
            pref_weight=np.asarray(pb.pref_weight, np.int32),
            tol_key=np.asarray(pb.tol_key, np.int32),
            tol_op=np.asarray(pb.tol_op, np.int32),
            tol_val=np.asarray(pb.tol_val, np.int32),
            tol_effect=np.asarray(pb.tol_effect, np.int32),
            tsc_table=DTable.host_tree(pb.tsc_table),
            tsc_topo=np.asarray(pb.tsc_topo_key, np.int32),
            tsc_max_skew=np.asarray(pb.tsc_max_skew, np.int32),
            tsc_hard=np.asarray(pb.tsc_hard, bool),
            tsc_min_domains=np.asarray(pb.tsc_min_domains, np.int32),
            tsc_honor_affinity=np.asarray(pb.tsc_honor_affinity, bool),
            tsc_honor_taints=np.asarray(pb.tsc_honor_taints, bool),
            aff_table=DTable.host_tree(pb.aff_table),
            aff_kind=np.asarray(pb.aff_kind, np.int32),
            aff_topo=np.asarray(pb.aff_topo_key, np.int32),
            aff_weight=np.asarray(pb.aff_weight, np.int32),
            aff_ns_all=np.asarray(pb.aff_ns_all, bool),
            aff_ns_ids=np.asarray(pb.aff_ns_ids, np.int32),
            target_name_val=np.asarray(pb.target_name_val, np.int32),
            want_ppk=np.asarray(pb.want_ppk, np.int32),
            want_ip=np.asarray(pb.want_ip, np.int32),
            want_wild=np.asarray(pb.want_wild, bool),
            img_ids=np.asarray(pb.img_ids, np.int32),
            n_containers=np.asarray(pb.n_containers, np.int32),
        ))


# ---------------------------------------------------------------------------
# Named-axis schema (consumed by the static analyzer's shape/dtype/shard
# interpreter — `python -m kubernetes_tpu.analysis`, ANALYSIS.md glossary).
# One entry per device dataclass; dims use the canonical axis names
# (P pods, N nodes, Rn/Rp resource lanes, K label keys, V value vocab,
# TA taints, U/UP ports, E placed pods, M terms, NS namespaces, C spread
# slots, A inter-pod slots, NT/PT selector terms, TL tolerations,
# IMG/IP images, L log table).  A trailing underscore marks a dim PRIVATE
# to the class schema (each DTable instance is bucketed independently);
# `*` splices the owning field's lead dims.
# ---------------------------------------------------------------------------

_KTPU_AXES = {
    "DTable": {
        "req_key": "i32[*,Q_]",
        "req_op": "i32[*,Q_]",
        "req_vals": "i32[*,Q_,Y_]",
        "req_rhs": "i32[*,Q_]",
        "term_valid": "bool[*]",
    },
    "DeviceCluster": {
        "allocatable": "i32[N,Rn]",
        "requested": "i32[N,Rn]",
        "nonzero_req": "i32[N,2]",
        "num_pods": "i32[N]",
        "allowed_pods": "i32[N]",
        "node_labels": "i32[N,K]",
        "val_ints": "i32[V]",
        "taint_key": "i32[N,TA]",
        "taint_val": "i32[N,TA]",
        "taint_effect": "i32[N,TA]",
        "unschedulable": "bool[N]",
        "node_valid": "bool[N]",
        "used_ppk": "i32[N,U]",
        "used_ip": "i32[N,U]",
        "used_wild": "bool[N,U]",
        "img_sizes": "i64[N,IMG]",
        "visit_rank": "i32[N]",
        "epod_node": "i32[E]",
        "epod_ns": "i32[E]",
        "epod_labels": "i32[E,K]",
        "epod_valid": "bool[E]",
        "epod_deleting": "bool[E]",
        "term_pod": "i32[M]",
        "term_kind": "i32[M]",
        "term_topo": "i32[M]",
        "term_weight": "i32[M]",
        "term_table": "DTable[M,1]",
        "term_ns_all": "bool[M]",
        "term_ns_ids": "i32[M,NS]",
        "name_key": "i32",
        "unsched_key": "i32",
        "empty_val": "i32",
        "n_valid_nodes": "i32",
        # NOT the node axis: a value-indexed fixed-point log table (its
        # length happens to be N+2) — gathers into it are shard-neutral
        "log_tab": "i64[L]",
    },
    "DeviceBatch": {
        "requests": "i32[P,Rp]",
        "nonzero_req": "i32[P,2]",
        "ns_id": "i32[P]",
        "priority": "i32[P]",
        "labels": "i32[P,K]",
        "valid": "bool[P]",
        "node_sel": "DTable[P,NT]",
        "pref_node": "DTable[P,PT]",
        "pref_weight": "i32[P,PT]",
        "tol_key": "i32[P,TL]",
        "tol_op": "i32[P,TL]",
        "tol_val": "i32[P,TL]",
        "tol_effect": "i32[P,TL]",
        "tsc_table": "DTable[P,C]",
        "tsc_topo": "i32[P,C]",
        "tsc_max_skew": "i32[P,C]",
        "tsc_hard": "bool[P,C]",
        "tsc_min_domains": "i32[P,C]",
        "tsc_honor_affinity": "bool[P,C]",
        "tsc_honor_taints": "bool[P,C]",
        "aff_table": "DTable[P,A]",
        "aff_kind": "i32[P,A]",
        "aff_topo": "i32[P,A]",
        "aff_weight": "i32[P,A]",
        "aff_ns_all": "bool[P,A]",
        "aff_ns_ids": "i32[P,A,NS]",
        "target_name_val": "i32[P]",
        "want_ppk": "i32[P,UP]",
        "want_ip": "i32[P,UP]",
        "want_wild": "bool[P,UP]",
        "img_ids": "i32[P,IP]",
        "n_containers": "i32[P]",
    },
}

# Declared N-axis collectives (shard rule): these helpers deliberately
# cross the node axis — segment-scatters into per-node rows and
# domain-id spaces.  Under a sharded N mesh each becomes a cross-shard
# collective; the multichip refactor (ROADMAP item 2) routes exactly
# this roster through jax collectives.
_KTPU_N_COLLECTIVES = {
    "per_node_counts": "resolved(collective): segment-scatter of per-pod "
    "values into [N] rows — contributions route to the owning node shard "
    "(all-to-all + local scatter-add; integer counts, order-free)",
    "domain_stats": "resolved(collective): segment-reduce of [N] rows "
    "into topology domains and gather back per node — per-shard partial "
    "domain sums psum into the small replicated [D] domain table, then "
    "the per-node gather reads it shard-locally",
    "compact_domain_stats": "resolved(collective): the same aggregate as a "
    "[D, N] compare+reduce over compact domain ids — per-shard partial "
    "domain sums psum into the small replicated [D] domain table, and the "
    "read-back per node (a compare+reduce over D) is shard-local",
}


# ---------------------------------------------------------------------------
# Conjunction evaluation
# ---------------------------------------------------------------------------


def eval_table(table: DTable, label_vals, val_ints):
    """Evaluate every conjunction against every label row.

    table arrays have shape ``lead + (R,)`` / ``lead + (R, V)``; ``label_vals``
    is ``[N, K]``.  Returns matches ``lead + (N,)`` — term_valid is already
    folded in (invalid/padding terms match nothing).

    Requirement semantics mirror labels.Requirement.Matches (selector.go):
    NotIn also matches absent keys; Gt/Lt need integer-parsing both sides.

    The integer of a label (``val_ints[id]``, an element gather: 8.6 ns an
    element on a v5e) belongs to the (label row, key) pair, not to the
    (term, row) pair.  So it is parsed once a call over the ``[K, N]`` label
    columns, and a requirement selects its parsed column by key as it selects
    its value column: K·N gathered elements, not ``prod(lead)``·R·N.  The two
    orders read ``val_ints`` at the same clipped id or yield INT_INVALID
    (``present`` ⇔ key known ∧ ``cols[key] >= 0``), and the parse is done on
    the side with fewer elements, read off the static shapes: after the
    select only where ``prod(lead) * R <= K`` (one pod's few terms against a
    wide label vocabulary).

    The static R/V loops keep the working set at ``lead+(N,)`` buffers (a
    slot's values and their integers, a bool per op), never
    ``lead+(R, V, N)``; the parsed columns add K·N·4 bytes.
    """
    R = table.req_key.shape[-1]
    V = table.req_vals.shape[-1]
    N, K = label_vals.shape
    cols = label_vals.T  # [K, N]

    def parse(ids):
        # label-value ids → their integers; absent (negative) → INT_INVALID
        safe = jnp.clip(ids, 0, val_ints.shape[0] - 1)
        return jnp.where(ids >= 0, val_ints[safe], INT_INVALID)

    parse_cols = math.prod(table.req_key.shape[:-1]) * R > K
    int_cols = parse(cols) if parse_cols else None  # [K, N]

    ok = None
    for r in range(R):
        key = table.req_key[..., r]  # lead
        op = table.req_op[..., r]
        rhs = table.req_rhs[..., r]
        val = gather_at(cols, key)  # lead+(N,)
        present = val >= 0

        in_any = jnp.zeros_like(present)
        for v in range(V):
            rv = table.req_vals[..., r, v]
            in_any = in_any | (present & (val == rv[..., None]) & (rv >= 0)[..., None])

        iv = gather_at(int_cols, key, INT_INVALID) if parse_cols else parse(val)
        int_ok = (iv != INT_INVALID) & (rhs != INT_INVALID)[..., None]

        opb = op[..., None]
        res = jnp.where(
            opb == OP_IN,
            in_any,
            jnp.where(
                opb == OP_NOT_IN,
                ~in_any,
                jnp.where(
                    opb == OP_EXISTS,
                    present,
                    jnp.where(
                        opb == OP_DOES_NOT_EXIST,
                        ~present,
                        jnp.where(
                            opb == OP_GT,
                            int_ok & (iv > rhs[..., None]),
                            int_ok & (iv < rhs[..., None]),  # OP_LT
                        ),
                    ),
                ),
            ),
        )
        res = jnp.where(opb == PAD, True, res)  # padded requirement slot
        ok = res if ok is None else (ok & res)
    if ok is None:
        ok = jnp.ones(table.req_key.shape[:-1] + (N,), bool)
    return ok & table.term_valid[..., None]


def dnf_any(term_matches):
    """OR over the term axis (second-to-last): ``lead+(T, N)`` → ``lead+(N,)``."""
    return jnp.any(term_matches, axis=-2)


def ns_member(ns_all, ns_ids, target_ns):
    """Namespace-set membership: ``lead`` bools / ``lead+(S,)`` ids vs ``[E]``
    namespaces → ``lead+(E,)``."""
    S = ns_ids.shape[-1]
    ok = jnp.broadcast_to(
        ns_all[..., None], ns_all.shape + (target_ns.shape[0],)
    )
    for s in range(S):
        nid = ns_ids[..., s]
        ok = ok | ((nid >= 0)[..., None] & (nid[..., None] == target_ns))
    return ok


# ---------------------------------------------------------------------------
# Segment helpers (per-node and per-domain aggregation)
# ---------------------------------------------------------------------------


def per_node_counts(values_e, node_idx, n_nodes: int):
    """Sum values over placed pods grouped by their node:
    ``lead+(E,)`` → ``lead+(N,)``.  Invalid node_idx rows are dropped."""
    lead = values_e.shape[:-1]
    E = values_e.shape[-1]
    seg = jnp.where((node_idx >= 0) & (node_idx < n_nodes), node_idx, n_nodes)
    flat = values_e.reshape((-1, E))
    out = jax.vmap(
        lambda d: jax.ops.segment_sum(d, seg, num_segments=n_nodes + 1)
    )(flat)
    return out[:, :n_nodes].reshape(lead + (n_nodes,))


def domain_stats(count_n, present_n, dv, v_cap: int):
    """Aggregate per-node values by topology-domain id and read them back
    per node.

    count_n:   lead+(N,) int — per-node quantity to sum per domain
    present_n: lead+(N,) bool — nodes whose domain "exists" (pair tracked)
    dv:        lead+(N,) int — domain id per node (label-value id; <0 absent)
    v_cap:     static domain-id bound (label-value vocab capacity)

    Returns (per_node_total, per_node_domain_present, min_over_present,
    n_domains): the first two gathered back at each node's domain, the last
    two reduced over present domains (min is INT32_MAX when none present).
    """
    lead = count_n.shape[:-1]
    N = count_n.shape[-1]
    seg = jnp.where((dv >= 0) & (dv < v_cap), dv, v_cap)
    flat_cnt = count_n.reshape((-1, N))
    flat_pres = present_n.reshape((-1, N)).astype(I32)
    flat_seg = seg.reshape((-1, N))

    def one(cnt, pres, s):
        tot = jax.ops.segment_sum(cnt, s, num_segments=v_cap + 1)
        dpres = jax.ops.segment_max(pres, s, num_segments=v_cap + 1) > 0
        dpres = dpres.at[v_cap].set(False)
        per_node_tot = tot[s]
        per_node_pres = dpres[s]
        big = jnp.iinfo(jnp.int32).max
        mn = jnp.min(jnp.where(dpres, tot, big))
        ndom = jnp.sum(dpres.astype(I32))
        return per_node_tot, per_node_pres, mn, ndom

    tot, pres, mn, ndom = jax.vmap(one)(flat_cnt, flat_pres, flat_seg)
    return (
        tot.reshape(lead + (N,)),
        pres.reshape(lead + (N,)),
        mn.reshape(lead),
        ndom.reshape(lead),
    )


def compact_domain_stats(count_n, present_n, cdv, d_cap: int):
    """``domain_stats`` over COMPACT domain ids, as a dense compare+reduce.

    ``cdv`` lead+(N,) holds each node's domain as an id in ``[0, d_cap)``
    (``gang.batch_tables``: one map a topology key, the same for every row
    of that key; <0 absent).  With the ids that small a domain's total is a
    masked sum over N and a node reads it back by a masked sum over D:
    elementwise and fusible, where ``domain_stats``' segment ids — private
    to each row once vmapped — lower to a scatter and a gather of
    prod(lead)·N scalars.  The work grows with ``d_cap``.

    Returns (per_node_total, per_node_domain_present, n_domains): the
    first, second and fourth of ``domain_stats``, with 0 / False at a node
    whose domain is absent (there ``domain_stats`` reads its overflow
    segment back).
    """
    d_ids = jnp.arange(d_cap, dtype=I32)[:, None]
    hit = cdv[..., None, :] == d_ids  # lead+(D, N)
    tot_d = jnp.sum(jnp.where(hit, count_n[..., None, :], 0), axis=-1, dtype=I32)
    pres_d = jnp.any(hit & present_n[..., None, :], axis=-1)  # lead+(D,)
    per_node_tot = jnp.sum(
        jnp.where(hit, tot_d[..., None], 0), axis=-2, dtype=I32
    )
    per_node_pres = jnp.any(hit & pres_d[..., None], axis=-2)
    return per_node_tot, per_node_pres, jnp.sum(pres_d.astype(I32), axis=-1)


def gather_rows(matrix, idx):
    """``matrix[idx]`` with negative indices masked to a sentinel row of
    ABSENT values: [N, K] gathered by lead-shaped idx → lead+(K,)."""
    safe = jnp.clip(idx, 0, matrix.shape[0] - 1)
    out = matrix[safe]
    return jnp.where((idx >= 0)[..., None], out, ABSENT)


def gather_at(cols_t, key, fill=ABSENT):
    """cols_t: [K, N]; key: lead → lead+(N,) of label values (``fill`` when
    the key id is out of range/padding)."""
    K = cols_t.shape[0]
    known = (key >= 0) & (key < K)
    safe = jnp.clip(key, 0, K - 1)
    return jnp.where(known[..., None], cols_t[safe], fill)


# ---------------------------------------------------------------------------
# The shared usage carry update — ONE serial-recurrence commit
# ---------------------------------------------------------------------------


# The stage names the scheduling roots put on their device ops
# (``jax.named_scope`` at the function boundaries that exist: metadata only,
# the compiled code is the same with and without them).  Flat and stable:
# ``ktpu/<module>/<stage>``; an op inside two scopes belongs to the inner
# one.  PERF.md section 3 lists them, tests/test_spans.py holds each root's
# lowered text to them, benchmarks/readers/scope.py groups device time by
# them.
STAGES = (
    "ktpu/gang/precompute",  # statics of a batch: masks, counts, scores
    "ktpu/gang/heavy_parts",  # gang scan: batch-peer contractions of a step
    "ktpu/gang/spread_constraints",  # spread verdict + score counts of a pod
    "ktpu/gang/interpod_constraints",  # inter-pod verdict + raw score
    "ktpu/gang/filter",  # pod_step: dynamic filters + failure diagnosis
    "ktpu/gang/score",  # pod_step: scores and their normalisation
    "ktpu/gang/select",  # pod_step: argmax / tie-break
    "ktpu/gang/commit",  # pod_step: the usage carry update
    "ktpu/wave/speculation",  # wave pass 1: every pod against the snapshot
    "ktpu/wave/admission",  # wave pass 2: factored deltas, carry, attribution
    "ktpu/chain/append",  # chain_dispatch: splice of the committed pods
    "ktpu/resident/round",  # resident_run: one speculation/admission round
    "ktpu/fastpath/sig_step",  # sig_scan / resident serial tail: one pod
    "ktpu/fastpath/static_eval",  # static filters + raw scores per signature
)


def usage_carry_update(rows, deltas, nodes, live):
    """THE per-commit node-usage update shared by every serial-recurrence
    replayer: the gang scan / wave admission / workloads admission (via
    gang.pod_step), the sig_scan serial tail (fastpath.make_sig_step), and
    the resident fixed point's round commit (ops/resident.py).

    rows:   dict name → [N, ...] carried usage tensor
    deltas: dict name → per-commit row delta (broadcastable against the
            trailing dims of rows[name]; scalar for counters)
    nodes:  committed node index — a scalar i32 choice, or an [W] window of
            per-slot choices (the resident loop commits a whole agreement
            prefix at once)
    live:   bool commit gate, same leading shape as ``nodes``

    Scalar commits are scatter-free rank-1 one-hot updates — scan bodies
    must never scatter (the TPU op-latency discipline of ops/gang.py).
    Windowed commits scatter-add: within a resident round each walk
    position commits at most once, so the adds are disjoint and the result
    equals replaying the scalar form per slot.
    """
    if nodes.ndim == 0:
        N = next(iter(rows.values())).shape[0]
        onehot = (jnp.arange(N, dtype=I32) == nodes) & live
        out = {}
        # ktpu: allow(jit-boundary) — rows' KEYS are static python
        # structure fixed per call site; only the values are traced
        for k, row in rows.items():
            d = jnp.asarray(deltas[k], row.dtype)
            oh = onehot.reshape((N,) + (1,) * (row.ndim - 1)).astype(row.dtype)
            out[k] = row + oh * d
        return out
    out = {}
    # ktpu: allow(jit-boundary) — rows' KEYS are static python structure
    # fixed per call site; only the values are traced
    for k, row in rows.items():
        d = jnp.asarray(deltas[k], row.dtype)
        gate = live.reshape(live.shape + (1,) * (row.ndim - 1))
        d = jnp.broadcast_to(d, nodes.shape + row.shape[1:]) * gate.astype(
            row.dtype
        )
        out[k] = row.at[nodes].add(d)
    return out
