"""Scalar Filter semantics (golden model).

Each filter returns None (fits) or a reason string mirroring the reference's
Status messages.  File:line citations point at the reference implementation
whose behavior is reproduced.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import labels as k8slabels
from .resource import Resource
from .types import (
    Node,
    Pod,
    PodAffinityTerm,
    TAINT_NO_EXECUTE,
    TAINT_NO_SCHEDULE,
    Toleration,
    find_untolerated_taint,
    required_node_affinity_matches,
)
from .state import NodeState, OracleState

REASON_NODE_NAME = "node(s) didn't match the requested node name"
REASON_UNSCHEDULABLE = "node(s) were unschedulable"
REASON_AFFINITY = "node(s) didn't match Pod's node affinity/selector"
REASON_TAINT = "node(s) had untolerated taint"
REASON_PODS_LIMIT = "Too many pods"
REASON_PORTS = "node(s) didn't have free ports for the requested pod ports"
REASON_EXISTING_ANTI = (
    "node(s) didn't satisfy existing pods anti-affinity rules"
)
REASON_POD_AFFINITY = "node(s) didn't match pod affinity rules"
REASON_POD_ANTI = "node(s) didn't match pod anti-affinity rules"
REASON_SPREAD = "node(s) didn't match pod topology spread constraints"
REASON_SPREAD_LABEL = (
    "node(s) didn't match pod topology spread constraints (missing required label)"
)


def insufficient(resource: str) -> str:
    return f"Insufficient {resource}"


# ---------------------------------------------------------------------------
# NodeName (plugins/nodename/node_name.go)
# ---------------------------------------------------------------------------


def filter_node_name(pod: Pod, ns: NodeState) -> Optional[str]:
    if pod.node_name and pod.node_name != ns.node.name:
        return REASON_NODE_NAME
    return None


# ---------------------------------------------------------------------------
# NodeUnschedulable (plugins/nodeunschedulable/node_unschedulable.go)
# ---------------------------------------------------------------------------

_UNSCHEDULABLE_TAINT_KEY = "node.kubernetes.io/unschedulable"


def filter_node_unschedulable(pod: Pod, ns: NodeState) -> Optional[str]:
    if not ns.node.unschedulable:
        return None
    # Tolerated iff pod tolerates the synthetic unschedulable:NoSchedule taint.
    from .types import Taint

    t = Taint(key=_UNSCHEDULABLE_TAINT_KEY, effect=TAINT_NO_SCHEDULE)
    if any(tol.tolerates(t) for tol in pod.tolerations):
        return None
    return REASON_UNSCHEDULABLE


# ---------------------------------------------------------------------------
# NodeResourcesFit (plugins/noderesources/fit.go:423-503)
# ---------------------------------------------------------------------------


def filter_node_resources(
    pod: Pod,
    ns: NodeState,
    ignored_extended_prefixes: Tuple[str, ...] = (),
) -> List[str]:
    """Returns ALL insufficient-resource reasons (fitsRequest returns the
    full list, fit.go:460)."""
    reasons: List[str] = []
    alloc = ns.node.allocatable
    if len(ns.pods) + 1 > (alloc.allowed_pod_number or 110):
        reasons.append(REASON_PODS_LIMIT)
    req = pod.compute_requests()
    if (
        req.milli_cpu == 0
        and req.memory == 0
        and req.ephemeral_storage == 0
        and not req.scalars
    ):
        return reasons
    if req.milli_cpu > alloc.milli_cpu - ns.requested.milli_cpu:
        reasons.append(insufficient("cpu"))
    if req.memory > alloc.memory - ns.requested.memory:
        reasons.append(insufficient("memory"))
    if req.ephemeral_storage > alloc.ephemeral_storage - ns.requested.ephemeral_storage:
        reasons.append(insufficient("ephemeral-storage"))
    for name, v in req.scalars.items():
        if any(name.startswith(p) for p in ignored_extended_prefixes):
            continue
        if v > alloc.scalars.get(name, 0) - ns.requested.scalars.get(name, 0):
            reasons.append(insufficient(name))
    return reasons


# ---------------------------------------------------------------------------
# NodeAffinity (plugins/nodeaffinity/node_affinity.go:182-203)
# ---------------------------------------------------------------------------


def filter_node_affinity(pod: Pod, ns: NodeState) -> Optional[str]:
    if not required_node_affinity_matches(pod, ns.node):
        return REASON_AFFINITY
    return None


# ---------------------------------------------------------------------------
# TaintToleration (plugins/tainttoleration/taint_toleration.go:103-113)
# ---------------------------------------------------------------------------


def filter_taints(pod: Pod, ns: NodeState) -> Optional[str]:
    t = find_untolerated_taint(
        ns.node.taints, pod.tolerations, (TAINT_NO_SCHEDULE, TAINT_NO_EXECUTE)
    )
    if t is not None:
        return f"{REASON_TAINT} {{{t.key}: {t.value}}}"
    return None


# ---------------------------------------------------------------------------
# NodePorts (plugins/nodeports/node_ports.go)
# ---------------------------------------------------------------------------


def _ports_conflict(wanted, existing) -> bool:
    # Conflict when protocol+port equal and host IPs overlap (0.0.0.0 ⊇ all).
    if wanted.protocol != existing.protocol or wanted.host_port != existing.host_port:
        return False
    wip = wanted.host_ip or "0.0.0.0"
    eip = existing.host_ip or "0.0.0.0"
    return wip == eip or wip == "0.0.0.0" or eip == "0.0.0.0"


def filter_node_ports(pod: Pod, ns: NodeState) -> Optional[str]:
    wanted = pod.host_ports()
    if not wanted:
        return None
    existing = [p for ep in ns.pods for p in ep.host_ports()]
    for w in wanted:
        if any(_ports_conflict(w, e) for e in existing):
            return REASON_PORTS
    return None


# ---------------------------------------------------------------------------
# InterPodAffinity (plugins/interpodaffinity/filtering.go)
# ---------------------------------------------------------------------------


def _term_namespaces(term: PodAffinityTerm, pod: Pod, state: OracleState) -> Optional[set]:
    """Namespace set the term selects; None ⇒ all namespaces."""
    names = set(term.namespaces or ())
    if term.namespace_selector is not None:
        sel = k8slabels.selector_from_label_selector(term.namespace_selector)
        if sel.empty:
            return None  # empty selector ⇒ all namespaces
        for ns_name, lbls in state.namespace_labels.items():
            if sel.matches(lbls):
                names.add(ns_name)
    if not names and term.namespace_selector is None:
        names = {pod.namespace}
    return names


def _term_matches_pod(
    term: PodAffinityTerm, candidate: Pod, incoming: Pod, state: OracleState
) -> bool:
    nss = _term_namespaces(term, incoming, state)
    if nss is not None and candidate.namespace not in nss:
        return False
    sel = k8slabels.selector_from_label_selector(term.label_selector)
    return sel.matches(candidate.labels)


def _required_terms(pod: Pod, anti: bool) -> Tuple[PodAffinityTerm, ...]:
    if not pod.affinity:
        return ()
    a = pod.affinity.pod_anti_affinity if anti else pod.affinity.pod_affinity
    if not a:
        return ()
    return a.required_during_scheduling_ignored_during_execution


def filter_interpod_affinity(
    pod: Pod, ns: NodeState, state: OracleState
) -> Optional[str]:
    """satisfyExistingPodsAntiAffinity + satisfyPodAntiAffinity +
    satisfyPodAffinity (filtering.go:306-365)."""
    node = ns.node

    # 1. Existing pods' required anti-affinity terms matching the incoming pod
    #    forbid nodes in the same topology domain as the existing pod.
    #    Walk only the placed pods that HAVE such terms (state-level cache,
    #    the reference's precomputed existing-anti map, filtering.go:141).
    for ens, epod, terms in state.anti_term_pods():
        for term in terms:
            if not _term_matches_pod(term, pod, epod, state):
                continue
            ev = ens.node.labels.get(term.topology_key)
            nv = node.labels.get(term.topology_key)
            if ev is not None and nv is not None and ev == nv:
                return REASON_EXISTING_ANTI

    # 2. Incoming pod's required anti-affinity vs existing pods.
    for term in _required_terms(pod, anti=True):
        nv = node.labels.get(term.topology_key)
        if nv is None:
            continue
        for ens in state.nodes.values():
            ev = ens.node.labels.get(term.topology_key)
            if ev != nv:
                continue
            for epod in ens.pods:
                if _term_matches_pod(term, epod, pod, state):
                    return REASON_POD_ANTI

    # 3. Incoming pod's required affinity: every term needs a matching
    #    existing pod co-located in the term's topology (filtering.go:336).
    aff_terms = _required_terms(pod, anti=False)
    if aff_terms:
        any_match_anywhere = False
        all_satisfied = True
        for term in aff_terms:
            nv = node.labels.get(term.topology_key)
            if nv is None:
                return REASON_POD_AFFINITY  # all topology labels must exist
            satisfied = False
            for ens in state.nodes.values():
                ev = ens.node.labels.get(term.topology_key)
                for epod in ens.pods:
                    if _term_matches_pod(term, epod, pod, state):
                        any_match_anywhere = True
                        if ev is not None and ev == nv:
                            satisfied = True
            if not satisfied:
                all_satisfied = False
        if not all_satisfied:
            # First-pod-in-series escape hatch: no pod anywhere matches any
            # term AND the pod matches all its own terms.
            if not any_match_anywhere and all(
                _term_matches_pod(t, pod, pod, state) for t in aff_terms
            ):
                return None
            return REASON_POD_AFFINITY
    return None


# ---------------------------------------------------------------------------
# PodTopologySpread (plugins/podtopologyspread/filtering.go)
# ---------------------------------------------------------------------------


def _spread_selector_matches(tsc, target: Pod, incoming: Pod) -> bool:
    sel = k8slabels.selector_from_label_selector(tsc.label_selector)
    if not sel.matches(target.labels):
        return False
    for key in tsc.match_label_keys or ():
        if key in incoming.labels and target.labels.get(key) != incoming.labels[key]:
            return False
    return True


def _node_eligible_for_constraint(tsc, pod: Pod, node: Node) -> bool:
    """matchNodeInclusionPolicies (common.go)."""
    if tsc.node_affinity_policy == "Honor":
        if not required_node_affinity_matches(pod, node):
            return False
    if tsc.node_taints_policy == "Honor":
        if find_untolerated_taint(node.taints, pod.tolerations) is not None:
            return False
    return True


def spread_pair_counts(
    pod: Pod, state: OracleState
) -> Dict[Tuple[str, str], int]:
    """TpPairToMatchNum over eligible nodes (calcPreFilterState)."""
    constraints = [
        c
        for c in pod.topology_spread_constraints
        if c.when_unsatisfiable == "DoNotSchedule"
    ]
    counts: Dict[Tuple[str, str], int] = {}
    for ens in state.nodes.values():
        node = ens.node
        if not all(c.topology_key in node.labels for c in constraints):
            continue
        for c in constraints:
            if not _node_eligible_for_constraint(c, pod, node):
                continue
            pair = (c.topology_key, node.labels[c.topology_key])
            n = sum(
                1
                for ep in ens.pods
                if ep.namespace == pod.namespace
                and ep.deletion_timestamp is None
                and _spread_selector_matches(c, ep, pod)
            )
            counts[pair] = counts.get(pair, 0) + n
    return counts


def filter_topology_spread(
    pod: Pod,
    ns: NodeState,
    state: OracleState,
    pair_counts: Optional[Dict[Tuple[str, str], int]] = None,
) -> Optional[str]:
    constraints = [
        c
        for c in pod.topology_spread_constraints
        if c.when_unsatisfiable == "DoNotSchedule"
    ]
    if not constraints:
        return None
    counts = pair_counts if pair_counts is not None else spread_pair_counts(pod, state)
    node = ns.node
    for c in constraints:
        tp_val = node.labels.get(c.topology_key)
        if tp_val is None:
            return REASON_SPREAD_LABEL
        self_match = 1 if _spread_selector_matches(c, pod, pod) else 0
        pair = (c.topology_key, tp_val)
        if pair not in counts:
            # Node's domain wasn't tracked at PreFilter (node ineligible);
            # the reference skips the constraint then (filtering.go:340).
            continue
        match_num = counts[pair]
        domain_counts = [v for (k, _), v in counts.items() if k == c.topology_key]
        min_match = min(domain_counts) if domain_counts else 0
        if c.min_domains and len(domain_counts) < c.min_domains:
            min_match = 0
        skew = match_num + self_match - min_match
        if skew > c.max_skew:
            return REASON_SPREAD
    return None
