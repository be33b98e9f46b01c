"""Scalar Score semantics (golden model).

Every function returns raw per-node int64 scores plus (where the reference
has one) a normalize step, reproducing the exact integer/float arithmetic so
device kernels can be bit-checked against it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from . import labels as k8slabels
from .types import (
    Pod,
    TAINT_PREFER_NO_SCHEDULE,
    node_selector_term_matches,
)
from .filters import (
    _required_terms,
    _spread_selector_matches,
    _term_matches_pod,
    _node_eligible_for_constraint,
)
from .state import NodeState, OracleState

MAX_NODE_SCORE = 100


def default_normalize(scores: List[int], reverse: bool = False) -> List[int]:
    """plugins/helper/normalize_score.go DefaultNormalizeScore."""
    max_count = max(scores) if scores else 0
    if max_count == 0:
        return [MAX_NODE_SCORE if reverse else s for s in scores]
    out = []
    for s in scores:
        v = MAX_NODE_SCORE * s // max_count
        if reverse:
            v = MAX_NODE_SCORE - v
        out.append(v)
    return out


# ---------------------------------------------------------------------------
# NodeResourcesFit — LeastAllocated (noderesources/least_allocated.go:29-60)
# ---------------------------------------------------------------------------


def _alloc_and_requested(
    pod: Pod, ns: NodeState, resource: str, use_requested: bool
) -> Tuple[int, int]:
    """resource_allocation.go:89 calculateResourceAllocatableRequest."""
    req = pod.compute_requests()
    pod_req = req.non_zero_defaulted() if not use_requested else req
    node_req = ns.requested if use_requested else ns.non_zero_requested
    if resource == "cpu":
        return ns.node.allocatable.milli_cpu, node_req.milli_cpu + pod_req.milli_cpu
    if resource == "memory":
        return ns.node.allocatable.memory, node_req.memory + pod_req.memory
    if resource == "ephemeral-storage":
        return (
            ns.node.allocatable.ephemeral_storage,
            ns.requested.ephemeral_storage + req.ephemeral_storage,
        )
    # extended: bypass when pod doesn't request it
    if req.scalars.get(resource, 0) == 0:
        return 0, 0
    if resource not in ns.node.allocatable.scalars:
        return 0, 0
    return (
        ns.node.allocatable.scalars[resource],
        ns.requested.scalars.get(resource, 0) + req.scalars[resource],
    )


def score_least_allocated(
    pod: Pod,
    ns: NodeState,
    resources: Sequence[Tuple[str, int]] = (("cpu", 1), ("memory", 1)),
) -> int:
    node_score = 0
    weight_sum = 0
    for name, weight in resources:
        alloc, requested = _alloc_and_requested(pod, ns, name, use_requested=False)
        if alloc == 0:
            continue
        if requested > alloc:
            r = 0
        else:
            r = (alloc - requested) * MAX_NODE_SCORE // alloc
        node_score += r * weight
        weight_sum += weight
    if weight_sum == 0:
        return 0
    return node_score // weight_sum


def score_most_allocated(
    pod: Pod,
    ns: NodeState,
    resources: Sequence[Tuple[str, int]] = (("cpu", 1), ("memory", 1)),
) -> int:
    """noderesources/most_allocated.go: requested*100/capacity, 0 if over."""
    node_score = 0
    weight_sum = 0
    for name, weight in resources:
        alloc, requested = _alloc_and_requested(pod, ns, name, use_requested=False)
        if alloc == 0:
            continue
        r = 0 if requested > alloc else requested * MAX_NODE_SCORE // alloc
        node_score += r * weight
        weight_sum += weight
    if weight_sum == 0:
        return 0
    return node_score // weight_sum


def broken_linear(points: Sequence[Tuple[int, int]], p: int) -> int:
    """helper.BuildBrokenLinearFunction (plugins/helper/shape_score.go:40)
    with Go's truncating integer division."""
    for i, (x1, y1) in enumerate(points):
        if p <= x1:
            if i == 0:
                return points[0][1]
            x0, y0 = points[i - 1]
            num = (y1 - y0) * (p - x0)
            den = x1 - x0
            q = num // den if num >= 0 else -((-num) // den)
            return y0 + q
    return points[-1][1]


def score_requested_to_capacity_ratio(
    pod: Pod,
    ns: NodeState,
    shape: Sequence[Tuple[int, int]],
    resources: Sequence[Tuple[str, int]] = (("cpu", 1), ("memory", 1)),
) -> int:
    """noderesources/requested_to_capacity_ratio.go:32-58: per-resource
    broken-linear score over utilization (shape scores pre-scaled to the
    0-100 range), weight-averaged over resources with a positive score;
    math.Round on the final mean."""
    node_score = 0
    weight_sum = 0
    for name, weight in resources:
        alloc, requested = _alloc_and_requested(pod, ns, name, use_requested=False)
        if alloc == 0:
            continue
        if requested > alloc:
            util = MAX_NODE_SCORE
        else:
            util = requested * MAX_NODE_SCORE // alloc
        r = broken_linear(shape, util)
        if r > 0:
            node_score += r * weight
            weight_sum += weight
    if weight_sum == 0:
        return 0
    return (2 * node_score + weight_sum) // (2 * weight_sum)


# ---------------------------------------------------------------------------
# NodeResourcesBalancedAllocation (balanced_allocation.go:138-160)
# ---------------------------------------------------------------------------


def score_balanced_allocation(
    pod: Pod,
    ns: NodeState,
    resources: Sequence[str] = ("cpu", "memory"),
) -> int:
    fractions: List[float] = []
    for name in resources:
        alloc, requested = _alloc_and_requested(pod, ns, name, use_requested=True)
        if alloc == 0:
            continue
        f = min(requested / alloc, 1.0)
        fractions.append(f)
    if len(fractions) == 2:
        std = abs(fractions[0] - fractions[1]) / 2
    elif len(fractions) > 2:
        mean = sum(fractions) / len(fractions)
        std = math.sqrt(sum((f - mean) ** 2 for f in fractions) / len(fractions))
    else:
        std = 0.0
    return int((1 - std) * MAX_NODE_SCORE)


# ---------------------------------------------------------------------------
# NodeAffinity preferred terms (nodeaffinity/node_affinity.go:239)
# ---------------------------------------------------------------------------


def score_node_affinity(pod: Pod, ns: NodeState) -> int:
    score = 0
    if pod.affinity and pod.affinity.node_affinity:
        for t in (
            pod.affinity.node_affinity.preferred_during_scheduling_ignored_during_execution
        ):
            if t.weight and node_selector_term_matches(t.preference, ns.node):
                score += t.weight
    return score


def normalize_node_affinity(scores: List[int]) -> List[int]:
    return default_normalize(scores, reverse=False)


# ---------------------------------------------------------------------------
# TaintToleration (tainttoleration/taint_toleration.go:164-196)
# ---------------------------------------------------------------------------


def score_taint_toleration(pod: Pod, ns: NodeState) -> int:
    """Count of intolerable PreferNoSchedule taints (lower is better)."""
    tolerations = [
        t
        for t in pod.tolerations
        if t.effect == "" or t.effect == TAINT_PREFER_NO_SCHEDULE
    ]
    count = 0
    for taint in ns.node.taints:
        if taint.effect != TAINT_PREFER_NO_SCHEDULE:
            continue
        if not any(t.tolerates(taint) for t in tolerations):
            count += 1
    return count


def normalize_taint_toleration(scores: List[int]) -> List[int]:
    return default_normalize(scores, reverse=True)


# ---------------------------------------------------------------------------
# InterPodAffinity (interpodaffinity/scoring.go)
# ---------------------------------------------------------------------------


def _preferred_terms(pod: Pod, anti: bool):
    if not pod.affinity:
        return ()
    a = pod.affinity.pod_anti_affinity if anti else pod.affinity.pod_affinity
    if not a:
        return ()
    return a.preferred_during_scheduling_ignored_during_execution


def score_interpod_affinity_all(
    pod: Pod,
    state: OracleState,
    node_names: Sequence[str],
    hard_pod_affinity_weight: int = 1,
    ignore_preferred_terms_of_existing: bool = False,
) -> List[int]:
    """Raw scores for each node (scoring.go:50-224 processExistingPod +
    topology aggregation). Positive for affinity, negative for anti."""
    topo_score: Dict[Tuple[str, str], int] = {}

    def bump(topo_key: str, node, w: int):
        v = node.labels.get(topo_key)
        if v is not None and w != 0:
            topo_score[(topo_key, v)] = topo_score.get((topo_key, v), 0) + w

    has_constraints = bool(
        _preferred_terms(pod, False)
        or _preferred_terms(pod, True)
        or _required_terms(pod, False)
        or _required_terms(pod, True)
    )

    for ens in state.nodes.values():
        enode = ens.node
        for epod in ens.pods:
            e_has_required_aff = bool(_required_terms(epod, False))
            e_has_pref = bool(
                _preferred_terms(epod, False) or _preferred_terms(epod, True)
            )
            # The reference only processes existing pods that have affinity
            # constraints, or all pods when the incoming pod has constraints
            # (scoring.go PreScore: podsToProcess).
            if not (has_constraints or e_has_required_aff or e_has_pref):
                continue
            # incoming preferred terms vs existing pod
            for wt in _preferred_terms(pod, False):
                if _term_matches_pod(wt.pod_affinity_term, epod, pod, state):
                    bump(wt.pod_affinity_term.topology_key, enode, wt.weight)
            for wt in _preferred_terms(pod, True):
                if _term_matches_pod(wt.pod_affinity_term, epod, pod, state):
                    bump(wt.pod_affinity_term.topology_key, enode, -wt.weight)
            # symmetry: existing pod's required affinity terms matching pod
            if hard_pod_affinity_weight > 0:
                for term in _required_terms(epod, False):
                    if _term_matches_pod(term, pod, epod, state):
                        bump(term.topology_key, enode, hard_pod_affinity_weight)
            # symmetry: existing pod's preferred terms matching pod
            if not ignore_preferred_terms_of_existing:
                for wt in _preferred_terms(epod, False):
                    if _term_matches_pod(wt.pod_affinity_term, pod, epod, state):
                        bump(wt.pod_affinity_term.topology_key, enode, wt.weight)
                for wt in _preferred_terms(epod, True):
                    if _term_matches_pod(wt.pod_affinity_term, pod, epod, state):
                        bump(wt.pod_affinity_term.topology_key, enode, -wt.weight)

    out = []
    for name in node_names:
        node = state.nodes[name].node
        s = 0
        for (k, v), w in topo_score.items():
            if node.labels.get(k) == v:
                s += w
        out.append(s)
    return out


def normalize_interpod_affinity(scores: List[int]) -> List[int]:
    """scoring.go:265 NormalizeScore: map [min,max] → [0,100]."""
    if not scores:
        return scores
    mx, mn = max(scores), min(scores)
    diff = mx - mn
    out = []
    for s in scores:
        if diff == 0:
            out.append(0)
        else:
            out.append(int(MAX_NODE_SCORE * (s - mn) / diff))
    return out


# ---------------------------------------------------------------------------
# PodTopologySpread (podtopologyspread/scoring.go)
# ---------------------------------------------------------------------------

HOSTNAME_LABEL = "kubernetes.io/hostname"


def score_topology_spread_all(
    pod: Pod,
    state: OracleState,
    filtered_node_names: Sequence[str],
) -> List[int]:
    """Raw scores (matching-pod counts weighted by log-domain-size) for the
    filtered nodes; pair with normalize_topology_spread."""
    constraints = [
        c
        for c in pod.topology_spread_constraints
        if c.when_unsatisfiable == "ScheduleAnyway"
    ]
    if not constraints:
        return [0] * len(filtered_node_names)

    filtered = [state.nodes[n] for n in filtered_node_names]
    ignored = set()
    pair_counts: Dict[Tuple[str, str], int] = {}
    topo_size = [0] * len(constraints)
    for ns in filtered:
        labels = ns.node.labels
        if not all(c.topology_key in labels for c in constraints):
            ignored.add(ns.node.name)
            continue
        for i, c in enumerate(constraints):
            if c.topology_key == HOSTNAME_LABEL:
                continue
            pair = (c.topology_key, labels[c.topology_key])
            if pair not in pair_counts:
                pair_counts[pair] = 0
                topo_size[i] += 1

    weights = []
    for i, c in enumerate(constraints):
        sz = topo_size[i]
        if c.topology_key == HOSTNAME_LABEL:
            sz = len(filtered) - len(ignored)
        weights.append(math.log(sz + 2))

    # Count matching pods over ALL nodes (PreScore walks allNodes).
    for ens in state.nodes.values():
        labels = ens.node.labels
        if not all(c.topology_key in labels for c in constraints):
            continue
        for c in constraints:
            if not _node_eligible_for_constraint(c, pod, ens.node):
                continue
            pair = (c.topology_key, labels[c.topology_key])
            if pair not in pair_counts:
                continue
            pair_counts[pair] += sum(
                1
                for ep in ens.pods
                if ep.namespace == pod.namespace
                and ep.deletion_timestamp is None
                and _spread_selector_matches(c, ep, pod)
            )

    out = []
    for ns in filtered:
        if ns.node.name in ignored:
            out.append(None)  # invalidScore marker
            continue
        score = 0.0
        labels = ns.node.labels
        for i, c in enumerate(constraints):
            tp_val = labels.get(c.topology_key)
            if tp_val is None:
                continue
            if c.topology_key == HOSTNAME_LABEL:
                cnt = sum(
                    1
                    for ep in ns.pods
                    if ep.namespace == pod.namespace
                    and ep.deletion_timestamp is None
                    and _spread_selector_matches(c, ep, pod)
                )
            else:
                cnt = pair_counts.get((c.topology_key, tp_val), 0)
            score += cnt * weights[i] + (c.max_skew - 1)
        out.append(int(round(score)))
    return out


def normalize_topology_spread(scores: List[Optional[int]]) -> List[int]:
    """scoring.go:227 NormalizeScore (None = ignored node → 0)."""
    valid = [s for s in scores if s is not None]
    if not valid:
        return [0 for _ in scores]
    mn, mx = min(valid), max(valid)
    out = []
    for s in scores:
        if s is None:
            out.append(0)
        elif mx == 0:
            out.append(MAX_NODE_SCORE)
        else:
            out.append(MAX_NODE_SCORE * (mx + mn - s) // mx)
    return out


# ---------------------------------------------------------------------------
# ImageLocality (imagelocality/image_locality.go:54-96)
# ---------------------------------------------------------------------------

_MB = 1024 * 1024
_MIN_THRESHOLD = 23 * _MB
_MAX_CONTAINER_THRESHOLD = 1000 * _MB


def score_image_locality(pod: Pod, ns: NodeState, state: OracleState) -> int:
    total_nodes = len(state.nodes)
    if total_nodes == 0 or not pod.images:
        return 0
    sum_scores = 0
    for image in pod.images:
        if image in ns.node.images:
            spread = sum(
                1 for e in state.nodes.values() if image in e.node.images
            )
            sum_scores += int(ns.node.images[image] * spread / total_nodes)
    # image_locality.go: init containers count toward the thresholds too.
    num_containers = max(len(pod.containers) + len(pod.init_containers), 1)
    max_threshold = _MAX_CONTAINER_THRESHOLD * num_containers
    min_threshold = _MIN_THRESHOLD * num_containers
    if sum_scores < min_threshold:
        sum_scores = min_threshold
    elif sum_scores > max_threshold:
        sum_scores = max_threshold
    return int(
        MAX_NODE_SCORE * (sum_scores - min_threshold) / (max_threshold - min_threshold)
    )
