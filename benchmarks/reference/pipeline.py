"""Oracle scheduling pipeline: filter → score → select.

Serial reimplementation of findNodesThatFitPod / prioritizeNodes /
selectHost (reference schedule_one.go:408-917) with the default plugin set
and weights (apis/config/v1/default_plugins.go:30-52):

    TaintToleration 3, NodeAffinity 2, PodTopologySpread 2,
    InterPodAffinity 2, NodeResourcesFit 1, BalancedAllocation 1,
    ImageLocality 1.

Tie-breaking: the reference reservoir-samples among max-score nodes
(schedule_one.go:870).  The oracle (and the device pipeline) default to the
deterministic "first max in node order" policy; an optional seeded RNG
reproduces reservoir sampling when bit-compat with a recorded run is needed.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .types import Pod
from . import filters as F
from . import scores as S
from .state import NodeState, OracleState

DEFAULT_SCORE_WEIGHTS = {
    "TaintToleration": 3,
    "NodeAffinity": 2,
    "PodTopologySpread": 2,
    "InterPodAffinity": 2,
    "NodeResourcesFit": 1,
    "NodeResourcesBalancedAllocation": 1,
    "ImageLocality": 1,
}


@dataclass
class FitResult:
    feasible: List[str]
    # node name → list of reasons (Diagnosis.NodeToStatusMap analogue)
    reasons: Dict[str, List[str]] = field(default_factory=dict)
    # nodes visited before the sampling cutoff (drives nextStartNodeIndex,
    # schedule_one.go:625)
    processed: int = 0
    # size of the node list actually walked (PreFilterResult-narrowed) —
    # the modulus for nextStartNodeIndex advancement
    n_considered: int = 0


MIN_FEASIBLE_NODES_TO_FIND = 100  # schedule_one.go minFeasibleNodesToFind


def num_feasible_nodes_to_find(percentage: int, num_all: int) -> int:
    """numFeasibleNodesToFind (schedule_one.go:673-699): adaptive percentage
    `50 - nodes/125` (floor 5%) when the configured percentage is 0."""
    if num_all < MIN_FEASIBLE_NODES_TO_FIND:
        return num_all
    if percentage == 0:
        percentage = 50 - num_all // 125
        if percentage < 5:
            percentage = 5
    if percentage >= 100:
        return num_all
    num = num_all * percentage // 100
    return max(num, MIN_FEASIBLE_NODES_TO_FIND)


ALL_FILTERS = frozenset(
    {
        "NodeName",
        "NodeUnschedulable",
        "TaintToleration",
        "NodeAffinity",
        "NodePorts",
        "NodeResourcesFit",
        "InterPodAffinity",
        "PodTopologySpread",
    }
)


def feasible_nodes(
    pod: Pod,
    state: OracleState,
    enabled: frozenset = ALL_FILTERS,
    allowed: Optional[frozenset] = None,
    sample_k: Optional[int] = None,
    start_index: int = 0,
    sample_pct: Optional[int] = None,
) -> FitResult:
    """Filter plugins in the reference's iteration shape (every node, all
    reasons collected).  ``enabled`` limits evaluation to a profile's
    enabled plugin set (kernel names); ``allowed`` is the PreFilterResult
    node-name narrowing — applied BEFORE sampling, like the reference
    (findNodesThatFitPod narrows the node list first, then
    findNodesThatPassFilters sizes numFeasibleNodesToFind and the
    nextStartNodeIndex rotation over the narrowed list,
    schedule_one.go:478-486,588-669).

    ``sample_k``/``start_index`` reproduce the adaptive sampling: nodes
    are visited in rotation order from start_index and the walk stops once
    sample_k feasible nodes are found; FitResult.processed reports how
    many nodes were visited.  ``sample_pct`` instead derives sample_k from
    the NARROWED list length (the correct sizing when combined with
    ``allowed``); it overrides sample_k."""
    spread_counts = (
        F.spread_pair_counts(pod, state) if "PodTopologySpread" in enabled else None
    )
    checks = [
        ("NodeName", lambda ns: F.filter_node_name(pod, ns)),
        ("NodeUnschedulable", lambda ns: F.filter_node_unschedulable(pod, ns)),
        ("TaintToleration", lambda ns: F.filter_taints(pod, ns)),
        ("NodeAffinity", lambda ns: F.filter_node_affinity(pod, ns)),
        ("NodePorts", lambda ns: F.filter_node_ports(pod, ns)),
        ("InterPodAffinity", lambda ns: F.filter_interpod_affinity(pod, ns, state)),
        (
            "PodTopologySpread",
            lambda ns: F.filter_topology_spread(pod, ns, state, spread_counts),
        ),
    ]
    checks = [c for c in checks if c[0] in enabled]
    check_resources = "NodeResourcesFit" in enabled
    feasible: List[str] = []
    reasons: Dict[str, List[str]] = {}
    names = list(state.nodes)
    if sample_k is not None or sample_pct is not None:
        # sampling-compat mode walks nodes in the reference's nodeTree
        # order — zone round-robin (node_tree.go:119-143); the rotation
        # below and first-max selection both ride this order
        from .nodetree import ZONE_LABEL, node_tree_order

        order = node_tree_order(
            [state.nodes[n].node.labels.get(ZONE_LABEL) for n in names]
        )
        names = [names[i] for i in order]
    if allowed is not None:
        names = [n for n in names if n in allowed]
    n_considered = len(names)
    if sample_pct is not None:
        k = num_feasible_nodes_to_find(sample_pct, n_considered)
        sample_k = k if k < n_considered else None
    if sample_k is not None and names:
        start = start_index % len(names)
        names = names[start:] + names[:start]
    processed = 0
    for name in names:
        ns = state.nodes[name]
        processed += 1
        rs: List[str] = []
        for _, fn in checks:
            r = fn(ns)
            if r:
                rs.append(r)
        if check_resources:
            rs.extend(F.filter_node_resources(pod, ns))
        if rs:
            reasons[name] = rs
        else:
            feasible.append(name)
            if sample_k is not None and len(feasible) >= sample_k:
                break
    return FitResult(
        feasible=feasible,
        reasons=reasons,
        processed=processed,
        n_considered=n_considered,
    )


def prioritize(
    pod: Pod,
    state: OracleState,
    feasible: Sequence[str],
    weights: Optional[Dict[str, int]] = None,
    fit_scorer=None,
) -> Dict[str, int]:
    """Weighted sum of normalized plugin scores per feasible node
    (prioritizeNodes, schedule_one.go:752).  ``fit_scorer(pod, ns)``
    overrides the NodeResourcesFit strategy (default LeastAllocated)."""
    w = dict(DEFAULT_SCORE_WEIGHTS if weights is None else weights)
    nodes = [state.nodes[n] for n in feasible]
    totals = {n: 0 for n in feasible}

    def accumulate(name: str, scores: List[int]):
        weight = w.get(name, 0)
        for node_name, s in zip(feasible, scores):
            totals[node_name] += s * weight

    if w.get("TaintToleration"):
        raw = [S.score_taint_toleration(pod, ns) for ns in nodes]
        accumulate("TaintToleration", S.normalize_taint_toleration(raw))
    if w.get("NodeAffinity"):
        raw = [S.score_node_affinity(pod, ns) for ns in nodes]
        accumulate("NodeAffinity", S.normalize_node_affinity(raw))
    if w.get("PodTopologySpread"):
        raw = S.score_topology_spread_all(pod, state, list(feasible))
        accumulate("PodTopologySpread", S.normalize_topology_spread(raw))
    if w.get("InterPodAffinity"):
        raw = S.score_interpod_affinity_all(pod, state, list(feasible))
        accumulate("InterPodAffinity", S.normalize_interpod_affinity(raw))
    if w.get("NodeResourcesFit"):
        scorer = fit_scorer or S.score_least_allocated
        accumulate(
            "NodeResourcesFit",
            [scorer(pod, ns) for ns in nodes],
        )
    if w.get("NodeResourcesBalancedAllocation"):
        accumulate(
            "NodeResourcesBalancedAllocation",
            [S.score_balanced_allocation(pod, ns) for ns in nodes],
        )
    if w.get("ImageLocality"):
        accumulate(
            "ImageLocality",
            [S.score_image_locality(pod, ns, state) for ns in nodes],
        )
    return totals


def select_host(
    totals: Dict[str, int], rng: Optional[random.Random] = None
) -> Optional[str]:
    """Max score; ties broken deterministically by node order, or by
    reservoir sampling when an rng is supplied (schedule_one.go:870)."""
    if not totals:
        return None
    best = max(totals.values())
    tied = [n for n, s in totals.items() if s == best]
    if rng is None or len(tied) == 1:
        return tied[0]
    selected = tied[0]
    cnt = 1
    for cand in tied[1:]:
        cnt += 1
        if rng.randrange(cnt) == 0:
            selected = cand
    return selected


@dataclass
class ScheduleResult:
    node: Optional[str]
    feasible: List[str] = field(default_factory=list)
    reasons: Dict[str, List[str]] = field(default_factory=dict)
    scores: Dict[str, int] = field(default_factory=dict)


def schedule_one(
    pod: Pod,
    state: OracleState,
    weights: Optional[Dict[str, int]] = None,
    rng: Optional[random.Random] = None,
) -> ScheduleResult:
    fit = feasible_nodes(pod, state)
    if not fit.feasible:
        return ScheduleResult(node=None, feasible=[], reasons=fit.reasons)
    if len(fit.feasible) == 1:
        return ScheduleResult(
            node=fit.feasible[0], feasible=fit.feasible, reasons=fit.reasons
        )
    totals = prioritize(pod, state, fit.feasible, weights)
    return ScheduleResult(
        node=select_host(totals, rng),
        feasible=fit.feasible,
        reasons=fit.reasons,
        scores=totals,
    )
