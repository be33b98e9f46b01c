"""Host-side cluster state the oracle evaluates against.

Equivalent in role to the reference's Snapshot (a consistent view of nodes +
placed pods, pkg/scheduler/backend/cache/snapshot.go) but kept as plain
Python objects.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional

from .resource import Resource
from .types import Node, Pod


_POD_SET_VERSION = [0]  # global NodeState mutation counter (cache key)


def bump_pod_set_version() -> None:
    """Invalidate pod-set-derived caches (anti_term_pods) after a
    mutation that bypasses the NodeState mutators — e.g. preemption's
    working-copy dict swap."""
    _POD_SET_VERSION[0] += 1


@dataclass
class NodeState:
    """Per-node accounting mirroring framework.NodeInfo (types.go:585)."""

    node: Node
    pods: List[Pod] = field(default_factory=list)
    requested: Resource = field(default_factory=Resource)
    non_zero_requested: Resource = field(default_factory=Resource)

    def add_pod(self, pod: Pod) -> None:
        req = pod.compute_requests()
        self.requested.add(req)
        self.non_zero_requested.add(req.non_zero_defaulted())
        self.pods.append(pod)
        _POD_SET_VERSION[0] += 1

    def remove_pod(self, pod: Pod) -> bool:
        _POD_SET_VERSION[0] += 1
        for i, p in enumerate(self.pods):
            if p.uid == pod.uid:
                req = p.compute_requests()
                self.requested.sub(req)
                self.non_zero_requested.sub(req.non_zero_defaulted())
                del self.pods[i]
                return True
        return False


@dataclass
class OracleState:
    nodes: Dict[str, NodeState] = field(default_factory=dict)
    namespace_labels: Dict[str, Dict[str, str]] = field(default_factory=dict)

    @classmethod
    def build(
        cls,
        nodes: Iterable[Node],
        placed_pods: Iterable[Pod] = (),
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
    ) -> "OracleState":
        st = cls(namespace_labels=dict(namespace_labels or {}))
        for n in nodes:
            st.nodes[n.name] = NodeState(node=n)
        for p in placed_pods:
            st.place(p)
        return st

    def place(self, pod: Pod) -> None:
        ns = self.nodes.get(pod.node_name)
        if ns is None:
            raise KeyError(f"pod {pod.key} placed on unknown node {pod.node_name!r}")
        ns.add_pod(pod)

    def unplace(self, pod: Pod) -> None:
        ns = self.nodes.get(pod.node_name)
        if ns is not None:
            ns.remove_pod(pod)

    def anti_term_pods(self):
        """[(node_state, pod, required-anti-terms)] for every PLACED pod
        that carries required anti-affinity — cached per pod-set version.
        satisfyExistingPodsAntiAffinity walks exactly these (the reference
        precomputes topologyToMatchedExistingAntiAffinityTerms the same
        way, filtering.go:141); without the cache the serial oracle costs
        O(nodes × placed) per (pod, node) check, which is unusable at
        parity-evidence scale."""
        from .filters import _required_terms

        version = _POD_SET_VERSION[0]
        cached = getattr(self, "_anti_cache", None)
        if cached is not None and cached[0] == version:
            return cached[1]
        out = []
        for ns in self.nodes.values():
            for epod in ns.pods:
                terms = _required_terms(epod, anti=True)
                if terms:
                    out.append((ns, epod, terms))
        self._anti_cache = (version, out)
        return out

    def node_list(self) -> List[NodeState]:
        return list(self.nodes.values())

    def all_pods(self) -> List[Pod]:
        return [p for ns in self.nodes.values() for p in ns.pods]
