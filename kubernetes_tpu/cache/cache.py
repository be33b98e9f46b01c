"""Host scheduler cache (pkg/scheduler/backend/cache/cache.go).

Holds the authoritative view of nodes and pods between the informer stream
and the scheduling loop:

  * ``assume_pod``/``forget_pod``/``finish_binding`` implement the
    optimistic-binding protocol (cache.go:360-422): a scheduled pod is
    charged to its node immediately so the next cycle sees it, before the
    API write round-trips.
  * informer Add/Update/RemovePod reconcile against assumed state,
    including the assumed-vs-informer races (cache.go:484-568).
  * every mutation bumps the node's ``generation``; the device mirror
    repacks only nodes newer than its own generation (cache.go:185-279's
    incremental UpdateSnapshot, reproduced for HBM).
  * assumed pods that never confirm expire after a TTL (cache.go:721-752;
    the reference default is "never", kept configurable here).
"""

from __future__ import annotations

import copy
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from kubernetes_tpu.analysis import sanitizer
from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.cache.term_probes import ProbeRegistry

# Lock-discipline registry (kubernetes_tpu.analysis): Cache has no lock of
# its own — every mutating method is contractually entered with the owning
# Scheduler's _mu held (cache.mu in the reference lives inside the cache;
# here the scheduler's one lock covers cache+queue+mirror so commit tails
# settle under a single acquisition).  Methods listed read-only are safe to
# call without the lock.
_KTPU_GUARDED = {
    "Cache": {
        "external_lock": "Scheduler._mu",
        "readonly": [
            "is_assumed", "real_nodes", "placed_pods", "stats", "_pod_flags",
            "term_probe_view",
        ],
    },
}

_generation = itertools.count(1)


def next_generation() -> int:
    return next(_generation)


@dataclass
class CachedNode:
    """NodeInfo analogue (framework/types.go:585): node + accounting."""

    node: Optional[Node]  # None for a "ghost" node that only hosts pods
    pods: Dict[str, Pod] = field(default_factory=dict)  # uid → pod
    requested: Resource = field(default_factory=Resource)
    non_zero_requested: Resource = field(default_factory=Resource)
    generation: int = 0
    # bumped only when the Node OBJECT changes (labels/taints/capacity) —
    # not on pod accounting; device caches keyed on this skip re-uploads
    # for usage-only churn
    static_generation: int = 0

    def add_pod(self, pod: Pod) -> None:
        self.requested.add(pod.compute_requests())
        self.non_zero_requested.add(pod.non_zero_requests())
        self.pods[pod.uid] = pod
        self.generation = next_generation()

    def remove_pod(self, pod: Pod) -> bool:
        if pod.uid not in self.pods:
            return False
        old = self.pods.pop(pod.uid)
        self.requested.sub(old.compute_requests())
        self.non_zero_requested.sub(old.non_zero_requests())
        self.generation = next_generation()
        return True


@dataclass
class _PodState:
    pod: Pod
    binding_finished: bool = False
    deadline: Optional[float] = None


class CacheError(RuntimeError):
    """Cache invariant violation — the reference fatals on these
    (cache.go:537-541); we raise and let the caller decide."""


class Cache:
    def __init__(self, assumed_pod_ttl_s: Optional[float] = None):
        # ttl None reproduces durationToExpireAssumedPod=0 (never expire,
        # scheduler.go:57)
        self.ttl = assumed_pod_ttl_s
        self.nodes: Dict[str, CachedNode] = {}
        self.pod_states: Dict[str, _PodState] = {}
        self.assumed: set[str] = set()
        # O(1) feature counters + change version so consumers (device
        # mirror, fast path) can gate expensive rebuilds without scans
        self.pod_version = 0
        self.n_term_pods = 0  # placed pods carrying (anti-)affinity terms
        self.n_port_pods = 0  # placed pods using host ports
        # registry of the term-carrying placed pods themselves: the fast
        # path's per-batch gate asks "could any placed term admit this
        # pod" instead of disabling itself cluster-globally
        self.term_pods: Dict[str, Pod] = {}
        self.term_version = 0
        # the same pods' terms as DISTINCT probes with reference counts:
        # what the gate asks, at any count of placed term pods
        self.term_probes = ProbeRegistry()

    @staticmethod
    def _pod_flags(pod: Pod) -> Tuple[bool, bool]:
        has_terms = pod.affinity is not None and (
            pod.affinity.pod_affinity is not None
            or pod.affinity.pod_anti_affinity is not None
        )
        return has_terms, bool(pod.host_ports())

    def _count_pod(self, pod: Pod, sign: int) -> None:
        self.pod_version += 1
        has_terms, has_ports = self._pod_flags(pod)
        if has_terms:
            self.n_term_pods += sign
            self.term_version += 1
            if sign > 0:
                self.term_pods[pod.uid] = pod
                self.term_probes.add(pod)
            else:
                # by the object that was added (an adopted API object may
                # stand in ``pod``): exactly what its addition counted
                old = self.term_pods.pop(pod.uid, None)
                if old is not None:
                    self.term_probes.remove(old)
        if has_ports:
            self.n_port_pods += sign

    # ----- nodes (informer) -----------------------------------------------

    def add_node(self, node: Node) -> None:
        cn = self.nodes.get(node.name)
        if cn is None:
            g = next_generation()
            self.nodes[node.name] = CachedNode(
                node=node, generation=g, static_generation=g
            )
        else:
            cn.node = node
            cn.generation = next_generation()
            cn.static_generation = cn.generation

    def update_node(self, node: Node) -> None:
        self.add_node(node)

    def remove_node(self, name: str) -> None:
        cn = self.nodes.get(name)
        if cn is None:
            return
        if cn.pods:
            # Ghost node: keep accounting until its pods are deleted
            # (cache.go:601-668).
            cn.node = None
            cn.generation = next_generation()
        else:
            del self.nodes[name]

    # ----- assume protocol (scheduler) ------------------------------------

    def assume_pod(self, pod: Pod, node_name: str) -> None:
        """Assumes a COPY of the pod (schedule_one.go:943 assumes
        podInfo.DeepCopy()): the queued object stays pristine, so a failed
        reserve/permit/bind never leaves a stale node_name pinning the pod
        to the node it just failed on."""
        if pod.uid in self.pod_states:
            raise CacheError(f"pod {pod.key} already assumed/added")
        # shallow copy without __reduce_ex__ dispatch (copy.copy costs ~5×
        # on dataclasses; this runs once per scheduled pod)
        assumed = object.__new__(type(pod))
        assumed.__dict__.update(pod.__dict__)
        assumed.node_name = node_name
        cn = self.nodes.setdefault(node_name, CachedNode(node=None))
        cn.add_pod(assumed)
        self._count_pod(assumed, +1)
        self.pod_states[pod.uid] = _PodState(assumed)
        self.assumed.add(pod.uid)

    def assume_pods_bulk(self, pairs) -> List[object]:
        """assume_pod for one dispatch's worth of placements in one pass.

        Same protocol and invariants as the per-pod assume, minus the
        per-pod overhead: callers guarantee the pods use no host ports
        (the fast path's eligibility; the chained path refuses them), so
        that probe collapses; a pod that carries (anti-)affinity terms —
        a wave's bulk tail commits those here — is counted and registered
        as ``assume_pod`` counts it, or the fast gate would never learn
        of its terms and its removal would take the count below zero.
        The generation bump aggregates to one per TOUCHED NODE instead of
        one per pod (the mirror repacks per node row, so per-pod bumps
        carry no extra information).  Returns a list aligned with ``pairs``:
        the assumed pod copy, or an error STRING for pods that violated
        the protocol (already assumed/added) — those are not assumed,
        exactly like the per-pod path's CacheError."""
        # KTPU_SANITIZE probe: memoized enabled() check + getattr, once per
        # bulk dispatch (not per pod).  The owning scheduler stamps
        # _ktpu_lock at construction when the sanitizer is on; a standalone
        # Cache has no discipline to enforce.
        sanitizer.assert_owned(
            getattr(self, "_ktpu_lock", None), "cache.assume_pods_bulk"
        )
        out: List[object] = []
        pod_states = self.pod_states
        nodes = self.nodes
        assumed_set = self.assumed
        touched: Dict[str, CachedNode] = {}
        n_ok = 0
        for pod, node_name in pairs:
            if pod.uid in pod_states:
                out.append(f"pod {pod.key} already assumed/added")
                continue
            assumed = object.__new__(type(pod))
            assumed.__dict__.update(pod.__dict__)
            assumed.node_name = node_name
            cn = nodes.get(node_name)
            if cn is None:
                cn = nodes[node_name] = CachedNode(node=None)
            cn.requested.add(assumed.compute_requests())
            cn.non_zero_requested.add(assumed.non_zero_requests())
            cn.pods[pod.uid] = assumed
            touched[node_name] = cn
            pod_states[pod.uid] = _PodState(assumed)
            assumed_set.add(pod.uid)
            out.append(assumed)
            if assumed.affinity is not None:
                self._count_pod(assumed, +1)  # bumps pod_version itself
            else:
                n_ok += 1
        self.pod_version += n_ok
        for cn in touched.values():
            cn.generation = next_generation()
        return out

    def finish_binding(self, pod: Pod, now: Optional[float] = None) -> None:
        ps = self.pod_states.get(pod.uid)
        if ps is None or pod.uid not in self.assumed:
            return
        ps.binding_finished = True
        if self.ttl is not None:
            ps.deadline = (now or time.monotonic()) + self.ttl

    def forget_pod(self, pod: Pod) -> None:
        ps = self.pod_states.get(pod.uid)
        if ps is None:
            return
        if pod.uid not in self.assumed:
            raise CacheError(f"pod {pod.key} was added, not assumed; cannot forget")
        self._remove_pod_internal(ps.pod)
        del self.pod_states[pod.uid]
        self.assumed.discard(pod.uid)

    def cleanup_expired_assumed(self, now: Optional[float] = None) -> List[Pod]:
        """TTL janitor (cache.go:729 cleanupAssumedPods)."""
        now = now or time.monotonic()
        expired = []
        for uid in list(self.assumed):
            ps = self.pod_states[uid]
            if ps.binding_finished and ps.deadline is not None and now >= ps.deadline:
                expired.append(ps.pod)
                self._remove_pod_internal(ps.pod)
                del self.pod_states[uid]
                self.assumed.discard(uid)
        return expired

    # ----- pods (informer) -------------------------------------------------

    def add_pod(self, pod: Pod) -> None:
        """Informer confirmation of a (possibly assumed) pod
        (cache.go:484)."""
        ps = self.pod_states.get(pod.uid)
        if ps is not None and pod.uid in self.assumed:
            if ps.pod.node_name != pod.node_name:
                # Assumed to another node than the API says: trust the API
                # (the race in cache.go:498-516).
                self._remove_pod_internal(ps.pod)
                self._add_pod_internal(pod)
            else:
                # Same node: adopt the API object (it is the truth).
                self.nodes[pod.node_name].pods[pod.uid] = pod
                self.pod_version += 1
            # Confirmed: no longer assumed.
            self.assumed.discard(pod.uid)
            ps.pod = pod
            ps.deadline = None
        elif ps is None:
            self._add_pod_internal(pod)
            self.pod_states[pod.uid] = _PodState(pod)
        else:
            raise CacheError(f"pod {pod.key} added twice")

    def update_pod(self, old: Pod, new: Pod) -> None:
        ps = self.pod_states.get(old.uid)
        if ps is None:
            raise CacheError(f"updating unknown pod {old.key}")
        if old.uid in self.assumed:
            raise CacheError(f"updating assumed pod {old.key}")
        self._remove_pod_internal(ps.pod)
        self._add_pod_internal(new)
        ps.pod = new

    def remove_pod(self, pod: Pod) -> None:
        ps = self.pod_states.get(pod.uid)
        if ps is None:
            return
        self._remove_pod_internal(ps.pod)
        del self.pod_states[pod.uid]
        self.assumed.discard(pod.uid)
        # Drop ghost nodes whose last pod left.
        cn = self.nodes.get(ps.pod.node_name)
        if cn is not None and cn.node is None and not cn.pods:
            del self.nodes[ps.pod.node_name]

    def _add_pod_internal(self, pod: Pod) -> None:
        cn = self.nodes.setdefault(pod.node_name, CachedNode(node=None))
        cn.add_pod(pod)
        self._count_pod(pod, +1)

    def _remove_pod_internal(self, pod: Pod) -> None:
        cn = self.nodes.get(pod.node_name)
        if cn is None or not cn.remove_pod(pod):
            raise CacheError(f"pod {pod.key} not found on node {pod.node_name!r}")
        self._count_pod(pod, -1)

    # ----- introspection ----------------------------------------------------

    def term_probe_view(self):
        """The placed terms the fast gate asks, as an immutable view: safe
        to take and to read without the lock, beside ``_count_pod`` (the
        accessor the lock discipline above registers as read-only)."""
        return self.term_probes.view()

    def is_assumed(self, uid: str) -> bool:
        return uid in self.assumed

    def real_nodes(self) -> List[CachedNode]:
        return [cn for cn in self.nodes.values() if cn.node is not None]

    def placed_pods(self) -> List[Pod]:
        return [
            p
            for cn in self.nodes.values()
            for p in cn.pods.values()
        ]

    def stats(self) -> Dict[str, int]:
        return {
            "nodes": len(self.real_nodes()),
            "pods": sum(len(cn.pods) for cn in self.nodes.values()),
            "assumed": len(self.assumed),
        }
