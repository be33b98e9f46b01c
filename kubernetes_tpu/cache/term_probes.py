"""Placed-term interaction probes and the cache's registry of them.

A probe is one selector-with-namespace-scope through which a pod's
(anti-)affinity or spread term could interact with a newcomer.  The fast
gate (``Scheduler._fast_gate_ok``) asks "could any placed pod's term admit
this newcomer" of the cache's DISTINCT probes (``ProbeRegistry``).
Conservative: may claim interaction where none exists (only costs fast-path
eligibility, never correctness).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from kubernetes_tpu.api.types import LabelSelector, Pod


def _selector_matches(sel: Optional[LabelSelector], labels: Dict[str, str]) -> bool:
    """LabelSelector match; unknown operators match conservatively."""
    if sel is None:
        # a nil selector matches nothing (labels.Nothing()) in spread
        # counting; the callers that mean "everything" pass empty selector
        return False
    for k, v in (sel.match_labels or {}).items():
        if labels.get(k) != v:
            return False
    for e in sel.match_expressions or ():
        op = e.operator
        if op == "In":
            if labels.get(e.key) not in (e.values or ()):
                return False
        elif op == "NotIn":
            if e.key in labels and labels[e.key] in (e.values or ()):
                return False
        elif op == "Exists":
            if e.key not in labels:
                return False
        elif op == "DoesNotExist":
            if e.key in labels:
                return False
        else:  # unknown op: conservative
            return True
    return True


def sel_key(sel: Optional[LabelSelector]):
    """Hashable content key of a LabelSelector (match_labels is a plain
    dict, so the dataclass itself doesn't hash).  Hashing the result still
    raises TypeError where a selector holds an unhashable value."""
    if sel is None:
        return None
    return (
        tuple(sorted((sel.match_labels or {}).items())),
        tuple(sel.match_expressions or ()),
    )


class _Probe:
    """One selector-with-namespace-scope an interacting pod would match."""

    __slots__ = ("sel", "ns_any", "namespaces")

    def __init__(self, sel, ns_any: bool, namespaces: Tuple[str, ...]):
        self.sel = sel
        self.ns_any = ns_any
        self.namespaces = namespaces

    def admits(self, pod: Pod) -> bool:
        if not self.ns_any and pod.namespace not in self.namespaces:
            return False
        return _selector_matches(self.sel, pod.labels)

    def key(self):
        """Content key: probes of pods stamped from one template share it.
        None where the selector will not hash (custom mappings)."""
        try:
            key = (self.ns_any, self.namespaces, sel_key(self.sel))
            hash(key)
        except TypeError:
            return None
        return key


def _pod_probes(pod: Pod) -> List[_Probe]:
    """Probes for every selector through which ``pod`` could interact with
    a newcomer: spread constraints count same-namespace peers only
    (podtopologyspread/filtering.go:236-310); affinity/anti terms scope by
    their namespace set, a namespaceSelector conservatively admitting
    everything (interpodaffinity/filtering.go:306-365)."""
    probes: List[_Probe] = []
    for c in pod.topology_spread_constraints:
        probes.append(_Probe(c.label_selector, False, (pod.namespace,)))
    aff = pod.affinity
    terms = []
    if aff is not None:
        for grp in (aff.pod_affinity, aff.pod_anti_affinity):
            if grp is None:
                continue
            terms.extend(
                grp.required_during_scheduling_ignored_during_execution or ()
            )
            for wt in (
                grp.preferred_during_scheduling_ignored_during_execution or ()
            ):
                terms.append(wt.pod_affinity_term)
    for t in terms:
        if getattr(t, "namespace_selector", None) is not None:
            probes.append(_Probe(t.label_selector, True, ()))
        else:
            nss = tuple(t.namespaces or ()) or (pod.namespace,)
            probes.append(_Probe(t.label_selector, False, nss))
    return probes


def probe_entries(pod: Pod) -> Tuple[Tuple[object, _Probe], ...]:
    """``(content key, probe)`` for each of ``_pod_probes(pod)``, memoized ON
    the pod object (spec updates arrive as new Pod objects, the
    ``compute_requests`` memo pattern).  The cache's assumed copy is made
    from the queued pod's ``__dict__``, so what was derived for a batch pod
    before its commit is what the registry counts when that pod is
    committed."""
    d = pod.__dict__
    entries = d.get("_probe_entries_memo")
    if entries is None:
        entries = d["_probe_entries_memo"] = tuple(
            [(pr.key(), pr) for pr in _pod_probes(pod)]
        )
    return entries


# One batch's sweep of probes is bounded: past this many admits()
# evaluations the asker (the fast gate, ``routing.py``) answers
# conservatively instead of finishing the sweep.
MAX_PROBES_ASKED = 100_000


class ProbeView:
    """An immutable view of a ProbeRegistry's distinct probes, indexed so
    that a pod's candidates follow ITS labels: a probe whose selector has
    match_labels can admit only a pod carrying each of those pairs, so it is
    filed under one of them (the first in sorted order); the rest
    (expressions only, the empty or nil selector) are ``unindexed``."""

    __slots__ = ("epoch", "unindexed", "by_pair")

    def __init__(self, epoch: int, probes: Sequence[_Probe]):
        self.epoch = epoch
        unindexed: List[_Probe] = []
        by_pair: Dict[Tuple[str, str], List[_Probe]] = {}
        for pr in probes:
            ml = pr.sel.match_labels if pr.sel is not None else None
            try:
                by_pair.setdefault(min(ml.items()), []).append(pr)
            except (AttributeError, ValueError, TypeError):
                # no match_labels, or a pair that will not hash or order
                unindexed.append(pr)
        self.unindexed = tuple(unindexed)
        self.by_pair = {k: tuple(v) for k, v in by_pair.items()}

    def __bool__(self) -> bool:
        return bool(self.unindexed or self.by_pair)

    def candidates(self, pod: Pod) -> Tuple[_Probe, ...]:
        """The probes that could admit ``pod``; each is still to be asked."""
        out = self.unindexed
        by_pair = self.by_pair
        if by_pair:
            for pair in pod.labels.items():
                filed = by_pair.get(pair)
                if filed:
                    out = out + filed
        return out


class ProbeRegistry:
    """The placed pods' terms as DISTINCT probes with reference counts —
    what the fast gate asks instead of walking every placed term pod.  The
    cache maintains it where it counts term pods (``Cache._count_pod``,
    under the scheduler's lock) and removes a pod by the object it added,
    so a removal decrements exactly what the addition incremented;
    ``view()`` may be called beside it from another thread: it hands out an
    immutable ProbeView, rebuilt only after a distinct probe appeared or
    disappeared (a reference count moving changes nothing a reader sees)."""

    def __init__(self) -> None:
        # content key → [probe, refcount]; a selector that will not hash is
        # kept under a key of the pod's own (uid, index): never deduped
        self._entries: Dict[object, list] = {}
        self._epoch = 0  # moves with the SET of keys, not with a count
        self._view = ProbeView(0, ())

    def add(self, pod: Pod) -> None:
        entries = self._entries
        for i, (key, pr) in enumerate(probe_entries(pod)):
            if key is None:
                key = (pod.uid, i)
            ent = entries.get(key)
            if ent is None:
                entries[key] = [pr, 1]
                self._epoch += 1
            else:
                ent[1] += 1

    def remove(self, pod: Pod) -> None:
        entries = self._entries
        for i, (key, _) in enumerate(probe_entries(pod)):
            if key is None:
                key = (pod.uid, i)
            ent = entries.get(key)
            if ent is None:
                continue
            ent[1] -= 1
            if not ent[1]:
                del entries[key]
                self._epoch += 1

    def view(self) -> ProbeView:
        view = self._view
        epoch = self._epoch
        if view.epoch != epoch:
            # list(dict.values()) is one atomic step under the interpreter
            # lock: a whole state of the registry, at ``epoch`` or later —
            # if later, the next call rebuilds once more
            view = self._view = ProbeView(
                epoch, [ent[0] for ent in list(self._entries.values())]
            )
        return view
