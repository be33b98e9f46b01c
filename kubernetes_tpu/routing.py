"""Which engine runs this batch: the protocol between the scheduling loop
and its engines (``Scheduler._schedule_pending_impl`` and the offers it
makes).  The per-pod gates are defined once here, and an offer answers in
one shape.  Nothing here imports ``scheduler.py``.

The routing table (``tests/test_routing.py`` has a case a row).  A batch is
one profile's pods of one pop, on a scheduler whose mirror is packed (the
process's first batch finds no mirror and skips the chained offer).  The
loop asks ``_chain_quickcheck`` + ``_try_dispatch_chained``, then
``_try_dispatch_fast``, then runs ``_schedule_batch`` itself (direct); the
first that does not decline takes the batch and is booked ``route.<it>``.

| a batch whose pods ...                      | first reason       | route   | engine (metric that moves)                  |
|---------------------------------------------|--------------------|---------|---------------------------------------------|
| ask resources only                          | —                  | fast    | signature path / resident run (`fast_batches`) |
| name a nominated node                       | `nominated_node`   | direct  | one-pod cycle on that node first            |
| a host Filter plugin finds relevant         | `host_filter`      | direct  | one-pod batches on the scan (`scan_batches`, one a pod) |
| an extender is interested in                | `extender`         | direct  | one-pod host-oracle cycle                   |
| a normalizing Score plugin finds relevant   | `normalizing_score`| direct  | one-pod host-oracle cycle                   |
| a weighted host Score plugin finds relevant | `host_score`       | direct  | scan with the host's score matrix (`scan_batches`) |
| belong to a registered PodGroup             | `gang` ¹           | direct  | workloads dispatch (`workload_batches`)     |
| rank at or under a nomination's priority    | `nomination` ¹     | chained | sequential scan on the chain (`chain_batches`) |
| a placed pod's term admits                  | `term_admits` ¹ ²  | chained | sequential scan on the chain (`chain_batches`) |
| have more candidate terms than one sweep asks | `term_count` ¹ ² | chained | the same                                    |
| carry a spread or inter-pod term            | `no_signature`     | chained | wave inside the chained dispatch (`wave_batches`) |
| ask a host port                             | `no_signature`     | direct  | wave, ports carried (`wave_batches`): the chain's append splices no port rows |
| under sampling (percentage, compat, seeded ties) | the profile's | direct  | scan (`scan_batches`): the direct path owns the rotation cursor |
| under a non-default fit strategy            | the profile's      | chained | sequential scan on the chain (`chain_batches`) |

¹ the fast gate's reasons: booked ``fast_gate.refused.<reason>`` beside the
route.  ² the gate's asking is booked ``fast_gate.probes_asked``.

Below the table: a wave-shaped batch falls to the gang scan where the wave
is switched off or two nodes share a hostname, or (direct path only) the
wave's breaker is open (``Scheduler._wave_tables_for``, WAVE.md); any
extender configured, an open chain breaker or an unpacked mirror keeps a
batch off the chain; an offer can still decline after a preparation only
it can make (the chained cluster's capacity, a failed chain restart, a
signature whose static scores vary), and the next engine is asked.
"""

from __future__ import annotations

import enum
from typing import Callable, NamedTuple, Optional

from kubernetes_tpu.cache.term_probes import MAX_PROBES_ASKED
from kubernetes_tpu.framework.interface import ScorePlugin
from kubernetes_tpu.workloads.gang import group_key_of

# ----- why a pod cannot ride the signature path ------------------------------

NOMINATED_NODE = "nominated_node"
HOST_FILTER = "host_filter"
EXTENDER = "extender"
NORMALIZING_SCORE = "normalizing_score"
HOST_SCORE = "host_score"
GANG = "gang"
NOMINATION = "nomination"
TERM_ADMITS = "term_admits"
TERM_COUNT = "term_count"
NO_SIGNATURE = "no_signature"

_MISSING = object()


def weighted_host_scores(fwk) -> list:
    return [p for p in fwk.host_score_plugins() if fwk.score_weights.get(p.name, 0)]


def normalizing_score_plugins(fwk) -> list:
    """Enabled host Score plugins that OVERRIDE normalize — their
    scores depend on the feasible set, which only the one-pod oracle
    cycle knows (see the routing in _schedule_batch).  Also includes
    NodeResourcesFit when its scoringStrategy weighs resources beyond
    the device kernel's cpu/memory lanes (device_score=False): its
    score evolves with every in-batch commit, so only the one-pod
    cycle (whose fit_scorer recomputes per attempt) is exact."""
    out = [p for p in weighted_host_scores(fwk) if type(p).normalize is not ScorePlugin.normalize]
    fit = fwk.plugin_instance("NodeResourcesFit")
    if fit is not None and not getattr(fit, "device_score", True) and fwk.score_weights.get(fit.name, 0):
        out.append(fit)
    return out


class PodGates(NamedTuple):
    """One batch's readings of the gates; each answers a reason or None."""

    one_pod: Callable  # (pod): what only a one-pod cycle decides
    host_score: Callable  # (pod): a weighted host Score plugin's pod
    fast_gate: Callable  # (batch): the fast gate's verdict over the batch
    first_reason: Callable  # (pod): the first of every gate, in order


def _first_of(gates):
    gates = tuple(gates)

    def first(p):
        for gate in gates:
            r = gate(p)
            if r is not None:
                return r
        return None

    return first


def _any_of(checks, reason):
    # explicit loops, not any(genexpr): first_reason runs once per
    # extended pod (pop_batch_while) and the genexpr closure allocation
    # showed up in the drain profile
    def gate(p):
        for check in checks:
            if check(p):
                return reason
        return None

    return gate


def pod_gates(
    fwk, *, extenders, gang_on: bool, max_nomination: Optional[int], view, tally, signature=None
) -> PodGates:
    """The gates as closures over what they observe, bound once: the
    profile's host Filter, weighted host Score and normalizing Score plugins
    (``fwk`` None: none of them — the fast gate alone needs no profile), the
    extenders, whether gangs take the workloads tier, the highest priority a
    nomination holds (None: no nomination), the cache's ``term_probe_view()``
    (immutable: the gates run outside ``Scheduler._mu``; falsy where no term
    is placed), ``tally.asked``: the ``admits()`` evaluations so far, which
    one batch's sweep and its extension share and the loop books, and
    ``signature(pod)``: the pod's signature key, None where it has none the
    caller can take."""
    hf = ns_plugins = host_scores = ()
    if fwk is not None:
        hf, ns_plugins, host_scores = fwk.host_filter_plugins(), normalizing_score_plugins(fwk), weighted_host_scores(fwk)

    def nominated_node(p):
        return NOMINATED_NODE if p.nominated_node_name else None

    host_filter = _any_of([pl.maybe_relevant for pl in hf], HOST_FILTER)
    extender = _any_of([e.is_interested for e in extenders], EXTENDER)
    # a host Score plugin with a CUSTOM normalize must score over
    # the true feasible set (runtime/framework.go:1158 runs
    # NormalizeScore post-Filter) — the oracle one-pod cycle does;
    # the batched extra_score merge cannot
    normalizing_score = _any_of([pl.score_relevant for pl in ns_plugins], NORMALIZING_SCORE)
    host_score = _any_of([pl.score_relevant for pl in host_scores], HOST_SCORE)

    # gang members need the workloads tier's all-or-nothing admission —
    # the signature committer has no rollback
    def gang(p):
        return GANG if group_key_of(p) is not None else None

    def nomination(p):
        return NOMINATION if p.priority <= max_nomination else None

    def ask(p):
        """Ask the placed terms that could admit ``p`` (``view``'s
        candidates for its labels) whether one does: ``term_admits`` where
        one does, ``term_count`` where asking would take the batch past the
        work bound, else None.  ``tally.asked`` counts the asking."""
        candidates = view.candidates(p)
        if tally.asked + len(candidates) > MAX_PROBES_ASKED:
            return TERM_COUNT
        for pr in candidates:
            tally.asked += 1
            if pr.admits(p):
                return TERM_ADMITS
        return None

    def terms(p, asked_groups):
        """``ask``, once a distinct (namespace, labels) group."""
        gk = (p.namespace, tuple(sorted(p.labels.items())))
        r = asked_groups.get(gk, _MISSING)
        if r is _MISSING:
            r = asked_groups[gk] = ask(p)
        return r

    # the default registry leaves every plugin list empty — a gate with
    # nothing to observe is left out, so the hot steady-state first_reason
    # is the nominated-node read, the gang key and the signature memo lookup
    # (a host Filter's pod last: alone in a batch it needs no one-pod cycle)
    one_pod = [nominated_node]
    one_pod += [g for g, on in ((extender, extenders), (normalizing_score, ns_plugins), (host_filter, hf)) if on]
    of_the_batch = [g for g, on in ((gang, gang_on), (nomination, max_nomination is not None)) if on]
    every = one_pod + ([host_score] if host_scores else []) + of_the_batch
    if view:
        groups_of_the_extension: dict = {}
        every.append(lambda p: terms(p, groups_of_the_extension))
    if signature is not None:
        every.append(lambda p: NO_SIGNATURE if signature(p) is None else None)

    def fast_gate(batch):
        """The fast gate's three clauses in order over the WHOLE batch:
        gang for any pod, then nomination for any, then the placed terms:

        * nominations count as present only for pods of priority <= the
          nomination's (runtime:973): if every batch pod outranks every
          nomination, the signature committer's capacity view is exact;
        * a placed pod's required anti-affinity (and symmetric term score)
          affects only newcomers its term selectors ADMIT — checked per
          batch label-group against the cache's registry of DISTINCT
          placed terms, at any count of placed term pods;
        * placed host-port users never constrain port-FREE pods (and port
          users are already signature-ineligible), so no port gate at all."""
        for gate in of_the_batch:
            for qp in batch:
                r = gate(qp.pod)
                if r is not None:
                    return r
        if view:
            asked_groups: dict = {}
            for qp in batch:
                r = terms(qp.pod, asked_groups)
                if r is not None:
                    return r
        return None

    return PodGates(_first_of(one_pod), host_score, fast_gate, _first_of(every))


# ----- what an offer answers -------------------------------------------------


class Offer(enum.Enum):
    IN_FLIGHT = "in flight"  # dispatched: ``record`` is harvested later
    HANDLED = "handled"  # nothing left of the batch to schedule
    SETTLE = "settle"  # the pipeline must settle first; offer once more
    DECLINED = "declined"  # side-effect free: the next engine is asked
    SERIAL = "serial"  # abandoned dispatch: drain ``batch`` serially


class Answer(NamedTuple):
    status: Offer
    record: Optional[dict] = None  # IN_FLIGHT; "harvest_now": settle it at once
    batch: Optional[list] = None  # SERIAL: the live batch


HANDLED, SETTLE, DECLINED = Answer(Offer.HANDLED), Answer(Offer.SETTLE), Answer(Offer.DECLINED)


class Pipeline(NamedTuple):
    """What the loop's pipeline holds when an offer is made."""

    empty: bool  # no unharvested record: host state may be rebuilt from
    only_fast: bool  # no chained record: its commits move what a fast rebuild reads
