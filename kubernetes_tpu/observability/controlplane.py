"""Control-plane observability: end-to-end pipeline tracing, per-hop lag
attribution, and the snapshot-staleness sentinel.

Every tier built before this one (tracer, flight recorder, SLO
attribution, dispatch ledger) watches the scheduler and device side; the
L0–L4 watch path — `client/api_server.py`'s watch caches, the
`client/client.py` reflectors, the informer handlers, the queue, the
bind sink — was dark.  This module lights it up as ONE monitor with
three surfaces:

  * CAUSAL PIPELINE STITCHING — every pod carries a chain of
    (resourceVersion, monotonic ts) breadcrumbs across

        api_write → watch_delivery → informer_handler → enqueue
                  → pop → assumed → bind_start → bound

    The first three hops are stamped by the serving/client tier through
    ``note_api_write`` / ``note_delivery`` / ``note_pod_handled``; the
    scheduler-side hops ride the PR 7 flight-recorder breadcrumb stream
    (the monitor chains in front of the SLO evaluator's sink), so the
    hot loop grows ZERO new producer sites.  A chain closes on the
    ``bound`` breadcrumb: consecutive stamps become named hop durations
    (the waterfall ``/debug/pipeline?pod=`` serves), aggregate into the
    ``scheduler_tpu_pipeline_hop_seconds`` histogram, and — when the
    tracer is capturing — land as spans on a synthetic "controlplane"
    track, ``lt``-stamped from the attached chaos journal so a replay
    reconstructs byte-identical chains.

  * PER-REQUEST APISERVER ACCOUNTING — ``attach_api_server`` wires the
    HTTP handler's verb/resource/status latencies, watch-cache window
    occupancy, compaction/410 counters, and per-watcher fanout lag into
    the scheduler's registry, synced on scrape (the serving hot path
    never touches a registry lock).

  * SNAPSHOT-STALENESS SENTINEL — ``scheduler_tpu_snapshot_staleness_
    seconds``: at each batch dispatch, the gap between the newest event
    the watch stream DELIVERED and the newest event the informer
    handlers APPLIED.  A sustained breach (N consecutive dispatches over
    the threshold) files a ``snapshot_staleness`` verdict through
    ``SLOEvaluator.external_breach`` — the same freeze→dump→re-arm
    black-box machinery objective breaches and kernel regressions use.

Cost model: the monitor is None until ``Scheduler.install_controlplane``
— every producer site is one attribute read + None check when off.
Installed, the flight-recorder sink defers: it appends the raw batch
(plus a logical-time stamp) to a deque and returns, so the scheduling
loop and bind workers never pay for chain stitching.  Stamping, hop
bucketing, and span emission run in ``_drain_pending`` on the next read
(scrape, /debug/pipeline, snapshot) — or inline only past the
``max_pending_batches`` backlog bound.
"""

from __future__ import annotations

import bisect
import threading
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Deque, Dict, List, Optional, Tuple

from kubernetes_tpu.metrics import bucket_quantile, wide_duration_buckets

# Lock-discipline registry (kubernetes_tpu.analysis): reflector threads,
# apiserver handler threads, the scheduling loop, binding workers, and
# HTTP debug handlers all stamp into the monitor.  ``_delivered_mono`` /
# ``_applied_mono`` are deliberately NOT guarded — single float stores
# read by the dispatch sentinel (GIL-atomic, the _slo_buf discipline) —
# and neither is ``_pending``: deque append/popleft are GIL-atomic, and
# batch PROCESSING order is serialized by taking _mu around the whole
# popleft loop in ``_drain_pending``.
_KTPU_GUARDED = {
    "ControlPlaneMonitor": {
        "lock": "_mu",
        "guards": {
            "_open": None,
            "_done": None,
            "_hops": None,
            "_hops_synced": None,
            "_rv_stamp": None,
            "_rv_order": None,
            "_req_pending": None,
            "_lag_pending": None,
            "_cache_synced": None,
            "_stale_last": None,
            "_stale_peak": None,
            "_stale_hits": None,
            "_stale_breaches": None,
            "_cp_evicted": None,
        },
    },
}

# The watch-path hops stamped by the serving/client tier (everything
# after ``enqueue`` rides the flight recorder's breadcrumb kinds).
CHAIN_KINDS = (
    "api_write",
    "watch_delivery",
    "informer_handler",
    "enqueue",
    "pop",
    "assumed",
    "bind_start",
    "bound",
    "requeue",
)
_FLIGHT_KINDS = frozenset(
    ("enqueue", "pop", "assumed", "bind_start", "bound", "requeue")
)

# Canonical names for consecutive-stamp segments; an unmapped pair keeps
# the raw "a→b" form so the waterfall still telescopes to the e2e span.
SEGMENTS: Dict[Tuple[str, str], str] = {
    ("api_write", "watch_delivery"): "watch_fanout",
    ("watch_delivery", "informer_handler"): "informer_deliver",
    ("informer_handler", "enqueue"): "handler",
    ("enqueue", "pop"): "queue_wait",
    ("requeue", "pop"): "backoff",
    ("pop", "assumed"): "dispatch",
    ("pop", "requeue"): "dispatch",
    ("assumed", "bind_start"): "commit",
    ("assumed", "requeue"): "commit",
    ("bind_start", "bound"): "bind",
    ("bind_start", "requeue"): "bind",
}


@dataclass
class ControlPlaneConfig:
    # staleness sentinel: breach after `staleness_consecutive` dispatches
    # in a row observe newest-delivered − newest-applied > threshold
    staleness_threshold_s: float = 1.0
    staleness_consecutive: int = 3
    # chain retention: open chains (pods in flight) and closed chains
    # (bound pods the waterfall can still serve) are both LRU-bounded
    max_open_chains: int = 8192
    max_done_chains: int = 1024
    # deferred-ingest backlog bound: the flight-recorder sink only
    # appends raw batches; stitching happens on the next read (scrape,
    # /debug/pipeline, snapshot).  Past this many queued batches the
    # sink drains inline so an unscraped monitor can't grow unbounded.
    max_pending_batches: int = 8192
    # rv → write-timestamp ring per resource (delivery-lag join window)
    rv_window: int = 8192
    track: str = "controlplane"


def _hist_new(nb: int) -> list:
    """[bucket counts (+overflow), sum, n] — the off-registry accumulator
    shape Histogram.merge_counts drains on scrape."""
    return [[0] * (nb + 1), 0.0, 0]


class ControlPlaneMonitor:
    """One monitor per Scheduler (``sched.controlplane``); built by
    ``Scheduler.install_controlplane``."""

    def __init__(
        self,
        config: Optional[ControlPlaneConfig] = None,
        tracer=None,
        slo_getter: Optional[Callable] = None,
        mono_clock=time.monotonic,
    ):
        self.config = config or ControlPlaneConfig()
        self.enabled = True
        self.tracer = tracer
        # chaos-journal logical time (``Journal.now`` while a
        # JournalRecorder is attached; the replayer drives a cursor) —
        # chain stamps carry it so live and replayed chains compare
        # byte-for-byte on (kind, rv, lt)
        self.logical_time: Optional[Callable[[], int]] = None
        self._slo = slo_getter or (lambda: None)
        self._mono = mono_clock
        self._mu = threading.Lock()
        self._buckets = wide_duration_buckets()
        nb = len(self._buckets)
        # uid → [[kind, mono, rv, lt], ...] (insertion-ordered for LRU)
        self._open: "OrderedDict[str, List[list]]" = OrderedDict()
        self._done: "OrderedDict[str, dict]" = OrderedDict()
        self._cp_evicted = 0
        # per-hop durations, CUMULATIVE (hop_summary reads them; scrape
        # syncs deltas against _hops_synced): hop → [counts, sum, n]
        self._hops: Dict[str, list] = {}
        self._hops_synced: Dict[str, list] = {}
        self._hops_nb = nb
        # rv → api-write mono stamp, per resource (bounded join window)
        self._rv_stamp: Dict[str, Dict[int, float]] = {}
        self._rv_order: Dict[str, Deque[int]] = {}
        # apiserver request accounting pending sync:
        # (verb, resource, status) → [counts, sum, n]
        self._req_pending: Dict[Tuple[str, str, str], list] = {}
        # reflector delivery lag pending sync: resource → [counts, sum, n]
        self._lag_pending: Dict[str, list] = {}
        # last-synced snapshots of the api server's monotonic counters
        self._cache_synced: Dict[Tuple[str, str], int] = {}
        # staleness sentinel state (mutated by the scheduling loop only,
        # read by scrape under the same lock)
        self._stale_last = 0.0
        self._stale_peak = 0.0
        self._stale_hits = 0
        self._stale_breaches = 0
        # newest-delivered / newest-applied stamps: plain float stores
        # (GIL-atomic), written per event on the watch/handler paths —
        # a lock there would serialize reflector threads against drains
        self._delivered_mono: Optional[float] = None
        self._applied_mono: Optional[float] = None
        # deferred sink batches, (mono, lt, events) — appended lock-free
        # from the scheduling/bind paths (deque.append is GIL-atomic; lt
        # is captured at sink time so replayed chains stay byte-equal)
        # and stitched into chains under _mu by the next reader
        self._pending: Deque[tuple] = deque()
        self._api = None  # weakref to the attached ApiServer

    # ----- wiring -----------------------------------------------------------

    def attach_api_server(self, server) -> None:
        """In-process wiring: the server stamps api_write breadcrumbs
        through ``server.cp`` and scrape pulls its watch-cache counters."""
        server.cp = self
        self._api = weakref.ref(server)

    def attach_source(self, source) -> None:
        """Hook the RemoteClusterSource's reflectors so every delivered
        watch event stamps the newest-delivered clock + pod chains."""
        for inf in source.informers.values():
            inf._reflector.cp = self

    def make_sink(self, downstream=None):
        """Chain in front of the flight recorder's existing sink (the SLO
        evaluator's ingest_async) — one breadcrumb stream feeds both.

        The sink itself is deliberately almost free: one logical-time
        read plus a deque append per flight-recorder flush.  Chain
        stitching, hop bucketing, and span emission all happen in
        ``_drain_pending`` on the next *read* (scrape, /debug/pipeline,
        snapshot), so the scheduling and bind hot paths never pay for
        them — that is how the full tier stays inside its ≤2% drain
        budget even on a single core."""

        def sink(mono: float, events) -> None:
            if self.enabled:
                pend = self._pending
                pend.append((mono, self._lt(), events))
                if len(pend) > self.config.max_pending_batches:
                    self._drain_pending()
            if downstream is not None:
                downstream(mono, events)

        return sink

    # ----- producer sites (each gated by the caller on .enabled) ------------

    def _lt(self) -> Optional[int]:
        lt = self.logical_time
        if lt is None:
            return None
        try:
            return lt()
        except Exception:  # noqa: BLE001 — journal detached mid-stamp
            return None

    def _stamp_locked(self, uid: str, kind: str, rv, mono, lt) -> None:
        chain = self._open.get(uid)
        if chain is None:
            if len(self._open) >= self.config.max_open_chains:
                self._open.popitem(last=False)
                self._cp_evicted += 1
            chain = self._open[uid] = []
        chain.append([kind, mono, rv, lt])

    def note_api_write(self, res: str, rv: int, obj) -> None:
        """ApiServer._record: the event entered the watch cache at rv."""
        self.note_api_write_many(res, ((rv, obj),))

    def note_api_write_many(self, res: str, writes) -> None:
        """ApiServer._record_many: ``writes`` ((rv, obj), ...) entered the
        watch cache in one append — one clock reading and one acquisition
        of the lock for the lot."""
        mono = self._mono()
        lt = self._lt()
        window = self.config.rv_window
        with self._mu:
            stamps = self._rv_stamp.get(res)
            if stamps is None:
                stamps = self._rv_stamp[res] = {}
                self._rv_order[res] = deque()
            order = self._rv_order[res]
            for rv, obj in writes:
                if len(order) >= window:
                    stamps.pop(order.popleft(), None)
                stamps[rv] = mono
                order.append(rv)
                uid = getattr(obj, "uid", None)  # pods chain; nodes only join rv
                if uid is not None:
                    self._stamp_locked(uid, "api_write", rv, mono, lt)

    def note_delivery(self, res: str, rv: int, obj) -> None:
        """Reflector watch loop: the event reached this process (decoded,
        about to hit the informer handlers)."""
        mono = self._mono()
        lt = self._lt()
        self._delivered_mono = mono
        uid = getattr(obj, "uid", None)
        with self._mu:
            wrote = self._rv_stamp.get(res, {}).get(rv)
            if wrote is not None:
                acc = self._lag_pending.get(res)
                if acc is None:
                    acc = self._lag_pending[res] = _hist_new(self._hops_nb)
                self._observe_locked(acc, mono - wrote)
            if uid is not None:
                self._stamp_locked(uid, "watch_delivery", rv, mono, lt)

    def note_pod_handled(self, uid: str) -> None:
        """Scheduler.on_pod_add (unscheduled branch), under Scheduler._mu:
        the informer handler is applying the pod, enqueue imminent."""
        mono = self._mono()
        lt = self._lt()
        with self._mu:
            self._stamp_locked(uid, "informer_handler", None, mono, lt)

    def note_applied(self) -> None:
        """Entry of every scheduler informer handler (under Scheduler._mu
        — the apply completes before any dispatch can interleave)."""
        self._applied_mono = self._mono()

    def note_request(self, verb: str, res: str, status: int, dur_s: float) -> None:
        """ApiServer handler: one request served."""
        with self._mu:
            key = (verb, res, str(status))
            acc = self._req_pending.get(key)
            if acc is None:
                acc = self._req_pending[key] = _hist_new(self._hops_nb)
            self._observe_locked(acc, dur_s)

    def note_dispatch(self, bid: int) -> None:
        """Scheduling loop, at the batch-id stamp: sample the staleness
        sentinel.  Breach filing happens OUTSIDE the monitor lock — the
        evaluator takes its own lock and dumps to disk."""
        delivered = self._delivered_mono
        applied = self._applied_mono
        staleness = 0.0
        if delivered is not None and applied is not None:
            staleness = max(0.0, delivered - applied)
        cfg = self.config
        record = None
        with self._mu:
            self._stale_last = staleness
            if staleness > self._stale_peak:
                self._stale_peak = staleness
            if staleness > cfg.staleness_threshold_s:
                self._stale_hits += 1
            else:
                self._stale_hits = 0
            if self._stale_hits >= cfg.staleness_consecutive:
                self._stale_hits = 0
                self._stale_breaches += 1
                record = {
                    "objective": "snapshot_staleness",
                    "staleness_s": staleness,
                    "threshold_s": cfg.staleness_threshold_s,
                    "consecutive": cfg.staleness_consecutive,
                    "bid": bid,
                }
        if record is not None:
            slo = self._slo()
            if slo is not None:
                slo.external_breach(record)

    # ----- breadcrumb ingest (the flight-recorder sink chain) ---------------

    def _observe_locked(self, acc: list, dur: float) -> None:
        acc[0][bisect.bisect_left(self._buckets, dur)] += 1
        acc[1] += dur
        acc[2] += 1

    def _drain_pending(self) -> None:
        """Stitch every deferred sink batch into chains.  Runs at the top
        of each read path; batches are popped and processed under one _mu
        acquisition so cross-thread arrival order is preserved."""
        pend = self._pending
        if not pend:
            return
        kinds = _FLIGHT_KINDS
        spans: List[tuple] = []
        with self._mu:
            while True:
                try:
                    mono, lt, events = pend.popleft()
                except IndexError:
                    break
                for uid, kind, _detail in events:
                    if kind not in kinds:
                        continue
                    self._stamp_locked(uid, kind, None, mono, lt)
                    if kind == "bound":
                        spans.extend(self._finalize_locked(uid))
        if spans:
            self._emit_spans(spans)

    def _finalize_locked(self, uid: str) -> List[tuple]:
        chain = self._open.pop(uid, None)
        if not chain:
            return []
        hops = []
        for prev, cur in zip(chain, chain[1:]):
            name = SEGMENTS.get((prev[0], cur[0]), f"{prev[0]}→{cur[0]}")
            dur = cur[1] - prev[1]
            hops.append((name, prev[1], cur[1], dur))
            acc = self._hops.get(name)
            if acc is None:
                acc = self._hops[name] = _hist_new(self._hops_nb)
            self._observe_locked(acc, max(dur, 0.0))
        first_enq = next((e[1] for e in chain if e[0] == "enqueue"), None)
        self._done[uid] = {
            "chain": chain,
            "hops": hops,
            "e2e_s": (chain[-1][1] - first_enq) if first_enq is not None else None,
        }
        if len(self._done) > self.config.max_done_chains:
            self._done.popitem(last=False)
        tr = self.tracer
        if tr is not None and tr.enabled:
            return [(uid, hops, chain[-1][3])]
        return []

    def _emit_spans(self, spans: List[tuple]) -> None:
        """Per-hop spans on the synthetic control-plane track; mono stamps
        convert to the tracer's clock with one offset per flush."""
        tr = self.tracer
        if tr is None:
            return
        off = tr.now() - self._mono()
        track = self.config.track
        for uid, hops, _lt in spans:
            for name, t0, t1, _dur in hops:
                tr.complete_track(
                    track, name, t0 + off, t1 + off, cat="controlplane", pod=uid
                )

    # ----- queries ----------------------------------------------------------

    @staticmethod
    def _chain_dicts(chain: List[list]) -> List[dict]:
        return [
            {"kind": kind, "mono": mono, "rv": rv, "lt": lt}
            for kind, mono, rv, lt in chain
        ]

    def chain_signature(self, uid: str) -> Optional[List[list]]:
        """The replay-comparable projection of a chain: (kind, rv, lt)
        only — no wall/monotonic stamps, so a live recording and its
        journal replay serialize byte-identically."""
        self._drain_pending()
        with self._mu:
            rec = self._done.get(uid)
            chain = rec["chain"] if rec is not None else self._open.get(uid)
            if chain is None:
                return None
            return [[kind, rv, lt] for kind, _mono, rv, lt in chain]

    def pipeline_for(self, uid: str) -> Optional[dict]:
        """The per-hop lag waterfall /debug/pipeline?pod= serves."""
        self._drain_pending()
        with self._mu:
            rec = self._done.get(uid)
            if rec is not None:
                chain, hops, e2e = rec["chain"], rec["hops"], rec["e2e_s"]
                complete = True
            else:
                chain = self._open.get(uid)
                if chain is None:
                    return None
                hops = [
                    (
                        SEGMENTS.get((p[0], c[0]), f"{p[0]}→{c[0]}"),
                        p[1],
                        c[1],
                        c[1] - p[1],
                    )
                    for p, c in zip(chain, chain[1:])
                ]
                e2e, complete = None, False
            out = {
                "pod": uid,
                "complete": complete,
                "e2e_s": e2e,
                "chain": self._chain_dicts(chain),
                "hops": [
                    {"hop": name, "t0": t0, "t1": t1, "duration_s": dur}
                    for name, t0, t1, dur in hops
                ],
            }
        return out

    def hop_summary(self) -> Dict[str, dict]:
        """Aggregate per-hop decomposition over every chain closed so far
        (bench's config16_pipeline_* source; /debug/pipeline default)."""
        self._drain_pending()
        with self._mu:
            rows = {
                name: (list(acc[0]), acc[1], acc[2])
                for name, acc in self._hops.items()
            }
        out = {}
        for name, (counts, sum_, n) in rows.items():
            p50, _ = bucket_quantile(self._buckets, counts, 0.5)
            p99, _ = bucket_quantile(self._buckets, counts, 0.99)
            out[name] = {
                "count": n,
                "sum_s": sum_,
                "mean_s": (sum_ / n) if n else 0.0,
                "p50_s": p50,
                "p99_s": p99,
            }
        return out

    def staleness(self) -> dict:
        with self._mu:
            return {
                "last_s": self._stale_last,
                "peak_s": self._stale_peak,
                "threshold_s": self.config.staleness_threshold_s,
                "breaches": self._stale_breaches,
            }

    def snapshot(self) -> dict:
        """/debug/pipeline without ?pod= — the tier's status surface."""
        self._drain_pending()
        with self._mu:
            open_n, done_n, evicted = (
                len(self._open),
                len(self._done),
                self._cp_evicted,
            )
        return {
            "enabled": self.enabled,
            "open_chains": open_n,
            "done_chains": done_n,
            "evicted_chains": evicted,
            "staleness": self.staleness(),
            "hops": self.hop_summary(),
        }

    # ----- scrape sync ------------------------------------------------------

    def sync_registry(self, prom) -> None:
        """Drain pending accumulators into the scheduler's registry and
        refresh the serving-tier gauges — scrape-time only, so neither
        the apiserver handlers nor the reflectors ever touch a registry
        lock (the PR 7 merge_counts discipline)."""
        self._drain_pending()
        with self._mu:
            hops = []
            for name, acc in self._hops.items():
                prev = self._hops_synced.get(name)
                if prev is None:
                    prev = self._hops_synced[name] = _hist_new(self._hops_nb)
                dn = acc[2] - prev[2]
                if dn:
                    dcounts = [a - b for a, b in zip(acc[0], prev[0])]
                    hops.append((name, (dcounts, acc[1] - prev[1], dn)))
                    prev[0] = list(acc[0])
                    prev[1], prev[2] = acc[1], acc[2]
            reqs = list(self._req_pending.items())
            self._req_pending = {}
            lags = list(self._lag_pending.items())
            self._lag_pending = {}
            stale = self._stale_last
        for name, (counts, sum_, n) in hops:
            prom.pipeline_hop_duration.merge_counts(counts, sum_, n, hop=name)
        for (verb, res, status), (counts, sum_, n) in reqs:
            prom.apiserver_request_duration.merge_counts(
                counts, sum_, n, verb=verb, resource=res, status=status
            )
        for res, (counts, sum_, n) in lags:
            prom.informer_delivery_lag.merge_counts(counts, sum_, n, resource=res)
        prom.snapshot_staleness.set(stale)
        api = self._api() if self._api is not None else None
        if api is None:
            return
        for res, cache in api.caches.items():
            with cache.cond:
                occupancy = len(cache.events)
                head_rv = cache.rv
                compactions = cache.compactions
                gone = cache.gone_total
                watcher_rvs = list(cache.watchers.values())
            prom.watch_window_events.set(occupancy, resource=res)
            lag = max((head_rv - rv for rv in watcher_rvs), default=0)
            prom.watch_fanout_lag.set(lag, resource=res)
            with self._mu:
                dc = compactions - self._cache_synced.get((res, "compact"), 0)
                dg = gone - self._cache_synced.get((res, "gone"), 0)
                self._cache_synced[(res, "compact")] = compactions
                self._cache_synced[(res, "gone")] = gone
            if dc:
                prom.watch_compactions.inc(dc, resource=res)
            if dg:
                prom.watch_relists.inc(dg, resource=res)
        for what in ("txns", "items", "fallback_items"):
            total = getattr(api, "bulk_bind_" + what)
            with self._mu:
                key = ("bulk_bind", what)
                d = total - self._cache_synced.get(key, 0)
                self._cache_synced[key] = total
            if d:
                prom.apiserver_bulk_bind.inc(d, what=what)
        with api._wire_mu:
            wire = dict(api.wire_bytes)
        for (codec, direction), total in wire.items():
            with self._mu:
                key = ("wire", codec, direction)
                d = total - self._cache_synced.get(key, 0)
                self._cache_synced[key] = total
            if d:
                prom.wire_bytes_total.inc(d, codec=codec, direction=direction)
