"""Span tracer: Chrome trace-event JSON for one scheduling process.

The distributed-tracing role the reference scheduler gets from component
tracing (utiltrace + the kube-scheduler's OpenTelemetry spans) rebuilt for
the batched hot loop: spans cover a whole drain, each batch's dispatch and
harvest halves, the per-phase breakdown (queue_pop/pack/h2d/device/d2h/
commit/bind — fed by metrics.PhaseAccumulator), and the binding workers'
chunks, each on its own thread track.  The export is the Chrome trace-event
format ("traceEvents" complete/instant events with microsecond ts/dur), so
``chrome://tracing`` and Perfetto load it directly.

Spans carry scheduler context in ``args``: pod uids (small batches), batch
ids, pod counts — and, when a chaos journal is attached
(``JournalRecorder.attach`` wires ``tracer.logical_time``), the journal's
logical timestamp ``lt``, so a wall-clock span can be located in the
replayable journal stream.

Cost model: when ``enabled`` is False every instrumentation site reduces to
one attribute load and a branch — no locks, no clock reads, no allocation,
and ZERO device-path involvement (nothing here touches jax).  When enabled,
each span is one lock acquisition + one dict append; the buffer is bounded
(``max_events``), overflow increments a drop counter instead of growing.

Black-box mode (``blackbox_start``): the same recorder as an ALWAYS-ON
bounded rolling ring — overflow evicts the OLDEST event (counted) instead
of dropping the newest, so the buffer always holds the trailing window of
spans.  An SLO breach (observability/slo.py) freezes the ring
(``blackbox_freeze``) and exports it, so the trace of the bad window
exists *after* the incident without anyone having started a capture.
The hot-path discipline is identical: off is one attribute read per site.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

# Lock-discipline registry (kubernetes_tpu.analysis): the scheduling loop,
# binding workers, and HTTP debug handlers all record/export concurrently.
_KTPU_GUARDED = {
    "Tracer": {
        "lock": "_mu",
        "guards": {
            "_trace_events": None,
            "_trace_dropped": None,
            "_trace_evicted": None,
            "_ring_mode": None,
            "_ring_cap": None,
            "_tid_names": None,
            "_track_tids": None,
            "_overhead_s": None,
        },
    },
}

DEFAULT_MAX_EVENTS = 200_000
# black-box ring default: deep enough that a multi-second bad window of
# batch/phase spans survives until the breach evaluator fires, small
# enough (~15 MB of dicts) to sit resident in a serving process forever
DEFAULT_BLACKBOX_EVENTS = 65_536


class Tracer:
    """Bounded in-memory span recorder with Chrome trace-event export.

    ``enabled`` is the single hot-path gate: instrumentation sites read it
    as a plain attribute before doing any work.  ``start()`` resets the
    buffer and enables; ``stop()`` disables but keeps events for export.
    """

    def __init__(
        self,
        max_events: int = DEFAULT_MAX_EVENTS,
        clock=time.perf_counter,
    ):
        self.enabled = False
        self.max_events = max_events
        self._clock = clock
        self._mu = threading.Lock()
        self._trace_events: deque = deque()
        self._trace_dropped = 0
        self._trace_evicted = 0
        # black-box ring mode: overflow evicts OLDEST instead of dropping
        # the newest — the buffer becomes a rolling trailing window
        self._ring_mode = False
        self._ring_cap = DEFAULT_BLACKBOX_EVENTS
        self._tid_names: Dict[int, str] = {}
        # synthetic tracks (the dispatch ledger's submit spans, the
        # control plane's hops):
        # track name → synthetic tid, far above any OS thread ident so
        # Perfetto renders them as their own named rows
        self._track_tids: Dict[str, int] = {}
        self._overhead_s = 0.0
        self._t0 = clock()
        # optional journal logical-time source (JournalRecorder.attach sets
        # it to Journal.now) — sampled into every span's args as "lt"
        self.logical_time = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        """Begin a MANUAL capture (drop-newest on overflow).  Overrides an
        active black-box ring until ``blackbox_start`` re-arms it."""
        with self._mu:
            self._trace_events = deque()
            self._trace_dropped = 0
            self._trace_evicted = 0
            self._ring_mode = False
            self._tid_names = {}
            self._track_tids = {}
            self._overhead_s = 0.0
            self._t0 = self._clock()
        self.enabled = True

    def stop(self) -> None:
        self.enabled = False

    def blackbox_start(self, capacity: int = DEFAULT_BLACKBOX_EVENTS) -> None:
        """Arm (or re-arm after a freeze/dump) the always-on black-box
        ring: recording on, evict-oldest at ``capacity`` events."""
        with self._mu:
            self._trace_events = deque()
            self._trace_dropped = 0
            self._trace_evicted = 0
            self._ring_mode = True
            self._ring_cap = max(int(capacity), 1)
            self._tid_names = {}
            self._track_tids = {}
            self._overhead_s = 0.0
            self._t0 = self._clock()
        self.enabled = True

    def blackbox_freeze(self) -> Optional[dict]:
        """Freeze the black-box ring (stop recording, keep events) and
        return ``{"trace": <export>, "freeze_offset_us": <ring-relative
        freeze time>}`` — None when the ring isn't armed.  The caller
        (the SLO breach handler) dumps the trace and calls
        ``blackbox_start`` again to resume recording."""
        with self._mu:
            if not self._ring_mode:
                return None
            # armed-check, recording stop, freeze stamp, and ring snapshot
            # in ONE critical section: a concurrent manual start() (the
            # /debug/trace HTTP thread) serializes either before us (ring
            # disarmed — we return None, the operator's capture survives)
            # or after us (it swaps in a fresh buffer — our snapshot is
            # still the bad window, and its capture keeps recording)
            self.enabled = False
            freeze_offset_us = (self._clock() - self._t0) * 1e6
            events = list(self._trace_events)
            names = dict(self._tid_names)
            dropped = self._trace_dropped
        return {
            "trace": self._build_trace(events, names, dropped),
            "freeze_offset_us": freeze_offset_us,
        }

    def now(self) -> float:
        return self._clock()

    # -- recording -----------------------------------------------------------

    def _append(self, name, cat, ph, t0, t1, args, track=None) -> None:
        """Finalize and buffer one event.  The origin read, the clamp, and
        the buffer append all happen under ONE lock hold: start() swaps
        the buffer and the origin atomically, so a concurrent recorder can
        never stamp a stale origin into the fresh buffer.  A span whose
        work STARTED before the capture renders only its in-capture part —
        an unclamped t0 would paint pre-trace time as a fat span at the
        origin.  ``track`` routes the event onto a named SYNTHETIC track
        (a tid above any OS thread ident) instead of the calling thread's
        — the synthetic spans' own row in Perfetto."""
        t_in = self._clock()
        if track is None:
            tid = threading.get_ident()
            tname = threading.current_thread().name
        else:
            tid = None
            tname = track
        with self._mu:
            if tid is None:
                tid = self._track_tids.get(track)
                if tid is None:
                    tid = self._track_tids[track] = (1 << 40) + len(
                        self._track_tids
                    )
            if tid not in self._tid_names:
                self._tid_names[tid] = tname
            origin = self._t0
            if t0 < origin:
                t0 = origin
            if t1 < t0:
                t1 = t0
            ev = {
                "name": name,
                "cat": cat,
                "ph": ph,
                "ts": (t0 - origin) * 1e6,
                "pid": 1,
                "tid": tid,
                "args": args,
            }
            if ph == "X":
                ev["dur"] = (t1 - t0) * 1e6
            else:
                ev["s"] = "t"
            if self._ring_mode:
                # black-box ring: recent history always wins
                if len(self._trace_events) >= self._ring_cap:
                    self._trace_events.popleft()
                    self._trace_evicted += 1
                self._trace_events.append(ev)
            elif len(self._trace_events) >= self.max_events:
                self._trace_dropped += 1
            else:
                self._trace_events.append(ev)
            self._overhead_s += self._clock() - t_in

    def complete(self, name: str, t0: float, cat: str = "sched", **args) -> None:
        """Record a complete ('X') event spanning [t0, now).  ``t0`` is a
        reading of ``self.now()`` taken when the work started."""
        if not self.enabled:
            return
        t1 = self._clock()
        self._record_x(name, t0, t1, cat, args)

    def complete_tail(
        self, name: str, dur_s: float, cat: str = "phase", **args
    ) -> None:
        """Record a complete event of ``dur_s`` seconds ENDING now — the
        shape PhaseAccumulator.add has (it learns the duration after the
        fact, at the accumulate call)."""
        if not self.enabled:
            return
        t1 = self._clock()
        self._record_x(name, t1 - dur_s, t1, cat, args)

    def _record_x(self, name, t0, t1, cat, args) -> None:
        lt = self.logical_time
        if lt is not None:
            try:
                args = dict(args, lt=lt())
            except Exception:  # noqa: BLE001 — journal detached mid-trace
                pass
        self._append(name, cat, "X", t0, t1, args)

    def complete_track(
        self, track: str, name: str, t0: float, t1: float,
        cat: str = "track", **args,
    ) -> None:
        """Record a complete event spanning [t0, t1) on the named
        synthetic track (the dispatch ledger's ``dispatch_submit`` spans,
        the control plane's hops — rendered alongside the host thread
        tracks).  Carries the journal
        logical time like every other span when one is attached."""
        if not self.enabled:
            return
        lt = self.logical_time
        if lt is not None:
            try:
                args = dict(args, lt=lt())
            except Exception:  # noqa: BLE001 — journal detached mid-trace
                pass
        self._append(name, cat, "X", t0, t1, args, track=track)

    def instant(self, name: str, cat: str = "sched", **args) -> None:
        if not self.enabled:
            return
        lt = self.logical_time
        if lt is not None:
            try:
                args = dict(args, lt=lt())
            except Exception:  # noqa: BLE001
                pass
        now = self._clock()
        self._append(name, cat, "i", now, now, args)

    def span(self, name: str, cat: str = "sched", **args) -> "_Span":
        """Context manager form; a no-op singleton when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    # -- export --------------------------------------------------------------

    def export(self) -> dict:
        """Perfetto/chrome://tracing-loadable trace object."""
        with self._mu:
            events = list(self._trace_events)
            names = dict(self._tid_names)
            dropped = self._trace_dropped
        return self._build_trace(events, names, dropped)

    @staticmethod
    def _build_trace(events, names, dropped) -> dict:
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 1,
                "tid": 0,
                "args": {"name": "kubernetes-tpu-scheduler"},
            }
        ]
        for tid, tname in sorted(names.items()):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 1,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": dropped},
        }

    def stats(self) -> dict:
        with self._mu:
            return {
                "enabled": self.enabled,
                "mode": "blackbox" if self._ring_mode else "capture",
                "events": len(self._trace_events),
                "dropped": self._trace_dropped,
                "evicted": self._trace_evicted,
                "overhead_s": self._overhead_s,
                "max_events": (
                    self._ring_cap if self._ring_mode else self.max_events
                ),
            }


class _Span:
    __slots__ = ("tr", "name", "cat", "args", "_t0")

    def __init__(self, tr: Tracer, name: str, cat: str, args: dict):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._t0 = self.tr.now()
        return self

    def __exit__(self, *exc):
        if self.tr.enabled:
            self.tr._record_x(
                self.name, self._t0, self.tr.now(), self.cat, self.args
            )
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
