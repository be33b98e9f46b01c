"""Collector policy: the serving loop owns the garbage collector's schedule.

A full (generation-2) collection stops every thread of the process and
walks the whole heap: every node, every pod several times over (store,
informer cache, scheduler cache, queue), the compiled-program caches.  None
of that is garbage, and the interpreter cannot know it; nor can it know
when no pod is waiting.  The loop that serves knows both, so while a
``SchedulerServer`` leads:

* **engage** (the loop starts leading): ``gc.freeze()`` moves everything
  allocated so far — the synced cluster state — out of the collector's
  working set.  O(1); nothing is collected there, a backlog may be waiting.
  The young generation is sized for a scheduler's batch and the automatic
  full collection is raised to a ceiling (below).
* **idle pass** (``LoopCollector.poll``): once the loop has had nothing to
  decide and no bind in flight for ``IDLE_SETTLE_S``, and the interpreter
  has collected at least once since the last pass, collect the unfrozen
  heap and freeze again, so that what the last drain bound joins the frozen
  state.  The first pass and every ``UNFREEZE_EVERY``-th after it unfreeze
  first: cyclic garbage among frozen objects (start-up's, deleted pods,
  replaced cache entries) is reclaimed there.
* **ceiling**: a loop that is never idle still collects.  The interpreter's
  own full collection comes through once ``FULL_CEILING`` middle passes
  have run since the last one; after freezing it walks what was allocated
  since, not the cluster.  The collector is never switched off.
* **release** (``stop()``, lost leadership): thresholds as found,
  ``gc.unfreeze()``.  Engagement is process-wide and counted: two servers
  in one process share one.

Embedding ``Scheduler`` without a ``SchedulerServer`` leaves the
interpreter's defaults alone: the policy belongs to the process that
serves, not to the class that decides.

A collection can start inside ANY allocation, also one made under
``Scheduler._mu``, ``SchedulerServer``'s locks or ``PhaseAccumulator``'s:
the ``gc.callbacks`` entry below books into plain module ints and takes no
lock.  ``LoopCollector`` moves them into the accumulator and the registry
from the loop thread and the scrape, as ``ApiServer``'s ``bulk_bind_*``
ints are read.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional, Tuple

# Net container allocations between young collections.  The interpreter's
# default of 700 is a few pods' worth: a 10,000-pod drain ran 500 young and
# 46 middle passes (PERF.md §6 PR 35).  50,000 is about a batch of 1,000
# pods with their watch events (35-80 net allocations a pod); cyclic garbage
# waits at most that long, ~10 MB.
YOUNG_THRESHOLD = 50_000
# Middle passes between automatic full collections (the interpreter's third
# threshold; the second stays as found, 10).  A full pass becomes eligible
# after 2 x 12 young passes = 1.2 M net allocations, about 170 MB of pods
# and events at the 138 B an object a 10,000-pod drain grew by (PERF.md §6
# PR 35): the bound on what a never-idle loop holds before it pays one
# pause for it.  The default (700, 10, 10) allows one after 85,000.
FULL_CEILING = 1
# The loop counts as idle once it has had nothing to decide and no bind in
# flight for this long (five polls at the default 20 ms).
IDLE_SETTLE_S = 0.1
# The first idle pass and every this-many-th after it unfreeze before they
# collect (a walk of the whole heap, at idle).
UNFREEZE_EVERY = 8

GENERATIONS = (0, 1, 2)

_mu = threading.Lock()  # engage / release / idle pass; never the callback
_engaged = 0
_found: Optional[Tuple[int, int, int]] = None  # thresholds before engaging
_passes = 0  # idle passes of this engagement

# written by the callback only (one collection runs at a time, under the
# interpreter lock); read anywhere without a lock
_load = [0, 0, 0]  # automatic collections, by generation
_idle = [0, 0, 0]  # collections made by an idle pass
_pause_s = [0.0, 0.0, 0.0]
_in_idle_pass = False
_t0 = 0.0
_BOOKED = (("load", _load), ("idle", _idle), ("pause", _pause_s))


def _on_gc(phase: str, info: dict) -> None:
    global _t0
    if phase == "start":
        _t0 = time.perf_counter()
        return
    g = info["generation"]
    _pause_s[g] += time.perf_counter() - _t0
    (_idle if _in_idle_pass else _load)[g] += 1


def engaged() -> int:
    """How many loops hold the policy engaged in this process."""
    return _engaged


def _engage() -> None:
    global _engaged, _found, _passes
    with _mu:
        _engaged += 1
        if _engaged > 1:
            return
        _found = gc.get_threshold()
        _passes = 0
        gc.callbacks.append(_on_gc)
        gc.set_threshold(YOUNG_THRESHOLD, _found[1], FULL_CEILING)
        gc.freeze()


def _release() -> None:
    global _engaged
    with _mu:
        _engaged -= 1
        if _engaged:
            return
        gc.set_threshold(*_found)
        gc.unfreeze()
        gc.callbacks.remove(_on_gc)


def _idle_pass() -> bool:
    """Collect the unfrozen heap and freeze again; unfreeze first on the
    passes that are due to.  False if the policy was released meanwhile."""
    global _passes, _in_idle_pass
    with _mu:
        if not _engaged:
            return False
        unfreeze = _passes % UNFREEZE_EVERY == 0
        _passes += 1
        _in_idle_pass = True
        try:
            if unfreeze:
                gc.unfreeze()
            gc.collect()
            gc.freeze()
        finally:
            _in_idle_pass = False
    return True


class LoopCollector:
    """One serving loop's handle on the process-wide policy.  ``engage``,
    ``release`` and ``poll`` are the loop's (``release`` also ``stop()``'s);
    ``sync_registry`` is the scrape's."""

    def __init__(self, phases) -> None:
        self.phases = phases  # the scheduler's PhaseAccumulator
        self._mu = threading.Lock()  # engage vs release from stop()
        self._engaged = False
        self._idle_since: Optional[float] = None
        self._load_at_pass = 0
        self._full_booked = 0
        self._synced = {name: list(totals) for name, totals in _BOOKED}

    def engage(self) -> None:
        with self._mu:
            if self._engaged:
                return
            self._engaged = True
            self._idle_since = None
            self._load_at_pass = sum(_load)
            self._full_booked = _load[2]
            _engage()

    def release(self) -> None:
        with self._mu:
            if not self._engaged:
                return
            self._engaged = False
            _release()
        self._book_full_under_load()

    def _book_full_under_load(self) -> None:
        full = _load[2]
        if full != self._full_booked:
            self.phases.count("gc.full_under_load", full - self._full_booked)
            self._full_booked = full

    def poll(self, busy: bool) -> bool:
        """Called by the loop once an iteration, after ``schedule_pending``.
        ``busy``: a pod waits in the active queue or a bind is in flight,
        read this instant, so a pass never starts over work that arrived.
        True if an idle pass ran."""
        self._book_full_under_load()
        if busy:
            self._idle_since = None
            return False
        now = time.monotonic()
        if self._idle_since is None:
            self._idle_since = now
        if now - self._idle_since < IDLE_SETTLE_S:
            return False
        if sum(_load) == self._load_at_pass:
            return False  # under a young generation's worth allocated since
        with self.phases.span("gc.idle_pass"):
            ran = _idle_pass()
        if ran:
            self._load_at_pass = sum(_load)
            self.phases.count("gc.idle_passes", 1)
        return ran

    def sync_registry(self, prom) -> None:
        """Move what the callback booked since the last scrape into the
        scheduler's registry."""
        for name, totals in _BOOKED:
            synced = self._synced[name]
            for g in GENERATIONS:
                d = totals[g] - synced[g]
                if not d:
                    continue
                synced[g] += d
                if name == "pause":
                    prom.gc_pause_seconds.inc(d, generation=g)
                else:
                    prom.gc_collections.inc(d, generation=g, when=name)
        # counted here, not at the freeze: the count walks the frozen list
        # (15 ms at 600,000 objects), which engaging must not pay
        prom.gc_frozen_objects.set(gc.get_freeze_count() if _engaged else 0)
