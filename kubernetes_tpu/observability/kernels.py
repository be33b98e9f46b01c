"""Device telemetry ledger: per-kernel dispatch accounting.

The host side of the pipeline is thoroughly observed (spans, flight
recorder, SLO attribution); the device side — the jit roots that ARE the
system — was a black box beyond a recompile counter and two unattributed
aggregate totals.  The ``DispatchLedger`` closes that: every registered
jit root (the same roster the sanitizer's retrace hook sweeps —
``analysis.sanitizer._discover_jit_roots`` plus anything that arrives
through ``register_jit_root``) is wrapped with a ``_LedgerRoot`` proxy
that accounts each dispatch:

  * **execute wall time** (``execute_s``) — HOST seconds inside the
    dispatch call.  On an asynchronous backend that is enqueue (submit)
    time, NOT device time (the same definition the ``device`` phase
    uses): the tracer track these spans land on is called
    ``dispatch_submit`` for that reason.  Synchronous work, first-trace
    time, and any blocking the call performs land here in full; the
    device latency the host failed to hide shows up in the per-kernel d2h
    series below, and device time proper is read from a profiler trace
    (each dispatch holds a ``ktpu.dispatch.<kernel>`` annotation open, so
    under a profiler session the call sits on the device trace's clock).
  * **first-trace compile time** — a dispatch that grew the root's
    compilation cache (``fn._cache_size()``) is a compile: its wall time
    counts into ``compiles``/``compile_s`` instead of the execute series,
    so a compile storm can't masquerade as a kernel regression.
  * **batch-shape buckets** — dispatches are keyed by the (shapes,
    dtypes, statics) of their arguments PLUS their device placement
    (device count + mesh axis shape off the most-sharded argument), so a
    mesh-partitioned dispatch never shares an execute-time series — or a
    sentinel baseline — with its single-chip twin; each kernel reports
    its bucket population, and the bucket's abstract args are retained (as
    ``ShapeDtypeStruct`` leaves — never the arrays, which may be donated)
    for cost analysis.
  * **XLA cost estimates** — ``fn.lower(*abstract).cost_analysis()``
    FLOPs / bytes-accessed per bucket, computed LAZILY on the first
    table request and memoized per (kernel, bucket): the lowering
    re-trace is far too slow for the dispatch path, and a repeat shape
    must never pay it twice.
  * **d2h attribution** — ``Scheduler._d2h`` threads a kernel tag
    through the choke point (ANALYSIS.md §d2h); the ledger splits
    ``scheduler_tpu_d2h_bytes_total`` into per-kernel bytes / seconds /
    fetches, with untagged fetches under ``_untagged`` so the per-kernel
    rows always sum to the aggregate counter.
  * **live HBM** — ``device.memory_stats()`` rows (bytes_in_use / peak /
    limit) surface in the table and as scrape-refreshed gauges where the
    backend supports them (CPU returns None; gated).
  * **regression sentinel** — a per-kernel rolling execute-time baseline
    (EWMA over non-compile dispatches, outliers excluded so a regression
    can't teach the baseline to accept it).  ``sustain`` consecutive
    dispatches past ``factor``× the warm baseline is a sustained breach:
    the ledger files a ``kernel_regression`` breach record NAMING the
    kernel through ``SLOEvaluator.external_breach`` — the PR-7 freeze →
    dump → re-arm machinery — and counts it in
    ``scheduler_tpu_kernel_regressions_total{kernel=}``.

Cost model: the ``kernelLedger`` kill switch reduces the disabled path
to the wrapper's single module-global read + branch per dispatch (the
tracer's discipline); enabled, each dispatch pays two clock reads, one
``_cache_size`` probe, a flat shape-key build, and one short lock hold —
per BATCH, not per pod, which keeps it unmeasurable next to the
dispatches themselves (measured numbers in OBSERVABILITY.md §5).

Attribution scope: the wrapped roots are process-global (module
attributes), the ledger is per-Scheduler; dispatches route to the
ACTIVE ledger (``activate``, weakly held — the normal one-scheduler
process routes exactly).  ``Scheduler._d2h`` records into its OWN
scheduler's ledger, so per-kernel d2h rows reconcile per scheduler.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax

from kubernetes_tpu.metrics import annotation

# Lock-discipline registry (kubernetes_tpu.analysis): the scheduling
# loop records dispatches, binding workers/HTTP handlers read tables,
# and the planner thread records d2h — all concurrently.
_KTPU_GUARDED = {
    "DispatchLedger": {
        "lock": "_mu",
        "guards": {
            "_kstats": None,
            "_cost_memo": None,
            "_cost_hits": None,
            "_cost_misses": None,
            "_regressions": None,
            "_breakers": None,
        },
    },
}

# ---------------------------------------------------------------------------
# per-kernel circuit breaker (ISSUE 15: the device-fault robustness tier)
# ---------------------------------------------------------------------------

# a kernel whose dispatches keep failing trips OPEN after this many
# consecutive failed dispatches (abandoned retries, real backend errors,
# watchdog stalls, poisoned readbacks, and sentinel sustained-breach
# verdicts all count one each; any success resets the streak)
BREAKER_TRIP_THRESHOLD = 3
# in-place retries per dispatch for faults raised BEFORE the kernel ran
# (injected errors: the args — possibly donated — are still live; a real
# backend error never retries in place, its buffers may be consumed)
BREAKER_RETRIES = 2
BREAKER_BACKOFF_S = 0.0  # per-attempt backoff (scaled by attempt number)
# cooldown is counted in DENIED dispatch-family requests, not wall time:
# routing checks are sequenced by the scheduling loop, so breaker state
# transitions — and therefore the chaos fault schedule that depends on
# dispatch ordinals — replay deterministically from the seed alone
BREAKER_HALF_OPEN_AFTER = 8

BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half_open"
_BREAKER_GAUGE = {BREAKER_CLOSED: 0, BREAKER_OPEN: 1, BREAKER_HALF_OPEN: 2}


class DispatchFailed(RuntimeError):
    """A kernel dispatch was abandoned (retries exhausted, a real backend
    error, or an un-retryable injected fault).  Callers route the batch
    to the kernel's registered fallback engine — the breaker fallback
    roster below names it — and, for ``kind == "mesh_device_loss"``,
    degrade the mesh first (Scheduler._degrade_mesh)."""

    def __init__(self, kernel: str, cause, kind: str = "dispatch_error"):
        super().__init__(f"dispatch of {kernel} failed ({kind}): {cause}")
        self.kernel = kernel
        self.cause = cause
        self.kind = kind


class BreakerOpen(DispatchFailed):
    """A dispatch reached an OPEN breaker (the routing gates normally
    prevent this; an ungated site falls back exactly like a failure)."""

    def __init__(self, kernel: str):
        super().__init__(kernel, "circuit breaker open", kind="breaker_open")


# Every registered jit root must declare how the scheduler drains when
# its breaker is open: a ``fallback(<engine>): <how>`` story naming the
# parity-certified engine that replaces it, or an explicit
# ``no_fallback: <why>`` waiver.  The static analyzer's ``breaker`` rule
# (kubernetes_tpu/analysis/breaker.py) gates this literal against the
# discovered jit-root surface — the same burn-down discipline as the
# shard rule's ``resolved(...)`` roster.
_KTPU_BREAKER_FALLBACKS = {
    "chain.chain_dispatch": (
        "fallback(direct): the chained pipeline drains and the live batch "
        "degrades to per-pod host-oracle cycles; later batches redispatch "
        "on the direct wave/scan path (same verdict kernels, no overlap)"
    ),
    "coscheduling.workloads_run": (
        "fallback(serial-oracle): the workloads gate refuses and the batch "
        "degrades to the per-pod host-plugin cycle — the gangDispatch "
        "kill-switch path (WORKLOADS.md; decision-identical for DRA/volume "
        "pods, gangs lose quorum semantics exactly as documented there)"
    ),
    "coscheduling.workloads_schedule": (
        "fallback(serial-oracle): inner admission scan of workloads_run — "
        "same routing gate, same per-pod host-plugin fallback"
    ),
    "counterfactual.counterfactual_run": (
        "fallback(serial-oracle): fork specs replay through "
        "oracle/planner.serial_plan — the plannerKernel kill-switch engine "
        "(decision-identical, plan_vs_serial_oracle)"
    ),
    "explain.explain_masks": (
        "no_fallback: read-only diagnosis endpoint — a failure surfaces as "
        "an error field in /debug/explain; no placement depends on it"
    ),
    "fastpath.sig_scan": (
        "fallback(host-committer): the FastCommitter lazy-heap greedy "
        "answers the batch bit-identically (tests/test_fastpath.py); the "
        "device lineage re-materializes from it at the next dispatch"
    ),
    "fastpath.static_eval": (
        "fallback(scan): a failed static eval fails the fast gate and the "
        "batch takes the direct gang-scan path, which reads no "
        "per-signature rows"
    ),
    "gang.gang_run": (
        "fallback(serial-oracle): pods degrade to one-pod host-oracle "
        "cycles (_schedule_one_extender) — the fallback ladder's floor, "
        "bit-identical by the parity property"
    ),
    "gang.gang_schedule": (
        "fallback(serial-oracle): inner scan of gang_run — same routing "
        "gate, same per-pod host-oracle fallback"
    ),
    "pipeline._pipeline": (
        "no_fallback: the standalone parity harness's reference engine — "
        "it IS the ladder's floor and runs outside the Scheduler"
    ),
    "preemption.narrow_candidates": (
        "fallback(superset): narrowing is an optimization — on failure the "
        "preemption evaluator walks the full candidate node set "
        "(superset-sound by construction)"
    ),
    "resident.resident_run": (
        "fallback(host-committer): the epoch-guarded resync drops the "
        "device lineage and the FastCommitter greedy finishes the run "
        "bit-identically (RESIDENT.md fallback matrix)"
    ),
    "resident.usage_checksum": (
        "no_fallback: the epoch guard's integrity probe — a failure here "
        "IS the fault signal, booked against the resident family's breaker"
    ),
    "wave.wave_run": (
        "fallback(scan): wave-shaped batches ride the gang scan — the "
        "waveDispatch kill-switch path, bit-identical to queue order by "
        "construction (WAVE.md)"
    ),
    "wave.wave_schedule": (
        "fallback(scan): inner conflict-resolution scan of wave_run — "
        "same gate, same gang-scan fallback"
    ),
}


def breaker_fallbacks() -> Dict[str, str]:
    """The breaker fallback roster (copy) — tests assert runtime jit-root
    coverage against it; the static analyzer reads the literal."""
    return dict(_KTPU_BREAKER_FALLBACKS)


class _BreakerState:
    """Per-kernel breaker bookkeeping; mutated under the ledger's _mu
    (the ``_breakers`` dict is the registered guarded state)."""

    __slots__ = (
        "state",
        "failures",
        "denials",
        "trips",
        "last_kind",
        "half_open_probes",
        "latched",
    )

    def __init__(self) -> None:
        self.state = BREAKER_CLOSED
        self.failures = 0  # consecutive, resets on success
        self.denials = 0  # while open — the count-based cooldown
        self.trips = 0
        self.last_kind = ""
        self.half_open_probes = 0
        self.latched = False  # force_breaker_open: no half-open cooldown


# chaos hook (chaos/device.py installs a DeviceFaultInjector; None in
# production).  Module-global like the active-ledger ref: the hot path
# reads one global and never imports the chaos package.
_fault_injector = None


def set_fault_injector(inj) -> None:
    global _fault_injector
    _fault_injector = inj


def fault_injector():
    return _fault_injector

# the sentinel's defaults: a kernel must have this many warm (non-compile)
# samples before its baseline judges anything; a sustained run of
# dispatches all past factor× baseline is a breach
SENTINEL_MIN_SAMPLES = 16
SENTINEL_FACTOR = 4.0
SENTINEL_SUSTAIN = 5
# dispatches faster than this never breach — µs-level submits jitter by
# factors without meaning anything
SENTINEL_FLOOR_S = 0.002
# EWMA step for the rolling baseline (slow: the baseline tracks drift,
# not noise)
BASELINE_ALPHA = 0.05

_UNTAGGED = "_untagged"


class _KernelStats:
    """Per-kernel accumulation; every field mutated under the ledger's
    ``_mu`` (the whole ``_kstats`` dict is the registered guarded
    state)."""

    __slots__ = (
        "dispatches",
        "execute_s",
        "last_execute_s",
        "compiles",
        "compile_s",
        "buckets",
        "cache_size",
        "d2h_fetches",
        "d2h_bytes",
        "d2h_s",
        "baseline_s",
        "baseline_n",
        "streak",
        "regressions",
    )

    def __init__(self) -> None:
        self.dispatches = 0
        self.execute_s = 0.0
        self.last_execute_s = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        # bucket key → {"count": int, "spec": (args, kwargs) with arrays
        # replaced by ShapeDtypeStruct, or None when unbuildable}
        self.buckets: Dict[tuple, dict] = {}
        # high watermark of the root's jit compilation-cache size (-1 =
        # not yet seen): compile classification compares against THIS,
        # not the caller's own before-read, so a warm dispatch racing a
        # concurrent first-shape compile doesn't book the growth twice
        self.cache_size = -1
        self.d2h_fetches = 0
        self.d2h_bytes = 0
        self.d2h_s = 0.0
        self.baseline_s = 0.0
        self.baseline_n = 0
        self.streak = 0
        self.regressions = 0


def _leaf_key(leaf):
    """One flat, hashable token per argument leaf: (shape, dtype) for
    array-likes, the value itself for jit statics (strings/bools/ints/
    floats/enums — all hashable by the jit contract), repr as the
    fallback so an exotic static can never make the key unhashable."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    try:
        hash(leaf)
        return leaf
    except TypeError:
        return repr(leaf)


def _bucket_key(args, kwargs) -> tuple:
    """The dispatch's batch-shape bucket + its device placement: flat
    leaf tokens in pytree order (dict keys sort deterministically under
    tree_flatten), so two calls share a bucket exactly when jit would
    share an executable (modulo weak types) — PLUS the dispatch's device
    count and mesh axis shape, read off the most-sharded array argument.
    Single-chip and mesh-partitioned dispatches of the same shapes are
    different executables with different cost profiles; keying them apart
    keeps the execute-time series (and the regression sentinel's EWMA
    baseline) from smearing into one meaningless average.

    Returns ``(key, n_devices, mesh_shape)`` where mesh_shape is a tuple
    of (axis_name, size) pairs (empty off-mesh)."""
    ndev, mesh_shape = 1, ()
    toks = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        toks.append(_leaf_key(leaf))
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        try:
            # a replicated placement spans the mesh's device set without
            # PARTITIONING anything — counting it would let a silently
            # replicated run satisfy every engagement guard (bench
            # collective_ratio, the paritycheck __engaged__ check)
            if sh.is_fully_replicated:
                continue
            n = len(sh.device_set)
        except Exception:  # noqa: BLE001 — placement probing is best-effort
            continue
        if n > ndev:
            ndev = n
            m = getattr(sh, "mesh", None)
            try:
                mesh_shape = tuple(
                    (str(k), int(v)) for k, v in m.shape.items()
                )
            except Exception:  # noqa: BLE001
                mesh_shape = ()
    return tuple(toks) + (("devices", ndev, mesh_shape),), ndev, mesh_shape


def _in_trace(args, kwargs) -> bool:
    """True when the call is part of an outer trace (one root tracing
    through another, ``eval_shape``/``lower`` of the wrapper): some
    argument leaf is a ``jax.core.Tracer`` — the public marker.  Host
    dispatches only ever carry concrete arrays and statics."""
    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def _abstract_spec(args, kwargs):
    """(args, kwargs) with array leaves replaced by ShapeDtypeStruct —
    retained per bucket for the lazy cost lowering.  Never holds the
    arrays themselves: dispatch args may be DONATED, and pinning them
    here would defeat the donation."""

    def conv(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return leaf

    return jax.tree_util.tree_map(conv, (tuple(args), dict(kwargs)))


class DispatchLedger:
    """Per-kernel dispatch accounting + the regression sentinel.

    One per Scheduler (``sched.kernels``); the process-global root
    wrappers route through the ACTIVE ledger (``activate``).  ``prom``
    is the scheduler's ``SchedulerMetrics`` (optional — standalone
    ledgers in tests run without a registry); ``tracer`` feeds
    ``dispatch_submit``-track spans (the host's clock around each dispatch
    call) when a capture is running; ``slo_getter`` returns the
    scheduler's SLOEvaluator (or None) at breach time; ``bid_getter``
    returns the scheduling loop's current batch id for the dispatch span.
    """

    def __init__(
        self,
        prom=None,
        tracer=None,
        slo_getter=None,
        bid_getter=None,
        clock=time.perf_counter,
        sentinel_factor: float = SENTINEL_FACTOR,
        sentinel_min_samples: int = SENTINEL_MIN_SAMPLES,
        sentinel_sustain: int = SENTINEL_SUSTAIN,
        sentinel_floor_s: float = SENTINEL_FLOOR_S,
        breaker_trip_threshold: int = BREAKER_TRIP_THRESHOLD,
        breaker_retries: int = BREAKER_RETRIES,
        breaker_backoff_s: float = BREAKER_BACKOFF_S,
        breaker_half_open_after: int = BREAKER_HALF_OPEN_AFTER,
        watchdog_s: Optional[float] = None,
    ):
        self.enabled = True
        self.prom = prom
        self.tracer = tracer
        self.slo_getter = slo_getter
        # the scheduling loop's current batch id, for the dispatch span
        self.bid_getter = bid_getter
        self._clock = clock
        self.sentinel_factor = sentinel_factor
        self.sentinel_min_samples = sentinel_min_samples
        self.sentinel_sustain = sentinel_sustain
        self.sentinel_floor_s = sentinel_floor_s
        self.breaker_trip_threshold = breaker_trip_threshold
        self.breaker_retries = breaker_retries
        self.breaker_backoff_s = breaker_backoff_s
        self.breaker_half_open_after = breaker_half_open_after
        # per-dispatch watchdog deadline: a warm (non-compile) dispatch
        # slower than this books a "dispatch_hang" breaker failure — the
        # hung-collective detector.  None = off (the default: CPU test
        # boxes jitter by seconds; chaos scenarios and accelerator
        # deployments set it).  An INJECTED hang always books the failure
        # regardless — the chaos contract defines its stall as past the
        # deadline, so the verdict never races a real clock.
        self.watchdog_s = watchdog_s
        self._mu = threading.Lock()
        self._kstats: Dict[str, _KernelStats] = {}
        # (kernel, bucket) → cost dict or None (lowering failed)
        self._cost_memo: Dict[tuple, Optional[dict]] = {}
        self._cost_hits = 0
        self._cost_misses = 0
        self._regressions: List[dict] = []
        self._breakers: Dict[str, _BreakerState] = {}

    # -- dispatch recording ---------------------------------------------------

    def dispatch(self, name: str, fn, args, kwargs):
        """Account one dispatch of jit root ``name`` and return its
        result.  Called by the ``_LedgerRoot`` wrappers; host-side calls
        only — an in-trace call (one root tracing through another, or an
        ``eval_shape`` of the wrapper) passes straight through, because
        it is not a dispatch and its tracer args have no dispatch cost.

        Fault boundary (ISSUE 15): an installed chaos injector draws a
        device fault per ATTEMPT; injected errors retry in place with
        backoff (the kernel never ran — the args, donated or not, are
        live), real backend errors never do (their buffers may be
        consumed).  Either way the per-kernel breaker books the failure,
        and an abandoned dispatch raises ``DispatchFailed`` for the
        caller's registered fallback engine."""
        if _in_trace(args, kwargs):
            return fn(*args, **kwargs)
        # an OPEN breaker that a routing gate didn't consult: deny here
        # (counts toward the same half-open cooldown the gates feed)
        if not self._breaker_admit(name):
            raise BreakerOpen(name)
        attempt = 0
        while True:
            inj = _fault_injector
            stall = 0.0
            injected_hang = False
            if inj is not None:
                kind = inj.dispatch_fault(name)
                if kind == "dispatch_hang":
                    injected_hang = True
                    stall = inj.hang_s
                elif kind is not None:
                    # error/mesh-loss raised BEFORE the kernel runs
                    self._breaker_failure(name, kind)
                    if kind == "dispatch_error" and attempt < self.breaker_retries:
                        attempt += 1
                        if self.breaker_backoff_s:
                            time.sleep(self.breaker_backoff_s * attempt)
                        continue
                    try:
                        inj.raise_for(kind, name)
                    except RuntimeError as e:
                        raise DispatchFailed(name, e, kind=kind) from e
            try:
                return self._record_dispatch(
                    name,
                    fn,
                    args,
                    kwargs,
                    stall_s=stall,
                    injected_hang=injected_hang,
                )
            except DispatchFailed:
                raise
            except Exception as e:  # noqa: BLE001 — backend failure class
                # a REAL dispatch failure: the kernel may have consumed
                # its donated inputs, so no in-place retry — the breaker
                # books it and the caller's fallback engine (with the
                # epoch-guarded resync where resident state is involved)
                # takes the batch
                self._breaker_failure(name, "dispatch_error")
                raise DispatchFailed(name, e) from e

    def _record_dispatch(
        self, name: str, fn, args, kwargs, stall_s=0.0, injected_hang=False
    ):
        # the bucket key is built BEFORE the call: args may be donated,
        # and their metadata (shapes AND shardings) must be read while
        # they're live
        key, ndev, mesh_shape = _bucket_key(args, kwargs)
        size_before = fn._cache_size()
        with self._mu:
            ks = self._kstats.get(name)
            if ks is None:
                ks = self._kstats[name] = _KernelStats()
            if ks.cache_size < 0:
                ks.cache_size = size_before
            known_bucket = key in ks.buckets
        spec = None
        if not known_bucket:
            try:
                spec = _abstract_spec(args, kwargs)
            except Exception:  # noqa: BLE001 — cost analysis is optional
                spec = None
        # the dispatch call as a span on the profiler's clock (live only
        # under a profiler session): a compile inside a traced window
        # shows as a long named span next to the device's ops
        bid = self.bid_getter() if self.bid_getter is not None else 0
        ann = annotation("dispatch." + name, bid=bid).begin()
        t0 = self._clock()
        if stall_s:
            # injected dispatch_hang: the stall rides the execute wall
            # exactly where a hung collective's would
            time.sleep(stall_s)
        out = fn(*args, **kwargs)
        dt = self._clock() - t0
        size_after = fn._cache_size()
        ann.set_metadata(compile=int(size_after > size_before))
        ann.end()
        breach = None
        with self._mu:
            ks = self._kstats[name]
            # watermark comparison (not size_before): with two threads
            # dispatching one root, only the FIRST to book the growth
            # counts as the compile.  A _clear_cache() shrink leaves the
            # watermark high (test-only; the next growth re-books).
            compiled = size_after > ks.cache_size
            if size_after > ks.cache_size:
                ks.cache_size = size_after
            ks.dispatches += 1
            b = ks.buckets.get(key)
            if b is None:
                b = ks.buckets[key] = {
                    "count": 0,
                    "spec": spec,
                    "devices": ndev,
                    "mesh": mesh_shape,
                }
            elif b["spec"] is None and spec is not None:
                b["spec"] = spec
            b["count"] += 1
            if compiled:
                ks.compiles += 1
                ks.compile_s += dt
            else:
                ks.execute_s += dt
                ks.last_execute_s = dt
                breach = self._sentinel_locked(name, ks, dt)
        prom = self.prom
        if prom is not None:
            prom.kernel_dispatches.inc(kernel=name)
            if compiled:
                prom.kernel_compiles.inc(kernel=name)
                prom.kernel_compile_seconds.inc(dt, kernel=name)
            else:
                prom.kernel_execute.observe(dt, kernel=name)
        tr = self.tracer
        if tr is not None and tr.enabled:
            # execute_s is the HOST's clock around the dispatch call: on
            # an asynchronous backend that is enqueue (submit) time, not
            # device time — the track says so
            tr.complete_track(
                "dispatch_submit",
                name,
                t0,
                t0 + dt,
                cat="dispatch_submit",
                compile=bool(compiled),
            )
        # watchdog verdict: an injected hang is a breach BY CONTRACT
        # (its stall is DEFINED as past the deadline, even when replay
        # skips the sleep itself); a real dispatch breaches only when
        # warm (compile storms are not hangs) and a deadline is set
        hung = injected_hang or (
            self.watchdog_s is not None
            and not compiled
            and dt > self.watchdog_s
        )
        if hung:
            self._breaker_failure(name, "dispatch_hang")
        else:
            self._breaker_success(name)
        if breach is not None:
            self._file_breach(name, breach)
        return out

    def _sentinel_locked(self, name: str, ks: _KernelStats, dt: float):
        """Rolling-baseline regression check for one warm sample; returns
        a breach record when the sustained-breach bar is crossed.  The
        baseline learns only from NON-breaching samples — a regression
        must not teach the baseline to accept it."""
        if ks.baseline_n < self.sentinel_min_samples:
            # warmup: establish the baseline unconditionally
            ks.baseline_n += 1
            ks.baseline_s += (dt - ks.baseline_s) / ks.baseline_n
            return None
        threshold = max(
            ks.baseline_s * self.sentinel_factor, self.sentinel_floor_s
        )
        if dt <= threshold:
            ks.streak = 0
            ks.baseline_s += BASELINE_ALPHA * (dt - ks.baseline_s)
            return None
        ks.streak += 1
        if ks.streak < self.sentinel_sustain:
            return None
        ks.streak = 0
        ks.regressions += 1
        record = {
            "objective": "kernel_regression",
            "kernel": name,
            "baseline_s": round(ks.baseline_s, 6),
            "measured_s": round(dt, 6),
            "factor": self.sentinel_factor,
            "sustained": self.sentinel_sustain,
        }
        self._regressions.append(record)
        del self._regressions[:-8]
        return record

    def _file_breach(self, name: str, record: dict) -> None:
        """Outside ``_mu``: count the regression and hand the record to
        the SLO tier's freeze→dump→re-arm machinery (when installed —
        the record is already retained in ``_regressions`` either way).
        A sustained-breach verdict also counts toward the kernel's
        breaker trip threshold: a kernel that got pathologically slow is
        drained through its fallback engine the same way a faulting one
        is (ISSUE 15 satellite)."""
        if self.prom is not None:
            self.prom.kernel_regressions.inc(kernel=name)
        self._breaker_failure(name, "sentinel")
        getter = self.slo_getter
        slo = getter() if getter is not None else None
        if slo is not None:
            try:
                slo.external_breach(dict(record))
            except Exception:  # noqa: BLE001 — accounting must not
                pass  # break the dispatch that happened to breach

    # -- circuit breaker (ISSUE 15) -------------------------------------------

    def _breaker_of_locked(self, name: str) -> _BreakerState:
        b = self._breakers.get(name)
        if b is None:
            b = self._breakers[name] = _BreakerState()
        return b

    def _set_breaker_gauge(self, name: str, state: str) -> None:
        prom = self.prom
        if prom is not None:
            prom.kernel_breaker_state.set(
                _BREAKER_GAUGE[state], kernel=name
            )

    def _breaker_failure(self, name: str, kind: str) -> None:
        """Book one failed dispatch/readback/verdict against ``name``'s
        breaker; trips it open at the threshold (a half-open probe's
        failure re-trips immediately)."""
        with self._mu:
            b = self._breaker_of_locked(name)
            b.last_kind = kind
            b.failures += 1
            tripped = False
            if b.state == BREAKER_HALF_OPEN or (
                b.state == BREAKER_CLOSED
                and b.failures >= self.breaker_trip_threshold
            ):
                b.state = BREAKER_OPEN
                b.denials = 0
                b.trips += 1
                tripped = True
            state = b.state
        prom = self.prom
        if prom is not None:
            prom.kernel_breaker_failures.inc(kernel=name, kind=kind)
            if tripped:
                prom.kernel_breaker_trips.inc(kernel=name)
        self._set_breaker_gauge(name, state)

    def _breaker_success(self, name: str) -> None:
        """A clean dispatch: reset the streak; a half-open probe's
        success closes the breaker (recovery)."""
        with self._mu:
            b = self._breakers.get(name)
            if b is None:
                return
            changed = b.state != BREAKER_CLOSED
            if b.state == BREAKER_HALF_OPEN:
                b.half_open_probes += 1
            b.failures = 0
            b.denials = 0
            b.state = BREAKER_CLOSED
        if changed:
            self._set_breaker_gauge(name, BREAKER_CLOSED)

    def _breaker_admit(self, name: str) -> bool:
        """Should a dispatch of ``name`` proceed?  Closed/half-open →
        yes; open → no, but the denial counts toward the COUNT-BASED
        cooldown (deterministic under replay — no wall clock), and the
        request that crosses it becomes the half-open probe."""
        with self._mu:
            b = self._breakers.get(name)
            if b is None or b.state == BREAKER_CLOSED:
                return True
            if b.state == BREAKER_HALF_OPEN:
                return True
            if b.latched:
                return False
            b.denials += 1
            if b.denials < self.breaker_half_open_after:
                return False
            b.state = BREAKER_HALF_OPEN
        self._set_breaker_gauge(name, BREAKER_HALF_OPEN)
        return True  # this request is the probe

    def breaker_allows(self, kernel: str) -> bool:
        """The routing-gate check: False routes the dispatch family to
        its registered fallback engine (the caller bumps
        ``scheduler_tpu_wave_fallback_total{reason="breaker"}``)."""
        if not self.enabled:
            return True
        return self._breaker_admit(kernel)

    def breaker_state(self, kernel: str) -> str:
        with self._mu:
            b = self._breakers.get(kernel)
            return b.state if b is not None else BREAKER_CLOSED

    def record_breaker_failure(self, kernel: str, kind: str) -> None:
        """Public failure feed for faults detected OUTSIDE the dispatch
        wrapper: poisoned readbacks (Scheduler's guarded fetches) and
        resident-snapshot placement failures."""
        self._breaker_failure(kernel, kind)

    def force_breaker_open(self, kernel: str) -> None:
        """Latch ``kernel``'s breaker open (tests / paritycheck's
        breaker-degraded parity run): denials never reach the half-open
        cooldown until ``reset_breaker``."""
        with self._mu:
            b = self._breaker_of_locked(kernel)
            b.state = BREAKER_OPEN
            b.latched = True
        self._set_breaker_gauge(kernel, BREAKER_OPEN)

    def reset_breaker(self, kernel: str) -> None:
        with self._mu:
            b = self._breakers.get(kernel)
            if b is None:
                return
            b.state = BREAKER_CLOSED
            b.failures = 0
            b.denials = 0
            b.latched = False
        self._set_breaker_gauge(kernel, BREAKER_CLOSED)

    def breaker_rows(self) -> Dict[str, dict]:
        """Per-kernel breaker snapshot (the /debug/kernels column)."""
        with self._mu:
            return {
                name: {
                    "state": b.state,
                    "failures": b.failures,
                    "denials": b.denials,
                    "trips": b.trips,
                    "half_open_probes": b.half_open_probes,
                    "last_kind": b.last_kind,
                }
                for name, b in self._breakers.items()
            }

    # -- d2h attribution (fed by Scheduler._d2h) ------------------------------

    def record_d2h(self, kernel: Optional[str], nbytes: int, dt: float) -> None:
        """One blocking device→host fetch, attributed to ``kernel`` (None
        → ``_untagged``, so per-kernel rows always sum to the aggregate
        d2h counters)."""
        name = kernel or _UNTAGGED
        with self._mu:
            ks = self._kstats.get(name)
            if ks is None:
                ks = self._kstats[name] = _KernelStats()
            ks.d2h_fetches += 1
            ks.d2h_bytes += nbytes
            ks.d2h_s += dt
        prom = self.prom
        if prom is not None:
            prom.kernel_d2h_bytes.inc(nbytes, kernel=name)
            prom.kernel_d2h_seconds.inc(dt, kernel=name)

    # -- cost analysis (lazy, memoized) ---------------------------------------

    def _cost_for(self, name: str, key: tuple, spec) -> Optional[dict]:
        """FLOPs / bytes-accessed estimate for one (kernel, bucket),
        memoized: the ``fn.lower`` re-trace is seconds-scale on the big
        kernels, so a repeat shape must hit the memo.  Returns None when
        the root is gone or the lowering fails (a cost estimate is never
        worth an error surface)."""
        memo_key = (name, key)
        with self._mu:
            if memo_key in self._cost_memo:
                self._cost_hits += 1
                return self._cost_memo[memo_key]
            self._cost_misses += 1
        cost: Optional[dict] = None
        fn = _wrapped_fn(name)
        if fn is not None and spec is not None:
            try:
                s_args, s_kwargs = spec
                ca = fn.lower(*s_args, **s_kwargs).cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                cost = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                }
            except Exception:  # noqa: BLE001 — estimate only
                cost = None
        with self._mu:
            self._cost_memo[memo_key] = cost
        return cost

    # -- reporting ------------------------------------------------------------

    def table(self, cost: bool = True) -> List[dict]:
        """The per-kernel table /debug/kernels and the bench line serve:
        one row per kernel in the ROSTER (wrapped roots + the sanitizer's
        runtime registry) plus every kernel the ledger has seen, sorted
        by execute seconds descending — a registered root that never
        dispatched still shows, with zeros, so nothing is unobserved
        silently.  ``cost=True`` fills FLOPs/bytes estimates for each
        kernel's most-dispatched bucket (first call pays the lowering;
        memoized after)."""
        names = set(roster()) | self._seen()
        with self._mu:
            names |= set(self._breakers)  # breaker-only rows still show
        want_cost: List[Tuple[str, tuple, object]] = []
        rows = []
        with self._mu:
            for name in sorted(names):
                ks = self._kstats.get(name)
                if ks is None:
                    ks = _KernelStats()
                # device placement summary: which device counts / mesh
                # shapes this kernel's dispatches ran on (bucket-keyed, so
                # single-chip vs multichip series never smear — ISSUE 14)
                dev_counts = sorted(
                    {b.get("devices", 1) for b in ks.buckets.values()}
                ) or [1]
                mesh_shapes = sorted(
                    {
                        "x".join(str(s) for _a, s in b["mesh"])
                        for b in ks.buckets.values()
                        if b.get("mesh")
                    }
                )
                multi_dev = sum(
                    b["count"]
                    for b in ks.buckets.values()
                    if b.get("devices", 1) > 1
                )
                brk = self._breakers.get(name)
                row = {
                    "kernel": name,
                    "dispatches": ks.dispatches,
                    "execute_s": round(ks.execute_s, 6),
                    "last_execute_s": round(ks.last_execute_s, 6),
                    "compiles": ks.compiles,
                    "compile_s": round(ks.compile_s, 6),
                    "shape_buckets": len(ks.buckets),
                    "devices": dev_counts,
                    "mesh_shapes": mesh_shapes,
                    "multi_device_dispatches": multi_dev,
                    "d2h_fetches": ks.d2h_fetches,
                    "d2h_bytes": ks.d2h_bytes,
                    "d2h_s": round(ks.d2h_s, 6),
                    "baseline_s": round(ks.baseline_s, 6),
                    "regressions": ks.regressions,
                    # breaker column: closed kernels that never faulted
                    # show "closed"/0 so the table is uniformly shaped
                    "breaker": brk.state if brk is not None else BREAKER_CLOSED,
                    "breaker_trips": brk.trips if brk is not None else 0,
                }
                if cost and ks.buckets:
                    key, b = max(
                        ks.buckets.items(), key=lambda kv: kv[1]["count"]
                    )
                    want_cost.append((name, key, b["spec"]))
                rows.append(row)
        by_name = {r["kernel"]: r for r in rows}
        for name, key, spec in want_cost:
            c = self._cost_for(name, key, spec)
            if c is not None:
                by_name[name]["est_flops"] = c["flops"]
                by_name[name]["est_bytes_accessed"] = c["bytes_accessed"]
        prom = self.prom
        if prom is not None:
            for r in rows:
                p50 = prom.kernel_execute.percentile(0.5, kernel=r["kernel"])
                p99 = prom.kernel_execute.percentile(0.99, kernel=r["kernel"])
                r["execute_p50_s"] = None if p50 != p50 or p50 == float("inf") else round(p50, 6)
                r["execute_p99_s"] = None if p99 != p99 or p99 == float("inf") else round(p99, 6)
        rows.sort(key=lambda r: (-r["execute_s"], r["kernel"]))
        return rows

    def _seen(self) -> set:
        with self._mu:
            return set(self._kstats)

    def stats(self) -> dict:
        with self._mu:
            return {
                "enabled": self.enabled,
                "kernels": len(self._kstats),
                "dispatches": sum(
                    ks.dispatches for ks in self._kstats.values()
                ),
                # dispatches whose arguments were partitioned across >1
                # device — the bench tier's collective_ratio numerator
                "multi_device_dispatches": sum(
                    b["count"]
                    for ks in self._kstats.values()
                    for b in ks.buckets.values()
                    if b.get("devices", 1) > 1
                ),
                "cost_memo_hits": self._cost_hits,
                "cost_memo_misses": self._cost_misses,
                "regressions": list(self._regressions),
                "breakers_open": sum(
                    1
                    for b in self._breakers.values()
                    if b.state != BREAKER_CLOSED
                ),
                "breaker_trips": sum(
                    b.trips for b in self._breakers.values()
                ),
            }

    def hbm_rows(self) -> List[dict]:
        """Live per-device memory stats where the backend supports them
        (``device.memory_stats()`` — None on CPU backends, gated): the
        scrape-refreshed ``scheduler_tpu_device_hbm_bytes`` feed and the
        /debug/kernels header."""
        rows = []
        try:
            devices = jax.devices()
        except Exception:  # noqa: BLE001 — backend torn down
            return rows
        for d in devices:
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — unsupported backend
                ms = None
            if not ms:
                continue
            rows.append(
                {
                    "device": str(d.id),
                    "platform": getattr(d, "platform", "?"),
                    "bytes_in_use": int(ms.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(ms.get("bytes_limit", 0)),
                }
            )
        return rows

    def snapshot(self, cost: bool = True) -> dict:
        """The /debug/kernels body."""
        out = {
            "enabled": self.enabled,
            "kernels": self.table(cost=cost),
            "memory": self.hbm_rows(),
        }
        st = self.stats()
        out["dispatches"] = st["dispatches"]
        out["cost_memo_hits"] = st["cost_memo_hits"]
        out["cost_memo_misses"] = st["cost_memo_misses"]
        out["regressions"] = st["regressions"]
        out["breakers"] = self.breaker_rows()
        out["clocks"] = {
            "execute_s": "host seconds inside the dispatch call: enqueue "
            "(submit) time on an asynchronous backend, not device time",
            "d2h_seconds": "host seconds blocked in the fetch: where the "
            "host waits for the device",
        }
        return out


# ---------------------------------------------------------------------------
# root wrapping (module-global: the roots are module attributes)
# ---------------------------------------------------------------------------

# name → (module, attr, original fn) for everything currently wrapped
_wrapped: Dict[str, tuple] = {}
# weakly-held active ledger: the wrappers' single global read.  Weak so a
# torn-down Scheduler's ledger (and its metrics registry) never outlives
# it just because it was the last one activated.
_active_ref: Optional["weakref.ref"] = None
_install_mu = threading.Lock()


class _LedgerRoot:
    """Instrumented stand-in for one module-level jit root.  Disabled
    path (no active ledger / kill switch off): one module-global read +
    branch, then the original call.  Everything else (``_cache_size``,
    ``lower``, ``trace``, ``eval_shape``) proxies to the wrapped
    PjitFunction so the sanitizer's retrace sweep and the shapecheck
    cross-check see the root unchanged.  ``__weakref__`` rides along:
    jax's tracing caches take weak references to the callable."""

    __slots__ = ("_fn", "_name", "__weakref__")

    def __init__(self, name: str, fn):
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        ref = _active_ref
        led = ref() if ref is not None else None
        if led is None or not led.enabled:
            return self._fn(*args, **kwargs)
        return led.dispatch(self._name, self._fn, args, kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    @property
    def __wrapped__(self):
        return self._fn

    def __repr__(self):
        return f"<LedgerRoot {self._name} of {self._fn!r}>"


def activate(ledger: DispatchLedger) -> None:
    """Route dispatches through ``ledger`` (weakly held).  The normal
    process has ONE scheduler; with several, the last activation wins —
    dispatch attribution is process-wide, d2h attribution stays exact
    per scheduler (``Scheduler._d2h`` records into its own ledger)."""
    global _active_ref
    _active_ref = weakref.ref(ledger)


def deactivate(ledger: Optional[DispatchLedger] = None) -> None:
    """Stop routing (``ledger`` given: only if it is the active one)."""
    global _active_ref
    if ledger is not None:
        ref = _active_ref
        if ref is None or ref() is not ledger:
            return
    _active_ref = None


def active() -> Optional[DispatchLedger]:
    ref = _active_ref
    return ref() if ref is not None else None


def install() -> int:
    """Wrap every discovered module-level jit root (idempotent; returns
    the wrapped-root count).  Rides the sanitizer's discovery so the
    ledger's roster and the retrace hook's can never diverge, and
    subscribes to ``register_jit_root`` so runtime-created roots join
    the roster as they appear."""
    from kubernetes_tpu.analysis import sanitizer

    with _install_mu:
        for name, fn in sanitizer._discover_jit_roots().items():
            _wrap_under_install_mu(name, fn)
    # subscribe OUTSIDE the lock: add_jit_root_listener synchronously
    # replays already-registered roots into _on_registered, which takes
    # _install_mu itself — holding it here would self-deadlock on the
    # first install after a mark_jit_warm()/register_jit_root()
    sanitizer.add_jit_root_listener(_on_registered)
    with _install_mu:
        return len(_wrapped)


def _candidate_modules(short: str):
    """Full module names whose basename is ``short``, from the SAME
    roster the sanitizer's discovery walks (JIT_MODULES +
    device_mirror) — no prefix guessing, so a kernel module added
    anywhere in the tree wraps the day it lands in JIT_MODULES."""
    import os as _os

    from kubernetes_tpu.analysis import JIT_MODULES

    rels = list(JIT_MODULES) + [_os.path.join("cache", "device_mirror.py")]
    for rel in rels:
        modname = "kubernetes_tpu." + rel[:-3].replace(_os.sep, ".")
        if modname.rsplit(".", 1)[-1] == short:
            yield modname


def _wrap_under_install_mu(name: str, fn) -> None:
    if name in _wrapped or isinstance(fn, _LedgerRoot):
        return
    mod_short, attr = name.rsplit(".", 1)
    import importlib

    for modname in _candidate_modules(mod_short):
        try:
            mod = importlib.import_module(modname)
        except ImportError:
            continue
        if getattr(mod, attr, None) is fn:
            wrapper = _LedgerRoot(name, fn)
            setattr(mod, attr, wrapper)
            _wrapped[name] = (mod, attr, fn)
            return
    # not a module attribute we can rebind (runtime-created root): it
    # still appears in roster() so coverage tests see it — its dispatches
    # just can't be intercepted at the module seam
    _wrapped[name] = (None, None, fn)


def _on_registered(name: str, fn) -> None:
    with _install_mu:
        if name not in _wrapped:
            _wrapped[name] = (None, None, fn)


def uninstall() -> None:
    """Restore every wrapped module attribute (tests)."""
    with _install_mu:
        for name, (mod, attr, fn) in list(_wrapped.items()):
            if mod is not None and isinstance(
                getattr(mod, attr, None), _LedgerRoot
            ):
                setattr(mod, attr, fn)
            del _wrapped[name]


def roster() -> List[str]:
    """Every jit root the ledger knows: wrapped module-level roots plus
    the sanitizer's runtime registry — the coverage tests assert the
    sanitizer's roster is a subset of this, so a new kernel cannot land
    unobserved."""
    from kubernetes_tpu.analysis import sanitizer

    with _install_mu:
        names = set(_wrapped)
    names |= set(sanitizer._jit_roots)
    return sorted(names)


def _wrapped_fn(name: str):
    """The ORIGINAL PjitFunction for ``name`` (cost lowering must not
    recurse through the wrapper)."""
    with _install_mu:
        rec = _wrapped.get(name)
    if rec is not None:
        return rec[2]
    from kubernetes_tpu.analysis import sanitizer

    fn = sanitizer._jit_roots.get(name)
    return fn._fn if isinstance(fn, _LedgerRoot) else fn
