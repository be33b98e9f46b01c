"""Scheduler metrics: Prometheus-compatible series + async recorder.

Mirrors pkg/scheduler/metrics/metrics.go:86-260 (the ~25 scheduler series,
stability labels dropped) and metric_recorder.go (the lock-free buffered
async recorder, flush interval 1s).  The TPU build adds device-path series
(gang dispatch timing, fast-path batch counts, HBM upload bytes) because
the hot loop is one fused kernel dispatch rather than per-pod goroutines.

Export is the Prometheus text exposition format (``registry.expose()``) —
what the server wrapper serves at /metrics.
"""

from __future__ import annotations

import bisect
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------------------
# core metric types
# ---------------------------------------------------------------------------


def _esc_label(v: str) -> str:
    """Prometheus text-exposition label-value escaping: backslash, double
    quote, and newline (exposition format spec).  Pod names and plugin
    reason strings flow into labels, so raw interpolation would corrupt
    the scrape on the first quote or newline."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_esc_label(str(v))}"' for k, v in labels)
    return "{" + inner + "}"


class Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str = "", label_names: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        # binding workers record series concurrently with the scheduling
        # loop; the read-modify-write below (dict get + add) loses updates
        # without it.  Frequency is per batch/slice, not per pod, so the
        # uncontended acquire is noise next to the observed phases.
        self._mu = threading.Lock()

    def expose(self) -> List[str]:
        raise NotImplementedError

    def _key(self, labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
        if not self.label_names:  # hot unlabeled counters skip the genexpr
            return ()
        return tuple((k, str(labels.get(k, ""))) for k in self.label_names)


class Counter(Metric):
    kind = "counter"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._values: Dict[Tuple, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        k = self._key(labels)
        with self._mu:
            self._values[k] = self._values.get(k, 0.0) + amount

    def value(self, **labels) -> float:
        return self._values.get(self._key(labels), 0.0)

    def expose(self) -> List[str]:
        # snapshot under the metric lock: a concurrent inc from a binding
        # worker mid-scrape would otherwise raise "dictionary changed size
        # during iteration" (and could expose a torn series list)
        with self._mu:
            items = sorted(self._values.items())
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        for k, v in items:
            out.append(f"{self.name}{_fmt_labels(k)} {v:g}")
        return out


class Gauge(Metric):
    kind = "gauge"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._values: Dict[Tuple, float] = {}

    def set(self, value: float, **labels) -> None:
        k = self._key(labels)
        with self._mu:
            self._values[k] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        k = self._key(labels)
        with self._mu:
            self._values[k] = self._values.get(k, 0.0) + amount

    def value(self, **labels) -> float:
        return self._values.get(self._key(labels), 0.0)

    def expose(self) -> List[str]:
        with self._mu:
            items = sorted(self._values.items())
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        for k, v in items:
            out.append(f"{self.name}{_fmt_labels(k)} {v:g}")
        return out


# the reference's default scheduler duration buckets: 0.001 → ~16s
def duration_buckets() -> List[float]:
    return [0.001 * (2**i) for i in range(15)]


# widened buckets for the serving-tier latency SLIs (0.001 → ~17.5 min):
# at saturation the open-loop harness drives queue waits far past the
# default 16 s ceiling, and a p99 that lands in the overflow bucket comes
# back as +Inf (Histogram.percentile) — the SLO series use these so the
# sentinel only fires when latency is truly off the scale
def wide_duration_buckets() -> List[float]:
    return [0.001 * (2**i) for i in range(21)]


# per-kernel execute buckets (the dispatch ledger, observability/
# kernels.py): submits range from tens of µs (a warm static_eval) to
# tens of seconds (a first-trace compile on a cold cache), so the span
# is wider at both ends than the scheduler duration buckets
def kernel_duration_buckets() -> List[float]:
    return [0.00001 * (2**i) for i in range(24)]


# coarse batch-size label values for the per-pod attempt-latency series:
# one batched dispatch smears its latency uniformly over the batch, so the
# serving analysis needs to know HOW MUCH smear a sample carries (batch=1
# is a real per-pod latency; batch=4096+ is a drain average).  Coarse
# powers-of-16 keep the label cardinality at 5.
def batch_size_bucket(n: int) -> str:
    if n <= 1:
        return "1"
    if n < 16:
        return "2-15"
    if n < 256:
        return "16-255"
    if n < 4096:
        return "256-4095"
    return "4096+"


def bucket_quantile(bounds, counts, q: float) -> Tuple[float, int]:
    """``(estimate, n)``: the promql histogram_quantile bucket
    interpolation over ``counts`` aligned with ``bounds`` plus one
    overflow slot last.  A rank landing in the overflow bucket returns
    ``math.inf`` — an explicit sentinel, NOT the top finite bound:
    clamping silently under-reports the quantile exactly when the series
    saturates.  The ONE copy of this estimate — ``Histogram.percentile``
    and the SLO evaluator's windowed quantiles both delegate here, so
    breach decisions can never diverge from /metrics-derived values."""
    n = int(sum(counts))
    if n == 0:
        return 0.0, 0
    rank = q * n
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= rank:
            if i >= len(bounds):
                return math.inf, n
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            frac = (rank - (cum - c)) / c if c else 0.0
            return float(lo + (hi - lo) * frac), n
    return math.inf, n


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name, help_="", label_names=(), buckets: Optional[Sequence[float]] = None):
        super().__init__(name, help_, label_names)
        self.buckets = sorted(buckets if buckets is not None else duration_buckets())
        self._counts: Dict[Tuple, List[int]] = {}
        self._sum: Dict[Tuple, float] = {}
        self._n: Dict[Tuple, int] = {}

    def observe(self, value: float, **labels) -> None:
        self.observe_n(value, 1, **labels)

    def observe_n(self, value: float, n: int, **labels) -> None:
        """n identical observations in one bucket update — the batched
        dispatch amortizes one latency over a whole batch, so per-pod
        series would otherwise pay len(batch) bucket walks per cycle."""
        if n <= 0:
            return
        k = self._key(labels)
        with self._mu:
            counts = self._counts.get(k)
            if counts is None:
                counts = self._counts[k] = [0] * (len(self.buckets) + 1)
                self._sum[k] = 0.0
                self._n[k] = 0
            counts[bisect.bisect_left(self.buckets, value)] += n
            self._sum[k] += value * n
            self._n[k] += n

    def merge_counts(self, counts, sum_, n, **labels) -> None:
        """Merge PRE-BUCKETED observations: ``counts`` aligns with
        ``len(buckets)+1`` (overflow last).  The SLO tier's batched feed —
        its ingest loop buckets into plain arrays off the registry lock
        and syncs deltas here on scrape, so the hot join never pays a
        per-observation metric-lock acquisition."""
        if n <= 0:
            return
        k = self._key(labels)
        with self._mu:
            cur = self._counts.get(k)
            if cur is None:
                cur = self._counts[k] = [0] * (len(self.buckets) + 1)
                self._sum[k] = 0.0
                self._n[k] = 0
            for i, c in enumerate(counts):
                if c:
                    cur[i] += c
            self._sum[k] += sum_
            self._n[k] += n

    def count(self, **labels) -> int:
        return self._n.get(self._key(labels), 0)

    def total_sum(self, **labels) -> float:
        return self._sum.get(self._key(labels), 0.0)

    def percentile(self, q: float, **labels) -> float:
        """Bucket-interpolated quantile (the promql histogram_quantile
        estimate) over ALL label sets when none given, else one set.

        A rank landing in the overflow (+Inf) bucket returns ``math.inf``
        — an explicit sentinel, NOT the top finite bound (see
        ``bucket_quantile``).  Callers that want a finite display value
        clamp explicitly; latency SLIs widen their buckets
        (``wide_duration_buckets``) instead."""
        if self.label_names and not labels:
            # aggregate across label sets (snapshot under the lock — a
            # concurrent observe can add a label set mid-iteration)
            counts = [0] * (len(self.buckets) + 1)
            with self._mu:
                rows = [list(c) for c in self._counts.values()]
            for row in rows:
                for i, c in enumerate(row):
                    counts[i] += c
        else:
            k = self._key(labels)
            with self._mu:
                counts = list(
                    self._counts.get(k, [0] * (len(self.buckets) + 1))
                )
        est, _ = bucket_quantile(self.buckets, counts, q)
        return est

    def expose(self) -> List[str]:
        # consistent snapshot under the lock (see Counter.expose): bucket
        # rows, _sum and _count must come from ONE moment or a concurrent
        # observe_n mid-scrape yields sum/count that disagree with buckets
        with self._mu:
            snap = [
                (k, list(self._counts[k]), self._sum[k], self._n[k])
                for k in sorted(self._counts)
            ]
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        for k, counts, total, n in snap:
            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                lab = k + (("le", f"{b:g}"),)
                out.append(f"{self.name}_bucket{_fmt_labels(lab)} {cum}")
            cum += counts[-1]
            lab = k + (("le", "+Inf"),)
            out.append(f"{self.name}_bucket{_fmt_labels(lab)} {cum}")
            out.append(f"{self.name}_sum{_fmt_labels(k)} {total:g}")
            out.append(f"{self.name}_count{_fmt_labels(k)} {n}")
        return out


class Registry:
    def __init__(self) -> None:
        self._metrics: List[Metric] = []

    def register(self, metric: Metric) -> Metric:
        # duplicate names would expose two HELP/TYPE headers for one series
        # family — rejected by Prometheus parsers mid-scrape
        if any(m.name == metric.name for m in self._metrics):
            raise ValueError(f"metric {metric.name!r} already registered")
        self._metrics.append(metric)
        return metric

    def expose(self) -> str:
        lines: List[str] = []
        for m in self._metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# async recorder (metric_recorder.go)
# ---------------------------------------------------------------------------


@dataclass
class _Observation:
    metric: Histogram
    value: float
    labels: Dict[str, str]


class MetricAsyncRecorder:
    """Buffered histogram recorder: observations append to a bounded buffer
    and flush on interval or overflow (metric_recorder.go: bufferSize 1000,
    interval 1s).  The scheduler loop is single-threaded here, so flushing
    happens inline rather than on a goroutine; the buffer still decouples
    the hot path from histogram bucket math."""

    BUFFER_SIZE = 1000

    def __init__(self, flush_interval_s: float = 1.0, clock=time.monotonic):
        self._buf: List[_Observation] = []
        self._interval = flush_interval_s
        self._clock = clock
        self._last_flush = clock()

    def observe(self, metric: Histogram, value: float, **labels) -> None:
        self._buf.append(_Observation(metric, value, labels))
        if (
            len(self._buf) >= self.BUFFER_SIZE
            or self._clock() - self._last_flush >= self._interval
        ):
            self.flush()

    def flush(self) -> None:
        for obs in self._buf:
            obs.metric.observe(obs.value, **obs.labels)
        self._buf.clear()
        self._last_flush = self._clock()


# ---------------------------------------------------------------------------
# per-phase attribution (the scheduler_perf collector's per-op breakdown:
# test/integration/scheduler_perf reports steady-state throughput WITH the
# time attributed to each phase of the hot loop, so a regression names its
# phase instead of hiding in a total)
# ---------------------------------------------------------------------------

# The canonical hot-loop phases of one batched scheduling cycle.  Async
# dispatch makes two of them subtle: ``device`` is the host-side submit of
# the jitted kernel (the XLA work itself overlaps later host phases), and
# ``d2h`` is the time the harvest BLOCKS waiting for results — i.e. the
# device+copy latency that host work failed to hide.  ``bind`` accumulates
# worker-thread time, so it can exceed the drain's wall clock.
PHASES = (
    "queue_pop",  # activeQ pop + batch-extension predicate
    "pack",  # signature keys, PreFilter/PreScore, row packing, mirror sync
    "h2d",  # host→device uploads (committer state, ids, stacked sigs)
    "device",  # jitted dispatch submit (async: XLA overlaps host work)
    "d2h",  # blocked time fetching results the async copy hadn't landed
    "commit",  # assume/reserve/permit walk + committer replay
    "bind",  # binding-cycle worker time (sink + post-bind bookkeeping)
)


class PhaseAccumulator:
    """Cumulative per-phase wall seconds + per-observation histogram feed.

    ``span`` is the one way an interval is written: where the work
    happens, on the thread that does it.  It books the interval (``add``:
    total, histogram, the tracer's ``complete_tail``) and holds a
    ``jax.profiler.TraceAnnotation("ktpu.<name>", **ctx)`` open for it, so
    while a profiler session is live the span also lands in the profiler's
    host plane, on the device trace's clock.  The session is the switch:
    with none live the annotation is one atomic check.

    Spans are opened from the scheduling loop AND binding workers, so
    booking takes a lock; the frequency is per batch / per bind slice (never
    per pod that binds; a pod that fails opens one, ``post_filter``), which
    keeps the overhead unmeasurable next to the phases themselves.
    ``snapshot`` returns a plain dict, so a caller can diff two snapshots
    around a window.

    ``span(..., off_cpu=True)`` (those of the scheduling loop's spans that
    a metric reads it from, ``Scheduler._OFF_CPU_SPANS``) also reads the
    thread's CPU clock and books the count ``<phase>.off_cpu`` = wall -
    thread CPU: the seconds of the interval in which the thread did not
    run.  For a span that blocks on nothing by design that is time spent
    runnable but not running (the interpreter lock held by another thread,
    a contended mutex, the OS).  A total, not a timeline, and not split by
    cause.  Booked SIGNED, not clipped at 0 span by span: where the thread
    CPU clock advances in ticks (10 ms under gVisor) one span reads a tick
    too many or too few, and only the unclipped sum lets those errors
    cancel.  So a phase that never waits totals within a tick of 0, either
    side, and ``diff`` carries a window's negative delta as it is.
    """

    def __init__(self, hist: Optional[Histogram] = None):
        self._mu = threading.Lock()
        self._totals: Dict[str, float] = {}
        self.hist = hist
        # optional observability.Tracer: when tracing is enabled every
        # accumulated phase interval ALSO lands as a complete span on the
        # recording thread's track — one hook covers all dispatch paths
        self.tracer = None

    def add(self, phase: str, dt: float, off_cpu: Optional[float] = None) -> None:
        """Book ``dt`` seconds that ended now.  ``span`` ends here; called
        directly only for an interval no one thread spans (a bind slice's
        wait in the pool's queue: submitted by the loop, picked up by a
        worker).  ``off_cpu`` (a span that read the thread's CPU clock)
        goes to the count ``<phase>.off_cpu`` under the same acquisition."""
        with self._mu:
            self._totals[phase] = self._totals.get(phase, 0.0) + dt
            if off_cpu is not None:
                key = phase + ".off_cpu"
                self._totals[key] = self._totals.get(key, 0.0) + off_cpu
            if self.hist is not None:
                self.hist.observe(dt, phase=phase)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.complete_tail(phase, dt)

    def count(self, name: str, n: float) -> None:
        """Book a count (pods, rows) beside the phases: a total that
        ``snapshot`` and ``diff`` carry like a phase's seconds, with no
        histogram observation and no span (it is not an interval)."""
        with self._mu:
            self._totals[name] = self._totals.get(name, 0.0) + n

    def span(self, phase: str, off_cpu: bool = False, **ctx) -> "PhaseSpan":
        """The interval ``phase``, as a context manager, or opened with
        ``.begin()`` and closed with ``.end()`` where the interval does not
        sit in one block.  ``ctx`` (batch id, pod count) goes to the
        profiler's annotation only.  ``off_cpu``: also book the count
        ``<phase>.off_cpu`` (begun and ended on one thread)."""
        return PhaseSpan(self, phase, ctx, off_cpu)

    def snapshot(self) -> Dict[str, float]:
        with self._mu:
            return dict(self._totals)

    @staticmethod
    def diff(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
        """What the window between two snapshots booked; a key that did
        not move is left out.  Seconds and counts only grow; a signed
        ``.off_cpu`` total may fall, and its delta is kept."""
        out = {}
        for k, v in after.items():
            d = v - before.get(k, 0.0)
            if d != 0.0:
                out[k] = d
        return out


class Annotation(TraceAnnotation):
    """``jax.profiler.TraceAnnotation`` with an explicit begin/end form,
    for an interval that does not sit in one block.  Begun and ended on
    one thread."""

    def begin(self) -> "Annotation":
        self.__enter__()
        return self

    def end(self) -> None:
        self.__exit__(None, None, None)


def annotation(name: str, **ctx) -> Annotation:
    """A profiler annotation ``ktpu.<name>`` with no accumulator behind it
    (the loop's enclosing batch span, the API server's requests, the
    ledger's dispatches)."""
    return Annotation("ktpu." + name, **ctx)


class PhaseSpan:
    __slots__ = ("acc", "phase", "_ctx", "_ann", "_t0", "_cpu0")

    def __init__(
        self, acc: PhaseAccumulator, phase: str, ctx: dict, off_cpu: bool = False
    ):
        self.acc = acc
        self.phase = phase
        self._ctx = ctx
        self._cpu0 = 0.0 if off_cpu else None

    def begin(self) -> "PhaseSpan":
        # made here, not with the span: a profiler annotation's interval
        # opens where it is constructed
        self._ann = annotation(self.phase, **self._ctx).begin()
        # the CPU clock is read OUTSIDE the wall clock's interval at both
        # ends: the reads' own cost never shows as off-CPU time
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def end(self) -> float:
        """Close and book the interval; returns its seconds."""
        dt = time.perf_counter() - self._t0
        off = None
        if self._cpu0 is not None:
            off = dt - (time.thread_time() - self._cpu0)
        self._ann.end()
        self.acc.add(self.phase, dt, off)
        return dt

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


# ---------------------------------------------------------------------------
# the scheduler's series (metrics.go:86-260)
# ---------------------------------------------------------------------------

SCHEDULED = "scheduled"
UNSCHEDULABLE = "unschedulable"
ERROR = "error"


class SchedulerMetrics:
    def __init__(self) -> None:
        r = self.registry = Registry()
        self.schedule_attempts = r.register(
            Counter(
                "scheduler_schedule_attempts_total",
                "Number of attempts to schedule pods, by result and profile.",
                ("result", "profile"),
            )
        )
        self.attempt_duration = r.register(
            Histogram(
                "scheduler_scheduling_attempt_duration_seconds",
                "Scheduling attempt latency (algorithm + binding).  The "
                "batched dispatch amortizes one latency over the batch; "
                "the coarse batch label (batch_size_bucket) says how much "
                "smear a sample carries (batch=1 is a real per-pod "
                "latency, batch=4096+ a drain average).",
                ("result", "profile", "batch"),
            )
        )
        self.algorithm_duration = r.register(
            Histogram(
                "scheduler_scheduling_algorithm_duration_seconds",
                "Scheduling algorithm latency.",
                ("profile",),
            )
        )
        self.pod_scheduling_sli_duration = r.register(
            Histogram(
                "scheduler_pod_scheduling_sli_duration_seconds",
                "E2e latency for a pod being scheduled, from first attempt.",
                ("attempts",),
            )
        )
        self.pod_scheduling_attempts = r.register(
            Histogram(
                "scheduler_pod_scheduling_attempts",
                "Number of attempts to successfully schedule a pod.",
                (),
                buckets=[1, 2, 4, 8, 16],
            )
        )
        self.extension_point_duration = r.register(
            Histogram(
                "scheduler_framework_extension_point_duration_seconds",
                "Latency for running all plugins of an extension point.",
                ("extension_point", "status", "profile"),
            )
        )
        self.plugin_execution_duration = r.register(
            Histogram(
                "scheduler_plugin_execution_duration_seconds",
                "Duration for running a plugin at an extension point.",
                ("plugin", "extension_point", "status"),
                buckets=[0.00001 * (1.5**i) for i in range(20)],
            )
        )
        self.queue_incoming_pods = r.register(
            Counter(
                "scheduler_queue_incoming_pods_total",
                "Number of pods added to scheduling queues by event and queue type.",
                ("queue", "event"),
            )
        )
        self.pending_pods = r.register(
            Gauge(
                "scheduler_pending_pods",
                "Pending pods by queue: active, backoff, unschedulable, gated.",
                ("queue",),
            )
        )
        self.cache_size = r.register(
            Gauge(
                "scheduler_scheduler_cache_size",
                "Number of nodes, pods and assumed pods in the scheduler cache.",
                ("type",),
            )
        )
        self.preemption_attempts = r.register(
            Counter(
                "scheduler_preemption_attempts_total",
                "Total preemption attempts in the cluster until now.",
            )
        )
        self.preemption_victims = r.register(
            Histogram(
                "scheduler_preemption_victims",
                "Number of selected preemption victims.",
                (),
                buckets=[1, 2, 4, 8, 16, 32, 64],
            )
        )
        self.goroutines = r.register(
            Gauge(
                "scheduler_goroutines",
                "Number of running goroutines split by work type (threads here).",
                ("work",),
            )
        )
        self.event_handling_duration = r.register(
            Histogram(
                "scheduler_event_handling_duration_seconds",
                "Event handling latency by resource and action.",
                ("event",),
                buckets=[0.00001 * (1.5**i) for i in range(20)],
            )
        )
        self.queueing_hint_duration = r.register(
            Histogram(
                "scheduler_queueing_hint_execution_duration_seconds",
                "Latency of QueueingHintFn execution.",
                ("plugin", "event", "hint"),
                buckets=[0.00001 * (1.5**i) for i in range(20)],
            )
        )
        self.binding_duration = r.register(
            Histogram(
                "scheduler_binding_duration_seconds",
                "Binding latency.",
                (),
            )
        )
        self.permit_wait_duration = r.register(
            Histogram(
                "scheduler_permit_wait_duration_seconds",
                "Latency of waiting on Permit.",
                ("result",),
            )
        )
        self.unschedulable_reasons = r.register(
            Gauge(
                "scheduler_unschedulable_pods",
                "Number of unschedulable pods by plugin name.",
                ("plugin",),
            )
        )
        # --- TPU-path extensions (no reference counterpart: the hot loop
        # is a fused device dispatch, not per-pod goroutines) ---
        self.gang_dispatch_duration = r.register(
            Histogram(
                "scheduler_tpu_gang_dispatch_duration_seconds",
                "Device time for one fused gang dispatch (batch filter+score+select).",
                ("path",),  # fast / scan
            )
        )
        self.batch_size_hist = r.register(
            Histogram(
                "scheduler_tpu_batch_size",
                "Pods per gang batch.",
                (),
                buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024],
            )
        )
        self.wave_admitted = r.register(
            Counter(
                "scheduler_tpu_wave_admitted_total",
                "Pods whose speculative wave placement survived the "
                "conflict-resolution pass unchanged (ops/wave.py).",
            )
        )
        self.wave_conflicts = r.register(
            Counter(
                "scheduler_tpu_wave_conflicts_total",
                "Pods demoted by the wave's conflict-resolution pass, by "
                "conflicting constraint kind "
                "(spread / affinity / ports / fit / score).",
                ("kind",),
            )
        )
        self.wave_static_signatures = r.register(
            Counter(
                "scheduler_tpu_wave_static_signatures_total",
                "Distinct pod rows a wave dispatch computed its cross-pod "
                "statics for (gang.precompute by signature), summed over "
                "the wave batches that ran with the table; a batch with "
                "more distinct rows than the bucket, or with host-plugin "
                "vetoes, computes them per pod and adds nothing.",
            )
        )
        self.statics_host_by_domain = r.register(
            Counter(
                "scheduler_tpu_statics_host_by_domain_total",
                "Dispatches whose cross-pod statics (gang.precompute) summed "
                "a hostname spread constraint's domains by the hostname "
                "key's node-to-domain map instead of by node identity: two "
                "nodes share a hostname label value.  Zero on a cluster "
                "whose hostnames are unique.",
            )
        )
        self.wave_fallback = r.register(
            Counter(
                "scheduler_tpu_wave_fallback_total",
                "Wave-shaped work (pods/batches carrying cross-pod "
                "constraint terms or in-batch host ports) that fell off "
                "the factored wave engine, by reason (dup_hostname / "
                "kill_switch / nominated / extender / host_filters / "
                "host_scores / ...).  reason=ports and "
                "reason=sampling_compat are RETIRED rungs — the factored "
                "engine carries both — and must stay zero; a bump is a "
                "fallback-ladder regression.",
                ("reason",),
            )
        )
        self.gang_admitted = r.register(
            Counter(
                "scheduler_tpu_gang_admitted_total",
                "Gang (PodGroup) member pods admitted by the workloads "
                "tier's all-or-nothing admission pass (ops/coscheduling.py).",
            )
        )
        self.gang_rollbacks = r.register(
            Counter(
                "scheduler_tpu_gang_rollbacks_total",
                "Gangs whose members could not cover the remaining "
                "minMember quorum this batch — every member placement, "
                "topology count, and device grant restored in-kernel.",
            )
        )
        self.dra_allocations = r.register(
            Counter(
                "scheduler_tpu_dra_allocations_total",
                "ResourceClaims allocated through the batched DRA "
                "device-matching kernel (ops/dra.py).",
            )
        )
        self.plan_forks = r.register(
            Counter(
                "scheduler_tpu_plan_forks_total",
                "Counterfactual snapshot forks simulated by the planner "
                "tier (ops/counterfactual.py) — K forks per fused "
                "[K, P, N] dispatch.",
            )
        )
        self.plan_duration = r.register(
            Histogram(
                "scheduler_tpu_plan_duration_seconds",
                "End-to-end planner runs (fork packing + one fused "
                "dispatch + readback) by planner.",
                ("planner",),
            )
        )
        self.resident_rounds = r.register(
            Counter(
                "scheduler_tpu_resident_rounds_total",
                "Speculation/admission rounds run by the device-resident "
                "drain loop (ops/resident.py) across all runs.",
            )
        )
        self.host_roundtrips = r.register(
            Counter(
                "scheduler_tpu_host_roundtrips_total",
                "Blocking device→host result fetches across all paths "
                "(dispatch harvests plus static-eval / preemption-narrow / "
                "diagnosis reads) — the traffic the resident drain "
                "amortizes.",
            )
        )
        self.d2h_bytes = r.register(
            Counter(
                "scheduler_tpu_d2h_bytes_total",
                "Bytes copied device→host by blocking result fetches.",
            )
        )
        self.phase_duration = r.register(
            Histogram(
                "scheduler_tpu_phase_duration_seconds",
                "Per-batch hot-loop time by phase (queue_pop/pack/h2d/"
                "device/d2h/wave_resolve/resident_rounds/commit/bind, "
                "chain_dispatch and the parts of it and of commit: "
                "chain_dispatch.pack, commit.assume, ...).",
                ("phase",),
            )
        )
        self.sanitizer_violations = r.register(
            Counter(
                "scheduler_tpu_sanitizer_violations_total",
                "Invariant violations detected by the KTPU_SANITIZE runtime "
                "mode (kind: lock / mirror).",
                ("kind",),
            )
        )
        self.jit_recompiles = r.register(
            Counter(
                "scheduler_tpu_jit_recompiles_total",
                "Unexpected post-warmup jit compilation-cache misses per "
                "root (KTPU_SANITIZE=1 retrace hook; fn: module.function).",
                ("fn",),
            )
        )
        self.shape_check_failures = r.register(
            Counter(
                "scheduler_tpu_shape_check_failures_total",
                "eval_shape cross-check mismatches against the symbolic "
                "shape interpreter, per jit root (KTPU_SANITIZE=1; fn: "
                "module.function).",
                ("fn",),
            )
        )
        self.chaos_injected = r.register(
            Counter(
                "scheduler_tpu_chaos_injected_total",
                "Faults delivered by the chaos subsystem, by kind "
                "(watch_cut / compact / api_error / api_timeout / "
                "bind_conflict / bind_slow / node_flap / lease_contention / "
                "clock_skew).",
                ("kind",),
            )
        )
        self.chaos_recovery = r.register(
            Histogram(
                "scheduler_tpu_chaos_recovery_seconds",
                "Latency from a fault injection to the next fully drained "
                "scheduling queue, by fault kind.",
                ("kind",),
            )
        )
        # --- observability-layer overhead accounting (observability/) ---
        # refreshed on scrape from Tracer.stats()/FlightRecorder.stats()
        # (Scheduler.refresh_gauges) so the hot recording path never touches
        # the registry.
        self.trace_buffered = r.register(
            Gauge(
                "scheduler_tpu_trace_buffered_events",
                "Trace events currently buffered by the span tracer.",
            )
        )
        self.trace_dropped = r.register(
            Gauge(
                "scheduler_tpu_trace_dropped_events",
                "Trace events dropped by the tracer's bounded buffer since "
                "the trace started.",
            )
        )
        self.tracer_overhead = r.register(
            Gauge(
                "scheduler_tpu_tracer_overhead_seconds",
                "Cumulative host seconds spent appending trace events "
                "(the tracer's own cost, for overhead audits).",
            )
        )
        self.flightrec_events = r.register(
            Gauge(
                "scheduler_tpu_flightrecorder_events",
                "Pod lifecycle events currently retained in the flight "
                "recorder ring.",
            )
        )
        self.flightrec_evicted = r.register(
            Gauge(
                # scrape-refreshed snapshot of a monotonic count — exposed
                # as a gauge, so no _total suffix (OpenMetrics lint rejects
                # a _total-named gauge)
                "scheduler_tpu_flightrecorder_evicted_events",
                "Pod lifecycle events evicted from the flight recorder "
                "ring since process start (monotonic, sampled on scrape).",
            )
        )
        # --- steady-state SLO tier (observability/slo.py) ---
        self.slo_stage_duration = r.register(
            Histogram(
                "scheduler_tpu_slo_stage_duration_seconds",
                "Per-pod latency attribution joined from flight-recorder "
                "breadcrumbs by stage (queue_wait / backoff / dispatch / "
                "commit / bind) plus the e2e SLI — monotonic-clock "
                "durations, widened buckets.",
                ("stage",),
                buckets=wide_duration_buckets(),
            )
        )
        self.slo_burn_rate = r.register(
            Gauge(
                "scheduler_tpu_slo_burn_rate",
                "Error-budget burn rate per SLO objective over the rolling "
                "window (1.0 = burning exactly the budget), sampled on "
                "scrape.",
                ("objective",),
            )
        )
        self.slo_breaches = r.register(
            Counter(
                "scheduler_tpu_slo_breaches_total",
                "SLO breaches that froze and dumped the black-box trace "
                "ring, by objective.",
                ("objective",),
            )
        )
        self.trace_evicted = r.register(
            Gauge(
                "scheduler_tpu_trace_evicted_events",
                "Trace events evicted from the black-box ring since it was "
                "armed (monotonic, sampled on scrape).",
            )
        )
        # --- device telemetry ledger (observability/kernels.py): the
        # per-kernel split of the device path the aggregate
        # host_roundtrips/d2h_bytes counters can't attribute ---
        self.kernel_dispatches = r.register(
            Counter(
                "scheduler_tpu_kernel_dispatches_total",
                "Dispatches per jit root (kernel: module.function, the "
                "sanitizer's jit-root roster).",
                ("kernel",),
            )
        )
        self.kernel_execute = r.register(
            Histogram(
                "scheduler_tpu_kernel_execute_seconds",
                "Per-dispatch execute wall time by kernel — the dispatch "
                "call's wall clock (host submit on async backends; the "
                "device latency the host failed to hide shows in the "
                "kernel d2h series).  First-trace compiles are excluded "
                "(they count into the compile series).",
                ("kernel",),
                buckets=kernel_duration_buckets(),
            )
        )
        self.kernel_compiles = r.register(
            Counter(
                "scheduler_tpu_kernel_compiles_total",
                "Dispatches that grew a kernel's jit compilation cache "
                "(first trace of a new shape/static bucket).",
                ("kernel",),
            )
        )
        self.kernel_compile_seconds = r.register(
            Counter(
                "scheduler_tpu_kernel_compile_seconds_total",
                "Wall seconds spent in compiling dispatches, by kernel.",
                ("kernel",),
            )
        )
        self.kernel_d2h_bytes = r.register(
            Counter(
                "scheduler_tpu_kernel_d2h_bytes_total",
                "Blocking device→host readback bytes attributed per "
                "kernel through the Scheduler._d2h choke point "
                "(kernel=_untagged: fetches with no kernel context, so "
                "the rows sum to scheduler_tpu_d2h_bytes_total).",
                ("kernel",),
            )
        )
        self.kernel_d2h_seconds = r.register(
            Counter(
                "scheduler_tpu_kernel_d2h_seconds_total",
                "Seconds blocked in device→host readbacks per kernel.",
                ("kernel",),
            )
        )
        self.kernel_regressions = r.register(
            Counter(
                "scheduler_tpu_kernel_regressions_total",
                "Sustained per-kernel execute-time regressions detected "
                "by the dispatch ledger's sentinel (each one files a "
                "kernel_regression breach through the SLO tier's "
                "black-box freeze→dump machinery when installed).",
                ("kernel",),
            )
        )
        self.device_hbm_bytes = r.register(
            Gauge(
                "scheduler_tpu_device_hbm_bytes",
                "Live device memory from device.memory_stats() where the "
                "backend supports it (absent on CPU), sampled on scrape "
                "(kind: bytes_in_use / peak_bytes_in_use / bytes_limit).",
                ("device", "kind"),
            )
        )
        # --- device-fault tier (ISSUE 15): per-kernel circuit breakers +
        # epoch-guarded resident-state recovery ---
        self.kernel_breaker_state = r.register(
            Gauge(
                "scheduler_tpu_kernel_breaker_state",
                "Per-kernel circuit breaker state (0=closed, 1=open, "
                "2=half_open).  Open routes the dispatch family to its "
                "registered fallback engine — every trip is also visible "
                'in scheduler_tpu_wave_fallback_total{reason="breaker"}.',
                ("kernel",),
            )
        )
        self.kernel_breaker_trips = r.register(
            Counter(
                "scheduler_tpu_kernel_breaker_trips_total",
                "Breaker trips (closed/half_open → open) per kernel.",
                ("kernel",),
            )
        )
        self.kernel_breaker_failures = r.register(
            Counter(
                "scheduler_tpu_kernel_breaker_failures_total",
                "Failures booked against per-kernel breakers, by kind "
                "(dispatch_error / dispatch_hang / mesh_device_loss / "
                "poisoned_output / hbm_oom / sentinel).",
                ("kernel", "kind"),
            )
        )
        self.resident_resyncs = r.register(
            Counter(
                "scheduler_tpu_resident_resyncs_total",
                "Epoch-guarded resident-state resyncs: the device usage "
                "lineage was dropped and rebuilt from the host committer "
                "(reason: dispatch_failed / checksum_mismatch / "
                "epoch_stale / mesh_degraded / hbm_oom).",
                ("reason",),
            )
        )
        # --- control-plane pipeline tier (observability/controlplane.py):
        # the serving/watch path's accounting, synced on scrape ---
        self.apiserver_request_duration = r.register(
            Histogram(
                "scheduler_tpu_apiserver_request_duration_seconds",
                "API server request latency by verb/resource/status "
                "(apiserver_request_duration_seconds's shape), accumulated "
                "off-registry in the handler threads and merged on scrape.",
                ("verb", "resource", "status"),
                buckets=wide_duration_buckets(),
            )
        )
        self.watch_window_events = r.register(
            Gauge(
                "scheduler_tpu_watch_window_events",
                "Watch-cache sliding-window occupancy per resource "
                "(events retained; 410s start when watchers fall behind "
                "the window), sampled on scrape.",
                ("resource",),
            )
        )
        self.watch_fanout_lag = r.register(
            Gauge(
                "scheduler_tpu_watch_fanout_lag_events",
                "Max per-watcher fanout lag in events (cache head rv minus "
                "the slowest active watcher's delivered rv), sampled on "
                "scrape.",
                ("resource",),
            )
        )
        self.watch_compactions = r.register(
            Counter(
                "scheduler_tpu_watch_compactions_total",
                "Watch-cache compactions that dropped retained events "
                "(the etcd-compaction shape; the chaos runner's forced-410 "
                "lever), refreshed on scrape.",
                ("resource",),
            )
        )
        self.watch_relists = r.register(
            Counter(
                "scheduler_tpu_watch_relists_total",
                "410 Gone responses served by the watch cache (each one "
                "forces a client relist — reflector.go:340), refreshed on "
                "scrape.",
                ("resource",),
            )
        )
        self.apiserver_bulk_bind = r.register(
            Counter(
                "scheduler_tpu_apiserver_bulk_bind_total",
                "Bulk binding POSTs the API server applied as one store "
                "transaction: what=txns (slices), items (bindings in them), "
                "fallback_items (per-item deliveries to a store subscriber "
                "without a batch handler), refreshed on scrape.",
                ("what",),
            )
        )
        self.gc_collections = r.register(
            Counter(
                "scheduler_tpu_gc_collections_total",
                "Garbage collections of this process while a serving loop "
                "held the collector policy (util/collector.py), by "
                "generation and when: load = the interpreter's own, idle = "
                "made by the loop's idle pass.  generation=2, when=load is a "
                "full collection that landed on a busy loop.  Refreshed on "
                "scrape.",
                ("generation", "when"),
            )
        )
        self.gc_pause_seconds = r.register(
            Counter(
                "scheduler_tpu_gc_pause_seconds_total",
                "Seconds every thread of the process stood still in those "
                "collections (the program's own gc.callbacks clock), by "
                "generation, refreshed on scrape.",
                ("generation",),
            )
        )
        self.gc_frozen_objects = r.register(
            Gauge(
                "scheduler_tpu_gc_frozen_objects",
                "Objects outside the collector's walk while the policy is "
                "engaged (gc.get_freeze_count(), counted on scrape), 0 once "
                "released.",
            )
        )
        self.malloc_system_bytes = r.register(
            Gauge(
                "scheduler_tpu_malloc_system_bytes",
                "Bytes the process's malloc arenas hold from the kernel "
                "(glibc mallinfo2 arena, summed over arenas; "
                "util/allocator.py), read on scrape.  With one arena it rises "
                "to the main heap's high-water mark and stays.",
            )
        )
        self.malloc_mmapped_bytes = r.register(
            Gauge(
                "scheduler_tpu_malloc_mmapped_bytes",
                "Bytes in blocks malloc mapped one by one (glibc mallinfo2 "
                "hblkhd: requests over the mmap threshold), read on scrape.",
            )
        )
        self.malloc_arenas = r.register(
            Gauge(
                "scheduler_tpu_malloc_arenas",
                "Arenas glibc has made in this process (malloc_info), read "
                "on scrape: 1 where the allocator policy was engaged before "
                "any other thread of the process allocated.",
            )
        )
        self.wire_bytes_total = r.register(
            Counter(
                "scheduler_tpu_wire_bytes_total",
                "Bytes the API server moved over the list/watch/bind wire, "
                "split by codec (json vs the length-prefixed binary frames) "
                "and direction (tx/rx as the server sees them), refreshed "
                "on scrape.",
                ("codec", "direction"),
            )
        )
        self.informer_delivery_lag = r.register(
            Histogram(
                "scheduler_tpu_informer_delivery_lag_seconds",
                "API-write to reflector-delivery lag per resource (the "
                "watch cache's rv stamp joined against the client's decode "
                "time — in-process clocks).",
                ("resource",),
                buckets=wide_duration_buckets(),
            )
        )
        self.pipeline_hop_duration = r.register(
            Histogram(
                "scheduler_tpu_pipeline_hop_seconds",
                "Per-hop duration of the end-to-end pod pipeline "
                "(api_write → watch_delivery → informer_handler → enqueue "
                "→ pop → assumed → bind_start → bound), joined per pod "
                "from causal-chain breadcrumbs when the chain closes.",
                ("hop",),
                buckets=wide_duration_buckets(),
            )
        )
        self.snapshot_staleness = r.register(
            Gauge(
                "scheduler_tpu_snapshot_staleness_seconds",
                "Newest-delivered minus newest-applied informer event at "
                "the last batch dispatch — how stale the scheduling "
                "snapshot ran; sustained breaches file a "
                "snapshot_staleness black-box dump.",
            )
        )
        self.queue_depth = r.register(
            Gauge(
                "scheduler_tpu_queue_depth",
                "Scheduling-queue depth per sub-queue (active / backoff / "
                "unschedulable / gated), sampled on scrape under the "
                "scheduler lock.",
                ("queue",),
            )
        )
        self.queue_oldest_age = r.register(
            Gauge(
                "scheduler_tpu_queue_oldest_age_seconds",
                "Age of the oldest pod per sub-queue (monotonic clock "
                "since first enqueue), sampled on scrape under the "
                "scheduler lock.",
                ("queue",),
            )
        )
        self.recorder = MetricAsyncRecorder()

    def expose(self) -> str:
        self.recorder.flush()
        return self.registry.expose()


# ---------------------------------------------------------------------------
# slow-cycle tracing (utiltrace: schedule_one.go:409-449 — any scheduling
# cycle over 100ms dumps its per-step timings).  This is the LOG-side
# surface: one text dump per slow cycle.  The span-based tracer with
# Perfetto export, per-batch context, and HTTP control lives in
# kubernetes_tpu/observability/tracer.py — see OBSERVABILITY.md for how the
# two relate (Trace stays as the always-on cheap outlier dump; the span
# tracer is the on-demand full-timeline capture).
# ---------------------------------------------------------------------------

SLOW_CYCLE_THRESHOLD_S = 0.100


class Trace:
    """k8s.io/utils/trace analogue: named steps, dumped when the total
    exceeds a threshold."""

    def __init__(self, name: str, clock=time.monotonic, sink=None, **fields):
        self.name = name
        self.fields = fields
        self._clock = clock
        self._start = clock()
        self._steps: List[Tuple[float, str]] = []
        self._sink = sink  # callable(str); default logging

    def step(self, msg: str) -> None:
        self._steps.append((self._clock(), msg))

    def log_if_long(self, threshold_s: float = SLOW_CYCLE_THRESHOLD_S) -> Optional[str]:
        total = self._clock() - self._start
        if total < threshold_s:
            return None
        parts = [
            f'Trace "{self.name}" '
            + ",".join(f"{k}:{v}" for k, v in self.fields.items())
            + f" (total {total * 1000:.1f}ms):"
        ]
        prev = self._start
        for t, msg in self._steps:
            parts.append(f"  +{(t - prev) * 1000:.1f}ms {msg}")
            prev = t
        text = "\n".join(parts)
        if self._sink is not None:
            self._sink(text)
        else:
            import logging

            logging.getLogger("kubernetes_tpu.trace").info(text)
        return text
