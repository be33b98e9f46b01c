"""Invariant analyzers for the TPU scheduler (``python -m kubernetes_tpu.analysis``).

Eleven AST checkers guard the contracts the concurrency layering, the
device boundary, and the named-axis shape algebra rely on (the
race-detector/vet role the reference scheduler gets from the Go
toolchain):

  * ``lock-discipline`` — registered lock-guarded fields are only mutated
    under their lock or in callers-verified ``*_under_lock`` methods;
  * ``plugin-purity`` — ``pre_filter_spec_pure`` plugins keep their spec
    path free of state reads/writes;
  * ``jit-boundary`` — nothing reachable from the jitted pipelines in
    ``ops/`` host-syncs or branches on tracers;
  * ``d2h-leak`` — every BLOCKING device→host fetch on the host side
    routes through ``Scheduler._d2h`` (the round-trip accounting choke
    point), nothing coerces/truth-tests a device value ad hoc;
  * ``donation`` — no caller reads a buffer after donating it to a
    ``donate_argnums`` kernel, and every donating kernel is documented
    in RESIDENT.md's donation/aliasing contract;
  * ``slice-clamp`` — ``dynamic_update_slice``/``.at[...].set`` with a
    traced start is only allowed with a padded destination, a provably
    static start, or a justified suppression (XLA clamps/drops
    out-of-range window writes SILENTLY);
  * ``retrace`` — no weak-typed Python scalars or unbucketed
    shape-derived static args leak into jit signatures;
  * ``shape`` — a symbolic named-dim interpreter over everything
    reachable from the jit roots (``# ktpu: axes(...)`` annotations +
    ``_KTPU_AXES`` schema tables) flags named-axis mismatches that
    rank-1 broadcasting would silently absorb, and scan-carry drift;
  * ``dtype`` — implicit promotions inside the integer kernels (true
    division, bool arithmetic, weak float widening, per-root
    ``accum(...)`` carry contracts);
  * ``shard`` — classifies every op against the ('pods','nodes') mesh:
    N-axis reductions/gathers must sit under a helper declared in the
    module's ``_KTPU_N_COLLECTIVES`` roster (the multichip collective
    inventory, MULTICHIP.md), and every roster entry must carry a
    ``resolved(collective|local|replicated): <how>`` sharding story —
    the worklist is a burn-down, not a parking lot;
  * ``breaker`` — every module-level jit root must carry a
    ``_KTPU_BREAKER_FALLBACKS`` entry (observability/kernels.py) naming
    the parity-certified engine its open circuit breaker routes to
    (``fallback(<engine>): <how>``) or an explicit ``no_fallback: <why>``
    waiver — the device-fault tier's drain story is analyzer-gated
    (ISSUE 15, CHAOS.md "Device seams").

Plus a runtime sanitizer (``KTPU_SANITIZE=1``, see ``sanitizer.py``),
including the jit recompile hook (``scheduler_tpu_jit_recompiles_total``)
and the eval_shape cross-check of the shape interpreter
(``scheduler_tpu_shape_check_failures_total``, ``shapecheck.py``).
Suppressions: ``# ktpu: allow(<rule>) — <reason>`` (reason mandatory).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from kubernetes_tpu.analysis.core import (
    Finding,
    SourceModule,
    collect_bare_suppressions,
    load_source,
    render_json,
    render_text,
)
from kubernetes_tpu.analysis.breaker import BreakerChecker
from kubernetes_tpu.analysis.clamp import ClampChecker
from kubernetes_tpu.analysis.d2h import D2HChecker
from kubernetes_tpu.analysis.donation import DonationChecker
from kubernetes_tpu.analysis.jit import JitChecker
from kubernetes_tpu.analysis.locks import LockChecker
from kubernetes_tpu.analysis.purity import PurityChecker
from kubernetes_tpu.analysis.retrace import RetraceChecker
from kubernetes_tpu.analysis.shape import (
    DtypeChecker,
    ShapeChecker,
    ShardChecker,
    collective_roster,
)

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)

# the shipped tree's checker targets
LOCK_MODULES = (
    "scheduler.py",
    os.path.join("cache", "cache.py"),
    os.path.join("cache", "mirror.py"),
    os.path.join("queue", "scheduling_queue.py"),
    # chaos subsystem: the injection log / one-shot ledger, per-seam
    # ordinal counters, and journal entries are all appended from
    # reflector threads and binding workers concurrently
    os.path.join("chaos", "faults.py"),
    os.path.join("chaos", "proxy.py"),
    os.path.join("chaos", "journal.py"),
    # wire codec: pure by design (empty registry) — vetted so any mutable
    # module state a future change adds lands under the lock checker;
    # frames are encoded on apiserver handler + watch-cache append threads
    os.path.join("client", "wire_codec.py"),
    # observability: the span buffer and flight-recorder ring are appended
    # from the scheduling loop, binding workers, informer threads, and HTTP
    # debug handlers; explain holds the Scheduler lock across its prep
    os.path.join("observability", "tracer.py"),
    os.path.join("observability", "flightrecorder.py"),
    os.path.join("observability", "explain.py"),
    # SLO tier: ingest runs on every flight-recorder producer thread,
    # snapshot/evaluate on HTTP handlers and the bench harness
    os.path.join("observability", "slo.py"),
    # device telemetry ledger: the scheduling loop records dispatches,
    # the planner thread records d2h, HTTP handlers read tables/costs
    os.path.join("observability", "kernels.py"),
    # control-plane pipeline tier: chains are stamped from apiserver
    # handler threads, reflector threads, informer handlers, and the
    # flight-recorder sink; scrape-time sync reads from HTTP handlers
    os.path.join("observability", "controlplane.py"),
    # workloads tier: the GangDirectory registry/bookkeeping is mutated by
    # informer handlers, the workloads dispatch, and bind-failure unwinds
    os.path.join("workloads", "gang.py"),
)
PURITY_MODULES = (
    os.path.join("framework", "plugins.py"),
    os.path.join("framework", "volume_plugins.py"),
    os.path.join("framework", "volumebinding.py"),
    os.path.join("framework", "dynamicresources.py"),
)
JIT_MODULES = (
    os.path.join("ops", "chain.py"),
    os.path.join("ops", "common.py"),
    os.path.join("ops", "coscheduling.py"),
    os.path.join("ops", "counterfactual.py"),
    os.path.join("ops", "dra.py"),
    os.path.join("ops", "explain.py"),
    os.path.join("ops", "fastpath.py"),
    os.path.join("ops", "filters.py"),
    os.path.join("ops", "gang.py"),
    os.path.join("ops", "pipeline.py"),
    os.path.join("ops", "preemption.py"),
    os.path.join("ops", "resident.py"),
    os.path.join("ops", "scores.py"),
    os.path.join("ops", "wave.py"),
    os.path.join("ops", "wire.py"),
)
# host modules that handle device values — the d2h-leak surface.
# ops/pipeline.py is targeted but allowlisted inside the checker (the
# standalone parity harness has no Scheduler, hence no counters to feed).
D2H_MODULES = (
    "scheduler.py",
    "fastpath.py",
    os.path.join("cache", "mirror.py"),
    os.path.join("cache", "device_mirror.py"),
    os.path.join("observability", "explain.py"),
    os.path.join("ops", "pipeline.py"),
    os.path.join("ops", "wire.py"),
)
# donation roots live in the kernels; the callers that can hold dead
# references are the scheduler and the device-mirror glue
DONATION_MODULES = JIT_MODULES + (
    os.path.join("cache", "device_mirror.py"),
    "scheduler.py",
    "fastpath.py",
)
CLAMP_MODULES = JIT_MODULES + (os.path.join("cache", "device_mirror.py"),)
# breaker-fallback roster rule (ISSUE 15): the jit-root surface plus the
# module that owns the _KTPU_BREAKER_FALLBACKS literal
BREAKER_MODULES = JIT_MODULES + (
    os.path.join("cache", "device_mirror.py"),
    os.path.join("observability", "kernels.py"),
)
# the symbolic shape/dtype/shard interpreter walks everything reachable
# from the jit roots; device_mirror's delta splicer is a root too
SHAPE_MODULES = JIT_MODULES + (os.path.join("cache", "device_mirror.py"),)
RETRACE_MODULES = JIT_MODULES + (
    os.path.join("cache", "device_mirror.py"),
    "scheduler.py",
    "fastpath.py",
    os.path.join("observability", "explain.py"),
)
DONATION_CONTRACT_DOC = os.path.join(_REPO_ROOT, "RESIDENT.md")


def default_targets() -> Dict[str, List[str]]:
    return {
        "locks": [os.path.join(_PKG_ROOT, p) for p in LOCK_MODULES],
        "purity": [os.path.join(_PKG_ROOT, p) for p in PURITY_MODULES],
        "jit": [os.path.join(_PKG_ROOT, p) for p in JIT_MODULES],
        "d2h": [os.path.join(_PKG_ROOT, p) for p in D2H_MODULES],
        "donation": [os.path.join(_PKG_ROOT, p) for p in DONATION_MODULES],
        "clamp": [os.path.join(_PKG_ROOT, p) for p in CLAMP_MODULES],
        "retrace": [os.path.join(_PKG_ROOT, p) for p in RETRACE_MODULES],
        "shape": [os.path.join(_PKG_ROOT, p) for p in SHAPE_MODULES],
        "dtype": [os.path.join(_PKG_ROOT, p) for p in SHAPE_MODULES],
        "shard": [os.path.join(_PKG_ROOT, p) for p in SHAPE_MODULES],
        "breaker": [os.path.join(_PKG_ROOT, p) for p in BREAKER_MODULES],
    }


# per-rule wall time of the most recent run_analysis() call, seconds —
# surfaced by `--json` (analyzer-perf telemetry; the shape/dtype/shard
# families share ONE interpretation, whose cost lands on 'shape')
last_rule_seconds: Dict[str, float] = {}


def run_analysis(
    targets: Optional[Dict[str, Sequence[str]]] = None,
) -> List[Finding]:
    """Run every checker over its target file set; returns ALL findings
    (post-suppression), sorted by path/line.  ``targets`` maps checker key
    ('locks'/'purity'/'jit'/'d2h'/'donation'/'clamp'/'retrace'/'shape'/
    'dtype'/'shard') → file paths; defaults to the shipped tree.  The
    donation contract document (RESIDENT.md) is only consulted on
    shipped-tree runs — fixture runs override 'donation' and skip it.

    Every checker shares one parsed AST per file (core.load_source's
    mtime-keyed process cache), and the shape/dtype/shard families share
    one symbolic interpretation per target set.
    """
    import time as _time

    t = dict(default_targets())
    fixture_donation = targets is not None and "donation" in targets
    if targets is not None:
        t.update({k: list(v) for k, v in targets.items()})

    loaded: Dict[str, SourceModule] = {}

    def load(paths: Sequence[str]) -> List[SourceModule]:
        out = []
        for p in paths:
            key = os.path.abspath(p)
            if key not in loaded:
                loaded[key] = load_source(p)
            out.append(loaded[key])
        return out

    findings: List[Finding] = []
    last_rule_seconds.clear()

    contract = None
    if not fixture_donation and os.path.exists(DONATION_CONTRACT_DOC):
        with open(DONATION_CONTRACT_DOC, "r", encoding="utf-8") as f:
            contract = f.read()

    engine_cache: Dict[tuple, object] = {}
    plan = (
        ("locks", LockChecker, {}),
        ("purity", PurityChecker, {}),
        ("jit", JitChecker, {}),
        ("d2h", D2HChecker, {"root_mods": lambda: load(t.get("jit", ()))}),
        ("donation", DonationChecker, {"contract_text": lambda: contract}),
        ("clamp", ClampChecker, {}),
        ("retrace", RetraceChecker, {}),
        ("shape", ShapeChecker, {"engine_cache": lambda: engine_cache}),
        ("dtype", DtypeChecker, {"engine_cache": lambda: engine_cache}),
        ("shard", ShardChecker, {"engine_cache": lambda: engine_cache}),
        ("breaker", BreakerChecker, {}),
    )
    for key, cls, extra in plan:
        start = _time.perf_counter()
        checker = cls()
        kwargs = {k: v() for k, v in extra.items()}
        checker.run(load(t.get(key, ())), **kwargs)
        findings.extend(checker.findings)
        last_rule_seconds[checker.rule] = _time.perf_counter() - start

    findings.extend(collect_bare_suppressions(loaded.values()))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


__all__ = [
    "Finding",
    "run_analysis",
    "default_targets",
    "collective_roster",
    "render_text",
    "render_json",
]
