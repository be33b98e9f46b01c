#!/usr/bin/env python3
"""chip_smoke.py — does the scheduling path still start, and answer
correctly, on the chip?

Drives the main path once through the entry points a user calls
(``Scheduler`` + informer handlers + ``schedule_pending``), at the upstream
scheduler_perf widths (BASELINE.md), and checks the decisions against the
plain reference (the serial oracle, via ``tools/paritycheck``).  ONE
process, the only one that touches JAX.  Phases: device, drain,
constraints, identity; with ``--chips 4``: device, mesh.

The served path (``ApiServer`` ← ``RemoteClusterSource`` ←
``SchedulerServer``, binds read back through LIST) is proven elsewhere: on
the chip by ``python3 benchmarks/run.py`` (every cell of BENCHMARK.json), on
the CPU by tests/test_bench_interpod_cell.py and
tests/test_bench_template_format.py, which drive ``benchmarks/harness.py``
at tiny sizes.

    python chip_smoke.py                 # one chip, full widths
    python chip_smoke.py --chips 4       # four chips: the mesh drain and its
                                         # single-chip comparison, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --nodes 64 --pods 128
                                         # CPU rehearsal at tiny sizes: same
                                         # phases, never "ok"

The script never sets or clears the JAX platform.  Its LAST stdout line is
one JSON object ``{"ok": ..., "device": {"platform", "kind", "count"}}``
with the device as JAX reports it; everything else is on earlier lines.
Exit code 0 only when ok: a device that is not a TPU, a phase that bound
fewer pods than it was given, a parity diff, a dispatch the host answered
in the device's place (breaker failure / fallback), a kernel the phase
exists to exercise that never dispatched, a compile in the warm drain, or
a zero HBM peak all fail the run.

The wall times printed are SMOKE TIMINGS: one cold pass with compilation
inside it.  They size the cold run; they are not benchmark numbers and go
into no record as such.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from typing import Dict, List, Optional

# scheduler_perf widths (BASELINE.md → performance-config.yaml line refs)
FULL_NODES = 5000  # SchedulingBasic :51, TopologySpreading :512
FULL_PODS = 10000  # SchedulingBasic backlog; the other phases derive theirs
# Backlog cuts the cold run's 1200 s force at the full node width (node and
# pod SHAPES are never cut).  Every (root, statics, bucket) variant of the
# cross-pod engine costs ~5 min of TPU compile on the chip's host whatever
# the node bucket (CHANGES.md PR 24) and the cold run has room for ONE: so
# TopologySpreading keeps one wave batch (its chained batches would add
# chain_dispatch, a second such compile) and that same drain is the one
# check_wave_vs_oracle replays (the check's own mixed workload would be a
# third).  The plain reference is a per-pod Python loop over every node
# (≈0.1 s/pod on the chip's host at 5000 nodes).
CONSTRAINT_PODS = 512  # one wave batch (config batch_size)
ORACLE_BASIC_PODS = 1024  # fast_device_min: the smallest resident run
# --chips 4 drains THREE times (single-chip, pods-major, nodes-major) and
# every drain compiles its own executables at four times the charge: one
# cross-pod batch (chain.chain_dispatch, the partitioned root), a basic
# backlog below fast_device_min (static_eval on the mesh + the host
# committer; resident_run's arguments are replicated under the mesh, so
# its ~2 min compile per drain would prove little), no gang pods.
MESH_BASIC_PODS = 512
MESH_CROSS_PODS = 256
MESH_GANGS = 0

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


# what Scheduler logs (WARNING+) when the device did not answer: an abandoned
# dispatch with its exception text, a failed fast-path dispatch, a torn
# resident checksum, a degraded mesh
_FAULT_WORDS = ("abandoned", "failed", "mismatch", "degraded")


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---- workloads (fixed seeds; SchedulingBasic-shaped pods on paritycheck's
# basic nodes, TopologySpreading-shaped pods) ---------------------------------


def basic_pods(n: int, prefix: str, seed: int = 0) -> list:
    import random

    from kubernetes_tpu.api.types import Container, Pod

    rng = random.Random(f"{seed}/{prefix}")
    return [
        Pod(
            name=f"{prefix}-{i}",
            labels={"app": f"app-{i % 10}"},
            containers=[
                Container(
                    name="c",
                    requests={
                        "cpu": f"{rng.choice([100, 250, 500])}m",
                        "memory": f"{rng.choice([128, 256, 512])}Mi",
                    },
                )
            ],
        )
        for i in range(n)
    ]


def spread_pods(n: int, prefix: str = "ts") -> list:
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )

    pods = []
    for i in range(n):
        app = f"a{i % 20}"
        pods.append(
            Pod(
                name=f"{prefix}-{i}",
                labels={"app": app},
                topology_spread_constraints=(
                    TopologySpreadConstraint(
                        max_skew=5,
                        topology_key="topology.kubernetes.io/zone",
                        when_unsatisfiable="DoNotSchedule",
                        label_selector=LabelSelector(match_labels={"app": app}),
                    ),
                ),
                containers=[
                    Container(
                        name="c", requests={"cpu": "100m", "memory": "64Mi"}
                    )
                ],
            )
        )
    return pods


class _LogTap(logging.Handler):
    """Collects WARNING+ records of the package's loggers: every abandoned
    dispatch logs its underlying exception text there
    (Scheduler._note_dispatch_failure) whichever Scheduler it hit."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.records: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}")


class Smoke:
    def __init__(self, nodes: int, pods: int, seed: int = 0) -> None:
        import jax

        import kubernetes_tpu  # noqa: F401 — x64 + compile-cache config

        self.jax = jax
        self.nodes = nodes
        self.pods = pods
        self.seed = seed
        self.full = nodes >= FULL_NODES
        self.failures: List[str] = []
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._tap = _LogTap()
        self._t_start = time.perf_counter()

    # ---- plumbing ---------------------------------------------------------

    def __enter__(self) -> "Smoke":
        mon = self.jax.monitoring
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        logging.getLogger("kubernetes_tpu").addHandler(self._tap)
        return self

    def __exit__(self, *exc) -> None:
        mon = self.jax.monitoring
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        logging.getLogger("kubernetes_tpu").removeHandler(self._tap)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def fail(self, phase: str, msg: str) -> None:
        self.failures.append(f"{phase}: {msg}")
        say(f"FAIL {phase}: {msg}")

    def device_doc(self) -> dict:
        devs = self.jax.devices()
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }

    class _Phase:
        """Wall / compile split for one phase (process-wide compile clock
        from jax.monitoring: trace + lowering + backend compile)."""

        def __init__(self, smoke: "Smoke", name: str) -> None:
            self.smoke, self.name = smoke, name

        def __enter__(self):
            s = self.smoke
            self.t0 = time.perf_counter()
            self.c0 = s.compile_s
            self.h0, self.m0 = s.cache_hits, s.cache_misses
            self.log0 = len(s._tap.records)
            say(f"--- phase {self.name} ---")
            return self

        def __exit__(self, et, ev, tb):
            s = self.smoke
            if et is not None and not issubclass(et, KeyboardInterrupt):
                s.fail(self.name, f"raised {et.__name__}: {ev}")
            bad = [
                r
                for r in s._tap.records[self.log0 :]
                if any(w in r for w in _FAULT_WORDS)
            ]
            if bad:
                # the first underlying exception text, not just a count
                s.fail(
                    self.name,
                    f"{len(bad)} device fault(s) logged; first: "
                    f"{bad[0][:1500]}",
                )
            wall = time.perf_counter() - self.t0
            comp = s.compile_s - self.c0
            say(
                f"phase {self.name}: smoke timing wall {wall:.1f}s = compile "
                f"{comp:.1f}s + steady {max(wall - comp, 0.0):.1f}s; compile "
                f"cache hits {s.cache_hits - self.h0} misses "
                f"{s.cache_misses - self.m0}"
            )
            return et is not None and not issubclass(et, KeyboardInterrupt)

    def phase(self, name: str) -> "Smoke._Phase":
        return Smoke._Phase(self, name)

    def loud(self, phase: str, sched, want_kernels=(), want_metrics=()) -> None:
        """The loudness checks for one Scheduler: no breaker failure, no
        breaker fallback, ≥1 device dispatch of each kernel the phase
        exists to exercise, a non-zero HBM peak (on the chip)."""
        from kubernetes_tpu.tools.paritycheck import device_faults

        for label, got, want in device_faults(sched):
            self.fail(phase, f"{label}: {got} (want {want})")
        table = {
            r["kernel"]: r for r in sched.kernels.table(cost=False)
        }
        disp = {
            k: (r["dispatches"], r["compiles"])
            for k, r in sorted(table.items())
            if r["dispatches"]
        }
        say(f"{phase}: kernel dispatches (n, compiles) {disp}")
        for k in want_kernels:
            if not table.get(k, {}).get("dispatches", 0):
                self.fail(phase, f"no device dispatch of {k}")
        for m in want_metrics:
            if not sched.metrics[m] > 0:
                self.fail(phase, f"sched.metrics[{m!r}] is 0")
        say(
            f"{phase}: metrics "
            f"{ {k: v for k, v in sorted(sched.metrics.items()) if v} }"
        )
        self.hbm(phase, sched.kernels)

    def hbm(self, phase: str, ledger=None) -> None:
        """The ledger's HBM rows (process-wide device memory stats, never
        non-empty on a CPU): on the chip the peak must be non-zero."""
        from kubernetes_tpu.observability.kernels import DispatchLedger

        rows = (ledger or DispatchLedger()).hbm_rows()
        if rows:
            say(f"{phase}: hbm {rows}")
        if self.device_doc()["platform"] == "tpu":
            if not any(r["peak_bytes_in_use"] > 0 for r in rows):
                self.fail(phase, f"no HBM row with a non-zero peak: {rows}")
        elif not rows:
            say(f"{phase}: hbm not reported by this backend")

    def _check_result(self, phase: str, name: str, res: dict) -> None:
        """A paritycheck result: zero diffs (device faults and missing
        engagement count as diffs there) and every pod bound on both
        sides."""
        if res["diffs"]:
            self.fail(
                phase,
                f"{name}: {res['diffs']} diffs, first {res['first_diffs']}",
            )
        bound = [v for k, v in res.items() if k.startswith("bound_")]
        if any(b != res["pods"] for b in bound):
            self.fail(phase, f"{name}: bound {bound} of {res['pods']}")

    # ---- phases -------------------------------------------------------------

    def device(self) -> None:
        import importlib.metadata as md

        jax = self.jax
        with self.phase("device"):
            vers = {}
            for pkg in ("jax", "jaxlib", "libtpu"):
                try:
                    vers[pkg] = md.version(pkg)
                except md.PackageNotFoundError:
                    vers[pkg] = "not installed"
            say(f"versions {vers}")
            doc = self.device_doc()
            say(f"devices {doc} {jax.devices()}")
            say(
                "compile cache dir "
                f"{jax.config.jax_compilation_cache_dir} (enabled="
                f"{jax.config.jax_enable_compilation_cache})"
            )
            if doc["platform"] != "tpu":
                self.fail(
                    "device",
                    f"platform {doc['platform']!r} is not a TPU — a "
                    "rehearsal, never a chip result",
                )

    def drain(self) -> None:
        """SchedulingBasic (performance-config.yaml:51): a first drain that
        compiles, then a second backlog of the same shape on the same
        scheduler that must compile nothing."""
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.tools import paritycheck as pc

        with self.phase("drain"):
            sched = Scheduler()
            bound: Dict[str, str] = {}
            sched.binding_sink = lambda pod, node: bound.__setitem__(
                pod.name, node
            )
            # pre-size the placed-pod axes for BOTH backlogs so the second
            # drain meets the shapes the first one compiled
            sched.mirror.e_cap_hint = (
                2 * self.pods + sched.config.batch_size + 128
            )
            for n in pc._basic_nodes(self.nodes):
                sched.on_node_add(n)

            def drain(prefix: str):
                for p in basic_pods(self.pods, prefix, self.seed):
                    sched.on_pod_add(p)
                ok = sum(1 for o in sched.schedule_pending() if o.node)
                compiles = sum(
                    r["compiles"] for r in sched.kernels.table(cost=False)
                )
                return ok, compiles, time.perf_counter()

            t0 = time.perf_counter()
            ok1, c1, t1 = drain("sb1")
            ok2, c2, t2 = drain("sb2")
            say(
                f"drain: SchedulingBasic {self.nodes} nodes / {self.pods} "
                f"pods: first drain bound {ok1} in {t1 - t0:.2f}s "
                f"({c1} compiles), second drain bound {ok2} in "
                f"{t2 - t1:.2f}s ({c2 - c1} compiles) [smoke timings]"
            )
            if ok1 != self.pods or ok2 != self.pods:
                self.fail("drain", f"bound {ok1}+{ok2} of 2x{self.pods} pods")
            if len(bound) != 2 * self.pods:
                self.fail("drain", f"{len(bound)} bindings reached the sink")
            if c2 != c1:
                self.fail(
                    "drain", f"second drain compiled {c2 - c1} kernel(s)"
                )
            self.loud(
                "drain",
                sched,
                want_kernels=("resident.resident_run", "fastpath.static_eval"),
                want_metrics=("resident_batches",),
            )

    def constraints(self) -> None:
        """TopologySpreading (performance-config.yaml:512): the cross-pod
        engine must dispatch on the device, not only the signature path —
        and its decisions must equal the plain reference's
        (paritycheck.check_wave_vs_oracle on this workload)."""
        from kubernetes_tpu.tools import paritycheck as pc

        n_pods = min(self.pods // 2, CONSTRAINT_PODS)
        with self.phase("constraints"):
            if self.full:
                say(
                    f"constraints: CUT backlog {FULL_PODS // 2} -> {n_pods} "
                    "pods (one wave batch: wave.wave_run; the chained "
                    "batches' chain.chain_dispatch is a second ~5 min "
                    "compile the cold run has no room for); node width "
                    "NOT cut"
                )
            res = pc.check_wave_vs_oracle(
                self.nodes, n_pods, make_pods=spread_pods, zones=8
            )
            say(
                f"constraints: TopologySpreading {self.nodes} nodes / "
                f"{n_pods} pods, wave_vs_oracle {json.dumps(res, default=str)}"
            )
            self._check_result("constraints", "wave_vs_oracle", res)
            if not any(
                res["kernel_dispatches"].get(k)
                for k in ("chain.chain_dispatch", "wave.wave_run", "gang.gang_run")
            ):
                self.fail("constraints", "no device dispatch of a cross-pod root")
            self.hbm("constraints")

    def identity(self) -> None:
        """Decisions against the plain reference at the drain's node width
        (backlogs cut to what the per-pod Python oracle can replay inside
        the run's limit — each cut printed)."""
        from kubernetes_tpu.tools import paritycheck as pc

        n_basic = min(self.pods // 2, ORACLE_BASIC_PODS)
        with self.phase("identity"):
            if self.full:
                say(
                    f"identity: CUT backlog at {self.nodes} nodes — "
                    f"resident_vs_oracle {n_basic} pods (the serial oracle "
                    "replays ~10 pods/s there); cross_batch keeps the "
                    f"drain's {self.pods} pods; node width NOT cut"
                )
            say(
                "identity: wave_vs_oracle ran in the constraints phase "
                "(where its compile lives)"
            )
            checks = {
                "resident_vs_oracle": lambda: pc.check_resident_vs_oracle(
                    self.nodes, n_basic
                ),
                "cross_batch": lambda: pc.check_cross_batch(
                    self.nodes, self.pods
                ),
            }
            for name, check in checks.items():
                res = check()
                say(f"identity: {name} {json.dumps(res, default=str)}")
                self._check_result("identity", name, res)
            self.hbm("identity")

    def mesh(self, chips: int) -> None:
        """--chips N: the mesh drain and what it is compared with —
        paritycheck.check_multichip_vs_singlechip at the full node width
        (it drains three times and compiles per mesh layout: the BACKLOG
        is cut, the node width is not)."""
        from kubernetes_tpu.tools import paritycheck as pc

        n_dev = self.device_doc()["count"]
        with self.phase("mesh"):
            if n_dev != chips:
                self.fail("mesh", f"{n_dev} devices visible, want {chips}")
                return
            n_basic = min(self.pods, MESH_BASIC_PODS)
            n_cross = min(self.pods // 2, MESH_CROSS_PODS)
            n_gangs = MESH_GANGS
            say(
                f"mesh: CUT backlog at {self.nodes} nodes — {n_basic} basic "
                f"+ {n_cross} cross-pod pods + {n_gangs} gangs (the check's "
                "defaults are 600 + 240 + 24 at 120 nodes; three drains, one "
                "~5 min chain_dispatch compile per mesh layout, four chips "
                "charged); node width NOT cut"
            )
            res = pc.check_multichip_vs_singlechip(
                n_nodes=self.nodes,
                n_pods=n_basic,
                n_cross=n_cross,
                n_gangs=n_gangs,
            )
            say(f"mesh: multichip_vs_singlechip {json.dumps(res, default=str)}")
            if res["diffs"]:
                self.fail(
                    "mesh",
                    f"{res['diffs']} diffs, first {res['first_diffs']}",
                )
            if res["devices"] != chips:
                self.fail("mesh", f"check saw {res['devices']} devices")
            for label, run in res["mesh_runs"].items():
                if not run.get("multi_device_dispatches", 0) > 0:
                    self.fail("mesh", f"{label}: no multi-device dispatch")
                if run.get("dispatch_device_span") != chips:
                    self.fail(
                        "mesh",
                        f"{label}: partitioned arguments spanned "
                        f"{run.get('dispatch_device_span')} devices",
                    )
                if len(set(run.get("mesh_devices", ()))) != chips:
                    self.fail("mesh", f"{label}: mesh {run.get('mesh')}")

    # ---- the run ------------------------------------------------------------

    def run(self, chips: int = 1, rehearse: bool = False) -> bool:
        self.device()
        if self.failures and not rehearse:
            # no chip and no rehearsal sizes asked for: nothing to measure
            return False
        if chips > 1:
            self.mesh(chips)
        else:
            self.drain()
            self.constraints()
            self.identity()
        say(
            f"total: smoke timing wall "
            f"{time.perf_counter() - self._t_start:.1f}s, compile "
            f"{self.compile_s:.1f}s, compile cache hits {self.cache_hits} "
            f"misses {self.cache_misses}"
        )
        if self.failures:
            say(f"{len(self.failures)} failure(s):")
            for f in self.failures:
                say(f"  - {f}")
        return not self.failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py", description=__doc__)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--nodes",
        type=int,
        default=None,
        help=f"node width (default {FULL_NODES}; smaller = a rehearsal)",
    )
    ap.add_argument(
        "--pods",
        type=int,
        default=None,
        help=f"SchedulingBasic backlog (default {FULL_PODS}); the other "
        "phases take half of it",
    )
    ap.add_argument("--seed", type=int, default=0, help="workload seed")
    args = ap.parse_args(argv)
    rehearse = args.nodes is not None or args.pods is not None
    with Smoke(
        args.nodes or FULL_NODES, args.pods or FULL_PODS, args.seed
    ) as smoke:
        ok = smoke.run(chips=args.chips, rehearse=rehearse)
        doc = {"ok": bool(ok), "device": smoke.device_doc()}
    print(json.dumps(doc), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
