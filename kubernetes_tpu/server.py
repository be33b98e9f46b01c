"""Server wrapper: process entrypoint, serving, leader election, debugger.

The operational tier of cmd/kube-scheduler/app/server.go:163-318 rebuilt
around the embeddable Scheduler:

  * ``SchedulerServer`` — owns the scheduling loop thread, an HTTP mux
    serving /healthz, /readyz (handler-sync gated, server.go:202-211),
    /metrics (Prometheus text exposition), /configz, and the
    observability debug endpoints (OBSERVABILITY.md; the catalogue lives
    in ``DEBUG_ENDPOINTS`` and is served as a JSON index at /debug/):
    /debug/trace (start/stop/export span tracing),
    /debug/flightrecorder?pod= (per-pod lifecycle events),
    /debug/explain?pod= (per-node, per-plugin rejection reasons),
    /debug/slo (live SLI snapshot, per-stage latency breakdown,
    last-breach record + black-box trace),
    /debug/plan (counterfactual planners), and
    /debug/kernels (the device telemetry ledger's per-kernel table);
  * ``LeaseElector`` — Lease-based leader election
    (client-go/tools/leaderelection/leaderelection.go:116 semantics:
    LeaseDuration/RenewDeadline/RetryPeriod over a CAS'd lease record);
    only the leader runs scheduling cycles, a lost lease stops them;
  * ``CacheDebugger`` — SIGUSR2 dump of cache + queue and a comparer
    against the informer ground truth (backend/cache/debugger).

The serving loop also owns the garbage collector's schedule
(``util/collector.py``): when it starts leading, the synced cluster state is
frozen out of the collector's walk (``gc.freeze()``, no collection) and the
young generation is sized for a batch; a full collection runs at the loop's
idle point (nothing to decide, active queue and in-flight binds empty), or
past a ceiling where the loop is never idle; ``stop()`` and a lost lease put
the interpreter back as it was.  Embedding ``Scheduler`` without a
``SchedulerServer`` leaves the interpreter's defaults alone.
"""

from __future__ import annotations

import json
import signal
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib.parse import parse_qs, urlparse

from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.util.allocator import HeapWatch
from kubernetes_tpu.util.collector import LoopCollector

# ---------------------------------------------------------------------------
# Debug-endpoint catalogue: the ONE table both surfaces render from —
# GET /debug/ serves it as a JSON index, and the handler's plain-text
# help block is generated from it below (debug_help_text), so the two
# can never drift.
# ---------------------------------------------------------------------------

DEBUG_ENDPOINTS = (
    (
        "/debug/",
        "",
        "this JSON index of the debug endpoints",
    ),
    (
        "/debug/cache",
        "",
        "cache + queue dump with the informer ground-truth comparer (text)",
    ),
    (
        "/debug/trace",
        "?action=start|stop|export|status",
        "span tracer control + Perfetto-loadable export (default: status)",
    ),
    (
        "/debug/flightrecorder",
        "?pod=<uid|name>",
        "per-pod lifecycle breadcrumbs (default: ring stats + tail)",
    ),
    (
        "/debug/explain",
        "?pod=<uid|name>[&whatif_node=<node>][&max_nodes=N]",
        "per-node per-plugin rejection reasons; preemption what-if",
    ),
    (
        "/debug/slo",
        "?action=status|trace",
        "live SLI snapshot + burn rates; last breach's black-box trace",
    ),
    (
        "/debug/plan",
        "?planner=autoscale|deschedule|preempt_cost[&...]",
        "counterfactual planners over batched [K,P,N] snapshot forks "
        "(default: the planner catalogue)",
    ),
    (
        "/debug/kernels",
        "?cost=0|1",
        "device telemetry ledger: per-kernel dispatches, p50/p99 execute, "
        "compiles, est. FLOPs, d2h bytes, HBM, sentinel state",
    ),
    (
        "/debug/pipeline",
        "?pod=<uid|name>",
        "control-plane per-hop lag waterfall for one pod (api_write → "
        "watch_delivery → informer_handler → enqueue → pop → assumed → "
        "bind_start → bound); default: hop summary + staleness sentinel",
    ),
)


def debug_endpoint_index() -> dict:
    """The /debug/ response body."""
    return {
        "endpoints": [
            {"path": p, "params": params, "description": desc}
            for p, params, desc in DEBUG_ENDPOINTS
        ]
    }


def debug_help_text() -> str:
    """The plain-text help block, rendered from DEBUG_ENDPOINTS."""
    width = max(len(p + params) for p, params, _ in DEBUG_ENDPOINTS)
    return "\n".join(
        f"  {(p + params).ljust(width)}   {desc}"
        for p, params, desc in DEBUG_ENDPOINTS
    )


# ---------------------------------------------------------------------------
# Leader election (Lease objects + CAS)
# ---------------------------------------------------------------------------

# LeaseRecord/LeaseStore live in util.leases (shared with the API tier's
# /api/v1/leases resource and the HTTP RemoteLeaseStore); re-exported here
# for the established import path.
from kubernetes_tpu.util.leases import LeaseRecord, LeaseStore  # noqa: E402


class LeaseElector:
    """leaderelection.LeaderElector: acquire → renew loop → on lost, stop.

    tryAcquireOrRenew semantics (leaderelection.go:116): take the lease
    when empty, expired, or already ours; renewals CAS the renew_time.
    Expiry is judged against the LOCAL clock at which this elector last
    OBSERVED the record's resourceVersion change — never against the
    writer's timestamps — so two processes with skewed clocks still elect
    correctly (the reference's observedRecord/observedTime discipline)."""

    def __init__(
        self,
        store: LeaseStore,
        identity: str,
        lease_name: str = "kube-scheduler",
        lease_duration_s: float = 15.0,
        retry_period_s: float = 2.0,
        clock=time.monotonic,
    ):
        self.store = store
        self.identity = identity
        self.lease_name = lease_name
        self.lease_duration_s = lease_duration_s
        self.retry_period_s = retry_period_s
        self.clock = clock
        self._observed_rv = -1
        self._observed_time = 0.0

    def _observe(self, rec: Optional[LeaseRecord]) -> None:
        if rec is not None and rec.resource_version != self._observed_rv:
            self._observed_rv = rec.resource_version
            self._observed_time = self.clock()

    def try_acquire_or_renew(self) -> bool:
        now = self.clock()
        rec = self.store.get(self.lease_name)
        self._observe(rec)
        if rec is None:
            rec = LeaseRecord()
        expired = (
            not rec.holder
            or now >= self._observed_time + rec.lease_duration_s
        )
        if rec.holder != self.identity and not expired:
            return False
        if rec.holder != self.identity:
            rec.holder = self.identity
            rec.acquire_time = now
        rec.renew_time = now
        rec.lease_duration_s = self.lease_duration_s
        ok = self.store.update(self.lease_name, rec)
        if ok:
            # our own write: observe it immediately (the next get() sees
            # the bumped rv; counting renewal freshness from now is exact)
            self._observed_rv = rec.resource_version + 1
            self._observed_time = now
        return ok

    def is_leader(self) -> bool:
        rec = self.store.get(self.lease_name)
        self._observe(rec)
        return (
            rec is not None
            and rec.holder == self.identity
            and self.clock() < self._observed_time + rec.lease_duration_s
        )

    def release(self) -> None:
        rec = self.store.get(self.lease_name)
        if rec is not None and rec.holder == self.identity:
            rec.holder = ""
            self.store.update(self.lease_name, rec)


# ---------------------------------------------------------------------------
# Cache debugger (backend/cache/debugger)
# ---------------------------------------------------------------------------


class CacheDebugger:
    """Dump + compare on demand (SIGUSR2 in the reference,
    debugger.go:37-59)."""

    def __init__(self, scheduler: Scheduler, ground_truth=None):
        self.sched = scheduler
        # informer ground truth: () -> (node_names, {pod_uid: node_name});
        # FakeCluster supplies one, a real client would list the apiserver
        self.ground_truth = ground_truth

    def dump(self) -> str:
        with self.sched._mu:
            lines: List[str] = ["== cache dump =="]
            for cn in self.sched.cache.real_nodes():
                lines.append(
                    f"node {cn.node.name}: pods={sorted(p.name for p in cn.pods.values())} "
                    f"requested_cpu={cn.requested.milli_cpu}m"
                )
            lines.append(
                f"assumed: {sorted(self.sched.cache.assumed)}"
            )
            lines.append("== queue dump ==")
            for q, n in self.sched.queue.stats().items():
                lines.append(f"{q}: {n}")
            return "\n".join(lines)

    def compare(self) -> List[str]:
        """Cache vs informer ground truth (comparer.go): lists what the
        cache has that the API doesn't, and vice versa."""
        if self.ground_truth is None:
            return []
        api_nodes, api_pods = self.ground_truth()
        problems: List[str] = []
        with self.sched._mu:
            cache_nodes = {cn.node.name for cn in self.sched.cache.real_nodes()}
            missing = set(api_nodes) - cache_nodes
            extra = cache_nodes - set(api_nodes)
            if missing:
                problems.append(f"cache is missing nodes: {sorted(missing)}")
            if extra:
                problems.append(f"cache has ghost nodes: {sorted(extra)}")
            cache_pods = {
                uid: ps.pod.node_name
                for uid, ps in self.sched.cache.pod_states.items()
                if uid not in self.sched.cache.assumed
            }
            for uid, node in api_pods.items():
                if uid in cache_pods and cache_pods[uid] != node:
                    problems.append(
                        f"pod {uid}: cache says {cache_pods[uid]}, API says {node}"
                    )
            for uid in set(cache_pods) - set(api_pods):
                problems.append(f"cache has ghost pod {uid}")
        return problems

    def install_signal_handler(self) -> None:
        signal.signal(
            signal.SIGUSR2,
            lambda *_: print(self.dump() + "\n" + "\n".join(self.compare())),
        )


# ---------------------------------------------------------------------------
# HTTP serving + run loop
# ---------------------------------------------------------------------------


class SchedulerServer:
    """The kube-scheduler process body (app/server.go Run): healthz/readyz +
    metrics serving, leader election gate, scheduling loop."""

    def __init__(
        self,
        scheduler: Scheduler,
        elector: Optional[LeaseElector] = None,
        port: int = 0,
        poll_interval_s: float = 0.02,
        ground_truth=None,
    ):
        self.sched = scheduler
        self.elector = elector
        self.poll_interval_s = poll_interval_s
        self.debugger = CacheDebugger(scheduler, ground_truth)
        # the garbage collector's schedule while this loop leads
        self.collector = LoopCollector(scheduler.phases)
        # what the process obtains from the kernel while this loop runs
        self.heap = HeapWatch(scheduler.phases)
        self._stop = threading.Event()
        self._synced = threading.Event()
        self._loop_thread: Optional[threading.Thread] = None
        self._le_thread: Optional[threading.Thread] = None
        self._is_leader = threading.Event()
        self.cycles = 0
        self.loop_errors = 0

        srv = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code: int, body: str, ctype="text/plain"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_json(self, obj, code: int = 200):
                self._send(
                    code, json.dumps(obj), ctype="application/json"
                )

            def do_GET(self):  # noqa: N802 — stdlib handler name
                parsed = urlparse(self.path)
                if parsed.path.startswith("/debug/"):
                    try:
                        self._debug_get(parsed)
                    except Exception as e:  # noqa: BLE001 — debug surface
                        self._send_json({"error": str(e)}, code=500)
                    return
                if self.path == "/healthz":
                    self._send(200, "ok")
                elif self.path == "/readyz":
                    # WaitForHandlersSync gate (server.go:202-211)
                    if srv._synced.is_set():
                        self._send(200, "ok")
                    else:
                        self._send(500, "informers not synced")
                elif self.path == "/metrics":
                    srv.collector.sync_registry(srv.sched.prom)
                    srv.heap.sync_registry(srv.sched.prom)
                    self._send(
                        200,
                        srv.sched.expose_metrics(),
                        ctype="text/plain; version=0.0.4",
                    )
                elif self.path == "/configz":
                    self._send(
                        200,
                        json.dumps(
                            {
                                "batchSize": srv.sched.config.batch_size,
                                "parallelism": srv.sched.config.parallelism,
                                "profiles": [
                                    p.scheduler_name
                                    for p in srv.sched.config.profiles
                                ],
                            }
                        ),
                        ctype="application/json",
                    )
                else:
                    self._send(404, "not found")

            def _debug_get(self, parsed):
                # docstring generated from DEBUG_ENDPOINTS after the
                # class body — one table, both surfaces
                q = parse_qs(parsed.query)
                path = parsed.path
                sched = srv.sched
                if path == "/debug/":
                    # the bare prefix: a JSON index of everything below,
                    # with ?format=text for the generated help block
                    if q.get("format", ["json"])[0] == "text":
                        self._send(
                            200,
                            "debug endpoints:\n" + debug_help_text() + "\n",
                        )
                    else:
                        self._send_json(debug_endpoint_index())
                elif path == "/debug/cache":
                    self._send(
                        200,
                        srv.debugger.dump()
                        + "\n"
                        + "\n".join(srv.debugger.compare()),
                    )
                elif path == "/debug/trace":
                    action = q.get("action", ["status"])[0]
                    tracer = sched.tracer
                    if action == "start":
                        tracer.start()
                        self._send_json(tracer.stats())
                    elif action == "stop":
                        tracer.stop()
                        self._send_json(tracer.stats())
                    elif action == "export":
                        out = tracer.export()
                        # a manual start() overrides an armed black-box
                        # ring; export is the terminal step of the manual
                        # start→stop→export flow, so RE-ARM here — without
                        # this, one manual capture silently disarms the
                        # "always-on" breach-dump guarantee until the next
                        # install_slo
                        slo = getattr(sched, "slo", None)
                        if (
                            slo is not None
                            and slo.config.blackbox
                            and not tracer.enabled
                        ):
                            tracer.blackbox_start(slo.config.blackbox_capacity)
                        self._send_json(out)
                    elif action == "status":
                        self._send_json(tracer.stats())
                    else:
                        self._send_json(
                            {"error": f"unknown action {action!r}"}, code=400
                        )
                elif path == "/debug/flightrecorder":
                    fr = sched.flight
                    ref = q.get("pod", [None])[0]
                    if ref is None:
                        out = fr.stats()
                        out["tail"] = fr.tail(50)
                        self._send_json(out)
                        return
                    from kubernetes_tpu.observability import find_pod

                    pod = find_pod(sched, ref)
                    uid = pod.uid if pod is not None else ref
                    events = fr.events_for(uid)
                    if not events and pod is None:
                        self._send_json(
                            {"error": f"no events for pod {ref!r}"}, code=404
                        )
                        return
                    self._send_json({"pod": uid, "events": events})
                elif path == "/debug/explain":
                    ref = q.get("pod", [None])[0]
                    if ref is None:
                        self._send_json(
                            {"error": "missing ?pod= parameter"}, code=400
                        )
                        return
                    from kubernetes_tpu.observability import (
                        explain_pod,
                        explain_whatif,
                        find_pod,
                    )

                    pod = find_pod(sched, ref)
                    if pod is None:
                        self._send_json(
                            {"error": f"pod {ref!r} not found"}, code=404
                        )
                        return
                    # ?whatif_node=X: preemption what-if — which victims
                    # would free node X for this pod (dry run, read-only)
                    whatif = q.get("whatif_node", [None])[0]
                    if whatif is not None:
                        self._send_json(explain_whatif(sched, pod, whatif))
                        return
                    try:
                        max_nodes = int(q.get("max_nodes", ["500"])[0])
                    except ValueError:
                        self._send_json(
                            {"error": "max_nodes must be an integer"},
                            code=400,
                        )
                        return
                    self._send_json(
                        explain_pod(sched, pod, max_nodes=max_nodes)
                    )
                elif path == "/debug/plan":
                    # the counterfactual planner tier (PLANNER.md): K
                    # what-if snapshot forks per fused device dispatch —
                    # autoscale / deschedule / preemption-cost planning
                    # the reference delegates to satellite projects
                    from kubernetes_tpu.planner import PLANNERS, run_planner

                    name = q.get("planner", ["list"])[0]
                    params = {k: v[0] for k, v in q.items()}
                    out = run_planner(sched, name, params)
                    bad = name != "list" and name not in PLANNERS
                    self._send_json(out, code=400 if bad else 200)
                elif path == "/debug/kernels":
                    # the device telemetry ledger (observability/
                    # kernels.py): per-kernel dispatch/compile/d2h
                    # accounting + live HBM + sentinel state.  ?cost=0
                    # skips the lazy FLOPs estimate (its first request
                    # per shape pays a lowering re-trace; memoized after)
                    led = sched.kernels
                    if not led.enabled:
                        self._send_json({"enabled": False})
                        return
                    want_cost = q.get("cost", ["1"])[0] not in ("0", "false")
                    self._send_json(led.snapshot(cost=want_cost))
                elif path == "/debug/pipeline":
                    # the control-plane pipeline tier (observability/
                    # controlplane.py): per-pod causal chain + hop
                    # waterfall; without ?pod=, the aggregate hop summary
                    # and staleness sentinel state
                    cp = getattr(sched, "controlplane", None)
                    if cp is None:
                        self._send_json({"enabled": False})
                        return
                    ref = q.get("pod", [None])[0]
                    if ref is None:
                        self._send_json(cp.snapshot())
                        return
                    from kubernetes_tpu.observability import find_pod

                    pod = find_pod(sched, ref)
                    uid = pod.uid if pod is not None else ref
                    out = cp.pipeline_for(uid)
                    if out is None:
                        self._send_json(
                            {"error": f"no pipeline chain for pod {ref!r}"},
                            code=404,
                        )
                        return
                    self._send_json(out)
                elif path == "/debug/slo":
                    # the steady-state SLO tier (observability/slo.py):
                    # live SLI snapshot + per-stage breakdown + last-breach
                    # record; ?action=trace serves the last breach's frozen
                    # black-box export when no dump_dir was configured
                    slo = getattr(sched, "slo", None)
                    if slo is None:
                        self._send_json({"enabled": False})
                        return
                    action = q.get("action", ["status"])[0]
                    if action == "status":
                        self._send_json(slo.snapshot())
                    elif action == "trace":
                        trace = slo.last_breach_trace()
                        if trace is None:
                            self._send_json(
                                {"error": "no breach trace captured"},
                                code=404,
                            )
                        else:
                            self._send_json(trace)
                    else:
                        self._send_json(
                            {"error": f"unknown action {action!r}"}, code=400
                        )
                else:
                    self._send_json(
                        {"error": "not found", **debug_endpoint_index()},
                        code=404,
                    )

            def log_message(self, *a):  # quiet
                pass

        # the mux help IS the endpoint table (satellite contract: the
        # JSON index and this text block cannot drift apart)
        Handler._debug_get.__doc__ = (
            "The observability debug mux (OBSERVABILITY.md):\n\n"
            + debug_help_text()
        )
        self.http = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self.http.server_port
        self._http_thread = threading.Thread(
            target=self.http.serve_forever, daemon=True
        )

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self._http_thread.start()
        self._synced.set()  # in-proc informers are synchronous
        if self.elector is not None:
            self._le_thread = threading.Thread(
                target=self._run_election, daemon=True
            )
            self._le_thread.start()
        else:
            # no election: this loop leads from here on
            self.collector.engage()
        self.heap.sample()  # the base growth is counted from
        self._loop_thread = threading.Thread(target=self._run_loop, daemon=True)
        self._loop_thread.start()

    def _run_election(self) -> None:
        """Dedicated renewal loop (the reference's leaderelection goroutine):
        the lease renews every retry period INDEPENDENTLY of scheduling
        cycles, so a long cycle (first jit compile, giant drain) cannot let
        the lease lapse under an active leader; a lost lease clears the
        flag and the scheduling loop stops at its next check."""
        renew_deadline = self.elector.lease_duration_s * (2.0 / 3.0)
        last_success = None
        while not self._stop.is_set():
            try:
                acquired = self.elector.try_acquire_or_renew()
            except Exception:  # noqa: BLE001 — remote store hiccup
                acquired = False
            now = self.elector.clock()
            if acquired:
                last_success = now
                self._is_leader.set()
            elif (
                self._is_leader.is_set()
                and last_success is not None
                and now - last_success < renew_deadline
            ):
                # a held lease survives transient renew failures until the
                # renew DEADLINE (leaderelection.go RenewDeadline) — one
                # dropped request must not stall scheduling while no
                # standby can legally take over anyway
                pass
            else:
                self._is_leader.clear()
            self._stop.wait(self.elector.retry_period_s)

    def _run_loop(self) -> None:
        while not self._stop.is_set():
            if self.elector is not None:
                if not self._is_leader.is_set():
                    self.collector.release()  # the lease is lost, or not won yet
                    self._stop.wait(self.elector.retry_period_s)
                    continue
                self.collector.engage()  # the first iteration as leader
            outs = None
            try:
                outs = self.sched.schedule_pending()
                if outs:
                    self.cycles += 1
            except Exception:  # noqa: BLE001 — loop must survive
                # a persistent failure (bad config/plugin) must be visible:
                # log with traceback and count it on /metrics so the loop
                # never becomes a silent busy-wait
                import logging

                logging.getLogger("kubernetes_tpu.server").exception(
                    "scheduling cycle failed"
                )
                self.loop_errors += 1
                try:
                    self.sched.metrics["errors"] += 1
                except Exception:  # noqa: BLE001
                    pass
            # idle = nothing decided, nobody queued, no bind in flight (while
            # "only binding remains" the loop waits; it is not idle)
            self.collector.poll(
                busy=bool(
                    outs
                    or self.sched.queue._active
                    or self.sched._inflight_binds
                )
            )
            if outs:
                self.heap.sample(due_only=True)  # the rest at stop()
            # the sleep on an empty queue, named on the loop's own thread
            with self.sched.phases.span("loop.idle"):
                self._stop.wait(self.poll_interval_s)

    def stop(self) -> None:
        self._stop.set()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5)
        self.collector.release()
        self.heap.sample()  # what grew since the loop's last iteration
        if self._le_thread is not None:
            # settle the renewal loop BEFORE releasing, or a concurrent
            # renew can defeat the release and strand the lease on this
            # dead process for a full lease_duration
            self._le_thread.join(timeout=5)
        if self.elector is not None:
            self.elector.release()
        self.http.shutdown()

    def is_leading(self) -> bool:
        return self.elector is None or self.elector.is_leader()


def main(argv: Optional[List[str]] = None) -> int:
    """cmd/kube-scheduler entrypoint: --config file → run loop + serving."""
    import argparse

    from kubernetes_tpu.framework.config import load_config
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    ap = argparse.ArgumentParser(prog="kubernetes-tpu-scheduler")
    ap.add_argument("--config", help="KubeSchedulerConfiguration YAML")
    ap.add_argument("--port", type=int, default=10259)
    ap.add_argument(
        "--leader-elect", action="store_true", default=False
    )
    ap.add_argument("--lease-duration", type=float, default=15.0)
    ap.add_argument("--retry-period", type=float, default=2.0)
    ap.add_argument(
        "--api-endpoint",
        help="HTTP list/watch API endpoint (e.g. http://127.0.0.1:8001); "
        "when omitted the process serves an in-proc FakeCluster",
    )
    args = ap.parse_args(argv)

    conf = load_config(args.config) if args.config else None
    # event broadcaster started before the scheduler runs
    # (cmd/kube-scheduler/app/server.go:179)
    from kubernetes_tpu.events import EventBroadcaster

    broadcaster = EventBroadcaster()
    sched = Scheduler(configuration=conf, event_broadcaster=broadcaster)
    ground_truth = None
    elector = None
    if args.api_endpoint:
        # real wire tier: reflector-based list/watch client
        from kubernetes_tpu.client import RemoteClusterSource, RemoteLeaseStore

        source = RemoteClusterSource(args.api_endpoint)
        source.connect(sched)
        source.start()
        source.wait_for_sync()
        if args.leader_elect:
            import os

            elector = LeaseElector(
                RemoteLeaseStore(source.client),
                identity=f"pid-{os.getpid()}",
                lease_duration_s=args.lease_duration,
                retry_period_s=args.retry_period,
            )
    else:
        # in-proc cluster (the FakeCluster source)
        api = FakeCluster()
        api.connect(sched)
        ground_truth = api.ground_truth
        if args.leader_elect:
            elector = LeaseElector(
                api.lease_store,
                identity=f"pid-{id(sched)}",
                lease_duration_s=args.lease_duration,
                retry_period_s=args.retry_period,
            )
    server = SchedulerServer(
        sched, elector=elector, port=args.port, ground_truth=ground_truth
    )
    server.debugger.install_signal_handler()
    server.start()
    print(f"serving on 127.0.0.1:{server.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
