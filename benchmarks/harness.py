"""The served path in ONE process, lifted from ``chip_smoke.py``'s served phase.

``ServedCluster`` stands up what an operator runs around the scheduler: the
API server over HTTP (``FakeCluster`` + ``ApiServer``), the scheduler's
reflectors over the binary codec (``RemoteClusterSource``), the scheduling
loop (``SchedulerServer``) and its asynchronous binding workers.  All of
them are threads of this process, the only one that touches JAX.

From the program it takes the system under test and its counters
(``PhaseAccumulator``, the dispatch ledger, ``hop_summary``).  Everything
that measures is the benchmark's own: the clock around the window, the
acknowledgement times (taken where the scheduler hands a decision to the
wire and the API server answers), the order in which decisions were
committed (a wrapper around this instance's ``schedule_pending``), and the
read-back through LIST.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import workload

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# what Scheduler logs (WARNING+) when the device did not answer
# (chip_smoke.py's _FAULT_WORDS)
FAULT_WORDS = ("abandoned", "failed", "mismatch", "degraded")

POLL_INTERVAL_S = 0.005  # the scheduling loop's idle poll, as chip_smoke.py runs it


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileWatch:
    """Counts XLA compiles (and persistent-cache misses) process-wide via
    ``jax.monitoring``; a window diffs two readings."""

    def __init__(self, jax) -> None:
        self._mon = jax.monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_misses = 0
        self.cache_hits = 0

    def __enter__(self) -> "CompileWatch":
        self._mon.register_event_duration_secs_listener(self._on_duration)
        self._mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self) -> int:
        """Compiles plus persistent-cache misses so far."""
        return self.compiles + self.cache_misses


class GcWatch:
    """Seconds this process stood still in the interpreter's garbage
    collector (``gc.callbacks``; a collection stops every thread).  A full
    collection walks the whole heap that set-up left behind, API server's
    store and harness included."""

    def __init__(self) -> None:
        self.collections = [0, 0, 0]  # per generation
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = 0.0

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.collections[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0

    def reading(self) -> dict:
        return {"collections": list(self.collections), "seconds": list(self.seconds)}


class LogTap(logging.Handler):
    """WARNING+ records of the package's loggers: every abandoned dispatch
    logs its exception text there (chip_smoke.py's _LogTap)."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.records: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}")

    def faults(self) -> List[str]:
        return [r for r in self.records if any(w in r for w in FAULT_WORDS)]


class PhaseTap:
    """Stands where ``PhaseAccumulator.tracer`` expects an
    ``observability.Tracer``: records every accumulated phase interval on
    the host's ``perf_counter`` clock, so a traced run can name what the
    host was doing in each idle gap of the device.  Installed only in the
    traced run."""

    enabled = True

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.spans: List[Tuple[str, float, float]] = []  # name, t0, t1

    def complete_tail(self, name: str, dur_s: float, *a, **kw) -> None:
        t1 = time.perf_counter()
        with self._mu:
            self.spans.append((name, t1 - dur_s, t1))


class ServedCluster:
    def __init__(self, cfg: dict, seed: int, e_cap_pods: int) -> None:
        self.cfg = cfg
        self.e_cap_pods = e_cap_pods
        self.nodes = workload.node_specs(cfg)
        ip = cfg["init_pods"]
        self.init_specs = workload.pod_specs(cfg, ip["template"], ip["count"], "init")
        self.init_nodes = workload.init_placement(cfg, ip["count"], self.nodes, seed)
        # uid -> (node, perf_counter at acknowledgement); a second,
        # different acknowledgement for one uid lands in double_binds
        self.acked: Dict[str, Tuple[str, float]] = {}
        self.double_binds: List[Tuple[str, str, str]] = []
        # (uid, node or None) as schedule_pending returned them: the
        # decisions.  The cross-pod path returns a batch's outcomes grouped,
        # not in queue order, so the ORDER is taken at the queue instead:
        self.order: List[Tuple[str, Optional[str]]] = []
        # uids in the order the scheduling queue popped them (QueueSort
        # order): the serial order the decisions have to be equivalent to
        self.popped: List[str] = []
        self._mu = threading.Lock()
        self._watch: Dict[str, "Countdown"] = {}  # uid -> the countdown waiting for it
        self.log_tap = LogTap()
        self.server = None  # the running SchedulerServer; None = loop held

    # ---- bring-up (chip_smoke.py:456-494) -----------------------------------

    def start(self) -> None:
        from kubernetes_tpu.api import resource as R
        from kubernetes_tpu.api import types as T
        from kubernetes_tpu.client import ApiClient, ApiServer, RemoteClusterSource
        from kubernetes_tpu.events import EventBroadcaster
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.server import SchedulerServer
        from kubernetes_tpu.testing.fake_cluster import FakeCluster

        self.T = T
        logging.getLogger("kubernetes_tpu").addHandler(self.log_tap)
        # the nodes (upstream's untimed createNodes op) go into the store
        # before it serves; the scheduler still learns of them only
        # through LIST over HTTP
        self.api = FakeCluster(pv_controller=False)
        for spec in self.nodes:
            self.api.create_node(workload.build_node(T, R, spec))
        self.apiserver = ApiServer(self.api).start()
        self.endpoint = f"http://127.0.0.1:{self.apiserver.port}"
        self.sched = sched = Scheduler(event_broadcaster=EventBroadcaster())
        sched.event_broadcaster.start_recording_to_sink(self.api.record_event)
        sched.mirror.e_cap_hint = (
            len(self.init_specs) + self.e_cap_pods + sched.config.batch_size + 128
        )
        self.source = RemoteClusterSource(self.endpoint)  # the binary codec
        self.source.connect(sched)
        self._wrap_sinks()
        self._wrap_loop_body()
        self.source.start()
        self._new_server = lambda: SchedulerServer(sched, poll_interval_s=POLL_INTERVAL_S)
        sched.install_controlplane(api_server=self.apiserver, source=self.source)
        if not self.source.wait_for_sync(timeout=120.0):
            raise RuntimeError("informers never synced")
        want = len(self.nodes)
        deadline = time.monotonic() + 120.0
        while len(sched.cache.nodes) < want and time.monotonic() < deadline:
            time.sleep(0.01)
        # the init pods (upstream's untimed createPods op) only now, bound,
        # over WATCH: a bound pod that reaches the scheduler before its node
        # puts that node first in the cache, and node order is what breaks
        # ties between equal nodes
        for spec, node in zip(self.init_specs, self.init_nodes):
            self.api.create_pod(workload.build_pod(T, spec, node_name=node))
            self.api.bindings[workload.uid_of(spec)] = node
        want = len(self.init_specs)
        while len(sched.cache.pod_states) < want and time.monotonic() < deadline:
            time.sleep(0.01)
        if len(sched.cache.pod_states) < want:
            raise RuntimeError("the init pods never reached the scheduler")
        self.client = ApiClient(self.endpoint)

    def _wrap_sinks(self) -> None:
        sched = self.sched
        bind_one, bind_many = sched.binding_sink, sched.binding_sink_many

        def note(uid: str, node: str, t: float) -> None:
            prior = self.acked.get(uid)
            if prior is not None and prior[0] != node:
                self.double_binds.append((uid, prior[0], node))
            elif prior is None:
                self.acked[uid] = (node, t)
                ev = self._watch.get(uid)
                if ev is not None:
                    ev.hit()

        def sink(pod, node):
            bind_one(pod, node)
            t = time.perf_counter()
            with self._mu:
                note(pod.uid, node, t)

        def sink_many(items):
            items = list(items)
            errs = bind_many(items)
            t = time.perf_counter()
            with self._mu:
                for (pod, node), err in zip(items, errs):
                    if err is None:
                        note(pod.uid, node, t)
            return errs

        sched.binding_sink, sched.binding_sink_many = sink, sink_many

    def _wrap_loop_body(self) -> None:
        """Log, from outside the program, every decision
        (``schedule_pending``'s outcomes) and the order in which the queue
        handed the pods to the scheduler (``pop_batch`` and its extension
        ``pop_batch_while``)."""
        queue = self.sched.queue
        for name in ("pop_batch", "pop_batch_while"):
            pop = getattr(queue, name)

            def logged_pop(*a, _pop=pop, **kw):
                out = _pop(*a, **kw)
                if out:
                    with self._mu:
                        self.popped.extend(qp.uid for qp in out)
                return out

            setattr(queue, name, logged_pop)
        orig = self.sched.schedule_pending

        def logged(*a, **kw):
            outs = orig(*a, **kw)
            if outs:
                with self._mu:
                    self.order.extend((o.pod.uid, o.node) for o in outs)
            return outs

        self.sched.schedule_pending = logged

    # ---- offering work -------------------------------------------------------

    def expect(self, uids) -> "Countdown":
        """A countdown that fires once every one of ``uids`` has an
        acknowledged bind."""
        cd = Countdown(len(uids))
        with self._mu:
            for u in uids:
                if u in self.acked:
                    cd.hit()
                else:
                    self._watch[u] = cd
        return cd

    def create_in_store(self, specs: List[dict]) -> None:
        """Create pods in the API server's store, in order; they reach the
        scheduler over WATCH (or a re-LIST where the watch window is
        overrun)."""
        T = self.T
        for spec in specs:
            self.api.create_pod(workload.build_pod(T, spec))

    def delete_from_store(self, specs: List[dict], timeout_s: float = 120.0) -> None:
        """Delete bound pods from the API server's store and wait until the
        scheduler has seen every one of them go (over WATCH); their
        acknowledgements, and every decision and pop logged so far, are
        forgotten with them."""
        uids = [workload.uid_of(s) for s in specs]
        for u in uids:
            self.api.delete_pod(u)
        deadline = time.monotonic() + timeout_s
        states = self.sched.cache.pod_states
        while any(u in states for u in uids):
            if time.monotonic() > deadline:
                raise RuntimeError("the scheduler never saw the warm-up pods go")
            time.sleep(0.02)
        with self._mu:
            for u in uids:
                self.acked.pop(u, None)
            self.order.clear()
            self.popped.clear()

    def wait_queued(self, n: int, timeout_s: float = 120.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while len(self.sched.queue) < n:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.02)
        return True

    def drain_by_loop_body(self, cd: "Countdown", timeout_s: float = 900.0) -> bool:
        """Warm-up: call the loop body from this thread until every pod of
        the countdown is bound and acknowledged."""
        deadline = time.monotonic() + timeout_s
        while not cd.done.is_set() and time.monotonic() < deadline:
            self.sched.schedule_pending()
            self.sched.wait_for_bindings()
        return cd.done.is_set()

    def quiesce(self, idle_share: float = 0.05, quiet_for: int = 5,
                tick_s: float = 0.1, timeout_s: float = 60.0) -> float:
        """Wait until this process has stopped working: the loop is held,
        so whatever still burns CPU is left over from set-up (events of the
        warm-up's binds still being broadcast, watch updates still being
        applied) and would share the interpreter lock with the window.
        Idle = under ``idle_share`` of a core for ``quiet_for`` ticks in a
        row.  Then collect garbage, so every window starts from the same
        collector state.  Returns the seconds it took."""
        t_start = time.perf_counter()
        quiet = 0
        while quiet < quiet_for and time.perf_counter() - t_start < timeout_s:
            c0, w0 = time.process_time(), time.perf_counter()
            time.sleep(tick_s)
            busy = (time.process_time() - c0) / (time.perf_counter() - w0)
            quiet = quiet + 1 if busy < idle_share else 0
        gc.collect()
        return time.perf_counter() - t_start

    def release_loop(self) -> None:
        """Start the scheduling loop (a ``SchedulerServer`` cannot be
        restarted once stopped, so each release is a new one)."""
        self.server = self._new_server()
        self.server.start()

    def hold_loop(self) -> None:
        """Stop the scheduling loop and settle in-flight binding cycles."""
        if self.server is not None:
            self.server.stop()
            self.server = None
        self.sched.wait_for_bindings()

    # ---- read-back and tear-down --------------------------------------------

    def read_back(self) -> Dict[str, str]:
        """uid -> nodeName of every pod, through the served path (LIST)."""
        from kubernetes_tpu.api.codec import decode

        out = {}
        for env in self.client.list("pods")["items"]:
            pod = decode(env)
            out[pod.uid] = pod.node_name
        return out

    def snapshot_acked(self) -> Dict[str, Tuple[str, float]]:
        with self._mu:
            return dict(self.acked)

    def snapshot_order(self) -> Tuple[List[Tuple[str, Optional[str]]], List[str]]:
        with self._mu:
            return list(self.order), list(self.popped)

    def stop(self) -> None:
        if self.server is not None:
            self.server.stop()
        self.source.stop()
        self.apiserver.stop()
        pool = getattr(self.sched, "_bind_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
        logging.getLogger("kubernetes_tpu").removeHandler(self.log_tap)


class Countdown:
    def __init__(self, n: int) -> None:
        self.left = n
        self.done = threading.Event()
        if n <= 0:
            self.done.set()

    def hit(self) -> None:  # called under ServedCluster._mu
        self.left -= 1
        if self.left <= 0:
            self.done.set()
