"""The scheduler: cache + queue + device pipeline + binding, wired.

The batched counterpart of pkg/scheduler/scheduler.go + schedule_one.go:
``Scheduler.schedule_pending()`` pops a whole batch in queue order, brings
the device mirror up to date (incremental, generation-gated), runs ONE
fused gang dispatch (sequential-equivalent — decisions identical to the
reference's one-pod-at-a-time loop), then walks the per-pod results through
assume → reserve → permit → bind exactly like schedulingCycle/bindingCycle
(schedule_one.go:135-340).

API access is abstracted behind ``ClusterSource`` (list/watch events in) and
the handle's ``bind`` (writes out) — a fake in-process implementation lives
in kubernetes_tpu.testing; a real client would speak the same interface.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from kubernetes_tpu import routing
from kubernetes_tpu.analysis import sanitizer
from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.cache import Cache, SnapshotMirror
from kubernetes_tpu.framework import config as cfg
from kubernetes_tpu.framework.interface import (
    ActionType,
    ClusterEvent,
    Code,
    CycleState,
    EventResource,
    Status,
)
from kubernetes_tpu.framework.registry import Registry, default_registry
from kubernetes_tpu.framework.runtime import Framework
from kubernetes_tpu.metrics import annotation
from kubernetes_tpu.oracle.scores import HOSTNAME_LABEL
from kubernetes_tpu.oracle.state import NodeState, OracleState
from kubernetes_tpu.ops import gang
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32
from kubernetes_tpu.queue import SchedulingQueue
from kubernetes_tpu.queue.nominator import Nominator
from kubernetes_tpu.snapshot.interner import PAD
from kubernetes_tpu.snapshot.schema import bucket_cap, pack_pod_batch
from kubernetes_tpu.workloads import gang as wlg

logger = logging.getLogger(__name__)

# Lock-discipline registry read by kubernetes_tpu.analysis (AST-only — the
# analyzer literal-evals this without importing the module).  Fields listed
# under "guards" may only be mutated while holding Scheduler._mu; methods in
# "requires_lock" are entered with the lock already held (the analyzer
# verifies every caller), same contract as the *_under_lock name suffix.
_KTPU_GUARDED = {
    "Scheduler": {
        "lock": "_mu",
        "guards": {
            "cache": "Cache",
            "queue": "SchedulingQueue",
            "mirror": "SnapshotMirror",
            "nominator": "Nominator",
            "_external_mutations": None,
            "_oracle_cache": None,
            "_nonfast_commits": None,
            "metrics": None,
            # PodGroup registry + gang bookkeeping (workloads/gang.py):
            # mutated by informer handlers, the workloads dispatch, and
            # bind-failure unwinds — all under _mu
            "gangs": "GangDirectory",
        },
        "requires_lock": [
            "_view_pod_added",
            "_view_pod_removed",
            "_invalidate_view",
            "_is_confirmation",
            "_repack_mirror",
            "_sync_mirror_external",
            "_wave_tables",
            "_wave_tables_for",
            "_hostnames_unique",
            "_pull_gang_siblings",
        ],
    },
    "Nominator": {
        "external_lock": "Scheduler._mu",
        "readonly": ["entries", "pods_for_node", "nominated_node"],
    },
}

_MISSING = object()  # dict-miss sentinel (cached signature keys can be None)

# One immutable success Status shared by bulk commits: success statuses are
# never mutated anywhere (failure paths REPLACE outcome.status wholesale).
STATUS_SUCCESS = Status.success()


@dataclass
class _BindTask:
    """One pod's buffered binding cycle (the goroutine-per-pod payload)."""

    fwk: object
    state: object
    qp: object
    node_name: str
    waited: bool
    binder_override: object
    outcome: "ScheduleOutcome"
    lean: bool = False
    bid: int = 0  # the batch that produced it
    t_submit: float = 0.0  # perf_counter when _flush_binds handed it over

    def lean_eligible(self) -> bool:
        return self.lean and not self.waited and self.binder_override is None


@dataclass
class _BulkBindTask:
    """A contiguous run of LEAN fast-path binding cycles: one worker
    submit, one sink write (bulk when the API tier installed one), one
    lock acquisition for the whole post-bind bookkeeping tail.  Built only
    by _commit_fast_bulk, whose gate proved every per-pod extension-point
    walk a no-op for these pods."""

    fwk: object
    state: object
    items: list  # [(qp, node_name, outcome)]
    bid: int = 0  # the batch that produced it
    t_submit: float = 0.0  # perf_counter when _flush_binds handed it over


@dataclass
class ScheduleOutcome:
    pod: Pod
    node: Optional[str]
    status: Status
    n_feasible: int = 0
    # plugin name → count of nodes it rejected (Diagnosis.NodeToStatus
    # aggregate, framework/types.go:367)
    diagnosis: Optional[Dict[str, int]] = None
    # metrics context (pod_scheduling_sli/attempts series).  The SLI
    # duration derives from the MONOTONIC pair (a wall/manual-clock jump
    # must not skew it); the queue-clock stamp stays for display/ordering.
    pod_attempts: int = 1
    first_enqueue_time: Optional[float] = None
    first_enqueue_mono: Optional[float] = None


# FitError reason strings keyed by diagnosis kernel (types.go:420-465 /
# the per-plugin ErrReason constants).
_DIAG_REASONS = {
    "NodeUnschedulable": "node(s) were unschedulable",
    "NodeName": "node(s) didn't match the requested node name",
    "TaintToleration": "node(s) had untolerated taints",
    "NodeAffinity": "node(s) didn't match Pod's node affinity/selector",
    "NodePorts": "node(s) didn't have free ports for the requested pod ports",
    "HostFilters": "node(s) were rejected by host filter plugins",
    "NodeResourcesFit": "node(s) had insufficient resources",
    "PodTopologySpread": "node(s) didn't match pod topology spread constraints",
    "InterPodAffinity": "node(s) didn't satisfy inter-pod affinity/anti-affinity rules",
}


def fit_error_message(num_nodes: int, diagnosis: Dict[str, int]) -> str:
    """FitError.Error() shape: '0/N nodes are available: <reasons>.'"""
    if not diagnosis:
        return f"0/{num_nodes} nodes are available"
    parts = [
        f"{c} {_DIAG_REASONS.get(k, k)}"
        for k, c in sorted(diagnosis.items(), key=lambda kv: -kv[1])
    ]
    return f"0/{num_nodes} nodes are available: " + ", ".join(parts)


class Handle:
    """framework.Handle analogue — what plugins see of the scheduler."""

    def __init__(self, scheduler: "Scheduler"):
        self._s = scheduler

    def bind(self, pod: Pod, node_name: str) -> None:
        self._s.binding_sink(pod, node_name)

    # -- storage listers / assume caches (scheduler.go:298-302) -------------

    @property
    def pv_cache(self):
        return self._s.pv_cache

    @property
    def pvc_cache(self):
        return self._s.pvc_cache

    @property
    def claim_cache(self):
        return self._s.claim_cache

    def get_storage_class(self, name: str):
        return self._s.storage_classes.get(name)

    def get_csinode(self, name: str):
        return self._s.csinodes.get(name)

    def get_csi_driver(self, name: str):
        return self._s.csidrivers.get(name)

    def list_capacities(self):
        return list(self._s.capacities.values())

    def list_resource_slices(self):
        return list(self._s.resource_slices.values())

    def get_device_class(self, name: str):
        return self._s.device_classes.get(name)

    def write_pv(self, pv) -> None:
        self._s.pv_writer(pv)

    def write_pvc(self, pvc) -> None:
        self._s.pvc_writer(pvc)

    def write_claim(self, claim) -> None:
        self._s.claim_writer(claim)

    def oracle_state(self) -> OracleState:
        return self._s.oracle_view()

    @property
    def nominator(self) -> Nominator:
        return self._s.nominator

    def delete_pod(self, pod: Pod) -> None:
        """Victim eviction — the preemption API write (preemption.go:380)."""
        self._s.pod_deleter(pod)

    def list_pdbs(self):
        return self._s.pdb_lister()

    def framework_for(self, pod: Pod):
        return self._s.profiles.get(pod.scheduler_name)


    def list_extenders(self):
        return list(self._s.extenders)

    @property
    def prom(self):
        return getattr(self._s, "prom", None)

    def get_waiting_pod(self, uid: str):
        for fwk in self._s.profiles.values():
            wp = fwk.waiting_pods.get(uid)
            if wp is not None:
                return wp
        return None

    def activate(self, pods) -> None:
        with self._s._mu:
            self._s.queue.activate(pods)

    def recorder_for(self, pod: Pod):
        """The profile's event recorder (framework.Handle EventRecorder)."""
        from kubernetes_tpu.events import NullRecorder

        return self._s.recorders.get(pod.scheduler_name) or NullRecorder()


class Scheduler:
    def __init__(
        self,
        configuration: Optional[cfg.SchedulerConfiguration] = None,
        registry: Optional[Registry] = None,
        binding_sink=None,
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        clock=time.monotonic,
        extenders=None,
        event_broadcaster=None,
        profile_dir: Optional[str] = None,
    ):
        self.config = configuration or cfg.SchedulerConfiguration()
        self.config.validate()
        from kubernetes_tpu.extender import build_extenders

        # HTTP extenders from config + injected in-proc extenders (the
        # fake-extender test pattern, testing/framework/fake_extender.go)
        self.extenders = build_extenders(self.config.extenders) + list(
            extenders or []
        )
        self.binding_sink = binding_sink or (lambda pod, node: None)
        # optional BULK sink ([(pod, node)] → per-item error or None); the
        # API tier installs it so a chunk's bindings ride one write
        self.binding_sink_many = None
        self.pod_deleter = lambda pod: None  # victim eviction sink
        self.pdb_lister = lambda: []
        self.status_patcher = lambda pod: None  # pod status writes (nomination)
        self.namespace_labels = namespace_labels or {}
        self.clock = clock

        self.cache = Cache()
        self.mirror = SnapshotMirror()
        from kubernetes_tpu.cache.device_mirror import DeviceClusterCache

        # Mesh-partitioned dispatch (MULTICHIP.md): resolve the
        # ('pods','nodes') mesh once per scheduler.  meshDispatch None =
        # AUTO — partition whenever the backend exposes >1 device; the
        # admission engine's decisions are bit-identical either way
        # (multichip_vs_singlechip paritycheck), the mesh only changes
        # where the flops run.
        from kubernetes_tpu.parallel import mesh as pmesh

        mesh_on = self.config.mesh_dispatch
        if mesh_on is None:
            mesh_on = pmesh.auto_enabled()
        self.mesh = (
            pmesh.make_mesh(pods_axis=self.config.mesh_pods_axis)
            if mesh_on
            else None
        )
        if self.mesh is not None:
            # every node pack must split evenly over the nodes axis
            # (cluster_shardings asserts; pack_nodes pads)
            self.mirror.node_pad_multiple = self.mesh.shape["nodes"]

        self._dc_cache = DeviceClusterCache(mesh=self.mesh)
        self._p_cap_max = 1  # sticky batch bucket: avoids per-size recompiles
        # sticky (spread, inter-pod, port) distinct-term buckets of the wave's
        # tables, for the same reason (wave.wave_tables' t_floor)
        self._t_cap_max = (1, 1, 1)
        if self.mesh is not None:
            # pod buckets must split evenly over the pods axis — seed the
            # sticky bucket so bucket_cap(n, 1) growth stays a multiple
            # (power-of-two buckets ≥ a power-of-two axis always divide;
            # non-power-of-two axes ride pad_to_multiple)
            self._p_cap_max = pmesh.pad_to_multiple(
                bucket_cap(self.mesh.shape["pods"], 1),
                self.mesh.shape["pods"],
            )
        self.nominator = Nominator()
        # Async binding pipeline (schedule_one.go:117-129): the scheduling
        # loop stops at assume+reserve+permit; wait/prebind/bind/postbind run
        # on worker threads against the assumed cache state, overlapping the
        # next batch's device dispatch.  self._mu is the cache.mu analogue —
        # every cache/queue mutation (informer handlers, commits, unwinds)
        # holds it; the device dispatch and bind RTTs run outside it.
        self._mu = threading.RLock()
        # KTPU_SANITIZE=1: lock-ownership probes at the annotated mutation
        # sites + the post-drain mirror-consistency check.  Captured once
        # per scheduler so the per-POD commit probe is a plain attribute
        # branch, not a function call, when the mode is off.
        self._sanitize = sanitizer.enabled()
        if self._sanitize:
            # the cache carries a backref to the guarding lock so its own
            # assert_owned works without knowing about the scheduler
            self.cache._ktpu_lock = self._mu
        self._bind_pool: Optional[ThreadPoolExecutor] = None
        self._inflight_binds: List = []
        self._bind_buffer: List = []
        self._bulk_bind_buffer: List = []  # _BulkBindTask runs (fast path)
        # ----- the routing state the offers share; written on the loop
        # thread, under the lock named
        # the chained cluster, None = restart from the mirror: _dispatch_chained (_mu); dropped by any commit outside it
        self._chain = None
        # the fast lineage's committer + device state: _fast_dispatch builds it; _reform_mesh resets its device copy (_mu)
        self._fastdev = None
        # the _fast_key _fastdev was built under, by the mirror sync it read: _fast_dispatch, with the holder
        self._fc_key = None
        # (_external_mutations, _nonfast_commits) at the mirror's last repack: _repack_mirror (_mu)
        self._mirror_sync = None
        # commits the fast committer did not see (scan, wave, one-pod): _commit, _commit_fast_bulk (_mu)
        self._nonfast_commits = 0
        # the fast gate's last verdict and asking: _fast_gate_ok + the extension's predicate; booked by _book_route
        self._gate = SimpleNamespace(refused=None, asked=0)

        # storage/DRA object views: assume caches for the objects plugins
        # optimistically mutate (PV/PVC/ResourceClaim, scheduler.go:298-302),
        # plain lister maps for the rest
        from kubernetes_tpu.util.assumecache import AssumeCache

        self.pv_cache = AssumeCache("persistent volumes")
        self.pvc_cache = AssumeCache("persistent volume claims")
        self.claim_cache = AssumeCache("resource claims")
        self.storage_classes: Dict[str, object] = {}
        self.csinodes: Dict[str, object] = {}
        self.csidrivers: Dict[str, object] = {}
        self.capacities: Dict[str, object] = {}
        self.resource_slices: Dict[str, object] = {}
        self.device_classes: Dict[str, object] = {}
        # gang/coscheduling tier: PodGroup registry + quorum bookkeeping
        # (workloads/gang.py; fed by the POD_GROUP informer or directly)
        self.gangs = wlg.GangDirectory(clock=clock)
        self.pv_writer = lambda pv: None
        self.pvc_writer = lambda pvc: None
        self.claim_writer = lambda claim: None

        # Event recorders, one per profile (profile.go:86) — NullRecorder
        # when no broadcaster is wired (bare unit-test Schedulers).
        from kubernetes_tpu.events import NullRecorder

        self.event_broadcaster = event_broadcaster
        self.recorders: Dict[str, object] = {}
        for p in self.config.profiles:
            self.recorders[p.scheduler_name] = (
                event_broadcaster.new_recorder(p.scheduler_name)
                if event_broadcaster is not None
                else NullRecorder()
            )

        handle = Handle(self)
        reg = registry or default_registry()
        self.profiles: Dict[str, Framework] = {
            p.scheduler_name: Framework(
                p, reg, handle, feature_gates=self.config.feature_gates
            )
            for p in self.config.profiles
        }

        # queueing hints: union over profiles (eventhandlers.go:431)
        hints: Dict[str, list] = {}
        for fwk in self.profiles.values():
            for name, evs in fwk.events_to_register().items():
                hints.setdefault(name, []).extend(evs)
        # gang barrier rejections ("waiting for members" / rollback / quorum
        # timeout) requeue on PodGroup events — the workloads dispatch fires
        # a synthetic one when a missing member finally arrives (the
        # coscheduling plugin's Pod-Add EventsToRegister analogue)
        from kubernetes_tpu.framework.interface import ClusterEventWithHint

        hints.setdefault("Coscheduling", []).append(
            ClusterEventWithHint(
                ClusterEvent(
                    EventResource.POD_GROUP,
                    ActionType.ADD | ActionType.UPDATE,
                )
            )
        )

        def pre_enqueue(pod: Pod):
            # PreEnqueue runs under the pod's OWN profile
            # (schedule_one.go:376 frameworkForPod).
            fwk = self.profiles.get(pod.scheduler_name)
            return fwk.run_pre_enqueue(pod) if fwk is not None else None

        # One queue serves all profiles, ordered by the QueueSort of the
        # first profile — the reference requires every profile to configure
        # the SAME QueueSort (apis/config/validation) and builds the activeQ
        # on its Less (scheduler.go:340).
        qs_names = {
            (fwk.queue_sort.name if fwk.queue_sort else None)
            for fwk in self.profiles.values()
        }
        if len(qs_names) > 1:
            raise ValueError(
                f"all profiles must use the same QueueSort plugin, got {qs_names}"
            )
        first = self.profiles[self.config.profiles[0].scheduler_name]
        less_fn = key_fn = None
        if first.queue_sort is not None:
            qs = first.queue_sort
            less_fn = lambda a, b: qs.less(a, b)  # noqa: E731
            # QueueSort plugins exposing a tuple sort_key consistent with
            # less() give the activeQ C-speed heap comparisons.  Only honor
            # sort_key when it is defined at (or below) the class that
            # defines less — a subclass overriding less() alone must not
            # inherit the base's now-inconsistent key.
            qs_cls = type(qs)
            def_sort = next(
                (c for c in qs_cls.__mro__ if "sort_key" in c.__dict__), None
            )
            def_less = next(
                (c for c in qs_cls.__mro__ if "less" in c.__dict__), None
            )
            if (
                def_sort is not None
                and def_less is not None
                and issubclass(def_sort, def_less)
            ):
                key_fn = qs.sort_key

        self.queue = SchedulingQueue(
            less_fn=less_fn,
            queueing_hints=hints,
            pre_enqueue_check=pre_enqueue,
            initial_backoff_s=self.config.pod_initial_backoff_seconds,
            max_backoff_s=self.config.pod_max_backoff_seconds,
            clock=clock,
            key_fn=key_fn,
        )
        from kubernetes_tpu.metrics import PhaseAccumulator, SchedulerMetrics

        self.prom = SchedulerMetrics()
        if self._sanitize:
            sanitizer.register_counter(self.prom.sanitizer_violations)
            # retrace hook: post-warmup compilation-cache misses land in
            # scheduler_tpu_jit_recompiles_total{fn=} once a caller marks
            # the warm watermark (sanitizer.mark_jit_warm)
            sanitizer.register_recompile_counter(self.prom.jit_recompiles)
            sanitizer.install_retrace_hook()
            # eval_shape cross-check failures (run once per process at
            # the first sanitized drain) land in
            # scheduler_tpu_shape_check_failures_total{fn=}
            sanitizer.register_shape_counter(self.prom.shape_check_failures)
        # Per-phase hot-loop attribution (queue_pop/pack/h2d/device/d2h/
        # commit/bind), the scheduler_perf-style breakdown.  Feeds the
        # phase_duration histogram too.
        self.phases = PhaseAccumulator(hist=self.prom.phase_duration)
        # Observability layer (observability/): span tracer (off until
        # /debug/trace?action=start — a disabled tracer is one attribute
        # read per site, zero device-path cost) + per-pod flight recorder
        # (bounded ring, on by default).  The phase accumulator doubles as
        # the tracer's phase-span feed; the queue records its own
        # enqueue/pop/requeue breadcrumbs.
        from kubernetes_tpu.observability import FlightRecorder, Tracer

        self.tracer = Tracer()
        self.flight = FlightRecorder()
        self.phases.tracer = self.tracer
        self.queue.flight = self.flight
        # steady-state SLO tier (observability/slo.py) — None until
        # install_slo wires it; /debug/slo serves {"enabled": false} then
        self.slo = None
        # control-plane pipeline tier (observability/controlplane.py) —
        # None until install_controlplane; every producer site below is
        # one attribute read + None check when off
        self.controlplane = None
        # device telemetry ledger (observability/kernels.py): per-kernel
        # dispatch/compile/d2h accounting over every registered jit root,
        # plus the execute-time regression sentinel (breaches reuse the
        # SLO tier's black-box freeze→dump).  The root wrappers are
        # process-global; dispatches route to the ACTIVE ledger, d2h
        # attribution records into THIS scheduler's ledger exactly.
        from kubernetes_tpu.observability import kernels as kernels_mod

        self_ref = weakref.ref(self)

        def _slo_of():
            s = self_ref()
            return s.slo if s is not None else None

        def _bid_of():
            s = self_ref()
            return s._bid if s is not None else 0

        self.kernels = kernels_mod.DispatchLedger(
            prom=self.prom,
            tracer=self.tracer,
            slo_getter=_slo_of,
            bid_getter=_bid_of,
        )
        if self.config.kernel_ledger:
            kernels_mod.install()
            kernels_mod.activate(self.kernels)
        else:
            self.kernels.enabled = False
        self._batch_seq = 0  # trace batch ids (scheduling-loop thread only)
        # the batch id the loop thread's spans carry: the id the NEXT
        # _trace_dispatch will stamp while a batch is prepared, the
        # record's own while it is harvested (scheduling-loop thread only)
        self._bid = 1
        # jax.profiler trace hook (SURVEY §5; the --profiling/pprof analog,
        # apis/config/types.go:60): when set, schedule_pending wraps each
        # drain in jax.profiler.trace(profile_dir).
        import os as _os

        self.profile_dir = profile_dir or _os.environ.get("KTPU_PROFILE_DIR")
        self._profiling = False  # reentrancy guard (nested drains)
        self.queue.incoming_counter = self.prom.queue_incoming_pods
        self._dirty_pending = False
        self._oracle_cache: Optional[OracleState] = None
        # bumped on every EXTERNAL node-state mutation (informer events,
        # forgets) — NOT on this scheduler's own commits, which the fast
        # committer already tracks itself
        self._external_mutations = 0
        self.metrics: Dict[str, float] = {
            "schedule_attempts": 0,
            "scheduled": 0,
            "unschedulable": 0,
            "errors": 0,
            "fast_batches": 0,
            "scan_batches": 0,
            "wave_batches": 0,
            "wave_pods": 0,
            "wave_admitted": 0,
            "resident_batches": 0,
            "resident_pods": 0,
            "resident_rounds": 0,
            "workload_batches": 0,
            "workload_spec_admitted": 0,
            "gang_admitted": 0,
            "gang_rolled_back": 0,
            "dra_pods": 0,
            "dra_claims_allocated": 0,
        }

    # ----- event handlers (eventhandlers.go:345-428) ------------------------

    def on_node_add(self, node: Node) -> None:
        with self._mu:
            cp = self.controlplane
            if cp is not None and cp.enabled:
                cp.note_applied()
            self._invalidate_view()
            self._external_mutations += 1
            self.cache.add_node(node)
            self.queue.move_all_on_event(
                ClusterEvent(EventResource.NODE, ActionType.ADD), None, node
            )

    def on_node_update(self, old: Node, new: Node) -> None:
      with self._mu:
        cp = self.controlplane
        if cp is not None and cp.enabled:
            cp.note_applied()
        import copy as _copy

        probe = _copy.copy(old)
        probe.ready = new.ready
        probe.last_heartbeat = new.last_heartbeat
        if probe == new:
            # heartbeat-only update (Ready condition / lastHeartbeatTime):
            # nothing the snapshot or queue reads moved — refresh the cache
            # object without invalidating the device pipeline, or 5000
            # kubelets heartbeating would repack the mirror continuously.
            # (Full-equality probe, not a field allowlist: a change to ANY
            # other Node field — present or future — takes the safe path.)
            cn = self.cache.nodes.get(new.name)
            if cn is not None and cn.node is not None:
                cn.node = new
                return
        self._invalidate_view()
        self._external_mutations += 1
        self.cache.update_node(new)
        action = ActionType(0)
        if old.labels != new.labels:
            action |= ActionType.UPDATE_NODE_LABEL
        if old.taints != new.taints or old.unschedulable != new.unschedulable:
            action |= ActionType.UPDATE_NODE_TAINT
        if (
            old.allocatable.milli_cpu != new.allocatable.milli_cpu
            or old.allocatable.memory != new.allocatable.memory
            or old.allocatable.scalars != new.allocatable.scalars
        ):
            action |= ActionType.UPDATE_NODE_ALLOCATABLE
        if action:
            self.queue.move_all_on_event(
                ClusterEvent(EventResource.NODE, action), old, new
            )

    def on_node_delete(self, node: Node) -> None:
      with self._mu:
        cp = self.controlplane
        if cp is not None and cp.enabled:
            cp.note_applied()
        self._invalidate_view()
        self._external_mutations += 1
        self.cache.remove_node(node.name)
        self.queue.move_all_on_event(
            ClusterEvent(EventResource.NODE, ActionType.DELETE), node, None
        )

    def _is_confirmation(self, pod: Pod) -> bool:
        """True when the event is the informer CONFIRMING our assumed pod
        unchanged — same node AND same labels AND same deletionTimestamp
        (the _adopt_equivalent field set): only then may the chain epoch
        and device mirror treat it as a no-op (cache.go:484)."""
        if pod.uid not in self.cache.assumed:
            return False
        ps = self.cache.pod_states.get(pod.uid)
        return (
            ps is not None
            and ps.pod.node_name == pod.node_name
            and ps.pod.labels == pod.labels
            and ps.pod.deletion_timestamp == pod.deletion_timestamp
            # requests too: in-place pod resize can mutate the spec while
            # node/labels stay equal — the view must be repatched then
            and ps.pod.compute_requests() == pod.compute_requests()
        )

    def on_pod_add(self, pod: Pod) -> None:
      with self._mu:
        cp = self.controlplane
        if cp is not None and cp.enabled:
            cp.note_applied()
            if not pod.node_name:
                # the informer_handler hop: stamped ahead of queue.add so
                # the chain orders informer_handler < enqueue
                cp.note_pod_handled(pod.uid)
        if pod.node_name:
            self.gangs.note_placed(pod)
            # Confirmation of OUR assumed pod on the same node changes no
            # capacity state (the assume already counted it) — don't treat
            # it as an external mutation (cache.go:484 reconciliation).
            ps = self.cache.pod_states.get(pod.uid)
            confirmed = self._is_confirmation(pod)
            if not confirmed:
                self._external_mutations += 1
                if ps is None:
                    self._view_pod_added(pod)
                elif ps.pod.node_name == pod.node_name:
                    self._view_pod_removed(ps.pod)
                    self._view_pod_added(pod)
                else:
                    self._invalidate_view()
            self.cache.add_pod(pod)
            self.queue.move_all_on_event(
                ClusterEvent(EventResource.ASSIGNED_POD, ActionType.ADD),
                None,
                pod,
            )
        elif self._responsible_for(pod):
            self.queue.add(pod)
            # a new member can complete a waiting gang's quorum — kick its
            # siblings out of the unschedulable pool via the group event
            key = wlg.group_key_of(pod)
            if key is not None:
                pg = self.gangs.get(key)
                if pg is not None:
                    self.queue.move_all_on_event(
                        ClusterEvent(
                            EventResource.POD_GROUP, ActionType.UPDATE
                        ),
                        pg,
                        pg,
                    )

    def on_pod_update(self, old: Pod, new: Pod) -> None:
      with self._mu:
        cp = self.controlplane
        if cp is not None and cp.enabled:
            cp.note_applied()
        if new.node_name:
            self.gangs.note_placed(new)
            ps = self.cache.pod_states.get(new.uid)
            if (
                ps is not None
                and ps.pod.node_name == new.node_name
                # an ASSUMED pod's echo is the binding CONFIRMATION — it
                # must take the full path (assumed → added transition)
                and new.uid not in self.cache.assumed
            ):
                import copy as _copy

                probe = _copy.copy(old)
                probe.phase = new.phase
                probe.start_time = new.start_time
                probe.node_name = new.node_name
                if probe == new:
                    # STATUS-only update of a pod we already account on
                    # that node (the kubelet's phase=Running report):
                    # nothing packed in the snapshot reads phase/startTime
                    # — swap the stored object without invalidating the
                    # device pipeline, or every kubelet status report
                    # would force a mirror repack mid-drain
                    cn = self.cache.nodes.get(new.node_name)
                    if cn is not None and new.uid in cn.pods:
                        cn.pods[new.uid] = new
                        ps.pod = new
                        return
            confirmed = (
                self._is_confirmation(new) and old.labels == new.labels
            )
            if not confirmed:
                self._external_mutations += 1
                if ps is not None and ps.pod.node_name == new.node_name:
                    self._view_pod_removed(ps.pod)
                    self._view_pod_added(new)
                elif ps is None and not old.node_name:
                    self._view_pod_added(new)
                else:
                    self._invalidate_view()
            if old.node_name:
                self.cache.update_pod(old, new)
            else:
                self.cache.add_pod(new)
                # the pod was assigned by SOMEONE ELSE (another scheduler —
                # the HA standby case) while still sitting in our queue:
                # the reference's unassigned-pod informer sees this
                # transition as a delete from the scheduling queue
                # (eventhandlers.go assignedPod split) — without it the
                # standby would later pop and re-schedule a bound pod
                self.queue.delete(new)
            action = ActionType(0)
            if old.labels != new.labels:
                action |= ActionType.UPDATE_POD_LABEL
            if action:
                self.queue.move_all_on_event(
                    ClusterEvent(EventResource.ASSIGNED_POD, action), old, new
                )
        else:
            self.queue.update(old, new)

    def on_pod_delete(self, pod: Pod) -> None:
      with self._mu:
        cp = self.controlplane
        if cp is not None and cp.enabled:
            cp.note_applied()
        self.gangs.note_removed(pod)
        ps = self.cache.pod_states.get(pod.uid)
        if pod.node_name or ps is not None:
            # ``ps`` without a node name on the event: a pod this scheduler
            # ASSUMED whose deletion arrives in its last-known UNASSIGNED
            # state — the reflector re-LISTed past a watch window that
            # held both the bind's update and the delete (a 100,000-pod
            # drain deleted at once overruns it), and a re-LIST reports a
            # vanished object as it last saw it.  The pod is gone all the
            # same: left in the cache it holds its node's resources for
            # good (assumed pods do not expire)
            self._external_mutations += 1
            self._view_pod_removed(
                ps.pod if ps is not None else pod,
                ps.pod.node_name if ps is not None else None,
            )
            self.cache.remove_pod(pod)
            self.queue.move_all_on_event(
                ClusterEvent(EventResource.ASSIGNED_POD, ActionType.DELETE),
                pod if pod.node_name else ps.pod,  # the hints read its node
                None,
            )
        if not pod.node_name:
            self.queue.delete(pod)
        self.nominator.delete(pod)

    def _responsible_for(self, pod: Pod) -> bool:
        return pod.scheduler_name in self.profiles

    def storage_handlers(self, resource: EventResource):
        """(add, update, delete) informer handlers for a storage/DRA
        resource kind — feed the right cache, then requeue through the
        queueing-hint machinery (the dynamic per-GVK handlers of
        eventhandlers.go:431-602)."""
        assume_caches = {
            EventResource.PV: self.pv_cache,
            EventResource.PVC: self.pvc_cache,
            EventResource.RESOURCE_CLAIM: self.claim_cache,
        }
        lister_maps = {
            EventResource.STORAGE_CLASS: self.storage_classes,
            EventResource.CSI_NODE: self.csinodes,
            EventResource.CSI_DRIVER: self.csidrivers,
            EventResource.CSI_STORAGE_CAPACITY: self.capacities,
            EventResource.RESOURCE_SLICE: self.resource_slices,
            EventResource.DEVICE_CLASS: self.device_classes,
        }
        cache = assume_caches.get(resource)
        lister = lister_maps.get(resource)

        is_pod_group = resource == EventResource.POD_GROUP

        def on_add(obj):
            with self._mu:
                if cache is not None:
                    cache.on_add(obj)
                if lister is not None:
                    lister[obj.key] = obj
                if is_pod_group:
                    self.gangs.upsert(obj)
                self.queue.move_all_on_event(
                    ClusterEvent(resource, ActionType.ADD), None, obj
                )

        def on_update(old, new):
            with self._mu:
                if cache is not None:
                    cache.on_update(old, new)
                if lister is not None:
                    lister[new.key] = new
                if is_pod_group:
                    self.gangs.upsert(new)
                self.queue.move_all_on_event(
                    ClusterEvent(resource, ActionType.UPDATE), old, new
                )

        def on_delete(obj):
            with self._mu:
                if cache is not None:
                    cache.on_delete(obj)
                if lister is not None:
                    lister.pop(obj.key, None)
                if is_pod_group:
                    self.gangs.delete(obj.key)
                self.queue.move_all_on_event(
                    ClusterEvent(resource, ActionType.DELETE), obj, None
                )

        return on_add, on_update, on_delete

    # ----- views ------------------------------------------------------------

    def _invalidate_view(self) -> None:
        self._oracle_cache = None

    # Incremental view maintenance: pod-level cache mutations patch the
    # cached OracleState in place instead of discarding it — a full rebuild
    # is O(all pods) and preemption storms mutate once per eviction.
    # Node-level events still invalidate.  Any surprise (unknown node, uid
    # miss) falls back to invalidation, so correctness never depends on
    # these paths.

    def _view_pod_added(self, pod: Pod) -> None:
        st = self._oracle_cache
        if st is None:
            return
        ns = st.nodes.get(pod.node_name)
        if ns is None:
            self._oracle_cache = None
            return
        ns.add_pod(pod)

    def _view_pod_removed(self, pod: Pod, node_name: Optional[str] = None) -> None:
        st = self._oracle_cache
        if st is None:
            return
        ns = st.nodes.get(node_name or pod.node_name)
        if ns is None or not ns.remove_pod(pod):
            self._oracle_cache = None

    def oracle_view(self) -> OracleState:
        """Host-object view of the cache for host-backed plugins/oracle.
        Cached until any cache mutation (informer event, assume/forget) —
        a batch's PostFilter calls share one build."""
        with self._mu:
            if self._oracle_cache is None:
                st = OracleState(namespace_labels=self.namespace_labels)
                for cn in self.cache.real_nodes():
                    ns = NodeState(node=cn.node)
                    for p in cn.pods.values():
                        ns.add_pod(p)
                    st.nodes[cn.node.name] = ns
                self._oracle_cache = st
            return self._oracle_cache

    # ----- the scheduling loop ---------------------------------------------

    def schedule_pending(self, max_batches: Optional[int] = None) -> List[ScheduleOutcome]:
        """Drain the active queue in gang batches; returns all outcomes.

        With ``profile_dir`` set (ctor arg or KTPU_PROFILE_DIR), the whole
        drain runs under ``jax.profiler.trace`` — one xplane artifact per
        drain, the device-dispatch answer to scheduler_perf's -cpuprofile.
        """
        if self.profile_dir and not self._profiling:
            import jax.profiler as _jprof

            self._profiling = True
            try:
                with _jprof.trace(self.profile_dir):
                    return self._schedule_pending_impl(max_batches)
            finally:
                self._profiling = False
        return self._schedule_pending_impl(max_batches)

    def _schedule_pending_impl(
        self, max_batches: Optional[int] = None
    ) -> List[ScheduleOutcome]:
        outcomes: List[ScheduleOutcome] = []
        batches = 0
        tr = self.tracer
        # None (not 0.0) when tracing was off at drain start: a trace
        # STARTED mid-drain must not produce a span with a garbage origin
        t_drain = tr.now() if tr.enabled else None
        # Pre-size the placed-pod tensor axes for the whole drain: every
        # distinct shape costs an XLA recompile of the gang pipeline.  One
        # extra batch of margin covers the chained append's bucket-stride
        # padding on the final partial batch.
        with self._mu:
            self.mirror.e_cap_hint = max(
                self.mirror.e_cap_hint,
                len(self.cache.pod_states)
                + len(self.queue)
                + self.config.batch_size,
            )
        from collections import deque

        pending: deque = deque()  # pipelined batches awaiting result harvest

        def flush(keep: int = 0) -> None:
            while len(pending) > keep:
                rec = pending.popleft()
                self._bid = rec.get("bid", self._bid)
                if rec.get("kind") == "fast":
                    outcomes.extend(self._finish_fast(rec))
                else:
                    outcomes.extend(self._finish_chained(rec))
            self._bid = self._batch_seq + 1

        while True:
            self._bid = self._batch_seq + 1
            # the iteration's enclosing span: annotation only — booked as a
            # phase it would cover every other phase of the loop
            ann_batch = annotation("batch", bid=self._bid).begin()
            sp_pop = self._span("queue_pop").begin()
            sp_lock = self._span("queue_pop.lock_wait").begin()
            with self._mu:
                sp_lock.end()
                batch = self.queue.pop_batch(self.config.batch_size)
                if batch and self.config.gang_dispatch:
                    # gang sibling-pull: a gang split across pop batches
                    # previously converged by waiting-retry; pull its
                    # ready members into THIS batch so quorum is judged
                    # once (PR 10 remainder; cheap for gang-free batches)
                    batch.extend(self._pull_gang_siblings(batch))
            sp_pop.end()
            if not batch:
                ann_batch.end()
                break
            # Segregate by profile (schedule_one.go:376-382): each group
            # runs ONE gang dispatch under its own framework's plugin set.
            groups: Dict[str, list] = {}
            for qp in batch:
                groups.setdefault(qp.pod.scheduler_name, []).append(qp)
            for profile_name, group in groups.items():
                fwk = self.profiles.get(
                    profile_name, next(iter(self.profiles.values()))
                )
                self._gate.refused, self._gate.asked = None, 0
                # the cascade (routing.py): each engine is OFFERED the batch
                # in turn; the first that does not decline takes it
                offers = [("fast", self._try_dispatch_fast)]
                if self._chain_quickcheck(fwk, group):
                    offers.insert(0, ("chained", self._try_dispatch_chained))
                for engine, offer in offers:
                    pipe = routing.Pipeline(
                        not pending, all(r.get("kind") == "fast" for r in pending)
                    )
                    ans = offer(fwk, group, outcomes, pipe)
                    if ans.status is routing.Offer.SETTLE:
                        flush(0)
                        ans = offer(fwk, group, outcomes, routing.Pipeline(True, True))
                    if ans.status is not routing.Offer.DECLINED:
                        break
                else:
                    # direct path: settle the pipeline first — its commits
                    # must land before a non-chained dispatch reads host
                    # state — and drop the chain (these commits happen
                    # outside it)
                    flush(0)
                    self._chain = None
                    t0 = time.perf_counter()
                    outs = self._schedule_batch(group)
                    dt = time.perf_counter() - t0
                    self._book_route("direct", group)
                    self._record_batch_metrics(profile_name, group, outs, dt)
                    outcomes.extend(outs)
                    continue
                # a record, nothing left or the serial fallback: its batch
                self._book_route(engine, group)
                if ans.status is routing.Offer.IN_FLIGHT:
                    # pipelined: keep up to two batches in flight so the
                    # harvest of batch k overlaps k+1's device compute AND
                    # k+2's dispatch (the async result copy finishes before
                    # the blocking fetch; the fast path's state chains on
                    # device the same way, so the device link's round trip
                    # hides behind host work).  With Reserve/Permit plugins in
                    # play a commit can realistically fail (and forget), so
                    # harvest eagerly — one batch in flight — to keep the
                    # optimism window close to the reference's (a forget is
                    # visible to the very next scheduling cycle).  When every
                    # Reserve/Permit plugin is also a host Filter the gate
                    # already proved irrelevant (the default volumebinding/
                    # DRA shape), their walks are no-ops for these batches —
                    # keep the full two-deep double buffer.
                    pending.append(ans.record)
                    flush(
                        0
                        if ans.record.get("harvest_now")
                        else 1 if self._rp_can_fail(fwk) else 2
                    )
                elif ans.status is routing.Offer.SERIAL:
                    # breaker fallback for an abandoned chained dispatch:
                    # settle the pipeline (its commits must land first),
                    # then drain the live batch serially OUTSIDE the
                    # scheduler lock
                    flush(0)
                    t0 = time.perf_counter()
                    outs = self._schedule_batch_serial(fwk, ans.batch)
                    self._record_batch_metrics(
                        profile_name, ans.batch, outs, time.perf_counter() - t0
                    )
                    outcomes.extend(outs)
            # hand this batch's buffered binds to the workers — they overlap
            # the next batch's device dispatch (the async binding pipeline)
            self._flush_binds()
            ann_batch.end()
            batches += 1
            if max_batches is not None and batches >= max_batches:
                break
        flush(0)
        # End-of-drain barrier: binding cycles of the LAST batches may still
        # be in flight (they overlapped the later dispatches); callers read
        # final outcomes, so settle them here.  Failed binds have been
        # requeued with backoff by now — they surface on a later drain,
        # exactly like the reference's retry flow.  The queue is empty
        # here, so the wait is the loop thread's idle time: nothing to
        # decide, only binds in flight.
        self._flush_binds()
        if self._inflight_binds:
            with self.phases.span("loop.idle"):
                self.wait_for_bindings()
        if self._sanitize:
            # KTPU_SANITIZE drift probe: every usage row the mirror claims
            # current must match a fresh recomputation from the cache
            with self._mu:
                sanitizer.check_mirror_consistency(self.cache, self.mirror)
            # one-shot per process: the symbolic shape interpreter's root
            # summaries must agree with jax.eval_shape on representative
            # instantiations (mismatches count into the shape_check metric)
            sanitizer.check_root_shapes()
        if t_drain is not None and tr.enabled:
            tr.complete(
                "drain",
                t_drain,
                cat="drain",
                pods=len(outcomes),
                batches=batches,
                scheduled=sum(1 for o in outcomes if o.node is not None),
            )
        return outcomes

    def _rp_can_fail(self, fwk) -> bool:
        """True when a Reserve/Permit plugin could actually reject a
        pipelined batch's pod — the case that caps the pipeline at one
        batch in flight.  Plugins covered by the host-filter gate are
        no-ops for gated batches (reserve_permit_covered_by_host_filters),
        so the default registry double-buffers at full depth."""
        return (
            fwk.has_reserve_or_permit()
            and not fwk.reserve_permit_covered_by_host_filters()
        )

    def _book_route(self, route: str, group) -> None:
        """One count a batch, where the loop has routed it: the batch's pods
        by the route that took them (``route.chained``: the chained
        dispatch, wave or scan, its serial fallback included; ``route.fast``:
        the signature / resident path, the pods its extension popped
        included; ``route.direct``) and, where ``_fast_gate_ok``'s last verdict on it
        was no, by the gate's reason (``fast_gate.refused.<reason>``); and the
        placed terms that verdict asked (``fast_gate.probes_asked``, the
        extension's asking included; absent where it asked none)."""
        self.phases.count("route." + route, len(group))
        if self._gate.refused is not None:
            self.phases.count(
                "fast_gate.refused." + self._gate.refused, len(group)
            )
        if self._gate.asked:
            self.phases.count("fast_gate.probes_asked", self._gate.asked)

    # The loop's spans whose off-CPU seconds a per-layer metric reads: the
    # top-level ones that block on nothing by design (loop.off_cpu_s_per_kpod;
    # top-level, so nothing nested is counted twice) and the two parts of
    # chain_dispatch that hand the interpreter lock away
    # (loop.chain_prep_off_cpu_s_per_kpod.<part>).  A span around a wait
    # (device, d2h, a lock_wait) would book its own wall.
    _OFF_CPU_SPANS = frozenset(
        (
            "queue_pop",
            "chain_dispatch",
            "pack",
            "h2d",
            "commit",
            "wave_resolve",
            "resident_rounds",
            "flush_binds",
            "chain_dispatch.h2d",
            "chain_dispatch.release",
        )
    )

    def _span(self, phase: str):
        """A loop-thread phase span carrying the current batch id; one of
        ``_OFF_CPU_SPANS`` also books ``<phase>.off_cpu``, the seconds of it
        the thread did not run (``PhaseAccumulator``)."""
        return self.phases.span(
            phase, off_cpu=phase in self._OFF_CPU_SPANS, bid=self._bid
        )

    def _trace_dispatch(self, kind: str, t0: float, batch, rec=None) -> int:
        """Stamp a monotonically-increasing batch id and — when tracing —
        record the dispatch-half span with pod context (batch id, pod
        count, the first few uids).  Scheduling-loop thread only."""
        self._batch_seq += 1
        bid = self._batch_seq
        if rec is not None:
            rec["bid"] = bid
        cp = self.controlplane
        if cp is not None and cp.enabled:
            # the staleness sentinel samples at every dispatch: how far
            # behind the newest DELIVERED informer event the snapshot this
            # batch scheduled against ran
            cp.note_dispatch(bid)
        tr = self.tracer
        if tr.enabled:
            tr.complete(
                f"dispatch.{kind}",
                t0,
                cat="batch",
                bid=bid,
                pods=len(batch),
                uids=[qp.pod.uid for qp in batch[:8]],
            )
        return bid

    def _d2h(self, value, kernel: Optional[str] = None):
        """Blocking device→host fetch with round-trip accounting: every
        harvest-side ``jax.device_get`` goes through here so
        scheduler_tpu_host_roundtrips_total / d2h_bytes_total measure the
        quantity the resident drain exists to minimize.  ``kernel`` tags
        the fetch with the jit root whose results it harvests — the
        dispatch ledger splits the aggregate bytes per kernel (untagged
        fetches land under ``_untagged`` so the split always sums to the
        total)."""
        led = self.kernels
        t0 = time.perf_counter() if led.enabled else 0.0
        out = jax.device_get(value)
        prom = self.prom
        prom.host_roundtrips.inc()
        nb = sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(out)
            if hasattr(a, "nbytes")
        )
        prom.d2h_bytes.inc(nb)
        if led.enabled:
            led.record_d2h(kernel, nb, time.perf_counter() - t0)
        return out

    # ----- device-fault tier (ISSUE 15): breaker routing, guarded
    # readbacks, epoch-guarded resync, mesh degradation -----------------------

    def _breaker_blocked(self, kernel: str) -> bool:
        """Routing-gate check against ``kernel``'s circuit breaker: True
        routes the dispatch family to its registered fallback engine
        (kernels._KTPU_BREAKER_FALLBACKS) and counts the event in
        scheduler_tpu_wave_fallback_total{reason="breaker"} — degraded
        placements stay bit-identical (the fallbacks are the engines
        paritycheck certifies), only the flops move."""
        led = self.kernels
        if not led.enabled:
            return False
        if led.breaker_allows(kernel):
            return False
        self.prom.wave_fallback.inc(reason="breaker")
        return True

    def _note_dispatch_failure(self, exc) -> None:
        """Bookkeeping for an abandoned kernel dispatch: log it, count
        the breaker-routed fallback (every fallback site calls this, so
        the wave_fallback{reason="breaker"} series — the engagement
        evidence CHAOS.md and the paritycheck assert lean on — can never
        silently miss a site), and for a mesh device loss re-form the
        mesh before the next dispatch (the current batch rides the
        serial fallback either way)."""
        kind = getattr(exc, "kind", "dispatch_error")
        logger.warning(
            "kernel dispatch abandoned (%s: %s) — batch takes the "
            "fallback engine",
            kind,
            exc,
        )
        self.prom.wave_fallback.inc(reason="breaker")
        if kind == "mesh_device_loss":
            self._degrade_mesh()

    def _degrade_mesh(self) -> bool:
        """A device dropped from the mesh: re-form ``meshDispatch`` on a
        smaller device set — halving, preserving the configured
        meshPodsAxis layout when it still divides — or fall back to
        single-chip, rebuild the device snapshot cache against the new
        placement, and resync the fast lineage's device copy.  Decisions
        are unaffected — the mesh only changes where the flops run
        (multichip_vs_singlechip parity) — so degradation is a pure
        capacity event.  Caveat: jax reports no per-device health, so
        the smaller mesh is drawn from the same device list and may
        still contain the dead chip — the next loss halves again, and
        the floor is always the single-chip engine (then the serial
        oracle under its breaker)."""
        from kubernetes_tpu.cache.device_mirror import DeviceClusterCache
        from kubernetes_tpu.parallel import mesh as pmesh

        with self._mu:
            if self.mesh is None:
                new_mesh = None
            else:
                n = int(self.mesh.devices.size) // 2
                pa = self.config.mesh_pods_axis
                if not (pa and n >= 2 and n % pa == 0):
                    pa = None  # make_mesh default (pods-major, pow2)
                new_mesh = (
                    pmesh.make_mesh(n_devices=n, pods_axis=pa)
                    if n >= 2
                    else None
                )
            self.mesh = new_mesh
            self.mirror.node_pad_multiple = (
                new_mesh.shape["nodes"] if new_mesh is not None else 1
            )
            self._dc_cache = DeviceClusterCache(mesh=new_mesh)
            self._chain = None
            holder = self._fastdev
            if holder is not None:
                # the old placement's device copy is suspect — the host
                # committer stays authoritative; rematerialize on the
                # degraded mesh at the next dispatch
                holder["dev"] = None
                holder["epoch"] = holder.get("epoch", 0) + 1
                holder["dev_sum"] = None
        self.prom.resident_resyncs.inc(reason="mesh_degraded")
        logger.warning(
            "mesh degraded to %s after device loss",
            dict(new_mesh.shape) if new_mesh is not None else "single-chip",
        )
        return True

    def _sync_device_cluster(self, vocab):
        """DeviceClusterCache.sync with hbm_oom recovery: a failed
        donation/placement (chaos hbm_oom, or a real RESOURCE_EXHAUSTED)
        invalidates the cache and rebuilds the snapshot whole from the
        host mirror — the full-pack path.  Bounded retries; persistent
        failure surfaces as DispatchFailed so callers route the batch to
        the serial fallback."""
        from kubernetes_tpu.observability import kernels as kernels_mod

        last = None
        for _ in range(3):
            try:
                return self._dc_cache.sync(self.mirror, vocab)
            except kernels_mod.DispatchFailed:
                raise
            except Exception as e:  # noqa: BLE001 — backend failure class
                last = e
                self.kernels.record_breaker_failure(
                    "device_mirror.apply", "hbm_oom"
                )
                self.prom.resident_resyncs.inc(reason="hbm_oom")
                self._dc_cache.invalidate()
        raise kernels_mod.DispatchFailed(
            "device_mirror.apply", last, kind="hbm_oom"
        )

    def _d2h_guarded(self, value, kernel: str, validate=None, retries: int = 2):
        """Blocking fetch (through ``_d2h``) with readback validation:
        float leaves must be finite, signed-int leaves must not carry the
        poison sentinel, and ``validate(fetched)`` (when given) must
        return None.  A bad readback books a poisoned_output breaker
        failure and re-fetches — the device array is intact, so an
        injected poison heals, while a REAL non-finite kernel output
        keeps failing and raises DispatchFailed for the caller's fallback
        engine.  Chaos poison is injected here (and ONLY here: unguarded
        fetches are never corrupted — a fault nobody validates would be
        an undetectable wrong answer, not a recoverable one)."""
        import numpy as np

        from kubernetes_tpu.observability import kernels as kernels_mod

        poison_i32 = -(2**31)
        attempt = 0
        while True:
            out = self._d2h(value, kernel=kernel)
            inj = kernels_mod.fault_injector()
            if inj is not None and self.kernels.enabled:
                out, _fired = inj.poison(kernel, out)
            err = None
            for leaf in jax.tree_util.tree_leaves(out):
                if not isinstance(leaf, np.ndarray) or leaf.size == 0:
                    continue
                if np.issubdtype(leaf.dtype, np.floating):
                    if not np.isfinite(leaf).all():
                        err = "non-finite float readback"
                        break
                elif np.issubdtype(leaf.dtype, np.signedinteger):
                    if (leaf == leaf.dtype.type(poison_i32)).any():
                        err = "out-of-range int readback"
                        break
            if err is None and validate is not None:
                err = validate(out)
            if err is None:
                return out
            self.kernels.record_breaker_failure(kernel, "poisoned_output")
            if attempt >= retries:
                raise kernels_mod.DispatchFailed(
                    kernel, err, kind="poisoned_output"
                )
            attempt += 1

    def _schedule_batch_serial(self, fwk, batch) -> List[ScheduleOutcome]:
        """Breaker fallback: the batch degrades to one-pod host-oracle
        cycles — the fallback ladder's floor, bit-identical to the device
        engines by the parity property.  This is the drain path while a
        kernel family's breaker is open (or after its dispatch was
        abandoned mid-batch)."""
        outs: List[ScheduleOutcome] = []
        for qp in batch:
            if qp.pod.nominated_node_name:
                outs.extend(self._schedule_one_nominated(fwk, qp))
            else:
                outs.extend(self._schedule_one_extender(fwk, qp))
        return outs

    def _record_batch_metrics(self, profile, group, outs, dt: float) -> None:
        """Attempt counters + latency histograms (metrics.go:86-147).  The
        batch shares one device dispatch, so per-pod attempt latency is the
        batch latency amortized over its pods."""
        from kubernetes_tpu import metrics as M

        prom = self.prom
        prom.batch_size_hist.observe(len(group))
        prom.recorder.observe(prom.algorithm_duration, dt, profile=profile)
        per_pod = dt / max(len(outs), 1)
        # one batched dispatch smears its latency over the batch: the
        # coarse batch label lets the serving analysis separate real
        # per-pod samples (batch=1) from drain averages (batch=4096+)
        bsz = M.batch_size_bucket(len(group))
        now_mono = time.monotonic()
        # Aggregate per-pod series by (result / attempts) before touching
        # the registry: the batch shares one latency, so one bucket update
        # per distinct label set replaces len(batch) walks.
        by_result: Dict[str, int] = {}
        by_attempts: Dict[int, int] = {}
        for o in outs:
            if o.node is not None:
                result = M.SCHEDULED
                a = o.pod_attempts or 1
                by_attempts[a] = by_attempts.get(a, 0) + 1
                # e2e SLI from the MONOTONIC enqueue stamp: the queue
                # clock is injectable (manual/wall), and a clock jump —
                # NTP step, chaos skew, a test skipping backoff — must
                # not skew the latency distribution
                if o.first_enqueue_mono is not None:
                    prom.pod_scheduling_sli_duration.observe(
                        max(now_mono - o.first_enqueue_mono, 0.0),
                        attempts=str(min(a, 16)),
                    )
            elif o.status.code == Code.ERROR:
                result = M.ERROR
            else:
                result = M.UNSCHEDULABLE
            by_result[result] = by_result.get(result, 0) + 1
        for result, n in by_result.items():
            prom.schedule_attempts.inc(n, result=result, profile=profile)
            prom.attempt_duration.observe_n(
                per_pod, n, result=result, profile=profile, batch=bsz
            )
        for a, n in by_attempts.items():
            prom.pod_scheduling_attempts.observe_n(a, n)

    def refresh_gauges(self) -> None:
        """pending_pods / cache_size gauges (metrics.go:180-220), refreshed
        on scrape rather than on every mutation."""
        stats = self.queue.stats()
        for queue_name, n in stats.items():
            self.prom.pending_pods.set(n, queue=queue_name)
        self.prom.cache_size.set(len(self.cache.real_nodes()), type="nodes")
        self.prom.cache_size.set(len(self.cache.pod_states), type="pods")
        self.prom.cache_size.set(len(self.cache.assumed), type="assumed_pods")
        # observability-layer overhead counters, sampled on scrape so the
        # recording hot paths never touch the registry
        ts = self.tracer.stats()
        self.prom.trace_buffered.set(ts["events"])
        self.prom.trace_dropped.set(ts["dropped"])
        self.prom.trace_evicted.set(ts["evicted"])
        self.prom.tracer_overhead.set(ts["overhead_s"])
        fs = self.flight.stats()
        self.prom.flightrec_events.set(fs["events"])
        self.prom.flightrec_evicted.set(fs["evicted_total"])
        slo = self.slo
        if slo is not None:
            for objective, burn in slo.gauge_rows():
                self.prom.slo_burn_rate.set(burn, objective=objective)
        # queue depth + oldest-pod age per sub-queue: the age walk reads
        # live heap entries, so it samples under the scheduler lock
        with self._mu:
            depth_age = self.queue.depth_age_stats()
        for queue_name, (depth, age) in depth_age.items():
            self.prom.queue_depth.set(depth, queue=queue_name)
            self.prom.queue_oldest_age.set(age, queue=queue_name)
        cp = self.controlplane
        if cp is not None:
            cp.sync_registry(self.prom)
        # live device memory where the backend reports it (None on CPU)
        if self.kernels.enabled:
            for row in self.kernels.hbm_rows():
                for kind in (
                    "bytes_in_use",
                    "peak_bytes_in_use",
                    "bytes_limit",
                ):
                    self.prom.device_hbm_bytes.set(
                        row[kind], device=row["device"], kind=kind
                    )

    def install_slo(self, slo_config=None):
        """Install the steady-state SLO tier (observability/slo.py): wires
        the evaluator as the flight recorder's streaming sink (per-stage
        latency attribution + objective/burn-rate tracking) and, unless
        disabled in the config, arms the tracer's always-on black-box ring
        so an SLO breach can freeze and dump the trace of the bad window.
        Returns the evaluator (also at ``self.slo``; served at
        /debug/slo)."""
        from kubernetes_tpu.observability.slo import SLOConfig, SLOEvaluator

        cfg = slo_config or SLOConfig()
        ev = SLOEvaluator(cfg, prom=self.prom, tracer=self.tracer)
        self.slo = ev
        # attribution needs the breadcrumbs flowing; the async sink keeps
        # producer threads at one buffer append — joining runs inline at
        # an amortized threshold, with the worker as the idle-tail backstop
        self.flight.enabled = True
        sink = ev.ingest_async
        cp = self.controlplane
        if cp is not None:
            # keep the control-plane monitor upstream of the evaluator —
            # install order between the two tiers must not matter
            sink = cp.make_sink(sink)
        self.flight.sink = sink
        if cfg.blackbox:
            self.tracer.blackbox_start(cfg.blackbox_capacity)
        return ev

    def install_controlplane(self, config=None, api_server=None, source=None):
        """Install the control-plane pipeline tier
        (observability/controlplane.py): causal per-pod chains across
        api_write → watch_delivery → informer_handler → enqueue → pop →
        assumed → bind_start → bound (served at /debug/pipeline), the
        snapshot-staleness sentinel sampled at every dispatch (sustained
        breaches file through the SLO tier's black-box machinery when
        installed), and — with ``api_server``/``source`` wired — the
        serving tier's per-request and delivery-lag accounting.  Returns
        the monitor (also at ``self.controlplane``)."""
        from kubernetes_tpu.observability.controlplane import (
            ControlPlaneConfig,
            ControlPlaneMonitor,
        )

        self_ref = weakref.ref(self)

        def _slo_of():
            s = self_ref()
            return s.slo if s is not None else None

        mon = ControlPlaneMonitor(
            config or ControlPlaneConfig(),
            tracer=self.tracer,
            slo_getter=_slo_of,
        )
        # a chaos journal attached before install already stamps the
        # tracer — inherit its logical clock for chain breadcrumbs
        mon.logical_time = self.tracer.logical_time
        self.controlplane = mon
        # scheduler-side hops ride the existing breadcrumb stream: chain
        # in front of whatever sink is installed (the SLO evaluator's)
        self.flight.enabled = True
        self.flight.sink = mon.make_sink(self.flight.sink)
        if api_server is not None:
            mon.attach_api_server(api_server)
        if source is not None:
            mon.attach_source(source)
        return mon

    def expose_metrics(self) -> str:
        """Prometheus text exposition (the /metrics handler body)."""
        self.refresh_gauges()
        return self.prom.expose()

    def _schedule_batch(
        self, batch, try_workloads: bool = True
    ) -> List[ScheduleOutcome]:
        fwk = self.profiles.get(
            batch[0].pod.scheduler_name, next(iter(self.profiles.values()))
        )
        outcomes: List[ScheduleOutcome] = []
        # direct-path commits happen outside any device chain
        self._chain = None

        # the workloads tier: gang/coscheduling + DRA + volume topology
        # batches take ONE fused dispatch with all-or-nothing gang
        # admission instead of degrading to one-pod host-plugin cycles
        if try_workloads and self.config.gang_dispatch:
            wl_out = self._try_dispatch_workloads(fwk, batch)
            if wl_out is not None:
                return wl_out
            # mixed batch: one disqualifying pod (nominated / extender /
            # host ports / uncovered plugin) must not silently drop the
            # quorum semantics for gang members sharing its batch — peel
            # the members out and retry the workloads dispatch on them
            # alone; only a member that ITSELF disqualifies falls through
            gang_qps = [
                qp
                for qp in batch
                if self._workloads_group_of(qp.pod) is not None
            ]
            if gang_qps and len(gang_qps) < len(batch):
                rest = [
                    qp
                    for qp in batch
                    if self._workloads_group_of(qp.pod) is None
                ]
                wl_out = self._try_dispatch_workloads(fwk, gang_qps)
                if wl_out is not None:
                    return wl_out + self._schedule_batch(rest)

        # Host-stateful Filter plugins (volumebinding/DRA class) judge
        # against cache state that earlier commits in the SAME batch
        # mutate — their veto masks can't be batched; extender webhooks
        # are serial per-pod HTTP round-trips by protocol.  Pods either
        # could act on (cheap spec check — routing's one-pod gates)
        # degrade to one-pod cycles (the reference's native granularity,
        # schedule_one.go:65); contiguous runs of clean pods stay on the
        # batched device path.  Runs preserve queue order, so decisions
        # stay sequential-equivalent.
        one_pod = self._pod_gates(fwk).one_pod
        if len(batch) > 1:
            run: List = []
            split = False
            for qp in batch:
                reason = one_pod(qp.pod)
                if reason is None:
                    run.append(qp)
                    continue
                split = True
                if run:
                    outcomes.extend(self._schedule_batch(run))
                    run = []
                if reason == routing.NOMINATED_NODE:
                    outcomes.extend(self._schedule_one_nominated(fwk, qp))
                else:
                    outcomes.extend(self._schedule_batch([qp]))
            if split:
                if run:
                    outcomes.extend(self._schedule_batch(run))
                return outcomes
        else:
            # (a pod that only a host Filter finds relevant stays here: its
            # veto mask is exact for a batch of one)
            reason = one_pod(batch[0].pod)
            if reason == routing.NOMINATED_NODE:
                return self._schedule_one_nominated(fwk, batch[0])
            if reason in (routing.EXTENDER, routing.NORMALIZING_SCORE):
                return self._schedule_one_extender(fwk, batch[0])

        # Host-side preparation reads cache/mirror/assume-cache state that
        # async binding workers mutate under self._mu — hold it for the
        # whole prep (the device dispatch below runs outside the lock).
        with self._mu:
            state = CycleState()

            # 0. PreFilter (runtime:698): per-pod rejection + Skip bookkeeping
            pf_failures = fwk.run_pre_filter(state, [qp.pod for qp in batch])
            if pf_failures:
                live = []
                for qp in batch:
                    s = pf_failures.get(qp.pod.uid)
                    if s is None:
                        live.append(qp)
                        continue
                    self.metrics["schedule_attempts"] += 1
                    outcomes.append(self._post_filter_or_fail(fwk, state, qp, s, 0))
                batch = live
                if not batch:
                    return outcomes
            pods = [qp.pod for qp in batch]
            from kubernetes_tpu.metrics import Trace

            trace = Trace(
                "Scheduling batch",
                clock=time.perf_counter,
                pods=len(pods),
                profile=fwk.profile_name,
            )
            trace.step("PreFilter done")

            # 1. intern pod labels FIRST so a fresh full pack covers them
            # (stale val-int tables would force a second repack next cycle).
            # The FULL mirror repack is deferred past the fast path: fast
            # batches never read the per-node usage tensors (the committer
            # tracks usage itself), so steady-state fast drains skip the
            # per-batch repack entirely; _sync_mirror_external below brings
            # the mirror up to date only when non-fast state moved.
            vocab = self.mirror.vocab
            for pod in pods:
                for k, v in pod.labels.items():
                    vocab.intern_label(k, v)
            self._sync_mirror_external()
            trace.step("Snapshot mirror synced")

            # 1a. FAST PATH: when the batch has no batch-dynamic constraints
            # beyond resources (no inter-pod/spread/ports/nominations/host
            # filters), pods collapse into signatures — one tiny device static
            # eval + exact host greedy replaces the per-pod device scan.
            enabled = fwk.device_enabled()
            weights = tuple(
                fwk.score_weights.get(n, 0) for n in gang.WEIGHT_ORDER
            )
            active_host = fwk.active_host_filters(state, pods)
            # Host PreScore/Score plugins (runtime/framework.go:1052,1101):
            # PreScore may Skip; surviving plugins contribute a pre-weighted
            # [P, N] score matrix merged before the device argmax.
            fwk.run_pre_score(state, pods, self.mirror.nodes.names)
            active_scores = fwk.active_host_scores(state, pods)
            if (
                not active_host
                and not active_scores
                and self._fast_gate_ok(batch)
                and self._signature_profile(fwk)
            ):
                fast = self._try_fast_schedule(
                    fwk, state, batch, enabled, weights, outcomes
                )
                if fast is not None:
                    # fast_batches + gang_dispatch_duration(path=fast) are
                    # both recorded inside the dispatch/harvest halves
                    trace.step("Fast-path commit done")
                    trace.log_if_long()
                    return fast

            # scan path: bring the full mirror (usage tensors included) up
            # to date — its kernels read requested/num_pods per node.
            with self._span("pack"):
                self._repack_mirror()
            trace.step("Snapshot mirror updated")

            self._p_cap_max = max(self._p_cap_max, self._p_bucket(len(pods)))
            p_cap = self._p_cap_max
            pb = pack_pod_batch(
                pods,
                vocab,
                k_cap=self.mirror.nodes.k_cap,
                p_cap=p_cap,
                namespace_labels=self.namespace_labels,
            )
            sp_h2d = self._span("h2d").begin()
            from kubernetes_tpu.observability import kernels as kernels_mod

            try:
                dc = self._sync_device_cluster(vocab)
            except kernels_mod.DispatchFailed as e:
                # persistent snapshot-placement failure (hbm_oom class):
                # the batch drains on the serial host-oracle path
                self._note_dispatch_failure(e)
                return outcomes + self._schedule_batch_serial(fwk, batch)
            db = self._place_db(DeviceBatch.from_host(pb))
            sp_h2d.end()
            v_cap = bucket_cap(len(vocab.label_vals))
            hostname_key = self._hostname_dev(vocab)
            tables = self._gang_tables(pb, vocab)

            has_interpod = bool(
                (pb.aff_kind != PAD).any()
                or (self.mirror.existing.term_kind != PAD).any()
            )
            has_spread = bool((pb.tsc_topo_key != PAD).any())
            has_images = bool((pb.img_ids >= 0).any())
            has_ports = bool(
                (pb.want_ppk != PAD).any() or (self.mirror.nodes.used_ppk != PAD).any()
            )

            # 1a'. the wave where the batch is shaped for it; an open
            # wave.wave_run breaker sends it to the scan fallback
            wt = self._wave_tables_for(pb, breaker="wave.wave_run")
            # an OPEN gang-scan breaker has no device engine left under it:
            # the batch degrades to one-pod host-oracle cycles (the ladder's
            # floor, bit-identical by the parity property)
            if wt is None and self._breaker_blocked("gang.gang_run"):
                return outcomes + self._schedule_batch_serial(fwk, batch)
            self.metrics[
                "wave_batches" if wt is not None else "scan_batches"
            ] += 1

            # 1b. host-backed Filter plugins veto (pod, node) pairs the device
            # kernels can't judge (stateful plugins — volumebinding class).
            extra_mask = None
            host_diags = host_plugin_sets = None
            if active_host:
                extra_mask, host_diags, host_plugin_sets = self._host_filter_mask(
                    fwk, state, pods, p_cap, db=db, enabled=enabled
                )
            # the statics by distinct pod row (gang.precompute's table) —
            # host-plugin vetoes are per pod, so a batch that carries
            # them, like one with too many distinct rows, takes the
            # per-pod program
            ss = None
            if wt is not None and extra_mask is None:
                ss = self._static_signatures(pb)
            sig_kw = (
                dict(sig=ss["sig"], rep_pod=ss["rep_pod"]) if ss else {}
            )

            # 1b'. host-backed Score plugins → pre-weighted additive [P, N]
            # matrix merged into the device selection (the RunScorePlugins
            # weight+sum pass, runtime/framework.go:1177, for kernel-less
            # plugins — e.g. VolumeBinding's VolumeCapacityPriority shape).
            extra_score = None
            if active_scores:
                extra_score = self._host_score_matrix(fwk, state, pods, p_cap)

            # 1c. nominated preemptors (victims still terminating) charge their
            # nominated node for pods of lower priority (runtime:973).
            nom_node = nom_prio = nom_req = None
            if len(self.nominator):
                nom_node, nom_prio, nom_req = self._nominated_arrays(
                    {qp.pod.uid for qp in batch}
                )

        # 2. one fused device dispatch (the whole Filter→Score→Select loop)
        sample_k, tie_key, attempt_base = self._sampling_args(fwk)
        sample_start = (
            jnp.asarray(getattr(self, "_next_start_node_index", 0), I32)
            if sample_k is not None
            else None
        )
        t_gang = time.perf_counter()
        sp_dev = self._span("device").begin()
        wstats_dev = None
        # kwargs shared VERBATIM by both dispatch kernels — one dict so a
        # future knob cannot reach one path and silently miss the other
        shared_kw = dict(
            has_interpod=has_interpod,
            has_spread=has_spread,
            has_images=has_images,
            enabled=enabled,
            weights=weights,
            extra_mask=extra_mask,
            nom_node=nom_node,
            nom_prio=nom_prio,
            nom_req=nom_req,
            extra_score=extra_score,
            fit_strategy=fwk.fit_strategy(),
            **tables,
        )
        path = "wave" if wt is not None else "scan"
        kroot = "wave.wave_run" if wt is not None else "gang.gang_run"
        n_bound = len(self.mirror.nodes.names)

        def _validate_direct(fetched):
            import numpy as np

            arr = np.asarray(fetched)
            ch, nf = arr[0], arr[1]
            if ((ch < -1) | (ch >= n_bound)).any():
                return "chosen index out of node range"
            if ((nf < 0) | (nf > n_bound)).any():
                return "n_feas out of range"
            return None

        try:
            if wt is not None:
                from kubernetes_tpu.ops import wave as wave_ops

                chosen, n_feas, reason_counts, tallies, wstats_dev = (
                    wave_ops.wave_run(
                        dc,
                        db,
                        hostname_key,
                        v_cap,
                        wt["tid_sp"],
                        wt["rep_sp_p"],
                        wt["rep_sp_c"],
                        wt["tid_ip"],
                        wt["rep_ip_p"],
                        wt["rep_ip_u"],
                        wt["ip_cdv_tab"],
                        d2_cap=wt["d2_cap"],
                        has_ports=wt["has_ports"],
                        tid_pt=wt["tid_pt"],
                        port_conf=wt["port_conf"],
                        sample_k=sample_k,
                        sample_start=sample_start,
                        tie_key=tie_key,
                        attempt_base=attempt_base,
                        **sig_kw,
                        **shared_kw,
                    )
                )
            else:
                chosen, n_feas, reason_counts, tallies = gang.gang_run(
                    dc,
                    db,
                    hostname_key,
                    v_cap,
                    has_ports=has_ports,
                    sample_k=sample_k,
                    sample_start=sample_start,
                    tie_key=tie_key,
                    attempt_base=attempt_base,
                    **shared_kw,
                )
            sp_dev.end()
            with self._span("d2h"):
                both = self._d2h_guarded(
                    jnp.stack([chosen, n_feas]),
                    kernel=kroot,
                    validate=_validate_direct,
                )
        except kernels_mod.DispatchFailed as e:
            # abandoned dispatch (or unrecoverable readback): nothing was
            # committed — the batch drains on the serial host-oracle path,
            # bit-identically, while the breaker keeps the kernel parked
            self._note_dispatch_failure(e)
            return outcomes + self._schedule_batch_serial(fwk, batch)
        chosen, n_feas = both[0], both[1]
        if sample_k is not None:
            self._next_start_node_index = int(
                self._d2h(tallies["sample_start"], kernel=kroot)
            )
        if tie_key is not None or sample_k is not None:
            self._attempt_counter = (
                getattr(self, "_attempt_counter", 0) + len(batch)
            )
        self.prom.recorder.observe(
            self.prom.gang_dispatch_duration,
            time.perf_counter() - t_gang,
            path=path,
        )
        self._trace_dispatch(path, t_gang, batch)
        trace.step("Gang dispatch done")

        # 3. per-pod commit: assume → reserve → permit → bind.  Wave
        # batches additionally resolve their speculation stats and, when
        # the framework allows lean binds, commit their successes as ONE
        # bulk run.
        wave_bulk = False
        if wstats_dev is not None:
            wave_bulk = self._wave_resolve(
                fwk,
                batch,
                chosen,
                wstats_dev,
                self.mirror.e_used,
                kernel=kroot,
                static_sigs=ss["n_valid"] if ss else None,
                n_terms=wt["n_terms"],
            )
        self._process_results(
            fwk,
            state,
            batch,
            chosen,
            n_feas,
            reason_counts,
            outcomes,
            host_diags,
            host_plugin_sets,
            wave_bulk=wave_bulk,
            kernel=kroot,
        )
        trace.step("Commits done")
        trace.log_if_long()
        return outcomes

    def _process_results(
        self,
        fwk,
        state,
        batch,
        chosen,
        n_feas,
        reason_counts,
        outcomes,
        host_diags=None,
        host_plugin_sets=None,
        wave_bulk=False,
        kernel=None,
    ) -> None:
        """The per-pod result walk shared by the direct and chained paths:
        failures → diagnosis + PostFilter, successes → _commit (which hands
        binding to the async workers).  ``wave_bulk`` (_wave_resolve's
        verdict: a wave batch whose commits may skip the per-pod walk)
        routes ALL the batch's successes through the bulk-commit path
        instead, as one run and one bulk binding task, which _submit_binds
        slices across the workers."""
        sp_commit = self._span("commit").begin()
        node_names = self.mirror.nodes.names
        n_nodes = len(self.cache.real_nodes())
        counts = None  # fetched lazily — only failures read it
        if fwk.has_post_filter():
            failed = [
                qp for i, qp in enumerate(batch) if int(chosen[i]) < 0
            ]
            if failed:
                # the dispatch's own committed placements ride into the
                # narrowing dry run (the admission scan's carried state,
                # not yet visible through the cache at this point).
                # Peers travel as node NAMES: the narrow repacks the
                # mirror first, which may compact node slots, so raw
                # dispatch-time indices could charge the wrong rows.
                self._batched_preemption_narrow(
                    fwk,
                    state,
                    failed,
                    batch=batch,
                    chosen=chosen,
                    node_names=node_names,
                )
        # one locked bump for the whole batch: `metrics` is a registered
        # lock-guarded field (binding workers write other keys of it under
        # _mu); uniform write discipline costs one acquisition per batch
        # and stays correct if the interpreter ever drops the GIL's
        # per-op dict atomicity
        with self._mu:
            self.metrics["schedule_attempts"] += len(batch)
        placed: List[int] = []
        for i, qp in enumerate(batch):
            idx = int(chosen[i])
            if idx < 0:
                if counts is None:
                    counts = self._d2h(reason_counts, kernel=kernel)
                diag = {
                    k: int(c)
                    for k, c in zip(gang.DIAG_KERNELS, counts[i])
                    if c > 0
                }
                plugins = set(diag)
                if "HostFilters" in plugins:
                    # replace the aggregate bucket with the per-plugin
                    # reasons recorded while building the veto mask
                    plugins.discard("HostFilters")
                    diag.pop("HostFilters", None)
                    if host_diags is not None:
                        diag.update(host_diags[i])
                        plugins |= host_plugin_sets[i]
                    else:
                        plugins |= {p.name for p in fwk.host_filter_plugins()}
                status = Status.unschedulable(
                    fit_error_message(n_nodes, diag)
                )
                outcomes.append(
                    self._post_filter_or_fail(
                        fwk, state, qp, status, int(n_feas[i]), diag, plugins
                    )
                )
                continue
            if wave_bulk:
                placed.append(i)
                continue
            node_name = node_names[idx]
            outcome = self._commit(fwk, state, qp, node_name, int(n_feas[i]))
            outcomes.append(outcome)
        # wave bulk tail: one vectorized assume + one bulk bind task for
        # the batch's successes (decisions are final)
        if placed:
            self._commit_fast_bulk(
                fwk,
                state,
                batch,
                chosen,
                0,
                0,
                node_names,
                outcomes,
                idxs=placed,
                n_feas=n_feas,
                nonfast=True,
            )
        sp_commit.end()

    # ----- the chained (pipelined) dispatch path ---------------------------
    #
    # chain_dispatch (ops/chain.py) appends each batch's placements into the
    # device cluster inside the dispatch itself, so batch k+1 launches
    # against batch k's output WITHOUT waiting for k's results to reach the
    # host — the drain becomes a software pipeline over the device link.
    # Anything the device can't see (informer events, bind failures, fast-
    # path or one-pod commits, vocab growth) changes the chain epoch and
    # forces a fresh host upload.

    def _chain_epoch(self, vocab):
        return (
            self._external_mutations,
            self.metrics["fast_batches"],
            self.mirror._full_packs,
            len(vocab.label_vals),
            len(vocab.label_keys),
        )

    def _chain_quickcheck(self, fwk, batch) -> bool:
        """Spec-only gate: True when the batch can take the chained path
        (no extenders/host-filter/host-score involvement, not a fast-path
        candidate, mirror already initialized)."""
        if self.extenders or self.mirror.nodes is None:
            return False
        # device-fault tier: an open chain breaker routes batches to the
        # direct path (same verdict kernels, no pipeline overlap)
        if self._breaker_blocked("chain.chain_dispatch"):
            return False
        # bit-compat sampling threads a rotation cursor through every
        # attempt — the direct path owns that state
        if self._sampling_active(fwk):
            return False
        # the device append doesn't splice node port-usage rows, so pods
        # with host ports must take the direct path (which resyncs the
        # snapshot from host state every batch)
        if any(qp.pod.host_ports() for qp in batch):
            return False
        # nominated pods take the single-node fast path via the direct
        # path's split (schedule_one.go:490); one-pod-only score plugins
        # (normalize overrides, extended-resource fit strategies) force the
        # same split routing
        gates = self._pod_gates(fwk)
        for qp in batch:
            if gates.one_pod(qp.pod) or gates.host_score(qp.pod):
                return False
        if not self._fast_gate_ok(batch, gates):
            # gang members take the direct path's workloads dispatch (all-or-
            # nothing admission with device-side rollback,
            # ops/coscheduling.py); a nomination or a placed term is the
            # chain's to honour
            return self._gate.refused != routing.GANG
        # a batch the signature fast path can commit is cheaper there —
        # the keys computed here are memoized for _try_fast_schedule so the
        # per-pod signature work runs ONCE per batch, not twice
        return (
            not self._signature_profile(fwk)
            or self._batch_signature_keys(batch) is None
        )

    def _repack_mirror(self) -> None:
        """mirror.update + key-width guard: one forced full repack when the
        label-key bucket grew past the packed node-tensor width.  The single
        definition shared by the scan path, the fast-path sync, and the
        chained-dispatch prep.  When a live fast committer proves every
        pending usage delta is its own (same lineage epoch, nothing
        unharvested), its state flushes into the mirror in one vectorized
        pass first, so update()'s per-dirty-node walk sees clean rows."""
        holder = self._fastdev
        if (
            holder is not None
            and not holder["dev_inflight"]
            and self._fc_key is not None
            and self._fc_key[:3]
            == (
                self._external_mutations,
                self._nonfast_commits,
                self.mirror._full_packs,
            )
            and self.mirror.nodes is holder["nt"]
        ):
            self.mirror.apply_fast_usage(holder["fc"], self.cache)
        self.mirror.update(self.cache, self.namespace_labels)
        if bucket_cap(len(self.mirror.vocab.label_keys)) > self.mirror.nodes.k_cap:
            self.mirror._force_full = True
            self.mirror.update(self.cache, self.namespace_labels)
        self._mirror_sync = (self._external_mutations, self._nonfast_commits)

    def _signature_profile(self, fwk) -> bool:
        """The signature committer assumes the default fit scoring,
        full-width evaluation, and first-max tie-break: properties of the
        profile, asked once a group before any pod's gates."""
        return (
            fwk.fit_strategy() == gang.DEFAULT_FIT_STRATEGY
            and not self._sampling_active(fwk)
        )

    def _pod_gates(self, fwk, signature=None) -> routing.PodGates:
        """``routing.pod_gates`` over what this scheduler observes NOW (a
        batch's readings share one build); ``fwk`` None for the fast gate
        alone, which reads no profile."""
        max_nom = None
        if len(self.nominator):
            max_nom = max(p.priority for _, p in self.nominator.entries())
        return routing.pod_gates(
            fwk,
            extenders=self.extenders,
            gang_on=self.config.gang_dispatch,
            max_nomination=max_nom,
            # an immutable view: the gates run outside _mu
            # (_chain_quickcheck) while the informer thread counts term
            # pods in and out
            view=self.cache.term_probe_view() if self.cache.n_term_pods else None,
            tally=self._gate,
            signature=signature,
        )

    def _fast_gate_ok(self, batch, gates=None) -> bool:
        """Per-batch fast-path eligibility, replacing the old cluster-global
        gates: nominations and placed (anti-)affinity terms only poison the
        pods they can actually touch (``routing.pod_gates``' ``fast_gate``
        has the clauses and why).

        Called from three places a batch may pass; ``_gate`` keeps the last
        verdict's reason (``gang``, ``nomination``, ``term_admits``: a
        placed term admits a batch pod, ``term_count``: the batch's
        label-groups have more candidate terms than one sweep may ask,
        ``MAX_PROBES_ASKED``) and its asking for the loop to book once,
        ``_book_route``.
        """
        gate = self._gate
        gate.asked = 0
        gate.refused = (gates or self._pod_gates(None)).fast_gate(batch)
        return gate.refused is None

    def _fast_pod_predicate(self, fwk, group_name: str, known_rows=None):
        """The pop_batch_while feed for fast-batch extension: the group's
        pods that routing's gates give no reason against (``first_reason``,
        the signature included) — exactly the pods a fresh batch through
        _try_dispatch_fast's gates would accept
        (tests/test_routing.py); with ``known_rows``
        (the signature row cache) it additionally requires the pod's
        signature to be already established as argmax-neutral, so the
        extension can never force a post-pop bail-out."""
        vocab = self.mirror.vocab
        n_lanes = self.mirror.nodes.allocatable.shape[1]
        params = (n_lanes, len(vocab.resources))
        lanes_box: list = [None]
        sig_key = self._pod_sig_key

        def signature(p):
            # the hot steady-state predicate is just the signature memo
            # lookup (pop_batch_while runs this once per extended pod)
            memo = p.__dict__.get("_sigkey_memo")
            if memo is not None and memo[0] == params:
                k = memo[1]
            else:
                k = sig_key(p, params, lanes_box)
            if k is None or known_rows is None:
                return k
            row = known_rows.get(k)
            return k if row is not None and row["const_ok"] else None

        first_reason = self._pod_gates(fwk, signature).first_reason

        def elig(qp) -> bool:
            p = qp.pod
            return p.scheduler_name == group_name and first_reason(p) is None

        return elig

    def _sync_mirror_external(self) -> None:
        """Repack the host mirror only when state the FAST path reads could
        have moved: external mutations (node/pod informer events, forgets)
        or non-fast commits (scan/extender paths, whose usage the fast
        committer didn't track).  Steady-state fast drains — where the only
        changes are the committer's own commits — skip the repack."""
        sync = (self._external_mutations, self._nonfast_commits)
        if self.mirror.nodes is None or self._mirror_sync != sync:
            self._repack_mirror()

    def _pod_sig_key(self, pod, params, lanes_box):
        """signature_key for one pod, memoized twice over: ON the pod object
        (spec updates arrive as new Pod objects, the compute_requests memo
        pattern) and CONTENT-ADDRESSED by spec (pods stamped from one
        template — the 100k-pod drain shape — share one computation)."""
        d = pod.__dict__
        memo = d.get("_sigkey_memo")
        if memo is not None and memo[0] == params:
            return memo[1]
        from kubernetes_tpu import fastpath as fp

        cache = getattr(self, "_speckey_cache", None)
        if cache is None:
            cache = self._speckey_cache = {}
        sk = fp.spec_key_memo(pod)
        if sk is not None:
            k = cache.get((params, sk), _MISSING)
            if k is not _MISSING:
                d["_sigkey_memo"] = (params, k)
                return k
        if lanes_box[0] is None:
            from kubernetes_tpu.snapshot.schema import ResourceLanes

            lanes_box[0] = ResourceLanes(self.mirror.vocab)
        k = fp.signature_key(pod, lanes_box[0], params[0])
        d["_sigkey_memo"] = (params, k)
        if sk is not None:
            if len(cache) > 65536:
                cache.clear()
            cache[(params, sk)] = k
        return k

    def _batch_signature_keys(self, batch):
        """signature_key per pod via _pod_sig_key's two-level memo, shared
        by the chain quickcheck, the fast gate, and batch extension.
        Returns the full key list, or None when any pod is ineligible."""
        vocab = self.mirror.vocab
        n_lanes = self.mirror.nodes.allocatable.shape[1]
        params = (n_lanes, len(vocab.resources))
        lanes_box: list = [None]
        keys = []
        append = keys.append
        for qp in batch:
            # inline the per-pod memo hit (the steady-state case: every pod
            # was keyed once by the extension predicate already)
            memo = qp.pod.__dict__.get("_sigkey_memo")
            if memo is not None and memo[0] == params:
                k = memo[1]
            else:
                k = self._pod_sig_key(qp.pod, params, lanes_box)
            if k is None:
                return None
            append(k)
        return keys

    def _chain_restart(self, vocab, epoch):
        """(Re)start the chain from the host mirror (the pipeline is settled,
        so its tensors are the ground truth).  Returns the new chain, or
        None on a persistent placement failure (hbm_oom class): the caller
        bails to the direct path, which owns the serial fallback."""
        from kubernetes_tpu.observability import kernels as kernels_mod

        with self._span("chain_dispatch.sync"):
            try:
                dc = self._sync_device_cluster(vocab)
            except kernels_mod.DispatchFailed as e:
                self._note_dispatch_failure(e)
                return None
            # the chain will donate/diverge these buffers — the delta
            # cache must not touch them again
            self._dc_cache.invalidate()
        return {
            "dc": dc,
            "e": self.mirror.e_used,
            "m": self.mirror.m_used,
            "epoch": epoch,
        }

    def _try_dispatch_chained(self, fwk, batch, outcomes, pipe) -> routing.Answer:
        """Offer the batch to the chained device cluster: in flight (the
        pending record), handled (nothing left to schedule), settle (the
        pipeline must be empty before the chain can restart), declined
        (fall back to the next engine), or serial (an abandoned dispatch's
        live batch).

        The ``chain_dispatch`` span is divided into consecutive,
        disjoint parts (``chain_dispatch.lock_wait``, ``.pack``, ``.repack``,
        ``.sync``, ``.prefilter``, ``.h2d``, ``.tables``, ``.submit``,
        ``.release``); the capacity checks and the record stay outside them.
        ``release`` is ``_dispatch_chained``'s return once a dispatch went
        out: ``Scheduler._mu`` released, then the frame's teardown dropping
        the batch's locals.  Deleting a device array that the dispatch in
        flight reads (the ``DeviceBatch``'s) hands the interpreter lock
        away, and beside binding the wait to win it back is most of the
        interval."""
        # the host's side of one chained dispatch (prep under
        # the lock, tables, the dispatch call): its own phase —
        # the chained path books no pack/h2d/device
        with self._span("chain_dispatch"):
            sp_release = self._span("chain_dispatch.release")
            ans = self._dispatch_chained(fwk, batch, outcomes, pipe.empty, sp_release)
            if ans.status is routing.Offer.IN_FLIGHT:
                sp_release.end()
            return ans

    def _dispatch_chained(self, fwk, batch, outcomes, can_restart, sp_release):
        """``_try_dispatch_chained``'s body, in a frame of its own so that
        its teardown can be spanned: ``sp_release`` is begun at the return
        of a pending record, and only there."""
        from kubernetes_tpu.observability import kernels as kernels_mod
        from kubernetes_tpu.ops import chain as chain_ops

        sp_lock = self._span("chain_dispatch.lock_wait").begin()
        with self._mu:
            sp_lock.end()
            with self._span("chain_dispatch.pack"):
                vocab = self.mirror.vocab
                for qp in batch:
                    for k, v in qp.pod.labels.items():
                        vocab.intern_label(k, v)
                epoch = self._chain_epoch(vocab)
                ch = self._chain
                if (ch is None or ch["epoch"] != epoch) and not can_restart:
                    return routing.SETTLE

            # ---- side-effect-free preparation: every bail-out below must
            # happen BEFORE PreFilter runs (its failures mutate outcomes/
            # queue/nominator and must not be replayed by the direct path)
            with self._span("chain_dispatch.repack"):
                self._repack_mirror()
            with self._span("chain_dispatch.pack"):
                pods = [qp.pod for qp in batch]
                self._p_cap_max = max(self._p_cap_max, self._p_bucket(len(pods)))
                pb = pack_pod_batch(
                    pods,
                    vocab,
                    k_cap=self.mirror.nodes.k_cap,
                    p_cap=self._p_cap_max,
                    namespace_labels=self.namespace_labels,
                )
                epoch = self._chain_epoch(vocab)  # interning may have grown it
                ch = self._chain
            if ch is None or ch["epoch"] != epoch:
                if not can_restart:
                    # packing interned new vocab (epoch moved) — the
                    # pipeline must settle before a host-state restart
                    return routing.SETTLE
                # this is still the side-effect-free prep, so declining is safe
                ch = self._chain_restart(vocab, epoch)
                if ch is None:
                    return routing.DECLINED
            # capacity/width checks against the CHAINED cluster's own
            # tensors — the live host mirror may have repacked to different
            # buckets mid-chain
            cdc = ch["dc"]
            dc_shapes = (
                cdc.term_table.req_key.shape[2],
                cdc.term_table.req_vals.shape[3],
                cdc.term_ns_ids.shape[1],
                cdc.epod_labels.shape[1],
            )
            if not chain_ops.caps_compatible(dc_shapes, pb):
                return routing.DECLINED
            P = pb.valid.shape[0]
            append_terms = bool((pb.aff_kind != PAD).any())
            AT = pb.aff_kind.shape[1] if append_terms else 0
            E = cdc.epod_node.shape[0]
            M = cdc.term_pod.shape[0]
            if ch["e"] + P > E or ch["m"] + P * AT > M:
                # cursor overflow: compact AND grow the host axes (the
                # append-only host path never enlarges them on its own),
                # then restart the chain once from the repacked state
                self._chain = None
                if not can_restart:
                    return routing.SETTLE
                self.mirror._m_cap_max = max(
                    self.mirror._m_cap_max,
                    bucket_cap(max((ch["m"] + P * AT) * 2, 1), 1),
                )
                self.mirror.e_cap_hint = max(
                    self.mirror.e_cap_hint, ch["e"] + 2 * P
                )
                self.mirror._epod_slots = None  # full existing repack
                self.mirror._existing_version = -1
                ch = self._chain_restart(vocab, epoch)
                if ch is None:
                    return routing.DECLINED  # direct path owns the serial fallback
                cdc = ch["dc"]
                E = cdc.epod_node.shape[0]
                M = cdc.term_pod.shape[0]
                if ch["e"] + P > E or ch["m"] + P * AT > M:
                    return routing.DECLINED  # genuinely beyond capacity — direct path

            # ---- PreFilter (side effects OK now: the dispatch is certain)
            with self._span("chain_dispatch.prefilter"):
                state = CycleState()
                pf_failures = fwk.run_pre_filter(state, [qp.pod for qp in batch])
                if pf_failures:
                    live = []
                    for qp in batch:
                        s = pf_failures.get(qp.pod.uid)
                        if s is None:
                            live.append(qp)
                            continue
                        self.metrics["schedule_attempts"] += 1
                        outcomes.append(
                            self._post_filter_or_fail(fwk, state, qp, s, 0)
                        )
                    batch = live
                    if not batch:
                        return routing.HANDLED
            if pf_failures:
                # repack without the rejected pods (their rows must not
                # reach the device as schedulable entries)
                with self._span("chain_dispatch.pack"):
                    pods = [qp.pod for qp in batch]
                    pb = pack_pod_batch(
                        pods,
                        vocab,
                        k_cap=self.mirror.nodes.k_cap,
                        p_cap=self._p_cap_max,
                        namespace_labels=self.namespace_labels,
                    )
                    append_terms = bool((pb.aff_kind != PAD).any())
                    AT = pb.aff_kind.shape[1] if append_terms else 0

            with self._span("chain_dispatch.h2d"):
                db = self._place_db(DeviceBatch.from_host(pb))
            with self._span("chain_dispatch.tables"):
                v_cap = bucket_cap(len(vocab.label_vals))
                tables = self._gang_tables(pb, vocab)
                nom_node = nom_prio = nom_req = None
                if len(self.nominator):
                    nom_node, nom_prio, nom_req = self._nominated_arrays(
                        {qp.pod.uid for qp in batch}
                    )
                # any term row in the chained cluster (host rows OR device-
                # appended ones, which ch["m"] counts past) keeps interpod on
                has_interpod = bool((pb.aff_kind != PAD).any()) or ch["m"] > 0
                has_spread = bool((pb.tsc_topo_key != PAD).any())
                has_images = bool((pb.img_ids >= 0).any())
                has_ports = bool(
                    (pb.want_ppk != PAD).any()
                    or (self.mirror.nodes.used_ppk != PAD).any()
                )
                enabled = fwk.device_enabled()
                weights = tuple(
                    fwk.score_weights.get(n, 0) for n in gang.WEIGHT_ORDER
                )
                fit_strategy = fwk.fit_strategy()
                # cross-pod-constraint batches ride the speculative wave
                # inside the chained dispatch (same self-append, wave
                # scheduling) — computed from the FINAL pb (post-PreFilter
                # repack); the chained dispatch consults no wave breaker
                wt = self._wave_tables_for(pb)
                wave_kw = {}
                ss = None
                if wt is not None:
                    wave_kw = dict(
                        wave=True,
                        tid_sp=wt["tid_sp"],
                        rep_sp_p=wt["rep_sp_p"],
                        rep_sp_c=wt["rep_sp_c"],
                        tid_ip=wt["tid_ip"],
                        rep_ip_p=wt["rep_ip_p"],
                        rep_ip_u=wt["rep_ip_u"],
                        ip_cdv_tab=wt["ip_cdv_tab"],
                        d2_cap=wt["d2_cap"],
                        wave_ports=wt["has_ports"],
                        tid_pt=wt["tid_pt"],
                        port_conf=wt["port_conf"],
                    )
                    ss = self._static_signatures(pb)
                    if ss is not None:
                        wave_kw.update(sig=ss["sig"], rep_pod=ss["rep_pod"])
            t0 = time.perf_counter()
            with self._span("chain_dispatch.submit"):
                try:
                    out = chain_ops.chain_dispatch(
                        ch["dc"],
                        db,
                        self._hostname_dev(vocab),
                        jnp.asarray(ch["e"], I32),
                        jnp.asarray(ch["m"], I32),
                        v_cap,
                        has_interpod=has_interpod,
                        has_spread=has_spread,
                        has_ports=has_ports,
                        has_images=has_images,
                        enabled=enabled,
                        weights=weights,
                        nom_node=nom_node,
                        nom_prio=nom_prio,
                        nom_req=nom_req,
                        append_terms=append_terms,
                        fit_strategy=fit_strategy,
                        **wave_kw,
                        **tables,
                    )
                except kernels_mod.DispatchFailed as e:
                    # the chained cluster was donated into the dead dispatch —
                    # drop the chain (the next batch rebuilds from the host
                    # mirror) and hand the LIVE batch back for the serial
                    # host-oracle fallback; nothing was committed, so the
                    # fallback is exact.  The serial drain itself runs in
                    # the caller OUTSIDE this lock — the snapshot-under-lock
                    # / replay-outside-lock discipline every other serial
                    # engine follows.
                    self._note_dispatch_failure(e)
                    self._chain = None
                    return routing.Answer(routing.Offer.SERIAL, batch=batch)
            if wt is not None:
                dc2, results, reasons, wstats = out
            else:
                dc2, results, reasons = out
                wstats = None
            self._chain = {
                "dc": dc2,
                "e": ch["e"] + P,
                "m": ch["m"] + P * AT,
                "epoch": epoch,
            }
            if wt is not None:
                self.metrics["wave_batches"] += 1
            else:
                self.metrics["chain_batches"] = (
                    self.metrics.get("chain_batches", 0) + 1
                )
            # start the host copy of the results as soon as the device
            # finishes this batch — by harvest time it's already local
            try:
                results.copy_to_host_async()
                reasons.copy_to_host_async()
                if wstats is not None:
                    wstats.copy_to_host_async()
            except AttributeError:
                pass
            rec = {
                "fwk": fwk,
                "state": state,
                "batch": batch,
                "results": results,
                "reasons": reasons,
                "wave_stats": wstats,
                "static_sigs": ss["n_valid"] if ss else None,
                "n_terms": wt["n_terms"] if wt else 0,
                "e_rows": ch["e"],
                "t0": t0,
            }
            self._trace_dispatch("wave" if wt is not None else "chain", t0, batch, rec)
            ans = routing.Answer(routing.Offer.IN_FLIGHT, rec)
            sp_release.begin()
            return ans

    def _finish_chained(self, rec) -> List[ScheduleOutcome]:
        """Harvest one pipelined batch: fetch its results and walk the
        commits (the host half that overlapped later dispatches)."""
        outcomes: List[ScheduleOutcome] = []
        tr = self.tracer
        t_h = tr.now() if tr.enabled else None
        sp_d2h = self._span("d2h").begin()
        from kubernetes_tpu.observability import kernels as kernels_mod

        n_bound = len(self.mirror.nodes.names)

        def _validate_chain(fetched):
            import numpy as np

            arr = np.asarray(fetched)
            if ((arr[0] < -1) | (arr[0] >= n_bound)).any():
                return "chosen index out of node range"
            return None

        try:
            both = self._d2h_guarded(
                rec["results"],
                kernel="chain.chain_dispatch",
                validate=_validate_chain,
            )
        except kernels_mod.DispatchFailed as e:
            # unrecoverable harvest: the chain's device state already
            # includes these commits, so drop it (the next batch rebuilds
            # from the host mirror) and re-derive the batch serially —
            # bit-identical placements, so host state stays consistent
            self._note_dispatch_failure(e)
            with self._mu:
                self._chain = None
            outcomes.extend(
                self._schedule_batch_serial(rec["fwk"], rec["batch"])
            )
            self._flush_binds()
            return outcomes
        sp_d2h.end()
        wstats = rec.get("wave_stats")
        self.prom.recorder.observe(
            self.prom.gang_dispatch_duration,
            time.perf_counter() - rec["t0"],
            path="wave" if wstats is not None else "chain",
        )
        wave_bulk = False
        if wstats is not None:
            wave_bulk = self._wave_resolve(
                rec["fwk"],
                rec["batch"],
                both[0],
                wstats,
                rec["e_rows"],
                kernel="chain.chain_dispatch",
                static_sigs=rec["static_sigs"],
                n_terms=rec["n_terms"],
            )
        self._process_results(
            rec["fwk"],
            rec["state"],
            rec["batch"],
            both[0],
            both[1],
            rec["reasons"],
            outcomes,
            wave_bulk=wave_bulk,
            kernel="chain.chain_dispatch",
        )
        self._record_batch_metrics(
            rec["fwk"].profile_name,
            rec["batch"],
            outcomes,
            time.perf_counter() - rec["t0"],
        )
        self._flush_binds()
        if t_h is not None and tr.enabled:
            tr.complete(
                "harvest.wave" if wstats is not None else "harvest.chain",
                t_h,
                cat="batch",
                bid=rec.get("bid"),
                pods=len(rec["batch"]),
            )
        return outcomes

    def _hostname_dev(self, vocab):
        hk_id = vocab.label_keys.lookup(HOSTNAME_LABEL)
        if getattr(self, "_hk_cached", None) != hk_id:
            self._hostname_key_dev = jnp.asarray(hk_id, I32)
            self._hk_cached = hk_id
        return self._hostname_key_dev

    def _place_db(self, db):
        """Mesh placement for a DeviceBatch: pod-major tensors sharded
        over the mesh's pods axis (no-op without meshDispatch).  The
        snapshot half rides DeviceClusterCache(mesh=...)."""
        if self.mesh is None:
            return db
        from kubernetes_tpu.parallel.mesh import place_batch

        return place_batch(self.mesh, db)

    def _p_bucket(self, n: int) -> int:
        """Pod-batch bucket: bucket_cap padded to the mesh's pods-axis
        multiple so sharded batches always split evenly (power-of-two
        buckets already divide power-of-two axes; this covers the rest)."""
        cap = bucket_cap(n, 1)
        if self.mesh is not None:
            from kubernetes_tpu.parallel.mesh import pad_to_multiple

            cap = pad_to_multiple(cap, self.mesh.shape["pods"])
        return cap

    def _gang_tables(self, pb, vocab):
        """batch_tables' device arrays, reused across batches with the same
        key sets + node labels (re-uploading them each batch costs a
        host→device transfer per table).  Called once a dispatch: one whose
        statics sum the hostname key by its domain map, not by node identity
        (``sp_host_cdv``: a hostname spread slot in the batch and two nodes
        under one hostname value), counts one ``statics.host_by_domain``."""
        import numpy as np

        hk_id = vocab.label_keys.lookup(HOSTNAME_LABEL)
        tkey = (
            self.mirror.static_generation,
            self.mirror._full_packs,
            len(vocab.label_vals),
            tuple(np.unique(pb.tsc_topo_key).tolist()),
            tuple(np.unique(pb.aff_topo_key).tolist()),
        )
        if getattr(self, "_tables_key", None) != tkey:
            self._tables = gang.batch_tables(
                pb.tsc_topo_key,
                pb.aff_topo_key,
                self.mirror.nodes.label_vals,
                hk_id,
                hostnames_unique=self.mirror.hostnames_unique,
            )
            self._tables_key = tkey
        if self._tables["sp_host_cdv"] is not None:
            self.prom.statics_host_by_domain.inc()
            self.phases.count("statics.host_by_domain", 1)
        return self._tables

    def _wave_tables_for(self, pb, breaker: Optional[str] = None):
        """WAVE eligibility, decided once for the direct and the chained
        dispatch: batches carrying their own cross-pod
        constraints — spread/inter-pod terms OR in-batch host ports
        — ride the speculative wave dispatch (ops/wave.py):
        speculation + term-factored conflict resolution,
        bit-identical to the scan at a fraction of its per-step
        cost.  Port users ride the [Tpt, N] occupancy carry and
        sampling-compat / seeded-tie drains replay their window +
        rotation per step, so neither falls back any more; the only
        remaining disqualifier is duplicate hostname labels
        (_wave_tables → mirror.hostnames_unique).  Every fallback
        bumps scheduler_tpu_wave_fallback_total{reason=}.  Returns the
        wave's tables, or None: the batch rides the gang scan.  ``breaker``
        names the kernel whose open breaker also sends it there (the direct
        path's ``wave.wave_run``; port batches never reach the chained
        dispatch, _chain_quickcheck refuses them)."""
        if not (
            (pb.aff_kind != PAD).any()
            or (pb.tsc_topo_key != PAD).any()
            or (pb.want_ppk != PAD).any()
        ):
            return None
        if not self.config.wave_dispatch:
            self.prom.wave_fallback.inc(reason="kill_switch")
            return None
        if breaker is not None and self._breaker_blocked(breaker):
            return None
        wt = self._wave_tables(pb)
        if wt is None:
            self.prom.wave_fallback.inc(reason="dup_hostname")
        return wt

    def _wave_tables(self, pb):
        """Host half of the wave's interaction partitioner: distinct-term
        tables (spread + inter-pod + port) for the factored admission pass
        (ops/wave.py).  None only when duplicate hostname labels disqualify
        the factored algebra — the caller falls back to the gang scan.

        Memoized like _gang_tables: template-stamped drains repeat the
        same term content batch after batch, so the np.unique row-dedup
        and per-key domain compaction collapse to one digest check.

        The term buckets are STICKY (``_t_cap_max``, as ``_p_cap_max`` is for
        the pod axis): a batch is given at least the largest bucket a batch
        before it was, so a drain whose batches hold pods of hundreds of
        Deployments — a count of distinct terms that moves from batch to
        batch, and falls on the drain's short last one — dispatches ONE
        admission program once the bucket has grown, and the window meets no
        bucket its warm-up did not (WAVE.md "Many terms a batch")."""
        import hashlib

        import numpy as np

        from kubernetes_tpu.ops import wave as wave_ops

        hk_id = self.mirror.vocab.label_keys.lookup(HOSTNAME_LABEL)
        h = hashlib.blake2b(digest_size=16)
        for a in (
            pb.valid,
            pb.ns_id,
            pb.want_ppk,
            pb.want_ip,
            pb.want_wild,
            pb.tsc_topo_key,
            pb.tsc_table.req_key,
            pb.tsc_table.req_op,
            pb.tsc_table.req_vals,
            pb.tsc_table.req_rhs,
            pb.tsc_table.term_valid,
            pb.aff_kind,
            pb.aff_topo_key,
            pb.aff_weight,
            pb.aff_ns_all,
            pb.aff_ns_ids,
            pb.aff_table.req_key,
            pb.aff_table.req_op,
            pb.aff_table.req_vals,
            pb.aff_table.req_rhs,
            pb.aff_table.term_valid,
        ):
            h.update(np.ascontiguousarray(a).tobytes())
        key = (
            self.mirror.static_generation,
            self.mirror._full_packs,
            len(self.mirror.vocab.label_vals),
            hk_id,
            self._t_cap_max,
            h.digest(),
        )
        cached = getattr(self, "_wave_tables_memo", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        wt = wave_ops.wave_tables(
            pb,
            self.mirror.nodes.label_vals,
            hk_id,
            hostnames_unique=self.mirror.hostnames_unique,
            t_floor=self._t_cap_max,
        )
        if wt is not None:
            self._t_cap_max = wt["t_caps"]
        self._wave_tables_memo = (key, wt)
        return wt

    def _static_signatures(self, pb):
        """The batch's distinct pod rows for gang.precompute's table
        (wave.static_signatures): {sig, rep_pod, n_valid}, or None when the
        batch has more distinct rows than the one bucket — the dispatch is
        then the per-pod program.

        Memoized like _wave_tables, under a digest of its own over EVERY
        leaf: a template-stamped drain repeats one content batch after
        batch and keeps the two device arrays; a leaf the term tables do
        not read (a toleration, a priority) misses here only.  (Leaf by
        leaf: stacking the rows first costs three times the digest.)"""
        import hashlib

        import numpy as np

        from kubernetes_tpu.ops import wave as wave_ops

        h = hashlib.blake2b(digest_size=16)
        shapes = []
        for a in wave_ops.batch_leaves(pb):
            shapes.append(a.shape)
            h.update(np.ascontiguousarray(a).tobytes())
        key = (tuple(shapes), h.digest())
        cached = getattr(self, "_static_sigs_memo", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        ss = wave_ops.static_signatures(pb)
        self._static_sigs_memo = (key, ss)
        return ss

    # ----- the workloads tier: gang/coscheduling + DRA + volume topology ----
    #
    # One fused dispatch (ops/coscheduling.py) schedules batches carrying
    # PodGroup gangs, DRA resource claims, and bound-volume topology —
    # workloads the per-pod reference pipeline (and our one-pod fallback)
    # handles only serially.  Gangs admit all-or-nothing with device-side
    # rollback; claims allocate inside the admission scan so in-batch
    # contention resolves in queue order; volume topology rides a kernel
    # mask.  Behind the gangDispatch kill-switch; bit-identical to the
    # serial gang/DRA oracle (oracle/workloads.py, paritycheck.py).

    def _pull_gang_siblings(self, batch):
        """Queue-level gang sibling-pull: when a popped batch carries gang
        members whose quorum the batch itself cannot cover, pop the gangs'
        remaining ACTIVE members into the same batch (QueueSort order
        preserved among them).  Backoff/unschedulable members stay parked —
        their gates still apply — so an uncoverable gang still takes the
        waiting/timeout barrier, just without burning an attempt per pop
        split.  Caller holds _mu.  Gang-free batches pay one dict probe
        per pod and never scan the queue."""
        present: Dict[str, int] = {}
        for qp in batch:
            key = self._workloads_group_of(qp.pod)
            if key is not None:
                present[key] = present.get(key, 0) + 1
        wanted = set()
        for key, n in present.items():
            pg = self.gangs.get(key)
            if pg is not None and n + self.gangs.bound_count(key) < pg.min_member:
                wanted.add(key)
        if not wanted:
            return []
        return self.queue.pop_siblings(
            lambda qp: self._workloads_group_of(qp.pod) in wanted
        )

    def _workloads_group_of(self, pod):
        """Gang key of a pod, or None when it has no REGISTERED PodGroup
        (pods referencing an unknown group schedule as ordinary pods)."""
        key = wlg.group_key_of(pod)
        if key is None or self.gangs.get(key) is None:
            return None
        return key

    def _vol_kernel_ok(self, pod) -> bool:
        """True when the pod's volume surface is exactly what the kernel
        mask covers: every PVC exists, fully bound, its PV present.  Any
        other shape (WaitForFirstConsumer, immediate unbound, missing PV)
        keeps the serial VolumeBinding path — including its
        unresolvable-status semantics."""
        for name in pod.pvc_names():
            pvc = self.pvc_cache.get(f"{pod.namespace}/{name}")
            if pvc is None or not pvc.is_fully_bound():
                return False
            if self.pv_cache.get(pvc.volume_name) is None:
                return False
        return True

    def _workloads_eligible(self, fwk, batch) -> bool:
        """Spec-only pre-gate: True when the batch MIGHT take the
        workloads dispatch — at least one gang/DRA/volume-kernel pod, and
        none of the spec-level disqualifiers (nominations, extenders, host
        ports, score-relevant host plugins, sampling compat).  The
        host-filter COVERAGE check runs post-PreFilter inside the dispatch
        (_workloads_covered), where the plugins' Skip verdicts are known."""
        if not self.config.gang_dispatch or self._sampling_active(fwk):
            return False
        hf_names = {p.name for p in fwk.host_filter_plugins()}
        dra_on = "DynamicResources" in hf_names
        vol_on = "VolumeBinding" in hf_names
        # cheap O(P) relevance pass FIRST: the common direct-path batch has
        # no gang/claim/volume pod at all and must not pay the plugin /
        # extender disqualifier scan below
        if not any(
            (dra_on and qp.pod.resource_claims)
            or (vol_on and qp.pod.pvc_names())
            or self._workloads_group_of(qp.pod) is not None
            for qp in batch
        ):
            return False
        ns_plugins = routing.normalizing_score_plugins(fwk)
        host_scores = routing.weighted_host_scores(fwk)
        for qp in batch:
            pod = qp.pod
            if pod.nominated_node_name or pod.host_ports():
                return False
            for e in self.extenders:
                if e.is_interested(pod):
                    return False
            for pl in ns_plugins:
                if pl.score_relevant(pod):
                    return False
            for pl in host_scores:
                if pl.score_relevant(pod):
                    return False
            if (
                vol_on
                and pod.pvc_names()
                and not self._vol_kernel_ok(pod)
            ):
                return False
        return True

    def _workloads_covered(self, fwk, state, pods) -> bool:
        """Post-PreFilter coverage check: every host Filter plugin still
        ACTIVE for some pod must be one the kernel replaces —
        DynamicResources (the batched allocator), VolumeBinding
        (bound-topology kernel mask; _vol_kernel_ok pre-checked),
        VolumeZone (zone-labeled PV constraints fold into the same mask as
        per-label In-conjunctions — _vol_tables), or NodeVolumeLimits when
        no CSINode advertises limits (its Filter is then a constant
        success).  Anything else falls back to the serial split path."""
        for p in fwk.host_filter_plugins():
            if p.name in ("DynamicResources", "VolumeBinding", "VolumeZone"):
                continue
            if p.name == "NodeVolumeLimits" and not self.csinodes:
                continue
            for pod in pods:
                if not state.is_filter_skipped(pod.uid, p.name):
                    return False
        return True

    def _hostnames_unique(self) -> bool:
        """The wave/workloads factored algebra treats hostname topology as
        node identity — duplicate hostname label values disqualify it.
        The bit is computed once per SNAPSHOT by the mirror (memoized on
        the static lineage), not re-derived per batch."""
        return self.mirror.hostnames_unique

    def _vol_tables(self, pods, p_cap: int, vocab):
        """Pack bound-PV node-affinity DNFs into the volume-topology kernel
        mask's tables: one PV per PV2 slot, ORed selector terms on the
        DTable term axis (ops/coscheduling.volume_topology_mask).  A PV
        carrying zone/region LABELS (the pre-CSI topology convention the
        VolumeZone plugin judges) contributes one extra slot whose single
        conjunction requires ``key In zone-set`` per topology label — the
        AND across slots reproduces volume_zone.go's every-label-must-
        match semantics, so zone-labeled shapes ride the kernel instead of
        falling back to the serial path.  Returns None when no pod
        carries an affinity- or zone-constrained bound PV."""
        import numpy as np

        from kubernetes_tpu.api import labels as k8slabels
        from kubernetes_tpu.api import storage as storage_api
        from kubernetes_tpu.framework.volume_plugins import _zone_value_set
        from kubernetes_tpu.ops.common import DTable
        from kubernetes_tpu.snapshot.schema import pack_conjunction_table
        from kubernetes_tpu.snapshot.selectors import (
            CompiledRequirements,
            compile_node_selector_dnf,
        )

        per_pod: List[list] = []
        bad = np.zeros((p_cap,), bool)
        any_rows = False
        for i, pod in enumerate(pods):
            rows = []
            for name in pod.pvc_names():
                pvc = self.pvc_cache.get(f"{pod.namespace}/{name}")
                if pvc is None or not pvc.is_fully_bound():
                    bad[i] = True  # gate should have routed this away
                    continue
                pv = self.pv_cache.get(pvc.volume_name)
                if pv is None:
                    bad[i] = True
                    continue
                zone_c = CompiledRequirements()
                for key in storage_api.VOLUME_TOPOLOGY_LABELS:
                    if key in pv.labels:
                        zone_c.add(
                            key,
                            k8slabels.IN,
                            sorted(_zone_value_set(pv.labels[key])),
                            vocab,
                        )
                if zone_c.n_reqs:
                    rows.append([zone_c])
                if pv.node_affinity is None:
                    continue  # nil affinity matches everywhere
                rows.append(compile_node_selector_dnf(pv.node_affinity, vocab))
            per_pod.append(rows)
            any_rows = any_rows or bool(rows)
        if not any_rows and not bad.any():
            return None
        pv_cap = bucket_cap(max((len(r) for r in per_pod), default=1) or 1, 1)
        flat: List[list] = []
        valid = np.zeros((p_cap, pv_cap), bool)
        for i in range(p_cap):
            rows = per_pod[i] if i < len(per_pod) else []
            for j in range(pv_cap):
                if j < len(rows):
                    flat.append(rows[j])
                    valid[i, j] = True
                else:
                    flat.append([])
        ct = pack_conjunction_table(flat)
        T, R, V = ct.req_key.shape[1], ct.req_key.shape[2], ct.req_vals.shape[3]

        def rs(a, tail):
            return jnp.asarray(
                np.asarray(a).reshape((p_cap, pv_cap) + tail)
            )

        table = DTable(
            req_key=rs(ct.req_key, (T, R)),
            req_op=rs(ct.req_op, (T, R)),
            req_vals=rs(ct.req_vals, (T, R, V)),
            req_rhs=rs(ct.req_rhs, (T, R)),
            term_valid=rs(ct.term_valid, (T,)),
        )
        return dict(
            vol_table=table,
            vol_valid=jnp.asarray(valid),
            vol_bad=jnp.asarray(bad),
        )

    def _try_dispatch_workloads(self, fwk, batch):
        """The workloads dispatch: gang planning + one fused admission
        kernel + the commit walk.  Returns the outcome list, or None when
        the batch should fall through to the existing machinery (the
        caller treats None as "not handled"; nothing is committed or
        failed before eligibility is certain)."""
        from kubernetes_tpu.ops import coscheduling as cos_ops
        from kubernetes_tpu.ops import dra as dra_ops

        if not self._workloads_eligible(fwk, batch):
            return None
        # device-fault tier: an open workloads breaker refuses the path
        # BEFORE any side effect — the caller falls through to the
        # existing machinery, i.e. the gangDispatch kill-switch fallback
        # (decision-identical for DRA/volume pods; gang pods schedule
        # individually, exactly the documented degraded semantics)
        if self._breaker_blocked("coscheduling.workloads_run"):
            return None
        outcomes: List[ScheduleOutcome] = []
        self._chain = None
        with self._mu:
            state = CycleState()
            vocab = self.mirror.vocab
            for qp in batch:
                for k, v in qp.pod.labels.items():
                    vocab.intern_label(k, v)
            self._sync_mirror_external()
            if not self._hostnames_unique():
                return None  # factored hostname-domain trick invalid
            from kubernetes_tpu.metrics import Trace

            trace = Trace(
                "Scheduling workloads batch",
                clock=time.perf_counter,
                pods=len(batch),
                profile=fwk.profile_name,
            )

            # 0. PreFilter (missing/deleted claims and PVCs reject here).
            # Failures are NOT emitted until the coverage check commits to
            # this path — a fallback must leave no trace.
            pf_failures = (
                fwk.run_pre_filter(state, [qp.pod for qp in batch]) or {}
            )
            live_pods = [
                qp.pod for qp in batch if qp.pod.uid not in pf_failures
            ]
            if not self._workloads_covered(fwk, state, live_pods):
                return None  # an uncovered host filter is active — serial
            if pf_failures:
                live = []
                for qp in batch:
                    s = pf_failures.get(qp.pod.uid)
                    if s is None:
                        live.append(qp)
                        continue
                    self.metrics["schedule_attempts"] += 1
                    outcomes.append(
                        self._post_filter_or_fail_locked(
                            fwk, state, qp, s, 0
                        )
                    )
                batch = live
                if not batch:
                    return outcomes
            trace.step("PreFilter done")

            # 1. gang planning: quorum/timeout barriers reject pre-dispatch
            # (the coscheduling plugin's PreFilter/Permit-timeout verdicts)
            keys = [self._workloads_group_of(qp.pod) for qp in batch]
            present: Dict[str, int] = {}
            for key in keys:
                if key is not None:
                    present[key] = present.get(key, 0) + 1
            needs: Dict[str, int] = {}
            rejected: Dict[str, Status] = {}
            for key, n_present in present.items():
                pg = self.gangs.get(key)
                bound = self.gangs.bound_count(key)
                if self.gangs.timed_out(key):
                    rejected[key] = Status.unresolvable(
                        f'pod group "{key}" scheduling timed out after '
                        f"{pg.schedule_timeout_s:.0f}s",
                        plugin="Coscheduling",
                    )
                    self.gangs.close_window(key)
                elif n_present + bound < pg.min_member:
                    rejected[key] = Status.unschedulable(
                        f'pod group "{key}" has {n_present + bound}/'
                        f"{pg.min_member} members; waiting for the rest",
                        plugin="Coscheduling",
                    )
                    self.gangs.note_attempt(key)
                else:
                    needs[key] = max(0, pg.min_member - bound)
                    self.gangs.note_attempt(key)
            if rejected:
                live = []
                for qp, key in zip(batch, keys):
                    if key in rejected:
                        s = rejected[key]
                        self.metrics["schedule_attempts"] += 1
                        if self.flight.enabled:
                            self.flight.record(
                                qp.pod.uid,
                                "unschedulable",
                                {"plugins": ["Coscheduling"], "reasons": list(s.reasons)[:3]},
                            )
                        self._handle_failure(qp, s)
                        outcomes.append(
                            ScheduleOutcome(qp.pod, None, s, 0)
                        )
                    else:
                        live.append(qp)
                batch = live
                if not batch:
                    return outcomes

            # 2. canonical order: gang members contiguous at first member
            order, gang_positions = wlg.plan_batch(
                [qp.pod for qp in batch], group_of=self._workloads_group_of
            )
            ordered = [batch[i] for i in order]
            pods = [qp.pod for qp in ordered]
            trace.step("Gang plan done")

            # 3. pack (the scan path's prep, workloads tables added)
            enabled = fwk.device_enabled()
            weights = tuple(
                fwk.score_weights.get(n, 0) for n in gang.WEIGHT_ORDER
            )
            with self._span("pack"):
                self._repack_mirror()
            self._p_cap_max = max(self._p_cap_max, self._p_bucket(len(pods)))
            p_cap = self._p_cap_max
            pb = pack_pod_batch(
                pods,
                vocab,
                k_cap=self.mirror.nodes.k_cap,
                p_cap=p_cap,
                namespace_labels=self.namespace_labels,
            )
            sp_h2d = self._span("h2d").begin()
            from kubernetes_tpu.observability import kernels as kernels_mod

            try:
                dc = self._sync_device_cluster(vocab)
            except kernels_mod.DispatchFailed as e:
                # persistent snapshot-placement failure (hbm_oom class)
                # PAST the commit point (PreFilter failures and quorum
                # rejections already emitted): finish the live pods on
                # the ordinary machinery — the same move as the
                # wave-tables drift guard below; nothing double-processes
                self._note_dispatch_failure(e)
                return outcomes + self._schedule_batch(
                    ordered, try_workloads=False
                )
            db = self._place_db(DeviceBatch.from_host(pb))
            sp_h2d.end()
            v_cap = bucket_cap(len(vocab.label_vals))
            hostname_key = self._hostname_dev(vocab)
            tables = self._gang_tables(pb, vocab)
            wt = self._wave_tables(pb)
            if wt is None:
                # The duplicate-hostname pre-check mirrors wave_tables'
                # only remaining refusal condition (in-batch ports ride
                # the factored port carry now), so this is unreachable
                # today — but PreFilter failures and quorum rejections
                # were already emitted above, so if the copies ever drift
                # the only safe move is to finish the REMAINING live pods
                # on the ordinary machinery (gang semantics degrade for
                # one batch; nothing double-processes).  Returning None
                # here instead would hand the caller the ORIGINAL batch,
                # re-processing pods whose failures already landed.
                return outcomes + self._schedule_batch(
                    ordered, try_workloads=False
                )
            has_interpod = bool(
                (pb.aff_kind != PAD).any()
                or (self.mirror.existing.term_kind != PAD).any()
            )
            has_spread = bool((pb.tsc_topo_key != PAD).any())
            has_images = bool((pb.img_ids >= 0).any())

            # 4. workloads tables: gang arrays + DRA pack + volume DNFs
            gid, gfirst, glast, gneed, g_cap, slot_keys = wlg.gang_arrays(
                p_cap, gang_positions, needs
            )
            dt = None
            claim_keys: List[str] = []
            dra_on = any(
                p.name == "DynamicResources"
                for p in fwk.host_filter_plugins()
            )
            claims_by_key = {}
            if dra_on and any(p.resource_claims for p in pods):
                # the WHOLE cache view, not just batch-referenced claims:
                # free0 must exclude devices held by ANY allocated claim
                # (the serial plugin's _allocated_devices walks the full
                # cache too) — a batch-local view would hand out devices
                # earlier drains already granted
                claims_by_key = {
                    c.key: c for c in self.claim_cache.list()
                }
                dt = dra_ops.dra_tables(
                    pods,
                    self.mirror.nodes.name_to_idx,
                    self.mirror.nodes.n_cap,
                    p_cap,
                    list(self.resource_slices.values()),
                    self.device_classes,
                    claims_by_key,
                )
                if dt is not None:
                    claim_keys = dt.pop("claim_keys")
                    dt.pop("has_claims")
            volt = self._vol_tables(pods, p_cap, vocab)
            nom_node = nom_prio = nom_req = None
            if len(self.nominator):
                nom_node, nom_prio, nom_req = self._nominated_arrays(
                    {qp.pod.uid for qp in ordered}
                )
            self.metrics["workload_batches"] += 1

        # 5. one fused dispatch (outside the lock, like every device path)
        from kubernetes_tpu.observability import kernels as kernels_mod

        t_gang = time.perf_counter()
        sp_dev = self._span("device").begin()
        try:
            chosen_dev, n_feas_dev, reason_counts, tallies, wl_dev = (
                cos_ops.workloads_run(
                dc,
                db,
                hostname_key,
                v_cap,
                g_cap,
                wt["tid_sp"],
                wt["rep_sp_p"],
                wt["rep_sp_c"],
                wt["tid_ip"],
                wt["rep_ip_p"],
                wt["rep_ip_u"],
                wt["ip_cdv_tab"],
                jnp.asarray(gid),
                jnp.asarray(gfirst),
                jnp.asarray(glast),
                jnp.asarray(gneed),
                **(dt or {}),
                **(volt or {}),
                has_interpod=has_interpod,
                has_spread=has_spread,
                has_images=has_images,
                enabled=enabled,
                weights=weights,
                nom_node=nom_node,
                nom_prio=nom_prio,
                nom_req=nom_req,
                    d2_cap=wt["d2_cap"],
                    fit_strategy=fwk.fit_strategy(),
                    **tables,
                )
            )
            sp_dev.end()
            sp_d2h = self._span("d2h").begin()
            n_bound = len(self.mirror.nodes.names)

            def _validate_wl(fetched):
                import numpy as np

                ch = np.asarray(fetched[0])
                if ((ch < -1) | (ch >= n_bound)).any():
                    return "chosen index out of node range"
                return None

            fetched = self._d2h_guarded(
                (
                    chosen_dev,
                    n_feas_dev,
                    wl_dev["raw"],
                    wl_dev["spec"],
                    wl_dev["gang_admit"],
                    wl_dev["gang_landed"],
                    wl_dev["claim_node"] if dt is not None else None,
                ),
                kernel="coscheduling.workloads_run",
                validate=_validate_wl,
            )
        except kernels_mod.DispatchFailed as e:
            # abandoned workloads dispatch: nothing committed yet — the
            # live batch degrades to per-pod host-plugin cycles (gang
            # members schedule individually, the documented kill-switch
            # semantics) while the breaker keeps the kernel parked
            self._note_dispatch_failure(e)
            return outcomes + self._schedule_batch_serial(fwk, ordered)
        chosen, n_feas, raw, spec, gang_admit, gang_landed, claim_node = (
            fetched
        )
        sp_d2h.end()
        self.prom.recorder.observe(
            self.prom.gang_dispatch_duration,
            time.perf_counter() - t_gang,
            path="workloads",
        )
        self._trace_dispatch("workloads", t_gang, ordered)
        trace.step("Workloads dispatch done")

        self._process_workloads_results(
            fwk,
            state,
            ordered,
            chosen,
            n_feas,
            raw,
            spec,
            reason_counts,
            gang_admit,
            gang_landed,
            gang_positions,
            slot_keys,
            needs,
            claim_keys,
            claims_by_key,
            claim_node,
            outcomes,
        )
        trace.step("Commits done")
        trace.log_if_long()
        return outcomes

    def _wl_host_replay(self, fwk, state, pod, node_name: str) -> Status:
        """Re-run PreFilter (fresh claim/volume ledgers) + the chosen
        node's host Filter walk for a DRA/volume pod, so Reserve/PreBind
        read per-pod decisions consistent with the live cache — the kernel
        proved feasibility; this materializes the concrete device/PV picks
        in cycle state, claim contention resolving in the same batch order
        the kernel replayed."""
        with self._mu:
            pf = fwk.run_pre_filter(state, [pod])
            if pf:
                s = pf.get(pod.uid)
                if s is not None:
                    return s
            st = self.oracle_view()
            ns = st.nodes.get(node_name)
            if ns is None:
                return Status.error(f"node {node_name} vanished", plugin="Workloads")
            return fwk.run_host_filters(state, pod, ns)

    def _process_workloads_results(
        self,
        fwk,
        state,
        ordered,
        chosen,
        n_feas,
        raw,
        spec,
        reason_counts,
        gang_admit,
        gang_landed,
        gang_positions,
        slot_keys,
        needs,
        claim_keys,
        claims_by_key,
        claim_node,
        outcomes,
    ) -> None:
        """The workloads result walk: gang admit/rollback accounting +
        flight events, rolled-back members failed WITHOUT preemption (a
        dry run for a pod its own gang rolled back just churns victims),
        genuine failures through the normal diagnosis path (DRA/volume
        lanes renamed to their plugin reasons), successes through the
        host-replay commit."""
        import numpy as np

        sp_commit = self._span("commit").begin()
        node_names = self.mirror.nodes.names
        n_nodes = len(self.cache.real_nodes())
        counts = None
        fr = self.flight
        chosen_n = np.asarray(chosen)[: len(ordered)]
        spec_n = np.asarray(spec)[: len(ordered)]
        sp_lock = self._span("commit.lock_wait").begin()
        with self._mu:
            sp_lock.end()
            self.metrics["schedule_attempts"] += len(ordered)
            # speculation stats: pods whose admitted placement survived
            # the serial admission pass unchanged (the wave's admitted-as-
            # speculated notion, here over gang/DRA-carried state)
            self.metrics["workload_spec_admitted"] += int(
                np.sum((chosen_n == spec_n) & (chosen_n >= 0))
            )
            # claim allocations count ONCE per newly-allocated claim (a
            # shared claim is one allocation however many pods reference
            # it; pre-allocated claims don't count)
            if claim_node is not None:
                new_allocs = sum(
                    1
                    for i, ckey in enumerate(claim_keys)
                    if int(claim_node[i]) >= 0
                    and claims_by_key[ckey].allocation is None
                )
                if new_allocs:
                    self.metrics["dra_claims_allocated"] += new_allocs
                    self.prom.dra_allocations.inc(new_allocs)
        pos_gang: Dict[int, str] = {}
        for key, positions in gang_positions.items():
            for pos in positions:
                pos_gang[pos] = key
        slot_of = {key: i for i, key in enumerate(slot_keys)}

        # gang verdicts: metrics + flight + scheduling-window bookkeeping
        for key, positions in gang_positions.items():
            slot = slot_of[key]
            admit = int(gang_admit[slot])
            landed = int(gang_landed[slot])
            with self._mu:
                if admit == 1:
                    self.gangs.close_window(key)
                    self.metrics["gang_admitted"] += landed
                    self.prom.gang_admitted.inc(landed)
                elif admit == 0:
                    self.metrics["gang_rolled_back"] += 1
                    self.prom.gang_rollbacks.inc()
            if fr.enabled:
                kind = "gang_admit" if admit == 1 else "gang_rollback"
                for pos in positions:
                    fr.record(
                        ordered[pos].pod.uid,
                        kind,
                        {
                            "group": key,
                            "landed": landed,
                            "need": needs.get(key, 0),
                        },
                    )

        for i, qp in enumerate(ordered):
            pod = qp.pod
            idx = int(chosen[i])
            if idx < 0:
                key = pos_gang.get(i)
                if key is not None and int(raw[i]) >= 0:
                    # placed by the admission pass, rolled back with its
                    # gang — not a feasibility failure, no preemption
                    slot = slot_of[key]
                    pg = self.gangs.get(key)
                    s = Status.unschedulable(
                        f'pod group "{key}" admission rolled back: '
                        f"{int(gang_landed[slot])}/"
                        f"{pg.min_member if pg else 0} members schedulable",
                        plugin="Coscheduling",
                    )
                    with self._mu:
                        self._handle_failure(qp, s)
                    outcomes.append(
                        ScheduleOutcome(pod, None, s, int(n_feas[i]))
                    )
                    continue
                if counts is None:
                    counts = self._d2h(
                        reason_counts, kernel="coscheduling.workloads_run"
                    )
                diag = {
                    k: int(c)
                    for k, c in zip(gang.DIAG_KERNELS, counts[i])
                    if c > 0
                }
                plugins = set(diag)
                # workloads batches carry no host ports, so the dynamic
                # hv lane counts exactly the DRA rejections; the extra
                # mask lane is the volume-topology kernel mask
                if "NodePorts" in diag and pod.resource_claims:
                    n = diag.pop("NodePorts")
                    plugins.discard("NodePorts")
                    diag["cannot allocate all devices"] = n
                    plugins.add("DynamicResources")
                if "HostFilters" in diag:
                    n = diag.pop("HostFilters")
                    plugins.discard("HostFilters")
                    diag["node(s) had volume node affinity conflict"] = n
                    plugins.add("VolumeBinding")
                status = Status.unschedulable(
                    fit_error_message(n_nodes, diag)
                )
                outcomes.append(
                    self._post_filter_or_fail(
                        fwk, state, qp, status, int(n_feas[i]), diag, plugins
                    )
                )
                continue
            node_name = node_names[idx]
            if pod.resource_claims or pod.pvc_names():
                s = self._wl_host_replay(fwk, state, pod, node_name)
                if not s.ok:
                    # a race moved the ground truth between dispatch and
                    # commit (informer event, concurrent binder) — fail
                    # the pod; the requeue converges like any lost race
                    outcomes.append(
                        self._post_filter_or_fail(
                            fwk, state, qp, s, int(n_feas[i])
                        )
                    )
                    continue
            outcome = self._commit(fwk, state, qp, node_name, int(n_feas[i]))
            if outcome.node is not None:
                with self._mu:
                    self.gangs.note_placed(pod)
                    if pod.resource_claims:
                        self.metrics["dra_pods"] += 1
                if fr.enabled and pod.resource_claims:
                    fr.record(
                        pod.uid,
                        "dra_alloc",
                        {
                            "node": node_name,
                            "claims": list(pod.resource_claims)[:4],
                        },
                    )
            outcomes.append(outcome)
        sp_commit.end()

    def _wave_resolve(
        self,
        fwk,
        batch,
        chosen,
        wstats_dev,
        e_rows,
        kernel=None,
        static_sigs=None,
        n_terms=0,
    ):
        """Harvest one wave's speculation stats: admitted/demoted counters
        (``wave.demoted`` and, with ``e_rows`` — the existing-pod rows live
        at the dispatch — ``wave.epod_rows`` go to the phase accumulator,
        as does each conflict kind as ``wave.conflicts.<kind>``;
        ``static_sigs`` — the distinct valid pod rows the dispatch computed
        its statics for — goes to ``wave.static_sigs``, and None, a
        dispatch that computed them per pod, counts one ``wave.static_full``;
        ``n_terms`` — the batch's distinct cross-pod terms, ``wave_tables``'
        count before the bucket — goes to ``wave.terms``),
        a ``wave_demoted`` flight-recorder event (with the conflicting
        term) per corrected pod.  Returns whether the batch's successes
        may commit as one bulk run (the framework permits lean binds and no
        per-pod extension point could act); False when commits must walk
        the per-pod path."""
        import numpy as np

        from kubernetes_tpu.ops import wave as wave_ops

        sp_resolve = self._span("wave_resolve").begin()
        stats = np.asarray(self._d2h(wstats_dev, kernel=kernel))
        n = len(batch)
        spec, kinds, cterms = stats[0][:n], stats[1][:n], stats[2][:n]
        chosen_n = np.asarray(chosen)[:n]
        demoted = np.nonzero(chosen_n != spec)[0]
        # "admitted" = a speculative PLACEMENT survived; pods unschedulable
        # in both passes are neither admitted nor demoted
        admitted = int(np.sum((chosen_n == spec) & (chosen_n >= 0)))
        conflicts: Dict[str, int] = {}
        fr = self.flight
        fr_on = fr.enabled
        names = self.mirror.nodes.names
        for i in demoted:
            code = int(kinds[i])
            upgraded = code == wave_ops.DEMOTE_UPGRADE
            if not upgraded:
                kind = wave_ops.DEMOTE_KINDS.get(code, "score")
                conflicts[kind] = conflicts.get(kind, 0) + 1
            if fr_on:
                c = int(chosen_n[i])
                if upgraded:
                    # infeasible alone, placed once a batch peer committed
                    # (required affinity satisfied) — not a conflict
                    detail = {}
                    if 0 <= c < len(names):
                        detail["node"] = names[c]
                    fr.record(batch[i].pod.uid, "wave_upgraded", detail)
                    continue
                detail = {"kind": kind, "term": int(cterms[i])}
                s = int(spec[i])
                if 0 <= s < len(names):
                    detail["spec_node"] = names[s]
                if 0 <= c < len(names):
                    detail["node"] = names[c]
                fr.record(batch[i].pod.uid, "wave_demoted", detail)
        with self._mu:
            self.metrics["wave_pods"] += n
            self.metrics["wave_admitted"] += admitted
        self.prom.wave_admitted.inc(admitted)
        self.phases.count("wave.demoted", len(demoted))
        self.phases.count("wave.epod_rows", e_rows)
        self.phases.count("wave.terms", n_terms)
        for kind, cnt in conflicts.items():
            self.prom.wave_conflicts.inc(cnt, kind=kind)
            self.phases.count(f"wave.conflicts.{kind}", cnt)
        if static_sigs is None:
            self.phases.count("wave.static_full", 1)
        else:
            self.prom.wave_static_signatures.inc(static_sigs)
            self.phases.count("wave.static_sigs", static_sigs)
        # Bulk-commit eligibility: lean_bind_ok()'s and the Reserve/Permit
        # "covered by host filters" no-op guarantees are BOTH conditioned
        # on the batch being spec-irrelevant to every host Filter plugin
        # (the fast gate proves this for fast batches) — a wave batch can
        # carry host-filter-relevant pods (the extra_mask route), whose
        # Reserve/PreBind walks must run, so prove irrelevance per pod
        # before routing anything around the per-pod commit path.
        hf = fwk.host_filter_plugins()
        hf_clean = not hf or not any(
            pl.maybe_relevant(qp.pod) for qp in batch for pl in hf
        )
        rp_ok = not fwk.has_reserve_or_permit() or (
            fwk.reserve_permit_covered_by_host_filters() and hf_clean
        )
        bulk_ok = (
            fwk.lean_bind_ok()
            and hf_clean
            and rp_ok
            and not self.extenders
        )
        sp_resolve.end()
        return bulk_ok

    def _static_device_cluster(self) -> DeviceCluster:
        """DeviceCluster cached across batches for STATIC reads only
        (labels/taints/allocatable/images) — usage-only churn (generation)
        does NOT invalidate it, so steady-state batches upload nothing.

        The placed-pod tensors are replaced by an EMPTY pack: every consumer
        of this cluster (fastpath static_eval, preemption narrowing) reads
        node-static fields only, and the placed-pod payload dominates the
        re-upload cost under node churn."""
        from kubernetes_tpu.snapshot.schema import pack_existing_pods

        key = (
            self.mirror.static_generation,
            self.mirror._full_packs,
            len(self.mirror.vocab.label_vals),
        )
        if getattr(self, "_static_dc_key", None) != key:
            empty = pack_existing_pods(
                [],
                self.mirror.nodes.name_to_idx,
                self.mirror.vocab,
                k_cap=self.mirror.nodes.k_cap,
            )
            sdc = DeviceCluster.from_host(
                self.mirror.nodes, empty, self.mirror.vocab
            )
            if self.mesh is not None:
                from kubernetes_tpu.parallel.mesh import place_cluster

                sdc = place_cluster(self.mesh, sdc)
            self._static_dc = sdc
            self._static_dc_key = key
        return self._static_dc

    def _try_fast_schedule(
        self, fwk, state, batch, enabled, weights, outcomes
    ) -> Optional[List[ScheduleOutcome]]:
        """Synchronous signature fast path (the _schedule_batch fallback for
        batches the pipelined loop didn't claim).

        Returns completed outcomes, or None when the batch isn't eligible
        (ineligible pods, or static score raws vary so normalization is
        batch-state-dependent) — the caller falls back to the gang scan.
        """
        keys = self._batch_signature_keys(batch)
        if keys is None:
            return None
        rows = self._fast_sig_rows(fwk, batch, keys, enabled, weights)
        if rows is None:
            return None
        rec = self._fast_dispatch(fwk, state, batch, keys, enabled, weights)
        if rec is None:
            return None
        outcomes.extend(self._finish_fast(rec))
        return outcomes

    def _fast_sig_rows(self, fwk, batch, keys, enabled, weights):
        """Per-signature static rows (masks + raw scores) for this batch,
        cached across batches keyed on the static snapshot: steady-state
        batches reuse them and make ZERO static_eval device calls
        (signatures recur — a Deployment's replicas share one).  Returns the
        row cache, or None when any signature's static score raws vary over its
        feasible set (normalization would be batch-state-dependent — the
        greedy's argmax-neutrality argument breaks, so the batch must take
        the gang scan)."""
        import numpy as np

        from kubernetes_tpu.ops import fastpath as ops_fp

        vocab = self.mirror.vocab
        dc_key = (
            self.mirror.static_generation,
            self.mirror._full_packs,
            fwk.profile_name,
        )
        cache = getattr(self, "_sig_cache", None)
        if cache is None or self._sig_cache_key != dc_key:
            cache = self._sig_cache = {}
            self._sig_cache_key = dc_key

        order: Dict[object, int] = {}
        reps: List[Pod] = []
        for k, qp in zip(keys, batch):
            if k not in order and k not in cache:
                order[k] = len(reps)
                reps.append(qp.pod)

        w_taint, w_naff = weights[0], weights[1]
        if reps and self._breaker_blocked("fastpath.static_eval"):
            # open static-eval breaker: fail the fast gate — the batch
            # takes the direct scan path, which reads no signature rows
            return None
        if reps:
            has_images = any(p.images for p in reps)
            pb = pack_pod_batch(
                reps,
                vocab,
                k_cap=self.mirror.nodes.k_cap,
                # floor 16: the count of NEW signatures per batch is noisy
                # (1 here, 2 there) and every distinct count would be a
                # fresh static_eval compile — one [16, N] shape covers them
                p_cap=self._p_bucket(max(len(reps), 16)),
            )
            db = self._place_db(DeviceBatch.from_host(pb))
            dc = self._static_device_cluster()
            from kubernetes_tpu.observability import kernels as kernels_mod

            try:
                res = ops_fp.static_eval(
                    dc, db, enabled=enabled, has_images=has_images
                )
                res = {
                    k: np.asarray(v)
                    for k, v in self._d2h_guarded(
                        res, kernel="fastpath.static_eval"
                    ).items()
                }
            except kernels_mod.DispatchFailed as e:
                # abandoned static eval: the fast gate fails and the batch
                # rides the direct scan path (no signature rows needed)
                self._note_dispatch_failure(e)
                return None
            for k, s in order.items():
                row = {name: res[name][s] for name in res}
                # Normalized static scores are argmax-neutral ONLY when
                # their raws are constant over the feasible set (then every
                # feasible node gets the same normalized value).
                m = row["mask"]
                const_ok = True
                for w, raw in (
                    (w_taint, row["taint_raw"]),
                    (w_naff, row["naff_raw"]),
                ):
                    if not w:
                        continue
                    vals = raw[m]
                    if vals.size and int(vals.min()) != int(vals.max()):
                        const_ok = False
                        break
                row["const_ok"] = const_ok
                cache[k] = row
        if any(not cache[k]["const_ok"] for k in keys):
            return None
        return cache

    def _fast_key(self, fwk, enabled, weights):
        return (
            self._external_mutations,
            self._nonfast_commits,
            self.mirror._full_packs,
            enabled,
            weights,
            fwk.profile_name,
        )

    def _fast_dispatch(self, fwk, state, batch, keys, enabled, weights):
        """Run one fast batch and return its pending record.

        Hybrid committer: the persistent source of truth is a host
        FastCommitter (holder["fc"]) that advances at every harvest — small
        batches with an empty pipeline commit directly on it (zero device
        round trips: the interactive/server-loop case), while large or
        pipelined batches dispatch the sig_scan kernel with device-resident
        chained state and START the async result copy (the bulk-drain case;
        the round trip hides behind the next batch's host work).  Both
        paths are bit-identical (property-tested, tests/test_fastpath.py);
        only EXTERNAL mutations or repacks rebuild the lineage."""
        import numpy as np

        from kubernetes_tpu import fastpath as fp

        from kubernetes_tpu.ops import fastpath as ops_fp
        from kubernetes_tpu.ops import resident as ops_res

        cache = self._sig_cache
        check_fit = "NodeResourcesFit" in enabled
        fc_key = self._fast_key(fwk, enabled, weights)
        holder = self._fastdev
        if holder is None or self._fc_key != fc_key:
            nt = self.mirror.nodes
            holder = self._fastdev = {
                "nt": nt,
                "fc": fp.FastCommitter(nt, weights, check_fit=check_fit),
                "dev": None,  # device state, materialized on demand
                "alloc": None,
                "allowed": None,
                "stack": None,
                "heaps_dirty": False,
                "dev_inflight": 0,  # unharvested device batches — the host
                # committer lags exactly these, so the host path is legal
                # only at zero
                "p_cap": 64,
                # epoch guard (ISSUE 15): lineage epoch (bumped on every
                # device-state rematerialization/resync — a pending record
                # from an older epoch re-derives on the committer) + the
                # host-tracked exact sum of the device usage state
                "epoch": 0,
                "dev_sum": None,
            }
            if getattr(self, "fast_shadow_check", False):
                # invariant-checking mode: a second host FastCommitter
                # replays every batch and must bit-match the chosen path
                holder["shadow"] = fp.FastCommitter(
                    nt, weights, check_fit=check_fit
                )
            # The committer is built from the MIRROR, so it is as old as
            # the mirror's last sync, not as the counters fc_key reads: the
            # pipelined prep between that sync and here (_try_dispatch_fast:
            # a static eval, a first compile) runs outside self._mu, and an
            # informer event that lands there moves the counters and not the
            # mirror.  Keyed by the counters, a committer that never saw
            # that node would pass for current at the next batch, and at
            # every batch until another event; keyed by the sync it was
            # built from, the next batch finds it stale and rebuilds it.
            self._fc_key = (self._mirror_sync or fc_key[:2]) + fc_key[2:]
            self._sig_objs: Dict[object, fp.Signature] = {}
            self._sig_list: List[fp.Signature] = []

        sigs = self._sig_objs
        for k in keys:
            if k in sigs:
                continue
            row = cache[k]
            req_row, nz, *_ = k
            img_list = None
            if weights[6] and row["img"].any():
                img_list = row["img"].tolist()
            sig = fp.Signature(
                req_row=req_row,
                nz0=nz[0],
                nz1=nz[1],
                all_zero=all(v == 0 for v in req_row),
                static_ok=row["mask"],
                img=img_list,
            )
            sig.sid = len(self._sig_list)
            sigs[k] = sig
            self._sig_list.append(sig)
            holder["stack"] = None  # new signature → restack
        pod_sigs = [sigs[k] for k in keys]
        t0 = time.perf_counter()

        # device-fault tier: an open breaker parks its kernel — resident
        # degrades to sig_scan, sig_scan degrades to the host committer
        # (every rung bit-identical, tests/test_fastpath.py /
        # tests/test_resident.py)
        res_on = self.config.resident_drain
        if res_on and self._breaker_blocked("resident.resident_run"):
            res_on = False
        device_ok = res_on or not self._breaker_blocked("fastpath.sig_scan")
        if not device_ok and holder["dev_inflight"] > 0:
            # no device engine available and the host committer lags the
            # unharvested pipeline — the caller flushes and retries or
            # takes the direct path; nothing is committed here
            return None

        # ---- host path: no unharvested device batches + small batch →
        # the greedy answers locally in O(P · log N) with no device link
        # involvement at all (host records already advanced the committer
        # at dispatch, so they may stay pending)
        if holder["dev_inflight"] == 0 and (
            not device_ok
            or len(batch) < self.config.fast_device_min
        ):
            if holder["heaps_dirty"]:
                # device-batch replays changed scores under the lazy heaps
                holder["fc"].invalidate_heaps()
                holder["heaps_dirty"] = False
            # the host greedy IS the selection step here — attribute it to
            # the device phase it replaces
            with self._span("device"):
                choices = holder["fc"].run(pod_sigs)
            holder["dev"] = None  # device copy (if any) is now stale
            with self._mu:  # metrics is a registered lock-guarded field
                self.metrics["fast_batches"] += 1
            rec = {
                "kind": "fast",
                "fwk": fwk,
                "state": state,
                "batch": batch,
                "keys": keys,
                "pod_sigs": pod_sigs,
                "choices_host": choices,
                "choices_dev": None,
                "rstats_dev": None,
                "rows": cache,
                "weights": weights,
                "check_fit": check_fit,
                "holder": holder,
                "t0": t0,
                "record_metrics": False,
            }
            self._trace_dispatch("fast", t0, batch, rec)
            return rec

        # ---- device path: the greedy commit loop runs as a lax.scan over
        # signature ids with the node-usage state resident in HBM
        # (ops/fastpath.sig_scan) — one dispatch per batch, no [P, N]
        # tensors, bit-identical to the host FastCommitter
        sp_h2d = self._span("h2d").begin()
        if holder["stack"] is None:
            holder["stack"] = self._stack_signatures(holder)
        st = holder["stack"]
        # p_cap quantized to three levels so the kernel compiles at most
        # three shapes ever: small drains stay cheap on the test backend,
        # and extended batches all share the fast_batch_max shape (pad
        # steps are masked inner iterations, ~0.2µs each)
        need = len(batch)
        levels = [64, 512, self.config.fast_batch_max]
        if self.config.resident_drain:
            levels.append(self.config.resident_run_max)
        for level in levels:
            if need <= level:
                need = level
                break
        else:
            need = bucket_cap(need, 1)
        p_cap = holder["p_cap"] = max(holder["p_cap"], need)
        ids = np.full((p_cap,), -1, np.int32)
        ids[: len(batch)] = [s.sid for s in pod_sigs]
        w_img = weights[6] if st["any_img"] else 0
        try:
            if holder["dev"] is None:
                # (re)materialize device state from the host committer —
                # one upload per host→device transition, folded into this
                # dispatch's async pipeline
                fc = holder["fc"]
                used_np = np.asarray(fc.used_rows, np.int64)
                nz0_np = np.asarray(fc.nz0, np.int64)
                nz1_np = np.asarray(fc.nz1, np.int64)
                npods_np = np.asarray(fc.num_pods, np.int32)
                holder["alloc"] = jnp.asarray(
                    np.asarray(fc.alloc_rows, np.int64)
                )
                holder["allowed"] = jnp.asarray(
                    np.asarray(fc.allowed, np.int32)
                )
                holder["dev"] = (
                    jnp.asarray(used_np),
                    jnp.asarray(nz0_np),
                    jnp.asarray(nz1_np),
                    jnp.asarray(npods_np),
                )
                # epoch guard: a fresh lineage epoch plus the exact host
                # sum of the uploaded state — each harvest advances the
                # sum by its commits and checks it against the device
                # checksum before trusting a round's results
                holder["epoch"] = holder.get("epoch", 0) + 1
                holder["dev_sum"] = int(
                    int(used_np.sum())
                    + int(nz0_np.sum())
                    + int(nz1_np.sum())
                    + int(npods_np.sum())
                )
            used, nz0, nz1, num_pods = holder["dev"]
            sp_h2d.end()
            sp_dev = self._span("device").begin()
            rstats_dev = None
            if res_on:
                # resident drain loop (ops/resident.py): the whole run is
                # placed on device through the speculation/admission fixed
                # point — same donated usage state as sig_scan, one d2h
                # readback of packed placements per run
                choices_dev, holder["dev"], rstats_dev = ops_res.resident_run(
                    jnp.asarray(ids),
                    st["req"],
                    st["nz"],
                    st["az"],
                    st["ok"],
                    st["img"],
                    holder["alloc"],
                    holder["allowed"],
                    used,
                    nz0,
                    nz1,
                    num_pods,
                    w_fit=weights[4],
                    w_bal=weights[5],
                    w_img=w_img,
                    check_fit=check_fit,
                    # ktpu: allow(retrace) — alloc's leading axis is the
                    # committer's node count, fixed for the holder's whole
                    # lineage (any node change rebuilds the holder): one
                    # compile per lineage, not one per batch
                    window=min(
                        self.config.resident_window,
                        int(holder["alloc"].shape[0]),
                    ),
                    serial_tail=self.config.resident_serial_tail,
                )
                self.phases.count("resident.runs", 1)
            else:
                choices_dev, holder["dev"] = ops_fp.sig_scan(
                    jnp.asarray(ids),
                    st["req"],
                    st["nz"],
                    st["az"],
                    st["ok"],
                    st["img"],
                    holder["alloc"],
                    holder["allowed"],
                    used,
                    nz0,
                    nz1,
                    num_pods,
                    w_fit=weights[4],
                    w_bal=weights[5],
                    w_img=w_img,
                    check_fit=check_fit,
                )
            # epoch guard: the device-side checksum of the NEW state rides
            # the same async pipeline; the harvest validates it against
            # the host-tracked sum BEFORE committing the round
            csum_dev = ops_res.usage_checksum(*holder["dev"])
            csum_dev.copy_to_host_async()
            # start the device→host result copy NOW; by harvest time the
            # data is local and the blocking fetch is cheap (the same
            # latency-hiding discipline as the chained gang pipeline)
            choices_dev.copy_to_host_async()
            if rstats_dev is not None:
                rstats_dev.copy_to_host_async()
            holder["dev_inflight"] += 1
            sp_dev.end()
        except Exception as e:
            # a dispatch died mid-round: the donated usage buffers are in
            # an unknown state — but the HOST committer is still the
            # committed truth, so the epoch-guarded resync only drops the
            # device lineage (epoch bump invalidates any unharvested
            # record dispatched against it) and answers this batch on the
            # committer, bit-identically.  No torn usage row can commit:
            # nothing reached the cache from the dead dispatch.
            from kubernetes_tpu.observability import kernels as kernels_mod

            if not isinstance(e, kernels_mod.DispatchFailed):
                logger.exception(
                    "fast-path dispatch failed; resyncing device lineage"
                )
            self._note_dispatch_failure(e)
            holder["dev"] = None
            holder["epoch"] = holder.get("epoch", 0) + 1
            holder["dev_sum"] = None
            self.prom.resident_resyncs.inc(reason="dispatch_failed")
            if holder["dev_inflight"] > 0:
                # unharvested records exist: their harvests re-derive on
                # the committer (epoch mismatch); this batch retries via
                # the caller's flush-and-fallback discipline
                return None
            if holder["heaps_dirty"]:
                holder["fc"].invalidate_heaps()
                holder["heaps_dirty"] = False
            with self._span("device"):
                choices = holder["fc"].run(pod_sigs)
            with self._mu:  # metrics is a registered lock-guarded field
                self.metrics["fast_batches"] += 1
            rec = {
                "kind": "fast",
                "fwk": fwk,
                "state": state,
                "batch": batch,
                "keys": keys,
                "pod_sigs": pod_sigs,
                "choices_host": choices,
                "choices_dev": None,
                "rstats_dev": None,
                "rows": cache,
                "weights": weights,
                "check_fit": check_fit,
                "holder": holder,
                "t0": t0,
                "record_metrics": False,
            }
            self._trace_dispatch("fast", t0, batch, rec)
            return rec
        with self._mu:  # metrics is a registered lock-guarded field
            self.metrics["fast_batches"] += 1
        rec = {
            "kind": "fast",
            "fwk": fwk,
            "state": state,
            "batch": batch,
            "keys": keys,
            "pod_sigs": pod_sigs,
            "choices_host": None,
            "choices_dev": choices_dev,
            "rstats_dev": rstats_dev,
            "csum_dev": csum_dev,
            "epoch": holder["epoch"],
            "rows": cache,
            "weights": weights,
            "check_fit": check_fit,
            "holder": holder,
            "t0": t0,
            "record_metrics": False,
        }
        self._trace_dispatch(
            "resident" if rstats_dev is not None else "fast", t0, batch, rec
        )
        return rec

    def _finish_fast(self, rec) -> List[ScheduleOutcome]:
        """Harvest one fast batch: fetch the kernel's choices (device
        records) or take the host greedy's, advance the host committer, and
        walk the commits (assume → reserve/permit → async bind), diagnosing
        unschedulable pods against the committer state."""
        import numpy as np

        tr = self.tracer
        t_h = tr.now() if tr.enabled else None
        fwk = rec["fwk"]
        state = rec["state"]
        batch = rec["batch"]
        cache = rec["rows"]
        weights = rec["weights"]
        pod_sigs = rec["pod_sigs"]
        holder = rec["holder"]
        outcomes: List[ScheduleOutcome] = []
        from kubernetes_tpu.observability import kernels as kernels_mod

        choices = rec["choices_host"]
        torn = None  # epoch-guard verdict: why the device round was discarded
        if choices is None and rec.get("epoch") is not None and rec[
            "epoch"
        ] != rec["holder"].get("epoch"):
            # the lineage was resynced AFTER this dispatch (a later
            # dispatch died, hbm_oom, mesh degrade): its results ride a
            # dead epoch — discard them un-fetched and re-derive on the
            # host committer, bit-identically
            torn = "epoch_stale"
        if choices is None and torn is None:
            rstats_dev = rec.get("rstats_dev")
            csum_dev = rec["csum_dev"]
            kern = (
                "resident.resident_run"
                if rstats_dev is not None
                else "fastpath.sig_scan"
            )
            n_fc = holder["fc"].n

            def _validate_choices(fetched):
                ch = np.asarray(fetched[0])[: len(batch)]
                if ((ch < -2) | (ch >= n_fc)).any():
                    return "choice index out of node range"
                return None

            sp_d2h = self._span("d2h").begin()
            try:
                fetched = self._d2h_guarded(
                    (rec["choices_dev"], rstats_dev, csum_dev),
                    kernel=kern,
                    validate=_validate_choices,
                )
            except kernels_mod.DispatchFailed as e:
                # unrecoverable readback: treat exactly like a torn round
                self._note_dispatch_failure(e)
                torn = "checksum_mismatch"
            else:
                choices_np = np.asarray(fetched[0])[: len(batch)]
                rstats = (
                    np.asarray(fetched[1]) if rstats_dev is not None else None
                )
                csum = int(fetched[2])
                choices = choices_np.tolist()
            sp_d2h.end()
        if torn is not None:
            # epoch-guarded resync: nothing from the dead round reaches
            # the cache or the committer — the host committer (still the
            # committed truth) answers the batch instead
            holder["dev"] = None
            holder["dev_sum"] = None
            holder["dev_inflight"] -= 1
            self.prom.resident_resyncs.inc(reason=torn)
            if holder["heaps_dirty"]:
                holder["fc"].invalidate_heaps()
                holder["heaps_dirty"] = False
            with self._span("resident_rounds"):
                choices = holder["fc"].run(pod_sigs)
            rec["rstats_dev"] = None  # the path label below reads it
        elif rec["choices_host"] is None:
            holder["dev_inflight"] -= 1
            # the host replay of a resident run's rounds; booked only for
            # resident records (a sig_scan harvest's replay stays unbooked)
            sp_res = (
                self._span("resident_rounds").begin()
                if rstats is not None
                else None
            )
            if rstats is not None:
                rounds = int(rstats[0])
                # resident_pods counts what the fixed point RESOLVED; the
                # host-committer tail below covers the rest
                resolved = min(int(rstats[1]), len(batch))
                with self._mu:  # metrics is a registered lock-guarded field
                    self.metrics["resident_batches"] += 1
                    self.metrics["resident_pods"] += resolved
                    self.metrics["resident_rounds"] += rounds
                self.prom.resident_rounds.inc(rounds)
            # advance the host committer to the post-batch state by
            # replaying the kernel's commits — VECTORIZED per-node
            # aggregates (scatter-add over the choices) + one python-int
            # update per TOUCHED node; the old per-pod loop was O(P)
            # interpreter work and dominated resident-run harvests
            fc = holder["fc"]
            rn = fc.rn
            sel = choices_np >= 0
            agg = add0 = add1 = cnt = None
            nodes = None
            if sel.any():
                st_np = holder["stack"]
                sids = np.fromiter(
                    (s.sid for s in pod_sigs), np.int64, len(pod_sigs)
                )[sel]
                nodes = choices_np[sel].astype(np.int64)
                agg = np.zeros((fc.n, rn), np.int64)
                np.add.at(agg, nodes, st_np["req_np"][sids][:, :rn])
                add0 = np.zeros(fc.n, np.int64)
                np.add.at(add0, nodes, st_np["nz_np"][sids, 0])
                add1 = np.zeros(fc.n, np.int64)
                np.add.at(add1, nodes, st_np["nz_np"][sids, 1])
                cnt = np.bincount(nodes, minlength=fc.n)
            # epoch guard: the device state's checksum must equal the
            # host-tracked base sum plus EXACTLY this round's commit
            # delta (identical int arithmetic on both sides) — validated
            # BEFORE anything touches the committer, so a dispatch that
            # died mid-round can never commit torn usage rows.  The base
            # is read at HARVEST time (holder["dev_sum"]): harvests are
            # FIFO, so with two batches in flight the earlier harvest has
            # already folded its delta in by the time the later validates.
            if holder.get("dev_sum") is not None:
                delta = 0
                if agg is not None:
                    delta = int(
                        int(agg.sum())
                        + int(add0.sum())
                        + int(add1.sum())
                        + int(cnt.sum())
                    )
                expected = holder["dev_sum"] + delta
                if csum != expected:
                    # torn state: discard the round, resync the lineage,
                    # and answer on the committer (bit-identical)
                    logger.warning(
                        "resident usage checksum mismatch (device %d != "
                        "expected %d) — resyncing from the host committer",
                        csum,
                        expected,
                    )
                    self.kernels.record_breaker_failure(
                        kern, "poisoned_output"
                    )
                    self.prom.resident_resyncs.inc(
                        reason="checksum_mismatch"
                    )
                    self.prom.wave_fallback.inc(reason="breaker")
                    holder["dev"] = None
                    holder["dev_sum"] = None
                    if holder["heaps_dirty"]:
                        fc.invalidate_heaps()
                        holder["heaps_dirty"] = False
                    choices = fc.run(pod_sigs)
                    choices_np = np.asarray(choices)
                    rstats = None
                    sel = np.zeros(0, bool)  # committer already committed
                    agg = None
                else:
                    holder["dev_sum"] = expected
            if agg is not None:
                used_rows = fc.used_rows
                nz0l, nz1l, npods = fc.nz0, fc.nz1, fc.num_pods
                for n in np.unique(nodes).tolist():
                    row = used_rows[n]
                    arow = agg[n]
                    for r in range(rn):
                        row[r] += int(arow[r])
                    nz0l[n] += int(add0[n])
                    nz1l[n] += int(add1[n])
                    npods[n] += int(cnt[n])
                holder["heaps_dirty"] = True
            unresolved = choices_np == -2  # ops/resident.py UNRESOLVED
            if unresolved.any():
                # host-committer tail: the fixed point handed back its
                # conflict tail (adaptive stop / round cap) — finish it
                # with the exact lazy-heap greedy, which beats serial
                # device steps on host-backed runs.  The device state
                # copy now lags these commits, so it re-materializes
                # from the committer at the next dispatch.
                fc.invalidate_heaps()
                tail_idx = np.nonzero(unresolved)[0]
                tail_choices = fc.run([pod_sigs[i] for i in tail_idx])
                for i, c in zip(tail_idx.tolist(), tail_choices):
                    choices[i] = c
                holder["heaps_dirty"] = False
                holder["dev"] = None
                holder["dev_sum"] = None
            if sp_res is not None:
                sp_res.end()
            shadow = holder.get("shadow")
            if shadow is not None:
                host_choices = shadow.run(pod_sigs)
                if host_choices != choices:
                    diffs = [
                        (i, h, d)
                        for i, (h, d) in enumerate(zip(host_choices, choices))
                        if h != d
                    ][:10]
                    raise AssertionError(
                        f"sig_scan diverged from host FastCommitter: {diffs}"
                    )
        elif holder.get("shadow") is not None:
            shadow_choices = holder["shadow"].run(pod_sigs)
            if shadow_choices != choices:
                raise AssertionError("host fast path diverged from shadow")
        self.prom.recorder.observe(
            self.prom.gang_dispatch_duration,
            time.perf_counter() - rec["t0"],
            path="resident" if rec.get("rstats_dev") is not None else "fast",
        )

        node_names = self.mirror.nodes.names
        diag_cache: Dict[int, Dict[str, int]] = {}
        node_valid = None
        n_nodes = None
        # The fast gate proved every host filter spec-irrelevant to every
        # batch pod; when Reserve/Permit plugins are exactly those plugins
        # (default registry: volumebinding/DRA), their walks are no-ops —
        # skip them for the whole batch.
        has_rp = (
            fwk.has_reserve_or_permit()
            and not fwk.reserve_permit_covered_by_host_filters()
        )
        lean = fwk.lean_bind_ok()
        # the bulk pass needs neither reserve/permit walks nor per-pod bind
        # plugin dispatch — exactly the lean fast-batch conditions
        bulk_ok = lean and not has_rp
        keys = rec["keys"]
        n = len(batch)
        with self._mu:  # metrics is a registered lock-guarded field
            self.metrics["schedule_attempts"] += n
        sp_commit = self._span("commit").begin()
        i = 0
        while i < n:
            if choices[i] >= 0:
                # commit the whole contiguous run of scheduled pods under
                # ONE lock acquisition (in order — runs preserve the
                # sequential-equivalent commit sequence)
                j = i
                while j < n and choices[j] >= 0:
                    j += 1
                if bulk_ok:
                    self._commit_fast_bulk(
                        fwk, state, batch, choices, i, j, node_names, outcomes
                    )
                else:
                    sp_lock = self._span("commit.lock_wait").begin()
                    with self._mu:
                        sp_lock.end()
                        for k_ in range(i, j):
                            outcomes.append(
                                self._commit_under_lock(
                                    fwk,
                                    state,
                                    batch[k_],
                                    node_names[choices[k_]],
                                    -1,
                                    None,
                                    has_rp,
                                    lean,
                                )
                            )
                i = j
                continue
            qp, sig, k = batch[i], pod_sigs[i], keys[i]
            i += 1
            diag = diag_cache.get(id(sig))
            if diag is None:
                if node_valid is None:
                    node_valid = np.asarray(self.mirror.nodes.valid)
                    n_nodes = len(self.cache.real_nodes())
                diag = holder["fc"].diagnose(sig, cache[k], node_valid)
                diag_cache[id(sig)] = diag
            status = Status.unschedulable(fit_error_message(n_nodes, diag))
            outcomes.append(
                self._post_filter_or_fail(
                    fwk, state, qp, status, 0, diag, set(diag)
                )
            )
        sp_commit.end()
        if rec["record_metrics"]:
            self._record_batch_metrics(
                fwk.profile_name,
                batch,
                outcomes,
                time.perf_counter() - rec["t0"],
            )
            self._flush_binds()
        if t_h is not None and tr.enabled:
            tr.complete(
                "harvest.resident"
                if rec.get("rstats_dev") is not None
                else "harvest.fast",
                t_h,
                cat="batch",
                bid=rec.get("bid"),
                pods=len(batch),
            )
        return outcomes


    def _try_dispatch_fast(self, fwk, batch, outcomes, pipe) -> routing.Answer:
        """Pipelined fast-path dispatch from the scheduling loop: run the
        eligibility gates and PreFilter, dispatch the sig_scan kernel, and
        answer in flight with a pending record the loop harvests later — the
        fast-path analogue of _try_dispatch_chained's ≤2-in-flight discipline,
        which hides the device link's round-trip latency behind the next
        batch's host work.  Else handled (nothing left), settle (chain
        records must settle first — their commits move host state the
        fast rebuild reads), or declined (not eligible — direct path)."""
        if not self._signature_profile(fwk):
            return routing.DECLINED
        if self.mirror.nodes is None:
            # first batch of a fresh scheduler: pack the mirror now so the
            # very first dispatch already takes the pipelined (and batch-
            # extended) path — otherwise the steady-state batch shape only
            # compiles after warm-up
            with self._mu:
                if self.mirror.nodes is None:
                    self._repack_mirror()
            if self.mirror.nodes is None:  # no nodes yet
                return routing.DECLINED
        gates = self._pod_gates(fwk)
        for qp in batch:
            if gates.one_pod(qp.pod) is not None:
                return routing.DECLINED
        if not self._fast_gate_ok(batch, gates):
            return routing.DECLINED
        keys = self._batch_signature_keys(batch)
        if keys is None:
            return routing.DECLINED
        if not pipe.only_fast:
            return routing.SETTLE
        # a lineage rebuild (external events moved the ground truth) must
        # not happen under unharvested records: their commits reach the
        # cache only at harvest, and a rebuild reads the mirror — settle
        # the pipeline first, then rebuild on the retry
        enabled = fwk.device_enabled()
        weights = tuple(fwk.score_weights.get(n, 0) for n in gang.WEIGHT_ORDER)
        if not pipe.empty and (
            self._fastdev is None
            or self._fc_key != self._fast_key(fwk, enabled, weights)
        ):
            return routing.SETTLE
        # spec-level host-score probe on the SEED batch (extension pods are
        # probed inside the predicate) — the pre-PreFilter equivalent of the
        # sync path's Skip-state check: a pod whose spec is irrelevant Skips
        # in PreScore by the stateful-plugin contract
        for qp in batch:
            if gates.host_score(qp.pod) is not None:
                return routing.DECLINED

        sp_pack = self._span("pack").begin()
        with self._mu:
            vocab = self.mirror.vocab
            for qp in batch:
                for k, v in qp.pod.labels.items():
                    vocab.intern_label(k, v)
            self._sync_mirror_external()
        # Establish the SEED batch's signature rows (and their argmax-
        # neutrality verdicts) BEFORE extending: every bail-out must happen
        # while the seed group is the only thing popped — extension pods
        # would be lost to the direct-path fallback otherwise.
        rows = self._fast_sig_rows(fwk, batch, keys, enabled, weights)
        sp_pack.end()
        if rows is None:
            return routing.DECLINED

        # Extend the batch from the queue head while pods stay eligible AND
        # their signatures are already established as argmax-neutral: per-
        # pod host cost is flat on the sig_scan path, so one big dispatch
        # amortizes the device round trip over many more pods (queue order
        # — and therefore decision sequence — is unchanged; a pod with a
        # NOVEL signature stops the extension and seeds a later batch).
        # resident runs extend further than plain fast batches: the whole
        # run rides ONE dispatch + ONE d2h readback, so per-run host cost
        # amortizes over far more pods (RESIDENT.md)
        cap = (
            self.config.resident_run_max
            if self.config.resident_drain
            else self.config.fast_batch_max
        )
        ext = cap - len(batch)
        if ext > 0:
            elig = self._fast_pod_predicate(
                fwk, batch[0].pod.scheduler_name, known_rows=rows
            )
            with self._span("queue_pop"):
                sp_lock = self._span("queue_pop.lock_wait").begin()
                with self._mu:
                    sp_lock.end()
                    extra = self.queue.pop_batch_while(ext, elig)
            if extra:
                with self._mu:
                    for qp in extra:
                        for k, v in qp.pod.labels.items():
                            vocab.intern_label(k, v)
                # in place: the loop's count of the pods this route took
                # (_book_route) holds the extension's too
                batch.extend(extra)
                keys = self._batch_signature_keys(batch)
                assert keys is not None  # predicate guarantees eligibility

        state = CycleState()
        pods_all = [qp.pod for qp in batch]
        sp_pack = self._span("pack").begin()
        # ---- point of commitment: PreFilter mutates outcomes/queue state,
        # so every bail-out above happened first (the direct path must not
        # replay it, and extension pods are already part of this batch);
        # after this, the rare dispatch failure error-requeues the batch
        with self._mu:
            fwk.run_pre_score(state, pods_all, self.mirror.nodes.names)
            pf_failures = self._run_pre_filter_fast(fwk, state, batch, keys)
            if pf_failures:
                live = []
                for qp in batch:
                    s = pf_failures.get(qp.pod.uid)
                    if s is None:
                        live.append(qp)
                        continue
                    self.metrics["schedule_attempts"] += 1
                    outcomes.append(
                        self._post_filter_or_fail(fwk, state, qp, s, 0)
                    )
                batch = live
                if not batch:
                    sp_pack.end()
                    return routing.HANDLED
                keys = self._batch_signature_keys(batch)
        sp_pack.end()
        # fast commits happen outside the chain's device state — drop it
        # (it restarts from the repacked mirror once the pipeline settles)
        self._chain = None
        rec = self._fast_dispatch(fwk, state, batch, keys, enabled, weights)
        if rec is None:
            # dispatch failure after pods (incl. extension) were popped and
            # PreFilter ran: error-requeue the whole batch with backoff —
            # the retry drains through whatever path is healthy then
            s = Status.error("fast-path device dispatch failed; requeued")
            with self._mu:  # one acquisition for the whole error-requeue
                self.metrics["schedule_attempts"] += len(batch)
                for qp in batch:
                    self._handle_failure(qp, s)
                    outcomes.append(ScheduleOutcome(qp.pod, None, s, 0))
            return routing.HANDLED
        rec["record_metrics"] = True
        # a resident run may finish its conflict tail on the HOST committer,
        # after which the chained device state is stale — harvest
        # immediately so no later dispatch rides a state that a host tail
        # is about to overtake
        rec["harvest_now"] = (
            rec.get("rstats_dev") is not None
            and not self.config.resident_serial_tail
        )
        return routing.Answer(routing.Offer.IN_FLIGHT, rec)


    def _run_pre_filter_fast(self, fwk, state, batch, keys):
        """RunPreFilterPlugins for a signature-gated batch, ONE walk per
        distinct signature instead of per pod.

        Pods of one signature share the spec fields every in-tree
        PreFilter reads (pre_filter_spec_pure), and the cluster state a
        fast lineage runs against is frozen between external mutations /
        non-fast commits — both are part of the memo key, so a cached
        verdict can never outlive the state it judged.  Signatures whose
        representative FAILED re-run the real per-pod walk (per-pod Status
        objects + CycleState writes feed the PostFilter/preemption path);
        the hot case — every signature passes — costs one dict hit per pod.
        Falls back to the reference-shaped per-pod walk whenever any
        enabled PreFilter plugin doesn't declare spec purity."""
        if not fwk.pre_filter_spec_pure():
            return fwk.run_pre_filter(state, [qp.pod for qp in batch])
        mkey = (
            self._external_mutations,
            self._nonfast_commits,
            self.mirror._full_packs,
            fwk.profile_name,
        )
        memo = getattr(self, "_pf_memo", None)
        if memo is None or memo[0] != mkey:
            memo = self._pf_memo = (mkey, {})
        verdicts = memo[1]
        failures: Dict[str, Status] = {}
        for k, qp in zip(keys, batch):
            hit = verdicts.get(k, _MISSING)
            if hit is _MISSING:
                s = fwk.run_pre_filter(state, [qp.pod]).get(qp.pod.uid)
                verdicts[k] = s
                if s is not None:
                    failures[qp.pod.uid] = s
            elif hit is not None:
                # known-failing signature: real walk for THIS pod so its
                # Status and per-uid state are its own
                s = fwk.run_pre_filter(state, [qp.pod]).get(qp.pod.uid)
                if s is not None:
                    failures[qp.pod.uid] = s
                else:
                    verdicts[k] = None  # plugin state moved — trust the rerun
        return failures

    def _stack_signatures(self, holder):
        """[S_cap, ...] stacked per-signature tensors for sig_scan; S_cap is
        a pow2 bucket so signature-set growth rarely changes the shape."""
        import numpy as np

        sig_list = self._sig_list
        n = holder["fc"].n
        r = holder["fc"].rn
        s_cap = bucket_cap(len(sig_list), 8)
        req = np.zeros((s_cap, r), np.int64)
        nz = np.zeros((s_cap, 2), np.int64)
        az = np.zeros((s_cap,), bool)
        ok = np.zeros((s_cap, n), bool)
        img = np.zeros((s_cap, n), np.int64)
        any_img = False
        for i, sg in enumerate(sig_list):
            row = np.asarray(sg.req_row, np.int64)
            req[i, : row.shape[0]] = row
            nz[i, 0] = sg.nz0
            nz[i, 1] = sg.nz1
            az[i] = sg.all_zero
            ok[i] = sg.static_ok
            if sg.img is not None:
                img[i] = sg.img
                any_img = True
        return {
            "req": jnp.asarray(req),
            "nz": jnp.asarray(nz),
            "az": jnp.asarray(az),
            "ok": jnp.asarray(ok),
            "img": jnp.asarray(img),
            "any_img": any_img,
            # numpy twins for the harvest-side vectorized committer replay
            "req_np": req,
            "nz_np": nz,
        }

    @staticmethod
    def _wave_shaped_pod(pod) -> bool:
        """Pod carries a cross-pod constraint the wave engine owns (spread
        or inter-pod terms, in-batch host ports) — routing it onto a
        one-pod host path is a fallback-ladder event worth counting in
        scheduler_tpu_wave_fallback_total."""
        if pod.host_ports() or pod.topology_spread_constraints:
            return True
        aff = pod.affinity
        return aff is not None and bool(
            aff.pod_affinity or aff.pod_anti_affinity
        )

    def _schedule_one_nominated(self, fwk, qp) -> List[ScheduleOutcome]:
        """The nominated-node fast path (schedule_one.go:490-499): a pod
        whose preemption already nominated a node evaluates feasibility of
        THAT node only — no scoring, no full dispatch — and binds there when
        it passes.  Falls back to the full one-pod cycle otherwise (the
        reference then runs the normal findNodesThatFitPod).  This is what
        keeps preemption retry rounds off the gang pipeline: by the time
        victims finish terminating, each preemptor costs one host-side
        single-node check instead of a device dispatch."""
        from kubernetes_tpu.oracle.pipeline import feasible_nodes

        pod = qp.pod
        nom = pod.nominated_node_name
        if self._wave_shaped_pod(pod):
            self.prom.wave_fallback.inc(reason="nominated")
        with self._mu:
            state = CycleState()
            self.metrics["schedule_attempts"] += 1
            pf_failures = fwk.run_pre_filter(state, [pod])
            if pf_failures:
                return [
                    self._post_filter_or_fail_locked(
                        fwk, state, qp, pf_failures[pod.uid], 0
                    )
                ]
            allowed = state.read(("pre_filter_result", pod.uid))
            st = self.oracle_view()
            ns = st.nodes.get(nom)
            ok = (
                ns is not None
                and (allowed is None or nom in allowed)
            )
            if ok:
                # RunFilterPluginsWithNominatedPods for the single node:
                # OTHER nominated preemptors of >= priority count as present
                # (runtime/framework.go:973), then a second pass without
                # them (a node feasible only via an unbound nomination may
                # never materialize).
                added = [
                    np_
                    for node, np_ in self.nominator.entries()
                    if node == nom
                    and np_.uid != pod.uid
                    and np_.priority >= pod.priority
                ]
                for np_ in added:
                    ns.add_pod(np_)
                    fwk.run_pre_filter_extension_add_pod(state, pod, np_, ns)
                try:
                    fit = feasible_nodes(
                        pod,
                        st,
                        enabled=fwk.device_enabled(),
                        allowed=frozenset({nom}),
                    )
                    ok = bool(fit.feasible)
                    # FIRST pass runs ALL Filter plugins — host-backed ones
                    # included — with the nominated pods counted as present
                    # (RunFilterPluginsWithNominatedPods, runtime:973): an
                    # occupancy-sensitive host plugin must see them
                    if ok and fwk.has_host_filters():
                        ok = fwk.run_host_filters(state, pod, ns).ok
                finally:
                    for np_ in added:
                        ns.remove_pod(np_)
                        fwk.run_pre_filter_extension_remove_pod(
                            state, pod, np_, ns
                        )
                if ok and added:
                    # second pass on the NEUTRAL state (a node feasible
                    # only via an unbound nomination may never materialize)
                    second = feasible_nodes(
                        pod,
                        st,
                        enabled=fwk.device_enabled(),
                        allowed=frozenset({nom}),
                    )
                    ok = bool(second.feasible)
                    if ok and fwk.has_host_filters():
                        ok = fwk.run_host_filters(state, pod, ns).ok
            if ok:
                for ext in self.extenders:
                    if not ext.is_filter() or not ext.is_interested(pod):
                        continue
                    try:
                        kept, _, _ = ext.filter(pod, [nom])
                    except Exception:  # noqa: BLE001 — ignorable or fallback
                        if getattr(ext, "ignorable", False):
                            continue
                        ok = False
                        break
                    if not kept:
                        ok = False
                        break
            if ok:
                # metrics parity: the attempt was already counted above;
                # _commit returns the success outcome (EvaluatedNodes=1)
                return [self._commit(fwk, state, qp, nom, 1)]
        # Nominated node no longer fits — full evaluation (the attempt
        # counter for the fallback cycle is bumped there, so compensate).
        with self._mu:  # metrics is a registered lock-guarded field
            self.metrics["schedule_attempts"] -= 1
        return self._schedule_one_extender(fwk, qp)

    def _schedule_one_extender(self, fwk, qp) -> List[ScheduleOutcome]:
        """One-pod cycle through the host oracle with the extender chain:
        in-tree Filter → extender Filter (serial, schedule_one.go:701-745)
        → in-tree Score → extender Prioritize (:796-854) → select → commit
        (extender Bind replaces in-tree bind plugins when offered).

        Holds the cache lock for the whole cycle: it reads and temporarily
        patches the SHARED oracle view (nominated-pod add/remove), which
        binding workers patch in place concurrently.  One-pod extender
        cycles are the rare path, so stalling binds behind an extender
        round-trip is acceptable (the reference's extender calls sit on the
        scheduling goroutine too)."""
        pod = qp.pod
        if not pod.nominated_node_name and self._wave_shaped_pod(pod):
            # nominated fall-through already counted its own reason
            reason = (
                "extender"
                if any(e.is_interested(pod) for e in self.extenders)
                else "host_scores"
            )
            self.prom.wave_fallback.inc(reason=reason)
        with self._mu:
            return self._schedule_one_extender_locked(fwk, qp)

    def _schedule_one_extender_locked(self, fwk, qp) -> List[ScheduleOutcome]:
        from kubernetes_tpu.extender import ExtenderError
        from kubernetes_tpu.oracle.pipeline import (
            feasible_nodes,
            prioritize,
            select_host,
        )

        pod = qp.pod
        state = CycleState()
        self.metrics["schedule_attempts"] += 1
        # bit-compat tie-break: one hash index per pod ATTEMPT, consumed up
        # front so early failures keep the sequence aligned with the gang
        # path (which advances by batch length, failures included)
        attempt = getattr(self, "_attempt_counter", 0)
        if self.config.tie_break_seed is not None:
            self._attempt_counter = attempt + 1

        pf_failures = fwk.run_pre_filter(state, [pod])
        if pf_failures:
            return [
                self._post_filter_or_fail(fwk, state, qp, pf_failures[pod.uid], 0)
            ]

        st = self.oracle_view()
        n_nodes = len(st.nodes)
        allowed = state.read(("pre_filter_result", pod.uid))
        # sample sizing happens INSIDE feasible_nodes over the
        # PreFilterResult-narrowed list (schedule_one.go narrows first)
        sample_pct = None
        if self._sampling_active(fwk):
            pct = fwk.percentage_of_nodes_to_score
            if pct is None:
                pct = self.config.percentage_of_nodes_to_score
            if pct > 0 or self.config.reference_sampling_compat:
                sample_pct = pct
        # RunFilterPluginsWithNominatedPods (runtime/framework.go:973):
        # nominated preemptors of >= priority count as present on their
        # nominated node during feasibility; PreFilter extensions keep
        # plugin cycle state in step (interface.go:443-520)
        added = []
        for node, np_ in self.nominator.entries():
            if (
                np_.uid != pod.uid
                and np_.priority >= pod.priority
                and node in st.nodes
            ):
                st.nodes[node].add_pod(np_)
                fwk.run_pre_filter_extension_add_pod(
                    state, pod, np_, st.nodes[node]
                )
                added.append((node, np_))
        try:
            fit = feasible_nodes(
                pod,
                st,
                enabled=fwk.device_enabled(),
                allowed=frozenset(allowed) if allowed is not None else None,
                sample_pct=sample_pct,
                start_index=getattr(self, "_next_start_node_index", 0),
            )
        finally:
            for node, np_ in added:
                st.nodes[node].remove_pod(np_)
                fwk.run_pre_filter_extension_remove_pod(
                    state, pod, np_, st.nodes[node]
                )
        if added and fit.feasible:
            # the reference's SECOND pass (runtime/framework.go:973): a node
            # that only passed BECAUSE of a nominated pod (e.g. required
            # affinity to it) must also pass without — the nomination may
            # never materialize there
            nominated_nodes = {n for n, _ in added}
            recheck = [n for n in fit.feasible if n in nominated_nodes]
            if recheck:
                second = feasible_nodes(
                    pod,
                    st,
                    enabled=fwk.device_enabled(),
                    allowed=frozenset(recheck),
                )
                ok2 = set(second.feasible)
                dropped = [n for n in recheck if n not in ok2]
                fit.feasible = [n for n in fit.feasible if n not in dropped]
                for n in dropped:
                    fit.reasons.setdefault(n, []).append(
                        "node(s) only feasible with unbound nominated pods"
                    )
        if sample_pct is not None:
            # advance the rotation modulo the NARROWED list length, like
            # findNodesThatPassFilters (schedule_one.go:625)
            self._next_start_node_index = (
                getattr(self, "_next_start_node_index", 0) + fit.processed
            ) % max(fit.n_considered, 1)
        feasible = fit.feasible
        diag: Dict[str, int] = {}
        for rs in fit.reasons.values():
            for r in rs:
                diag[r] = diag.get(r, 0) + 1
        plugins: set = set()
        if fwk.has_host_filters():
            kept = []
            for n in feasible:
                s = fwk.run_host_filters(state, pod, st.nodes[n])
                if s.ok:
                    kept.append(n)
                else:
                    reason = s.merge_reason() or s.plugin
                    diag[reason] = diag.get(reason, 0) + 1
                    plugins.add(s.plugin)
            feasible = kept

        for ext in self.extenders:
            if not feasible:
                break
            if not ext.is_filter() or not ext.is_interested(pod):
                continue
            try:
                feasible, failed, unresolvable = ext.filter(pod, feasible)
            except ExtenderError as e:
                if ext.ignorable:
                    continue
                status = Status.error(str(e))
                self._handle_failure(qp, status)
                return [ScheduleOutcome(pod, None, status, 0, diag)]
            for reason_map in (failed, unresolvable):
                for _, reason in reason_map.items():
                    key = reason or f"rejected by extender {ext.name}"
                    diag[key] = diag.get(key, 0) + 1

        if not feasible:
            status = Status.unschedulable(fit_error_message(n_nodes, diag))
            return [
                self._post_filter_or_fail(
                    fwk, state, qp, status, 0, diag, plugins or None
                )
            ]

        fit_inst = fwk.plugin_instance("NodeResourcesFit")
        fit_scorer = (
            (lambda pod_, ns_: fit_inst.score(state, pod_, ns_))
            if fit_inst is not None
            else None
        )
        totals = prioritize(
            pod, st, feasible, weights=fwk.score_weights, fit_scorer=fit_scorer
        )
        # host Score plugins contribute here too (the one-pod analogue of
        # the batched extra_score merge)
        fwk.run_pre_score(state, [pod], feasible)
        if fwk.active_host_scores(state, [pod]):
            node_states = [st.nodes.get(n) for n in feasible]
            for name, scores in fwk.run_host_scores(
                state, pod, node_states
            ).items():
                w = fwk.score_weights.get(name, 0)
                for n, s in zip(feasible, scores):
                    totals[n] = totals.get(n, 0) + s * w
        for ext in self.extenders:
            if not ext.is_prioritizer() or not ext.is_interested(pod):
                continue
            try:
                scores = ext.prioritize(pod, feasible)
            except ExtenderError as e:
                if ext.ignorable:
                    continue
                status = Status.error(str(e))
                self._handle_failure(qp, status)
                return [ScheduleOutcome(pod, None, status, len(feasible), diag)]
            for n, s in scores.items():
                if n in totals:
                    totals[n] += s * ext.weight

        if self.config.tie_break_seed is not None and totals:
            # same seeded-hash rule as the device pipeline (gang tie_key):
            # lexicographic (score, hash) max over the oracle's node order
            if getattr(self, "_tie_key", None) is None:
                self._tie_key = jax.random.PRNGKey(self.config.tie_break_seed)
            k_p = jax.random.fold_in(self._tie_key, attempt)
            import numpy as np

            h = np.asarray(
                self._d2h(jax.random.bits(k_p, (n_nodes,), dtype=jnp.uint32))
            )
            idx_of = {n: i for i, n in enumerate(st.nodes)}
            node = max(totals, key=lambda n: (totals[n], int(h[idx_of[n]])))
        else:
            node = select_host(totals) if totals else feasible[0]
        binder = next(
            (
                e
                for e in self.extenders
                if e.is_binder() and e.is_interested(pod)
            ),
            None,
        )
        binder_override = None
        if binder is not None:

            def binder_override(pod, node_name, _ext=binder):
                try:
                    _ext.bind(pod, node_name)
                    # The extender performed the API write itself — against
                    # a real apiserver a second binding POST would conflict.
                    # Only in-proc stores that opt in (the FakeCluster test
                    # pattern, whose "API" IS the sink) get mirrored.
                    sink_self = getattr(self.binding_sink, "__self__", None)
                    if getattr(self.binding_sink, "mirror_extender_binds", False) or getattr(
                        sink_self, "mirror_extender_binds", False
                    ):
                        self.binding_sink(pod, node_name)
                except ExtenderError as e:
                    return Status.error(str(e))
                return Status.success()

        return [
            self._commit(
                fwk, state, qp, node, len(feasible), binder_override=binder_override
            )
        ]

    def _nominated_arrays(self, exclude_uids):
        """Pack nominations (minus this batch's own pods) into the gang
        dispatch's nom_* arrays."""
        import numpy as np

        from kubernetes_tpu.snapshot.schema import ResourceLanes

        lanes = ResourceLanes(self.mirror.vocab)
        R = self.mirror.nodes.allocatable.shape[1]
        rows = []
        for node, pod in self.nominator.entries():
            if pod.uid in exclude_uids:
                continue
            idx = self.mirror.nodes.name_to_idx.get(node)
            if idx is None:
                continue
            rows.append((idx, pod.priority, lanes.request_row(pod.compute_requests(), R)))
        if not rows:
            return None, None, None
        # Sticky bucketed padding: the nomination count changes every
        # preemption round — exact-size arrays would recompile the gang
        # pipeline per distinct count (~8s each).  Pad rows use node=-1,
        # which matches no node in the kernel's one-hot and contributes 0.
        self._nom_cap_max = max(
            getattr(self, "_nom_cap_max", 1), bucket_cap(len(rows), 1)
        )
        G = self._nom_cap_max
        nom_node = np.full(G, -1, dtype=np.int32)
        nom_prio = np.zeros(G, dtype=np.int32)
        nom_req = np.zeros((G, R), dtype=np.int32)
        for i, (idx, prio, req) in enumerate(rows):
            nom_node[i] = idx
            nom_prio[i] = prio
            nom_req[i] = req
        return jnp.asarray(nom_node), jnp.asarray(nom_prio), jnp.asarray(nom_req)

    def _host_filter_mask(self, fwk, state, pods, p_cap: int, db=None, enabled=None):
        """[p_cap, N] bool: True where host Filter plugins allow the pair
        (the post-device-veto path of runtime:861 for host-backed plugins).

        The walk is NARROWED to nodes surviving the device static filters
        (one static_eval dispatch): statically-dead nodes are rejected by
        the device mask regardless, and the reference's per-node filter
        chain early-exits before host plugins there too — so skipping them
        both matches reason attribution and turns the O(pods × all-nodes)
        plugin-call storm into O(pods × surviving-nodes).

        Also returns per-pod failure detail for Diagnosis fidelity
        (types.go:367): ``diags[i]`` maps reason-string → node count and
        ``plugin_sets[i]`` names the rejecting plugins (drives queueing
        hints)."""
        import numpy as np

        nt = self.mirror.nodes
        n_cap = nt.valid.shape[0]
        mask = np.ones((p_cap, n_cap), dtype=bool)
        st = self.oracle_view()
        node_states = [
            st.nodes.get(nt.names[j]) if j < len(nt.names) else None
            for j in range(n_cap)
        ]
        candidates = None
        if db is not None and len(pods) * n_cap >= 4096:
            try:
                from kubernetes_tpu.ops import fastpath as ops_fp

                res = ops_fp.static_eval(
                    self._static_device_cluster(),
                    db,
                    enabled=enabled
                    if enabled is not None
                    else fwk.device_enabled(),
                    has_images=False,
                )
                candidates = np.asarray(
                    self._d2h(res["mask"], kernel="fastpath.static_eval")
                )
            except Exception:  # noqa: BLE001 — narrowing is best-effort
                candidates = None
        diags: List[Dict[str, int]] = [dict() for _ in pods]
        plugin_sets: List[set] = [set() for _ in pods]
        for i, pod in enumerate(pods):
            # RunFilterPluginsWithNominatedPods (runtime:973) for the host
            # veto pass: nominated preemptors of >= priority count as
            # present on their node, with PreFilter AddPod extensions.
            added = []
            if len(self.nominator):
                for node, np_ in self.nominator.entries():
                    if np_.uid != pod.uid and np_.priority >= pod.priority:
                        ns0 = st.nodes.get(node)
                        if ns0 is not None:
                            ns0.add_pod(np_)
                            fwk.run_pre_filter_extension_add_pod(
                                state, pod, np_, ns0
                            )
                            added.append((ns0, np_))
            try:
                for j, ns in enumerate(node_states):
                    if ns is None or not nt.valid[j]:
                        continue
                    if candidates is not None and not candidates[i, j]:
                        continue  # statically dead — device mask rejects it
                    s = fwk.run_host_filters(state, pod, ns)
                    if not s.ok:
                        mask[i, j] = False
                        reason = s.merge_reason() or s.plugin
                        diags[i][reason] = diags[i].get(reason, 0) + 1
                        if s.plugin:
                            plugin_sets[i].add(s.plugin)
            finally:
                for ns0, np_ in added:
                    ns0.remove_pod(np_)
                    fwk.run_pre_filter_extension_remove_pod(
                        state, pod, np_, ns0
                    )
            if added:
                # the reference's SECOND pass (runtime:973): a node that
                # passed only BECAUSE of an unbound nominated pod must also
                # pass without it — re-check passing nodes that carried
                # nominated adds now that the state is back to neutral
                nom_nodes = {ns0.node.name for ns0, _ in added}
                for j, ns in enumerate(node_states):
                    if (
                        ns is None
                        or not nt.valid[j]
                        or not mask[i, j]
                        or ns.node.name not in nom_nodes
                        or (candidates is not None and not candidates[i, j])
                    ):
                        continue
                    s = fwk.run_host_filters(state, pod, ns)
                    if not s.ok:
                        mask[i, j] = False
                        reason = "node(s) only feasible with unbound nominated pods"
                        diags[i][reason] = diags[i].get(reason, 0) + 1
                        if s.plugin:
                            plugin_sets[i].add(s.plugin)
        return jnp.asarray(mask), diags, plugin_sets

    def _sampling_args(self, fwk):
        """(sample_k, tie_key, attempt_base) device args for the bit-compat
        sampling/tie-break mode, or (None, None, None) when full-width
        first-max (the TPU-native default) applies."""
        from kubernetes_tpu.oracle.pipeline import num_feasible_nodes_to_find

        pct = fwk.percentage_of_nodes_to_score
        if pct is None:
            pct = self.config.percentage_of_nodes_to_score
        sample_k = None
        if pct > 0 or self.config.reference_sampling_compat:
            n_valid = len(self.cache.real_nodes())
            k = num_feasible_nodes_to_find(pct, n_valid)
            # k >= n visits every node, but compat mode still needs the
            # kernel's VISIT-ORDER branch: the reference walks (and
            # first-max tie-breaks) in nodeTree zone-round-robin order even
            # when nothing is cut, so pass k = n rather than disabling
            sample_k = jnp.asarray(min(k, n_valid), I32)
        tie_key = None
        if self.config.tie_break_seed is not None:
            if getattr(self, "_tie_key", None) is None:
                self._tie_key = jax.random.PRNGKey(self.config.tie_break_seed)
            tie_key = self._tie_key
        if sample_k is None and tie_key is None:
            return None, None, None
        return sample_k, tie_key, jnp.asarray(
            getattr(self, "_attempt_counter", 0), I32
        )

    def _sampling_active(self, fwk) -> bool:
        pct = fwk.percentage_of_nodes_to_score
        if pct is None:
            pct = self.config.percentage_of_nodes_to_score
        return (
            pct > 0
            or self.config.reference_sampling_compat
            or self.config.tie_break_seed is not None
        )

    def _batched_preemption_narrow(
        self, fwk, state, failed, batch=None, chosen=None, node_names=None
    ) -> None:
        """ONE device dispatch shortlisting preemption candidates for every
        failed pod of a batch (ops/preemption.narrow_candidates — the
        batched front of DryRunPreemption, preemption.go:548).  Shortlists
        land in the CycleState under ("preemption_potential", uid);
        DefaultPreemption passes them into the evaluator.  Best-effort: on
        any precondition failure the evaluator's host walk runs unassisted.

        ``batch``/``chosen``/``node_names`` hand over the dispatch's OWN
        committed placements — the admission scan's carried state, which
        the cache cannot show yet (commits happen in the result walk after
        this) — so victim evaluation reuses them instead of re-deriving
        peer state: strictly-higher-priority peers charge the kept plane,
        lower ones count as removable victims (ops/preemption.py
        docstring).  ``node_names`` is the DISPATCH-TIME packing's name
        list: the mirror.update() below may full-repack and compact node
        slots, so peers resolve name→current-index like the victim rows
        do, never by raw dispatch index."""
        import numpy as np

        from kubernetes_tpu.ops import preemption as ops_preemption
        from kubernetes_tpu.snapshot.schema import ResourceLanes

        with self._mu:
            if self.mirror.nodes is None or not failed:
                return
            try:
                vocab = self.mirror.vocab
                self.mirror.update(self.cache, self.namespace_labels)
                nt = self.mirror.nodes
                dc = self._static_device_cluster()
                pods = [qp.pod for qp in failed]
                # sticky bucket: retry rounds with shrinking failure sets
                # must not each compile a new narrow shape
                self._p_cap_max = max(
                    self._p_cap_max, self._p_bucket(len(pods))
                )
                pb = pack_pod_batch(
                    pods,
                    vocab,
                    k_cap=nt.k_cap,
                    p_cap=self._p_cap_max,
                    namespace_labels=self.namespace_labels,
                )
                placed = self.cache.placed_pods()
                lanes = ResourceLanes(vocab)
                R = nt.allocatable.shape[1]
                # sticky: the placed-pod count SHRINKS as victims are
                # evicted — tracking the running max avoids one recompile
                # per crossed bucket boundary on the way down
                self._vic_cap_max = max(
                    getattr(self, "_vic_cap_max", 1),
                    bucket_cap(max(len(placed), 1)),
                )
                E = self._vic_cap_max
                vnode = np.full(E, -1, np.int32)
                vprio = np.zeros(E, np.int32)
                vreq = np.zeros((E, R), np.int32)
                for i, p in enumerate(placed):
                    idx = nt.name_to_idx.get(p.node_name)
                    if idx is None:
                        continue
                    vnode[i] = idx
                    vprio[i] = p.priority
                    vreq[i] = lanes.request_row(p.compute_requests(), R)
                distinct = sorted({p.priority for p in pods})
                G = bucket_cap(len(distinct), 1)
                groups = np.full(G, np.iinfo(np.int32).min, np.int32)
                groups[: len(distinct)] = distinct
                gidx = {pr: i for i, pr in enumerate(distinct)}
                pod_group = np.zeros(pb.valid.shape[0], np.int32)
                for i, p in enumerate(pods):
                    pod_group[i] = gidx[p.priority]
                tree = {
                    "vnode": vnode,
                    "vprio": vprio,
                    "vreq": vreq,
                    "groups": groups,
                    "pg": pod_group,
                }
                if (
                    batch is not None
                    and chosen is not None
                    and node_names is not None
                ):
                    # this dispatch's committed peers (sticky bucket like
                    # the victim plane — retry rounds must not recompile)
                    self._bpeer_cap_max = max(
                        getattr(self, "_bpeer_cap_max", 1),
                        bucket_cap(max(len(batch), 1), 1),
                    )
                    B2 = self._bpeer_cap_max
                    bnode = np.full(B2, -1, np.int32)
                    bprio = np.zeros(B2, np.int32)
                    breq = np.zeros((B2, R), np.int32)
                    for i, qp in enumerate(batch):
                        c = int(chosen[i])
                        if c < 0 or c >= len(node_names):
                            continue
                        # dispatch index → name → CURRENT slot (the
                        # repack above may have moved it)
                        idx = nt.name_to_idx.get(node_names[c])
                        if idx is None:
                            continue
                        bnode[i] = idx
                        bprio[i] = qp.pod.priority
                        breq[i] = lanes.request_row(
                            qp.pod.compute_requests(), R
                        )
                    tree.update(bnode=bnode, bprio=bprio, breq=breq)
                from kubernetes_tpu.ops import wire

                # device-fault tier: narrowing is an optimization — an
                # open breaker (or the best-effort except below, for an
                # abandoned dispatch) leaves the FULL candidate set, which
                # is superset-sound by construction
                if self._breaker_blocked("preemption.narrow_candidates"):
                    return
                t = wire.device_put_packed(tree)
                masks_dev = ops_preemption.narrow_candidates(
                    dc,
                    self._place_db(DeviceBatch.from_host(pb)),
                    t["vnode"],
                    t["vprio"],
                    t["vreq"],
                    t["groups"],
                    t["pg"],
                    batch_node=t.get("bnode"),
                    batch_prio=t.get("bprio"),
                    batch_req=t.get("breq"),
                )
                masks = np.asarray(
                    self._d2h(
                        masks_dev, kernel="preemption.narrow_candidates"
                    )
                )
                names = nt.names
                for i, qp in enumerate(failed):
                    short = {
                        names[j]
                        for j in np.nonzero(masks[i])[0]
                        if j < len(names)
                    }
                    state.write(("preemption_potential", qp.pod.uid), short)
            except Exception:  # noqa: BLE001 — narrowing is best-effort
                return

    def _host_score_matrix(self, fwk, state, pods, p_cap: int):
        """[p_cap, N] i64: Σ weight·normalized host-plugin scores per
        (pod, node) — merged additively into the device total before the
        argmax (RunScorePlugins runtime/framework.go:1101-1207 for plugins
        without kernels).  NormalizeScore runs over the valid node set; a
        kernel-less plugin whose normalize depends on the *dynamic* feasible
        set is not representable here (none in-tree does)."""
        import numpy as np

        nt = self.mirror.nodes
        n_cap = nt.valid.shape[0]
        total = np.zeros((p_cap, n_cap), dtype=np.int64)
        st = self.oracle_view()
        node_states = [
            st.nodes.get(nt.names[j]) if j < len(nt.names) and nt.valid[j] else None
            for j in range(n_cap)
        ]
        relevant = {p.name: p for p in fwk.active_host_scores(state, pods)}
        for i, pod in enumerate(pods):
            if not any(
                p.score_relevant(pod)
                and not state.is_score_skipped(pod.uid, p.name)
                for p in relevant.values()
            ):
                continue
            per_plugin = fwk.run_host_scores(state, pod, node_states)
            for name, scores in per_plugin.items():
                w = fwk.score_weights.get(name, 0)
                if not w:
                    continue
                total[i] += np.asarray(scores, dtype=np.int64) * w
        return jnp.asarray(total)

    def _post_filter_or_fail(
        self,
        fwk,
        state,
        qp,
        status: Status,
        n_feas: int,
        diagnosis: Optional[Dict[str, int]] = None,
        plugins: Optional[set] = None,
    ) -> ScheduleOutcome:
        """Route a filter failure into PostFilter (preemption) when the
        profile has one (schedule_one.go:135-180).  Holds the cache lock:
        the preemption dry-run reads (and temporarily patches) the SHARED
        oracle view, which binding workers now patch in place on
        forget/assume — unsynchronized interleaving would corrupt it.
        The ``post_filter`` span is the failure path's whole cost for one
        pod (diagnosis record, PostFilter's dry run, requeue, the
        FailedScheduling event); it nests inside ``commit``."""
        with self._span("post_filter"):
            sp_lock = self._span("post_filter.lock_wait").begin()
            with self._mu:
                sp_lock.end()
                return self._post_filter_or_fail_locked(
                    fwk, state, qp, status, n_feas, diagnosis, plugins
                )

    def _post_filter_or_fail_locked(
        self,
        fwk,
        state,
        qp,
        status: Status,
        n_feas: int,
        diagnosis: Optional[Dict[str, int]] = None,
        plugins: Optional[set] = None,
    ) -> ScheduleOutcome:
        pod = qp.pod
        fr = self.flight
        if fr.enabled and status.code in (
            Code.UNSCHEDULABLE,
            Code.UNSCHEDULABLE_AND_UNRESOLVABLE,
        ):
            # the diagnosis counts the kernels already fetched ride along
            # for free — /debug/explain is the full per-node drill-down
            fr.record(
                pod.uid,
                "unschedulable",
                {
                    "plugins": sorted(plugins) if plugins else (
                        [status.plugin] if status.plugin else []
                    ),
                    "diagnosis": diagnosis,
                    "reasons": list(status.reasons)[:3],
                },
            )
        if fwk.has_post_filter() and status.code == Code.UNSCHEDULABLE:
            nominated, pf_status = fwk.run_post_filter(state, pod, None)
            if nominated:
                pod.nominated_node_name = nominated
                self.nominator.add(pod, nominated)
                self.status_patcher(pod)  # schedule_one.go:1117 PatchPodStatus
                if fr.enabled:
                    fr.record(pod.uid, "nominated", {"node": nominated})
            elif nominated == "" and pod.nominated_node_name:
                pod.nominated_node_name = ""
                self.nominator.delete(pod)
                self.status_patcher(pod)
        elif (
            status.code == Code.UNSCHEDULABLE_AND_UNRESOLVABLE
            and pod.nominated_node_name
        ):
            # Preemption can't resolve this class of failure — clear the
            # stale nomination so it stops reserving capacity.
            pod.nominated_node_name = ""
            self.nominator.delete(pod)
            self.status_patcher(pod)
        self._handle_failure(qp, status, plugins)
        return ScheduleOutcome(pod, None, status, n_feas, diagnosis)

    def _commit(
        self,
        fwk,
        state,
        qp,
        node_name: str,
        n_feas: int,
        binder_override=None,
        from_fast: bool = False,
    ) -> ScheduleOutcome:
        """The scheduling-cycle tail: assume → reserve → permit, then hand
        the pod to an async binding worker (schedule_one.go:117-129 — the
        goroutine-per-pod bindingCycle).  The returned outcome is
        provisional; a bind failure patches it to unschedulable before
        schedule_pending returns (its end-of-drain barrier).
        ``binder_override`` replaces the in-tree bind plugins when a binder
        extender claims the pod (schedule_one.go extendersBinding)."""
        has_rp = fwk.has_reserve_or_permit()
        with self._mu:
            if not from_fast:
                # scan/extender-path commits advance cache state the fast
                # committer didn't see — its cache key must change
                self._nonfast_commits += 1
            return self._commit_under_lock(
                fwk, state, qp, node_name, n_feas, binder_override, has_rp
            )

    def _commit_under_lock(
        self,
        fwk,
        state,
        qp,
        node_name,
        n_feas,
        binder_override,
        has_rp,
        lean: bool = False,
    ) -> ScheduleOutcome:
        """The _commit body with self._mu already held — lets the fast
        harvest commit a whole run of pods under ONE lock acquisition."""
        from kubernetes_tpu.cache.cache import CacheError

        if self._sanitize:
            sanitizer.assert_owned(self._mu, "_commit_under_lock")
        pod = qp.pod
        try:
            self.cache.assume_pod(pod, node_name)
        except CacheError as e:
            # the pod was assumed/added concurrently (an external binding
            # raced our decision — the multi-scheduler window): fail THIS
            # pod and let the event stream settle it; the drain continues
            s = Status.error(f"assume failed: {e}")
            self._handle_failure(qp, s)
            return ScheduleOutcome(pod, None, s, n_feas)
        ps = self.cache.pod_states.get(pod.uid)
        assumed = ps.pod if ps is not None else pod
        self._view_pod_added(assumed)

        waited = False
        if has_rp:
            s = fwk.run_reserve(state, pod, node_name)
            if not s.ok:
                self._external_mutations += 1  # committer state diverges
                self._view_pod_removed(assumed)
                self.cache.forget_pod(pod)
                if self.flight.enabled:
                    self.flight.record(
                        pod.uid,
                        "verdict",
                        {"ext": "Reserve", "plugin": s.plugin, "node": node_name},
                    )
                self._handle_failure(qp, s)
                return ScheduleOutcome(pod, None, s, n_feas)

            s = fwk.run_permit(state, pod, node_name)
            if s.rejected or s.code == Code.ERROR:
                fwk.run_unreserve(state, pod, node_name)
                self._external_mutations += 1  # committer state diverges
                self._view_pod_removed(assumed)
                self.cache.forget_pod(pod)
                if self.flight.enabled:
                    self.flight.record(
                        pod.uid,
                        "verdict",
                        {"ext": "Permit", "plugin": s.plugin, "node": node_name},
                    )
                self._handle_failure(qp, s)
                return ScheduleOutcome(pod, None, s, n_feas)
            waited = s.code == Code.WAIT

        if self.flight.enabled:
            self.flight.record(
                pod.uid, "assumed", {"node": node_name, "waited": waited}
            )
        outcome = ScheduleOutcome(
            pod,
            node_name,
            Status.success(),
            n_feas,
            pod_attempts=qp.attempts,
            first_enqueue_time=qp.timestamp,
            first_enqueue_mono=qp.mono_timestamp or None,
        )
        task = _BindTask(
            fwk, state, qp, node_name, waited, binder_override, outcome, lean,
            bid=self._bid,
        )
        if waited:
            # A Wait-ed pod's cycle can block on permit for its timeout —
            # it must not serialize behind (or ahead of) other pods' binds;
            # it gets a dedicated worker like the reference's goroutine.
            self._ensure_bind_pool()
            self._inflight_binds.append(
                self._bind_pool.submit(self._binding_cycle, task)
            )
        else:
            # Common case: buffer and submit in chunks at batch end — one
            # future per ~64 pods instead of per pod (submit + wakeup
            # overhead dominates when the bind sink is an in-proc store).
            self._bind_buffer.append(task)
        return outcome

    def _commit_fast_bulk(
        self,
        fwk,
        state,
        batch,
        choices,
        i,
        j,
        node_names,
        outcomes,
        idxs=None,
        n_feas=None,
        nonfast: bool = False,
    ) -> None:
        """Commit batch[i:j] — a contiguous run of fast-scheduled, lean
        pods — as ONE vectorized pass: bulk assume into the cache (per-node
        aggregated accounting), shared success Status, and a single bulk
        binding task instead of per-pod _BindTasks.  Decisions are
        untouched (they were made by the kernel/committer); this collapses
        the per-pod Python of the commit tail, which the config0 phase
        breakdown showed dominating the drain.  Falls back per pod
        (_commit_under_lock) whenever reserve/permit could act or a
        non-default binder is configured — see _finish_fast's bulk_ok.

        ``idxs`` replaces the [i:j) slice with an explicit index list (a
        wave batch's placed pods); ``n_feas`` supplies
        per-pod feasible counts for the outcomes (-1 otherwise);
        ``nonfast`` marks commits the fast committer didn't make, bumping
        the mirror-sync epoch the way per-pod _commit does."""
        if idxs is None:
            idxs = range(i, j)
        run = [batch[k] for k in idxs]
        names = [node_names[choices[k]] for k in idxs]
        feas = (
            [-1] * len(run)
            if n_feas is None
            else [int(n_feas[k]) for k in idxs]
        )
        # Seed the per-pod request memos from a representative keyed by RAW
        # spec identity (fastpath.spec_key — the exact request strings)
        # before the cache accounting reads them: template-stamped pods
        # share one quantity parse, and the memoized Resources are
        # read-only by contract.  Keying by Signature would be wrong here:
        # signature rows QUANTIZE (ceil-MiB memory lanes), so byte-
        # different pods can share a Signature, and stamping them with the
        # representative's Resources would charge the cache the wrong
        # values for the placement's whole lifetime.
        from kubernetes_tpu import fastpath as fp

        with self._span("commit.requests"):
            req_by_spec: Dict[object, tuple] = {}
            for qp_ in run:
                pod = qp_.pod
                d = pod.__dict__
                if "_nzreq_memo" in d:
                    continue
                sk = fp.spec_key_memo(pod)
                rep = req_by_spec.get(sk) if sk is not None else None
                if rep is None:
                    rep = (pod.compute_requests(), pod.non_zero_requests())
                    if sk is not None:
                        req_by_spec[sk] = rep
                else:
                    d["_req_memo"], d["_nzreq_memo"] = rep
        # one Status shared by the whole run: success statuses are treated
        # as immutable everywhere (failure paths REPLACE outcome.status)
        success = STATUS_SUCCESS
        items = []
        sp_lock = self._span("commit.lock_wait").begin()
        with self._mu:
            sp_lock.end()
            if self._sanitize:
                sanitizer.assert_owned(self._mu, "_commit_fast_bulk")
            if nonfast:
                # scan/wave-path commits advance cache state the fast
                # committer didn't see — its cache key must change
                self._nonfast_commits += len(run)
            with self._span("commit.assume"):
                results = self.cache.assume_pods_bulk(
                    list(zip((qp.pod for qp in run), names))
                )
            sp_out = self._span("commit.outcomes").begin()
            view_live = self._oracle_cache is not None
            fr = self.flight
            fr_on = fr.enabled
            fr_events = [] if fr_on else None
            for qp, nn, nf, res in zip(run, names, feas, results):
                if isinstance(res, str):
                    # protocol violation (double assume — the multi-
                    # scheduler race): fail the pod AND rebuild the fast
                    # lineage, whose committer already charged this
                    # placement the cache just rejected
                    self._external_mutations += 1
                    s = Status.error(f"assume failed: {res}")
                    self._handle_failure(qp, s)
                    outcomes.append(ScheduleOutcome(qp.pod, None, s, -1))
                    continue
                if view_live:
                    self._view_pod_added(res)
                if fr_on:
                    fr_events.append((qp.pod.uid, "assumed", {"node": nn}))
                outcome = ScheduleOutcome(
                    qp.pod,
                    nn,
                    success,
                    nf,
                    pod_attempts=qp.attempts,
                    first_enqueue_time=qp.timestamp,
                    first_enqueue_mono=qp.mono_timestamp or None,
                )
                outcomes.append(outcome)
                items.append((qp, nn, outcome))
        if fr_events:
            fr.record_many(fr_events)
        if items:
            self._bulk_bind_buffer.append(
                _BulkBindTask(fwk, state, items, bid=self._bid)
            )
        sp_out.end()

    def _ensure_bind_pool(self) -> None:
        if self._bind_pool is None:
            self._bind_pool = ThreadPoolExecutor(
                max_workers=max(self.config.parallelism, 1),
                thread_name_prefix="binding-cycle",
            )

    def _flush_binds(self, chunk: int = 64) -> None:
        """Submit buffered binding cycles, chunked — called at batch end so
        bindings still overlap the NEXT batch's device dispatch.  The chunk
        shrinks when the buffer is small relative to the worker pool so a
        single (possibly extended) batch still spreads its binds across all
        workers — one future per ~64 pods is only the ceiling.  Bulk tasks
        (fast-path runs) split into per-worker slices the same way, but
        keep their one-sink-write/one-lock-tail discipline per slice."""
        if not self._bulk_bind_buffer and not self._bind_buffer:
            return
        with self._span("flush_binds"):
            self._submit_binds(chunk)

    def _submit_binds(self, chunk: int) -> None:
        tasks = 0  # the futures this flush hands to the pool
        bulk = self._bulk_bind_buffer
        if bulk:
            self._bulk_bind_buffer = []
            self._ensure_bind_pool()
            workers = max(self.config.parallelism, 1)
            sink_many = self.binding_sink_many is not None
            for t in bulk:
                n = len(t.items)
                if sink_many:
                    # one bulk write + one lock tail per slice: big slices,
                    # or worker threads just fight the GIL with the
                    # scheduling loop over a few dict ops each
                    per = max(1024, -(-n // workers))
                else:
                    # per-pod sink calls may block on I/O (the reference's
                    # binding goroutine shape): small slices spread them
                    # across the pool so latencies overlap
                    per = min(64, max(1, -(-n // workers)))
                for lo in range(0, n, per):
                    part = _BulkBindTask(
                        t.fwk, t.state, t.items[lo : lo + per],
                        bid=t.bid, t_submit=time.perf_counter(),
                    )
                    self._inflight_binds.append(
                        self._bind_pool.submit(self._binding_bulk, part)
                    )
                    tasks += 1
        buf = self._bind_buffer
        if buf:
            chunk = min(chunk, max(1, -(-len(buf) // max(self.config.parallelism, 1))))
            self._bind_buffer = []
            self._ensure_bind_pool()
            for i in range(0, len(buf), chunk):
                part = buf[i : i + chunk]
                part[0].t_submit = time.perf_counter()
                self._inflight_binds.append(
                    self._bind_pool.submit(self._binding_chunk, part)
                )
                tasks += 1
        self.phases.count("bind.tasks", tasks)

    def _binding_bulk(self, t: "_BulkBindTask") -> None:
        """One worker's slice of a bulk fast-path binding run.

        The per-pod walk collapses by construction: the fast gate proved
        PreBind irrelevant and DefaultBinder is the only Bind plugin
        (lean), and no Reserve/Permit plugin can act — so the cycle is
        exactly one sink write per pod (or ONE bulk write for the slice
        when the API tier installed binding_sink_many) plus the post-bind
        bookkeeping, settled under a single lock acquisition.  Failures
        unwind per pod through the standard _bind_fail path."""
        from kubernetes_tpu import events as ev

        phases = self.phases
        sp_bind = phases.span("bind", bid=t.bid, pods=len(t.items)).begin()
        if t.t_submit:
            phases.add("bind.queue_wait", time.perf_counter() - t.t_submit)
        fwk, state, items = t.fwk, t.state, t.items
        fr = self.flight
        if fr.enabled:
            # worker picked the slice up: closes the commit stage (assumed
            # → bind_start) in the SLO tier's attribution join
            fr.record_many(
                (qp.pod.uid, "bind_start", None) for qp, _, _ in items
            )
        ok_items = []
        sink_many = self.binding_sink_many
        if sink_many is not None and len(items) > 1:
            try:
                with phases.span("bind.sink", bid=t.bid):
                    errs = sink_many([(qp.pod, nn) for qp, nn, _ in items])
            except Exception as e:  # noqa: BLE001 — whole-slice failure
                errs = [str(e)] * len(items)
            if not isinstance(errs, (list, tuple)) or len(errs) != len(items):
                # a misaligned result list would silently drop pods from
                # the zip below, leaking them as assumed-forever — treat
                # it as a whole-slice failure instead
                errs = ["bulk binding sink returned misaligned results"] * len(
                    items
                )
            for (qp, nn, outcome), err in zip(items, errs):
                if err is None:
                    ok_items.append((qp, nn, outcome))
                else:
                    self._bind_fail(fwk, state, qp, nn, outcome, Status.error(err))
        else:
            sink = self.binding_sink
            # one span for the slice's sink calls (never one per pod); a
            # failed pod's unwind inside it is the rare path
            with phases.span("bind.sink", bid=t.bid):
                for qp, nn, outcome in items:
                    try:
                        sink(qp.pod, nn)
                    except Exception as e:  # noqa: BLE001 — surfaced as Status
                        self._bind_fail(
                            fwk, state, qp, nn, outcome,
                            Status.error(f"binding cycle panicked: {e}"),
                        )
                        continue
                    ok_items.append((qp, nn, outcome))
        sp_tail = None
        if ok_items:
            sp_lock = phases.span("bind.lock_wait", bid=t.bid).begin()
            with self._mu:
                sp_lock.end()
                sp_tail = phases.span("bind.tail", bid=t.bid).begin()
                queue_done = self.queue.done
                finish = self.cache.finish_binding
                nom = self.nominator if len(self.nominator) else None
                for qp, _, _ in ok_items:
                    pod = qp.pod
                    queue_done(pod.uid)
                    finish(pod)
                    if nom is not None:
                        nom.delete(pod)
                self.metrics["scheduled"] += len(ok_items)
            fr = self.flight
            if fr.enabled:
                fr.record_many(
                    (qp.pod.uid, "bound", {"node": nn})
                    for qp, nn, _ in ok_items
                )
            if fwk.has_post_bind():
                for qp, nn, _ in ok_items:
                    fwk.run_post_bind(state, qp.pod, nn)
            rec = self.recorders.get(ok_items[0][0].pod.scheduler_name)
            if rec is not None and not isinstance(rec, ev.NullRecorder):
                for qp, nn, _ in ok_items:
                    pod = qp.pod
                    rec.eventf(
                        ev.ObjectRef.for_pod(pod),
                        ev.TYPE_NORMAL,
                        "Scheduled",
                        "Binding",
                        f"Successfully assigned {pod.key} to {nn}",
                    )
        if sp_tail is not None:
            sp_tail.end()
        dt = sp_bind.end()
        if items:
            # amortized binding latency: the slice shares one wall clock
            self.prom.binding_duration.observe_n(dt / len(items), len(items))

    def _binding_chunk(self, part: List["_BindTask"]) -> None:
        """One worker's buffered binding cycles.  Lean cycles (fast batches
        with the default binder only) run their sink calls first and then
        settle ALL their post-bind tails (queue.done / finish_binding /
        nominator) under ONE lock acquisition — the tail work is pure
        bookkeeping, so batching it shrinks per-pod lock traffic without
        changing what any concurrent reader can observe mid-chunk."""
        from kubernetes_tpu import events as ev

        phases = self.phases
        bid = part[0].bid
        sp_bind = phases.span("bind", bid=bid, pods=len(part)).begin()
        if part[0].t_submit:
            phases.add(
                "bind.queue_wait", time.perf_counter() - part[0].t_submit
            )
        lean_ok = []
        lean_tasks = [t for t in part if t.lean_eligible()]
        fr = self.flight
        if fr.enabled and lean_tasks:
            # lean tasks bind inline below; non-lean ones route through
            # _binding_cycle, which records its own bind_start
            fr.record_many(
                (t.qp.pod.uid, "bind_start", None) for t in lean_tasks
            )
        sink_many = getattr(self, "binding_sink_many", None)
        if sink_many is not None and len(lean_tasks) > 1:
            # BULK sink (the API tier's /bindings endpoint): the whole
            # chunk's bindings ride one write; per-item errors unwind
            # exactly the pods that failed
            try:
                with phases.span("bind.sink", bid=bid):
                    errs = sink_many(
                        [(t.qp.pod, t.node_name) for t in lean_tasks]
                    )
            except Exception as e:  # noqa: BLE001 — whole-batch failure
                errs = [str(e)] * len(lean_tasks)
            if not isinstance(errs, (list, tuple)) or len(errs) != len(
                lean_tasks
            ):
                # misaligned results would drop tasks from the zip —
                # whole-batch failure keeps every pod accounted for
                errs = ["bulk binding sink returned misaligned results"] * len(
                    lean_tasks
                )
            for t, err in zip(lean_tasks, errs):
                if err is None:
                    lean_ok.append(t)
                else:
                    self._bind_fail(
                        t.fwk, t.state, t.qp, t.node_name, t.outcome,
                        Status.error(err),
                    )
            lean_handled = set(map(id, lean_tasks))
        else:
            lean_handled = set()
        direct = [
            t for t in part if id(t) not in lean_handled and t.lean_eligible()
        ]
        if direct:
            # one span for the chunk's direct sink calls (never one per
            # pod); a failed pod's unwind inside it is the rare path
            with phases.span("bind.sink", bid=bid):
                for t in direct:
                    try:
                        s = t.fwk.run_bind_direct(
                            t.state, t.qp.pod, t.node_name
                        )
                    except Exception as e:  # noqa: BLE001 — surfaced as Status
                        s = Status.error(f"binding cycle panicked: {e}")
                    if s.ok:
                        lean_ok.append(t)
                    else:
                        self._bind_fail(
                            t.fwk, t.state, t.qp, t.node_name, t.outcome, s
                        )
        for t in part:
            if not t.lean_eligible():
                self._binding_cycle(t)
        if not lean_ok:
            sp_bind.end()
            return
        sp_lock = phases.span("bind.lock_wait", bid=bid).begin()
        with self._mu:
            sp_lock.end()
            sp_tail = phases.span("bind.tail", bid=bid).begin()
            for t in lean_ok:
                pod = t.qp.pod
                self.queue.done(pod.uid)
                self.cache.finish_binding(pod)
                self.nominator.delete(pod)
            self.metrics["scheduled"] += len(lean_ok)
        fr = self.flight
        if fr.enabled:
            fr.record_many(
                (t.qp.pod.uid, "bound", {"node": t.node_name})
                for t in lean_ok
            )
        for t in lean_ok:
            pod = t.qp.pod
            t.fwk.run_post_bind(t.state, pod, t.node_name)
            rec = self.recorders.get(pod.scheduler_name)
            if rec is not None and not isinstance(rec, ev.NullRecorder):
                rec.eventf(
                    ev.ObjectRef.for_pod(pod),
                    ev.TYPE_NORMAL,
                    "Scheduled",
                    "Binding",
                    f"Successfully assigned {pod.key} to {t.node_name}",
                )
        sp_tail.end()
        sp_bind.end()

    def _bind_fail(self, fwk, state, qp, node_name, outcome, s) -> None:
        """Bind-failure unwind: Unreserve + ForgetPod + requeue under the
        cache lock (schedule_one.go:342-374), outcome patched in place."""
        pod = qp.pod
        if self.flight.enabled:
            self.flight.record(
                pod.uid,
                "bind_failed",
                {"node": node_name, "reasons": list(s.reasons)[:3]},
            )
        with self._mu:
            # The in-flight ledger is still intact here, so events that
            # arrived during the attempt replay through add_unschedulable.
            fwk.run_unreserve(state, pod, node_name)
            self._external_mutations += 1  # committer state diverges
            ps = self.cache.pod_states.get(pod.uid)
            if ps is not None:
                self._view_pod_removed(ps.pod)
            self.cache.forget_pod(pod)
            self.gangs.note_removed(pod)  # quorum bookkeeping unwinds too
            self._handle_failure(qp, s)
        outcome.node = None
        outcome.status = s

    def _binding_cycle(self, t: "_BindTask") -> None:
        """WaitOnPermit → PreBind → Bind → PostBind on a worker thread
        (schedule_one.go:263-340); failure unwinds via Unreserve + ForgetPod
        + requeue under the cache lock (:342-374).  A lean task (fast
        batches whose gate proved PreBind irrelevant and whose only binder
        is the default) collapses the walk to the direct sink call."""
        fwk, state, qp, node_name = t.fwk, t.state, t.qp, t.node_name
        waited, binder_override, outcome = t.waited, t.binder_override, t.outcome
        pod = qp.pod
        if self.flight.enabled:
            self.flight.record(pod.uid, "bind_start", None)
        try:
            if t.lean_eligible():
                s = fwk.run_bind_direct(state, pod, node_name)
            else:
                s = fwk.wait_on_permit(pod) if waited else Status.success()
                if s.ok:
                    s = fwk.run_pre_bind(state, pod, node_name)
                if s.ok:
                    if binder_override is not None:
                        s = binder_override(pod, node_name)
                    else:
                        s = fwk.run_bind(state, pod, node_name)
        except Exception as e:  # noqa: BLE001 — surfaced as Status
            s = Status.error(f"binding cycle panicked: {e}")
        if not s.ok:
            self._bind_fail(fwk, state, qp, node_name, outcome, s)
            return
        with self._mu:
            self.queue.done(pod.uid)
            self.cache.finish_binding(pod)
            self.nominator.delete(pod)
            self.metrics["scheduled"] += 1
        if self.flight.enabled:
            self.flight.record(pod.uid, "bound", {"node": node_name})
        fwk.run_post_bind(state, pod, node_name)
        from kubernetes_tpu import events as ev

        self.recorders.get(pod.scheduler_name, ev.NullRecorder()).eventf(
            ev.ObjectRef.for_pod(pod),
            ev.TYPE_NORMAL,
            "Scheduled",
            "Binding",
            f"Successfully assigned {pod.key} to {node_name}",
        )

    def wait_for_bindings(self) -> None:
        """Barrier: block until every in-flight binding cycle completed and
        its outcome is final (the analogue of draining the reference's
        binding goroutines)."""
        self._flush_binds()
        while self._inflight_binds:
            futs, self._inflight_binds = self._inflight_binds, []
            for f in futs:
                f.result()

    def _handle_failure(self, qp, status: Status, plugins: Optional[set] = None) -> None:
        """handleSchedulingFailure (schedule_one.go:1020).  ``plugins`` is
        the rejecting-plugin set driving queueing-hint requeue; it defaults
        to the status's single plugin.  Takes the cache lock itself: called
        from both the scheduling loop and binding workers."""
        with self._mu:
            if status.code == Code.ERROR:
                self.metrics["errors"] += 1
                # Errors (API failures etc.) carry no rejector plugin —
                # the queue retries them after plain backoff instead of
                # waiting for a queueing hint (scheduling_queue.go:642).
                plugins = set()
            else:
                self.metrics["unschedulable"] += 1
                self.phases.count("sched.unschedulable", 1)
            if plugins is None:
                plugins = {status.plugin} if status.plugin else set()
            self.queue.add_unschedulable(qp, plugins)
        from kubernetes_tpu import events as ev

        pod = qp.pod
        self.recorders.get(pod.scheduler_name, ev.NullRecorder()).eventf(
            ev.ObjectRef.for_pod(pod),
            ev.TYPE_WARNING,
            "FailedScheduling",
            "Scheduling",
            "; ".join(status.reasons) or "scheduling failed",
        )
