"""In-process cluster: object store + watch fan-out + binding subresource."""

from __future__ import annotations

import copy
from dataclasses import replace
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.api import storage as st
from kubernetes_tpu.api.types import Node, NodeSelector, NodeSelectorRequirement, NodeSelectorTerm, Pod


class _ObjectStore:
    """One watched resource kind: name-keyed store with resource-version
    bumping and add/update/delete handler fan-out (the per-resource slice of
    a real apiserver's watch cache)."""

    def __init__(self, cluster: "FakeCluster") -> None:
        self._cluster = cluster
        self.objects: Dict[str, object] = {}
        self.handlers: List[tuple] = []  # (add, update, delete)

    def watch(self, on_add, on_update, on_delete) -> None:
        self.handlers.append((on_add, on_update, on_delete))
        for obj in list(self.objects.values()):
            on_add(copy.deepcopy(obj))

    def create(self, obj) -> None:
        obj = copy.deepcopy(obj)
        obj.resource_version = self._cluster._next_rv()
        self.objects[obj.key] = obj
        for add, _, _ in self.handlers:
            add(copy.deepcopy(obj))

    def update(self, obj) -> None:
        obj = copy.deepcopy(obj)
        old = self.objects.get(obj.key)
        obj.resource_version = self._cluster._next_rv()
        self.objects[obj.key] = obj
        for _, update, _ in self.handlers:
            update(copy.deepcopy(old), copy.deepcopy(obj))

    def delete(self, key: str) -> None:
        obj = self.objects.pop(key, None)
        if obj is None:
            return
        for _, _, delete in self.handlers:
            delete(copy.deepcopy(obj))

    def get(self, key: str):
        return self.objects.get(key)


class FakeCluster:
    """A miniature apiserver: CRUD on nodes/pods, watch handler fan-out, and
    the pods/binding subresource (registry/core/pod/storage/storage.go:169
    assignPod semantics — sets spec.nodeName via the store, then notifies
    watchers).  Storage objects (PV/PVC/StorageClass/CSINode/CSIDriver/
    CSIStorageCapacity) live in generic watched stores; ``pv_controller``
    emulates kube-controller-manager's PV binder + an external dynamic
    provisioner so VolumeBinding's PreBind write-and-wait completes in-proc
    (the integration-test role of the real PV controller)."""

    def __init__(self, pv_controller: bool = True) -> None:
        self.nodes: Dict[str, Node] = {}
        self.pods: Dict[str, Pod] = {}
        self.pdbs: Dict[str, object] = {}  # name → PodDisruptionBudget
        self._node_handlers: List[tuple] = []  # (add, update, delete)
        self._pod_handlers: List[tuple] = []  # (add, update, delete, update_many)
        # per-item (old, new) deliveries bind_many made to subscribers that
        # registered no batch handler
        self.bind_many_fallback_items = 0
        self.bindings: Dict[str, str] = {}  # pod uid → node name
        self.evictions: List[str] = []  # uids deleted via preemption
        self.events: List[object] = []  # recorded Events (events.k8s.io)
        self._rv = 0
        self.pvs = _ObjectStore(self)
        self.pvcs = _ObjectStore(self)
        self.storage_classes = _ObjectStore(self)
        self.csinodes = _ObjectStore(self)
        self.csidrivers = _ObjectStore(self)
        self.capacities = _ObjectStore(self)
        self.resource_claims = _ObjectStore(self)
        self.resource_slices = _ObjectStore(self)
        self.device_classes = _ObjectStore(self)
        self.pod_groups = _ObjectStore(self)  # coscheduling PodGroups
        self._pv_controller = pv_controller
        self.provisioned: List[str] = []  # PV names the fake provisioner made
        # coordination.k8s.io Lease objects (leader election, server.py)
        from kubernetes_tpu.server import LeaseStore

        self.lease_store = LeaseStore()

    def ground_truth(self):
        """(node_names, {pod_uid: node_name}) — the informer view the cache
        debugger compares against (backend/cache/debugger/comparer.go)."""
        return (
            list(self.nodes),
            {
                uid: p.node_name
                for uid, p in self.pods.items()
                if p.node_name
            },
        )

    def _next_rv(self) -> int:
        self._rv += 1
        return self._rv

    # ----- the in-proc PV controller + provisioner ---------------------------

    def _reconcile_volumes(self) -> None:
        """Bind PVs whose claimRef is set (the PV controller's syncVolume)
        and provision WaitForFirstConsumer claims annotated with a selected
        node (an external provisioner's watch loop)."""
        if not self._pv_controller:
            return
        changed = True
        while changed:
            changed = False
            for pv in list(self.pvs.objects.values()):
                if pv.claim_ref is None:
                    continue
                pvc = self.pvcs.get(f"{pv.claim_ref.namespace}/{pv.claim_ref.name}")
                if pvc is None:
                    continue
                if pvc.volume_name != pv.name or pvc.phase != st.PVC_BOUND:
                    pvc = pvc.clone()
                    pvc.volume_name = pv.name
                    pvc.phase = st.PVC_BOUND
                    self.pvcs.update(pvc)
                    changed = True
                if pv.phase != st.PV_BOUND:
                    pv = pv.clone()
                    pv.phase = st.PV_BOUND
                    self.pvs.update(pv)
                    changed = True
            for pvc in list(self.pvcs.objects.values()):
                node_name = pvc.annotations.get(st.ANN_SELECTED_NODE)
                if not node_name or pvc.volume_name:
                    continue
                sc = self.storage_classes.get(pvc.storage_class_name or "")
                if sc is None or sc.provisioner == st.NO_PROVISIONER:
                    continue
                pv_name = f"pv-provisioned-{pvc.namespace}-{pvc.name}"
                if self.pvs.get(pv_name) is not None:
                    continue
                affinity = NodeSelector(
                    (
                        NodeSelectorTerm(
                            match_fields=(
                                NodeSelectorRequirement(
                                    "metadata.name", "In", (node_name,)
                                ),
                            )
                        ),
                    )
                )
                pv = st.PersistentVolume(
                    name=pv_name,
                    capacity=pvc.request,
                    access_modes=pvc.access_modes,
                    storage_class_name=pvc.storage_class_name or "",
                    node_affinity=affinity,
                    claim_ref=st.ObjectRef(pvc.namespace, pvc.name),
                    csi_driver=sc.provisioner,
                    source_id=pv_name,
                )
                self.provisioned.append(pv_name)
                self.pvs.create(pv)
                changed = True

    # ----- watch registration ----------------------------------------------

    def watch_nodes(self, on_add, on_update, on_delete) -> None:
        self._node_handlers.append((on_add, on_update, on_delete))
        for node in self.nodes.values():
            on_add(node)

    def watch_pods(self, on_add, on_update, on_delete, on_update_many=None) -> None:
        """``on_update_many(pods)``, where given, takes a ``bind_many``
        slice's updates in ONE call: the stored pods, in item order,
        BORROWED for the duration of the call (no copy, no ``old``) — the
        subscriber reads or serialises them and keeps no reference."""
        self._pod_handlers.append((on_add, on_update, on_delete, on_update_many))
        for pod in self.pods.values():
            on_add(pod)

    # ----- nodes ------------------------------------------------------------

    def create_node(self, node: Node) -> None:
        self.nodes[node.name] = node
        for add, _, _ in self._node_handlers:
            add(node)

    def update_node(self, node: Node) -> None:
        old = self.nodes.get(node.name)
        self.nodes[node.name] = node
        for _, update, _ in self._node_handlers:
            update(old, node)

    def delete_node(self, name: str) -> None:
        node = self.nodes.pop(name, None)
        if node is None:
            return
        for _, _, delete in self._node_handlers:
            delete(node)

    # ----- pods -------------------------------------------------------------

    def create_pod(self, pod: Pod) -> None:
        # The store owns its copy and every event delivered to a per-item
        # handler carries a fresh copy — callers keep mutating theirs
        # (assume sets nodeName on the scheduler's object) without ever
        # aliasing the "API" state.  The one exception is declared by the
        # subscriber: a batch handler (watch_pods' on_update_many) borrows
        # the stored pods of a bind_many slice for the length of its call.
        pod = copy.deepcopy(pod)
        self.pods[pod.uid] = pod
        for add, *_ in self._pod_handlers:
            add(copy.deepcopy(pod))

    def update_pod(self, pod: Pod) -> None:
        pod = copy.deepcopy(pod)
        old = self.pods.get(pod.uid)
        self.pods[pod.uid] = pod
        for _, update, *_ in self._pod_handlers:
            update(copy.deepcopy(old), copy.deepcopy(pod))

    def delete_pod(self, uid: str) -> None:
        pod = self.pods.pop(uid, None)
        if pod is None:
            return
        # the binding ceases to exist with the pod — bindings is the
        # CURRENTLY-bound set (the HTTP tier and benches read it as such)
        self.bindings.pop(uid, None)
        for _, _, delete, _ in self._pod_handlers:
            delete(pod)

    # ----- binding subresource ----------------------------------------------

    # The in-proc store is where extender binds must ALSO be mirrored (a
    # real deployment's extender writes the binding itself and the watch
    # delivers it; see Scheduler binder_override).
    mirror_extender_binds = True

    def _bind_cas(self, stored: Pod, node_name: str) -> bool:
        """The binding CAS (assignPod, storage.go:254): True ⇒ set it,
        False ⇒ a same-node rebind, raises on a conflict or unknown node."""
        if stored.node_name and stored.node_name != node_name:
            raise RuntimeError(
                f"pod {stored.key} already bound to {stored.node_name}"
            )
        if stored.node_name == node_name and node_name:
            # same-node rebind: a transport-level POST retry replaying an
            # applied binding.  TRUE no-op — re-firing update handlers
            # here would fan a duplicate MODIFIED event to every watcher
            return False
        if node_name not in self.nodes:
            raise KeyError(f"binding to unknown node {node_name}")
        return True

    def bind(self, pod: Pod, node_name: str) -> None:
        """POST pods/{name}/binding: CAS-sets nodeName, rejects doubles."""
        stored = self.pods.get(pod.uid)
        if stored is None:
            raise KeyError(f"binding unknown pod {pod.key}")
        if not self._bind_cas(stored, node_name):
            return
        old = copy.deepcopy(stored)
        stored.node_name = node_name
        self.bindings[pod.uid] = node_name
        for _, update, *_ in self._pod_handlers:
            update(old, copy.deepcopy(stored))

    def bind_many(self, items) -> List[Optional[dict]]:
        """POST bindings: ``items`` is [(uid, node_name), ...], applied as
        ONE store transaction.  Each item runs ``bind``'s CAS, in item
        order, and a failure does not stop the slice; the result list is
        aligned with the input: None (bound, or a same-node rebind: no-op,
        no event), ``{"code": 404, "error"}`` (unknown pod or node) or
        ``{"code": 409, "error", "node"}`` carrying the EXISTING binding,
        so a client whose transport-level retry races its own applied
        first attempt can tell conflict-on-retry (node matches: success)
        from a real double-bind.

        The slice's updates are delivered once, after the last mutation:
        a subscriber with a batch handler gets one call with the stored
        pods (borrowed, see ``watch_pods``); one without gets ``bind``'s
        per-item ``update(old_copy, new_copy)``, and the copies are made
        only where such a subscriber exists."""
        per_item = any(h[3] is None for h in self._pod_handlers)
        results: List[Optional[dict]] = []
        bound: List[Pod] = []
        olds: List[Pod] = []
        for uid, node_name in items:
            stored = self.pods.get(uid)
            if stored is None:
                results.append({"code": 404, "error": f"pod {uid} not found"})
                continue
            try:
                apply = self._bind_cas(stored, node_name)
            except RuntimeError as e:
                results.append(
                    {"code": 409, "error": str(e), "node": stored.node_name}
                )
                continue
            except KeyError as e:
                results.append({"code": 404, "error": str(e)})
                continue
            results.append(None)
            if not apply:
                continue
            if per_item:
                olds.append(copy.deepcopy(stored))
            stored.node_name = node_name
            self.bindings[uid] = node_name
            bound.append(stored)
        if bound:
            for _, update, _, update_many in self._pod_handlers:
                if update_many is not None:
                    update_many(bound)
                    continue
                self.bind_many_fallback_items += len(bound)
                for old, stored in zip(olds, bound):
                    update(old, copy.deepcopy(stored))
        return results

    # ----- pod status subresource -------------------------------------------

    def patch_pod_status(self, pod: Pod) -> None:
        """PATCH pods/{name}/status: the scheduler's nomination/condition
        writes (util.PatchPodStatus)."""
        stored = self.pods.get(pod.uid)
        if stored is None:
            return
        old = copy.deepcopy(stored)
        stored.nominated_node_name = pod.nominated_node_name
        stored.phase = pod.phase
        for _, update, *_ in self._pod_handlers:
            update(old, copy.deepcopy(stored))

    # ----- PDBs -------------------------------------------------------------

    def create_pdb(self, pdb) -> None:
        self.pdbs[pdb.name] = pdb

    # ----- storage objects ----------------------------------------------------

    def create_pv(self, pv: st.PersistentVolume) -> None:
        self.pvs.create(pv)
        self._reconcile_volumes()

    def update_pv(self, pv: st.PersistentVolume) -> None:
        self.pvs.update(pv)
        self._reconcile_volumes()

    def create_pvc(self, pvc: st.PersistentVolumeClaim) -> None:
        self.pvcs.create(pvc)
        self._reconcile_volumes()

    def update_pvc(self, pvc: st.PersistentVolumeClaim) -> None:
        self.pvcs.update(pvc)
        self._reconcile_volumes()

    def create_storage_class(self, sc: st.StorageClass) -> None:
        self.storage_classes.create(sc)

    def create_csinode(self, cn: st.CSINode) -> None:
        self.csinodes.create(cn)

    def create_csidriver(self, d: st.CSIDriver) -> None:
        self.csidrivers.create(d)

    def create_capacity(self, c: st.CSIStorageCapacity) -> None:
        self.capacities.create(c)

    # ----- events API (events.k8s.io store) ---------------------------------

    def record_event(self, event, is_new: bool = True) -> None:
        """Event sink (the API's events registry shape): a NEW series
        appends; an update REPLACES the stored snapshot for its key, so
        counts reflect the latest aggregation without double-posting."""
        idx = self.__dict__.setdefault("_event_idx", {})
        key = getattr(event, "key", None)
        if key is None:
            self.events.append(event)
            return
        pos = idx.get(key)
        if pos is None or is_new:
            idx[key] = len(self.events)
            self.events.append(event)
        else:
            self.events[pos] = event

    def list_events(self, reason: Optional[str] = None) -> List[object]:
        return [e for e in self.events if reason is None or e.reason == reason]

    # ----- wiring -----------------------------------------------------------

    def connect(self, scheduler) -> None:
        """Attach a Scheduler's event handlers (addAllEventHandlers)."""
        # events API sink: the scheduler's broadcaster (when wired) lands
        # Events here like the real events.k8s.io API would store them
        if getattr(scheduler, "event_broadcaster", None) is not None:
            scheduler.event_broadcaster.start_recording_to_sink(
                self.record_event
            )
        self.watch_nodes(
            scheduler.on_node_add, scheduler.on_node_update, scheduler.on_node_delete
        )
        self.watch_pods(
            scheduler.on_pod_add, scheduler.on_pod_update, scheduler.on_pod_delete
        )
        scheduler.binding_sink = self.bind

        def evict(pod):
            self.evictions.append(pod.uid)
            self.delete_pod(pod.uid)

        scheduler.pod_deleter = evict
        scheduler.pdb_lister = lambda: list(self.pdbs.values())
        scheduler.status_patcher = self.patch_pod_status

        # storage informers → scheduler assume caches + requeue events
        # (the per-GVK dynamic handlers of eventhandlers.go:431)
        from kubernetes_tpu.framework.interface import EventResource

        for store, res in (
            (self.pvs, EventResource.PV),
            (self.pvcs, EventResource.PVC),
            (self.storage_classes, EventResource.STORAGE_CLASS),
            (self.csinodes, EventResource.CSI_NODE),
            (self.csidrivers, EventResource.CSI_DRIVER),
            (self.capacities, EventResource.CSI_STORAGE_CAPACITY),
            (self.resource_claims, EventResource.RESOURCE_CLAIM),
            (self.resource_slices, EventResource.RESOURCE_SLICE),
            (self.device_classes, EventResource.DEVICE_CLASS),
            (self.pod_groups, EventResource.POD_GROUP),
        ):
            store.watch(*scheduler.storage_handlers(res))
        scheduler.pvc_writer = self.update_pvc
        scheduler.pv_writer = self.update_pv
        scheduler.claim_writer = self.resource_claims.update
