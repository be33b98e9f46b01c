"""Device-resident cluster snapshot with delta uploads.

The host SnapshotMirror (mirror.py) is the source of truth; this cache keeps
its DeviceCluster image alive across batches and ships only what changed:

  * node USAGE rows (requested/nonzero/num_pods/ports) — small, re-uploaded
    every sync (they change with every commit);
  * placed-pod and term rows — append-only between rebuilds (the mirror's
    `_epod_slots` cursor discipline), so only the newly appended row range
    is uploaded and spliced in with dynamic_update_slice on device;
  * static node tensors / vocab tables — re-uploaded only when the mirror
    key (static generation, full packs, existing rebuilds, vocab sizes)
    changes.

This is the host→HBM half of SURVEY.md §2.4's "informer delta stream →
append-only update buffer DMA'd into HBM" design, replacing the previous
full `DeviceCluster.from_host` per batch (the whole snapshot re-uploaded
at 5k-node scale; the delta is ~100 KB).
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.ops.common import DeviceCluster, DTable, I32
from kubernetes_tpu.snapshot.schema import bucket_cap


def _dus(full, delta, start):
    """dynamic_update_slice of leading-axis rows."""
    start = jnp.asarray(start, I32)
    zero = jnp.zeros((), I32)
    starts = (start,) + (zero,) * (full.ndim - 1)
    # ktpu: allow(slice-clamp) — e0/m0 are clamped HOST-side before upload
    # (_row_range: start = min(lo, cap - size)), so start + size <= cap by
    # construction and the device splice can never reach the array end
    return jax.lax.dynamic_update_slice(full, delta, starts)


@functools.lru_cache(maxsize=64)
def _delta_applier(spec, treedef, with_rows: bool):
    """One jitted splice per delta signature: unpacks the single wire
    buffer (usage rows + appended pod/term rows + cursors) and merges it
    into the donated DeviceCluster — one transfer, one dispatch.

    Mesh note: under meshDispatch the incoming ``dc`` is mesh-committed
    and ``buf`` is replicated on the same mesh; GSPMD propagates the
    input shardings through the splice, so the output stays partitioned
    (sync() re-asserts the placement — a no-op when propagation held)."""
    from kubernetes_tpu.ops import wire

    # ktpu: axes(dc=DeviceCluster, buf=u8[B])
    # ktpu: noinstantiate — the delta layout lives in the lru_cache key
    #   (spec, treedef, with_rows); the splice is exercised end-to-end by
    #   test_device_mirror instead
    @functools.partial(jax.jit, donate_argnums=(0,))
    def apply(dc: DeviceCluster, buf) -> DeviceCluster:
        tree = jax.tree_util.tree_unflatten(treedef, wire.unpack(buf, spec))
        out = dict(tree["usage"])
        if with_rows:
            e0, m0 = tree["e0"], tree["m0"]
            for name, delta in tree["ep"].items():
                out[name] = _dus(getattr(dc, name), delta, e0)
            tm = dict(tree["tm"])
            tt = dc.term_table
            out["term_table"] = DTable(
                req_key=_dus(tt.req_key, tm.pop("tt_req_key"), m0),
                req_op=_dus(tt.req_op, tm.pop("tt_req_op"), m0),
                req_vals=_dus(tt.req_vals, tm.pop("tt_req_vals"), m0),
                req_rhs=_dus(tt.req_rhs, tm.pop("tt_req_rhs"), m0),
                term_valid=_dus(tt.term_valid, tm.pop("tt_term_valid"), m0),
            )
            for name, delta in tm.items():
                out[name] = _dus(getattr(dc, name), delta, m0)
        return replace(dc, **out)

    return apply


_EPOD_FIELDS = {
    "epod_node": ("node_idx", np.int32),
    "epod_ns": ("ns_id", np.int32),
    "epod_labels": ("label_vals", np.int32),
    "epod_valid": ("valid", bool),
    "epod_deleting": ("deleting", bool),
}

_TERM_FIELDS = {
    "term_pod": ("term_pod", np.int32),
    "term_kind": ("term_kind", np.int32),
    "term_topo": ("term_topo_key", np.int32),
    "term_weight": ("term_weight", np.int32),
    "term_ns_all": ("term_ns_all", bool),
    "term_ns_ids": ("term_ns_ids", np.int32),
}


class DeviceClusterCache:
    """Keeps one DeviceCluster in HBM, synced incrementally from the host
    mirror.  `sync()` returns the up-to-date device snapshot.

    With a ``mesh``, the snapshot is PLACED on it (parallel/mesh.py
    cluster_shardings: node-major tensors partitioned over the 'nodes'
    axis, everything else replicated) so every consumer kernel runs
    SPMD-partitioned; delta uploads ride a replicated wire buffer."""

    def __init__(self, mesh=None) -> None:
        self._dc = None
        self._key = None
        self._e_done = 0
        self._m_done = 0
        self._mesh = mesh

    def invalidate(self) -> None:
        self._dc = None

    def _row_range(self, lo: int, hi: int, cap: int):
        """Bucketed [start, start+size) covering [lo, hi) — size is a stable
        bucket so delta uploads hit a handful of jit shapes; rows below lo
        re-uploaded by the clamp carry identical content."""
        size = min(bucket_cap(hi - lo, 1), cap)
        start = min(lo, cap - size)
        return start, size

    def sync(self, mirror, vocab) -> DeviceCluster:
        # chaos seam (ISSUE 15 hbm_oom): an installed device-fault
        # injector can fail this donation/placement the way a real
        # RESOURCE_EXHAUSTED would; Scheduler._sync_device_cluster owns
        # the recovery (invalidate → rebuild-from-mirror, bounded retry)
        from kubernetes_tpu.observability.kernels import fault_injector

        inj = fault_injector()
        if inj is not None and inj.sync_fault() is not None:
            self.invalidate()
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: out of memory placing resident "
                "cluster snapshot (chaos hbm_oom)"
            )
        nt = mirror.nodes
        ep = mirror.existing  # materializes/append-updates the host tensors
        key = (
            mirror.static_generation,
            mirror._full_packs,
            mirror._existing_rebuilds,
            len(vocab.label_vals),
            len(vocab.label_keys),
        )
        if self._dc is None or key != self._key:
            dc = DeviceCluster.from_host(nt, ep, vocab)
            if self._mesh is not None:
                from kubernetes_tpu.parallel.mesh import place_cluster

                dc = place_cluster(self._mesh, dc)
            self._dc = dc
            self._key = key
            self._e_done = mirror.e_used
            self._m_done = mirror.m_used
            return self._dc

        from kubernetes_tpu.ops import wire

        tree = {
            "usage": dict(
                requested=np.asarray(nt.requested, np.int32),
                nonzero_req=np.asarray(nt.nonzero_req, np.int32),
                num_pods=np.asarray(nt.num_pods, np.int32),
                used_ppk=np.asarray(nt.used_ppk, np.int32),
                used_ip=np.asarray(nt.used_ip, np.int32),
                used_wild=np.asarray(nt.used_wild, bool),
            )
        }
        e1, m1 = mirror.e_used, mirror.m_used
        with_rows = not (e1 == self._e_done and m1 == self._m_done)
        if with_rows:
            e_cap = ep.node_idx.shape[0]
            m_cap = ep.term_pod.shape[0]
            e0, de = self._row_range(self._e_done, e1, e_cap)
            m0, dm = self._row_range(self._m_done, m1, m_cap)
            tree["ep"] = {
                dc_name: np.asarray(getattr(ep, host)[e0 : e0 + de], dt)
                for dc_name, (host, dt) in _EPOD_FIELDS.items()
            }
            tm_delta = {
                dc_name: np.asarray(getattr(ep, host)[m0 : m0 + dm], dt)
                for dc_name, (host, dt) in _TERM_FIELDS.items()
            }
            tt = ep.term_table
            tm_delta.update(
                tt_req_key=np.asarray(tt.req_key[m0 : m0 + dm], np.int32),
                tt_req_op=np.asarray(tt.req_op[m0 : m0 + dm], np.int32),
                tt_req_vals=np.asarray(tt.req_vals[m0 : m0 + dm], np.int32),
                tt_req_rhs=np.asarray(tt.req_rhs[m0 : m0 + dm], np.int32),
                tt_term_valid=np.asarray(tt.term_valid[m0 : m0 + dm], bool),
            )
            tree["tm"] = tm_delta
            tree["e0"] = np.asarray(e0, np.int32)
            tree["m0"] = np.asarray(m0, np.int32)
        buf, spec, treedef = wire.pack_tree(tree)
        if self._mesh is not None:
            from kubernetes_tpu.parallel.mesh import place_cluster, replicated

            # the wire buffer must commit to the SAME mesh as the resident
            # snapshot (mixed device sets are a jit error); re-asserting
            # the cluster placement after the splice is a no-op when GSPMD
            # propagation kept it, and repairs it when it didn't
            buf_dev = jax.device_put(buf, replicated(self._mesh))
            applied = _delta_applier(spec, treedef, with_rows)(
                self._dc, buf_dev
            )
            self._dc = place_cluster(self._mesh, applied)
        else:
            self._dc = _delta_applier(spec, treedef, with_rows)(
                self._dc, jax.device_put(buf)
            )
        self._e_done, self._m_done = e1, m1
        return self._dc
