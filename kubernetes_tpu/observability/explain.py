"""Explain mode: per-node, per-plugin rejection reasons for a pod.

Harvests the per-plugin feasibility masks the batched filter kernel
already computes (``ops.explain.explain_masks`` — the Diagnosis /
NodeToStatusMap surface the hot loop throws away on device) and renders
them as per-node plugin verdicts, merged with host-backed Filter plugin
results (the volumebinding class, which never had kernels) and the
PreFilter result narrowing.

Gating / cost model: nothing here runs on the scheduling hot path.  The
device dispatch and its d2h happen only when an operator (or test) asks
about a specific pod — ``/debug/explain?pod=`` — so the "extra" transfer
is strictly per diagnosed pod.  Unschedulable OUTCOMES get their
aggregate per-plugin counts for free (the reason_counts the kernels
already fetch), recorded in the flight recorder; this module is the
full-resolution drill-down.

``oracle_explain`` produces the same node → rejecting-plugins map from
the serial host oracle (``oracle.pipeline.feasible_nodes``) — the
validation surface: tests assert the kernel masks and the oracle agree
plugin-for-plugin on mixed feasible/infeasible batches.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.oracle import filters as OF
from kubernetes_tpu.oracle.pipeline import feasible_nodes

# gang.DIAG_KERNELS row order — kernel index → plugin name
DIAG_PLUGINS = (
    "NodeUnschedulable",
    "NodeName",
    "TaintToleration",
    "NodeAffinity",
    "NodePorts",
    "HostFilters",
    "NodeResourcesFit",
    "PodTopologySpread",
    "InterPodAffinity",
)

# oracle reason string → plugin name (exact matches; prefixes below)
_REASON_PLUGIN_EXACT = {
    OF.REASON_NODE_NAME: "NodeName",
    OF.REASON_UNSCHEDULABLE: "NodeUnschedulable",
    OF.REASON_AFFINITY: "NodeAffinity",
    OF.REASON_PORTS: "NodePorts",
    OF.REASON_PODS_LIMIT: "NodeResourcesFit",
    OF.REASON_EXISTING_ANTI: "InterPodAffinity",
    OF.REASON_POD_AFFINITY: "InterPodAffinity",
    OF.REASON_POD_ANTI: "InterPodAffinity",
    OF.REASON_SPREAD: "PodTopologySpread",
    OF.REASON_SPREAD_LABEL: "PodTopologySpread",
}
_REASON_PLUGIN_PREFIX = (
    (OF.REASON_TAINT, "TaintToleration"),
    ("Insufficient ", "NodeResourcesFit"),
)


def reason_to_plugin(reason: str) -> str:
    """Map an oracle Filter reason string to its plugin (kernel) name."""
    hit = _REASON_PLUGIN_EXACT.get(reason)
    if hit is not None:
        return hit
    for prefix, plugin in _REASON_PLUGIN_PREFIX:
        if reason.startswith(prefix):
            return plugin
    return reason  # host-plugin reasons pass through verbatim


def oracle_explain(
    pod: Pod, state, enabled: frozenset
) -> Dict[str, Set[str]]:
    """node name → rejecting-plugin set, from the serial host oracle."""
    fit = feasible_nodes(pod, state, enabled=enabled)
    return {
        node: {reason_to_plugin(r) for r in reasons}
        for node, reasons in fit.reasons.items()
    }


def find_pod(sched, ref: str) -> Optional[Pod]:
    """Resolve a pod by uid, key (ns/name#uid prefix), or bare name across
    the scheduling queue's sub-queues and the cache."""
    with sched._mu:
        pools = sched.queue.pending_pods()
        for pods in pools.values():
            for p in pods:
                if ref in (p.uid, p.name, p.key):
                    return p
        ps = sched.cache.pod_states.get(ref)
        if ps is not None:
            return ps.pod
        for ps in sched.cache.pod_states.values():
            if ref in (ps.pod.name, ps.pod.key):
                return ps.pod
    return None


def explain_pod(
    sched, pod: Pod, max_nodes: int = 500
) -> dict:
    """Per-node, per-plugin verdicts for ``pod`` against the scheduler's
    CURRENT snapshot.  Runs one explain-kernel dispatch + one gated d2h.

    Locking: host-side prep (mirror sync, packing, host-filter sweep)
    holds the scheduler lock for a consistent snapshot; the device
    dispatch and its d2h — including any first-shape XLA compile, which
    can take seconds — run OUTSIDE the lock against the already-built
    immutable arrays, so a debug query never stalls the scheduling loop
    behind a compile.  The hot loop's chained/delta-cached device state is
    never touched (a fresh upload); the shared vocab/mirror ARE touched —
    packing the pod interns its labels exactly as scheduling it would, so
    a never-before-packed label key can widen the key bucket for the next
    drain's repack (the same cost scheduling that pod would pay).

    ``max_nodes`` caps the per-node detail in the result; the summary
    counts always cover every node."""
    import numpy as np

    from kubernetes_tpu.framework.interface import CycleState
    from kubernetes_tpu.ops import explain as ops_explain
    from kubernetes_tpu.ops import gang
    from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster
    from kubernetes_tpu.snapshot.interner import PAD
    from kubernetes_tpu.snapshot.schema import bucket_cap, pack_pod_batch

    # DIAG_PLUGINS is declared without importing ops (keeps this module
    # importable AST-light); it must mirror the kernel row order exactly
    assert DIAG_PLUGINS == gang.DIAG_KERNELS, (
        "observability.DIAG_PLUGINS diverged from gang.DIAG_KERNELS"
    )
    fwk = sched.profiles.get(
        pod.scheduler_name, next(iter(sched.profiles.values()))
    )
    out: dict = {
        "pod": {"uid": pod.uid, "name": pod.name, "namespace": pod.namespace},
        "profile": fwk.profile_name,
    }
    with sched._mu:
        vocab = sched.mirror.vocab
        for k, v in pod.labels.items():
            vocab.intern_label(k, v)
        sched._repack_mirror()
        nt = sched.mirror.nodes
        if nt is None or not any(nt.valid):
            out["error"] = "no nodes in snapshot"
            return out

        state = CycleState()
        pf_failures = fwk.run_pre_filter(state, [pod])
        s = pf_failures.get(pod.uid)
        if s is not None:
            out["pre_filter"] = {
                "plugin": s.plugin,
                "reasons": list(s.reasons),
            }
            out["nodes"] = {}
            out["summary"] = {s.plugin or "PreFilter": int(np.sum(nt.valid))}
            out["feasible"] = []
            out["n_feasible"] = 0
            return out
        allowed = state.read(("pre_filter_result", pod.uid))

        enabled = fwk.device_enabled()
        pb = pack_pod_batch(
            [pod],
            vocab,
            k_cap=nt.k_cap,
            p_cap=bucket_cap(1, 1),
            namespace_labels=sched.namespace_labels,
        )
        from kubernetes_tpu.oracle.scores import HOSTNAME_LABEL

        tables = dict(
            gang.batch_tables(
                pb.tsc_topo_key,
                pb.aff_topo_key,
                nt.label_vals,
                vocab.label_keys.lookup(HOSTNAME_LABEL),
            )
        )
        has_interpod = bool(
            (pb.aff_kind != PAD).any()
            or (sched.mirror.existing.term_kind != PAD).any()
        )
        has_spread = bool((pb.tsc_topo_key != PAD).any())
        has_ports = bool(
            (pb.want_ppk != PAD).any() or (nt.used_ppk != PAD).any()
        )
        # a fresh device view, independent of the hot loop's chained /
        # delta-cached cluster state (explain never perturbs device caches)
        dc = DeviceCluster.from_host(nt, sched.mirror.existing, vocab)
        db = DeviceBatch.from_host(pb)
        hostname_dev = sched._hostname_dev(vocab)
        v_cap = bucket_cap(len(vocab.label_vals))

        # host-backed Filter plugins (no kernels — judged host-side here,
        # replacing the kernel stack's all-true HostFilters row; needs the
        # shared oracle view, so it stays under the lock)
        host_active = [
            p
            for p in fwk.host_filter_plugins()
            if not state.is_filter_skipped(pod.uid, p.name)
            and p.maybe_relevant(pod)
        ]
        host_verdicts: Dict[str, List[str]] = {}
        if host_active:
            st = sched.oracle_view()
            for name, ns in st.nodes.items():
                hs = fwk.run_host_filters(state, pod, ns)
                if not hs.ok:
                    host_verdicts[name] = [hs.plugin or "HostFilters"]

        names = list(nt.names)
        valid = np.asarray(nt.valid).copy()

    # device dispatch + the gated d2h OUTSIDE the lock: the arrays built
    # above are immutable, and a first-shape XLA compile here must not
    # stall the scheduling loop or informer handlers
    stack, feasible = ops_explain.explain_masks(
        dc,
        db,
        hostname_dev,
        v_cap,
        has_interpod=has_interpod,
        has_spread=has_spread,
        has_ports=has_ports,
        enabled=enabled,
        check_fit="NodeResourcesFit" in enabled,
        **tables,
    )
    # one accounted fetch for both artifacts: explain IS a host round
    # trip, and it must show up in host_roundtrips_total/d2h_bytes_total
    # like every other blocking fetch (Scheduler._d2h choke point)
    fetched = sched._d2h((stack, feasible), kernel="explain.explain_masks")
    stack = np.asarray(fetched[0])[:, 0, :]  # [N_DIAG, N]
    feasible = np.asarray(fetched[1])[0]  # [N]

    allowed_set = frozenset(allowed) if allowed is not None else None
    nodes: Dict[str, List[str]] = {}
    summary: Dict[str, int] = {}
    feasible_names: List[str] = []
    n_rejected = 0
    hf_row = DIAG_PLUGINS.index("HostFilters")
    for ni, name in enumerate(names):
        if ni >= valid.shape[0] or not valid[ni]:
            continue
        rejecting: List[str] = []
        if allowed_set is not None and name not in allowed_set:
            rejecting.append("PreFilterResult")
        for k, plugin in enumerate(DIAG_PLUGINS):
            if k == hf_row:
                continue  # replaced by host_verdicts below
            if not stack[k, ni]:
                rejecting.append(plugin)
        rejecting.extend(host_verdicts.get(name, ()))
        if rejecting:
            n_rejected += 1
            if len(nodes) < max_nodes:
                nodes[name] = rejecting
            for plugin in rejecting:
                summary[plugin] = summary.get(plugin, 0) + 1
        elif feasible[ni]:
            feasible_names.append(name)
    out["nodes"] = nodes
    out["truncated"] = n_rejected > len(nodes)
    out["summary"] = summary
    out["n_feasible"] = len(feasible_names)
    out["feasible"] = feasible_names[:max_nodes]

    # wave-dispatch history: a pod whose speculative placement was
    # invalidated by the wave's conflict-resolution pass carries
    # ``wave_demoted`` flight-recorder events — surface them so the
    # drill-down answers "why did this pod not land where the wave first
    # put it" alongside the per-node verdicts
    demotions = [
        {
            "kind": e.get("detail", {}).get("kind"),
            "term": e.get("detail", {}).get("term"),
            "spec_node": e.get("detail", {}).get("spec_node"),
            "node": e.get("detail", {}).get("node"),
        }
        for e in sched.flight.events_for(pod.uid)
        if e.get("kind") == "wave_demoted"
    ]
    if demotions:
        last = demotions[-1]
        out["wave"] = {
            "demoted": True,
            "reason": "demoted by wave conflict",
            "conflict_kind": last["kind"],
            "conflict_term": last["term"],
            "events": demotions[-8:],
        }
    return out


def explain_whatif(sched, pod: Pod, node_name: str) -> dict:
    """Preemption what-if: which victims would free ``node_name`` for
    ``pod`` — the existing preemption dry-run machinery
    (framework/preemption.Evaluator.select_victims_on_node, the same code
    PostFilter runs) restricted to one node, served read-only: the dry run
    works on a working copy and restores the shared view before returning.

    Returns eligibility, the victim list (what PostFilter would evict
    there, importance-ordered), and the PDB-violation count — "what would
    it take" without nominating anything or touching the queue."""
    from kubernetes_tpu.framework.interface import CycleState

    fwk = sched.profiles.get(
        pod.scheduler_name, next(iter(sched.profiles.values()))
    )
    out: dict = {
        "pod": {"uid": pod.uid, "name": pod.name, "namespace": pod.namespace},
        "node": node_name,
    }
    ev = next(
        (
            p.evaluator
            for p in fwk.post_filter_plugins()
            if hasattr(p, "evaluator")
        ),
        None,
    )
    if ev is None:
        out["error"] = "profile has no preemption evaluator"
        return out
    with sched._mu:
        state = sched.oracle_view()
        if node_name not in state.nodes:
            out["error"] = f"unknown node {node_name!r}"
            return out
        ok, msg = ev.pod_eligible(pod, state)
        out["eligible"] = ok
        if not ok:
            out["reason"] = msg
            return out
        cs = CycleState()
        failures = fwk.run_pre_filter(cs, [pod]) or {}
        s = failures.get(pod.uid)
        if s is not None:
            out["eligible"] = False
            out["reason"] = "; ".join(s.reasons) or "PreFilter rejected"
            return out
        # the same host-filter / extension context preempt() arms, saved
        # and restored so a live PostFilter's state never leaks
        prev = (ev._hf_fwk, ev._hf_state, ev._ext_fwk, ev._ext_state)
        prev_fast = getattr(ev, "_fast_fit", False)
        ev._hf_fwk = ev._hf_state = ev._ext_fwk = ev._ext_state = None
        ev._fast_fit = False  # one node: always run the full fit check
        if fwk.has_host_filters() and fwk.active_host_filters(cs, [pod]):
            ev._hf_fwk, ev._hf_state = fwk, cs
        if fwk.has_pre_filter_extensions():
            ev._ext_fwk, ev._ext_state = fwk, cs
        try:
            victims = ev.select_victims_on_node(
                pod, state, node_name, sched.pdb_lister()
            )
        finally:
            ev._hf_fwk, ev._hf_state, ev._ext_fwk, ev._ext_state = prev
            ev._fast_fit = prev_fast
        lower_uids = [
            p.uid
            for p in state.nodes[node_name].pods
            if p.priority < pod.priority
        ]
        out["lower_priority_pods"] = len(lower_uids)
        if victims is None:
            out["feasible_after_preemption"] = False
            out["reason"] = (
                "no lower-priority pods on the node"
                if not lower_uids
                else "pod still does not fit after removing every "
                "lower-priority pod"
            )
            evict_uids = lower_uids
        else:
            out["feasible_after_preemption"] = True
            out["num_pdb_violations"] = victims.num_pdb_violations
            out["victims"] = [
                {
                    "uid": v.uid,
                    "name": v.name,
                    "namespace": v.namespace,
                    "priority": v.priority,
                }
                for v in victims.pods
            ]
            evict_uids = [v.uid for v in victims.pods]

    # K=1 planner-kernel reroute (outside the lock — device dispatch +
    # compile must not stall the scheduling loop): the single
    # counterfactual and the batched /debug/plan tier share ONE
    # implementation (ops/counterfactual.py), so they cannot drift; the
    # host dry run above stays as the parity reference.
    from kubernetes_tpu.planner.plan import whatif_after_evictions

    try:
        k = whatif_after_evictions(sched, pod, node_name, evict_uids)
    except Exception as e:  # noqa: BLE001 — debug surface must not 500
        k = {"error": str(e)}
    out["kernel"] = k
    if "feasible" in k:
        host_verdict = out["feasible_after_preemption"]
        out["feasible_after_preemption"] = k["feasible"]
        out["host_feasible_after_preemption"] = host_verdict
        out["parity"] = k["feasible"] == host_verdict
    return out
