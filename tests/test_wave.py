"""Speculative wave dispatch must be decision-identical to the serial path.

The wave (ops/wave.py) replaces the gang scan's per-step peer contractions
with a speculation pass + a term-factored admission pass.  Its contract is
bit-identity with the gang scan — and therefore with the serial oracle the
scan is property-tested against.  The adversarial shapes from the issue:

  * ALL pods sharing ONE topology term — maximal interaction, the wave
    degenerates to the serial recurrence and must match the oracle
    placement for placement;
  * fully DISJOINT term footprints — zero interaction, one wave admits
    every pod at its speculative placement.

Both scheduler-level adversarial tests run under KTPU_SANITIZE=1.
"""

import os
import random

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.oracle.pipeline import schedule_one
from kubernetes_tpu.oracle.scores import HOSTNAME_LABEL
from kubernetes_tpu.oracle.state import OracleState
from kubernetes_tpu.ops import gang, wave
from kubernetes_tpu.ops.common import DeviceBatch, DeviceCluster, I32
from kubernetes_tpu.snapshot.cluster import pack_cluster
from kubernetes_tpu.snapshot.interner import Vocab
from kubernetes_tpu.snapshot.schema import bucket_cap, pack_pod_batch

from tests.gen import make_cluster, make_pod

NS_LABELS = {
    "default": {"team": "core"},
    "prod": {"team": "core", "env": "prod"},
    "dev": {"env": "dev"},
}


def _pack(state, pending):
    vocab = Vocab()
    pc = pack_cluster(state, vocab, pending_pods=pending)
    pb = pack_pod_batch(
        pending,
        vocab,
        k_cap=pc.nodes.k_cap,
        namespace_labels=state.namespace_labels,
    )
    dc = DeviceCluster.from_host(pc.nodes, pc.existing, vocab)
    db = DeviceBatch.from_host(pb)
    v_cap = bucket_cap(len(vocab.label_vals))
    hk_id = vocab.label_keys.lookup(HOSTNAME_LABEL)
    hostname_key = jnp.asarray(hk_id, I32)
    tables = gang.batch_tables(
        pb.tsc_topo_key, pb.aff_topo_key, pc.nodes.label_vals, hk_id
    )
    return vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables


def run_wave(state, pending, with_stats=False, sample_kw=None):
    """wave_schedule end to end — the wave analogue of run_gang."""
    vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _pack(
        state, pending
    )
    wt = wave.wave_tables(pb, pc.nodes.label_vals, hk_id)
    assert wt is not None, "generated batch unexpectedly wave-ineligible"
    d_cap = tables.pop("d_cap")
    d2_cap = wt.pop("d2_cap")
    wt.pop("n_terms")
    g = gang.precompute(dc, db, hostname_key, v_cap, **tables)
    chosen, n_feas, _, _, stats = wave.wave_schedule(
        dc,
        db,
        g,
        hostname_key,
        v_cap,
        wt["tid_sp"],
        wt["rep_sp_p"],
        wt["rep_sp_c"],
        wt["tid_ip"],
        wt["rep_ip_p"],
        wt["rep_ip_u"],
        wt["ip_cdv_tab"],
        d_cap=d_cap,
        d2_cap=d2_cap,
        has_ports=wt["has_ports"],
        tid_pt=wt["tid_pt"],
        port_conf=wt["port_conf"],
        **(sample_kw or {}),
    )
    names = list(state.nodes)
    out = [
        names[int(c)] if int(c) >= 0 else None
        for c in np.asarray(chosen)[: len(pending)]
    ]
    if with_stats:
        return out, np.asarray(stats)[:, : len(pending)]
    return out


def run_gang(state, pending):
    vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _pack(
        state, pending
    )
    d_cap = tables.pop("d_cap")
    g = gang.precompute(dc, db, hostname_key, v_cap, **tables)
    chosen, _, _, _ = gang.gang_schedule(dc, db, g, v_cap, d_cap=d_cap)
    names = list(state.nodes)
    return [
        names[int(c)] if int(c) >= 0 else None
        for c in np.asarray(chosen)[: len(pending)]
    ]


def run_serial(state, pending):
    out = []
    for pod in pending:
        r = schedule_one(pod, state)
        out.append(r.node)
        if r.node is not None:
            pod.node_name = r.node
            state.place(pod)
    return out


@pytest.mark.parametrize(
    "seed,n_nodes,n_placed,n_pending",
    [(41, 10, 20, 20), (42, 10, 20, 20), (43, 12, 24, 24),
     (111, 40, 80, 120), (222, 40, 80, 120), (333, 40, 80, 120)],
)
def test_wave_matches_gang_and_serial(seed, n_nodes, n_placed, n_pending):
    # in-batch host-port users ride the factored [Tpt, N] occupancy carry
    # now — the generator's port pods stay IN the batch
    rng = random.Random(seed)
    nodes, placed = make_cluster(rng, n_nodes, n_placed)
    pending = [make_pod(rng, f"pend-{i}") for i in range(n_pending)]

    state_w = OracleState.build(nodes, placed, namespace_labels=NS_LABELS)
    got = run_wave(state_w, pending)

    state_g = OracleState.build(nodes, placed, namespace_labels=NS_LABELS)
    want_gang = run_gang(state_g, pending)
    assert got == want_gang, (
        f"wave diverged from gang at "
        f"{[i for i, (a, b) in enumerate(zip(got, want_gang)) if a != b]}:\n"
        f"got  {got}\nwant {want_gang}"
    )

    state_s = OracleState.build(nodes, placed, namespace_labels=NS_LABELS)
    want = run_serial(state_s, pending)
    assert got == want


# ---------------------------------------------------------------------------
# Adversarial shapes (issue spec), full scheduler, KTPU_SANITIZE=1
# ---------------------------------------------------------------------------


@pytest.fixture()
def sanitize_on(monkeypatch):
    from kubernetes_tpu.analysis import sanitizer

    monkeypatch.setenv("KTPU_SANITIZE", "1")
    sanitizer.reset_enabled_memo()
    yield
    monkeypatch.delenv("KTPU_SANITIZE", raising=False)
    sanitizer.reset_enabled_memo()


def _zone_nodes(n, zones=4, extra=None):
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node

    return [
        Node(
            name=f"node-{i}",
            labels={
                "topology.kubernetes.io/zone": f"zone-{i % zones}",
                "kubernetes.io/hostname": f"node-{i}",
                **(extra(i) if extra else {}),
            },
            capacity=Resource.from_map(
                {"cpu": "8", "memory": "32Gi", "pods": 110}
            ),
        )
        for i in range(n)
    ]


def _drain_sched(nodes, pods, wave: bool):
    import copy

    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler

    conf = SchedulerConfiguration()
    conf.wave_dispatch = wave
    conf.batch_size = 64
    s = Scheduler(configuration=conf)
    got = {}
    s.binding_sink = lambda pod, node: got.__setitem__(pod.name, node)
    for n in nodes:
        s.on_node_add(n)
    for p in copy.deepcopy(pods):
        s.on_pod_add(p)
    for o in s.schedule_pending():
        got.setdefault(o.pod.name, o.node)
    return got, s


def _one_term_pods(n):
    """ALL pods share ONE topology term (same selector, same key) —
    maximal interaction: every placement shifts every later verdict."""
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )

    return [
        Pod(
            name=f"p{i}",
            labels={"app": "one"},
            topology_spread_constraints=(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": "one"}),
                ),
            ),
            containers=[
                Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})
            ],
        )
        for i in range(n)
    ]


def test_wave_one_shared_term_degenerates_serial(sanitize_on):
    """Degenerate case: one shared hard topology term.  The wave's
    admission pass must replay the serial recurrence exactly — placements
    equal the serial oracle's, pod for pod — and speculation survives for
    almost no one (the wave honestly reports the serialization)."""
    from kubernetes_tpu.oracle.state import OracleState as OS

    nodes = _zone_nodes(12)
    pods = _one_term_pods(40)

    state = OS.build(nodes)
    want = run_serial(state, [p for p in __import__("copy").deepcopy(pods)])

    got, s = _drain_sched(nodes, pods, wave=True)
    assert [got.get(f"p{i}") for i in range(len(pods))] == want
    assert s.metrics["wave_batches"] >= 1
    assert s.metrics["wave_pods"] >= len(pods)
    # maximal interaction: the vast majority of speculative placements are
    # demoted (corrected in-dispatch) — the wave degenerated to serial
    assert s.metrics["wave_admitted"] <= s.metrics["wave_pods"] * 0.5

    # the demotions are observable: flight-recorder events with the
    # conflicting term, surfaced by /debug/explain as a wave conflict
    demoted_uids = [
        e["pod"]
        for e in s.flight.tail(10_000)
        if e["kind"] == "wave_demoted"
    ]
    assert demoted_uids, "no wave_demoted flight events recorded"
    ev = [
        e
        for e in s.flight.events_for(demoted_uids[-1])
        if e["kind"] == "wave_demoted"
    ][-1]
    assert ev["detail"]["kind"] in ("spread", "affinity", "fit", "score")
    from kubernetes_tpu.observability.explain import explain_pod, find_pod

    pod = find_pod(s, demoted_uids[-1])
    assert pod is not None
    out = explain_pod(s, pod)
    assert out["wave"]["demoted"] is True
    assert out["wave"]["reason"] == "demoted by wave conflict"


def test_wave_disjoint_terms_single_wave_admits_all(sanitize_on):
    """Fully disjoint footprints: per-pod spread terms (distinct
    selectors) and disjoint feasible sets — one wave admits every pod at
    its speculative placement, bit-equal to the serial oracle."""
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )
    from kubernetes_tpu.oracle.state import OracleState as OS

    n_pods = 24
    # two dedicated nodes per pod (disjoint feasible sets via nodeSelector)
    nodes = _zone_nodes(
        2 * n_pods, zones=4, extra=lambda i: {"slot": f"s{i // 2}"}
    )
    pods = [
        Pod(
            name=f"p{i}",
            labels={"app": f"solo-{i}"},
            node_selector={"slot": f"s{i}"},
            topology_spread_constraints=(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(
                        match_labels={"app": f"solo-{i}"}
                    ),
                ),
            ),
            containers=[
                Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})
            ],
        )
        for i in range(n_pods)
    ]

    state = OS.build(nodes)
    want = run_serial(state, [p for p in __import__("copy").deepcopy(pods)])

    got, s = _drain_sched(nodes, pods, wave=True)
    assert [got.get(f"p{i}") for i in range(n_pods)] == want
    assert s.metrics["wave_batches"] >= 1
    # zero interaction ⇒ one wave admits everything as speculated
    assert s.metrics["wave_admitted"] == s.metrics["wave_pods"]
    assert not [
        e for e in s.flight.tail(10_000) if e["kind"] == "wave_demoted"
    ]


def test_wave_bulk_commit_never_skips_relevant_reserve():
    """The wave bulk-commit gate relies on the same 'Reserve/Permit are
    no-ops for host-filter-irrelevant pods' contract as the fast path —
    a wave batch carrying a host-filter-RELEVANT pod must take the
    per-pod commit path so the plugin's Reserve actually runs."""
    import copy

    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )
    from kubernetes_tpu.framework import config as cfg
    from kubernetes_tpu.framework.interface import (
        FilterPlugin,
        ReservePlugin,
        Status,
    )
    from kubernetes_tpu.framework.registry import default_registry
    from kubernetes_tpu.scheduler import Scheduler

    class CountingReserve(FilterPlugin, ReservePlugin):
        """Host Filter + Reserve (the volumebinding shape): relevant only
        to pods labeled pvc=yes."""

        name = "CountingReserve"
        reserve_calls = 0

        def filter(self, state, pod, node_state) -> Status:
            return Status.success()

        def maybe_relevant(self, pod) -> bool:
            return pod.labels.get("pvc") == "yes"

        def reserve(self, state, pod, node_name) -> Status:
            CountingReserve.reserve_calls += 1
            return Status.success()

    CountingReserve.reserve_calls = 0
    reg = default_registry()
    reg.register(
        CountingReserve.name,
        lambda args, handle: CountingReserve(args=args, handle=handle),
    )
    profile = cfg.Profile()
    profile.plugins.filter.enabled.append(cfg.PluginRef(CountingReserve.name))
    profile.plugins.reserve.enabled.append(cfg.PluginRef(CountingReserve.name))
    conf = cfg.SchedulerConfiguration(profiles=[profile], batch_size=32)
    sched = Scheduler(conf, registry=reg)
    bound = {}
    sched.binding_sink = lambda pod, node: bound.__setitem__(pod.name, node)
    for n in _zone_nodes(8):
        sched.on_node_add(n)

    def spread_pod(name, labels):
        app = labels.get("app", "x")
        return Pod(
            name=name,
            labels=labels,
            topology_spread_constraints=(
                TopologySpreadConstraint(
                    max_skew=3,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": app}),
                ),
            ),
            containers=[
                Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})
            ],
        )

    pods = [spread_pod(f"plain-{i}", {"app": "plain"}) for i in range(10)]
    pods += [
        spread_pod(f"pvc-{i}", {"app": "claims", "pvc": "yes"})
        for i in range(4)
    ]
    for p in copy.deepcopy(pods):
        sched.on_pod_add(p)
    outs = sched.schedule_pending()
    placed_pvc = sum(
        1 for o in outs if o.node and o.pod.labels.get("pvc") == "yes"
    )
    assert placed_pvc == 4
    # every placed relevant pod walked Reserve — the bulk path may only
    # bypass the walk for pods the plugin is provably irrelevant to
    assert CountingReserve.reserve_calls == placed_pvc


def test_wave_off_matches_wave_on():
    """The config kill-switch routes back to the gang scan — decisions
    must not depend on the switch (port users included: on the wave they
    ride the occupancy carry, off it the scan's pod×pod matrix)."""
    import random as _r

    rng = _r.Random(9)
    nodes, placed = make_cluster(rng, 14, 10)
    pods = [make_pod(rng, f"w-{i}") for i in range(60)]
    for p in pods:
        p.node_name = None
    g_on, s_on = _drain_sched(nodes, pods, wave=True)
    g_off, s_off = _drain_sched(nodes, pods, wave=False)
    assert g_on == g_off
    assert s_off.metrics["wave_batches"] == 0
    # the kill switch is a COUNTED fallback-ladder rung now
    assert s_off.prom.wave_fallback.value(reason="kill_switch") >= 1
    assert s_on.prom.wave_fallback.value(reason="kill_switch") == 0


# ---------------------------------------------------------------------------
# De-fallback coverage: port-heavy and sampling-compat batches ride the
# factored wave engine (ISSUE 11) — randomized property tests under
# KTPU_SANITIZE=1 plus kill-switch identity, with the fallback counter
# asserting the retired rungs (ports / sampling_compat) stay unused.
# ---------------------------------------------------------------------------


def _port_heavy_pods(n, seed=5):
    """THE port-contended mix — imported from paritycheck so the property
    tests, the parity artifact, and bench config13 all exercise one
    workload definition instead of drifting copies."""
    from kubernetes_tpu.tools.paritycheck import (
        _port_heavy_pods as _gen,
    )

    return _gen(n, seed=seed, apps=6, prefix="pt")


@pytest.mark.parametrize("seed", [1, 7, 23])
def test_port_heavy_wave_matches_serial(sanitize_on, seed):
    """Randomized port-heavy drains: the wave engine (port-occupancy carry
    engaged) is bit-identical to the serial oracle and to the kill-switch
    (gang scan) drain, and the retired `ports` fallback rung stays at
    zero."""
    import copy

    from kubernetes_tpu.oracle.state import OracleState as OS

    nodes = _zone_nodes(10)
    pods = _port_heavy_pods(48, seed=seed)

    state = OS.build(nodes)
    want = run_serial(state, copy.deepcopy(pods))

    got, s_on = _drain_sched(nodes, pods, wave=True)
    assert [got.get(p.name) for p in pods] == want
    assert s_on.metrics["wave_batches"] >= 1
    assert s_on.prom.wave_fallback.value(reason="ports") == 0
    assert s_on.prom.wave_fallback.value(reason="sampling_compat") == 0

    g_off, _ = _drain_sched(nodes, pods, wave=False)
    assert got == g_off


def test_port_conflict_demotes_with_ports_kind(sanitize_on):
    """Two pods racing ONE host port on a shared best node: the loser is
    demoted with kind=ports (attribution, flight event, counter)."""
    from kubernetes_tpu.api.types import Container, ContainerPort, Pod

    nodes = _zone_nodes(1)  # one node: identical speculative placements
    pods = [
        Pod(
            name=f"racer-{i}",
            labels={"app": "race"},
            containers=[
                Container(
                    name="c",
                    requests={"cpu": "100m", "memory": "64Mi"},
                    ports=(
                        ContainerPort(
                            container_port=8080, host_port=7777, protocol="TCP"
                        ),
                    ),
                )
            ],
        )
        for i in range(2)
    ]
    got, s = _drain_sched(nodes, pods, wave=True)
    assert got.get("racer-0") == "node-0"
    assert got.get("racer-1") is None
    assert s.metrics["wave_batches"] >= 1
    demoted = [
        e for e in s.flight.tail(1000) if e["kind"] == "wave_demoted"
    ]
    assert demoted and demoted[-1]["detail"]["kind"] == "ports"
    assert s.prom.wave_conflicts.value(kind="ports") >= 1


def _compat_drain(nodes, pods, wave: bool, seed=17):
    import copy

    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler

    conf = SchedulerConfiguration()
    conf.wave_dispatch = wave
    conf.batch_size = 64
    conf.reference_sampling_compat = True
    conf.tie_break_seed = seed
    s = Scheduler(configuration=conf)
    got = {}
    s.binding_sink = lambda pod, node: got.__setitem__(pod.name, node)
    for n in nodes:
        s.on_node_add(n)
    for p in copy.deepcopy(pods):
        s.on_pod_add(p)
    for o in s.schedule_pending():
        got.setdefault(o.pod.name, o.node)
    return got, s


@pytest.mark.parametrize("seed", [3, 19])
def test_sampling_compat_rides_wave(sanitize_on, seed):
    """reference_sampling_compat + seeded-tie drains with cross-pod terms
    ride the wave engine now — identical to the kill-switch (gang scan)
    drain, which the sampling modes are already oracle-parity-tested on,
    and the retired `sampling_compat` rung stays at zero."""
    rng = random.Random(seed)
    nodes = _zone_nodes(12)
    pods = [make_pod(rng, f"sc-{i}") for i in range(72)]
    for p in pods:
        p.node_name = None

    got_on, s_on = _compat_drain(nodes, pods, wave=True, seed=seed)
    got_off, s_off = _compat_drain(nodes, pods, wave=False, seed=seed)
    assert got_on == got_off
    # the compat drain actually exercised the wave (the generator mixes in
    # spread/affinity/port pods, so at least one batch is wave-shaped)
    assert s_on.metrics["wave_batches"] >= 1
    assert s_off.metrics["wave_batches"] == 0
    assert s_on.prom.wave_fallback.value(reason="sampling_compat") == 0
    assert s_on.prom.wave_fallback.value(reason="ports") == 0


def test_duplicate_hostname_falls_back_counted(sanitize_on):
    """Two nodes claiming ONE hostname label value: the mirror's
    once-per-snapshot uniqueness bit disqualifies the wave (the factored
    hostname-domain counts assume hostname ≡ node identity), the batch
    takes the gang scan with reason=dup_hostname counted, and decisions
    still match the serial oracle."""
    import copy

    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.oracle.state import OracleState as OS

    nodes = _zone_nodes(6)
    nodes.append(
        Node(
            name="impostor",
            labels={
                "topology.kubernetes.io/zone": "zone-0",
                # duplicates node-0's hostname label value
                "kubernetes.io/hostname": "node-0",
            },
            capacity=Resource.from_map(
                {"cpu": "8", "memory": "32Gi", "pods": 110}
            ),
        )
    )
    pods = _one_term_pods(16)

    state = OS.build(nodes)
    want = run_serial(state, copy.deepcopy(pods))

    got, s = _drain_sched(nodes, pods, wave=True)
    assert [got.get(p.name) for p in pods] == want
    assert s.metrics["wave_batches"] == 0
    assert s.prom.wave_fallback.value(reason="dup_hostname") >= 1
    assert not s.mirror.hostnames_unique


def test_mirror_hostnames_unique_memoizes():
    """The uniqueness bit is computed once per snapshot lineage: repeated
    reads hit the memo; adding a duplicate-hostname node invalidates it."""
    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler

    s = Scheduler(configuration=SchedulerConfiguration())
    for n in _zone_nodes(4):
        s.on_node_add(n)
    with s._mu:
        s.mirror.update(s.cache, s.namespace_labels)
        assert s.mirror.hostnames_unique
        memo = s.mirror._hostnames_unique_memo
        assert s.mirror.hostnames_unique  # second read: memo hit
        assert s.mirror._hostnames_unique_memo is memo

    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node

    s.on_node_add(
        Node(
            name="dup",
            labels={"kubernetes.io/hostname": "node-0"},
            capacity=Resource.from_map(
                {"cpu": "8", "memory": "32Gi", "pods": 110}
            ),
        )
    )
    with s._mu:
        s.mirror.update(s.cache, s.namespace_labels)
        assert not s.mirror.hostnames_unique


# ---------------------------------------------------------------------------
# As many distinct SOFT spread terms as pods (cl2load-5k's shape, WAVE.md
# "Many terms a batch"): every pod carries the two built-in default
# constraints — maxSkew 3 on the hostname key, maxSkew 5 on the zone key, both
# ScheduleAnyway — over its OWN Deployment's selector, so a batch of pods of D
# Deployments has T = 2·D distinct terms, C = 2 slots a pod, one of them the
# hostname slot, and the normalised score, not a mask, decides.
# ---------------------------------------------------------------------------


def _deployment_pod(name, deployment, node_name=""):
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )

    sel = LabelSelector(match_labels={"name": deployment})
    return Pod(
        name=name,
        namespace="default",
        uid=f"default/{name}",
        labels={"name": deployment},
        node_name=node_name,
        topology_spread_constraints=(
            TopologySpreadConstraint(
                max_skew=3, topology_key=HOSTNAME_LABEL,
                when_unsatisfiable="ScheduleAnyway", label_selector=sel,
            ),
            TopologySpreadConstraint(
                max_skew=5, topology_key="topology.kubernetes.io/zone",
                when_unsatisfiable="ScheduleAnyway", label_selector=sel,
            ),
        ),
        containers=[Container(name="c", requests={"cpu": "100m", "memory": "500Mi"})],
    )


def _wave_run_batch(state, pending, t_floor):
    """One fused ``wave_run`` (per-pod statics) of ``pending`` against
    ``state``: (placements, the term buckets its tables were built at, the
    demotion kinds)."""
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _pack(state, pending)
    wt = wave.wave_tables(pb, pc.nodes.label_vals, hk_id, t_floor=t_floor)
    chosen, _, _, _, stats = wave.wave_run(
        dc, db, hostname_key, v_cap,
        wt["tid_sp"], wt["rep_sp_p"], wt["rep_sp_c"], wt["tid_ip"], wt["rep_ip_p"], wt["rep_ip_u"],
        wt["ip_cdv_tab"], d2_cap=wt["d2_cap"], has_ports=wt["has_ports"], tid_pt=wt["tid_pt"],
        port_conf=wt["port_conf"], **tables,
    )
    names = list(state.nodes)
    placed = [names[int(c)] if int(c) >= 0 else None for c in np.asarray(chosen)[: len(pending)]]
    return placed, wt, np.asarray(stats)[1, : len(pending)]


@pytest.mark.parametrize(
    "seed,n_nodes,batches,sticky",
    [(5, 12, (14, 3), True), (5, 12, (14, 3), False), (17, 24, (16, 16, 5), True), (29, 9, (3, 12), True)],
    ids=["14-then-3-deployments-sticky", "14-then-3-deployments-own-bucket", "three-batches-sticky",
         "growing-bucket-sticky"],
)
def test_wave_run_equals_the_serial_oracle_with_as_many_soft_spread_terms_as_pods(seed, n_nodes, batches, sticky):
    """Consecutive batches of 16 pods, batch b of ``batches[b]`` Deployments
    (T = twice that), each against the state the batches before left: the
    fused wave equals the serial oracle at every position whether a batch's
    tables are built at its own bucket or, as the loop builds them, at the
    largest bucket a batch before it met (``t_floor``); a pod that loses its
    speculated node loses it on SCORE — nothing here is a hard mask."""
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node

    rng = random.Random(seed)
    nodes = [
        Node(
            name=f"n{i}",
            labels={HOSTNAME_LABEL: f"n{i}", "topology.kubernetes.io/zone": f"z{i % 3}"},
            capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110}),
        )
        for i in range(n_nodes)
    ]
    n_deployments = max(batches)
    placed = [
        _deployment_pod(f"placed-{d}-{j}", f"d{d}", node_name=rng.choice(nodes).name)
        for d in range(n_deployments) for j in range(rng.randrange(0, 4))
    ]
    state_w = OracleState.build(nodes, placed)
    state_s = OracleState.build(nodes, placed)
    floor, caps, kinds = (1, 1, 1), [], []
    for b, n_dep in enumerate(batches):
        owners = list(range(n_dep)) + [rng.randrange(n_dep) for _ in range(16 - n_dep)]
        rng.shuffle(owners)
        got, wt, kind = _wave_run_batch(
            state_w, [_deployment_pod(f"w{b}-{i}", f"d{d}") for i, d in enumerate(owners)], floor)
        want = run_serial(state_s, [_deployment_pod(f"w{b}-{i}", f"d{d}") for i, d in enumerate(owners)])
        assert got == want and None not in got
        assert wt["n_terms"] == 2 * n_dep  # C = 2 slots a pod, one term a slot a Deployment
        for i, (d, node) in enumerate(zip(owners, got)):
            state_w.place(_deployment_pod(f"w{b}-{i}", f"d{d}", node_name=node))
        caps.append(wt["t_caps"][0])
        kinds += list(kind)
        if sticky:
            floor = wt["t_caps"]
    own = [bucket_cap(2 * n_dep, 1) for n_dep in batches]
    assert len(set(own)) > 1  # the batches' own buckets differ ...
    assert caps == ([max(own[: b + 1]) for b in range(len(own))] if sticky else own)  # ... a sticky one only grows
    assert set(kinds) <= {wave.DEMOTE_NONE, wave.DEMOTE_SCORE} and wave.DEMOTE_SCORE in kinds
