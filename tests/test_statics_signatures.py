"""The cross-pod statics by distinct pod row (``wave.static_signatures`` +
``gang.precompute(sig=, rep_pod=)``): the device computes ``GangStatics`` for
one representative row a signature and every pod reads its signature's row.

Pinned here: the table changes no integer of the statics and no decision
(generator pods, each repeated and shuffled; a padded last batch); the row is
EVERY leaf of the packed batch, so two pods that differ only in a field the
wave's term dedup ignores keep their own rows; a batch with more distinct rows
than the one bucket, or with host-plugin vetoes, dispatches the per-pod
program (no ``sig`` argument: the jit signature of the parent commit) and is
counted; the served loop, on the ``spread-5k`` cell at cut counts, computes
one signature a batch and decides as the frozen reference does.
"""

import contextlib
import copy
import io
import random
import time

import jax
import numpy as np
import pytest

from kubernetes_tpu.ops import gang, wave
from kubernetes_tpu.ops.wave import STATIC_SIG_CAP
from kubernetes_tpu.oracle.state import OracleState
from kubernetes_tpu.snapshot.schema import bucket_cap

from tests.gen import make_cluster, make_pod
from tests.test_wave import NS_LABELS, _pack, run_serial

N_FIELDS = 39


def _stamp(pod, name):
    q = copy.deepcopy(pod)
    q.name, q.uid = name, f"{pod.namespace}/{name}"
    return q


def _repeated(rng, n_base):
    """Generator pods (spread, required and preferred (anti-)affinity, host
    ports, tolerations, node selectors, three namespaces), each stamped 1-4
    times as a Deployment stamps its replicas, shuffled."""
    base = [make_pod(rng, f"tmpl-{i}") for i in range(n_base)]
    pods = [_stamp(b, f"{b.name}-r{r}") for b in base for r in range(rng.randint(1, 4))]
    rng.shuffle(pods)
    return pods


def _statics_both_ways(state, pending, u_cap):
    """``gang.precompute`` per pod and by signature, each as ONE jitted
    program (op by op it compiles every primitive for every shape)."""
    _vocab, _pc, pb, dc, db, v_cap, _hk, hostname_key, tables = _pack(state, pending)
    tables.pop("d_cap")
    ss = wave.static_signatures(pb, u_cap=u_cap)
    assert ss is not None
    pre = jax.jit(lambda dc, db, hk, tables, sig, rep_pod: gang.precompute(
        dc, db, hk, v_cap, sig=sig, rep_pod=rep_pod, **tables))
    per_pod = pre(dc, db, hostname_key, tables, None, None)
    by_sig = pre(dc, db, hostname_key, tables, ss["sig"], ss["rep_pod"])
    return pb, ss, per_pod, by_sig


def _assert_same_statics(per_pod, by_sig):
    assert len(per_pod._fields) == N_FIELDS
    for name in per_pod._fields:
        a, b = np.asarray(getattr(per_pod, name)), np.asarray(getattr(by_sig, name))
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(a, b), name


def _placements(state, pending, chosen):
    names = list(state.nodes)
    return [names[int(c)] if int(c) >= 0 else None for c in np.asarray(chosen)[: len(pending)]]


def run_roots(state, pending, u_cap):
    """Placements of the three fused roots the loop dispatches: ``wave_run``
    with the table, ``wave_run`` without it, and the scan (``gang_run``)."""
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _pack(state, pending)
    wt = wave.wave_tables(pb, pc.nodes.label_vals, hk_id)
    ss = wave.static_signatures(pb, u_cap=u_cap)
    args = (dc, db, hostname_key, v_cap, wt["tid_sp"], wt["rep_sp_p"], wt["rep_sp_c"], wt["tid_ip"], wt["rep_ip_p"],
            wt["rep_ip_u"], wt["ip_cdv_tab"])
    kw = dict(d2_cap=wt["d2_cap"], has_ports=wt["has_ports"], tid_pt=wt["tid_pt"], port_conf=wt["port_conf"], **tables)
    by_sig = wave.wave_run(*args, sig=ss["sig"], rep_pod=ss["rep_pod"], **kw)[0]
    per_pod = wave.wave_run(*args, **kw)[0]
    scan = gang.gang_run(dc, db, hostname_key, v_cap, **tables)[0]
    return [_placements(state, pending, c) for c in (by_sig, per_pod, scan)]


# ---------------------------------------------------------------------------
# (a) the property: same integers, same decisions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,n_nodes,n_placed,n_base", [(41, 10, 20, 9), (42, 10, 20, 12), (111, 12, 24, 20), (222, 40, 80, 45)])
def test_statics_by_signature_are_the_per_pod_statics_bit_for_bit(seed, n_nodes, n_placed, n_base):
    rng = random.Random(seed)
    nodes, placed = make_cluster(rng, n_nodes, n_placed)
    pending = _repeated(rng, n_base)
    state = OracleState.build(nodes, placed, namespace_labels=NS_LABELS)
    pb, ss, per_pod, by_sig = _statics_both_ways(state, pending, u_cap=bucket_cap(len(pending) + 1))
    # the generator's pods differ, their stamped copies do not
    sig = np.asarray(ss["sig"])
    assert ss["n_valid"] == len(set(sig[np.asarray(pb.valid)])) <= n_base < len(pending)
    _assert_same_statics(per_pod, by_sig)


@pytest.mark.parametrize("seed,n_nodes,n_placed,n_base", [(41, 10, 20, 5), (42, 10, 20, 6), (43, 12, 24, 7), (44, 12, 24, 9)])
def test_wave_with_the_table_places_as_the_scan_the_wave_and_the_serial_oracle(seed, n_nodes, n_placed, n_base):
    rng = random.Random(seed)
    nodes, placed = make_cluster(rng, n_nodes, n_placed)
    pending = _repeated(rng, n_base)

    def fresh():
        return OracleState.build(nodes, placed, namespace_labels=NS_LABELS)

    by_sig, per_pod, scan = run_roots(fresh(), pending, u_cap=bucket_cap(len(pending) + 1))
    assert by_sig == per_pod == scan == run_serial(fresh(), pending)


# ---------------------------------------------------------------------------
# (b) a padded last batch: the padding rows are a second signature
# ---------------------------------------------------------------------------

def test_a_padded_batch_of_one_template_is_two_rows_and_decides_the_same():
    rng = random.Random(7)
    nodes, placed = make_cluster(rng, 10, 20)
    tmpl = next(p for p in (make_pod(rng, f"t-{i}") for i in range(50)) if p.topology_spread_constraints)
    pending = [_stamp(tmpl, f"replica-{i}") for i in range(5)]  # p_cap 8: three padding rows

    def fresh():
        return OracleState.build(nodes, placed, namespace_labels=NS_LABELS)

    pb, ss, per_pod, by_sig = _statics_both_ways(fresh(), pending, u_cap=STATIC_SIG_CAP)
    assert pb.valid.tolist() == [True] * 5 + [False] * 3
    rep = np.asarray(ss["rep_pod"])
    assert rep.shape == (STATIC_SIG_CAP,) and (rep >= 0).sum() == 2 and ss["n_valid"] == 1
    sig = np.asarray(ss["sig"])
    assert len(set(sig[:5])) == len(set(sig[5:])) == 1 and sig[0] != sig[5]
    _assert_same_statics(per_pod, by_sig)
    by_sig, per_pod, scan = run_roots(fresh(), pending, u_cap=STATIC_SIG_CAP)
    assert by_sig == per_pod == scan == run_serial(fresh(), pending)


# ---------------------------------------------------------------------------
# (d) a signature is every leaf, not the term dedup's choice of leaves
# ---------------------------------------------------------------------------

def _spread_pod(name, **kw):
    from kubernetes_tpu.api.types import Container, LabelSelector, Pod, TopologySpreadConstraint

    return Pod(
        name=name, labels={"app": "web"},
        topology_spread_constraints=(TopologySpreadConstraint(
            max_skew=1, topology_key="topology.kubernetes.io/zone", when_unsatisfiable="DoNotSchedule",
            label_selector=LabelSelector(match_labels={"app": "web"})),),
        containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})], **kw)


def _with_toleration(name):
    from kubernetes_tpu.api.types import Toleration

    return _spread_pod(name, tolerations=(Toleration(key="dedicated", operator="Equal", value="infra", effect="NoSchedule"),))


DIFFER_ONLY_IN = {
    "a-toleration": _with_toleration,
    "a-node-selector": lambda name: _spread_pod(name, node_selector={"disk": "ssd"}),
    "priority": lambda name: _spread_pod(name, priority=1000),
}


@pytest.mark.parametrize("field", sorted(DIFFER_ONLY_IN))
def test_two_pods_that_share_a_term_but_not_a_row_get_two_signatures(field):
    """One spread term in the wave's term table (``tid_sp`` dedups topology
    key, namespace, selector), two rows in the statics' table — and where the
    field decides feasibility, their own ``static_mask`` rows."""
    from kubernetes_tpu.api.types import Node, Taint
    from kubernetes_tpu.api.resource import Resource

    def node(i, labels=None, taints=()):
        return Node(name=f"n{i}", labels={"topology.kubernetes.io/zone": f"z{i % 2}", **(labels or {})},
                    taints=tuple(taints), capacity=Resource.from_map({"cpu": "4", "memory": "8Gi", "pods": 110}))

    nodes = [node(0), node(1, labels={"disk": "ssd"}), node(2, taints=[Taint("dedicated", "infra", "NoSchedule")]), node(3)]
    pending = [_spread_pod("plain-0"), DIFFER_ONLY_IN[field]("other-0"), _spread_pod("plain-1"), DIFFER_ONLY_IN[field]("other-1")]
    state = OracleState.build(nodes, [])
    _vocab, pc, pb, _dc, _db, _v, hk_id, _hk, _tables = _pack(state, pending)
    wt = wave.wave_tables(pb, pc.nodes.label_vals, hk_id)
    assert len(set(np.asarray(wt["tid_sp"])[:4, 0])) == 1  # ONE term
    pb, ss, per_pod, by_sig = _statics_both_ways(state, pending, u_cap=STATIC_SIG_CAP)
    sig = np.asarray(ss["sig"])
    assert ss["n_valid"] == 2 and sig[0] == sig[2] != sig[1] == sig[3]
    _assert_same_statics(per_pod, by_sig)
    mask = np.asarray(by_sig.static_mask)[:4, :4]
    assert np.array_equal(mask[0], mask[2]) and np.array_equal(mask[1], mask[3])
    if field != "priority":  # a priority is a row of its own and masks nothing
        assert not np.array_equal(mask[0], mask[1])


# ---------------------------------------------------------------------------
# (c) when the table is off: the per-pod program, counted
# ---------------------------------------------------------------------------

def _wave_specs(sched):
    """(kwargs of every retained dispatch spec) of the wave roots."""
    out = []
    for name in ("wave.wave_run", "chain.chain_dispatch"):
        ks = sched.kernels._kstats.get(name)
        for b in (ks.buckets.values() if ks else ()):
            if b["spec"] is not None:
                out.append(b["spec"][1])
    return out


def test_a_batch_with_more_rows_than_the_bucket_takes_the_per_pod_program_and_is_counted():
    from kubernetes_tpu.tools import paritycheck as pc

    pods = pc._cross_pod_pods(96)
    nodes = pc._basic_nodes(32, zones=4)
    got, s = pc._drain(nodes, pods, return_sched=True, mesh_dispatch=False, batch_size=32)
    phases = s.phases.snapshot()
    waves = s.metrics["wave_batches"]
    full, sigs = phases.get("wave.static_full", 0), phases.get("wave.static_sigs", 0)
    assert waves >= 3 and full >= 1  # 32 distinct rows a batch: past the bucket
    specs = _wave_specs(s)
    assert specs
    with_table = [kw for kw in specs if kw.get("sig") is not None]
    # every wave batch is booked one way or the other, and only a dispatch
    # that ran with the table carries the two arguments
    assert full + len(with_table) <= waves and (sigs > 0) == bool(with_table)
    assert s.prom.wave_static_signatures.value() == sigs
    want = pc._drain(nodes, pc._cross_pod_pods(96), mesh_dispatch=False, batch_size=32, wave_dispatch=False)
    assert got == want


def lowered_wave_runs():
    """``wave_run`` lowered at one small batch of stamped generator pods:
    (no table argument, ``sig=None`` spelled out, with the table)."""
    rng = random.Random(41)
    nodes, placed = make_cluster(rng, 10, 20)
    pending = _repeated(rng, 3)
    state = OracleState.build(nodes, placed, namespace_labels=NS_LABELS)
    _vocab, pc_, pb, dc, db, v_cap, hk_id, hostname_key, tables = _pack(state, pending)
    wt = wave.wave_tables(pb, pc_.nodes.label_vals, hk_id)
    ss = wave.static_signatures(pb)
    assert ss is not None
    args = (dc, db, hostname_key, v_cap, wt["tid_sp"], wt["rep_sp_p"], wt["rep_sp_c"], wt["tid_ip"], wt["rep_ip_p"],
            wt["rep_ip_u"], wt["ip_cdv_tab"])
    kw = dict(d2_cap=wt["d2_cap"], has_ports=wt["has_ports"], tid_pt=wt["tid_pt"], port_conf=wt["port_conf"], **tables)
    return (wave.wave_run.lower(*args, **kw), wave.wave_run.lower(*args, sig=None, rep_pod=None, **kw),
            wave.wave_run.lower(*args, sig=ss["sig"], rep_pod=ss["rep_pod"], **kw))


def test_the_per_pod_dispatch_is_the_parents_jit_signature():
    """``sig=None`` adds no argument to the program: the root's lowered
    text without the table mentions no signature operand, and differs from
    the text with it (which is the ONE new program a batch size)."""
    plain, explicit_none, tabled = lowered_wave_runs()
    assert plain.as_text() == explicit_none.as_text()
    assert plain.as_text() != tabled.as_text()
    n_args = lambda lowered: len(jax.tree_util.tree_leaves(lowered.in_avals))
    assert n_args(explicit_none) == n_args(plain) == n_args(tabled) - 2


def test_a_batch_with_host_plugin_vetoes_takes_the_per_pod_program_and_is_counted():
    from kubernetes_tpu.framework import config as cfg
    from kubernetes_tpu.framework.interface import FilterPlugin, Status
    from kubernetes_tpu.framework.registry import default_registry
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.tools import paritycheck as pc

    class VetoFirstNode(FilterPlugin):
        """A host Filter (the volumebinding shape), relevant to every pod."""

        name = "VetoFirstNode"

        def filter(self, state, pod, node_state) -> Status:
            if node_state.node.name.endswith("-0"):
                return Status.unschedulable("vetoed")
            return Status.success()

        def maybe_relevant(self, pod) -> bool:
            return True

    reg = default_registry()
    reg.register(VetoFirstNode.name, lambda args, handle: VetoFirstNode(args=args, handle=handle))
    profile = cfg.Profile()
    profile.plugins.filter.enabled.append(cfg.PluginRef(VetoFirstNode.name))
    s = Scheduler(cfg.SchedulerConfiguration(profiles=[profile], batch_size=32), registry=reg)
    bound = {}
    s.binding_sink = lambda pod, node: bound.__setitem__(pod.name, node)
    nodes = pc._basic_nodes(8, zones=4)
    for n in nodes:
        s.on_node_add(n)
    for i in range(12):  # one template: one signature, were the table on
        s.on_pod_add(_spread_pod(f"web-{i}"))
    s.schedule_pending()
    phases = s.phases.snapshot()
    assert s.metrics["wave_batches"] == phases["wave.static_full"] >= 1
    assert "wave.static_sigs" not in phases
    assert all(kw.get("sig") is None for kw in _wave_specs(s))
    assert len(bound) == 12 and nodes[0].name not in bound.values()


# ---------------------------------------------------------------------------
# (e) the served loop on the spread cell at cut counts
# ---------------------------------------------------------------------------

CELL = "spread-5k.backlog"
NODES, PODS, INIT, BATCH = 96, 96, 24, 32
WAVES = PODS // BATCH
NEW_METRIC = "loop.wave_static_sigs_per_kpod.backlog"


@pytest.fixture(scope="module")
def served():
    from benchmarks import cells, runner
    from tests.test_bench_unsched_cell import _watch

    bench = cells.benchmark()
    seen = {}

    def watch(cluster):
        cluster.sched.config.batch_size = BATCH
        _watch(seen)(cluster)

    with contextlib.redirect_stdout(io.StringIO()):
        res = runner.run_cell(
            cells.cut(cells.cell(CELL, bench), NODES, PODS, INIT), bench, 3800000007, 120.0, False,
            time.perf_counter(), require_chip=False, tamper=watch, identity_positions=list(range(PODS)),
        )
    cluster = seen.pop("cluster")
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    seen["specs"] = _wave_specs(cluster.sched)
    return res, seen, bench


def test_the_served_loop_decides_as_the_reference_at_every_position(served):
    res, _seen, _bench = served
    got = res["compared"]
    assert res["attempted"] == PODS and res["failed"] == 0
    assert got["identity.positions_compared"]["value"] == PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert got["device.compiles_in_window"]["value"] == 0
    assert res["correct"] is True, {k: v for k, v in got.items() if not v["ok"]}


def test_the_served_loop_computes_one_signature_a_batch(served):
    _res, seen, _bench = served
    window = seen["window"]
    assert window["wave.static_sigs"] == WAVES and "wave.static_full" not in window
    assert seen["specs"] and all(kw["rep_pod"].shape == (STATIC_SIG_CAP,) for kw in seen["specs"])


def test_the_new_metric_loads_and_reads_a_counter_the_program_books(served):
    from benchmarks import cells

    _res, seen, bench = served
    # the cross-pod cells; cl2load-5k (PR 48) reads 0.0 there: every batch holds more rows than the table
    cross_pod = ["spread-5k.backlog", "interpod-5k.backlog", "antiaffinity-5k.backlog",
                 "cl2load-5k.backlog-of-deployments"]
    entry, = [m for m in bench["per_layer"] if m["name"] == NEW_METRIC]
    assert entry["workloads"] == cross_pod and entry["moves"] == "pods_per_s" and entry["source"] == "program_counter"
    spec = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}[NEW_METRIC]
    assert spec["reader"] == "phase" and spec["layer"] == entry["layer"] == "scheduling loop"
    assert set(spec["params"]["phases"]) <= set(seen["window"])  # a name the program books
    assert spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"]) == 1000.0 * WAVES / PODS
    # at the source's counts: ten batches of one signature, 5,000 pods
    assert spec["read"]({"phases": {"wave.static_sigs": 10.0}, "pods_in_window": 5000}, spec["params"]) == 2.0
    # a program without the counter (the parent) reads 0.0 and raises nothing
    assert spec["read"]({"phases": {"wave.demoted": 4990.0}, "pods_in_window": 5000}, spec["params"]) == 0.0
    assert NEW_METRIC not in {s["name"] for s in cells.layer_metrics("basic-5k.backlog", bench)}
