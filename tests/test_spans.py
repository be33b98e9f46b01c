"""The one span primitive (``PhaseAccumulator.span``), the sub-phases it
splits the served path into, the ``ktpu.*`` events a profiler session
holds, and the stage names the scheduling roots put on their device ops.

Everything runs on the CPU backend: a profiler session there has the host
plane (where the program's spans land) and no device plane.
"""

import collections
import glob
import os
import re
import time

import pytest

from kubernetes_tpu.metrics import Histogram, PhaseAccumulator
from kubernetes_tpu.observability.tracer import Tracer


class _TailTap:
    """Stands where ``PhaseAccumulator.tracer`` expects a Tracer."""

    def __init__(self, enabled=True):
        self.enabled = enabled
        self.calls = []

    def complete_tail(self, name, dur_s, *a, **kw):
        self.calls.append((name, dur_s))


def _hist():
    return Histogram("t_phase_seconds", "test", ("phase",))


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def test_span_books_what_add_booked():
    """Same total, same histogram observation, same complete_tail call as
    ``add`` with the span's own duration."""
    a, b = PhaseAccumulator(hist=_hist()), PhaseAccumulator(hist=_hist())
    a.tracer, b.tracer = _TailTap(), _TailTap()
    with a.span("commit", bid=7):
        time.sleep(0.002)
    (name, dt), = a.tracer.calls
    assert name == "commit" and dt >= 0.002
    b.add("commit", dt)
    assert a.snapshot() == b.snapshot() == {"commit": dt}
    assert a.tracer.calls == b.tracer.calls
    assert a.hist.count(phase="commit") == b.hist.count(phase="commit") == 1


def test_count_books_a_total_and_nothing_else():
    """A count (pods, rows) rides in the snapshot beside the phases' seconds
    and is no interval: no histogram observation, no span."""
    acc = PhaseAccumulator(hist=_hist())
    acc.tracer = _TailTap()
    acc.count("wave.demoted", 3)
    acc.count("wave.demoted", 4)
    assert acc.snapshot() == {"wave.demoted": 7}
    assert acc.tracer.calls == [] and acc.hist.count(phase="wave.demoted") == 0
    assert PhaseAccumulator.diff(acc.snapshot(), {"wave.demoted": 3}) == {"wave.demoted": 4}


def test_begin_end_form_is_the_same_span():
    acc = PhaseAccumulator()
    sp = acc.span("device", bid=1).begin()
    time.sleep(0.001)
    dt = sp.end()
    assert acc.snapshot() == {"device": dt} and dt >= 0.001


def test_spans_nest():
    acc = PhaseAccumulator()
    acc.tracer = _TailTap()
    with acc.span("bind", bid=3, pods=4):
        with acc.span("bind.sink", bid=3):
            time.sleep(0.001)
        with acc.span("bind.tail", bid=3):
            pass
    snap = acc.snapshot()
    assert set(snap) == {"bind", "bind.sink", "bind.tail"}
    assert snap["bind.sink"] + snap["bind.tail"] <= snap["bind"]
    # inner spans close (and reach the tracer) before the outer one
    assert [n for n, _ in acc.tracer.calls] == ["bind.sink", "bind.tail", "bind"]


def test_span_books_its_interval_when_the_block_raises():
    acc = PhaseAccumulator()
    with pytest.raises(KeyError):
        with acc.span("commit"):
            time.sleep(0.001)
            raise KeyError("boom")
    assert acc.snapshot()["commit"] >= 0.001


def test_disabled_tracer_and_no_session_are_not_touched():
    class _Forbidden(_TailTap):
        def complete_tail(self, *a, **kw):  # pragma: no cover — must not run
            raise AssertionError("a disabled tracer was handed a span")

    acc = PhaseAccumulator()
    acc.tracer = _Forbidden(enabled=False)
    with acc.span("pack", bid=2):
        pass
    real = Tracer()  # never started: disabled
    acc.tracer = real
    with acc.span("pack", bid=2):
        pass
    assert real.stats()["events"] == 0
    assert set(acc.snapshot()) == {"pack"}
    # no profiler session is live: the annotation recorded nowhere
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()


# ---------------------------------------------------------------------------
# a small served drain under a CPU profiler session
# ---------------------------------------------------------------------------

N_TOO_LARGE = 3  # pods at the head of the served drain's queue that no node can hold

SUB_PHASES = (
    "bind.queue_wait", "bind.sink", "bind.lock_wait", "bind.tail",
    "queue_pop.lock_wait", "commit.lock_wait", "flush_binds", "loop.idle",
)


@pytest.fixture(scope="module")
def served_drain(tmp_path_factory):
    """API server over HTTP, reflectors, scheduling loop, binding workers;
    1,200 one-shape pods on 32 nodes, behind three that fit on none, drained
    under ``jax.profiler``.  Returns (phase totals, {event name: [stats
    dict, ...]} of the host planes, {event name: [(line, start ns, end
    ns), ...]}, the scheduler's phase histogram)."""
    import jax

    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.client import ApiClient, ApiServer, RemoteClusterSource
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.server import SchedulerServer
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    api = FakeCluster(pv_controller=False)
    apiserver = ApiServer(api).start()
    endpoint = f"http://127.0.0.1:{apiserver.port}"
    sched = Scheduler()
    source = RemoteClusterSource(endpoint)
    source.connect(sched)
    source.start()
    assert source.wait_for_sync(timeout=60.0)
    driver = ApiClient(endpoint)
    for i in range(32):
        driver.create_node(Node(
            name=f"n{i}",
            allocatable=Resource.from_map({"cpu": "64", "memory": "256Gi", "pods": "110"}),
        ))
    for i in range(N_TOO_LARGE):  # the head of the queue: 100 cpu on 64-cpu nodes
        driver.create_pod(Pod(
            name=f"big{i}", uid=f"default/big{i}",
            containers=[Container(name="c", requests={"cpu": "100", "memory": "64Mi"})],
        ))
    n_pods = 1200
    for i in range(n_pods):
        driver.create_pod(Pod(
            name=f"p{i}", uid=f"default/p{i}",
            containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})],
        ))
    deadline = time.monotonic() + 60.0
    while len(sched.queue) < n_pods + N_TOO_LARGE and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(sched.queue) == n_pods + N_TOO_LARGE
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    server = SchedulerServer(sched, poll_interval_s=0.005)
    try:
        server.start()
        while time.monotonic() < deadline:
            with sched._mu:
                if sched.metrics["scheduled"] >= n_pods:
                    break
            time.sleep(0.01)
        time.sleep(0.05)  # a poll or two on the empty queue
    finally:
        server.stop()
        sched.wait_for_bindings()
        jax.profiler.stop_trace()
        source.stop()
        apiserver.stop()
    assert sched.metrics["scheduled"] == n_pods
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events, spans = {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ktpu."):
                    events.setdefault(e.name, []).append(dict(e.stats))
                    spans.setdefault(e.name, []).append(
                        ((plane.name, line.name), e.start_ns, e.start_ns + e.duration_ns))
    return sched.phases.snapshot(), events, spans, sched.phases.hist


def test_every_sub_phase_appears_and_the_parts_of_bind_fit_in_bind(served_drain):
    phases, _, _, _ = served_drain
    for name in SUB_PHASES:
        assert name in phases, (name, sorted(phases))
    parts = phases["bind.sink"] + phases["bind.lock_wait"] + phases["bind.tail"]
    assert 0 < parts <= phases["bind"]
    assert phases["queue_pop.lock_wait"] <= phases["queue_pop"]
    assert phases["commit.lock_wait"] <= phases["commit"]


def test_profiler_session_holds_the_programs_spans(served_drain):
    _, events, _, _ = served_drain
    for name in ("ktpu.batch", "ktpu.commit", "ktpu.bind",
                 "ktpu.apiserver.POST.bindings", "ktpu.apiserver.lock_wait",
                 "ktpu.bind.sink", "ktpu.loop.idle", "ktpu.flush_binds"):
        assert name in events, (name, sorted(events))
    # a bind slice names the batch that produced it, and its pod count
    bids = {st["bid"] for st in events["ktpu.commit"]}
    for st in events["ktpu.bind"]:
        assert st["bid"] in bids and st["pods"] >= 1
    assert sum(st["pods"] for st in events["ktpu.bind"]) == 1200
    assert all("bid" in st for st in events["ktpu.batch"])
    # the ledger's dispatch call is a named span too
    assert any(n.startswith("ktpu.dispatch.") for n in events)


def test_post_filter_is_a_span_like_every_other_and_nests_inside_commit(served_drain):
    """The failure path of one pod (``Scheduler._post_filter_or_fail``): a
    total, a histogram observation and a ``ktpu.post_filter`` annotation an
    event, its lock wait split off as ``commit``'s is, each inside a
    ``ktpu.commit`` span of the loop's thread."""
    phases, events, spans, hist = served_drain
    assert 0 < phases["post_filter"] <= phases["commit"]
    assert phases["post_filter.lock_wait"] <= phases["post_filter"]
    assert hist.count(phase="post_filter") == hist.count(phase="post_filter.lock_wait") == N_TOO_LARGE
    assert len(events["ktpu.post_filter"]) == len(events["ktpu.post_filter.lock_wait"]) == N_TOO_LARGE
    assert all("bid" in st for st in events["ktpu.post_filter"])
    for line, t0, t1 in spans["ktpu.post_filter"]:
        assert any(ln == line and c0 <= t0 and t1 <= c1 for ln, c0, c1 in spans["ktpu.commit"])


def test_sched_unschedulable_is_a_count_not_an_interval(served_drain):
    """Booked where ``_handle_failure`` books ``metrics["unschedulable"]``:
    one a failed attempt; ``snapshot`` and ``diff`` carry it, and it has no
    histogram observation and no span."""
    phases, events, _, hist = served_drain
    assert phases["sched.unschedulable"] == N_TOO_LARGE
    assert PhaseAccumulator.diff(phases, {"sched.unschedulable": 1.0})["sched.unschedulable"] == N_TOO_LARGE - 1
    assert hist.count(phase="sched.unschedulable") == 0
    assert "ktpu.sched.unschedulable" not in events


# ---------------------------------------------------------------------------
# a wave's conflicts by kind (counts; ``Scheduler._wave_resolve``)
# ---------------------------------------------------------------------------

def _followers_and_repellers():
    """Two pods that repel each other by a REQUIRED hostname anti-affinity
    term (one ``affinity`` conflict: both speculate the first node) ahead of
    three that REQUIRE a peer of theirs in the zone and are infeasible until
    one has committed (three upgrades, which are demotions and no conflict)."""
    from kubernetes_tpu.api.types import (
        Affinity, Container, LabelSelector, Pod, PodAffinity, PodAffinityTerm, PodAntiAffinity,
    )
    from kubernetes_tpu.tools import paritycheck as pc

    web = LabelSelector(match_labels={"app": "web"})
    apart = Affinity(pod_anti_affinity=PodAntiAffinity(required_during_scheduling_ignored_during_execution=(
        PodAffinityTerm(topology_key="kubernetes.io/hostname", label_selector=web),)))
    beside = Affinity(pod_affinity=PodAffinity(required_during_scheduling_ignored_during_execution=(
        PodAffinityTerm(topology_key="topology.kubernetes.io/zone", label_selector=web),)))

    def pod(name, app, affinity):
        return Pod(name=name, labels={"app": app}, affinity=affinity,
                   containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})])

    pods = [pod(f"web-{i}", "web", apart) for i in range(2)]
    return pc._basic_nodes(8, zones=4), pods + [pod(f"follower-{i}", "f", beside) for i in range(3)]


def _mixed_cross_pod():
    from kubernetes_tpu.tools import paritycheck as pc

    return pc._basic_nodes(32, zones=4), pc._cross_pod_pods(96)


WAVE_DRAINS = {"followers-and-repellers": (_followers_and_repellers, {}),
               "mixed-cross-pod-chained": (_mixed_cross_pod, {"batch_size": 16})}


@pytest.fixture(scope="module", params=sorted(WAVE_DRAINS))
def wave_drain(request):
    """(the scheduler after one drain on the wave path, the drain's name)."""
    from kubernetes_tpu.tools import paritycheck as pc

    build, cfg_kw = WAVE_DRAINS[request.param]
    _got, s = pc._drain(*build(), return_sched=True, mesh_dispatch=False, **cfg_kw)
    assert s.metrics["wave_batches"] >= 1
    return s, request.param


def test_wave_resolve_books_each_conflict_kind_beside_the_prometheus_counter(wave_drain):
    from kubernetes_tpu.ops.wave import DEMOTE_KINDS

    s, _name = wave_drain
    phases = s.phases.snapshot()
    booked = {k[len("wave.conflicts."):]: v for k, v in phases.items() if k.startswith("wave.conflicts.")}
    assert booked and set(booked) <= set(DEMOTE_KINDS.values())
    assert booked == {k: n for k in DEMOTE_KINDS.values() if (n := s.prom.wave_conflicts.value(kind=k))}


def test_the_conflict_kinds_sum_to_the_demotions_less_the_upgrades(wave_drain):
    """An upgrade (infeasible alone, placed once a wave peer committed) is a
    demotion — the admitted node is not the speculated one — and no conflict."""
    s, name = wave_drain
    phases = s.phases.snapshot()
    events = [e["kind"] for e in s.flight.tail(100000)]
    upgrades = events.count("wave_upgraded")
    conflicts = sum(v for k, v in phases.items() if k.startswith("wave.conflicts."))
    assert conflicts == phases["wave.demoted"] - upgrades == events.count("wave_demoted") > 0
    if name == "followers-and-repellers":
        assert (upgrades, phases["wave.conflicts.affinity"]) == (3, 1)


def test_a_conflict_kind_is_a_count_no_span_and_no_histogram_and_diff_carries_it(wave_drain):
    """A span books a histogram observation an event; a count books none."""
    s, _name = wave_drain
    phases = s.phases.snapshot()
    kinds = [k for k in phases if k.startswith("wave.conflicts.")]
    for name in kinds + ["wave.demoted"]:
        assert s.phases.hist.count(phase=name) == 0
    assert s.phases.hist.count(phase="wave_resolve") >= 1  # the interval around them is a span
    # what the benchmark's ``phases`` line holds: a kind that fired, whole; one
    # that did not is absent, as every zero is
    window = PhaseAccumulator.diff(phases, {k: 0.0 for k in phases})
    assert {k: window[k] for k in kinds} == {k: phases[k] for k in kinds}
    assert not [k for k in window if k.startswith("wave.conflicts.") and not window[k]]


# ---------------------------------------------------------------------------
# stage names on the device ops (jax.named_scope: metadata only)
# ---------------------------------------------------------------------------

_POD_STEP = ("ktpu/gang/filter", "ktpu/gang/score", "ktpu/gang/select", "ktpu/gang/commit")
_CONSTRAINTS = ("ktpu/gang/spread_constraints", "ktpu/gang/interpod_constraints")
_WAVE = ("ktpu/gang/precompute", "ktpu/wave/speculation", "ktpu/wave/admission") + _POD_STEP + _CONSTRAINTS
ROOT_STAGES = {
    "chain.chain_dispatch": _WAVE + ("ktpu/chain/append",),
    "gang.gang_run": ("ktpu/gang/precompute", "ktpu/gang/heavy_parts") + _POD_STEP + _CONSTRAINTS,
    "wave.wave_run": _WAVE,
    "resident.resident_run": ("ktpu/resident/round",),
}


@pytest.fixture(scope="module")
def dispatched_specs():
    """{root: (args, kwargs) of ShapeDtypeStructs + statics} from tiny CPU
    drains, through the ledger's retained buckets."""
    from kubernetes_tpu.tools import paritycheck as pc

    specs = {}

    def harvest(s):
        for name, ks in s.kernels._kstats.items():
            for b in ks.buckets.values():
                if b["spec"] is not None:
                    specs.setdefault(name, b["spec"])

    def drain(nodes, pods, **cfg):
        _, s = pc._drain(nodes, pods, return_sched=True, mesh_dispatch=False, **cfg)
        harvest(s)

    drain(pc._basic_nodes(64), pc._basic_pods(2048))  # resident_run
    drain(pc._basic_nodes(16, zones=4), pc._cross_pod_pods(16))  # wave_run
    drain(pc._basic_nodes(16, zones=4), pc._cross_pod_pods(16), wave_dispatch=False)  # gang_run
    drain(pc._basic_nodes(32, zones=4), pc._cross_pod_pods(96), batch_size=16)  # chain_dispatch
    _, s = pc._drain_workloads(*pc._gang_workload(8, 4))  # workloads_run
    harvest(s)
    return specs


@pytest.mark.parametrize("root", sorted(ROOT_STAGES))
def test_lowered_root_carries_its_stage_names(root, dispatched_specs):
    from kubernetes_tpu.observability import kernels
    from kubernetes_tpu.ops.common import STAGES

    assert root in dispatched_specs, sorted(dispatched_specs)
    args, kwargs = dispatched_specs[root]
    text = kernels._wrapped_fn(root).lower(*args, **kwargs).as_text(debug_info=True)
    found = set(re.findall(r"ktpu/[a-z_]+/[a-z_]+", text))
    assert set(ROOT_STAGES[root]) <= found, sorted(set(ROOT_STAGES[root]) - found)
    assert found <= set(STAGES), sorted(found - set(STAGES))


def test_the_statics_by_signature_add_no_op_outside_a_stage():
    """``gang.precompute`` by signature (PR 38): the gather of the
    representative rows and the expansion back to [P, …] are the first and
    the last thing INSIDE ``ktpu/gang/precompute``, so the compiled program
    with the table names no op outside a stage that the per-pod program does
    not name too (its two arguments apart) and ``kernels.scoped_share``
    stays where it was."""
    from tests.test_statics_signatures import lowered_wave_runs

    plain, _, tabled = lowered_wave_runs()

    def op_names(lowered):
        return collections.Counter(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))

    per_pod, by_sig = op_names(plain), op_names(tabled)
    unstaged = {n for n in by_sig if "ktpu/" not in n and n not in per_pod}
    assert unstaged <= {"sig", "rep_pod"}, sorted(unstaged)

    # the expansion itself is there, as selects under the stage
    def selects(names):
        return sum(k for n, k in names.items() if n.endswith("ktpu/gang/precompute/select_n"))

    assert selects(by_sig) > selects(per_pod)


# every root whose program holds gang.pod_step (scan, speculation, admission)
POD_STEP_ROOTS = (
    "chain.chain_dispatch",
    "coscheduling.workloads_run",
    "gang.gang_run",
    "wave.wave_run",
)


@pytest.mark.parametrize("root", POD_STEP_ROOTS)
def test_pod_step_root_divides_no_int64_with_a_minor_axis_of_two(root, dispatched_specs):
    """The scan step keeps the node axis minor (ops/gang.py docstring): an
    emulated int64 division over [..., N, 2] pays for the tiles of
    [..., N, 128] on the chip (PERF.md, PR 28)."""
    from kubernetes_tpu.observability import kernels

    assert root in dispatched_specs, sorted(dispatched_specs)
    args, kwargs = dispatched_specs[root]
    text = kernels._wrapped_fn(root).lower(*args, **kwargs).as_text()
    divides = re.findall(r"stablehlo\.divide[^\n]*", text)
    assert any(d.rstrip().endswith("xi64>") for d in divides), "no int64 division found at all"
    two_lane = [d for d in divides if re.search(r"x2xi64>\s*$", d)]
    assert not two_lane, two_lane


def test_the_four_roots_cover_every_listed_stage_but_the_fastpath_ones():
    from kubernetes_tpu.ops.common import STAGES

    covered = {s for names in ROOT_STAGES.values() for s in names}
    assert set(STAGES) - covered == {"ktpu/fastpath/sig_step", "ktpu/fastpath/static_eval"}
