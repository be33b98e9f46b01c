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


# ---------------------------------------------------------------------------
# the loop's pacing intervals divided (PR 39): the parts of ``chain_dispatch``
# and of ``commit``, and each loop span's off-CPU seconds
# ---------------------------------------------------------------------------

CHAIN_PARTS = ("lock_wait", "repack", "pack", "sync", "prefilter", "h2d", "tables", "submit", "release")
COMMIT_PARTS = ("requests", "assume", "outcomes")
# the cells whose batches take the chained dispatch: the three whose pods carry a
# cross-pod constraint, and (PR 41) the one whose plain pods land on a base of
# term-carrying pods and are sent there by the fast gate's count, and (PR 48)
# the one whose pods carry the default soft spread constraints of many Deployments
CROSS_POD_CELLS = ["spread-5k.backlog", "interpod-5k.backlog", "antiaffinity-5k.backlog",
                   "mixedbase-5k.backlog-on-base", "cl2load-5k.backlog-of-deployments"]
ALL_CELLS = ["basic-5k.backlog", "spread-5k.backlog", "interpod-5k.backlog",
             "unsched-5k.backlog-pending-first", "antiaffinity-5k.backlog", "mixedbase-5k.backlog-on-base",
             "northstar-10k.backlog",  # PR 44: the resident set's lists gained the north star's cell
             "cl2load-5k.backlog-of-deployments"]
TOP_LEVEL_LOOP_SPANS = ("queue_pop", "chain_dispatch", "pack", "h2d", "commit", "wave_resolve",
                        "resident_rounds", "flush_binds")
# metric -> (the phases its data file names, the cells BENCHMARK.json lists it for)
NEW_PHASE_METRICS = {
    "loop.chain_prep_s_per_kpod.backlog": (["chain_dispatch"], CROSS_POD_CELLS),
    **{f"loop.chain_prep_s_per_kpod.{p}.backlog": ([f"chain_dispatch.{p}"], CROSS_POD_CELLS) for p in CHAIN_PARTS},
    **{f"loop.commit_s_per_kpod.{p}.backlog": ([f"commit.{p}"], ALL_CELLS) for p in COMMIT_PARTS},
    "loop.off_cpu_s_per_kpod.backlog": ([f"{p}.off_cpu" for p in TOP_LEVEL_LOOP_SPANS], ALL_CELLS),
}
IDLE_UNDER_PREP = "device.idle_under_chain_prep_s_per_kpod.backlog"
# the two parts of ``chain_dispatch`` that hand the interpreter lock away
OFF_CPU_PARTS = ("h2d", "release")
PART_OFF_CPU_METRICS = {
    f"loop.chain_prep_off_cpu_s_per_kpod.{p}.backlog": ([f"chain_dispatch.{p}.off_cpu"], CROSS_POD_CELLS)
    for p in OFF_CPU_PARTS
}


def _cpu_clock_tick():
    """The largest step the thread's CPU clock takes, probed: under gVisor it
    advances 10 ms at a time whatever ``get_clock_info`` says, and a span's
    signed ``off_cpu`` is then off by up to a tick either side."""
    tick = time.get_clock_info("thread_time").resolution
    for _ in range(3):
        c0 = time.thread_time()
        while True:
            c1 = time.thread_time()
            if c1 != c0:
                break
        tick = max(tick, c1 - c0)
    return tick


@pytest.fixture(scope="module")
def chained_drain(tmp_path_factory):
    """The chained wave drain (six batches of 16: the first dispatched
    directly, five through ONE chain) under ``jax.profiler``.  Returns (the
    scheduler, {event name: [(line, start ns, end ns), ...]})."""
    import jax
    from jax.profiler import ProfileData

    from kubernetes_tpu.tools import paritycheck as pc

    build, cfg_kw = WAVE_DRAINS["mixed-cross-pod-chained"]
    trace_dir = str(tmp_path_factory.mktemp("xplane_chain"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        _got, s = pc._drain(*build(), return_sched=True, mesh_dispatch=False, **cfg_kw)
    finally:
        jax.profiler.stop_trace()
    assert s.metrics["wave_batches"] >= 2
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        # a thread is a LINE of the host plane; the lines share one name
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ktpu."):
                    spans.setdefault(e.name, []).append(
                        ((plane.name, i), e.start_ns, e.start_ns + e.duration_ns))
    return s, spans


def test_every_part_of_chain_dispatch_appears_and_one_chain_start_is_one_sync(chained_drain):
    s, _ = chained_drain
    phases, hist = s.phases.snapshot(), s.phases.hist
    dispatches = hist.count(phase="chain_dispatch")
    assert dispatches >= 2
    for part in CHAIN_PARTS:
        assert f"chain_dispatch.{part}" in phases, (part, sorted(phases))
    # a steady batch has no ``sync``: the drain started ONE chain
    assert hist.count(phase="chain_dispatch.sync") == 1
    for part in ("lock_wait", "repack", "prefilter", "h2d", "tables", "submit", "release"):
        assert hist.count(phase=f"chain_dispatch.{part}") == dispatches, part
    # interning + epoch before the repack, ``pack_pod_batch`` after it
    assert hist.count(phase="chain_dispatch.pack") == 2 * dispatches


def test_the_parts_of_chain_dispatch_fit_in_it_and_cover_most_of_it(chained_drain):
    s, _ = chained_drain
    phases = s.phases.snapshot()
    parts = sum(phases[f"chain_dispatch.{p}"] for p in CHAIN_PARTS)
    assert 0.8 * phases["chain_dispatch"] <= parts <= phases["chain_dispatch"]


def test_the_parts_of_commit_fit_in_it(chained_drain, served_drain):
    for phases in (chained_drain[0].phases.snapshot(), served_drain[0]):
        for part in COMMIT_PARTS:
            assert phases[f"commit.{part}"] > 0, part
        parts = sum(phases[f"commit.{p}"] for p in COMMIT_PARTS) + phases["commit.lock_wait"]
        assert parts <= phases["commit"]
    # one set a contiguous RUN of placed pods, never one a pod: the served
    # drain's 1,200 pods are one run a batch
    _, _, _, hist = served_drain
    for part in COMMIT_PARTS:
        assert hist.count(phase=f"commit.{part}") == hist.count(phase="commit.lock_wait") < 40


def test_the_parts_of_a_bulk_commit_cover_most_of_it(served_drain):
    """Where ``commit`` is the bulk commit (1,200 pods in a few runs; the
    three failing pods' ``post_filter`` is the rest of the span)."""
    phases, _, _, _ = served_drain
    parts = sum(phases[f"commit.{p}"] for p in COMMIT_PARTS) + phases["commit.lock_wait"]
    assert parts + phases["post_filter"] >= 0.5 * phases["commit"]


def test_the_parts_are_events_on_the_loops_thread_inside_chain_dispatch(chained_drain):
    _, spans = chained_drain
    outer = spans["ktpu.chain_dispatch"]
    loop_thread, = {line for line, _, _ in spans["ktpu.batch"]}
    assert {line for line, _, _ in outer} == {loop_thread}
    assert {line for line, _, _ in spans["ktpu.bind"]} - {loop_thread}  # workers have lines of their own
    for part in CHAIN_PARTS:
        inner = spans[f"ktpu.chain_dispatch.{part}"]
        for line, t0, t1 in inner:
            assert any(ln == line and o0 <= t0 and t1 <= o1 for ln, o0, o1 in outer), part
    # consecutive and disjoint: no part's event overlaps another's (``release``
    # is made by the caller and begun at the callee's return: its event opens
    # where the span begins, not where it was made)
    parts = sorted((t0, t1) for p in CHAIN_PARTS for _, t0, t1 in spans[f"ktpu.chain_dispatch.{p}"])
    assert all(a1 <= b0 for (_, a1), (b0, _) in zip(parts, parts[1:]))
    # the ledger's dispatch annotation sits inside ``submit``
    submits = spans["ktpu.chain_dispatch.submit"]
    for line, t0, t1 in spans["ktpu.dispatch.chain.chain_dispatch"]:
        assert any(ln == line and o0 <= t0 and t1 <= o1 for ln, o0, o1 in submits)
    for part in COMMIT_PARTS:
        for line, t0, t1 in spans[f"ktpu.commit.{part}"]:
            assert any(ln == line and o0 <= t0 and t1 <= o1 for ln, o0, o1 in spans["ktpu.commit"]), part


def test_a_span_around_a_sleep_books_its_wall_as_off_cpu():
    acc = PhaseAccumulator(hist=_hist())
    tick = _cpu_clock_tick()
    with acc.span("h2d", off_cpu=True, bid=1):
        time.sleep(0.05)
    snap = acc.snapshot()
    assert set(snap) == {"h2d", "h2d.off_cpu"}
    assert 0.04 - tick <= snap["h2d.off_cpu"] <= snap["h2d"] + tick


def test_a_span_around_a_busy_loop_books_no_more_than_the_wall_it_did_not_run():
    """0 on an idle machine (to a tick of the CPU clock, either side: the
    count is signed); on a loaded one whatever the OS took, which is never
    the CPU the loop itself burned."""
    acc = PhaseAccumulator(hist=_hist())
    tick = _cpu_clock_tick()
    sp = acc.span("pack", off_cpu=True, bid=1).begin()
    c0, t0 = time.thread_time(), time.perf_counter()
    while time.perf_counter() - t0 < 0.05:
        pass
    burned = time.thread_time() - c0
    dt = sp.end()
    off = acc.snapshot()["pack.off_cpu"]
    assert dt >= 0.05 and -(tick + 1e-3) <= off <= dt - burned + tick + 1e-3


def test_a_span_not_asked_for_its_cpu_clock_books_no_off_cpu():
    """Binding workers open their spans on the accumulator directly: their
    wall is a wait on the sink by design."""
    acc = PhaseAccumulator(hist=_hist())
    with acc.span("bind.sink", bid=1):
        time.sleep(0.01)
    assert set(acc.snapshot()) == {"bind.sink"}


def test_off_cpu_is_a_count_no_histogram_observation_and_diff_carries_it():
    acc = PhaseAccumulator(hist=_hist())
    acc.tracer = _TailTap()
    for _ in range(2):
        with acc.span("queue_pop", off_cpu=True):
            time.sleep(0.01)
    snap = acc.snapshot()
    assert acc.hist.count(phase="queue_pop") == 2 and acc.hist.count(phase="queue_pop.off_cpu") == 0
    assert [n for n, _ in acc.tracer.calls] == ["queue_pop", "queue_pop"]
    before = {"queue_pop": snap["queue_pop"] / 2, "queue_pop.off_cpu": snap["queue_pop.off_cpu"] / 2}
    window = PhaseAccumulator.diff(snap, before)
    assert window["queue_pop.off_cpu"] == pytest.approx(snap["queue_pop.off_cpu"] / 2)
    # the count is signed (a coarse CPU clock's tick errors cancel in the
    # sum): a window in which it fell keeps its negative delta
    fell = PhaseAccumulator.diff({"pack": 2.0, "pack.off_cpu": -0.004}, {"pack": 2.0, "pack.off_cpu": 0.001})
    assert fell == {"pack.off_cpu": pytest.approx(-0.005)}


def test_only_the_loop_spans_a_metric_reads_carry_off_cpu(served_drain, chained_drain):
    from kubernetes_tpu.scheduler import Scheduler

    # what the metrics sum, and nothing else
    assert Scheduler._OFF_CPU_SPANS == set(TOP_LEVEL_LOOP_SPANS) | {f"chain_dispatch.{p}" for p in OFF_CPU_PARTS}
    tick = _cpu_clock_tick()
    s = chained_drain[0]
    for phases, hist in ((served_drain[0], served_drain[3]), (s.phases.snapshot(), s.phases.hist)):
        booked = {k[: -len(".off_cpu")] for k in phases if k.endswith(".off_cpu")}
        # not the binding workers' spans, not the waits by design (device,
        # d2h, loop.idle, the lock_waits), not the other nested parts
        assert booked == {n for n in Scheduler._OFF_CPU_SPANS if n in phases}
        for name in booked:
            # signed: each span is off by up to a tick of the CPU clock
            slack = hist.count(phase=name) * tick + 1e-3
            assert -slack <= phases[name + ".off_cpu"] <= phases[name] + slack, name
    assert {"chain_dispatch", "chain_dispatch.h2d", "chain_dispatch.release"} <= booked
    assert not any(name.endswith(".off_cpu") for name in served_drain[1])  # no annotation


def test_the_route_and_the_gates_reason_are_counts_no_span_and_no_histogram(chained_drain):
    """``route.<route>`` and ``fast_gate.refused.<reason>`` (PR 41) go through
    ``PhaseAccumulator.count``: totals that ``snapshot`` and ``diff`` carry,
    with no histogram observation and no profiler event; every pod the loop
    popped is booked under exactly one route."""
    s, spans = chained_drain
    phases = s.phases.snapshot()
    booked = {k: v for k, v in phases.items() if k.startswith(("route.", "fast_gate.refused."))}
    routes = {k: v for k, v in booked.items() if k.startswith("route.")}
    assert set(routes) == {"route.direct", "route.chained"}  # the first batch direct, the rest through the chain
    assert sum(routes.values()) == s.metrics["schedule_attempts"] == 96
    assert all(v <= sum(routes.values()) for k, v in booked.items() if k.startswith("fast_gate."))
    for name in booked:
        assert s.phases.hist.count(phase=name) == 0
    assert not [n for n in spans if n.startswith(("ktpu.route", "ktpu.fast_gate"))]
    assert PhaseAccumulator.diff(phases, {k: 0.0 for k in phases}).items() >= booked.items()


def test_the_route_is_booked_once_a_batch_and_holds_the_pods_a_fast_batch_pulled_in():
    """One ``count`` a batch, never one a pod: a fast batch that extends
    itself from the queue (16 popped, 32 pulled in) is ONE booking of 48."""
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.framework.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler import Scheduler

    cfg = SchedulerConfiguration()
    cfg.batch_size = 16
    sched = Scheduler(configuration=cfg)
    sched.binding_sink = lambda pod, node: None
    for i in range(8):
        sched.on_node_add(Node(name=f"n{i}", capacity=Resource.from_map({"cpu": "64", "memory": "64Gi", "pods": 110})))
    for i in range(48):
        sched.on_pod_add(Pod(name=f"p{i}", containers=[Container(name="c", requests={"cpu": "100m"})]))
    calls = []
    count = sched.phases.count
    sched.phases.count = lambda name, n: (calls.append((name, n)), count(name, n))[1]
    outs = sched.schedule_pending()
    assert len(outs) == 48 and all(o.node for o in outs)
    assert [c for c in calls if c[0].startswith(("route.", "fast_gate."))] == [("route.fast", 48)]


def test_every_new_metric_is_listed_for_its_cells_and_reads_a_number(chained_drain):
    import json

    from benchmarks import cells

    s, _ = chained_drain
    bench = json.load(open(os.path.join(os.path.dirname(__file__), "..", "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    window = PhaseAccumulator.diff(s.phases.snapshot(), {})
    ctx = {"phases": window, "pods_in_window": 96}
    by_cell = {c: {sp["name"]: sp for sp in cells.layer_metrics(c, bench)} for c in ALL_CELLS}
    tick = _cpu_clock_tick()
    for name, (phases, listed) in {**NEW_PHASE_METRICS, **PART_OFF_CPU_METRICS}.items():
        e = entries[name]
        assert e["workloads"] == listed and e["moves"] == "pods_per_s" and e["better"] == "lower", name
        assert (e["unit"], e["source"], e["layer"]) == ("s/kpod", "program_span", "scheduling loop"), name
        assert [c for c in ALL_CELLS if name in by_cell[c]] == listed, name
        spec = by_cell[listed[0]][name]
        assert spec["reader"] == "phase" and spec["params"]["phases"] == phases, name
        value = spec["read"](ctx, spec["params"])
        assert isinstance(value, float), name
        assert value == pytest.approx(sum(window.get(p, 0.0) for p in phases) / 0.096), name
        # seconds and their parts are never negative; an off-CPU count is
        # signed, to a tick of the CPU clock a span
        assert value >= (-(100 * tick + 1e-3) / 0.096 if "off_cpu" in name else 0.0), name
        # a program without the span (the parent) reads 0.0 and raises nothing
        assert spec["read"]({"phases": {"commit": 1.0}, "pods_in_window": 5000}, spec["params"]) == 0.0
    e = entries[IDLE_UNDER_PREP]
    assert e["workloads"] == CROSS_POD_CELLS and (e["source"], e["layer"]) == ("device_trace", "device")
    spec = by_cell["spread-5k.backlog"][IDLE_UNDER_PREP]
    assert spec["reader"] == "xspan"
    assert spec["params"] == {"what": "idle_overlap_s", "spans": ["ktpu.chain_dispatch"]}
    # appended together, in this order, after everything the benchmark had
    new = list(NEW_PHASE_METRICS) + [IDLE_UNDER_PREP] + list(PART_OFF_CPU_METRICS)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(new[0])
    assert first >= 33 and names[first:first + len(new)] == new
    # loop.off_cpu is at most the spans it is taken over
    off = by_cell["basic-5k.backlog"]["loop.off_cpu_s_per_kpod.backlog"]
    assert off["read"](ctx, off["params"]) <= sum(window.get(p, 0.0) for p in TOP_LEVEL_LOOP_SPANS) / 0.096 + 1e-9
