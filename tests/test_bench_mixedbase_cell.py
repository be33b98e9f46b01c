"""``mixedbase-5k.backlog-on-base`` at cut counts, whole, on the CPU: the
configuration ``benchmarks/configs/sched-perf-mixedbase-5k.json`` (upstream's
``:615`` MixedSchedulingBasePod) under the traffic kind ``backlog_on_base``
through ``runner.run_cell``, EVERY position of the window compared with the
frozen reference (``benchmarks/reference/``).

What the cell is there to see is pinned here: plain pods are scheduled onto a
base of five templates, four of which carry one inter-pod term each (required
zone affinity, required hostname anti-affinity, preferred hostname affinity
and anti-affinity), none of which admits a measured pod.  The base pods are
planted by the traffic kind, bound where the seeded order puts them, held by
``correct`` as init pods are (in the store, both required terms recounted WITH
the terms present) and never popped.  The counts are cut by hand
(``cells.cut`` cuts three; the four base groups are cut here) to a size at
which MORE THAN 64 placed pods carry a term: past the count at which the
fast gate used to give up without asking (``fast_gate.refused.term_count``,
every batch to the chained scan, until PR 42).  The gate now asks the cache's
registry of DISTINCT placed terms — four, whatever the count — finds none
filed under a label the plain pods carry, and the CPU run takes the route the
chip run takes: ``route.fast``, ONE ``resident_run`` for the window, no
``fast_gate.*`` count, which the three per-layer metrics of the router read.

Identity is blind to the base pods' terms by construction (no term admits a
measured pod: the reference with the terms stripped decides the same), so a
control stands in for what the window cannot show: ONE extra pod that the
required anti-affinity term DOES admit (``color=green`` in ``sched-1``) never
lands beside a green base pod, and the reference agrees.
"""

import collections
import contextlib
import io
import time
import types

import pytest

from benchmarks import cells, runner, workload
from benchmarks.control_terms import TermControls
from tests.test_bench_antiaffinity_cell import _notes  # part.name -> the note of every ``correct`` line
from tests.test_bench_unsched_cell import _watch  # the window's own phase totals and the store as read back

CELL = "mixedbase-5k.backlog-on-base"
NODES, PODS, GROUP, BATCH = 50, 96, 20, 32  # five groups of 20 on 50 nodes: two base pods a node, as at the source's counts
BASE = ("base_affinity", "base_anti_affinity", "base_preferred_affinity", "base_preferred_anti_affinity")
TERM_PODS = len(BASE) * GROUP
NEW_METRICS = ("loop.route_chained_per_kpod.backlog", "loop.fast_gate_term_count_refused_per_kpod.backlog",
               "loop.fast_gate_probes_asked_per_kpod.backlog")
# what BENCHMARK.json does not list for this cell: the five that only a wave writes, the
# bind tasks of the cross-pod cells' batches (PR 50; this cell's window is one resident run),
# and the two over the API server's bulk-binding handler (while the chained scan decided
# the cell, until PR 42, its binds were a POST a pod and the xspan reader found no span;
# they read again now, and listing the cell there is a benchmark PR's: ROADMAP.md)
NOT_HERE = {f"{m}.backlog" for m in (
    "kernels.stage_ms_per_kpod.admission", "kernels.stage_ms_per_kpod.speculation", "loop.wave_demoted_per_kpod",
    "loop.wave_conflicts_affinity_per_kpod", "loop.wave_static_sigs_per_kpod", "served.bind_tasks_per_kpod",
    "served.apiserver_bindings_s_per_kpod", "served.apiserver_lock_wait_s_per_kpod")}
# the control's seed: the first node in node order holds a green base pod, so
# a pod that ignored the term would land beside it (asserted below)
GREEN_SEED = 4100000015


def _cell(bench):
    cell = cells.cut(cells.cell(CELL, bench), NODES, PODS, GROUP)
    for g in BASE:
        cell["config"][g]["count"] = GROUP
    return cell


def _small_batches(cluster):
    """The loop's sizes cut with the counts: a pop batch of 32 (of 512), and
    the device engine of the fast path from 32 pods on (from 1,024), so the
    96 pods ride ONE ``resident_run`` as the 5,000 do on the chip."""
    cluster.sched.config.batch_size = BATCH
    cluster.sched.config.fast_device_min = BATCH


def _base_of(cell, seed):
    return cell["kind"].plan(cell["config"], cell["traffic"], seed, 120.0)["base"]


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    seen = {"decided": []}
    controls = TermControls([1])
    cell = _cell(bench)

    def watch(cluster):
        _small_batches(cluster)
        _watch(seen)(cluster)

    def at_position(replay, pos, spec, decided, want):
        seen["decided"].append(decided)
        controls(replay, pos, spec, decided, want)

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        res = runner.run_cell(
            cell, bench, 4100000007, 120.0, False, time.perf_counter(), require_chip=False,
            tamper=watch, identity_positions=list(range(PODS)), on_identity_position=at_position,
        )
    cluster = seen.pop("cluster")  # the scheduler itself is let go
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    seen["notes"] = _notes(out.getvalue())
    seen["controls"] = controls.readings()
    seen["acked"] = cluster.snapshot_acked()
    seen["popped"] = cluster.snapshot_order()[1]
    seen["term_pods"] = cluster.sched.cache.n_term_pods
    seen["distinct_terms"] = sorted(ent[1] for ent in cluster.sched.cache.term_probes._entries.values())
    seen["batches"] = {k: cluster.sched.metrics.get(k, 0) for k in ("chain_batches", "wave_batches")}
    seen["bound_before"] = list(zip(cluster.init_specs, cluster.init_nodes))
    seen["base"] = _base_of(cell, 4100000007)
    return res, seen, bench


def test_the_cut_cell_runs_whole_binds_every_measured_pod_and_is_correct(run):
    res, _seen, _bench = run
    assert res["attempted"] == PODS and res["failed"] == 0
    assert set(res["metrics"]) == {"pods_per_s", "setup_s"}
    assert res["compared"]["guarantee.decisions_not_read_back"]["value"] == 0
    assert res["correct"] is True, {k: v for k, v in res["compared"].items() if not v["ok"]}


def test_the_program_equals_the_frozen_reference_at_every_position(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["identity.positions_compared"]["value"] == PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert len(seen["decided"]) == PODS and all(seen["decided"])


def test_more_than_64_placed_pods_carry_a_term_and_the_gate_still_asks(run):
    """The gate's shortcut looked at the count of placed term-carrying pods;
    at the source's counts it is 8,000, here 80: past 64 either way.  What
    the gate asks instead is the DISTINCT terms: four, one a template, each
    held by a group's pods (2,000 each at the source's counts)."""
    _res, seen, _bench = run
    assert seen["term_pods"] == TERM_PODS > 64
    assert seen["distinct_terms"] == [GROUP] * len(BASE)


@pytest.mark.parametrize("counters", ["route", "fast_gate"])
def test_every_pod_of_the_window_takes_the_fast_route_and_the_gate_refuses_none(run, counters):
    _res, seen, _bench = run
    window = seen["window"]
    if counters == "route":
        assert {k: v for k, v in window.items() if k.startswith("route.")} == {"route.fast": PODS}
    else:
        # not refused, and nothing asked: the measured pods carry no label, so no
        # placed term is a candidate for them (all four are filed under color=…)
        assert [k for k in window if k.startswith("fast_gate.")] == []


def test_the_window_is_one_resident_run_not_a_chained_dispatch_and_compiles_nothing(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]
    # expect_kernels is any-of: it holds through the OTHER name since PR 42
    assert "dispatched ['resident.resident_run']" in seen["notes"]["device.dispatches_of_the_cells_kernels"]
    assert got["device.breaker_faults"]["value"] == got["device.device_faults_logged"]["value"] == 0
    window = seen["window"]
    assert not [k for k in window if k.startswith(("wave", "chain_dispatch"))]
    assert window["resident_rounds"] >= 0.0 and "commit.assume" in window  # the bulk commit's parts are on
    # the warm-up and the window alike: no chained dispatch, no wave
    assert seen["batches"] == {"chain_batches": 0, "wave_batches": 0}


def test_the_base_pods_are_in_the_store_bound_where_planted_and_never_popped(run):
    _res, seen, _bench = run
    base = seen["base"]
    assert len(base) == TERM_PODS
    assert [s["name"] for s, _n in base[::GROUP]] == \
        ["base-affinity-0", "base-anti-affinity-0", "base-preferred-affinity-0", "base-preferred-anti-affinity-0"]
    assert all(seen["store"][workload.uid_of(s)] == n for s, n in base)
    uids = {workload.uid_of(s) for s, _n in base}
    assert not uids & set(seen["popped"]) and not uids & set(seen["acked"])
    assert len(seen["popped"]) == PODS == len(seen["acked"])
    # correct held them as init pods: the harness's own group first, then the planted ones in order
    assert seen["bound_before"][GROUP:] == base and len(seen["bound_before"]) == GROUP + TERM_PODS
    per_node = collections.Counter(n for _s, n in seen["bound_before"])
    assert len(per_node) == NODES and set(per_node.values()) == {2}  # two base pods a node
    green = [n for s, n in base if s["labels"].get("color") == "green"]
    assert len(set(green)) == GROUP  # the required anti-affinity group on distinct nodes


@pytest.mark.parametrize("check", ["required_anti_affinity_violations", "required_affinity_unmet"])
def test_both_required_terms_are_recounted_over_base_and_measured_pods_with_the_term_present(run, check):
    res, seen, _bench = run
    assert res["compared"][f"feasibility.{check}"] == {"value": 0, "limit": 0, "ok": True}
    assert seen["notes"][f"feasibility.{check}"] == "(0 of 1 terms)"  # not "0 of 0": a term to count
    assert res["compared"]["feasibility.overcommitted_node_resources"]["ok"]
    assert f"{GROUP + TERM_PODS + PODS} placed pods" in seen["notes"]["feasibility.overcommitted_node_resources"]


@pytest.mark.parametrize("control,differs_at", [
    ("both_stripped", 0), ("incoming_stripped", 0), ("stale_lag1", PODS - 1),
], ids=["the-reference-without-the-terms-differs-nowhere", "incoming-only-stripped-differs-nowhere",
        "one-commit-stale-differs-at-every-position-but-the-first"])
def test_controls_identity_is_blind_to_the_base_terms_and_sees_a_stale_decision(run, control, differs_at):
    """No term admits a measured pod, so stripping the pod's labels changes
    nothing the placed terms see (``both_stripped``); a measured pod carries
    no term of its own (``incoming_stripped``).  A one-commit-stale decision
    takes the node the pod before it took (every node holds two base pods;
    the emptiest node in node order wins) at every position but the
    window's first, which has nothing to be stale about."""
    _res, seen, _bench = run
    got = seen["controls"]
    assert got["positions"] == PODS
    assert got[control] == differs_at, got


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_routers_metrics_read_the_windows_counts_through_the_phase_reader(run, name):
    _res, seen, bench = run
    listed = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}
    assert set(NEW_METRICS) <= set(listed)
    # interpod-5k's metrics but those, and the two of the resident route, which read since the gate lets
    # the batch through: resident_round, and the runs the drain was cut into (PR 44: one here)
    interpod = {s["name"] for s in cells.layer_metrics("interpod-5k.backlog", bench)}
    assert set(listed) == (interpod - NOT_HERE) | {
        "kernels.stage_ms_per_kpod.resident_round.backlog", "loop.resident_runs_per_kpod.backlog"}
    per_cell = {w["name"]: {s["name"] for s in cells.layer_metrics(w["name"], bench)} for w in bench["workloads"]}
    assert all(NEW_METRICS[0] in names for names in per_cell.values())  # the route: every cell
    for gate_metric in NEW_METRICS[1:]:  # the gate's two: the three cells with placed terms
        assert sorted(c for c, names in per_cell.items() if gate_metric in names) == \
            ["antiaffinity-5k.backlog", "interpod-5k.backlog", CELL]
    spec = listed[name]
    asked = name == NEW_METRICS[2]
    assert spec["reader"] == "phase" and spec["layer"] == "scheduling loop"
    assert spec["unit"] == ("probes/kpod" if asked else "pods/kpod") and spec["better"] == "lower"
    # this window: the fast route, not refused, nothing asked
    assert spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"]) == 0.0
    # the parent's window at the source's counts: ten chained dispatches took the 5,000
    # pods, refused on the count; a program without the new counter reads 0.0 there
    parent = {"route.chained": 5000.0, "fast_gate.refused.term_count": 5000.0}
    assert spec["read"]({"phases": parent, "pods_in_window": 5000}, spec["params"]) == (0.0 if asked else 1000.0)
    # interpod-5k's window: the ONE placed term admits every batch, asked once a batch of 512
    interpod_window = {"route.chained": 5000.0, "fast_gate.refused.term_admits": 5000.0, "fast_gate.probes_asked": 10.0}
    assert spec["read"]({"phases": interpod_window, "pods_in_window": 5000}, spec["params"]) == \
        {NEW_METRICS[0]: 1000.0, NEW_METRICS[1]: 0.0, NEW_METRICS[2]: 2.0}[name]
    # no window says nothing, and raises nothing
    assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None


# ---- controls: a pod a placed term admits; a base pod moved in the read-back ------

GREEN = {"name": "green-0", "namespace": "sched-1", "labels": {"color": "green"},
         "requests": {"cpu": "100m", "memory": "500Mi"}, "topology_spread": [], "affinity": None}
MOVED, ONTO = "sched-0/base-anti-affinity-0", "sched-0/base-anti-affinity-1"


@pytest.fixture(scope="module")
def control_run():
    """One run serves controls (a) and (b): the measured backlog gains, at
    its head, ONE pod that the placed required anti-affinity term admits
    (the warm-up gains its twin, so the window meets no new shape); and after
    the window one base anti-affinity pod reads back on a peer's node."""
    bench = cells.benchmark()
    cell = _cell(bench)
    kind = cell["kind"]
    seen = {"at": []}

    def plan(cfg, mix, seed, seconds):
        plan_ = kind.plan(cfg, mix, seed, seconds)
        plan_["warm"] = [{**GREEN, "name": "warm-green-0"}] + plan_["warm"]
        plan_["measure"] = [dict(GREEN)] + plan_["measure"]
        return plan_

    cell["kind"] = types.SimpleNamespace(
        plan=plan, **{f: getattr(kind, f) for f in ("pods_alive", "warm_up", "offer", "window", "reduce")})

    def tamper(cluster):
        _small_batches(cluster)
        _watch(seen)(cluster)
        read_back = cluster.read_back  # _watch's, which keeps the store as it was read

        def read():
            store = read_back()
            return {**store, MOVED: store[ONTO]}

        cluster.read_back = read

    def at_position(replay, pos, spec, decided, want):
        plain = replay.choose({**spec, "labels": {}}) if spec["name"] == "green-0" else None
        seen["at"].append((pos, spec["name"], decided, want, plain))

    res = runner.run_cell(
        cell, bench, GREEN_SEED, 120.0, False, time.perf_counter(), require_chip=False,
        tamper=tamper, identity_positions=[0, 1, BATCH], on_identity_position=at_position,
    )
    cluster = seen.pop("cluster")
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    seen["green_nodes"] = {n for s, n in _base_of(_cell(bench), GREEN_SEED) if s["labels"].get("color") == "green"}
    return res, seen


def test_control_a_pod_the_required_term_admits_never_lands_beside_a_green_base_pod(control_run):
    """(a) The pod pops first, onto nodes that are all alike but for the
    base pods' terms: stripped of its label the reference would put it on the
    first node in node order, which holds a green base pod; with the label
    the program and the reference both take another node."""
    res, seen = control_run
    pos, name, decided, want, plain = seen["at"][0]
    assert (pos, name) == (0, "green-0")
    assert len(seen["green_nodes"]) == GROUP and plain in seen["green_nodes"]  # the control bites
    assert decided == want and decided not in seen["green_nodes"]
    assert seen["store"]["sched-1/green-0"] == decided
    got = res["compared"]
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert got["identity.positions_compared"]["value"] == 3
    assert res["attempted"] == PODS + 1 and res["failed"] == 0
    # its batch, and only its batch, is refused — by the ONE term that admits it, asked
    # once; the batches behind it hold plain pods and take the fast route
    window = seen["window"]
    assert window["fast_gate.refused.term_admits"] == window["route.chained"] == BATCH
    assert window["route.fast"] == PODS + 1 - BATCH and window["fast_gate.probes_asked"] == 1
    assert [k for k in window if k.startswith("fast_gate.refused.")] == ["fast_gate.refused.term_admits"]


def test_control_a_base_pod_moved_onto_a_peers_node_in_the_read_back_is_not_correct(control_run):
    """(b) The NEGATIVE control of the recount: the term of each of the two
    base pods selects the other, so both ordered pairs are counted, against
    the limit 0, and by no count of resources; the green measured pod adds
    none (it sits beside no green pod)."""
    res, _seen = control_run
    got = res["compared"]
    assert got["feasibility.required_anti_affinity_violations"] == {"value": 2, "limit": 0, "ok": False}
    assert got["feasibility.overcommitted_node_resources"]["ok"]
    assert got["feasibility.required_affinity_unmet"]["value"] == 0
    assert res["correct"] is False
