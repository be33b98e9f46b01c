"""``antiaffinity-5k.backlog`` at cut counts, whole, on the CPU: the configuration
``benchmarks/configs/sched-perf-antiaffinity-5k.json`` (upstream's ``:93``
SchedulingPodAntiAffinity) through ``runner.run_cell``, EVERY position of the
window compared with the frozen reference (``benchmarks/reference/``).

What the cell is there to see is pinned here: the REQUIRED hostname
anti-affinity term keeps every pod on a node of its own, and ``correct`` holds
that by recounting the read-back over every ordered pair (``0 of 1 terms``; a
pod moved onto a peer's node in the read-back is two violations); every pod of
a wave speculates the first empty node, so all but the wave's first are
demoted by the hard mask, and the loop books them as ``wave.demoted`` and, by
kind, as ``wave.conflicts.affinity``, which the per-layer metric this cell
adds reads.  The controls read as they did when the cell was chosen: the
reference with BOTH directions of the term stripped decides the same at every
position (with or without the term the serial scheduler takes the first empty
node in node order: identity is blind to the term on this shape, the recount
is what holds it), a one-commit-stale decision differs at every position.

One run serves every case but the last: the counts are cut and the
scheduler's batch with them, so the window is several chained dispatches, as
at the source's counts it is four (512, 512, 512, 464).
"""

import collections
import contextlib
import io
import re
import time

import pytest

from benchmarks import cells, runner
from benchmarks.control_terms import TermControls
from tests.test_bench_unsched_cell import _watch  # the window's own phase totals and the store as read back

CELL = "antiaffinity-5k.backlog"
NODES, PODS, INIT, BATCH = 160, 96, 24, 32
WAVES = PODS // BATCH
NEW_METRIC = "loop.wave_conflicts_affinity_per_kpod.backlog"


def _cell(bench):
    return cells.cut(cells.cell(CELL, bench), NODES, PODS, INIT)


def _small_batches(cluster):
    cluster.sched.config.batch_size = BATCH


def _notes(out: str) -> dict:
    """``part.name`` -> the note of every ``correct`` line printed."""
    return {m[1]: m[2].strip() for m in
            re.finditer(r"correct (?:ok  |FAIL) (\S+): \S+ \(limit [^)]*\)(.*)", out)}


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    seen = {"decided": []}
    controls = TermControls([1, BATCH])

    def watch(cluster):
        _small_batches(cluster)
        _watch(seen)(cluster)

    def at_position(replay, pos, spec, decided, want):
        seen["decided"].append(decided)
        controls(replay, pos, spec, decided, want)

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        res = runner.run_cell(
            _cell(bench), bench, 3700000007, 120.0, False, time.perf_counter(), require_chip=False,
            tamper=watch, identity_positions=list(range(PODS)), on_identity_position=at_position,
        )
    cluster = seen.pop("cluster")  # the scheduler itself is let go
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    seen["notes"] = _notes(out.getvalue())
    seen["controls"] = controls.readings()
    return res, seen, bench


def test_the_cut_cell_runs_whole_binds_every_pod_and_is_correct(run):
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


def test_no_two_pods_share_a_node_and_the_recount_has_one_term_to_count(run):
    res, seen, _bench = run
    per_node = collections.Counter(seen["store"].values())  # init pods and measured ones, as LIST read them back
    assert len(per_node) == PODS + INIT and set(per_node.values()) == {1}
    got = res["compared"]
    assert got["feasibility.required_anti_affinity_violations"] == {"value": 0, "limit": 0, "ok": True}
    assert seen["notes"]["feasibility.required_anti_affinity_violations"] == "(0 of 1 terms)"
    assert seen["notes"]["feasibility.required_affinity_unmet"] == "(0 of 0 terms)"


def test_the_window_is_chained_dispatches_and_compiles_nothing(run):
    res, _seen, _bench = run
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]
    assert got["device.breaker_faults"]["value"] == got["device.device_faults_logged"]["value"] == 0


@pytest.mark.parametrize("counter", ["wave.demoted", "wave.conflicts.affinity"])
def test_every_pod_of_a_wave_but_its_first_is_demoted_by_the_required_term(run, counter):
    """Every pod of a wave speculates the first empty node; the wave's first
    pod commits there and the hard mask sends each of the others on: pods −
    waves demotions, every one of kind ``affinity`` and of no other kind."""
    _res, seen, _bench = run
    window = seen["window"]
    assert window[counter] == PODS - WAVES
    assert [k for k in window if k.startswith("wave.conflicts.")] == ["wave.conflicts.affinity"]
    assert window["wave.epod_rows"] == sum(INIT + first for first in range(0, PODS, BATCH))


@pytest.mark.parametrize("control,differs_at", [
    ("both_stripped", 0), ("incoming_stripped", 0), ("stale_lag1", PODS - 1), (f"stale_lag{BATCH}", PODS - 1),
], ids=["both-directions-stripped-differs-nowhere", "incoming-only-stripped-differs-nowhere",
        "one-commit-stale-differs-at-every-position-but-the-first", "a-wave-stale-differs-at-every-position-but-the-first"])
def test_controls_read_as_when_the_cell_was_chosen(run, control, differs_at):
    """Identity is blind to the term on this shape and sees a stale decision
    at every position but the window's first, which has nothing to be stale
    about: the init pods are not on the replay's trail."""
    _res, seen, _bench = run
    got = seen["controls"]
    assert got["positions"] == PODS
    assert got[control] == differs_at, got


def test_the_new_metric_reads_the_windows_counter_through_the_phase_reader(run):
    _res, seen, bench = run
    listed = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}
    interpod = {s["name"] for s in cells.layer_metrics("interpod-5k.backlog", bench)}
    assert set(listed) == interpod and NEW_METRIC in listed  # all of interpod-5k's, this one in both
    assert NEW_METRIC not in {s["name"] for s in cells.layer_metrics("spread-5k.backlog", bench)}
    spec = listed[NEW_METRIC]
    assert spec["reader"] == "phase" and spec["layer"] == "scheduling loop"
    assert spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"]) == \
        1000.0 * (PODS - WAVES) / PODS
    # at the source's counts: four waves of 2,000 pods
    assert spec["read"]({"phases": {"wave.conflicts.affinity": 1996.0}, "pods_in_window": 2000},
                        spec["params"]) == 998.0
    # a program without the counter (the parent), or a window in which no
    # required term demoted a pod (interpod-5k), reads 0.0 and raises nothing
    assert spec["read"]({"phases": {"wave.demoted": 951.0}, "pods_in_window": PODS}, spec["params"]) == 0.0
    assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None


def test_control_a_pod_moved_onto_a_peers_node_in_the_read_back_is_not_correct():
    """The NEGATIVE control of the recount: one measured pod reads back on
    the node of an init pod (the read-back patched after the window).  The
    term of each selects the other: both ordered pairs are counted, against
    the limit 0, by ``required_anti_affinity_violations`` and by no count of
    resources (a node holds 40 such pods)."""
    bench = cells.benchmark()

    def moved_by_hand(cluster):
        _small_batches(cluster)
        read_back = cluster.read_back

        def read():
            return {**read_back(), "sched-1/load-7": cluster.init_nodes[0]}

        cluster.read_back = read

    res = runner.run_cell(
        _cell(bench), bench, 3700000011, 120.0, False, time.perf_counter(), require_chip=False,
        tamper=moved_by_hand, identity_positions=[7],
    )
    assert res["attempted"] == PODS
    got = res["compared"]
    assert got["feasibility.required_anti_affinity_violations"]["ok"] is False
    assert got["feasibility.required_anti_affinity_violations"]["value"] >= 2
    assert got["feasibility.overcommitted_node_resources"]["ok"]
    assert got["feasibility.required_affinity_unmet"]["value"] == 0
    assert res["correct"] is False

