"""``interpod-5k.backlog`` at cut counts, whole, on the CPU: the configuration
``benchmarks/configs/sched-perf-prefaffinity-5k.json`` (upstream's ``:354``
SchedulingPreferredPodAffinity) through ``runner.run_cell``, EVERY position of
the window compared with the frozen reference (``benchmarks/reference/``).

What the cell is there to see is pinned here: the preferred term decides (the
pods pack node by node, a node fills inside the run and the next takes over),
and the two controls read as they did when the cell was chosen — the reference
with BOTH directions of the term removed (the incoming pod's ``affinity`` and
its label, which the placed pods' terms select) decides otherwise almost
everywhere, the reference with only the incoming pod's ``affinity`` removed
decides the same everywhere (all pods share one template, the two directions'
raw scores are proportional and the min-max normalisation maps them to one
decision).  A change of the format or of the reference that breaks either is
seen here.

One run serves every case: the counts are cut (one init pod a node, as at the
source's counts) and the scheduler's batch with them, so the window is
several chained dispatches, as at 5,000 it is ten of 512.
"""

import collections
import time

import pytest

from benchmarks import cells, runner

CELL = "interpod-5k.backlog"
NODES = PODS = 160
BATCH = 32
# 4 cpu a node, 100m a pod: an init pod and 39 measured ones fill a node
PODS_TO_FILL = 39


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    cell = cells.cut(cells.cell(CELL, bench), NODES, PODS, NODES)
    seen = {"decided": [], "both_stripped": [], "incoming_stripped": []}

    def small_batches(cluster):
        cluster.sched.config.batch_size = BATCH
        seen["sched"] = cluster.sched

    def controls(replay, pos, spec, decided, want):
        seen["decided"].append(decided)
        seen["both_stripped"].append(replay.choose({**spec, "affinity": None, "labels": {}}))
        seen["incoming_stripped"].append(replay.choose({**spec, "affinity": None}))

    res = runner.run_cell(
        cell, bench, 5, 120.0, False, time.perf_counter(), require_chip=False,
        tamper=small_batches, identity_positions=list(range(PODS)),
        on_identity_position=controls,
    )
    seen["totals"] = seen.pop("sched").phases.snapshot()  # the scheduler itself is let go
    return res, seen


def test_the_cut_cell_runs_whole_and_binds_every_pod(run):
    res, _seen = run
    assert res["attempted"] == PODS and res["failed"] == 0
    assert res["compared"]["guarantee.decisions_not_read_back"]["value"] == 0


def test_the_program_equals_the_frozen_reference_at_every_position(run):
    res, seen = run
    got = res["compared"]
    assert got["identity.positions_compared"]["value"] == PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert len(seen["decided"]) == PODS and all(seen["decided"])
    assert res["correct"] is True, {k: v for k, v in got.items() if not v["ok"]}


def test_the_window_is_chained_dispatches_and_compiles_nothing(run):
    res, _seen = run
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]


@pytest.mark.parametrize("part", ["required_anti_affinity_violations", "required_affinity_unmet"])
def test_a_preferred_term_adds_no_required_term_to_feasibility(run, part):
    res, _seen = run
    assert res["compared"][f"feasibility.{part}"] == {"value": 0, "limit": 0, "ok": True}


def test_the_term_packs_the_pods_node_by_node(run):
    """The hot node fills and the next takes over inside the run."""
    _res, seen = run
    per_node = collections.Counter(seen["decided"])
    assert len(per_node) <= 6, per_node
    assert max(per_node.values()) == PODS_TO_FILL, per_node
    # consecutive pods share a node until it is full: at most one change of
    # node per node used
    changes = sum(a != b for a, b in zip(seen["decided"], seen["decided"][1:]))
    assert changes == len(per_node) - 1


def test_the_loop_books_the_waves_demotions_and_existing_pod_rows(run):
    """``wave.demoted`` is what ``loop.wave_demoted_per_kpod.backlog`` reads:
    every pod of a batch speculates the node that is hot when the batch
    starts (where its first pod lands), so the demoted are the pods that land
    elsewhere because it filled.  ``wave.epod_rows``: the init pods plus the
    batches before, at each dispatch.  The warm-up backlog is the measured
    one over again, so the process's totals are twice the window's."""
    _res, seen = run
    decided = seen["decided"]
    demoted = sum(decided[i] != decided[i - i % BATCH] for i in range(PODS))
    rows = sum(NODES + first for first in range(0, PODS, BATCH))
    totals = seen["totals"]
    assert demoted > PODS // 3
    assert totals["wave.demoted"] == 2 * demoted
    assert totals["wave.epod_rows"] == 2 * rows


@pytest.mark.parametrize("control,differs_at", [
    ("both_stripped", lambda n: n >= 0.9 * PODS),
    ("incoming_stripped", lambda n: n == 0),
], ids=["both-directions-stripped-differs", "incoming-only-stripped-does-not"])
def test_controls_read_as_when_the_cell_was_chosen(run, control, differs_at):
    _res, seen = run
    n = sum(c != d for c, d in zip(seen[control], seen["decided"]))
    assert differs_at(n), f"{control}: differs at {n} of {PODS} positions"
