"""``cl2load-5k.backlog-of-deployments`` at cut counts, whole, on the CPU: the
configuration ``benchmarks/configs/cl2-load-5k.json`` (ClusterLoader2's load
test: Deployments of 250 / 30 / 5 replicas, every pod under the scheduler's two
built-in default SOFT spread constraints over its own Deployment's selector)
under the traffic kind ``backlog_of_deployments`` through ``runner.run_cell``,
EVERY position of the window compared with the frozen reference
(``benchmarks/reference/``).

The cut: 60 nodes in 3 zones, 2 namespaces of 1 + 3 + 12 Deployments (the
sizes are the source's: 250, 30, 5), so 480 placed replicas and 320 pending
ones in 32 Deployments, a loop batch of 32: ten chained dispatches, each of
pods of 8 to 16 Deployments.

What the cell is there to see is pinned here.  A ``ScheduleAnyway`` constraint
has no limit to recount, so the two constraints are held by IDENTITY alone —
and identity sees them: the reference with both stripped differs at most
positions, with the zone constraint alone stripped at many.  The pods of a
batch carry many distinct terms (``wave.terms``: two a Deployment present),
more distinct rows than the statics' signature table holds (``wave.static_full``
every batch), and what a wave demotes it demotes on SCORE.

Since PR 50 a batch here binds as ONE task, whatever its Deployments: 16
probes x 32 pods is far under the cap at which the interaction sweep gave up
(the full-size cell's short last batch, 368 pods of ≈ 260 Deployments, stood
there at 92 % of seeds), so until then every batch of this cut bound one task
a Deployment present.  ``bind.tasks`` counts them and
``served.bind_tasks_per_kpod.backlog`` reads it.
"""

import collections
import contextlib
import io
import json
import time

import pytest

from benchmarks import cells, runner
from benchmarks.control_spread import SpreadControls
from tests.test_bench_unsched_cell import _watch  # the window's own phase totals and the store as read back

CELL = "cl2load-5k.backlog-of-deployments"
NODES, NAMESPACES, PER_NAMESPACE, BATCH = 60, 2, (1, 3, 12), 32
SIZES = (250, 30, 5)
PLACED = NAMESPACES * sum(r * 3 // 5 * n for r, n in zip(SIZES, PER_NAMESPACE))  # 480
PODS = NAMESPACES * sum((r - r * 3 // 5) * n for r, n in zip(SIZES, PER_NAMESPACE))  # 320
DEPLOYMENTS = NAMESPACES * sum(PER_NAMESPACE)
WAVES = PODS // BATCH
NEW_METRICS = {f"{m}.backlog": c for m, c in (
    ("loop.wave_terms_per_kpod", "wave.terms"), ("loop.wave_conflicts_score_per_kpod", "wave.conflicts.score"),
    ("loop.wave_static_full_per_kpod", "wave.static_full"))}
TASKS_METRIC = "served.bind_tasks_per_kpod.backlog"
CROSS_POD_CELLS = ["spread-5k.backlog", "interpod-5k.backlog", "antiaffinity-5k.backlog", CELL]
STAGE_METRIC = "kernels.stage_ms_per_kpod.spread_constraints.backlog"
SEED = 4800000007


def _cell(bench):
    cell = cells.cell(CELL, bench)
    table = cell["config"]["deployments"]
    table["namespaces"] = NAMESPACES
    for size, n in zip(table["sizes"], PER_NAMESPACE):
        size["per_namespace"] = n
    cell["config"]["placed_pods"]["count"] = PLACED
    return cells.cut(cell, NODES, PODS, 0)


def _small_batches(cluster):
    cluster.sched.config.batch_size = BATCH


@pytest.fixture(scope="module")
def run():
    bench = cells.benchmark()
    seen = {"decided": []}
    controls = SpreadControls([1, BATCH])

    def watch(cluster):
        _small_batches(cluster)
        _watch(seen)(cluster)
        release = cluster.release_loop

        def release_loop():
            seen["txns0"] = (cluster.apiserver.bulk_bind_txns, cluster.apiserver.bulk_bind_items)
            release()

        cluster.release_loop = release_loop

    def at_position(replay, pos, spec, decided, want):
        seen["decided"].append(decided)
        controls(replay, pos, spec, decided, want)

    with contextlib.redirect_stdout(io.StringIO()):
        res = runner.run_cell(
            _cell(bench), bench, SEED, 120.0, False, time.perf_counter(), require_chip=False,
            tamper=watch, identity_positions=list(range(PODS)), on_identity_position=at_position,
        )
    cluster = seen.pop("cluster")  # the scheduler itself is let go
    seen["window"] = cluster.sched.phases.diff(seen.pop("phases1"), seen.pop("phases0"))
    txns0, items0 = seen.pop("txns0")
    seen["bind_txns"] = (cluster.apiserver.bulk_bind_txns - txns0, cluster.apiserver.bulk_bind_items - items0,
                         cluster.apiserver.bulk_bind_fallback_items)
    seen["controls"] = controls.readings()
    return res, seen, bench


def test_the_cut_cell_runs_whole_binds_every_pod_and_is_correct(run):
    res, seen, _bench = run
    assert res["attempted"] == PODS and res["failed"] == 0
    assert set(res["metrics"]) == {"pods_per_s", "setup_s"}
    assert res["compared"]["guarantee.decisions_not_read_back"]["value"] == 0
    assert len(seen["store"]) == PLACED + PODS and all(seen["store"].values())  # the warm-up is gone, all are bound
    assert "feasibility.selectors_over_max_skew" not in res["compared"]  # nothing hard to recount
    assert res["correct"] is True, {k: v for k, v in res["compared"].items() if not v["ok"]}


def test_the_program_equals_the_frozen_reference_at_every_position(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["identity.positions_compared"]["value"] == PODS
    assert got["identity.decisions_differing_from_reference"]["value"] == 0
    assert len(seen["decided"]) == PODS and all(seen["decided"])


def test_the_window_is_chained_dispatches_and_compiles_nothing(run):
    res, seen, _bench = run
    got = res["compared"]
    assert got["device.compiles_in_window"]["value"] == 0
    assert got["device.dispatches_of_the_cells_kernels"]["ok"]
    assert got["device.breaker_faults"]["value"] == got["device.device_faults_logged"]["value"] == 0
    assert seen["window"]["route.chained"] == PODS
    assert not [k for k in seen["window"] if k.startswith(("route.fast", "route.direct", "fast_gate."))]


def test_a_batch_holds_many_terms_more_rows_than_the_table_and_demotes_on_score(run):
    """Two terms a Deployment present in a batch (8 to 16 of the 32 here),
    the statics per pod in every batch, and every demotion that is a conflict
    one of kind ``score``: no hard mask exists."""
    _res, seen, bench = run
    window = seen["window"]
    cell = _cell(bench)
    order = [s["labels"]["name"] for s in cell["kind"].plan(cell["config"], cell["traffic"], SEED, 120.0)["measure"]]
    present = [len(set(order[i:i + BATCH])) for i in range(0, PODS, BATCH)]
    assert len(set(order)) == DEPLOYMENTS and all(8 <= n <= 16 for n in present)
    assert window["wave.terms"] == 2 * sum(present) > 2 * WAVES
    assert window["wave.static_full"] == WAVES and "wave.static_sigs" not in window
    assert [k for k in window if k.startswith("wave.conflicts.")] == ["wave.conflicts.score"]
    assert 0 < window["wave.conflicts.score"] == window["wave.demoted"] <= PODS - WAVES
    assert window["wave.epod_rows"] == sum(PLACED + first for first in range(0, PODS, BATCH))


def test_every_batch_binds_as_one_task_and_one_bulk_transaction_whatever_its_deployments(run):
    """Ten batches of 32 pods of 8 to 16 Deployments: ten commit runs, ten
    bind tasks, ten bulk transactions in the emulated API server and not one
    single-pod POST (a task of ONE pod takes the per-pod sink), where the
    parent of PR 50 bound a task a Deployment present."""
    res, seen, _bench = run
    window = seen["window"]
    assert window["bind.tasks"] == WAVES
    assert seen["bind_txns"] == (WAVES, PODS, 0)  # transactions, the pods they carried, per-item fallbacks
    assert res["compared"]["identity.decisions_differing_from_reference"]["value"] == 0
    assert res["compared"]["identity.positions_compared"]["value"] == PODS


@pytest.mark.parametrize("control,least,most", [
    ("all_stripped", PODS // 2, PODS), ("zone_stripped", PODS // 3, PODS), ("hostname_stripped", PODS // 3, PODS),
    ("stale_lag1", PODS // 8, PODS // 2), (f"stale_lag{BATCH}", 3 * PODS // 4, PODS - 1),
], ids=["both-constraints-stripped", "zone-constraint-alone-stripped", "hostname-constraint-alone-stripped",
        "one-commit-stale", "a-wave-stale"])
def test_identity_sees_the_soft_constraints_and_a_stale_decision(run, control, least, most):
    """The cell's term is held by identity alone, so identity has to see it:
    the reference without both constraints decides otherwise at 225 of the 320
    positions (stated share: at least half), without the zone constraint alone
    at 162 and without the hostname one at 172 (a third or more); a decision a
    whole wave stale differs at 313 (three quarters or more), one ONE commit
    stale only at 92 (an eighth to a half: the commit before is mostly another
    Deployment's, and reaches this pod through the resource scores alone)."""
    _res, seen, _bench = run
    got = seen["controls"]
    assert got["positions"] == PODS
    assert least <= got[control] <= most, sorted(got.items())


def test_the_new_metrics_read_the_windows_counters_through_the_phase_reader(run):
    _res, seen, bench = run
    listed = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}
    spread = {s["name"] for s in cells.layer_metrics("spread-5k.backlog", bench)}
    assert set(listed) == spread | set(NEW_METRICS)  # all of spread-5k's, and the three counters here alone
    assert STAGE_METRIC in listed and STAGE_METRIC in spread
    assert listed[STAGE_METRIC]["params"]["stage"] == "ktpu/gang/spread_constraints"
    for name, counter in NEW_METRICS.items():
        spec = listed[name]
        assert spec["reader"] == "phase" and spec["layer"] == "scheduling loop" and spec["params"] == {"phases": [counter]}
        assert spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"]) == \
            1000.0 * seen["window"][counter] / PODS > 0
        # a program without the counter (the parent) reads 0.0 and raises nothing
        assert spec["read"]({"phases": {"wave.demoted": 9.0}, "pods_in_window": PODS}, spec["params"]) == 0.0
        assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None
    # at the source's counts: twelve batches of about 340 Deployments for 6,000 pods
    terms = listed["loop.wave_terms_per_kpod.backlog"]
    assert terms["read"]({"phases": {"wave.terms": 8160.0}, "pods_in_window": 6000}, terms["params"]) == 1360.0


def test_the_bind_tasks_metric_reads_the_windows_counter_and_divides_it_by_the_pods_bound(run):
    _res, seen, bench = run
    spec = {s["name"]: s for s in cells.layer_metrics(CELL, bench)}[TASKS_METRIC]
    assert (spec["reader"], spec["layer"], spec["unit"], spec["source"]) == ("phase", "served path", "tasks/kpod", "program_counter")
    assert spec["params"] == {"phases": ["bind.tasks"]} and spec["better"] == "lower" and spec["moves"] == "pods_per_s"
    assert spec["read"]({"phases": seen["window"], "pods_in_window": PODS}, spec["params"]) == 1000.0 * WAVES / PODS
    # at the cells' counts: one task a batch of 512 (twelve for 6,000 pods, ten for 5,000, four for 2,000);
    # the parent of PR 50 on a swept last batch of this cell: 259 to 281 tasks a window (270 and 281 in two traced runs)
    def read(tasks, pods):
        return spec["read"]({"phases": {"bind.tasks": tasks}, "pods_in_window": pods}, spec["params"])

    assert (read(12.0, 6000), read(10.0, 5000), read(4.0, 2000)) == (2.0, 2.0, 2.0)
    assert 45.0 <= read(270.0, 6000) < read(281.0, 6000) < 47.0
    # no window: nothing said.  A program without the counter (the parent) reads 0.0 and raises nothing
    assert spec["read"]({"phases": {}, "pods_in_window": PODS}, spec["params"]) is None
    assert spec["read"]({"phases": {"bind": 1.0}, "pods_in_window": PODS}, spec["params"]) == 0.0


@pytest.mark.parametrize("cell", [c["name"] for c in cells.benchmark()["workloads"]])
def test_the_bind_tasks_metric_is_listed_in_the_four_cross_pod_cells_and_no_other(cell):
    bench = cells.benchmark()
    listed = {s["name"] for s in cells.layer_metrics(cell, bench)}
    assert (TASKS_METRIC in listed) == (cell in CROSS_POD_CELLS)
    entry = next(m for m in bench["per_layer"] if m["name"] == TASKS_METRIC)
    assert entry == {"name": TASKS_METRIC, "unit": "tasks/kpod", "better": "lower", "source": "program_counter",
                     "layer": "served path", "moves": "pods_per_s", "workloads": CROSS_POD_CELLS}


# ---- the plan at the file's own size: nothing runs ----------------------------


@pytest.fixture(scope="module")
def full():
    cell = cells.cell(CELL, cells.benchmark())
    return cell, cell["kind"].plan(cell["config"], cell["traffic"], SEED, 30.0)


def test_the_plan_at_full_size_is_the_load_tests_five_namespaces(full):
    cell, plan = full
    cfg = cell["config"]
    assert cfg["init_pods"]["count"] == 0  # every placed pod belongs to a Deployment
    assert (len(plan["base"]), len(plan["measure"]), len(plan["warm"])) == (9000, 6000, 6000)
    assert (cfg["placed_pods"]["count"], cfg["measure_pods"]["count"]) == (9000, 6000)  # the file's counts are the table's sums
    assert cell["kind"].pods_alive(plan) == 15000
    sizes = collections.Counter()
    for part, per in (("base", (150, 18, 3)), ("measure", (100, 12, 2))):
        specs = [s for s, _node in plan[part]] if part == "base" else plan[part]
        owners = collections.Counter((s["namespace"], s["labels"]["name"]) for s in specs)
        assert len(owners) == 1640 and len({name for _ns, name in owners}) == 1640  # one label value a Deployment
        assert collections.Counter(owners.values()) == dict(zip(per, (15, 125, 1500)))
        assert collections.Counter(ns for ns, _name in owners) == {f"test-{n}": 328 for n in range(5)}
        sizes.update(owners)
    assert collections.Counter(sizes.values()) == {250: 15, 30: 125, 5: 1500}  # 3 + 25 + 300 a namespace, whole


def test_every_pod_carries_its_own_deployments_label_and_the_two_default_constraints(full):
    _cell_, plan = full
    for spec in plan["measure"] + plan["warm"] + [s for s, _node in plan["base"]]:
        name = spec["labels"]["name"]
        assert spec["labels"] == {"name": name} and name.startswith(spec["namespace"] + ".")
        assert spec["requests"] == {"cpu": "100m", "memory": "500Mi"} and spec["affinity"] is None
        assert [(c["max_skew"], c["topology_key"], c["when_unsatisfiable"], c["match_labels"])
                for c in spec["topology_spread"]] == [
            (3, "kubernetes.io/hostname", "ScheduleAnyway", {"name": name}),
            (5, "topology.kubernetes.io/zone", "ScheduleAnyway", {"name": name})]


def test_the_order_is_drawn_from_the_seed_over_all_deployments(full):
    cell, plan = full

    def owners(p, part):
        return [s["labels"]["name"] for s in p[part]]

    again = cell["kind"].plan(cell["config"], cell["traffic"], SEED, 30.0)
    other = cell["kind"].plan(cell["config"], cell["traffic"], SEED + 1, 30.0)
    assert json.dumps(again) == json.dumps(plan)  # same seed: same specs, same order, same nodes
    assert owners(plan, "warm") == owners(plan, "measure")  # the warm-up is the measured backlog under another name
    assert [s["name"] for s in plan["measure"][:3]] == ["load-0", "load-1", "load-2"]
    assert owners(other, "measure") != owners(plan, "measure")
    assert sorted(owners(other, "measure")) == sorted(owners(plan, "measure"))
    # a seeded shuffle of the whole list: a batch of 512 holds pods of hundreds of Deployments
    per_batch = [len(set(owners(plan, "measure")[i:i + 512])) for i in range(0, 6000, 512)]
    assert len(per_batch) == 12 and all(300 <= n <= 400 for n in per_batch[:-1]) and 200 <= per_batch[-1] <= 300
    # the placed replicas sit round-robin on the seeded node order the harness draws: two a node at most
    per_node = collections.Counter(node for _s, node in plan["base"])
    assert len(per_node) == 5000 and set(per_node.values()) == {1, 2}


def test_a_cut_of_the_pending_pods_cuts_the_placed_ones_by_the_same_share():
    cell = cells.cut(cells.cell(CELL, cells.benchmark()), 64, 128, 0)
    plan = cell["kind"].plan(cell["config"], cell["traffic"], SEED, 30.0)
    assert (len(plan["measure"]), len(plan["warm"]), len(plan["base"])) == (128, 128, 192)
    cell["config"]["measure_pods"]["count"] = 6001
    with pytest.raises(ValueError, match="6001 pods asked of the 6000 replicas"):
        cell["kind"].plan(cell["config"], cell["traffic"], SEED, 30.0)


def test_the_kind_refuses_a_program_whose_term_bucket_is_not_sticky(monkeypatch):
    """The parent of PR 48: it compiles a cross-pod program more at the seeds
    whose last batch falls under 512 terms (458 s), so the run is refused at
    the plan, before anything is built, and the process ends with code 1."""
    from kubernetes_tpu.ops import wave

    cell = cells.cut(cells.cell(CELL, cells.benchmark()), 64, 128, 0)
    monkeypatch.setattr(wave, "wave_tables", lambda pb, node_label_vals, hostname_id, hostnames_unique=None: None)
    with pytest.raises(RuntimeError, match="no sticky distinct-term bucket"):
        cell["kind"].plan(cell["config"], cell["traffic"], SEED, 30.0)
