"""``correct`` turns false on each fault it is there to catch (no JAX)."""

import json
import os

from benchmarks import cells, correct, workload

CFG = json.load(open(os.path.join(cells.HERE, "configs", "sched-perf-spread-5k.json")))
CFG["nodes"]["count"] = 16


def _sound_run(n_pods=40):
    """Decisions made BY the reference itself: a sound system by construction."""
    nodes = workload.node_specs(CFG)
    pods = workload.pod_specs(CFG, "pod-with-topology-spreading", n_pods, "load")
    replay = correct.ReferenceReplay(nodes, [])
    order = []
    for spec in pods:
        node = replay.choose(spec)
        assert node
        replay.place(spec, node)
        order.append((spec, node))
    store = {workload.uid_of(s): n for s, n in order}
    acked = {u: (n, float(i)) for i, (u, n) in enumerate(store.items())}
    return nodes, pods, order, store, acked


def _ok(checks):
    return all(c.ok for c in checks)


def test_sound_run_is_correct():
    nodes, pods, order, store, acked = _sound_run()
    g, good = correct.check_guarantee(acked, store, [], list(store))
    assert _ok(g) and len(good) == len(pods)
    assert _ok(correct.check_feasibility(nodes, pods, store))
    assert _ok(correct.check_identity(nodes, [], order, k=len(order), seed=1))


def test_decision_moved_to_another_feasible_node_fails_identity_only():
    nodes, pods, order, store, acked = _sound_run()
    spec, node = order[7]
    zone = {n["name"]: n["labels"][workload.ZONE_LABEL] for n in nodes}
    other = next(n["name"] for n in reversed(nodes)
                 if n["name"] != node and zone[n["name"]] == zone[node])  # same zone: still feasible
    order[7] = (spec, other)
    store[workload.uid_of(spec)] = other
    assert _ok(correct.check_feasibility(nodes, pods, store))
    checks = correct.check_identity(nodes, [], order, k=len(order), seed=1)
    assert not _ok(checks)
    assert checks[0].value >= 1 and checks[0].limit == 0


def test_one_readback_differs_fails_guarantee():
    nodes, pods, order, store, acked = _sound_run()
    uid = workload.uid_of(pods[5])
    store[uid] = next(n["name"] for n in nodes if n["name"] != store[uid])
    g, good = correct.check_guarantee(acked, store, [], list(store))
    assert not _ok(g) and g[0].value == 1 and uid not in good


def test_double_bind_fails_guarantee():
    nodes, pods, order, store, acked = _sound_run()
    g, _ = correct.check_guarantee(acked, store, [("default/load-1", nodes[1]["name"], nodes[2]["name"])], list(store))
    assert not _ok(g)


def test_overcommitted_node_fails_feasibility():
    nodes, pods, order, store, acked = _sound_run()
    fat = workload.pod_specs(CFG, "pod-default", 60, "fat")
    for s in fat:  # 60 pods of 100m on one node of 4 cpu
        store[workload.uid_of(s)] = nodes[3]["name"]
    checks = correct.check_feasibility(nodes, pods + fat, store)
    assert not _ok(checks) and checks[0].value >= 1


def test_skew_over_max_fails_feasibility():
    nodes, pods, order, store, acked = _sound_run(n_pods=9)
    more = workload.pod_specs(CFG, "pod-with-topology-spreading", 6, "skew")
    for s in more:  # six more matching pods, all into the first node's zone
        store[workload.uid_of(s)] = nodes[0]["name"]
    checks = correct.check_feasibility(nodes, pods + more, store)
    assert any(c.name == "selectors_over_max_skew" and not c.ok for c in checks)


def test_quantities():
    assert correct.quantity("cpu", "250m") == 250
    assert correct.quantity("cpu", "4") == 4000
    assert correct.quantity("memory", "32Gi") == 32 * 2**30
    assert correct.quantity("pods", 110) == 110


def test_the_seed_draws_the_init_pods_nodes_and_nothing_else():
    nodes = workload.node_specs(CFG)
    a = workload.init_placement(CFG, 10, nodes, 1)
    b = workload.init_placement(CFG, 10, nodes, 2**31 + 7)
    assert a != b and len(set(a)) == 10 == len(set(b))
    assert a == workload.init_placement(CFG, 10, nodes, 1)
    assert [n["labels"][workload.ZONE_LABEL] for n in nodes[:4]] == ["moon-1", "moon-2", "moon-3", "moon-1"]
