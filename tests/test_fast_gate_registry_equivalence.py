"""The fast gate's registry of DISTINCT placed terms (PR 42) against the walk it
replaced: over random sequences of placed term pods coming and going, by every
route the cache counts a pod through, the gate says no exactly where a
brute-force walk of every probe of every pod in ``cache.term_pods`` — the
gate's loop before PR 42, kept here as the oracle — finds one that admits."""

import random
from types import SimpleNamespace

import pytest

from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    LabelSelector,
    LabelSelectorRequirement,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.cache.term_probes import _pod_probes
from kubernetes_tpu.scheduler import Scheduler

NAMESPACES = ("default", "sched-0", "sched-1", "team-a", "team-b", "namespace-6")
KEYS = ("color", "app", "tier")
VALUES = ("red", "green", "blue", "yellow")
TOPOLOGY = ("kubernetes.io/hostname", "topology.kubernetes.io/zone")
STEPS = 120


def _registry_counts(cache):
    """content key -> reference count, from one atomic copy of the registry's entries."""
    return {key: ent[1] for key, ent in list(cache.term_probes._entries.items())}


def _walk_admits(cache, pod) -> bool:
    return any(pr.admits(pod) for p in cache.term_pods.values() for pr in _pod_probes(p))


def _labels(rng, at_least=0):
    return {k: rng.choice(VALUES) for k in rng.sample(KEYS, rng.randint(at_least, len(KEYS)))}


def _selector(rng):
    shape = rng.random()
    if shape < 0.05:
        return None  # selects nothing
    if shape < 0.1:
        return LabelSelector()  # selects every pod of the term's namespaces
    match_labels = _labels(rng, at_least=1) if shape < 0.7 else None
    exprs = []
    for _ in range(rng.choice((0, 0, 1)) if match_labels else rng.randint(1, 2)):
        op = rng.choice(("In", "NotIn", "Exists", "DoesNotExist"))
        values = tuple(rng.sample(VALUES, rng.randint(1, 2))) if op in ("In", "NotIn") else ()
        if rng.random() < 0.1:
            values = list(values)  # a requirement that will not hash
        exprs.append(LabelSelectorRequirement(rng.choice(KEYS), op, values))
    return LabelSelector(match_labels=match_labels, match_expressions=tuple(exprs))


def _term(rng):
    return PodAffinityTerm(
        topology_key=rng.choice(TOPOLOGY),
        label_selector=_selector(rng),
        namespaces=tuple(rng.sample(NAMESPACES, rng.choice((0, 1, 1, 2, 3)))),
        namespace_selector=LabelSelector(match_labels={"env": "prod"}) if rng.random() < 0.05 else None,
    )


def _group(rng, cls):
    required = tuple(_term(rng) for _ in range(rng.choice((0, 1, 1, 2))))
    preferred = tuple(WeightedPodAffinityTerm(rng.randint(1, 100), _term(rng)) for _ in range(rng.choice((0, 0, 1))))
    return cls(
        required_during_scheduling_ignored_during_execution=required,
        preferred_during_scheduling_ignored_during_execution=preferred,
    )


def _term_pod(rng, i):
    sign = rng.choice(("affinity", "anti", "both"))
    return Pod(
        name=f"t{i}",
        namespace=rng.choice(NAMESPACES),
        labels=_labels(rng),
        affinity=Affinity(
            pod_affinity=_group(rng, PodAffinity) if sign != "anti" else None,
            pod_anti_affinity=_group(rng, PodAntiAffinity) if sign != "affinity" else None,
        ),
        containers=[Container(name="c", requests={"cpu": "10m"})],
    )


def _batch_pod(rng, i):
    return Pod(name=f"b{i}", namespace=rng.choice(NAMESPACES), labels=_labels(rng))


def _check(sched, rng, step):
    cache = sched.cache
    counts = _registry_counts(cache)
    assert all(v > 0 for v in counts.values())
    assert sum(counts.values()) == sum(len(_pod_probes(p)) for p in cache.term_pods.values())
    assert cache.n_term_pods == len(cache.term_pods)
    pods = [_batch_pod(rng, f"{step}-{j}") for j in range(6)]
    for pod in pods:
        ok = sched._fast_gate_ok([SimpleNamespace(pod=pod)])
        assert ok != _walk_admits(cache, pod), (step, pod.namespace, pod.labels, sched._gate.refused)
        assert sched._gate.refused in (None, "term_admits")
    # and as ONE batch: refused exactly where some pod of it is admitted
    ok = sched._fast_gate_ok([SimpleNamespace(pod=p) for p in pods])
    assert ok != any(_walk_admits(cache, p) for p in pods)


@pytest.mark.parametrize("seed", range(8))
def test_the_gates_verdict_equals_the_walk_over_every_placed_term_pod(seed):
    rng = random.Random(seed)
    sched = Scheduler()
    cache = sched.cache
    added, assumed = [], []  # the pods in the cache, by the state they are in
    n = 0
    for step in range(STEPS):
        roll = rng.random()
        if roll < 0.2 or not (added or assumed):
            pod = _term_pod(rng, n)
            n += 1
            pod.node_name = f"n{rng.randrange(8)}"
            cache.add_pod(pod)  # the informer reports it placed
            added.append(pod)
        elif roll < 0.3:
            pod = _term_pod(rng, n)
            n += 1
            cache.assume_pod(pod, f"n{rng.randrange(8)}")
            assumed.append(pod)
        elif roll < 0.38:
            pods = [_term_pod(rng, n + j) for j in range(rng.randint(1, 4))]
            n += len(pods)
            with sched._mu:
                cache.assume_pods_bulk([(p, f"n{rng.randrange(8)}") for p in pods])
            assumed.extend(pods)
        elif roll < 0.5 and added:
            old = added.pop(rng.randrange(len(added)))
            new = _term_pod(rng, n)  # an update that changes the affinity
            n += 1
            new.name, new.uid, new.node_name = old.name, old.uid, old.node_name
            cache.update_pod(old, new)
            added.append(new)
        elif roll < 0.65 and assumed:
            cache.forget_pod(assumed.pop(rng.randrange(len(assumed))))
        elif roll < 0.7 and assumed:
            pod = assumed.pop(rng.randrange(len(assumed)))
            confirmed = cache.pod_states[pod.uid].pod  # the informer confirms the assumed pod
            cache.add_pod(confirmed)
            added.append(confirmed)
        elif added:
            cache.remove_pod(added.pop(rng.randrange(len(added))))
        _check(sched, rng, step)
    for pod in assumed:
        cache.forget_pod(pod)
    for pod in added:
        cache.remove_pod(pod)
    assert cache.n_term_pods == 0 and not cache.term_pods
    assert not _registry_counts(cache) and not cache.term_probes.view()
