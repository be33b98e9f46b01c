"""Traffic kind ``backlog_of_deployments``: a closed drain of a delivered
queue whose pods belong to MANY Deployments, onto the placed replicas of the
same Deployments.

As ``backlog_on_base``, with one difference: the pods are not stamped from
one template a group.  The configuration's ``deployments`` table (namespaces
x sizes) lists the Deployments; of each, ``placed_fifths`` fifths of the
replicas are PLACED (the group ``placed_pods``: planted bound before anything
is scheduled, on nodes of the seeded order the harness draws for init pods,
and held by ``correct`` as init pods are) and the rest are PENDING (the group
``measure_pods``: the measured backlog).  Every spec is stamped with its
Deployment's namespace and, wherever the template says ``{deployment}``, with
its name, before either side builds a pod from it: the program and the frozen
reference are handed the same specs.

Both lists are in an order drawn from ``--seed`` over ALL Deployments (a
seeded shuffle of the whole list), so a batch of the loop holds pods of
hundreds of Deployments; the warm-up backlog is the measured one, in the same
order, under another name.  A group count under the table's sum is a cut (a
rehearsal, the CPU test): a prefix of the seeded order, the placed pods cut by
the share the pending ones were.  ``pods_alive``, ``warm_up``, ``offer``,
``window`` and ``reduce`` are ``backlog_on_base``'s own.

Keys.  Of the mix: ``count`` (the group of pending replicas) and ``placed``
(the group of placed ones); both groups name the template every replica is
stamped from.  Of the configuration, ``deployments``: ``namespaces`` (how
many), ``namespace`` (their name, ``{n}``), ``name`` (a Deployment's,
``{namespace}`` ``{size}`` ``{i}``: the value its pods' labels and selectors
carry), ``placed_fifths`` (of every Deployment's replicas, rounded down) and
``sizes``, a list of ``size`` (a word), ``replicas`` and ``per_namespace``
(how many Deployments of that size a namespace holds).  Of the template:
``{deployment}`` wherever a label value or a constraint's ``match_labels``
value is its Deployment's name.

What the kind asks of the program.  A batch of 512 holds 618-716 distinct
terms and the drain's short last batch 504-542, on either side of a bucket's
edge: a program whose distinct-term bucket follows each batch compiles one
more cross-pod program (458 s, PR 48) at the seeds whose last batch falls
under 512, which no run's time limit holds.  So ``plan`` refuses, before
anything is built, a program whose ``wave.wave_tables`` takes no ``t_floor``
(the sticky bucket ISSUE 48 brought): the run ends soon, with exit code 1
and no result line.
"""

from __future__ import annotations

import inspect
import random
from typing import List, Tuple

from benchmarks import cells, workload
from kubernetes_tpu.ops import wave

_on_base = cells.traffic_kind("backlog_on_base")
pods_alive = _on_base.pods_alive
warm_up = _on_base.warm_up
offer = _on_base.offer
window = _on_base.window
reduce = _on_base.reduce


def deployments(cfg: dict) -> List[Tuple[str, str, int, int]]:
    """(namespace, name, placed, pending) of every Deployment, namespace by
    namespace in the table's order."""
    table = cfg["deployments"]
    out = []
    for n in range(table["namespaces"]):
        namespace = table["namespace"].format(n=n)
        for size in table["sizes"]:
            placed = size["replicas"] * table["placed_fifths"] // 5
            out += [
                (namespace, table["name"].format(namespace=namespace, size=size["size"], i=i),
                 placed, size["replicas"] - placed)
                for i in range(size["per_namespace"])
            ]
    return out


def _stamped(cfg: dict, group: str, role: str, owners: list, seed: int, count: int) -> List[dict]:
    """``count`` specs of the group, pod i of them a replica of
    ``owners[i]`` once the owners are shuffled by the seed."""
    if count > len(owners):
        raise ValueError(f"{group}: {count} pods asked of the {len(owners)} replicas the deployments table gives")
    random.Random(f"{seed}/{cfg['name']}/{group}").shuffle(owners)
    specs = workload.group_specs(cfg, group, role, count)
    for spec, (namespace, name) in zip(specs, owners):
        spec["namespace"] = namespace
        spec["labels"] = {k: v.format(deployment=name) for k, v in spec["labels"].items()}
        for c in spec["topology_spread"]:
            c["match_labels"] = {k: v.format(deployment=name) for k, v in c["match_labels"].items()}
    return specs


def plan(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """``measure`` and ``warm``: the pending replicas in ONE seeded order
    (``load-<i>`` and ``warm-<i>`` are replicas of the same Deployment);
    ``base``: the placed replicas, in a seeded order of their own, pod j on
    node (init + j) mod nodes of the init pods' seeded node order."""
    if "t_floor" not in inspect.signature(wave.wave_tables).parameters:
        raise RuntimeError(
            "backlog_of_deployments: this program keeps no sticky distinct-term bucket "
            "(ops/wave.py wave_tables has no t_floor); it compiles a cross-pod program "
            "more at the seeds whose last batch falls under 512 terms, and cannot run "
            "the cell inside a run's time limit"
        )
    table = deployments(cfg)
    pending = [(ns, name) for ns, name, _placed, n in table for _ in range(n)]
    placed = [(ns, name) for ns, name, n, _pending in table for _ in range(n)]
    g_pending, g_placed = mix["count"], mix["placed"]
    n_pending = cfg[g_pending]["count"]
    plan_ = {part: _stamped(cfg, g_pending, role, list(pending), seed, n_pending)
             for part, role in (("measure", "load"), ("warm", "warm"))}
    # a cut of the pending pods cuts the placed ones by the same share
    n_placed = min(cfg[g_placed]["count"], len(placed) * n_pending // len(pending))
    specs = _stamped(cfg, g_placed, "placed", placed, seed, n_placed)
    n_init = cfg["init_pods"]["count"]
    nodes = workload.init_placement(cfg, n_init + n_placed, workload.node_specs(cfg), seed)[n_init:]
    plan_["base"] = list(zip(specs, nodes))
    return plan_
