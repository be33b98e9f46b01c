"""Cluster and pod generation for one configuration file, from ``--seed``.

A configuration (``benchmarks/configs/<name>.json``) describes nodes and
named pod templates as DATA; this module turns them into plain specs
(dicts of strings and numbers) and, separately, into objects of whichever
types module it is handed — the program's (``kubernetes_tpu.api.types``)
for the system under test, the frozen copy (``benchmarks/reference/types``)
for the plain reference.  The two sides share the specs and nothing else.

The source's templates have ONE shape each, so every seed gives the same
pods; what the seed draws is the nodes the init pods sit on (upstream's
scheduler picks at random among equal nodes) and the positions ``correct``
samples.
"""

from __future__ import annotations

import random
from typing import Iterable, List

ZONE_LABEL = "topology.kubernetes.io/zone"


def node_specs(cfg: dict) -> List[dict]:
    """``label_cycles``: label key -> values cycled over the nodes in node
    order (scheduler_perf's labelNodePrepareStrategy)."""
    nd = cfg["nodes"]
    return [
        {
            "name": nd["name"].format(i=i),
            "labels": {k: v[i % len(v)] for k, v in nd.get("label_cycles", {}).items()},
            "capacity": dict(nd["capacity"]),
        }
        for i in range(nd["count"])
    ]


def pod_specs(cfg: dict, template: str, count: int, role: str) -> List[dict]:
    """``count`` pod specs of one template, named ``<role>-<i>``, in the
    order they are to be created."""
    tpl = cfg["pod_templates"][template]
    return [
        {
            "name": f"{role}-{i}",
            "labels": dict(tpl.get("labels", {})),
            "requests": dict(tpl["requests"]),
            "topology_spread": [dict(c) for c in tpl.get("topology_spread", [])],
        }
        for i in range(count)
    ]


def uid_of(spec: dict) -> str:
    return f"default/{spec['name']}"


def build_node(T, R, spec: dict):
    """A ``Node`` of types module ``T`` (resource module ``R``)."""
    return T.Node(
        name=spec["name"],
        labels=dict(spec["labels"]),
        capacity=R.Resource.from_map(dict(spec["capacity"])),
    )


def build_pod(T, spec: dict, node_name: str = ""):
    """A ``Pod`` of types module ``T``; ``node_name`` set = already bound."""
    spread = tuple(
        T.TopologySpreadConstraint(
            max_skew=c["max_skew"],
            topology_key=c["topology_key"],
            when_unsatisfiable=c["when_unsatisfiable"],
            label_selector=T.LabelSelector(match_labels=dict(c["match_labels"])),
        )
        for c in spec.get("topology_spread", ())
    )
    return T.Pod(
        name=spec["name"],
        uid=uid_of(spec),
        labels=dict(spec["labels"]),
        node_name=node_name,
        topology_spread_constraints=spread,
        containers=[T.Container(name="c", requests=dict(spec["requests"]))],
    )


def init_placement(cfg: dict, n_init: int, nodes: Iterable[dict], seed: int) -> List[str]:
    """Node name for each init pod: round-robin over the nodes in an order
    drawn from the seed (what LeastAllocated does on empty equal nodes when
    ties are broken at random, as upstream's selectHost breaks them)."""
    names = [n["name"] for n in nodes]
    random.Random(f"{seed}/{cfg["name"]}/init").shuffle(names)
    return [names[j % len(names)] for j in range(n_init)]
