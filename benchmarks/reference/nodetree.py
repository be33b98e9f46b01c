"""Zone-interleaved node iteration order (backend/cache/node_tree.go).

The reference's scheduler cache keeps nodes in a nodeTree: a map of zone →
node list, with zones remembered in FIRST-SEEN order, and produces its
snapshot list by round-robining one node per zone per round (exhausted
zones skipped, node_tree.go:119-143).  Every order-sensitive mechanism —
adaptive-sampling windows, nextStartNodeIndex rotation, first-max
tie-breaks — rides that order, so multi-zone decision parity requires
reproducing it exactly.  This build keeps PACKED tensor slots stable for
delta uploads and instead threads a visit-rank permutation through the
sampling-compat paths; this module is the one shared definition of the
order, used by the snapshot mirror and the host oracle alike.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

ZONE_LABEL = "topology.kubernetes.io/zone"


def node_tree_order(zone_per_node: Sequence[Optional[str]]) -> List[int]:
    """Indices 0..n-1 reordered zone-round-robin.

    ``zone_per_node[i]`` is node i's zone label value ("" / None for
    unzoned nodes, which form their own bucket like the reference's empty
    zone key).  Zones iterate in first-seen order; nodes within a zone keep
    their given order; each round takes at most one node per zone.
    """
    by_zone: Dict[str, List[int]] = {}
    zones: List[str] = []
    for i, z in enumerate(zone_per_node):
        z = z or ""
        bucket = by_zone.get(z)
        if bucket is None:
            bucket = by_zone[z] = []
            zones.append(z)
        bucket.append(i)
    out: List[int] = []
    round_no = 0
    n = len(zone_per_node)
    while len(out) < n:
        for z in zones:
            bucket = by_zone[z]
            if round_no < len(bucket):
                out.append(bucket[round_no])
        round_no += 1
    return out
