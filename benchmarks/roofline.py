"""Peaks and the floor a scheduling dispatch cannot go under.

``peaks(device_kind)`` reads ``peaks.json``; a kind that is not in the
table is an error, never a default.

``floor_bytes`` counts the bytes the scheduling kernels of one window must
move AT LEAST ONCE whatever the algorithm, from the window's own shapes:

* every dispatch reads the allocatable rows and the usage rows of every
  node once (it must see every node to pick the best one): 2 x N x L lanes;
* the window writes back one usage row for every node it bound a pod to
  (``nodes_touched``), and one decision per pod.

Lanes are counted at 4 bytes, the narrowest lane that holds the
configuration's quantities (milli-cpu, Mi, pods); the program's lanes are
int64 today, so the true traffic is at least twice this.  It is a lower
bound on purpose: no later algorithm can read over 100 % against it.  The
bound is on the memory side (bytes over HBM bandwidth); the kernels do
integer compares and adds, a few operations per byte, far under the
compute roof.
"""

from __future__ import annotations

import json
import os

LANE_BYTES = 4
DECISION_BYTES = 4


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def floor_bytes(
    n_nodes: int, n_lanes: int, dispatches: int, pods: int, nodes_touched: int
) -> int:
    reads = dispatches * 2 * n_nodes * n_lanes * LANE_BYTES
    writes = nodes_touched * n_lanes * LANE_BYTES + pods * DECISION_BYTES
    return reads + writes


def floor_seconds(device_kind: str, **shapes) -> float:
    return floor_bytes(**shapes) / peaks(device_kind)["hbm_bytes_per_s"]
