"""How ``correct`` is decided: four exact checks, after the window.

1. guarantee   — every bind the API server acknowledged reads back equal
                 through LIST, and no pod was acknowledged on two nodes;
2. device      — the device answered (no breaker failure, trip or
                 fallback, no device fault logged), the kernels the cell
                 exists for dispatched, the HBM peak is non-zero, nothing
                 compiled inside a window whose shapes are deterministic,
                 and the device is a TPU;
3. feasibility — recomputed in plain Python from the read-back alone:
                 per node the sum of requests is within allocatable and at
                 most the node's pod limit; per spread selector the zone
                 counts differ by at most maxSkew;
4. identity    — on K positions of the window's commit order, drawn from
                 the seed, the plain reference (``benchmarks/reference``),
                 given the nodes, the init pods and THE SYSTEM'S OWN
                 decisions before position k, picks the same node for pod
                 k.  No tolerance: a decision is equal or it is not.

Every number compared is returned beside its limit and printed by the
caller.  Nothing here imports the program.
"""

from __future__ import annotations

import random
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import workload
from benchmarks.reference import resource as RR
from benchmarks.reference import types as RT
from benchmarks.reference.pipeline import schedule_one
from benchmarks.reference.state import OracleState

_SUFFIX = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}


def quantity(name: str, value) -> int:
    """cpu in millicores, everything else in plain units (bytes, pods)."""
    s = str(value)
    if name == "cpu":
        return int(s[:-1]) if s.endswith("m") else int(float(s) * 1000)
    for suf, mult in _SUFFIX.items():
        if s.endswith(suf):
            return int(s[: -len(suf)]) * mult
    return int(s)


class Check:
    """One compared number beside its limit."""

    def __init__(self, part: str, name: str, value, limit, ok: bool, note: str = ""):
        self.part, self.name, self.value, self.limit = part, name, value, limit
        self.ok, self.note = bool(ok), note

    def line(self) -> str:
        mark = "ok  " if self.ok else "FAIL"
        tail = f"  ({self.note})" if self.note else ""
        return f"correct {mark} {self.part}.{self.name}: {self.value} (limit {self.limit}){tail}"


# ---- 1. guarantee -----------------------------------------------------------


def check_guarantee(
    acked: Dict[str, Tuple[str, float]],
    store: Dict[str, str],
    double_binds: Sequence,
    expected_uids: Iterable[str],
) -> Tuple[List[Check], set]:
    wrong = [
        (uid, node, store.get(uid))
        for uid, (node, _t) in acked.items()
        if store.get(uid) != node
    ]
    good = {uid for uid, (node, _t) in acked.items() if store.get(uid) == node}
    unknown = [u for u in expected_uids if u not in store]
    return [
        Check("guarantee", "readback_mismatches", len(wrong), 0, not wrong,
              f"first {wrong[:3]}" if wrong else f"{len(acked)} acknowledged binds read back"),
        Check("guarantee", "double_binds", len(double_binds), 0, not double_binds,
              f"first {list(double_binds)[:3]}" if double_binds else ""),
        Check("guarantee", "pods_missing_from_store", len(unknown), 0, not unknown),
    ], good


# ---- 3. feasibility ---------------------------------------------------------


def check_feasibility(
    node_specs: Sequence[dict],
    pod_specs: Sequence[dict],
    store: Dict[str, str],
) -> List[Check]:
    cap = {
        n["name"]: {k: quantity(k, v) for k, v in n["capacity"].items()}
        for n in node_specs
    }
    zone = {n["name"]: n["labels"].get(workload.ZONE_LABEL, "") for n in node_specs}
    zones = sorted(set(zone.values()))
    used: Dict[str, Dict[str, int]] = {n: {} for n in cap}
    selectors: Dict[Tuple, int] = {}  # (topology key, sorted labels) -> maxSkew
    placed = []
    unknown_nodes = 0
    for spec in pod_specs:
        node = store.get(workload.uid_of(spec)) or ""
        for c in spec.get("topology_spread", ()):
            if c["when_unsatisfiable"] == "DoNotSchedule":
                key = (c["topology_key"], tuple(sorted(c["match_labels"].items())))
                selectors[key] = min(selectors.get(key, c["max_skew"]), c["max_skew"])
        if not node:
            continue
        if node not in cap:
            unknown_nodes += 1
            continue
        placed.append((spec, node))
        u = used[node]
        for k, v in spec["requests"].items():
            u[k] = u.get(k, 0) + quantity(k, v)
        u["pods"] = u.get("pods", 0) + 1
    over = [
        (n, k, u[k], cap[n].get(k, 0))
        for n, u in used.items()
        for k in u
        if u[k] > cap[n].get(k, 0)
    ]
    out = [
        Check("feasibility", "overcommitted_node_resources", len(over), 0, not over,
              f"first {over[:3]}" if over else f"{len(placed)} placed pods on {len(cap)} nodes"),
        Check("feasibility", "pods_on_unknown_nodes", unknown_nodes, 0, not unknown_nodes),
    ]
    worst, worst_sel = 0, None
    over_skew = 0
    for (tkey, labels), max_skew in selectors.items():
        if tkey != workload.ZONE_LABEL:
            continue
        counts = {z: 0 for z in zones}
        want = dict(labels)
        for spec, node in placed:
            if all(spec["labels"].get(k) == v for k, v in want.items()):
                counts[zone[node]] += 1
        skew = max(counts.values()) - min(counts.values())
        if skew - max_skew > worst or worst_sel is None:
            worst, worst_sel = skew - max_skew, (labels, skew, max_skew)
        if skew > max_skew:
            over_skew += 1
    if selectors:
        out.append(
            Check("feasibility", "selectors_over_max_skew", over_skew, 0, not over_skew,
                  f"{len(selectors)} selectors; tightest {worst_sel}")
        )
    return out


# ---- 4. identity on a seeded sample ------------------------------------------


def sample_positions(n: int, k: int, seed: int) -> List[int]:
    k = min(k, n)
    return sorted(random.Random(f"{seed}/identity").sample(range(n), k))


class ReferenceReplay:
    """The reference's cluster state, advanced along the system's own
    commit order; asks the reference for one pod at a time."""

    def __init__(self, node_specs, bound_before: Iterable[Tuple[dict, str]]):
        nodes = [workload.build_node(RT, RR, s) for s in node_specs]
        self.state = OracleState.build(nodes)
        self.trail: list = []  # pods placed along the window, oldest first
        for spec, node in bound_before:
            self.state.place(workload.build_pod(RT, spec, node_name=node))

    def place(self, spec: dict, node: str) -> None:
        pod = workload.build_pod(RT, spec, node_name=node)
        self.state.place(pod)
        self.trail.append(pod)

    def choose(self, spec: dict) -> Optional[str]:
        return schedule_one(workload.build_pod(RT, spec), self.state).node


def check_identity(
    node_specs: Sequence[dict],
    bound_before: Sequence[Tuple[dict, str]],
    order: Sequence[Tuple[dict, Optional[str]]],
    k: int,
    seed: int,
    positions: Optional[Sequence[int]] = None,
    on_position=None,
) -> List[Check]:
    """``bound_before``: (spec, node) of every pod bound before the window
    (init pods, warm-up).  ``order``: (spec, decided node or None) in the
    window's commit order.  ``on_position(replay, pos, spec, decided,
    want)`` lets a control read more at the same positions."""
    if positions is None:
        positions = sample_positions(len(order), k, seed)
    replay = ReferenceReplay(node_specs, bound_before)
    diffs = []
    at = 0
    for pos in positions:
        while at < pos:
            spec, node = order[at]
            if node:
                replay.place(spec, node)
            at += 1
        spec, decided = order[pos]
        want = replay.choose(spec)
        if on_position is not None:
            on_position(replay, pos, spec, decided, want)
        if want != decided:
            diffs.append((pos, spec["name"], decided, want))
    return [
        Check("identity", "decisions_differing_from_reference", len(diffs), 0, not diffs,
              f"first {diffs[:3]}" if diffs
              else f"{len(positions)} positions of {len(order)} compared"),
        Check("identity", "positions_compared", len(positions), f">={min(k, len(order))}",
              len(positions) >= min(k, len(order)) and len(positions) > 0),
    ]


# ---- 2. the device answered ---------------------------------------------------


def check_device(
    device: dict,
    faults: Sequence,
    logged_faults: Sequence[str],
    dispatches: Dict[str, int],
    expect_kernels: Sequence[str],
    compiles_in_window: int,
    require_chip: bool,
) -> List[Check]:
    hit = [k for k in expect_kernels if dispatches.get(k, 0) > 0]
    out = [
        Check("device", "breaker_faults", len(faults), 0, not faults,
              f"first {list(faults)[:2]}" if faults else ""),
        Check("device", "device_faults_logged", len(logged_faults), 0, not logged_faults,
              logged_faults[0][:300] if logged_faults else ""),
        Check("device", "dispatches_of_the_cells_kernels",
              sum(dispatches.get(k, 0) for k in expect_kernels), ">=1", bool(hit),
              f"one of {list(expect_kernels)}; dispatched {hit}"),
    ]
    out.append(Check("device", "compiles_in_window", compiles_in_window, 0, compiles_in_window == 0))
    is_tpu = device.get("platform") == "tpu"
    out.append(
        Check("device", "platform", device.get("platform"), "tpu",
              is_tpu or not require_chip,
              "" if is_tpu else "not a TPU: a rehearsal, never a result")
    )
    if is_tpu:
        peak = device.get("memory_peak_bytes", 0)
        out.append(Check("device", "memory_peak_bytes", peak, ">0", peak > 0))
    return out
