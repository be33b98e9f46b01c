#!/usr/bin/env python3
"""The controls of ``correct``: what a tempting shortcut would decide.

A control is the plain reference put in the program's place with ONE
guarantee of the configuration broken, read at the very positions a run's
identity check samples (same cluster state, same pods):

* ``stale``: "decisions equal the serial reference's in queue order" —
  broken by deciding pod k against a state that lacks the ``lag`` commits
  just before it (a batch committed without resolving its conflicts, a
  speculation never re-checked).  Feasibility still holds, so only the
  identity check can see it.
* ``f32``: the same decision with NodeResourcesBalancedAllocation's
  fractions in float32 instead of exact arithmetic (the precision step a
  device kernel would be tempted by).

For each, the number of sampled positions at which the control's choice
differs from the reference's is what ``correct`` would have counted had
the control been the system; the limit is 0, so a control has failed the
check when its count is 1 or more.

    python3 benchmarks/control.py --workload basic-5k.backlog --seeds 11,12,13 --seconds 20

runs the cell once per seed IN ONE PROCESS on the chip (a short window at
the cell's own size), prints the program's and the controls' readings, and
writes them to ``chiprun_out/control_<workload>.json``.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _balanced_f32(pod, ns, resources=("cpu", "memory")):
    """score_balanced_allocation with float32 fractions."""
    from benchmarks.reference import scores as S

    f32 = np.float32
    fr = []
    for name in resources:
        alloc, requested = S._alloc_and_requested(pod, ns, name, use_requested=True)
        if alloc == 0:
            continue
        fr.append(min(f32(requested) / f32(alloc), f32(1.0)))
    if len(fr) == 2:
        std = abs(fr[0] - fr[1]) / f32(2)
    elif len(fr) > 2:
        mean = sum(fr, f32(0)) / f32(len(fr))
        std = np.sqrt(sum(((f - mean) ** 2 for f in fr), f32(0)) / f32(len(fr)))
    else:
        std = f32(0)
    return int((f32(1) - std) * f32(S.MAX_NODE_SCORE))


class Controls:
    """``on_position`` hook of ``correct.check_identity``."""

    def __init__(self, lag: int = 1) -> None:
        self.lag = lag
        self.positions = 0
        self.stale_diffs = 0
        self.f32_diffs = 0

    def __call__(self, replay, pos, spec, decided, want) -> None:
        from benchmarks.reference import scores as S

        self.positions += 1
        held = replay.trail[-self.lag:] if self.lag else []
        for pod in held:
            replay.state.unplace(pod)
        try:
            stale = replay.choose(spec)
        finally:
            for pod in held:
                replay.state.place(pod)
        self.stale_diffs += stale != want
        exact = S.score_balanced_allocation
        S.score_balanced_allocation = _balanced_f32
        try:
            low = replay.choose(spec)
        finally:
            S.score_balanced_allocation = exact
        self.f32_diffs += low != want

    def readings(self) -> dict:
        return {
            "positions": self.positions,
            "stale_mismatches": self.stale_diffs,
            "f32_mismatches": self.f32_diffs,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--lag", type=int, default=1)
    ap.add_argument("--rehearse-sizes", default="",
                    help="nodes,pods,init: a CPU rehearsal at cut sizes, never a result")
    args = ap.parse_args(argv)

    from benchmarks import cells, runner

    bench = cells.benchmark()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = cells.cell(args.workload, bench)
        if args.rehearse_sizes:
            cells.cut(cell, *(int(x) for x in args.rehearse_sizes.split(",")))
        ctl = Controls(args.lag)
        res = runner.run_cell(
            cell, bench, seed, args.seconds, False, time.perf_counter(),
            require_chip=not args.rehearse_sizes, on_identity_position=ctl,
        )
        row = {"seed": seed, "correct": res["correct"], "failed": res["failed"],
               "metrics": res["metrics"], **ctl.readings()}
        rows.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)
    out = os.path.join(cells.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"control_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    sound = all(r["correct"] for r in rows)
    caught = all(r["stale_mismatches"] >= 1 for r in rows)
    print(f"[control] program correct on every seed: {sound}; stale control fails on every seed: {caught}")
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
