#!/usr/bin/env python3
"""Controls for a cell whose pods carry pod (anti-)affinity terms: does the
identity check see the term, and a decision made on a stale state?

``control.py`` asks what a one-commit-stale decision would choose.  This
file asks, at the very positions a run's identity check samples (same
cluster state, same pods), what the plain reference chooses

* ``both_stripped``: with BOTH directions of the terms removed from the
  incoming pod — its ``affinity`` (the terms it carries) and its labels (what
  the placed pods' terms select): a program that dropped the inter-pod
  plugin;
* ``incoming_stripped``: with only its ``affinity`` removed: a program that
  dropped the incoming pod's own terms and kept the symmetric path;
* ``stale`` at each ``--lags``: ``control.py``'s control, lag 1 and a whole
  batch (a wave committed as speculated, conflicts never resolved).

A count is the number of sampled positions at which the control differs from
the reference; had the control been the system, ``correct`` would have counted
it against the limit 0.  A count of 0 says the check is BLIND to that fault
on this shape (``:93`` and ``:312`` are blind to ``both_stripped``, PERF.md
Findings PR 29; ``:354`` is blind to ``incoming_stripped``, PR 30).

    python3 benchmarks/control_terms.py --workload interpod-5k.backlog --seeds 11,12 --seconds 30

runs the cell once per seed IN ONE PROCESS on the chip, prints the readings
and writes them to ``chiprun_out/control_terms_<workload>.json``.  It exits 1
where the program is not ``correct`` or ``both_stripped`` differs nowhere.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TermControls:
    """``on_position`` hook of ``correct.check_identity``."""

    def __init__(self, lags) -> None:
        self.positions = 0
        self.diffs = {"both_stripped": 0, "incoming_stripped": 0, **{f"stale_lag{lag}": 0 for lag in lags}}
        self.lags = list(lags)

    def __call__(self, replay, pos, spec, decided, want) -> None:
        self.positions += 1
        self.diffs["both_stripped"] += replay.choose({**spec, "affinity": None, "labels": {}}) != want
        self.diffs["incoming_stripped"] += replay.choose({**spec, "affinity": None}) != want
        for lag in self.lags:  # as control.py's Controls, without its float32 reading
            held = replay.trail[-lag:] if lag else []
            for pod in held:
                replay.state.unplace(pod)
            try:
                self.diffs[f"stale_lag{lag}"] += replay.choose(spec) != want
            finally:
                for pod in held:
                    replay.state.place(pod)

    def readings(self) -> dict:
        return {"positions": self.positions, **self.diffs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/control_terms.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--lags", default="1,512", help="comma-separated commits a stale decision lacks")
    ap.add_argument("--rehearse-sizes", default="",
                    help="nodes,pods,init[,batch]: a CPU rehearsal at cut sizes, never a result")
    args = ap.parse_args(argv)

    from benchmarks import cells, runner

    bench = cells.benchmark()
    cut = [int(x) for x in args.rehearse_sizes.split(",")] if args.rehearse_sizes else []
    tamper = None
    if len(cut) == 4:
        def tamper(cluster):
            cluster.sched.config.batch_size = cut[3]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = cells.cell(args.workload, bench)
        if cut:
            cells.cut(cell, *cut[:3])
        ctl = TermControls([int(x) for x in args.lags.split(",")])
        res = runner.run_cell(
            cell, bench, seed, args.seconds, False, time.perf_counter(),
            require_chip=not cut, tamper=tamper, on_identity_position=ctl,
        )
        row = {"seed": seed, "correct": res["correct"], "failed": res["failed"],
               "metrics": res["metrics"], **ctl.readings()}
        rows.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)
    out = os.path.join(cells.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"control_terms_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    sound = all(r["correct"] for r in rows)
    seen = all(r["both_stripped"] >= 1 for r in rows)
    print(f"[control] program correct on every seed: {sound}; "
          f"the reference without the terms differs on every seed: {seen}")
    return 0 if sound and seen else 1


if __name__ == "__main__":
    sys.exit(main())
