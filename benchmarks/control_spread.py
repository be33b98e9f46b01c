#!/usr/bin/env python3
"""Controls for a cell whose pods carry SOFT (``ScheduleAnyway``) topology
spread constraints: does the identity check see them, and a decision made on a
stale state?

A soft constraint has no limit to recount from the read-back
(``correct.check_feasibility`` counts ``DoNotSchedule`` selectors only), so it
is held by identity alone.  This file asks, at the very positions a run's
identity check samples (same cluster state, same pods), what the plain
reference chooses

* ``all_stripped``: with every spread constraint removed from the incoming
  pod: a program that dropped the PodTopologySpread score;
* ``<key>_stripped``, once a topology key the pod's constraints name (the last
  path segment: ``zone``, ``hostname``): with the constraints on that key
  removed and the others kept: a program that scored one slot of two;
* ``stale`` at each ``--lags``: ``control.py``'s control, lag 1 and a whole
  batch (a wave committed as speculated, its score conflicts never resolved).

A count is the number of sampled positions at which the control differs from
the reference; had the control been the system, ``correct`` would have counted
it against the limit 0.  A count of 0 says the check is BLIND to that fault on
this shape.

    python3 benchmarks/control_spread.py --workload cl2load-5k.backlog-of-deployments --seeds 11,12 --seconds 30

runs the cell once per seed IN ONE PROCESS on the chip, prints the readings
and writes them to ``chiprun_out/control_spread_<workload>.json``.  It exits 1
where the program is not ``correct`` or ``all_stripped`` differs nowhere.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _short(topology_key: str) -> str:
    return topology_key.rsplit("/", 1)[-1]


class SpreadControls:
    """``on_position`` hook of ``correct.check_identity``."""

    def __init__(self, lags) -> None:
        self.positions = 0
        self.diffs = {"all_stripped": 0, **{f"stale_lag{lag}": 0 for lag in lags}}
        self.lags = list(lags)

    def __call__(self, replay, pos, spec, decided, want) -> None:
        self.positions += 1
        spread = spec["topology_spread"]
        self.diffs["all_stripped"] += replay.choose({**spec, "topology_spread": []}) != want
        for key in sorted({c["topology_key"] for c in spread}):
            kept = [c for c in spread if c["topology_key"] != key]
            name = f"{_short(key)}_stripped"
            self.diffs[name] = self.diffs.get(name, 0) + (replay.choose({**spec, "topology_spread": kept}) != want)
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
    ap = argparse.ArgumentParser(prog="benchmarks/control_spread.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--lags", default="1,512", help="comma-separated commits a stale decision lacks")
    args = ap.parse_args(argv)

    from benchmarks import cells, runner

    bench = cells.benchmark()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctl = SpreadControls([int(x) for x in args.lags.split(",")])
        res = runner.run_cell(
            cells.cell(args.workload, bench), bench, seed, args.seconds, False, time.perf_counter(),
            on_identity_position=ctl,
        )
        row = {"seed": seed, "correct": res["correct"], "failed": res["failed"],
               "metrics": res["metrics"], **ctl.readings()}
        rows.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)
    out = os.path.join(cells.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"control_spread_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    sound = all(r["correct"] for r in rows)
    seen = all(r["all_stripped"] >= 1 for r in rows)
    print(f"[control] program correct on every seed: {sound}; "
          f"the reference without the constraints differs on every seed: {seen}")
    return 0 if sound and seen else 1


if __name__ == "__main__":
    sys.exit(main())
