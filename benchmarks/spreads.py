#!/usr/bin/env python3
"""Median and spread of each metric over the result lines of some runs.

    python3 benchmarks/spreads.py chiprun_out/basic-5k.backlog.t0.*.log

A spread is the distance between the first and the third quartile, as
``statistics.quantiles(values, n=4)`` gives them, as a share of the median
(the rule the bounds in BENCHMARK.json are set by: about five times the
widest spread over the cells, never under 1 %)."""

import json
import statistics
import sys


def main(paths) -> int:
    values, bad = {}, []
    for path in paths:
        with open(path) as f:
            line = f.read().strip().splitlines()[-1]
        try:
            doc = json.loads(line)
        except ValueError:
            bad.append(path)
            continue
        if not doc["correct"]:
            bad.append(path)
        for name, m in doc["metrics"].items():
            values.setdefault(name, []).append(m["value"])
    for name, v in values.items():
        med = statistics.median(v)
        if len(v) >= 2:
            q = statistics.quantiles(v, n=4)
            spread = (q[2] - q[0]) / med if med else float("nan")
        else:
            spread = float("nan")
        print(f"{name}: n={len(v)} median={med:.6g} min={min(v):.6g} max={max(v):.6g} "
              f"spread={100 * spread:.2f}%")
    if bad:
        print(f"NOT CORRECT or no result line: {bad}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
