#!/usr/bin/env python3
"""CPU rehearsal of a cell at cut-down sizes, for finding faults only.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload basic-5k.backlog \
        --nodes 64 --pods 128 [--init 16] [--seed 1] [--seconds 5] [--trace 1] [--all-positions]

Skips the look for a chip and cuts the configuration's counts; everything
else is ``run.py``'s path.  Its numbers are NOT device numbers and go into
no record; its last line says so by naming the device as JAX reports it.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/rehearse.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--pods", type=int, default=128)
    ap.add_argument("--init", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--all-positions", action="store_true",
                    help="identity on every position of the window, not a sample")
    args = ap.parse_args(argv)

    from benchmarks import cells, runner

    bench = cells.benchmark()
    cell = cells.cut(cells.cell(args.workload, bench), args.nodes, args.pods, args.init)
    positions = list(range(args.pods)) if args.all_positions else None
    result = runner.run_cell(
        cell, bench, args.seed, args.seconds, bool(args.trace), _T0,
        require_chip=False, identity_positions=positions,
    )
    print("REHEARSAL, not a result: " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
