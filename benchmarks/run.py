#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it loads, warms up (set-up), measures for
``--seconds``, decides ``correct`` and prints, as the LAST line of its
standard output, one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Everything else (progress, each
number ``correct`` compared beside its limit) is on earlier lines.

The process is the only one that touches JAX; API server, reflectors,
scheduling loop, binding workers and load generator are its threads.  It
never sets or clears the JAX platform.  Where JAX finds no TPU, or fewer
chips than the cell asks for, it exits with code 2 and prints no result.
"""

import time

_T_PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import cells, runner

    bench = cells.benchmark()
    cell = cells.cell(args.workload, bench)
    try:
        result = runner.run_cell(
            cell, bench, args.seed, args.seconds, bool(args.trace), _T_PROCESS_START
        )
    except runner.NoChip as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
