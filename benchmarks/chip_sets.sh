#!/bin/bash
# Two sets of runs of ONE cell with the same seeds in both, and their spreads:
#   chiprun --timeout 3000 -- bash benchmarks/chip_sets.sh <workload> <seed> [<seed>...]
W=$1; shift
bash benchmarks/chip_call.sh $W 30 0 "$@"
mkdir -p chiprun_out/set1; mv chiprun_out/$W.t0.*.log chiprun_out/set1/
bash benchmarks/chip_call.sh $W 30 0 "$@"
mkdir -p chiprun_out/set2; mv chiprun_out/$W.t0.*.log chiprun_out/set2/
echo "== set 1"; python3 benchmarks/spreads.py chiprun_out/set1/$W.*.log
echo "== set 2"; python3 benchmarks/spreads.py chiprun_out/set2/$W.*.log
