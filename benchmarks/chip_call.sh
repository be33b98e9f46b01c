#!/bin/bash
# Runs of ONE cell in one chip call, so they share a compilation: the first
# run of a cell in a checkout compiles (cold), the rest load from the cache.
#   chiprun --timeout 1800 -- bash benchmarks/chip_call.sh <workload> <seconds> <trace 0|1> <seed> [<seed>...]
# Each run's whole output goes to chiprun_out/<workload>.t<trace>.<seed>.log; the
# lines that matter (progress, any check that failed, the result line) are echoed.
W=$1; S=$2; T=$3; shift 3
mkdir -p chiprun_out
for seed in "$@"; do
  log=chiprun_out/$W.t$T.$seed.log
  python3 benchmarks/run.py --workload "$W" --seed "$seed" --seconds "$S" --trace "$T" > "$log" 2>&1
  echo "== $W seed $seed trace $T rc=$?"
  grep "^\[bench\]" "$log" | grep -v "correct ok"
  tail -n 1 "$log"
done
