#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, every token
# stamp dumped: what the bounds in BENCHMARK.json were set from.
#   chiprun -- bash benchmarks/tools/run_sets.sh <cell> <seconds> <outdir> <seed> [<seed> ...]
cell=$1; seconds=$2; out=$3; shift 3
for set in 1 2; do
  mkdir -p "$out/set$set"
  for seed in "$@"; do
    log="$out/set$set/$cell.$seed.log"
    python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace 0 --dump "$out/set$set" > "$log" 2>&1
    echo "set$set seed=$seed rc=$?"
    grep -E "^(setup|window|check|Traceback)" "$log" | cut -c1-700
    tail -n 1 "$log" | cut -c1-500
  done
done
