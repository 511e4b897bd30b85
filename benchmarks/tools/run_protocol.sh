#!/bin/bash
# The driver's shape of a check, for the builder: two sets of runs, a
# fresh process a run, every cell of BENCHMARK.json in rotation (so that
# between two runs of one cell the others have run), every seed of a set
# different and the same seeds in both sets, --trace 0, every token
# stamp dumped. One cell back to back
# with the same seeds in both sets (run_sets.sh, until PR 27) read a
# fifth of the spread the driver read on the co-located cell (PERF.md
# section 2); name cells to run fewer than all.
#   chiprun --timeout 3500 -- bash benchmarks/tools/run_protocol.sh <outdir> <seconds> <runs a set> <first seed> [cell ...]
# SETS="2" runs the second set alone (a chip call lasts an hour at most,
# and two sets of six runs of four cells take 75 minutes).
# then, per cell:
#   python3 benchmarks/tools/steadiness.py <cell> <outdir>/set1 <outdir>/set2
out=$1; seconds=$2; runs=$3; seed=$4; shift 4
cells=("$@")
if [ ${#cells[@]} -eq 0 ]; then
  mapfile -t cells < <(python3 -c "import json; [print(w['name']) for w in json.load(open('BENCHMARK.json'))['workloads']]")
fi
first=$seed
for set in ${SETS:-1 2}; do
  seed=$first
  mkdir -p "$out/set$set"
  for i in $(seq 1 "$runs"); do
    for cell in "${cells[@]}"; do
      seed=$((seed + 10007))
      log="$out/set$set/$cell.$seed.log"
      python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace 0 --dump "$out/set$set" > "$log" 2>&1
      echo "set$set run$i $cell seed=$seed rc=$?"
      grep -E "^(setup|window|Traceback)" "$log" | cut -c1-420
      tail -n 1 "$log" | cut -c1-330
    done
  done
done
