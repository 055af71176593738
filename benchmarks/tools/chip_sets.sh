#!/bin/bash
# The two full sets of one cell, in one call: the same seeds in both sets.
#   chiprun --chips N -- bash benchmarks/tools/chip_sets.sh <workload> <seconds> <first seed> [runs per set] [first set]
w=$1; secs=$2; base=$3; n=${4:-6}; first=${5:-1}
mkdir -p chiprun_out/sets/$w
for set in $(seq $first 2); do
  for i in $(seq 0 $((n-1))); do
    seed=$((base+i))
    python3 benchmarks/run.py --workload $w --seed $seed --seconds $secs --trace 0 \
      > chiprun_out/sets/$w/set${set}_$seed.out 2> chiprun_out/sets/$w/set${set}_$seed.err
    echo "rc=$? set=$set seed=$seed $(tail -n 1 chiprun_out/sets/$w/set${set}_$seed.out | cut -c1-600)"
  done
done
