#!/bin/bash
# Trial runs of one cell on the chip, in one call:
#   chiprun --chips N -- bash benchmarks/tools/chip_trial.sh <workload> <seconds> "<seed> <trace>" ...
# Full output goes to chiprun_out/trial/<workload>/; the end of each run is echoed.
w=$1; secs=$2; shift 2
mkdir -p chiprun_out/trial/$w
for a in "$@"; do
  set -- $a
  python3 benchmarks/run.py --workload $w --seed $1 --seconds $secs --trace $2 \
    > chiprun_out/trial/$w/run_$1_t$2.out 2> chiprun_out/trial/$w/run_$1_t$2.err
  echo "rc=$? workload=$w seed=$1 trace=$2"
  grep -v '"line": "blocks"' chiprun_out/trial/$w/run_$1_t$2.out | tail -c ${TAIL:-2500}
  grep -v "hugepages\|warnings.warn" chiprun_out/trial/$w/run_$1_t$2.err | tail -8
done
