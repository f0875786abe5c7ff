#!/bin/sh
# The whole proof of one cell, in one chip call (benchmarks/README.md):
#   chiprun --timeout 3500 -- sh benchmarks/prove.sh <cell> <run_seconds> <short_seconds> <all|controls|sets> ['<driver-args of the program's own lower-precision path>']
# 1. control.py on 3 seeds with the controls and faults, and on 9 more
#    without: the readings the limits of `correct` are set from;
# 2. two sets of 6 runs with the same seeds in both, then 3 traced runs:
#    the spreads the bounds are set from (benchmarks/spread.py reads them);
# 3. a cut of the last traced run's trace, for the reduction's fixture.
# Everything lands under chiprun_out/.
W=$1; T=$2; SHORT=$3; STAGES=$4; OWN=$5
mkdir -p chiprun_out
if [ "$STAGES" != sets ]; then
python3 benchmarks/control.py --workload $W --seeds 4100000001,4100000002,4100000003 --seconds $SHORT --controls 1 --tag .controls 2> chiprun_out/control.$W.controls.err | cut -c1-3000
if [ -n "$OWN" ]; then
  python3 benchmarks/control.py --workload $W --seeds 4100000001,4100000002,4100000003 --seconds $SHORT --driver-args "$OWN" --tag .own 2> chiprun_out/control.$W.own.err | cut -c1-1500
fi
python3 benchmarks/control.py --workload $W --seeds 4500000001,4500000002,4500000003,4500000004,4500000005,4500000006,4500000007,4500000008,4500000009 --seconds $SHORT --tag .program 2> chiprun_out/control.$W.program.err | cut -c1-1500
fi
[ "$STAGES" = controls ] && exit 0
OUT=chiprun_out/sets.$W.jsonl
for SET in 1 2; do
  for SEED in 2500000011 2500000022 2500000033 2500000044 2500000055 2500000066; do
    LINE=$(python3 benchmarks/run.py --workload $W --seed $SEED --seconds $T --trace 0 2> chiprun_out/last.err | tail -n 1)
    echo "{\"set\": $SET, \"seed\": $SEED, \"line\": $LINE}" >> $OUT
    echo "set $SET seed $SEED: $(echo "$LINE" | cut -c1-420)"
  done
done
for SEED in 2600000011 2600000022 2600000033; do
  START=$(date +%s)
  LINE=$(python3 benchmarks/run.py --workload $W --seed $SEED --seconds $T --trace 1 2> chiprun_out/last.err | tail -n 1)
  echo "{\"set\": \"trace\", \"seed\": $SEED, \"line\": $LINE}" >> $OUT
  echo "trace seed $SEED took $(( $(date +%s) - START )) s: $(echo "$LINE" | cut -c1-1800)"
done
tail -n 12 chiprun_out/last.err | cut -c1-600
ls -l .bench_work/$W/trace/plugins/profile/*/
timeout 600 python3 benchmarks/trace_dump.py .bench_work/$W/trace chiprun_out/$W.trace_cut.json.gz 0.45 "custom-call" > chiprun_out/$W.trace_dump.txt 2>&1
ls -l chiprun_out
