#!/bin/bash
# Two sets of six runs of one cell, the same seeds in both, as the bounds
# are measured (and a traced run): one chip call per cell.
#   [RUNS=6] [CONTROL_RUNS=6] bash benchmarks/tools/run_set.sh <cell> <seconds> <seed0> [extra args]
# The extra arguments (--control) go to the first CONTROL_RUNS runs of the first set.
cell=$1; seconds=$2; seed0=$3; shift 3
mkdir -p chiprun_out/sets
for set in 1 2; do
  out=chiprun_out/sets/$cell.set$set.jsonl; : > $out
  for i in $(seq 0 $((${RUNS:-6} - 1))); do
    extra=""; [ $set = 1 ] && [ $i -lt ${CONTROL_RUNS:-6} ] && extra="$@"
    python3 benchmarks/run.py --workload $cell --seed $((seed0 + i)) --seconds $seconds --trace 0 $extra \
      > chiprun_out/sets/$cell.s$set.r$i.txt 2> chiprun_out/sets/$cell.s$set.r$i.err
    echo "set $set run $i rc=$?"
    grep "check\|control\|digest" chiprun_out/sets/$cell.s$set.r$i.txt | cut -c1-160
    tail -1 chiprun_out/sets/$cell.s$set.r$i.txt >> $out
  done
done
python3 benchmarks/run.py --workload $cell --seed $((seed0 + ${RUNS:-6})) --seconds $seconds --trace 1 \
  > chiprun_out/sets/$cell.traced.txt 2> chiprun_out/sets/$cell.traced.err
echo "traced rc=$?"; grep "check\|roofline" chiprun_out/sets/$cell.traced.txt | cut -c1-200; tail -1 chiprun_out/sets/$cell.traced.txt
python3 benchmarks/tools/spread.py chiprun_out/sets/$cell.set1.jsonl chiprun_out/sets/$cell.set2.jsonl
