#!/bin/bash
# Run one cell several times, each run with another seed, and keep every
# printed line: what a bound is set from (chipbench/tools/spread.py reads
# the kept lines). Runs on the machine it is started on.
#   chipbench/tools/measure.sh <cell> <first seed> <untraced runs> <traced runs> <keep dir>
cell=$1; seed=$2; runs=$3; traced=$4; keep=$5
mkdir -p "$keep"
for i in $(seq 0 $((runs - 1))); do
  python3 -m chipbench.run --workload "$cell" --seed $((seed + i)) --trace 0 \
    --keep "$keep" 2>>"$keep/$cell.err" | tail -n 1 | cut -c1-600
  echo "rc=${PIPESTATUS[0]} $cell seed $((seed + i)) trace 0"
done
for i in $(seq 0 $((traced - 1))); do
  python3 -m chipbench.run --workload "$cell" --seed $((seed + 100 + i)) --trace 1 \
    --keep "$keep" 2>>"$keep/$cell.err" | tail -n 1 | cut -c1-300
  echo "rc=${PIPESTATUS[0]} $cell seed $((seed + 100 + i)) trace 1"
  python3 -m chipbench.tools.trace_summary "$keep" 14 \
    >"$keep/$cell.trace_summary.txt" 2>/dev/null
  rm -rf "$keep/plugins"
done
