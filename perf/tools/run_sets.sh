#!/bin/bash
# bash perf/tools/run_sets.sh <cell> <runs per set> <seconds> [traced runs]
# Two sets of untraced runs with the same seeds in both (each run of a
# set another seed, large ones as the driver's are), then traced runs.
# Every run's last line goes to chiprun_out/sets-<cell>.jsonl with its
# set, seed and trace flag; spread.py reads that file. Run it through
# chiprun, all runs of one cell in one call.
cell=$1; n=$2; secs=$3; traced=${4:-1}
mkdir -p chiprun_out
out=chiprun_out/sets-$cell.jsonl
log=chiprun_out/sets-$cell.log
one() {  # set seed trace
  python3 perf/run.py --workload "$cell" --seed "$2" --seconds "$secs" --trace "$3" > chiprun_out/.run.log 2>&1
  rc=$?
  cat chiprun_out/.run.log >> "$log"
  last=$(grep -a '^{' chiprun_out/.run.log | tail -n 1)
  echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"line\": ${last:-null}}" >> "$out"
  echo "set $1 seed $2 trace $3 rc $rc: $(echo "$last" | cut -c1-420)"
  grep -a 'PROBLEM\|Error' chiprun_out/.run.log | head -n 5
}
for s in 1 2; do
  for i in $(seq 1 "$n"); do
    one "$s" $((2147480000 + 7919 * i)) 0
    if [ "$s$i" = 11 ] && ! grep -q '"rc": 0, "line": {' "$out"; then
      echo "the first run failed: stopping"; tail -n 30 "$log"; exit 1
    fi
  done
done
for i in $(seq 1 "$traced"); do one 0 $((2147480000 + 7919 * i)) 1; done
rm -f chiprun_out/.run.log
