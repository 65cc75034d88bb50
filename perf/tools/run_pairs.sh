#!/bin/bash
# bash perf/tools/run_pairs.sh <parent dir> <change dir> <cell> <pairs> <seconds> [traced]
# Two checkouts against each other on one chip, in one chiprun call:
# each pair one seed (large ones, as the driver's are), the sides
# alternating (parent, change, change, parent, ..), then `traced`
# traced runs a side (default 0). Unpack both under the repo where
# .gitignore lists them and chiprun copies them:
#   git archive HEAD | tar -x -C .parent      (mkdir first)
#   git archive $(git write-tree) | tar -x -C .final   (the committed files alone)
# with this PR's BENCHMARK.json and perf/ laid over .parent where a traced
# parent run is to report what the benchmark now has. Every run's last
# line goes to chiprun_out/pairs-<cell>.jsonl with its side, seed and
# trace flag, and its whole output to chiprun_out/pairs-<cell>.log.
parent=$1; change=$2; cell=$3; n=$4; secs=$5; traced=${6:-0}
root=$(pwd)
mkdir -p chiprun_out
out=$root/chiprun_out/pairs-$cell.jsonl
log=$root/chiprun_out/pairs-$cell.log
one() {  # side dir seed trace
  (cd "$2" && python3 perf/run.py --workload "$cell" --seed "$3" --seconds "$secs" --trace "$4") > "$root/chiprun_out/.run.log" 2>&1
  rc=$?
  cat "$root/chiprun_out/.run.log" >> "$log"
  last=$(grep -a '^{' "$root/chiprun_out/.run.log" | tail -n 1)
  echo "{\"side\": \"$1\", \"seed\": $3, \"trace\": $4, \"rc\": $rc, \"line\": ${last:-null}}" >> "$out"
  echo "$cell $1 seed $3 trace $4 rc $rc: $(echo "$last" | cut -c1-420)"
  grep -a 'PROBLEM\|Error' "$root/chiprun_out/.run.log" | head -n 5
}
for i in $(seq 1 "$n"); do
  seed=$((2147480000 + 7919 * i + 104729 * ${PAIR_SEED_BASE:-0}))
  if [ $((i % 2)) = 1 ]; then
    one parent "$parent" "$seed" 0; one change "$change" "$seed" 0
  else
    one change "$change" "$seed" 0; one parent "$parent" "$seed" 0
  fi
done
for i in $(seq 1 "$traced"); do
  seed=$((2147480000 + 7919 * (i + n) + 104729 * ${PAIR_SEED_BASE:-0}))
  one parent "$parent" "$seed" 1; one change "$change" "$seed" 1
done
rm -f "$root/chiprun_out/.run.log"
