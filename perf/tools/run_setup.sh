#!/bin/bash
# bash perf/tools/run_setup.sh <cell> [parent dir]
# Where a cell's set-up goes, in one chiprun call (a call starts with an
# empty compile cache, so its first run of a checkout compiles and its
# later ones read): the change traced cold, traced warm and untraced
# warm (the last two on one seed: what tracing costs set-up); with a
# parent checkout (see run_pairs.sh) the parent untraced cold and warm,
# the warm one on that same seed. Every run's last line goes to
# chiprun_out/setup-<cell>.jsonl with its side, cache state, seed and
# trace flag, its whole output to chiprun_out/setup-<cell>.log.
cell=$1; parent=$2; secs=${SETUP_RUN_SECONDS:-20}
root=$(pwd)
mkdir -p chiprun_out
out=$root/chiprun_out/setup-$cell.jsonl
log=$root/chiprun_out/setup-$cell.log
one() {  # side dir cache seed trace
  (cd "$2" && python3 perf/run.py --workload "$cell" --seed "$4" --seconds "$secs" --trace "$5") > "$root/chiprun_out/.run.log" 2>&1
  rc=$?
  cat "$root/chiprun_out/.run.log" >> "$log"
  last=$(grep -a '^{' "$root/chiprun_out/.run.log" | tail -n 1)
  echo "{\"side\": \"$1\", \"cache\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"line\": ${last:-null}}" >> "$out"
  echo "$cell $1 $3 seed $4 trace $5 rc $rc: $(echo "$last" | python3 -c '
import json, sys
try:
    m = json.loads(sys.stdin.read())
    print(m["correct"], {k: round(v["value"], 3) for k, v in m["metrics"].items()
          if k.split(".")[0] in ("setup_s", "train_tokens_per_s", "cache", "setup", "exec", "lower")})
except Exception as e:
    print("no line:", e)')"
  grep -a 'PROBLEM\|Error\|perf: set-up\|perf: first calls' "$root/chiprun_out/.run.log" | head -n 8
}
base=$((2147480000 + 104729 * ${SETUP_SEED_BASE:-0}))
one change . cold $((base + 7919)) 1
one change . warm $((base + 15838)) 1
one change . warm $((base + 15838)) 0
if [ -n "$parent" ]; then
  one parent "$parent" cold $((base + 23757)) 0
  one parent "$parent" warm $((base + 15838)) 0
fi
rm -f "$root/chiprun_out/.run.log"
