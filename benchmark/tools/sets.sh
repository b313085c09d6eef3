#!/bin/sh
# The chip call that runs one cell's runs in one process after another:
#
#   chiprun --timeout 3000 -- sh benchmark/tools/sets.sh <cell> <seconds> <name>:<seed>:<trace> ...
#
# Each run's output goes to chiprun_out/<cell>/<name>.jsonl (+ .err); the
# result line, the run's wall time (the driver allows 360 s) and the numbers
# `correct` compared are echoed. With DIR=<path>
# the runs are made from that directory (a `git archive` copy of the tree that
# .gitignore lists), to prove that the committed files are enough.
set -u
CELL=$1; SECS=$2; shift 2
OUT=$(pwd)/chiprun_out/$CELL; mkdir -p "$OUT"
cd "${DIR:-.}" || exit 1
for spec in "$@"; do
  name=${spec%%:*}; rest=${spec#*:}; seed=${rest%%:*}; trace=${rest#*:}
  t0=$(date +%s)
  python3 benchmark/run.py --workload "$CELL" --seed "$seed" --seconds "$SECS" --trace "$trace" \
    > "$OUT/$name.jsonl" 2> "$OUT/$name.err"
  echo "rc=$? wall_s=$(( $(date +%s) - t0 )) $name $(tail -n 1 "$OUT/$name.jsonl" | cut -c1-1500)"
  grep -h '"phase": "setup"\|"phase": "reference"' "$OUT/$name.jsonl" | cut -c1-400
  grep -h '"phase": "correct"' "$OUT/$name.jsonl" | grep -o '"number": "[^}]*' | grep 'image_gap\|steps' | cut -c1-260
done
