#!/bin/bash
# Measure cells as the bounds need them, from the root of a checkout on the chip:
#   bash chipbench/tools/sets.sh <cell> [<cell> ...]
# For each cell: one first run (it compiles; its set-up is recorded apart), two
# sets of RUNS runs (default 6) on the same seeds, and TRACED traced runs
# (default 3), on seeds from BASE (default 4000000000) up.  Each run's stdout
# and stderr go to chiprun_out/sets/<cell>/<tag>.<seed>.{out,err}.
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
for w in "$@"; do
  d=chiprun_out/sets/$w
  mkdir -p "$d"
  run() {  # tag seed trace
    python3 chipbench/run.py --workload "$w" --seed "$2" --seconds "$seconds" --trace "$3" \
      > "$d/$1.$2.out" 2> "$d/$1.$2.err"
    echo "$w $1 $2 rc=$? $(grep -E '^run:' "$d/$1.$2.err")"
    tail -1 "$d/$1.$2.out" | cut -c 1-600
  }
  b=${BASE:-4000000000}
  run first $((b + 1)) 0
  for i in $(seq 1 "${RUNS:-6}"); do run A $((b + 10 + i)) 0; done
  for i in $(seq 1 "${RUNS:-6}"); do run B $((b + 10 + i)) 0; done
  for i in $(seq 1 "${TRACED:-3}"); do run T $((b + 20 + i)) 1; done
done
