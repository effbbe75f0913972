#!/bin/bash
# Measure one cell on the machine that holds its chips: the readings its
# limits are set from, two sets of runs for its bounds, and traced runs.
#
#   bash bench/measure_cell.sh CELL SECONDS OUT "CONTROL SEEDS" \
#       "FAULT SEEDS" "SET SEEDS" "TRACE SEEDS" [cold]
#
# In order, each step a process of its own, its output under OUT:
#   first, second   two runs (seeds 900001, 900002): the first compiles,
#                   the second has to find every program in the cache;
#   calibrate       bench/calibrate.py over the control and fault seeds
#                   (cal.jsonl), then --write-limits from it;
#   setA, setB      one run per set seed, twice over (same seeds);
#   trace           one --trace 1 run per trace seed.
# Each run's last line goes to OUT/lines.jsonl, its exit code and wall
# seconds to OUT/runs.txt.  Where JAX_COMPILATION_CACHE_DIR names a cache
# the machine keeps, the checkout's cache starts from it (unless "cold")
# and is written back at the end.  NOFIRST, NOSETA, NOSETB skip steps.
CELL=$1; RS=$2; O=$3; CTL=$4; FLT=$5; SET=$6; TRC=$7; COLD=$8
mkdir -p "$O"
MC=$JAX_COMPILATION_CACHE_DIR
if [ -n "$MC" ] && [ -z "$COLD" ]; then
  mkdir -p .jax_cache; cp -r "$MC"/. .jax_cache/ 2>/dev/null
fi
run() {  # seed trace name
  local t0=$(date +%s)
  timeout 1300 python3 bench/run.py --workload "$CELL" --seed "$1" \
    --seconds "$RS" --trace "$2" > "$O/$3.out" 2> "$O/$3.err"
  echo "$3 seed=$1 trace=$2 rc=$? wall=$(( $(date +%s) - t0 ))" \
    | tee -a "$O/runs.txt"
  tail -n 1 "$O/$3.out" >> "$O/lines.jsonl"
}
if [ -z "$NOFIRST" ]; then run 900001 0 first; run 900002 0 second; fi
if [ -n "$CTL$FLT" ]; then
  t0=$(date +%s)
  timeout 2400 python3 bench/calibrate.py --workload "$CELL" \
    --control-seeds "$(echo $CTL | tr ' ' ,)" \
    --fault-seeds "$(echo $FLT | tr ' ' ,)" \
    --out "$O/cal.jsonl" > "$O/cal.out" 2> "$O/cal.err"
  echo "calibrate rc=$? wall=$(( $(date +%s) - t0 ))" | tee -a "$O/runs.txt"
  python3 bench/calibrate.py --workload "$CELL" \
    --write-limits "$O/cal.jsonl" > "$O/limits.out" 2>&1
  echo "limits rc=$?" | tee -a "$O/runs.txt"
  cp "bench/limits/$CELL.json" "$O/" 2>/dev/null
fi
if [ -z "$NOSETA" ]; then
  i=0; for s in $SET; do i=$((i + 1)); run "$s" 0 "setA$i"; done
fi
if [ -z "$NOSETB" ]; then
  i=0; for s in $SET; do i=$((i + 1)); run "$s" 0 "setB$i"; done
fi
i=0; for s in $TRC; do i=$((i + 1)); run "$s" 1 "trace$i"; done
if [ -n "$MC" ]; then cp -r .jax_cache/. "$MC"/ 2>/dev/null; fi
cat "$O/runs.txt"
for f in "$O"/*.err; do echo "== $f"; tail -n 6 "$f"; done
