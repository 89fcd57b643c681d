#!/bin/bash
# PR 46, the measuring call: bash _archive/pr46_call.sh PAIRS SEED0 CELL [CELL ...]
# parent = _parent (git archive of PR 45's commit), change = _checkout (git archive $(git write-tree));
# per cell one traced run a side on SEED0 (cold: each side compiles its own programs), its line's metrics and breakdown printed;
# then PAIRS rounds of one untraced pair a cell on seeds SEED0+1.., alternating which side runs first,
# while PR46_BUDGET_S (3400) leaves 330 s; PR46_TRACED=0 leaves the traced runs out, PR46_TRACED=change traces the change alone
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
PAIRS=$1; SEED0=$2; shift 2
T0=${PR46_T0:-$(date +%s)}
left() { echo $(( ${PR46_BUDGET_S:-3400} - ( $(date +%s) - T0 ) )); }
run() {  # cell side seed trace
  dir=$PWD/_checkout; [ $2 = parent ] && dir=$PWD/_parent
  (cd $dir && python3 perf/run.py --workload $1 --seed $3 --seconds 40 --trace $4 > $OUT/pr46_$1_$2_$3_t$4.log 2>&1); echo "$1 $2 $3 trace=$4 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^{"correct"' $OUT/pr46_$1_$2_$3_t$4.log | cut -c1-330
  [ $4 = 1 ] && grep -a '^{"correct"' $OUT/pr46_$1_$2_$3_t$4.log > $OUT/pr46_$1_line_$2.json
}
for cell in "$@"; do
  [ "${PR46_TRACED:-1}" = 0 ] && break
  if [ $(left) -lt 900 ]; then echo "traced runs of $cell left out: $(left) s left"; continue; fi
  [ "${PR46_TRACED:-1}" = change ] || run $cell parent $SEED0 1
  run $cell change $SEED0 1
  python3 - $cell <<'PY'
import json, sys
cell = sys.argv[1]
for side in ("parent", "change"):
    try:
        m = json.loads(open(f"chiprun_out/pr46_{cell}_line_{side}.json").read())
    except Exception as e:
        print(side, "no line", e); continue
    print(side, json.dumps({k: round(v["value"], 3) for k, v in m["metrics"].items()}), m["device"].get("memory_peak_bytes"))
    print(side, "breakdown", json.dumps(m["breakdown"]["device_ops"])[:1500])
PY
done
for i in $(seq 1 $PAIRS); do
  for cell in "$@"; do
    if [ $(left) -lt 330 ]; then echo "round $i of $cell left out: $(left) s left"; continue; fi
    seed=$(( SEED0 + i ))
    if [ $(( i % 2 )) = 1 ]; then run $cell parent $seed 0; run $cell change $seed 0; else run $cell change $seed 0; run $cell parent $seed 0; fi
  done
done
echo "done at $(( $(date +%s) - T0 )) s"
