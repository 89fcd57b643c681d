#!/bin/bash
# PR 47, the measuring call: bash _archive/pr47_call.sh PAIRS SEED0 CELL [CELL ...]
# parent = _parent (git archive of PR 46's commit), change = _checkout (git archive $(git write-tree)).
# The PR changes no program a cell compiles, so both sides share ONE compile cache (.jax_cache_shared/, the
# machine's size cap unset): per cell one traced run of the change on SEED0 compiles it (its cold set-up is
# printed, not compared), then PAIRS rounds of untraced runs on seeds SEED0+1.., parent-change then change-parent
# turn about, while PR47_BUDGET_S (3400) leaves 200 s a run.
# Call A taught: a program that holds a Pallas kernel is keyed by the kernel's source path, so the parent's FIRST
# run compiles those again under _parent/ (+45 to +57 s of set-up, once). PR47_PARENT_WARMUP=1 (call B) spends one
# untraced parent run on SEED0 before the rounds, so that every round's pair is warm on both sides.
unset JAX_COMPILATION_CACHE_MAX_SIZE
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_shared
mkdir -p chiprun_out $JAX_COMPILATION_CACHE_DIR
OUT=$PWD/chiprun_out
PAIRS=$1; SEED0=$2; shift 2
T0=$(date +%s)
left() { echo $(( ${PR47_BUDGET_S:-3400} - ( $(date +%s) - T0 ) )); }
run() {  # cell side seed trace [a tag for the log's name]
  dir=$PWD/_checkout; [ $2 = parent ] && dir=$PWD/_parent
  (cd $dir && python3 perf/run.py --workload $1 --seed $3 --seconds 40 --trace $4 > $OUT/pr47_$1_$2_$3_t$4$5.log 2>&1); echo "$1 $2 $3 trace=$4 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^{"correct"' $OUT/pr47_$1_$2_$3_t$4$5.log | python3 -c "
import json, sys
for l in sys.stdin:
    m = json.loads(l)
    print('   ', m['correct'], m['device'].get('kind', m['device']), m['device'].get('memory_peak_bytes'), json.dumps({k: v['value'] for k, v in m['metrics'].items()}))
"
}
if [ $PAIRS = traced ]; then
  # Call D, the review round: bash _archive/pr47_call.sh traced SEED CELL. The host's spans by side: four TRACED runs
  # on ONE seed, change, parent, parent, change (the first of a side compiles), then `memory_peak_bytes` by directory:
  # the two trees change places and each runs once more, untraced, on the next seed.
  cell=$1
  n=0; for side in change parent parent change; do n=$(( n + 1 )); run $cell $side $SEED0 1 _run$n; done
  if [ $(( $(date +%s) - T0 )) -lt 1500 ]; then
    mv _parent _swap; mv _checkout _parent; mv _swap _checkout
    echo "swapped: _parent/ now holds the CHANGE and _checkout/ the PARENT; the logs keep the DIRECTORY's name"
    run $cell parent $(( SEED0 + 1 )) 0; run $cell change $(( SEED0 + 1 )) 0
  else echo "the swap left out"; fi
  echo "done at $(( $(date +%s) - T0 )) s"; exit 0
fi
for cell in "$@"; do
  if [ $(left) -lt 1100 ]; then echo "$cell left out: $(left) s left"; continue; fi
  run $cell change $SEED0 1
  [ "${PR47_PARENT_WARMUP:-0}" = 1 ] && run $cell parent $SEED0 0
  for i in $(seq 1 $PAIRS); do
    if [ $(left) -lt 400 ]; then echo "round $i of $cell left out: $(left) s left"; continue; fi
    seed=$(( SEED0 + i ))
    if [ $(( i % 2 )) = 1 ]; then run $cell parent $seed 0; run $cell change $seed 0; else run $cell change $seed 0; run $cell parent $seed 0; fi
  done
done
du -sh $JAX_COMPILATION_CACHE_DIR | cut -f1
echo "done at $(( $(date +%s) - T0 )) s"
