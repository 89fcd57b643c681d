#!/bin/bash
# call 3 of the review round, from a git archive of the index (_checkout: the committed files are enough): the new cell untraced under its FINAL limits file, the routed slots by layer over a window's steps, parent and change on two accepted cells whose files this PR edits (mellum2 P C C P, moonlight P C; one compile cache for both sides: the programs are the same), more seeds of the new cell while time is left
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
CELL=laguna-xs.2.pretrain_8k
T0=$(date +%s)
left() { echo $(( ${BUDGET:-3480} - ( $(date +%s) - T0 ) )); }
untraced() {
  (cd _checkout && python3 perf/run.py --workload $CELL --seed $1 --seconds 40 --trace 0 > $OUT/pr44R3_untraced_$1.log 2>&1); echo "untraced $1 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^check' $OUT/pr44R3_untraced_$1.log | grep -av "worst leaf" | cut -c1-120
  grep -a '^{"correct"' $OUT/pr44R3_untraced_$1.log | cut -c1-400
}
untraced 4400000711
untraced 4400000712
(cd _checkout && python3 _archive/pr44_slots.py --seeds 4400000901 --steps 150 > $OUT/pr44R3_slots.log 2>&1); echo "slots rc=$? at $(( $(date +%s) - T0 )) s"
grep -a '^{' $OUT/pr44R3_slots.log | cut -c1-3000
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_shared
mkdir -p $JAX_COMPILATION_CACHE_DIR
run() {  # side cell seed
  dir=$PWD/_checkout; [ $1 = parent ] && dir=$PWD/_parent
  (cd $dir && python3 perf/run.py --workload $2 --seed $3 --seconds 40 --trace 0 > $OUT/pr44R3_$1_$2_$3.log 2>&1); echo "$1 $2 $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^{"correct"' $OUT/pr44R3_$1_$2_$3.log | cut -c1-420
}
M=mellum2-12b-a2.5b.pretrain_8k
run parent $M 4400000201
run change $M 4400000201
run change $M 4400000202
run parent $M 4400000202
K=moonlight-16b-a3b.pretrain_8k
if [ $(left) -gt 900 ]; then run parent $K 4400000301; run change $K 4400000301; fi
if [ $(left) -gt 800 ]; then  # an accepted cell traced on the parent with this PR's benchmark files laid over it, as the driver runs it
  (cd _parent && python3 perf/run.py --workload $M --seed 4400000203 --seconds 40 --trace 1 > $OUT/pr44R3_parent_traced_mellum.log 2>&1); echo "parent traced $M rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^{"correct"' $OUT/pr44R3_parent_traced_mellum.log | cut -c1-1500
fi
unset JAX_COMPILATION_CACHE_DIR
for seed in 4400000713 4400000714 4400000715 4400000716; do
  if [ $(left) -gt 330 ]; then untraced $seed; fi
done
echo "done at $(( $(date +%s) - T0 )) s"
