#!/bin/bash
# call 3 of the review round, the short form (the long one, pr44_callR3.sh, waited 2.5 hours for a chip): from a git archive of the index (_checkout): the new cell untraced under its FINAL limits file on two seeds, then mellum2 parent and change on one seed (one compile cache for both sides: the programs are the same), then the routed slots of a second seed
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
CELL=laguna-xs.2.pretrain_8k
T0=$(date +%s)
left() { echo $(( 2300 - ( $(date +%s) - T0 ) )); }
untraced() {
  (cd _checkout && python3 perf/run.py --workload $CELL --seed $1 --seconds 40 --trace 0 > $OUT/pr44R3_untraced_$1.log 2>&1); echo "untraced $1 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^check' $OUT/pr44R3_untraced_$1.log | grep -av "worst leaf" | cut -c1-120
  grep -a '^{"correct"' $OUT/pr44R3_untraced_$1.log | cut -c1-400
}
untraced 4400000711
untraced 4400000712
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
if [ $(left) -gt 700 ]; then run change $M 4400000202; run parent $M 4400000202; fi
unset JAX_COMPILATION_CACHE_DIR
if [ $(left) -gt 400 ]; then
  (cd _checkout && python3 _archive/pr44_slots.py --seeds 4400000901 --steps 130 > $OUT/pr44R3_slots.log 2>&1); echo "slots rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^{' $OUT/pr44R3_slots.log | cut -c1-3000
fi
echo "done at $(( $(date +%s) - T0 )) s"
