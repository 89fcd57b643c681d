#!/bin/bash
# call 1 of the review round: what holds the chip at 32 held (diagnosis), then the committed cell (16 held) traced once, its trace cut, the parent's try
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
CELL=laguna-xs.2.pretrain_8k
T0=$(date +%s)
timeout 600 python3 _archive/pr44_ref_fit.py 32 > $OUT/pr44R_ref_fit_32.log 2>&1; echo "ref_fit rc=$? at $(( $(date +%s) - T0 )) s"
grep -a '^{' $OUT/pr44R_ref_fit_32.log | cut -c1-700
tail -c 1500 $OUT/pr44R_ref_fit_32.log | grep -av '^{' | tail -5
python3 perf/run.py --workload $CELL --seed 4400000101 --seconds 40 --trace 1 > $OUT/pr44R_traced_1.log 2>&1; rc=$?; echo "traced rc=$rc at $(( $(date +%s) - T0 )) s"
tail -c 9000 $OUT/pr44R_traced_1.log
if [ $rc = 0 ]; then python3 _archive/pr44_record_trace.py 2>&1 | tail -5; fi
t0=$(date +%s)
(cd _parent && timeout 300 python3 perf/run.py --workload $CELL --seed 4400000105 --seconds 40 --trace 0 > $OUT/pr44R_parent_try.log 2>&1; echo "parent rc=$? after $(( $(date +%s) - t0 )) s")
tail -3 $OUT/pr44R_parent_try.log
echo "done at $(( $(date +%s) - T0 )) s"
