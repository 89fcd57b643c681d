#!/bin/bash
# call 2 of the review round: the cell at the FIRST size (32 held) traced once; if that fails, the second size (16 held: _archive/pr44_laguna-xs.2.16held.json) takes the configuration's place for the rest of the call.  Then the trace cut, the parent's try (the parent's checkout with this PR's benchmark files laid over it, as the driver does), untraced seeds, the check's readings on two seeds (sound, fp8 control, the two faults), more untraced seeds while time is left
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
CELL=laguna-xs.2.pretrain_8k
T0=$(date +%s)
left() { echo $(( 3480 - ( $(date +%s) - T0 ) )); }
python3 perf/run.py --workload $CELL --seed 4400000101 --seconds 40 --trace 1 > $OUT/pr44R2_traced_32.log 2>&1; rc=$?; echo "traced at 32 held rc=$rc at $(( $(date +%s) - T0 )) s"
SIZE=32
if [ $rc != 0 ]; then
  grep -a "RESOURCE_EXHAUSTED\|Error\|error" $OUT/pr44R2_traced_32.log | cut -c1-400 | tail -8
  cp _archive/pr44_laguna-xs.2.16held.json perf/configs/laguna-xs.2.json; SIZE=16   # (the 16-held copy of the file stood there for the call; 32 held fit and it was not needed)
  python3 perf/run.py --workload $CELL --seed 4400000101 --seconds 40 --trace 1 > $OUT/pr44R2_traced_16.log 2>&1; rc=$?; echo "traced at 16 held rc=$rc at $(( $(date +%s) - T0 )) s"
fi
echo "SIZE=$SIZE"
grep -a '^check' $OUT/pr44R2_traced_$SIZE.log | cut -c1-200
tail -c 7000 $OUT/pr44R2_traced_$SIZE.log
if [ $rc != 0 ]; then exit 1; fi
python3 _archive/pr44_record_trace.py 2>&1 | tail -3
t0=$(date +%s)
(cd _parent && timeout 300 python3 perf/run.py --workload $CELL --seed 4400000105 --seconds 40 --trace 0 > $OUT/pr44R2_parent_try.log 2>&1; echo "parent rc=$? after $(( $(date +%s) - t0 )) s")
tail -3 $OUT/pr44R2_parent_try.log | cut -c1-300
untraced() {
  python3 perf/run.py --workload $CELL --seed $1 --seconds 40 --trace 0 > $OUT/pr44R2_untraced_$1.log 2>&1; echo "untraced $1 rc=$? at $(( $(date +%s) - T0 )) s"
  grep -a '^check' $OUT/pr44R2_untraced_$1.log | grep -av "worst leaf" | cut -c1-120
  grep -a '^{"correct"' $OUT/pr44R2_untraced_$1.log | cut -c1-400
  grep -a '"reference_s"' $OUT/pr44R2_untraced_$1.log | cut -c1-420
}
for seed in 4400000102 4400000103 4400000104 4400000106; do untraced $seed; done
for seed in 4400000801 4400000802; do
  if [ $(left) -gt 700 ]; then
    python3 _archive/pr44_controls.py --seeds $seed > $OUT/pr44R2_controls_$seed.log 2>&1; echo "controls $seed rc=$? at $(( $(date +%s) - T0 )) s"
    grep -a '^{' $OUT/pr44R2_controls_$seed.log | cut -c1-5000
    tail -c 400 $OUT/pr44R2_controls_$seed.log
  fi
done
for seed in 4400000107 4400000108 4400000109 4400000110; do
  if [ $(left) -gt 330 ]; then untraced $seed; fi
done
echo "done at $(( $(date +%s) - T0 )) s"
