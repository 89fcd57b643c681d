#!/bin/bash
# PR 43, the measuring call (made twice: on the first tree, whose spreads a recompute kept, and on the final one): the glue alone (microbench), one traced run a side on one seed, four untraced pairs P C C P P C C P;
# parent = _parent (git archive of PR 42's commit), change = _checkout (git archive $(git write-tree))
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
CELL=kimi-linear-48b-a3b.pretrain_8k
(cd _checkout && PR43_PARENT=$OUT/../_parent python3 _archive/pr43_glue_microbench.py) > $OUT/pr43_micro.log 2>&1; echo "micro rc=$?"
grep -a '^{"variant"\|parent vs\|highest vs' $OUT/pr43_micro.log
run() {  # side seed trace
  dir=$PWD/_checkout; [ $1 = parent ] && dir=$PWD/_parent
  (cd $dir && python3 perf/run.py --workload $CELL --seed $2 --seconds 40 --trace $3 > $OUT/pr43_$1_$2_t$3.log 2>&1); echo "$1 $2 trace=$3 rc=$?"
  grep -a '^{"correct"' $OUT/pr43_$1_$2_t$3.log | cut -c1-420
  if [ $3 = 1 ]; then
    python3 _archive/pr43_trace_ops.py $dir $CELL $OUT/pr43_ops_$1.json > $OUT/pr43_ops_$1.txt 2>&1; echo "ops rc=$?"
    grep -a '^{"correct"' $OUT/pr43_$1_$2_t$3.log > $OUT/pr43_line_$1.json
  fi
}
run parent 4300000778 1
run change 4300000778 1
run parent 4300000201 0
run change 4300000201 0
run change 4300000202 0
run parent 4300000202 0
run parent 4300000203 0
run change 4300000203 0
run change 4300000204 0
run parent 4300000204 0
python3 - <<'PY'
import json
for side in ("parent", "change"):
    m = json.loads(open(f"chiprun_out/pr43_line_{side}.json").read())
    print(side, {k: round(v["value"], 3) for k, v in m["metrics"].items()
                 if any(s in k for s in ("kda", "linear", "unattr", "step_device", "recompute", "mfu", "tokens", "setup", "forward", "backward"))},
          m["device"]["memory_peak_bytes"])
    print(json.dumps(m["breakdown"]["device_ops"]))
PY
