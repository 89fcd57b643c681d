#!/bin/bash
# call 3 of the review round, the least form (the longer ones, pr44_callR3.sh and pr44_callR3s.sh, waited three hours for a chip and never ran): from a git archive of the index (_checkout): the new cell untraced once under its FINAL limits file
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
T0=$(date +%s)
(cd _checkout && python3 perf/run.py --workload laguna-xs.2.pretrain_8k --seed 4400000711 --seconds 40 --trace 0 > $OUT/pr44R3_untraced_4400000711.log 2>&1); echo "untraced rc=$? at $(( $(date +%s) - T0 )) s"
grep -a '^check' $OUT/pr44R3_untraced_4400000711.log | grep -av "worst leaf" | cut -c1-120
grep -a '^{"correct"' $OUT/pr44R3_untraced_4400000711.log | cut -c1-400
