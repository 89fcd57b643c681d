#!/bin/bash
# PR 45, call A: the rotation alone on the chip (results against the jnp form's, times of both), the sweep of the block,
# the block chosen written into _checkout's ops/pallas/rope.py (the final tree carries the same three constants),
# then both claimed cells (_archive/pr45_call.sh): a traced pair each, then untraced pairs turn about
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
T0=$(date +%s)
(cd _checkout && python3 _archive/pr45_rope_microbench.py) > chiprun_out/pr45_micro.log 2>&1; echo "micro rc=$?"
grep -a '^{' chiprun_out/pr45_micro.log
tail -3 chiprun_out/pr45_micro.log | cut -c1-300
(cd _checkout && python3 _archive/pr45_rope_microbench.py --sweep) > chiprun_out/pr45_sweep.log 2>&1; echo "sweep rc=$?"
grep -a '^{' chiprun_out/pr45_sweep.log | cut -c1-330
python3 _archive/pr45_pick_block.py chiprun_out/pr45_sweep.log _checkout/paddle_tpu/ops/pallas/rope.py | tee chiprun_out/pr45_block.json
grep -n "^BLOCK_S\|^BLOCK_H\|^_ROWS" _checkout/paddle_tpu/ops/pallas/rope.py
echo "sweep done at $(( $(date +%s) - T0 )) s"
PR45_BUDGET_S=$(( 3420 - ( $(date +%s) - T0 ) )) bash _archive/pr45_call.sh ${1:-3} 4500000100 laguna-xs.2.pretrain_8k mellum2-12b-a2.5b.pretrain_8k
