#!/bin/bash
# PR 45, call B: the FINAL tree (block 512 x 8, 64 rows a pass; the kernel in the captured program alone) against the parent, untraced:
# both claimed cells, pairs turn about (the first round compiles: every checkout has its own cache), each run's setup_s beside its rate;
# then, if time is left, where Laguna's set-up goes on either side (_archive/pr45_setup_phases.py)
unset JAX_COMPILATION_CACHE_DIR JAX_COMPILATION_CACHE_MAX_SIZE
mkdir -p chiprun_out
T0=$(date +%s)
PR45_TRACED=0 PR45_BUDGET_S=${PR45_BUDGET_S:-2500} bash _archive/pr45_call.sh ${1:-4} 4500000200 laguna-xs.2.pretrain_8k mellum2-12b-a2.5b.pretrain_8k
for root in _parent _checkout; do
  if [ $(( 2850 - ( $(date +%s) - T0 ) )) -gt 200 ]; then
    python3 _archive/pr45_setup_phases.py $root laguna-xs.2 > chiprun_out/pr45_phases_$root.log 2>&1; echo "phases $root rc=$?"
    grep -a '^{' chiprun_out/pr45_phases_$root.log
  fi
done
echo "call B done at $(( $(date +%s) - T0 )) s"
