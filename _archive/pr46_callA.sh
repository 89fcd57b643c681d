#!/bin/bash
# PR 46, call A: the kernels alone beside ragged_dot at the five cells' shapes, the sweep at the three cells whose tiles the
# rule changes most (900 s at most), then both claimed cells: a traced run a side and untraced pairs turn about (pr46_call.sh)
mkdir -p chiprun_out
export PR46_T0=$(date +%s)
timeout 420 python3 _archive/pr46_grouped_microbench.py > chiprun_out/pr46_microbench.log 2>&1; echo "microbench rc=$? at $(( $(date +%s) - PR46_T0 )) s"
grep -a '^{' chiprun_out/pr46_microbench.log | python3 -c "
import json, sys
for l in sys.stdin:
    r = json.loads(l)
    print(r['cell'], r['product'], r['kind'], r['tiles'], 'ragged %.3f ms %.1f%%  kernel %.3f ms %.1f%%  diff %.3g of %.3g' % (r['ragged_ms'], 100 * r['ragged_peak_share'], r['kernel_ms'], 100 * r['kernel_peak_share'], r['max_abs_diff'], r['largest']))
"
grep -av '^{' chiprun_out/pr46_microbench.log | tail -5
timeout 900 python3 _archive/pr46_grouped_microbench.py --sweep --only-sweep mellum2-12b-a2.5b moonlight-16b-a3b laguna-xs.2 lfm2-24b-a2b > chiprun_out/pr46_sweep.log 2>&1; echo "sweep rc=$? at $(( $(date +%s) - PR46_T0 )) s"
grep -a '^{' chiprun_out/pr46_sweep.log | python3 -c "
import json, sys
for l in sys.stdin:
    r = json.loads(l)
    if 'sweep' in r: print(r['cell'], r['product'], r['kind'], r['sweep'], ('%.3f ms %.1f%%' % (r['kernel_ms'], 100 * r['kernel_peak_share'])) if 'kernel_ms' in r else r['refused'][:120])
"
bash _archive/pr46_call.sh ${1:-3} 4600000100 mellum2-12b-a2.5b.pretrain_8k moonlight-16b-a3b.pretrain_8k
