#!/bin/bash
# PR 46, call B: the FINAL tree (row tile 256, one pair of step plans a chunk, the kernel calls jitted) against the parent:
# Mellum2 again (call A's tree read its warm setup_s +15 to +21%), then Laguna-XS.2 and LFM2, which the change reaches and does
# not claim: the change traced on one seed (cold), then untraced pairs turn about (pr46_call.sh), each run's setup_s beside its rate
export PR46_T0=$(date +%s) PR46_TRACED=change
bash _archive/pr46_call.sh ${1:-2} 4600000200 mellum2-12b-a2.5b.pretrain_8k laguna-xs.2.pretrain_8k lfm2-24b-a2b.pretrain_8k
