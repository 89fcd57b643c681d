#!/bin/bash
# PR 46, call C: the final tree against the parent, untraced pairs turn about, in the claimed cell call B had no room for
# (Moonlight: its warm setup_s on the final tree) and in Kimi-Linear's; 40.0 chip-minutes were left: a budget of 2300 s
export PR46_T0=$(date +%s) PR46_TRACED=0 PR46_BUDGET_S=2300
bash _archive/pr46_call.sh ${1:-2} 4600000300 moonlight-16b-a3b.pretrain_8k kimi-linear-48b-a3b.pretrain_8k
