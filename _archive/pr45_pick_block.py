"""PR 45: the block the sweep chose.  Reads the sweep's JSON lines
(`_archive/pr45_rope_microbench.py --sweep`), sums each (BLOCK_S,
BLOCK_H, ROWS)'s forward + backward milliseconds over the three swept
cases (64 heads by a table of 128, of 64; 8 heads), leaves out a block
the compiler refused in any case, and prints the least; the default
stays unless another is 3% under it.  With a file of
``ops/pallas/rope.py`` as second argument, writes the three constants
there.

    python3 _archive/pr45_pick_block.py SWEEP.log [ROPE.py]
"""
import json
import re
import sys

DEFAULT = (512, 8, 32)
total, refused = {}, set()
for line in open(sys.argv[1]):
    if not line.startswith("{"):
        continue
    row = json.loads(line)
    key = (row["BLOCK_S"], row["BLOCK_H"], row["ROWS"])
    if "refused" in row:
        refused.add(key)
        continue
    # the first stage ran one case: whole blocks ran all three
    total.setdefault(key, {})[row["heads"], row["r"]] = (
        row["kernel_fwd_ms"] + row["kernel_bwd_ms"])
whole = {k: sum(v.values()) for k, v in total.items()
         if len(v) == 3 and k not in refused}
best = min(whole, key=whole.get)
if DEFAULT in whole and whole[best] > 0.97 * whole[DEFAULT]:
    best = DEFAULT
print(json.dumps({"chosen": best, "ms": whole[best],
                  "default_ms": whole.get(DEFAULT),
                  "ranked": sorted((round(v, 4), k)
                                   for k, v in whole.items())[:8]}))
if len(sys.argv) > 2:
    text = open(sys.argv[2]).read()
    for name, value in zip(("BLOCK_S", "BLOCK_H", "_ROWS"), best):
        text, n = re.subn(rf"(?m)^{name} = \d+", f"{name} = {value}", text)
        assert n == 1, name
    open(sys.argv[2], "w").write(text)
