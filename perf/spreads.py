#!/usr/bin/env python3
"""The spread of each metric over sets of runs, as the bounds are set
from it: the distance between the first and third quartile as a share
of the median, per set, and the wider of the sets.

    python3 perf/spreads.py <file of result lines> [<file> ...]

Each file is one set: the result lines (JSON objects with ``metrics``)
of the runs of one cell, one line a run; other lines are skipped.
"""
from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import stats  # noqa: E402


def read_set(path):
    """{metric: [values]} and the number of runs not ``correct``."""
    values, wrong = {}, 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith('{"correct"'):
                continue
            rec = json.loads(line)
            wrong += not rec["correct"]
            for name, m in rec["metrics"].items():
                values.setdefault(name, []).append(m["value"])
    return values, wrong


def summarize(sets):
    """{metric: {"medians": [...], "spreads": [...], "widest": x}}."""
    out = {}
    for values in sets:
        for name, v in values.items():
            o = out.setdefault(name, {"medians": [], "spreads": []})
            o["medians"].append(statistics.median(v))
            o["spreads"].append(stats.spread(v) if len(v) >= 2 else None)
    for o in out.values():
        known = [s for s in o["spreads"] if s is not None]
        o["widest"] = max(known) if known else None
    return out


def main(argv=None):
    paths = (argv if argv is not None else sys.argv[1:])
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    sets, wrong = [], 0
    for p in paths:
        values, w = read_set(p)
        sets.append(values)
        wrong += w
    print(json.dumps({"runs_not_correct": wrong,
                      "metrics": summarize(sets)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
