#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of offered rates on
the chip in one process (the benchmark's runs never search: the rate
is a number in the traffic file).

    python3 perf/sweep.py --workload <name> --rates 2,3,4,5,6 --seconds 15 --seed 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    from perf import loader
    from perf.run import build_context
    ctx, rc = build_context(args.workload, args.seed, args.seconds, 0)
    if ctx is None:
        return rc
    driver = loader.module("drivers", ctx.traffic["driver"])
    for row in driver.sweep(ctx, [float(r) for r in args.rates.split(",")]):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
