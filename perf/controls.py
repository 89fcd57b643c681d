#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers every
limit of its check is set from: what sound runs of the program give
over many seeds, and what the control gives (the plain reference put in
the program's place and computed in the nearest precision below the one
the configuration states).  The benchmark's own runs never run this.

    python3 perf/controls.py --workload <name> --seeds 1,2,3 --seconds 8

One process for all the seeds, so that one set-up is shared.  Prints
one JSON line per seed and a last line with, per number compared, the
sound runs' largest and the control's smallest.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="a serving cell's short window per seed")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also run the control")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from perf import loader
    from perf.run import build_context
    ctx, rc = build_context(args.workload, seeds[0], args.seconds, 0)
    if ctx is None:
        return rc

    def ctx_for(seed):
        c = copy.copy(ctx)
        c.seed = seed
        return c

    driver = loader.module("drivers", ctx.traffic["driver"])
    summary = {}
    for row in driver.controls(ctx_for, seeds, args.control_seeds):
        print(json.dumps(row), flush=True)
        for side in ("sound", "control"):
            for name, value in (row.get(side) or {}).items():
                s = summary.setdefault(name, {"sound_max": None,
                                              "control_min": None})
                if side == "sound":
                    s["sound_max"] = value if s["sound_max"] is None \
                        else max(s["sound_max"], value)
                else:
                    s["control_min"] = value if s["control_min"] is None \
                        else min(s["control_min"], value)
    print(json.dumps({"summary": summary, "seeds": seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
