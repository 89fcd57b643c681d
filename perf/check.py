"""The comparison that decides ``correct``: numbers the timed path
produced against the plain reference's, each with a limit of its own
from ``perf/limits/<workload>.json`` (which also records the readings
every limit was set from).
"""
from __future__ import annotations

import statistics


class Checks:
    """Collects (name, value, limit) and prints each as it is added."""

    def __init__(self, limits):
        self.limits, self.rows = limits, []

    def add(self, name, value):
        if name not in self.limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits "
                           f"file; it has {sorted(self.limits)}")
        limit = self.limits[name]["limit"]
        ok = bool(value <= limit)       # a NaN is not within any limit
        self.rows.append((name, value, limit, ok))
        print(f"check {name} value={value!r} limit={limit!r} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r[3] for r in self.rows)

    def as_dict(self):
        return {n: {"value": v, "limit": lim, "ok": ok}
                for n, v, lim, ok in self.rows}


def worst_leaf_gap(program, reference):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but
    zero).  Both map a leaf name to a norm."""
    if set(program) != set(reference):
        raise KeyError(f"leaves differ: "
                       f"{sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    worst, at = 0.0, None
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap != gap:                  # a NaN gap is the worst there is
            return gap, name
        if gap > worst:
            worst, at = gap, name
    return worst, at


def worst_leaf_sketch_gap(program, reference, ref_norms):
    """The widest gap between the program's sketch of a leaf and the
    reference's: the root mean square of the sketches' differences
    (about the norm of the two gradients' difference), against the
    reference's norm of that leaf or of the median leaf."""
    floor = statistics.median(ref_norms.values())
    worst, at = 0.0, None
    for name, ref in reference.items():
        diff = [a - b for a, b in zip(program[name], ref)]
        gap = (sum(d * d for d in diff) / len(diff)) ** 0.5 \
            / max(ref_norms[name], floor)
        if gap != gap:
            return gap, name
        if gap > worst:
            worst, at = gap, name
    return worst, at


def flatten_leaves(values, program_name):
    """The reference's {leaf: value, or one per layer for a stacked
    leaf} under the program's parameter names."""
    out = {}
    for name, v in values.items():
        if name.startswith("blocks."):
            for i, x in enumerate(v):
                out[program_name(name, i)] = x
        else:
            out[program_name(name, None)] = v
    return out


def train_numbers(program, reference):
    """A training cell's numbers: each checked step's loss gap, and by
    the worst leaf the first gradient's norm gap and sketch gap and the
    gap of the parameters' change after the last step.  Returns
    ({name: value}, {name: the worst leaf})."""
    out, at = {}, {}
    for i, (a, b) in enumerate(zip(program["losses"],
                                   reference["losses"]), start=1):
        out[f"loss_gap_step{i}"] = abs(a - b)
    out["first_grad_norm_gap"], at["first_grad_norm_gap"] = worst_leaf_gap(
        program["first_grad_norm"], reference["first_grad_norm"])
    out["first_grad_sketch_gap"], at["first_grad_sketch_gap"] = \
        worst_leaf_sketch_gap(program["first_grad_sketch"],
                              reference["first_grad_sketch"],
                              reference["first_grad_norm"])
    out["param_change_norm_gap"], at["param_change_norm_gap"] = \
        worst_leaf_gap(program["param_change_norm"],
                       reference["param_change_norm"])
    return out, at


def train_checks(checks, program, reference):
    numbers, at = train_numbers(program, reference)
    for name, value in numbers.items():
        if name in at:
            print(f"check {name} worst leaf: {at[name]}", flush=True)
        checks.add(name, value)


def token_gaps(ref_logits, tokens):
    """Per served token, how far its reference logit lies below the
    reference's best at that position.  ``ref_logits`` [n, V] are the
    reference's at the positions that predicted ``tokens`` [n]."""
    import numpy as np
    ref_logits = np.asarray(ref_logits, np.float32)
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None],
                             axis=-1)[:, 0]
    return best - got
