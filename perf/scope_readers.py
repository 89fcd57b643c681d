"""What the readers of the sparse-expert and short-conv metrics share:
device time under a named scope of the program, and the program's
routing counters.  Each returns None where the trace holds no such
scope or the program no such counter (a checkout from before they
existed), and raises nothing."""
from __future__ import annotations

from perf import phase_reduce


def device_ms_under(run, scopes, kernels=()):
    """Device self time per ``to_static.call`` of the window's
    operations traced under any of ``scopes`` (components of the scope
    path, e.g. ``router``), in every phase: forward, recompute and
    backward.  ``kernels`` names device operations that belong with
    them but carry no scope: the chip's compiler lowers a
    ``ragged_dot`` to a kernel of its own, ``%ragged-dot-none``, whose
    op_name is its own name and not the traced one (my chip run,
    PR 28)."""
    t = phase_reduce.table(run)
    if t is None or not t.calls:
        return None
    ns = sum(ns for cell, ns in t.cells.items()
             if set(scopes) & set(cell.path.split("/"))
             or any(k in cell.row for k in kernels))
    return ns / t.calls / 1e6 if ns else None


# the experts' products: the program's scope, and the compiler's
# grouped-product kernels, which only the experts' products use
EXPERT_MLP = dict(scopes=("expert_mlp",), kernels=("ragged-dot",))


def expert_counts(run):
    """({layer: slots routed to each held expert since the program was
    built}, {layer: share of the router's slots routed here}), or None:
    the family's adapter reads them from the program's registry."""
    read = getattr(run.ctx.models, "expert_counters", None)
    if read is None:
        return None
    tokens, shares = read()
    counts = [c for v in tokens.values() for c in v]
    # a gauge whose read failed gives None; a layer nothing was routed
    # to has nothing to divide by
    if not counts or None in counts or None in shares.values() \
            or not all(sum(v) for v in tokens.values()):
        return None
    return tokens, shares


def expert_slots_a_traced_step(run):
    """{layer: slots routed to the held experts in a step, the mean of
    the steps whose device time the trace holds}, or None.  The trace
    is of the window's first ``to_static.call``s, and the window's
    steps are the last the program counted, so the traced calls are
    numbers ``last - steps + 1`` onwards of the program's per-call
    tallies: the same steps as the time they are set against, where a
    router that trains moves its load during a run."""
    read = getattr(run.ctx.models, "expert_calls", None)
    t = phase_reduce.table(run)
    steps = run.counters.get("steps")
    if read is None or t is None or not t.calls \
            or not isinstance(steps, int):
        return None
    slots, ends = {}, {}
    for layer, calls in read().items():
        if not calls:
            return None
        first, last = max(calls) - steps + 1, max(calls)
        traced = [calls.get(n) for n in range(first, first + t.calls)]
        if None in traced:      # the ring kept fewer calls than the run made
            return None
        slots[layer] = sum(sum(tally[:-1]) for tally in traced) / t.calls
        ends[layer] = [sum(calls[n][:-1]) for n in (first, last)]
    if slots:
        run.note(expert_slots_in_the_windows_first_and_last_step=ends)
    return slots or None
