"""Least time the chip could take for the held experts' three grouped
products, forward and backward, of the token slots routed here in the
traced steps (per sparse layer, a mean step's, from the tallies the
program keeps by call), over the device time of the experts' products
in those same steps (``expert_mlp_device_ms.train``: the ``expert_mlp``
scope and the compiler's ``ragged-dot`` kernels).  Work run again by
recompute adds time and no need."""
from perf import readers, scope_readers


def read(run):
    ctx = run.ctx
    shape_of = getattr(ctx.models, "expert_shape", None)
    slots = scope_readers.expert_slots_a_traced_step(run)
    spent_ms = scope_readers.device_ms_under(run, **scope_readers.EXPERT_MLP)
    if shape_of is None or slots is None or not spent_ms:
        return None
    shape = shape_of(ctx.cfg)
    cost = readers.kernel_cost("expert_mlp")
    least, bounds = 0.0, set()
    for routed in slots.values():
        for need in (cost.fwd, cost.bwd):
            t, how = readers.least_seconds(
                *need(routed, **shape), ctx.peaks)
            least += t
            bounds.add(how)
    run.note(expert_mlp_slots_a_traced_step=slots,
             expert_mlp_bound=sorted(bounds),
             expert_mlp_least_ms=1e3 * least, expert_mlp_device_ms=spent_ms)
    return readers.roofline_share(least, spent_ms / 1e3)
