"""Bandwidth bound of the optimizer's state traffic over the device
time of ``fused_optimizer`` (one call per dtype bucket per step)."""
from perf import readers


def read(run):
    ctx = run.ctx
    calls, seconds = run.trace.kernel_seconds("fused_optimizer")
    if not calls:
        return None
    n_low = n_full = 0
    for shape, kind, _ in ctx.reference.table(ctx.cfg).values():
        n = 1
        for d in shape:
            n *= d
        if kind.endswith("_low"):
            n_low += n
        else:
            n_full += n
    buckets = (n_low > 0) + (n_full > 0)
    nbytes = readers.kernel_cost("fused_optimizer").step_bytes(n_low, n_full)
    least = (calls / buckets) * nbytes / ctx.peaks["hbm_bytes_per_s"]
    run.note(fused_optimizer_calls=calls, fused_optimizer_device_s=seconds,
             fused_optimizer_step_bytes=nbytes, fused_optimizer_bound="bandwidth")
    return readers.roofline_share(least, seconds)
