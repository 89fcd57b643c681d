"""Least time the chip could take for the attention the step needs
(one forward and one backward per layer per step; a forward run again
by recompute adds time and no need) over the summed device time of
``flash_attention_fwd`` and ``flash_attention_bwd``."""
from perf import readers


def read(run):
    ctx = run.ctx
    n_fwd, t_fwd = run.trace.kernel_seconds("flash_attention_fwd")
    n_bwd, t_bwd = run.trace.kernel_seconds("flash_attention_bwd")
    if not n_bwd or not n_fwd:
        return None
    shape = ctx.models.attention_shape(ctx.cfg, ctx.traffic["batch"])
    cost = readers.kernel_cost("flash_attention")
    fwd, how_f = readers.least_seconds(*cost.fwd(**shape), ctx.peaks)
    bwd, how_b = readers.least_seconds(*cost.bwd(**shape), ctx.peaks)
    run.note(flash_attention_bound={"fwd": how_f, "bwd": how_b},
             flash_attention_calls={"fwd": n_fwd, "bwd": n_bwd},
             flash_attention_device_s={"fwd": t_fwd, "bwd": t_bwd})
    return readers.roofline_share(n_bwd * (fwd + bwd), t_fwd + t_bwd)
