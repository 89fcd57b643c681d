"""Least time the chip could take for the sliding-window attention the
step needs (one forward and one backward per window layer per step,
over the band's score pairs exactly; a forward run again by recompute
adds time and no need) over the summed device time of
``flash_window_fwd`` and ``flash_window_bwd``."""
from perf import readers


def read(run):
    ctx = run.ctx
    shape_of = getattr(ctx.models, "window_shape", None)
    if shape_of is None or run.trace is None:
        return None
    n_fwd, t_fwd = run.trace.kernel_seconds("flash_window_fwd")
    n_bwd, t_bwd = run.trace.kernel_seconds("flash_window_bwd")
    if not n_bwd or not n_fwd:
        return None
    shape = shape_of(ctx.cfg, ctx.traffic["batch"])
    cost = readers.kernel_cost("window_attention")
    fwd, how_f = readers.least_seconds(*cost.fwd(**shape), ctx.peaks)
    bwd, how_b = readers.least_seconds(*cost.bwd(**shape), ctx.peaks)
    run.note(window_attention_bound={"fwd": how_f, "bwd": how_b},
             window_attention_calls={"fwd": n_fwd, "bwd": n_bwd},
             window_attention_device_s={"fwd": t_fwd, "bwd": t_bwd})
    return readers.roofline_share(n_bwd * (fwd + bwd), t_fwd + t_bwd)
