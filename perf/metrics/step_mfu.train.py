"""The whole step's share of the chip's peak: the model's operations of
the steps begun in the traced stretch (``train_flops_per_token`` of the
cell's adapter: the model's own multiply-adds forward and backward, work
run again by recompute NOT counted; x the tokens of a step x the
``to_static.call`` spans in the stretch) over what the chips could do in
the stretch's seconds, busy AND idle, at ``peaks['bf16_flops_per_s']``.
Not hardware utilisation (recompute adds time and no need) and not a
kernel's roofline: what bounds a claim once the kernel whose roofline
was read has left the path.  Over 100% the operations are counted too
high or the stretch leaves out part of the work: an error, not a value."""
from perf import phase_reduce, traffic_gen


def read(run):
    t = phase_reduce.spans_of(run)
    if t is None or not t.calls:
        return None
    ctx = run.ctx
    batch = ctx.traffic["batch"]
    flops = ctx.models.train_flops_per_token(ctx.cfg, batch) \
        * traffic_gen.tokens_per_step(batch) * t.calls
    could = run.trace.window_s * ctx.peaks["bf16_flops_per_s"] \
        * len(ctx.devices)
    share = 100.0 * flops / could
    if share > 100.0:
        raise ValueError(
            f"step_mfu.train reads {share:.2f}%: {flops:.4g} operations "
            f"in {t.calls} steps against {could:.4g} the chips could do "
            f"in {run.trace.window_s:.4g} s")
    run.note(step_mfu_steps=t.calls, step_mfu_model_flops=flops,
             step_mfu_stretch_s=run.trace.window_s)
    return share
