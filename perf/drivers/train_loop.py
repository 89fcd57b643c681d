"""The training loop a user writes: one ``train_step`` call a step on
a fresh host batch, the loss read back every few steps and at the end.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first steps by the window's own feed and
call (which also warms it: a to_static function runs eagerly first and
compiles second), compares those steps with the plain reference's, and
hands the same object to the window.
"""
from __future__ import annotations

import time

import numpy as np

from perf import check, traffic_gen
from perf.drivers import common

SPANS = ("input_feed", "train_step", "loss_readback")


def reference_steps(ctx, batches, mode="highest"):
    """The plain reference's first steps on ``batches`` (or a
    lower-precision control's, by ``mode``)."""
    import jax
    import jax.numpy as jnp

    from perf.reference import common as C
    cfg, spec = ctx.cfg, ctx.traffic["batch"]
    prec = cfg["precision"]["train"]
    ref = ctx.reference
    table = ref.table(cfg)

    def make():
        return C.make_weights(table, ctx.seed, low_dtype=prec["compute"])

    with jax.default_matmul_precision("highest"):
        out = C.train_three_steps(
            ref.train_loss_rows(cfg, spec, C.Matmul(mode)), make,
            [tuple(jnp.asarray(a) for a in b) for b in batches], prec,
            ctx.traffic["reference_rows_per_block"])
    name = ctx.models.program_name

    def flat(key, as_list=False):
        return {k: ([float(x) for x in v] if as_list else float(v))
                for k, v in check.flatten_leaves(out[key], name).items()}

    return {"losses": out["losses"],
            "first_grad_norm": flat("first_grad_norm"),
            "first_grad_sketch": flat("first_grad_sketch", as_list=True),
            "param_change_norm": flat("param_change_norm")}


def seeded_state(ctx, program):
    """The benchmark's seeded weights under the program's names, after
    checking that the leaves it rounded to the compute type are the
    ones the program holds in it."""
    from perf.models import common as M
    from perf.reference import common as C
    cfg = ctx.cfg
    table = ctx.reference.table(cfg)
    state = M.unstack(C.make_weights(
        table, ctx.seed, low_dtype=cfg["precision"]["train"]["compute"]),
        ctx.models.program_name)
    rounded = {
        ctx.models.program_name(leaf, i)
        for leaf, (shape, kind, _) in table.items() if kind.endswith("_low")
        for i in (range(shape[0]) if leaf.startswith("blocks.") else [None])}
    if rounded != program.low_leaves():
        raise RuntimeError(
            f"the leaves made in the compute type are not the ones the "
            f"program holds in it: "
            f"{sorted(rounded ^ program.low_leaves())[:6]}")
    return state


def checked_steps(ctx, program, batches):
    """The program's side of the check, through the window's own feed
    and call."""
    from perf.models import common as M
    M.load_weights(program.model, seeded_state(ctx, program))
    out = {"losses": []}
    for i, batch in enumerate(batches):
        out["losses"].append(float(program.step(program.feed(batch))))
        if i == 0:
            out["first_grad_norm"], out["first_grad_sketch"] = \
                program.first_grad_stats(ctx.reference.table(ctx.cfg),
                                         ctx.models.program_name)
    out["param_change_norm"] = program.param_change_norms(
        seeded_state(ctx, program))
    return out


def window(ctx, program, pool, first, stretch):
    """Step for ``ctx.seconds``.  Returns (steps whose loss readback
    returned, seconds to that readback, last loss)."""
    from jax.profiler import TraceAnnotation
    every = ctx.traffic["read_loss_every"]
    steps, t0 = 0, time.perf_counter()
    while True:
        with TraceAnnotation("input_feed"):
            tensors = program.feed(pool[(first + steps) % len(pool)])
        with TraceAnnotation("train_step"):
            loss = program.step(tensors)
        steps += 1
        now = time.perf_counter() - t0
        if steps % every == 0 or now >= ctx.seconds:
            with TraceAnnotation("loss_readback"):
                last = float(loss)
            now = time.perf_counter() - t0
            stretch.poll(now)
            if now >= ctx.seconds:
                return steps, now, last


def run(ctx):
    import jax
    run = common.Run(ctx)
    spec = ctx.traffic["batch"]
    n_check = ctx.traffic["checked_steps"]
    pool = traffic_gen.train_batches(
        spec, ctx.cfg["data_vocab_size"], ctx.seed,
        ctx.traffic["distinct_batches"])

    t = time.time()
    reference = reference_steps(ctx, pool[:n_check])
    reference_s = time.time() - t
    peak_after_reference = (ctx.devices[0].memory_stats() or {}).get(
        "peak_bytes_in_use")

    program = ctx.models.build_train(ctx.cfg, spec)
    mine = checked_steps(ctx, program, pool[:n_check])
    checks = check.Checks(ctx.limits)
    check.train_checks(checks, mine, reference)
    stretch = common.TracedStretch(ctx, SPANS)
    stretch.start()
    for i in range(ctx.traffic["settle_steps"]):
        loss = program.step(program.feed(pool[(n_check + i) % len(pool)]))
    float(loss)
    first = n_check + ctx.traffic["settle_steps"]
    compiled_before = ctx.compiles.programs

    window_start = time.time()
    steps, seconds, last = window(ctx, program, pool, first, stretch)
    run.trace = stretch.finish()
    compiled_inside = ctx.compiles.programs - compiled_before

    tokens = traffic_gen.tokens_per_step(spec)
    rate = steps * tokens / seconds
    flops = ctx.models.train_flops_per_token(ctx.cfg, spec)
    run.attempted, run.failed = steps, 0
    run.correct = (checks.correct and compiled_inside == 0
                   and bool(np.isfinite(last)))
    run.end_to_end = {
        "train_tokens_per_s": rate,
        "setup_s": common.setup_seconds(ctx, window_start, reference_s)}
    run.counters = {"steps": steps, "window_s": seconds,
                    "tokens_per_step": tokens}
    run.note(steps=steps, window_s=seconds, step_ms_mean=1e3 * seconds / steps,
             last_loss=last, step_programs=program.programs(),
             programs_compiled_in_window=compiled_inside,
             programs_obtained_in_setup=compiled_before,
             compile_seconds_in_setup=ctx.compiles.seconds,
             reference_s=reference_s,
             peak_bytes_after_reference=peak_after_reference,
             model_flops_per_token=flops,
             checks=checks.as_dict())
    return run


def numbers(mine, reference):
    return check.train_numbers(mine, reference)[0]


def controls(ctx_for, seeds, n_control):
    """Per seed the sound program's numbers and, for the first
    ``n_control`` seeds, the fp8 control's (training's readings need no
    measured window).  One program object is built per seed and freed
    before the next."""
    import gc
    for k, seed in enumerate(seeds):
        ctx = ctx_for(seed)
        spec = ctx.traffic["batch"]
        n = ctx.traffic["checked_steps"]
        pool = traffic_gen.train_batches(
            spec, ctx.cfg["data_vocab_size"], seed, n)
        t = time.time()
        reference = reference_steps(ctx, pool)
        row = {"seed": seed, "reference_s": time.time() - t}
        if k < n_control:
            row["control"] = numbers(
                reference_steps(ctx, pool, "fp8"), reference)
        t = time.time()
        program = ctx.models.build_train(ctx.cfg, spec)
        mine = checked_steps(ctx, program, pool)
        row["sound"] = numbers(mine, reference)
        row["program_s"] = time.time() - t
        row["losses"] = mine["losses"]
        del program, mine
        gc.collect()
        yield row
