"""Closed loop, saturated: a backlog queued at time 0 and refilled so
that the queue never empties; output tokens completed per second are
what is measured.  The engine is first run for ``ramp_seconds`` (set-up)
so that the window sees slots at every stage of a request and not 64
prefills at once."""
from __future__ import annotations

import time

from perf import check, traffic_gen
from perf.drivers import common, serving


def drive(served, ctx, stretch=None, on_window=None):
    """Queue the backlog, keep it from emptying, ramp, then step for
    ``ctx.seconds``.  ``on_window()`` is called as the window opens.
    Returns (window seconds, the window's step records, requests sent)."""
    spec = ctx.traffic["requests"]
    reqs = traffic_gen.requests(spec, ctx.cfg["data_vocab_size"], ctx.seed,
                                ctx.seconds)
    if stretch is not None:
        stretch.start()
    served.start(ctx.traffic["ramp_seconds"])
    sent = 0

    def refill():
        nonlocal sent
        while len(served.engine.pending_requests()) - served.resident \
                < spec["refill_below"]:
            served.send(reqs[sent % len(reqs)], served.now())
            sent += 1

    refill()
    while served.now() < 0:
        served.step()
        refill()
    if on_window is not None:
        on_window()
    first_step = len(served.steps)
    while served.now() < ctx.seconds:
        served.step()
        refill()
        if stretch is not None:
            stretch.poll(served.now())
    return served.now(), served.steps[first_step:], sent


def controls(ctx_for, seeds, n_control):
    return serving.controls(ctx_for, seeds, n_control, drive)


def run(ctx):
    run = common.Run(ctx)
    served = serving.Served(ctx, serving.build_engine(ctx))
    served.warm()
    stretch = common.TracedStretch(ctx, serving.SPANS)
    mark = {}

    def on_window():
        mark["compiled"] = ctx.compiles.programs
        mark["start"] = time.time()

    seconds, steps, sent = drive(served, ctx, stretch, on_window)
    compiled_before, window_start = mark["compiled"], mark["start"]
    run.trace = stretch.finish()
    compiled_inside = ctx.compiles.programs - compiled_before
    traced = (0.0, None if stretch.stopped is None
              else stretch.stopped - served.t0)
    tokens = sum(s["tokens"] for s in steps)

    sample = served.sample(ctx.traffic["check_sample"])
    finished = list(served.done)
    failed = sum(1 for rid in finished
                 if not served.done[rid].ok or served.done[rid].tokens.size
                 != served.req[rid]["max_new"])
    served.free()
    in_use_freed = (ctx.devices[0].memory_stats() or {}).get("bytes_in_use")
    t = time.time()
    checks = check.Checks(ctx.limits)
    serving.check_served(ctx, checks, sample)
    reference_s = time.time() - t

    run.attempted, run.failed = len(finished), failed
    run.correct = (checks.correct and compiled_inside == 0 and failed == 0
                   and tokens > 0)
    run.end_to_end = {
        "serve_tokens_per_s": tokens / seconds,
        "setup_s": common.setup_seconds(ctx, window_start, 0.0)}
    run.counters = {"steps": served.steps, "traced": traced}
    run.note(window_s=seconds, output_tokens=tokens, requests_sent=sent,
             requests_finished=len(finished), engine_steps=len(steps),
             mixed_steps=sum(s["kind"] == "mixed" for s in steps),
             mean_resident=sum(s["resident"] for s in steps) / len(steps),
             programs_compiled_in_window=compiled_inside,
             programs_obtained_in_setup=compiled_before,
             compile_seconds_in_setup=ctx.compiles.seconds,
             reference_s=reference_s,
             bytes_in_use_after_engine_freed=in_use_freed,
             checks=checks.as_dict())
    return run
