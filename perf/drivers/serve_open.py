"""Open loop: requests are sent when they are due, whether or not
earlier ones have finished, and every latency counts from the due
time.  After the window's last arrival the engine is drained, so that
every request due in the window is measured."""
from __future__ import annotations

import time

from perf import check, stats, traffic_gen
from perf.drivers import common, serving


def drive(served, ctx, stretch=None):
    """Send the seed's schedule as it falls due, step while there is
    work, drain.  The schedule runs over ``ramp_seconds`` before the
    window opens (warm-up traffic, due before 0, not measured) and
    ``ctx.seconds`` after."""
    from jax.profiler import TraceAnnotation
    ramp = ctx.traffic.get("ramp_seconds", 0.0)
    reqs = traffic_gen.requests(
        ctx.traffic["requests"], ctx.cfg["data_vocab_size"], ctx.seed,
        ctx.seconds + ramp)
    for r in reqs:
        r["due"] -= ramp
    if stretch is not None:
        stretch.start()
    served.start(ramp)
    nxt = 0
    while nxt < len(reqs) or served.engine.has_work:
        now = served.now()
        while nxt < len(reqs) and reqs[nxt]["due"] <= now:
            served.send(reqs[nxt], reqs[nxt]["due"])
            nxt += 1
        if served.engine.has_work:
            served.step()
            if stretch is not None:
                stretch.poll(served.now())
        else:
            with TraceAnnotation("generator_wait"):
                time.sleep(max(0.0, min(reqs[nxt]["due"] - served.now(),
                                        0.002)))


def controls(ctx_for, seeds, n_control):
    return serving.controls(ctx_for, seeds, n_control, drive)


def run(ctx):
    run = common.Run(ctx)
    spec = ctx.traffic["requests"]
    served = serving.Served(ctx, serving.build_engine(ctx))
    served.warm()
    compiled_before = ctx.compiles.programs
    stretch = common.TracedStretch(ctx, serving.SPANS)

    drive(served, ctx, stretch)
    drained = served.now()
    run.trace = stretch.finish()
    compiled_inside = ctx.compiles.programs - compiled_before
    traced = (0.0, None if stretch.stopped is None
              else stretch.stopped - served.t0)

    sample = served.sample(ctx.traffic["check_sample"])
    served.free()
    in_use_freed = (ctx.devices[0].memory_stats() or {}).get("bytes_in_use")
    t = time.time()
    checks = check.Checks(ctx.limits)
    serving.check_served(ctx, checks, sample)
    reference_s = time.time() - t

    ttft, tpot = served.ttft_ms(), served.tpot_ms()
    measured = served.measured()
    late = [1e3 * (r["sent"] - r["due"]) for r in measured]
    queue = [1e3 * (r["admitted"] - r["due"]) for r in measured
             if "admitted" in r]
    run.attempted, run.failed = len(measured), served.failed()
    run.correct = (checks.correct and compiled_inside == 0
                   and run.failed == 0)
    run.end_to_end = {"setup_s": common.setup_seconds(
        ctx, served.window_wall, 0.0)}
    for q in (50, 80, 90, 95):
        run.end_to_end[f"ttft_p{q}_ms"] = stats.percentile(ttft, q)
        run.end_to_end[f"tpot_p{q}_ms"] = (
            stats.percentile(tpot, q) if tpot else float("inf"))
    tokens = sum(s["tokens"] for s in served.steps)
    run.counters = {"steps": served.steps, "late_ms": late,
                    "queue_ms": queue, "traced": traced}
    run.note(requests=len(measured), offered_rate=spec["rate"],
             percentiles={k: v for k, v in run.end_to_end.items()
                          if k != "setup_s"},
             tpot_samples=len(tpot), drained_s=drained,
             output_tokens=tokens,
             output_tokens_per_s_to_drain=tokens / drained,
             engine_steps=len(served.steps),
             mixed_steps=sum(s["kind"] == "mixed" for s in served.steps),
             generator_late_ms_max=max(late),
             programs_compiled_in_window=compiled_inside,
             programs_obtained_in_setup=compiled_before,
             compile_seconds_in_setup=ctx.compiles.seconds,
             reference_s=reference_s,
             bytes_in_use_after_engine_freed=in_use_freed,
             checks=checks.as_dict())
    return run


def sweep(ctx, rates):
    """The knee, found once: for each offered rate a window of
    ``ctx.seconds`` through ONE engine.  A rate is sustained while the
    output tokens completed inside the window stay within 3% of those
    offered and the queue at the window's end is no longer than at its
    middle.  Yields one dict per rate."""
    import copy
    served0 = serving.Served(ctx, serving.build_engine(ctx))
    served0.warm()
    engine = served0.engine
    for rate in rates:
        c = copy.copy(ctx)
        c.traffic = copy.deepcopy(ctx.traffic)
        c.traffic["requests"]["rate"] = rate
        served = serving.Served(c, engine)
        drive(served, c)
        T = c.seconds
        measured = served.measured()
        offered = sum(r["max_new"] for r in measured)
        inside = sum(s["tokens"] for s in served.steps
                     if 0 <= s["t_ret"] <= T)

        def queued(t):
            return sum(1 for r in measured
                       if r["sent"] <= t and r.get("admitted", 1e30) > t)

        ttft, tpot = served.ttft_ms(), served.tpot_ms()
        yield {"rate": rate, "requests": len(measured),
               "offered_tokens_per_s": offered / T,
               "completed_tokens_per_s": inside / T,
               "completed_share": inside / offered,
               # steady state: tokens produced in the window's second
               # half against the offered rate (in a short window the
               # requests in flight at its end are a visible share)
               "second_half_share": sum(
                   s["tokens"] for s in served.steps
                   if T / 2 < s["t_ret"] <= T) / (offered / 2),
               "queue_mid": queued(T / 2), "queue_end": queued(T),
               "drained_s": served.now(),
               "ttft_p50_ms": stats.percentile(ttft, 50),
               "ttft_p95_ms": stats.percentile(ttft, 95),
               "tpot_p50_ms": stats.percentile(tpot, 50),
               "tpot_p95_ms": stats.percentile(tpot, 95),
               "late_p95_ms": stats.percentile(
                   [1e3 * (r["sent"] - r["due"]) for r in measured], 95),
               "failed": served.failed()}
