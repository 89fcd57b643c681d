"""Arithmetic the per-layer readers in ``perf/metrics`` share.  Each
takes the driver's ``Run`` record and returns a number, or None where
the trace or the counters hold nothing to read."""
from __future__ import annotations

from perf import loader, stats


def span_mean_ms(run, name):
    spans = run.trace.spans_named(name)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e6


def idle_share(run):
    """100 x (1 - union of device-op intervals / traced window)."""
    if not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def device_ms_per_span(run, name):
    """Device-busy milliseconds of the traced window per span ``name``
    begun in it (work is asynchronous, so a span's own operations run
    after it returns; over a steady window the edges cancel)."""
    n = len(run.trace.spans_named(name))
    if not n or not run.trace.ops:
        return None
    return 1e3 * run.trace.busy_s() / n


def roofline_share(least_seconds, kernel_seconds):
    """100 x the least time the chip could take / the time it took."""
    if not kernel_seconds:
        return None
    return 100.0 * least_seconds / kernel_seconds


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound) of a call needing ``flops`` operations
    and ``nbytes`` bytes of HBM traffic."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")


def kernel_cost(kernel):
    return loader.module("kernel_costs", kernel)


def p95(values):
    return stats.percentile(values, 95) if values else None


# ------------------------------------------------------------- serving
def traced_steps(run):
    """[(step record, (start, end) of its span in the trace)] for the
    ``engine.step()`` calls made while the profiler ran, or None where
    the driver's records and the trace's spans do not pair up."""
    c = run.counters
    started, stopped = c["traced"]
    stopped = float("inf") if stopped is None else stopped
    steps = [s for s in c["steps"]
             if s["t_call"] >= started and s["t_ret"] <= stopped]
    spans = run.trace.spans_named("engine_step")
    if not steps or len(steps) != len(spans):
        run.note(traced_steps_unpaired={"records": len(steps),
                                        "spans": len(spans)})
        return None
    return list(zip(steps, spans))


def step_device_ms(run, kind):
    """Mean device-busy milliseconds inside the spans of the engine
    steps that dispatched a program of ``kind`` (the step reads its
    result back, so its device work lies inside its span)."""
    pairs = traced_steps(run)
    if not pairs or not run.trace.ops:
        return None
    busy = [run.trace.busy_within(s, e) for st, (s, e) in pairs
            if st["kind"] == kind]
    return sum(busy) / len(busy) / 1e6 if busy else None


def engine_host_ms(run):
    """Mean per engine step of (its span less the device-busy time
    inside it)."""
    pairs = traced_steps(run)
    if not pairs or not run.trace.ops:
        return None
    idle = [(e - s) - run.trace.busy_within(s, e) for _, (s, e) in pairs]
    return sum(idle) / len(idle) / 1e6


def paged_attention_roofline(run):
    """Bandwidth bound of the keys and values resident for the slots in
    each ``ragged_paged_attention`` call over the kernel's device time.
    A decode window calls it layers x decode_window times, each over
    the KV resident at that scanned step (taken as the mean of the
    counts before and after the window); a mixed step calls it once a
    layer."""
    ctx = run.ctx
    pairs = traced_steps(run)
    calls, seconds = run.trace.kernel_seconds("ragged_paged_attention")
    if not pairs or not calls:
        return None
    shape = ctx.models.kv_shape(ctx.cfg)
    cost = kernel_cost("ragged_paged_attention")
    eng = ctx.traffic["engine"]
    K, layers = eng["decode_window"], shape["layers"]
    all_steps = run.counters["steps"]
    nbytes = 0.0
    for st, _ in pairs:
        i = all_steps.index(st)
        before = all_steps[i - 1]["kv_tokens"] if i else 0.0
        if st["kind"] == "window":
            kv = 0.5 * (before + st["kv_tokens"])
            nbytes += layers * K * cost.call_bytes(
                kv, shape["kv_heads"], shape["head_dim"],
                st["resident"], shape["q_heads"])
        elif st["kind"] == "mixed":
            q = eng["prefill_chunk"] + st["resident"]
            nbytes += layers * cost.call_bytes(
                st["kv_tokens"], shape["kv_heads"], shape["head_dim"],
                q, shape["q_heads"])
    run.note(paged_attention_calls=calls, paged_attention_device_s=seconds,
             paged_attention_needed_bytes=nbytes,
             paged_attention_bound="bandwidth")
    return roofline_share(nbytes / ctx.peaks["hbm_bytes_per_s"], seconds)
