#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the machine this is started on.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it imports JAX once, starts no child and sets no platform.
It exits non-zero, with no result line, where ``jax.devices()`` is not
TPUs, holds fewer chips than the cell asks for, or is of a
``device_kind`` that ``perf/peaks.json`` does not list.  The cell's
configuration, traffic mix, driver, family adapter, reference, metric
readers and limits are files found by the names in ``BENCHMARK.json``.
The last line of standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Programs that compile in under a second are kept too: a to_static
# function's first call compiles some thousand per-op programs, and a
# run that finds them in the cache starts minutes sooner (PERF.md,
# set-up).  Only this process is set so; the program's default stands.
CACHE_MIN_COMPILE_SECS = 0.0


class Compiles:
    """Programs the backend produced, from JAX's monitoring events
    (copied from chip_smoke.py): every executable obtained, compiled or
    read back from the persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.programs, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.programs += 1
            self.seconds += secs


class Context:
    """What a driver is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def device_record(devices, chips):
    d = devices[0]
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def result_line(bench, workload, run, trace):
    """The result's ``metrics``: with ``--trace 0`` the cell's
    end-to-end metrics, with ``--trace 1`` its per-layer metrics, each
    from a reader found by the metric's name."""
    from perf import loader
    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if not loader.applies(m, workload["name"]):
                continue
            if m["name"] not in run.end_to_end:
                raise KeyError(f"the driver reported no {m['name']!r}; it "
                               f"reported {sorted(run.end_to_end)}")
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not loader.applies(m, workload["name"]):
                continue
            value = loader.module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def build_context(workload_name, seed, seconds, trace):
    """Everything a driver is given for one run of one cell, or an
    exit code where this machine cannot run it."""
    from perf import loader
    bench = loader.benchmark()
    workload = loader.by_name(bench["workloads"], workload_name, "workload")
    config = loader.by_name(bench["configs"], workload["config"], "config")
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    traffic = loader.data("traffic", workload["traffic"])
    limits = loader.data("limits", workload["name"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"perf: jax.devices()[0].platform is "
              f"{devices[0].platform!r}, not 'tpu': nothing was run",
              file=sys.stderr)
        return None, 2
    if len(devices) < workload["chips"]:
        print(f"perf: the cell needs {workload['chips']} chip(s), "
              f"jax sees {len(devices)}", file=sys.stderr)
        return None, 2
    peaks = loader.peaks(devices[0].device_kind)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_SECS)
    trace_dir = os.path.join(ROOT, ".perf_trace", workload["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"workload": workload["name"], "seed": seed,
                      "seconds": seconds, "trace": int(trace),
                      "compile_cache": cache_dir,
                      "device_kind": devices[0].device_kind}), flush=True)
    return Context(
        root=ROOT, bench=bench, workload=workload, cfg=cfg,
        traffic=traffic, limits=limits, seed=seed, seconds=seconds,
        trace=bool(trace), trace_dir=trace_dir,
        devices=devices[:workload["chips"]], all_devices=devices,
        peaks=peaks, compiles=Compiles(), t_start=T_START,
        models=loader.module("models", cfg["family"]),
        reference=loader.module("reference", cfg["family"])), 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perf import loader
    ctx, rc = build_context(args.workload, args.seed, args.seconds,
                            args.trace)
    if ctx is None:
        return rc
    run = loader.module("drivers", ctx.traffic["driver"]).run(ctx)

    device = device_record(ctx.all_devices, ctx.workload["chips"])
    if args.trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed,
              "metrics": result_line(ctx.bench, ctx.workload, run,
                                     args.trace),
              "device": device}
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    for line in run.notes:
        print(line, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
