"""From the program's own log of a run to where a whole window's
seconds went.

The program keeps three rings of ``time.perf_counter_ns()`` marks
(``paddle_tpu/observability/steptimer.py``): every compiled
``to_static`` call (``n``, ``fn`` and five marks: four host phases
``lookup``, ``read_state``, ``launch``, ``write_state``), every blocking
host read of a device value (``begin``, ``end``, the process's rusage at
the end) and the collector's long pauses.  They are written with or
without a profiler, so they cover the 35 s of a window that the traced
stretch does not.

The window's calls are the LAST ``steps`` rows of the compiled program
that made the log's last call (the window is the last thing the process
runs).  Its reads are those that end after the first of these calls
enters and lie outside every call (a read made inside ``read_state``
is part of its call).  ``T_0`` is that entry, ``T_b`` the end of the
b-th read that follows at least one new call; block ``b`` is
``(T_(b-1), T_b]`` and its pace ``(T_b - T_(b-1)) / calls entered in
it``: while the host stays ahead of the chip, the device's step, read
with no profiler.

In a traced run the harness stops the profiler right after a read, and
that takes the host seconds.  The last traced call is the greatest ``n``
among the trace's ``to_static.call`` spans; the block after its own
holds the stop, and the readers take the blocks after that one: the
window's untraced stretch.

Everything from ``split`` down is arithmetic on record arrays, which
the tests feed made-up rows.  Every reader gives None, and raises
nothing, where the program keeps no such log (a checkout from before
it) or the log holds fewer calls than the window made.
"""
from __future__ import annotations

import statistics

import numpy as np

CALL_PHASES = ("lookup", "read_state", "launch", "write_state")
PHASES = CALL_PHASES + ("readback", "outside")
CALL_SPAN = "to_static.call"
# a block has stalled by what it took over this multiple of its
# neighbours' pace
STALL_OVER = 1.10
NEIGHBOURS = 2


# ------------------------------------------------------- the program's log
def program_log():
    """(calls, reads, gcs) as the program holds them now, or None
    where it keeps none."""
    try:
        from paddle_tpu.observability import steptimer
        return (steptimer.call_log(), steptimer.read_log(),
                steptimer.gc_log())
    except (ImportError, AttributeError):
        return None


def traced_calls(xplane_path):
    """{n: (start_ns, end_ns)} of the ``to_static.call`` spans in a
    trace, on the trace's clock ({} where they carry no ``n``)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != CALL_SPAN:
                    continue
                n = next((v for k, v in ev.stats if k == "n"), None)
                if n is not None:
                    out[int(n)] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return out


# ------------------------------------------------------------ the clock
def clock_offset(calls, traced):
    """(offset_ns, spread_us, pairs): the check that rows and spans
    pair by ``n``, and what a builder adds to a ``perf_counter_ns``
    mark of any of the three rings to find it in the trace's viewer (a
    row's ``done`` against its span's end: the span closes right after
    the mark); the median over the pairs, and the pairs' quartile
    distance in microseconds.  None without a pair: the profiler's
    stop cannot be placed."""
    offsets = [traced[int(n)][1] - int(done)
               for n, done in zip(calls["n"], calls["done"])
               if int(n) in traced]
    if not offsets:
        return None
    spread = 0.0
    if len(offsets) > 1:
        q = statistics.quantiles(offsets, n=4)
        spread = (q[2] - q[0]) / 1e3
    return statistics.median(offsets), spread, len(offsets)


# ----------------------------------------------------------- the blocks
def window_calls(calls, steps):
    """The last ``steps`` rows of the compiled program that made the
    log's last call (``fn`` is a program's own), or None where the log
    holds fewer."""
    if not len(calls) or not steps or steps < 1:
        return None
    mine = calls[calls["fn"] == calls["fn"][-1]]
    return mine[-steps:] if len(mine) >= steps else None


def _overlap_ns(begins, ends, lo, hi):
    return int(np.clip(np.minimum(ends, hi) - np.maximum(begins, lo),
                       0, None).sum())


def split(calls, reads, gcs, steps):
    """The window's blocks, oldest first, or None (see the module's
    text).  A block is a dict: ``first_n`` / ``last_n`` / ``calls``
    (the calls entered in it), ``t0`` / ``t1`` (ns), ``seconds``,
    ``pace_ms``, ``phase_s`` (the host's seconds by ``PHASES``),
    ``gc_s`` (pauses that overlap it; they also lie inside whichever
    phase they interrupted), ``call_ms`` (each call's four phases, for
    the medians) and ``rusage`` (the deltas between the reads at its
    two edges; None where no read came before it)."""
    win = window_calls(calls, steps)
    if win is None:
        return None
    t_first = int(win["enter"][0])
    # reads outside every call's entry-to-return, ending in the window
    order = np.argsort(calls["enter"], kind="stable")
    enters, dones = calls["enter"][order], calls["done"][order]
    at = np.searchsorted(enters, reads["begin"], side="right") - 1
    inside = (at >= 0) & (reads["begin"] < dones[np.clip(at, 0, None)])
    free = reads[~inside]
    before = free[free["end"] <= t_first]
    free = free[free["end"] > t_first]

    blocks, lo, edge, nxt = [], t_first, (before[-1] if len(before)
                                          else None), 0
    extra_wait = 0      # reads that follow no new call: the next block's
    for read in free:
        end = int(read["end"])
        stop = nxt + int(np.searchsorted(win["enter"][nxt:], end))
        wait = int(read["end"] - read["begin"])
        if stop == nxt:
            extra_wait += wait
            continue
        mine = win[nxt:stop]
        per_call = {
            "lookup": mine["read_state"] - mine["enter"],
            "read_state": mine["launch"] - mine["read_state"],
            "launch": mine["launched"] - mine["launch"],
            "write_state": mine["done"] - mine["launched"]}
        phase_s = {k: float(v.sum()) / 1e9 for k, v in per_call.items()}
        phase_s["readback"] = (wait + extra_wait) / 1e9
        seconds = (end - lo) / 1e9
        phase_s["outside"] = seconds - sum(phase_s.values())
        blocks.append({
            "first_n": int(mine["n"][0]), "last_n": int(mine["n"][-1]),
            "calls": len(mine), "t0": lo, "t1": end, "seconds": seconds,
            "pace_ms": 1e3 * seconds / len(mine), "phase_s": phase_s,
            "gc_s": _overlap_ns(gcs["begin"], gcs["end"], lo, end) / 1e9,
            "call_ms": {k: v / 1e6 for k, v in per_call.items()},
            "rusage": None if edge is None else {
                "cpu_user_s": float(read["utime_ns"] - edge["utime_ns"]) / 1e9,
                "cpu_system_s": float(read["stime_ns"]
                                      - edge["stime_ns"]) / 1e9,
                "involuntary_switches": int(read["nivcsw"] - edge["nivcsw"]),
                "major_faults": int(read["majflt"] - edge["majflt"])}})
        lo, edge, nxt, extra_wait = end, read, stop, 0
    return blocks or None


def untraced_stretch(blocks, last_traced_n=None):
    """(the blocks the readers take, the block that holds the
    profiler's stop or None).  With no trace every block is taken;
    with one, those after the block that follows the last traced
    call's."""
    if last_traced_n is None:
        return list(blocks), None
    held = [i for i, b in enumerate(blocks) if b["first_n"] <= last_traced_n]
    if not held:            # traced before the window only
        return list(blocks), None
    stop = held[-1] + 1
    return blocks[stop + 1:], (blocks[stop] if stop < len(blocks) else None)


def _neighbours(blocks, b):
    lo, hi = max(0, b - NEIGHBOURS), min(len(blocks), b + NEIGHBOURS + 1)
    return [blocks[i] for i in range(lo, hi) if i != b]


def _median(values):
    return statistics.median(values) if values else None


def profiler_stop_s(stop_block, stretch):
    """What the block that holds ``jax.profiler.stop_trace()`` took
    over the pace of the (up to two) untraced blocks after it."""
    if stop_block is None or not stretch:
        return None
    pace = _median([b["pace_ms"] for b in stretch[:NEIGHBOURS]])
    return stop_block["seconds"] - stop_block["calls"] * pace / 1e3


def stalls(stretch):
    """[(block index, excess seconds)] for every block of the stretch:
    ``e_b = max(0, its seconds - STALL_OVER x its calls x m_b)``, ``m_b``
    the median pace of the two blocks either side of it."""
    out = []
    for b, block in enumerate(stretch):
        near = _neighbours(stretch, b)
        if not near:
            out.append((b, 0.0))
            continue
        m = _median([x["pace_ms"] for x in near]) / 1e3
        out.append((b, max(0.0, block["seconds"]
                           - STALL_OVER * block["calls"] * m)))
    return out


def stall_blocks(stretch):
    """For every block that ran long: its number in the stretch, its
    excess, its seconds by phase beside what its neighbours' median of
    the same phase gives for as many calls, the collector's pauses in
    it, the rusage deltas, and ``blamed``: the phase whose excess over
    its neighbours is largest, spelt ``device`` where that phase is
    ``readback`` (the host had launched on time and waited: the chip or
    the runtime ran long, not the host) and ``gc`` where the
    collector's pauses in the block, over its neighbours', cover half
    of the excess or more."""
    out = []
    for b, excess in stalls(stretch):
        if excess <= 0:
            continue
        block, near = stretch[b], _neighbours(stretch, b)
        usual = {k: block["calls"] * _median(
            [x["phase_s"][k] / x["calls"] for x in near]) for k in PHASES}
        over = {k: block["phase_s"][k] - usual[k] for k in PHASES}
        blamed = max(PHASES, key=lambda k: over[k])
        gc_over = block["gc_s"] - block["calls"] * _median(
            [x["gc_s"] / x["calls"] for x in near])
        if gc_over >= 0.5 * excess:
            blamed = "gc"
        out.append({
            "block": b, "first_n": block["first_n"], "calls": block["calls"],
            "excess_s": excess, "seconds": block["seconds"],
            "phase_s": block["phase_s"], "neighbours_phase_s": usual,
            "gc_s": block["gc_s"], "rusage": block["rusage"],
            "blamed": "device" if blamed == "readback" else blamed})
    return out


def stretch_seconds(stretch):
    return (stretch[-1]["t1"] - stretch[0]["t0"]) / 1e9


def host_stall_share(stretch):
    """100 x the blocks' excess seconds / the stretch's seconds."""
    if len(stretch) < 2:
        return None
    return 100.0 * sum(e for _, e in stalls(stretch)) / stretch_seconds(
        stretch)


def step_growth_share(stretch):
    """100 x (median pace of the last quarter of the blocks / of the
    first quarter - 1)."""
    if len(stretch) < 2:
        return None
    q = max(1, len(stretch) // 4)
    paces = [b["pace_ms"] for b in stretch]
    return 100.0 * (_median(paces[-q:]) / _median(paces[:q]) - 1.0)


def host_busy_share(stretch):
    """100 x (1 - the stretch's readback waits / its seconds)."""
    if not stretch:
        return None
    waits = sum(b["phase_s"]["readback"] for b in stretch)
    return 100.0 * (1.0 - waits / stretch_seconds(stretch))


def call_phase_ms(stretch):
    """{phase: the median over the stretch's calls of its milliseconds}
    and under ``call`` the median of a call's four phases together."""
    if not stretch:
        return None
    per = {k: np.concatenate([b["call_ms"][k] for b in stretch])
           for k in CALL_PHASES}
    out = {k: float(np.median(v)) for k, v in per.items()}
    out["call"] = float(np.median(sum(per.values())))
    return out


# ------------------------------------------------------------ for a run
def of(run):
    """The run's untraced stretch (reduced once, kept on ``run``), or
    None.  The first call notes the clock's fit and what the
    profiler's stop took."""
    if hasattr(run, "_window_stretch"):
        return run._window_stretch
    run._window_stretch = None
    logs = program_log()
    steps = run.counters.get("steps")
    if logs is None or not isinstance(steps, int):
        return None
    calls, reads, gcs = logs
    blocks = split(calls, reads, gcs, steps)
    if blocks is None:
        run.note(window_log_missing={"call_rows": len(calls),
                                     "steps": steps})
        return None
    last_traced = None
    if run.trace is not None:
        from perf import trace_reduce
        try:
            traced = traced_calls(
                trace_reduce.find_xplane(run.ctx.trace_dir))
        except FileNotFoundError:
            traced = {}
        fit = clock_offset(calls, traced)
        if fit is None:     # spans without ``n``: the stop cannot be placed
            run.note(window_log_missing="no to_static.call span of the "
                     "trace carries n")
            return None
        last_traced = max(traced)
        run.note(log_clock_offset_ns=fit[0],
                 log_clock_offset_spread_us=fit[1],
                 log_clock_pairs=fit[2], last_traced_call=last_traced)
    stretch, stop_block = untraced_stretch(blocks, last_traced)
    run.note(window_blocks=len(blocks), stretch_blocks=len(stretch),
             stretch_calls=sum(b["calls"] for b in stretch),
             stretch_s=stretch_seconds(stretch) if stretch else 0.0,
             profiler_stop_s=profiler_stop_s(stop_block, stretch))
    run._window_stretch = stretch or None
    return run._window_stretch
