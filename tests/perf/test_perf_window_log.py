"""``perf/window_log.py``: the reduction of the program's call, read and
collector logs to a window's blocks and the four readers' values, over
made-up rows; the pairing of rows and trace by ``n``; and the four
readers over the log a tiny compiled step really writes."""
import gc
import types

import numpy as np
import pytest

import perf_testlib as L  # noqa: F401  (puts the root on sys.path)

from perf import loader, window_log as W  # noqa: E402

CALL = np.dtype([(f, "<i8") for f in (
    "n", "fn", "enter", "read_state", "launch", "launched", "done")])
READ = np.dtype([(f, "<i8") for f in (
    "seq", "begin", "end", "utime_ns", "stime_ns", "nivcsw", "majflt")])
GC = np.dtype([(f, "<i8") for f in (
    "seq", "begin", "end", "generation", "collected")])
MS, S = 1_000_000, 1_000_000_000
# a made-up call's host phases and the loop's own code after it
HOST = {"lookup": MS // 10, "read_state": MS // 2, "launch": 2 * MS,
        "write_state": 4 * MS // 10}
OUTSIDE = MS // 2
READERS = ("host_stall_share.train", "step_growth_share.train",
           "host_busy_share.train", "call_host_ms.train")


def made_up(paces_ms, calls=10, extra=None, last_calls=None,
            switches=None, inner_read=False, idle_reads=0):
    """A window's logs: block ``b`` makes ``calls`` calls at the
    device's pace ``paces_ms[b]`` and then reads the loss, which waits
    until the chip has run them.  ``extra[b] = (phase, seconds)`` puts
    more seconds into a phase of the block's first call (the chip then
    idles as long) or into its read's wait.  Before the window: three
    calls of the same function and a read, as a driver's settling."""
    extra, switches = extra or {}, switches or {}
    rows, reads, t, n = [], [], S, 0
    cpu = nivcsw = 0

    def call(more=None):
        nonlocal t, n, cpu
        n += 1
        marks = [t]
        for phase in W.CALL_PHASES:
            t += HOST[phase] + (more[1] if more and more[0] == phase else 0)
            marks.append(t)
        rows.append((n, 1, *marks))
        if inner_read:      # a host_syncs hook's read, inside read_state
            reads.append((0, marks[1] + 10, marks[1] + 20, cpu, 0, nivcsw, 0))
        cpu += sum(HOST.values()) + OUTSIDE
        t += OUTSIDE

    def read(until, more=0):
        nonlocal t
        begin = t
        t = max(until, t + MS // 20) + more
        reads.append((0, begin, t, cpu, 0, nivcsw, 0))

    for _ in range(3):
        call()
    read(t)
    for b, pace in enumerate(paces_ms):
        start = t
        count = last_calls if last_calls and b == len(paces_ms) - 1 \
            else calls
        phase, seconds = extra.get(b, (None, 0))
        more = int(seconds * S)
        for i in range(count):
            call((phase, more) if i == 0 and phase in HOST else None)
        if phase == "outside":
            t += more
        nivcsw += switches.get(b, 0)
        host_late = more if phase in HOST or phase == "outside" else 0
        read(start + host_late + int(count * pace * MS),
             more if phase == "readback" else 0)
        for _ in range(idle_reads):     # a second look at the same loss
            read(t)
    calls_arr = np.array(rows, dtype=CALL)
    reads_arr = np.array(reads, dtype=READ)
    reads_arr["seq"] = np.arange(1, len(reads) + 1)
    steps = len(calls_arr) - 3
    return calls_arr, reads_arr, np.zeros(0, dtype=GC), steps


def reduced(*a, **k):
    calls, reads, gcs, steps = made_up(*a, **k)
    return W.split(calls, reads, gcs, steps)


QUIET = [100.0] * 12


def test_a_quiet_window_reads_no_stall_and_no_growth():
    blocks = reduced(QUIET)
    assert len(blocks) == 12 and all(b["calls"] == 10 for b in blocks)
    assert [b["first_n"] for b in blocks[:2]] == [4, 14]
    assert all(b["pace_ms"] == pytest.approx(100.0) for b in blocks)
    assert W.host_stall_share(blocks) == 0.0
    assert W.stall_blocks(blocks) == []
    assert W.step_growth_share(blocks) == pytest.approx(0.0, abs=1e-9)
    # the host works 3.5 ms a call and waits the rest of each 100
    assert W.host_busy_share(blocks) == pytest.approx(3.5, abs=1e-6)
    phases = W.call_phase_ms(blocks)
    assert phases == pytest.approx({"lookup": 0.1, "read_state": 0.5,
                                    "launch": 2.0, "write_state": 0.4,
                                    "call": 3.0})
    for b in blocks:
        assert sum(b["phase_s"].values()) == pytest.approx(b["seconds"])
        assert b["phase_s"]["outside"] == pytest.approx(10 * 0.0005)


@pytest.mark.parametrize("phase,blamed", [
    ("launch", "launch"), ("lookup", "lookup"), ("read_state", "read_state"),
    ("write_state", "write_state"), ("outside", "outside"),
    ("readback", "device")])
def test_two_seconds_more_in_one_block_read_their_share_and_are_blamed(
        phase, blamed):
    blocks = reduced(QUIET, extra={5: (phase, 2.0)}, switches={5: 7})
    # e_b = (10 x 0.1 + 2) - 1.10 x 10 x 0.1; the window 12 x 1 s + 2 s
    assert W.host_stall_share(blocks) == pytest.approx(
        100 * 1.9 / 14.0, rel=1e-6)
    (stall,) = W.stall_blocks(blocks)
    assert stall["block"] == 5 and stall["first_n"] == 54
    assert stall["excess_s"] == pytest.approx(1.9)
    assert stall["blamed"] == blamed
    key = "readback" if phase == "readback" else phase
    assert stall["phase_s"][key] - stall["neighbours_phase_s"][key] \
        == pytest.approx(2.0, rel=0.02)
    assert stall["rusage"]["involuntary_switches"] == 7
    assert stall["rusage"]["cpu_user_s"] == pytest.approx(0.035)
    assert W.step_growth_share(blocks) == pytest.approx(0.0, abs=1e-9)


def test_a_pace_that_steps_up_halfway_reads_growth_and_no_stall():
    blocks = reduced([100.0] * 6 + [115.0] * 6)
    assert W.step_growth_share(blocks) == pytest.approx(15.0)
    assert W.host_stall_share(blocks) == 0.0
    # a steady climb reads the last quarter over the first
    climb = reduced([100.0 + 2 * b for b in range(12)])
    assert W.step_growth_share(climb) == pytest.approx(
        100 * (120.0 / 102.0 - 1))
    assert W.host_stall_share(climb) == 0.0


def test_a_last_block_of_fewer_calls_is_a_block_by_its_pace():
    calls, reads, gcs, steps = made_up(QUIET, last_calls=3)
    assert steps == 11 * 10 + 3
    blocks = W.split(calls, reads, gcs, steps)
    assert blocks[-1]["calls"] == 3
    assert blocks[-1]["pace_ms"] == pytest.approx(100.0)
    assert W.host_stall_share(blocks) == 0.0
    assert W.step_growth_share(blocks) == pytest.approx(0.0, abs=1e-9)


def test_a_log_shorter_than_the_window_gives_none():
    calls, reads, gcs, steps = made_up(QUIET)
    assert W.split(calls, reads, gcs, steps + 4) is None     # 3 settled
    assert W.split(calls[:0], reads, gcs, steps) is None
    assert W.split(calls, reads, gcs, 0) is None
    # the settling calls are the same function's, and are not the window's
    assert W.split(calls, reads, gcs, steps)[0]["first_n"] == 4
    # another compiled function's call between two steps is not a step
    t = int(calls["done"][60]) + 1000
    mixed = np.insert(calls, 61, np.array(
        [(999, 0, t, t + 10, t + 20, t + 30, t + 40)], dtype=CALL))
    blocks = W.split(mixed, reads, gcs, steps)
    assert [b["calls"] for b in blocks] == [10] * 12
    assert W.split(mixed, reads, gcs, steps + 4) is None


def test_a_collector_pause_inside_a_block_is_named():
    calls, reads, _, steps = made_up(QUIET, extra={4: ("launch", 2.0)})
    first = calls[calls["n"] == 44][0]
    begin = int(first["launch"]) + MS
    gcs = np.array([(1, begin, begin + 2 * S, 2, 1234)], dtype=GC)
    blocks = W.split(calls, reads, gcs, steps)
    assert blocks[4]["gc_s"] == pytest.approx(2.0)
    assert sum(b["gc_s"] for b in blocks) == pytest.approx(2.0)
    (stall,) = W.stall_blocks(blocks)
    assert stall["blamed"] == "gc" and stall["gc_s"] == pytest.approx(2.0)
    # the seconds lie inside the phase the collector interrupted too
    assert stall["phase_s"]["launch"] > 2.0


def test_the_stop_block_and_all_before_it_are_left_out():
    # the profiler covered blocks 0 and 1 and was stopped after block
    # 1's read: block 2 holds the stop, 3.4 s of it
    blocks = reduced(QUIET, extra={2: ("outside", 3.4)})
    last_traced = blocks[1]["last_n"]
    stretch, stop = W.untraced_stretch(blocks, last_traced)
    assert stop is blocks[2] and stretch == blocks[3:]
    assert W.profiler_stop_s(stop, stretch) == pytest.approx(3.4)
    assert W.host_stall_share(stretch) == 0.0
    # any call of the traced block places the stop the same
    assert W.untraced_stretch(blocks, blocks[1]["first_n"])[1] is blocks[2]
    # with no trace every block is taken, and the stall is the window's
    every, none = W.untraced_stretch(blocks)
    assert none is None and len(every) == 12
    assert W.host_stall_share(every) > 20
    # a trace that ran to the window's end leaves nothing to read
    stretch, stop = W.untraced_stretch(blocks, blocks[-1]["last_n"])
    assert stretch == [] and stop is None
    assert W.host_stall_share(stretch) is None
    assert W.host_busy_share(stretch) is None
    assert W.call_phase_ms(stretch) is None


def test_a_read_inside_a_call_is_no_edge_and_an_idle_read_is_a_wait():
    plain = reduced(QUIET)
    inner = reduced(QUIET, inner_read=True)
    assert [b["calls"] for b in inner] == [b["calls"] for b in plain]
    assert [b["t1"] for b in inner] == [b["t1"] for b in plain]
    # a second read of the same loss makes no block of its own: its
    # wait is the next block's, and the reads after the last call are
    # not the window's
    twice = reduced(QUIET, idle_reads=1)
    assert len(twice) == 12 and all(b["calls"] == 10 for b in twice)
    assert twice[3]["phase_s"]["readback"] == pytest.approx(
        plain[3]["phase_s"]["readback"], abs=1e-3)


def test_rows_and_spans_pair_by_n_and_give_the_clocks_offset():
    calls, reads, gcs, steps = made_up(QUIET)
    offset = 7_000_000_123
    jitter = {4: 0, 5: 400, 6: -300, 7: 100, 8: 200}
    traced = {n: (int(calls["enter"][calls["n"] == n][0]) + offset,
                  int(calls["done"][calls["n"] == n][0]) + offset + j)
              for n, j in jitter.items()}
    traced[999] = (1, 2)                # a span whose row has wrapped away
    fit, spread_us, pairs = W.clock_offset(calls, traced)
    assert pairs == 5 and fit == offset + 100
    assert 0.3 <= spread_us <= 0.7
    assert W.clock_offset(calls, {}) is None
    assert W.clock_offset(calls, {4: traced[4]})[1] == 0.0


# ------------------------------------------------ the readers, on a run
def fake_run(steps, trace=None):
    run = types.SimpleNamespace(counters={"steps": steps}, trace=trace,
                                notes=[], ctx=None)
    run.note = lambda **kw: run.notes.append(kw)
    return run


def noted(run):
    return {k: v for note in run.notes for k, v in note.items()}


def test_the_four_readers_read_a_made_up_log(monkeypatch):
    calls, reads, gcs, steps = made_up(
        [100.0] * 6 + [110.0] * 6, extra={8: ("readback", 1.0)})
    monkeypatch.setattr(W, "program_log", lambda: (calls, reads, gcs))
    run = fake_run(steps)
    got = {name: loader.module("metrics", name).read(run)
           for name in READERS}
    assert got["host_stall_share.train"] == pytest.approx(
        100 * (1.0 - 0.11) / 13.6, rel=1e-3)
    assert got["step_growth_share.train"] == pytest.approx(10.0)
    assert 2.5 < got["host_busy_share.train"] < 3.5
    assert got["call_host_ms.train"] == pytest.approx(3.0)
    notes = noted(run)
    assert notes["stall_blocks"][0]["blamed"] == "device"
    assert len(notes["window_pace_ms"]) == 12
    assert notes["call_phase_ms"]["launch"] == pytest.approx(2.0)
    assert notes["profiler_stop_s"] is None and notes["stretch_blocks"] == 12


def test_a_traced_run_reads_the_stretch_after_the_profilers_stop(
        monkeypatch):
    from perf import trace_reduce
    calls, reads, gcs, steps = made_up(
        QUIET, extra={2: ("outside", 3.4), 7: ("launch", 2.0)})
    blocks = W.split(calls, reads, gcs, steps)
    offset = 5 * S
    traced = {int(n): (int(e) + offset, int(d) + offset)
              for n, e, d in zip(calls["n"], calls["enter"], calls["done"])
              if n <= blocks[1]["last_n"]}      # settling calls and 2 blocks
    monkeypatch.setattr(W, "program_log", lambda: (calls, reads, gcs))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "a.xplane.pb")
    spans = {"with_n": traced}
    monkeypatch.setattr(W, "traced_calls", lambda path: spans["with_n"])
    run = fake_run(steps, trace=object())
    run.ctx = types.SimpleNamespace(trace_dir="unused")
    got = {name: loader.module("metrics", name).read(run)
           for name in READERS}
    notes = noted(run)
    assert notes["last_traced_call"] == blocks[1]["last_n"]
    assert notes["log_clock_offset_ns"] == offset
    assert notes["log_clock_offset_spread_us"] == 0.0
    assert notes["profiler_stop_s"] == pytest.approx(3.4)
    assert notes["window_blocks"] == 12 and notes["stretch_blocks"] == 9
    # the stop's 3.4 s are no stall: the one stall is block 7, the
    # stretch's fifth
    assert got["host_stall_share.train"] == pytest.approx(
        100 * 1.9 / 11.0, rel=1e-6)
    (stall,) = notes["stall_blocks"]
    assert stall["block"] == 4 and stall["blamed"] == "launch"
    assert got["call_host_ms.train"] == pytest.approx(3.0)
    # a program whose spans carry no ``n``: the stop cannot be placed
    spans["with_n"] = {}
    run = fake_run(steps, trace=object())
    run.ctx = types.SimpleNamespace(trace_dir="unused")
    assert all(loader.module("metrics", name).read(run) is None
               for name in READERS)
    assert "window_log_missing" in noted(run)


@pytest.mark.parametrize("log", ["none", "short"])
def test_the_readers_give_none_where_the_log_does_not_hold_the_window(
        monkeypatch, log):
    calls, reads, gcs, steps = made_up(QUIET)
    monkeypatch.setattr(
        W, "program_log",
        lambda: None if log == "none" else (calls[-50:], reads, gcs))
    run = fake_run(steps)
    for name in READERS:
        assert loader.module("metrics", name).read(run) is None


UNITS = dict(zip(READERS, ("%", "%", "%", "ms")))
# the cells the four were declared for (PR 40); later cells with a
# compiled train step may join them
CELLS_AT_PR40 = {"gpt2-medium.pretrain", "lfm2-24b-a2b.pretrain_8k",
                 "moonlight-16b-a3b.pretrain_8k",
                 "kimi-linear-48b-a3b.pretrain_8k"}


@pytest.mark.parametrize("name", READERS)
def test_each_reader_is_declared_by_name_for_the_training_cells(name):
    bench = loader.benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["unit"] == UNITS[name] and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "train_tokens_per_s"
    cells = {w["name"] for w in bench["workloads"]}
    assert CELLS_AT_PR40 <= set(entry["workloads"]) <= cells
    # its layer is one that the benchmark's other metrics name too
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in READERS}


def test_the_readers_read_what_a_compiled_step_really_logs():
    """A tiny ``to_static`` step driven as the harness's window drives
    one: ten calls, then the loss read back."""
    import paddle_tpu as paddle
    from paddle_tpu.observability import steptimer
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    try:
        w = paddle.to_tensor(np.ones((8, 8), "float32"))

        @paddle.jit.to_static
        def tiny_train_step(x):
            return (x @ w).sum()

        x = paddle.to_tensor(np.ones((8, 8), "float32"))
        for _ in range(3):          # eager, compile, settle
            loss = tiny_train_step(x)
        float(loss)
        steps = 0
        for _ in range(6):
            for _ in range(10):
                loss = tiny_train_step(x)
                steps += 1
            assert float(loss) == 512.0
        gc.collect()
        run = fake_run(steps)
        got = {name: loader.module("metrics", name).read(run)
               for name in READERS}
    finally:
        paddle.set_flags({"metrics": old})
    assert all(v is not None for v in got.values()), got
    assert 0 < got["call_host_ms.train"] < 50
    assert 0 <= got["host_busy_share.train"] <= 100
    assert got["host_stall_share.train"] >= 0
    notes = noted(run)
    assert notes["window_blocks"] == 6 and notes["stretch_calls"] == 60
    assert notes["window_block_calls"] == [10] * 6
    assert set(notes["call_phase_ms"]) == set(W.CALL_PHASES) | {"call"}
    names = steptimer.call_fn_names()
    assert names[int(steptimer.call_log()["fn"][-1])] == "tiny_train_step"
