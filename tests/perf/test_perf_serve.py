"""The two serving drivers end to end at a tiny size on the CPU, the
per-request arithmetic, and the two ways the served-token check has
been shown to fail: tokens altered where they are produced, and a
lower-precision control."""
import json
import types

import numpy as np
import pytest

import perf_testlib as L


def _driver(kind):
    from perf import loader
    return loader.module("drivers", kind)


def _note(run, key):
    return next(json.loads(n)[key] for n in run.notes if f'"{key}"' in n)


def test_serve_open_end_to_end():
    ctx = L.serve_context("chat", seed=2**31 + 9)
    run = _driver("serve_open").run(ctx)
    assert run.correct and run.failed == 0
    assert abs(run.attempted - 8.0 * ctx.seconds) <= 2
    e = run.end_to_end
    assert e["ttft_p95_ms"] >= e["ttft_p50_ms"] > 0 and e["setup_s"] > 0
    assert e["tpot_p95_ms"] >= e["tpot_p50_ms"] > 0
    c = run.counters
    assert len(c["late_ms"]) == run.attempted and min(c["late_ms"]) >= 0
    assert len(c["queue_ms"]) == run.attempted
    assert all(q >= late - 1e-6
               for q, late in zip(c["queue_ms"], c["late_ms"]))
    kinds = {s["kind"] for s in c["steps"]}
    assert {"mixed", "window"} <= kinds
    assert sum(s["tokens"] for s in c["steps"]) == _note(run, "output_tokens")
    # the ramp's requests were served before the window and not measured
    assert c["steps"][0]["t_call"] < 0 <= c["steps"][-1]["t_call"]
    assert _note(run, "programs_compiled_in_window") == 0
    from perf import readers
    assert readers.p95(c["queue_ms"]) >= readers.p95(c["late_ms"])


def test_serve_closed_end_to_end():
    ctx = L.serve_context("offline", seed=11)
    run = _driver("serve_closed").run(ctx)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["serve_tokens_per_s"] > 0
    assert _note(run, "window_s") >= ctx.seconds
    # the backlog never empties: every step found the slots full
    assert _note(run, "mean_resident") > 3.5
    assert run.end_to_end["serve_tokens_per_s"] == pytest.approx(
        _note(run, "output_tokens") / _note(run, "window_s"))


def test_altered_tokens_are_not_correct(monkeypatch):
    """Drive a whole run with the engine's completions altered where
    they are produced: ``correct`` comes out false."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    real = ContinuousBatchingEngine.step

    def step(self):
        done = real(self)
        for c in done:
            c.tokens = (np.asarray(c.tokens) + 1) % 250
        return done

    monkeypatch.setattr(ContinuousBatchingEngine, "step", step)
    ctx = L.serve_context("chat", seed=4)
    run = _driver("serve_open").run(ctx)
    assert not run.correct and run.failed == 0
    assert not _note(run, "checks")["token_gap_max"]["ok"]


def test_lower_precision_control_is_not_correct():
    """At the served positions, the token a bfloat16 reference puts
    first lies further below the float32 reference's best than the
    limit allows; the served tokens themselves lie within it."""
    from perf.drivers import serving
    ctx = L.serve_context("chat", seed=6, seconds=3.0)
    served = serving.Served(ctx, serving.build_engine(ctx))
    from perf import traffic_gen
    for r in traffic_gen.requests(ctx.traffic["requests"],
                                  ctx.cfg["data_vocab_size"], ctx.seed,
                                  ctx.seconds):
        if served.t0 is None:
            served.start()
        served.send(r, 0.0)
    while served.engine.has_work:
        served.step()
    sample = served.sample(len(served.done))
    served.free()
    sound = np.concatenate(serving.served_token_gaps(ctx, sample))
    control = np.concatenate(
        serving.served_token_gaps(ctx, sample, "bfloat16"))
    limit = ctx.limits["token_gap_max"]["limit"]
    assert sound.size == control.size > 100
    assert sound.max() <= limit < control.max()


def _served(reqs, done):
    from perf.drivers import serving
    s = serving.Served(types.SimpleNamespace(seed=1), engine=None)
    s.req, s.done = reqs, done
    return s


def _completion(n, reason="length"):
    return types.SimpleNamespace(
        tokens=np.zeros(n, np.int32), prompt=np.zeros(3, np.int32),
        finish_reason=reason, ok=reason in ("length", "stop"),
        sequence=np.zeros(n + 3, np.int32))


def test_latency_arithmetic():
    reqs = {9: {"due": -1.0, "sent": -1.0, "first": 5.0, "last": 9.0,
                "max_new": 3},                    # the ramp's: not measured
            0: {"due": 1.0, "sent": 1.01, "first": 1.25, "last": 2.25,
                "max_new": 11},
            1: {"due": 2.0, "sent": 2.0, "first": 2.1, "last": 2.1,
                "max_new": 1},
            2: {"due": 3.0, "sent": 3.0, "max_new": 5}}      # never served
    done = {9: _completion(3), 0: _completion(11), 1: _completion(1)}
    s = _served(reqs, done)
    # a request without a first token counts as the worst seen
    assert s.ttft_ms() == pytest.approx([250.0, 100.0, 250.0])
    # (last - first) / (tokens - 1); one token has no gap
    assert s.tpot_ms() == pytest.approx([100.0])
    assert s.failed() == 1
    done[2] = _completion(4)                  # fewer tokens than asked
    assert s.failed() == 1
    done[2] = _completion(5, "timeout")
    assert s.failed() == 1
    done[2] = _completion(5)
    assert s.failed() == 0


def test_sample_holds_the_longest_and_is_the_seeds():
    reqs = {i: {"max_new": 2 + i} for i in range(10)}
    done = {i: _completion(2 + i) for i in range(10)}
    a, b = _served(reqs, done).sample(4), _served(reqs, done).sample(4)
    assert len(a) == 4 and a[0][1].size == 11
    assert [t.size for _, t in a] == [t.size for _, t in b]
