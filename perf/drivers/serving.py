"""What the two serving drivers share: the engine built from the
traffic file's arguments, its warm-up, one instrumented ``step``, the
per-request times, and the check of the served tokens.

Times are the harness's own clock at the points where ``engine.step()``
is called and returns: a token exists for a client once the step that
produced it has returned.  Which request got its first token, was
admitted or retired in a step is read from the engine's own events
(``serving.first_token`` / ``serving.admitted`` / ``serving.retired``),
drained from the bounded ring after every step.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from perf import check, traffic_gen

SPANS = ("engine_step", "generator_send", "generator_wait")


def build_engine(ctx):
    """The program's model with the benchmark's seeded float32 weights,
    inside the engine the traffic file describes."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    from perf.models import common as M
    from perf.reference import common as C
    prec = ctx.cfg["precision"]["serve"]
    if (prec["weights"], prec["kv_cache"]) != ("float32", "float32"):
        raise ValueError(f"the engine serves float32 weights and float32 "
                         f"KV pools only; the configuration asks {prec}")
    model = ctx.models.build_serve(ctx.cfg)
    M.load_weights(model, M.unstack(
        C.make_weights(ctx.reference.table(ctx.cfg), ctx.seed),
        ctx.models.program_name))
    return ContinuousBatchingEngine(model, **ctx.traffic["engine"])


class Served:
    """One engine under measurement."""

    def __init__(self, ctx, engine):
        from paddle_tpu.observability import events
        self.ctx, self.engine, self.events = ctx, engine, events
        self.steps = []          # one dict per step() call
        self.req = {}            # rid -> times and sizes
        self.done = {}           # rid -> CompletedRequest
        self.resident = 0        # admitted and not yet retired
        self.t0 = None
        self._stats = None
        self._last_ret = 0.0

    def warm(self):
        """Twice over (a to_static program runs eagerly first and
        compiles second): a prefill step, the scalar decode step and
        one whole decode window, at the engine's own shapes."""
        eng = self.engine
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, self.ctx.cfg["data_vocab_size"],
                              (eng.prefill_chunk + 7,)).astype(np.int32)
        for _ in range(2):
            eng.add_request(prompt, eng.decode_window + 2)
            eng.run()
        self.events.clear()

    def start(self, ramp=0.0):
        """``now()`` runs from -ramp: the window opens at 0, and what
        is sent or stepped before it is warm-up, not measured and not
        under the harness's span names."""
        self.t0 = time.perf_counter() + ramp
        self.window_wall = time.time() + ramp
        self._stats = dict(self.engine.stats)

    def now(self):
        return time.perf_counter() - self.t0

    def send(self, r, due):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("generator_send" if due >= 0 else "ramp_send"):
            rid = self.engine.add_request(r["prompt"], r["max_new"])
        self.req[rid] = {"due": due, "sent": self.now(),
                         "prompt_len": int(r["prompt"].size),
                         "max_new": int(r["max_new"])}
        return rid

    def step(self):
        """One ``engine.step()`` with its times, what it dispatched
        and the KV tokens resident after it."""
        from jax.profiler import TraceAnnotation
        eng = self.engine
        t_call = self.now()
        with TraceAnnotation("engine_step" if t_call >= 0 else "ramp_step"):
            done = eng.step()
        t_ret = self.now()
        for ev in self.events.tail():
            rec = self.req.get(ev.get("rid"))
            if rec is None:
                continue
            if ev["kind"] == "serving.admitted":
                rec.setdefault("admitted", t_call)
                self.resident += 1
            elif ev["kind"] == "serving.first_token":
                rec["first"] = t_ret
            elif ev["kind"] == "serving.retired":
                # retired at the start of this step: its last token came
                # out of the step before
                rec["last"] = self._last_ret
                self.resident -= 1
        self.events.clear()
        for c in done:
            self.done[c.request_id] = c
        stats = eng.stats
        kind = ("mixed" if stats["mixed_steps"] > self._stats["mixed_steps"]
                else "window" if stats["decode_dispatches"]
                > self._stats["decode_dispatches"] else "none")
        page = eng.page_size
        self.steps.append({
            "t_call": t_call, "t_ret": t_ret, "kind": kind,
            "tokens": stats["tokens_generated"]
            - self._stats["tokens_generated"],
            # each resident request's last page is half full on average
            "kv_tokens": max(0.0, (stats["pages_in_use"]
                                   - 0.5 * self.resident) * page),
            "resident": self.resident})
        self._stats, self._last_ret = dict(stats), t_ret
        return done

    # ------------------------------------------------------ reductions
    def measured(self):
        """The requests due in the window (a ramp's are due before 0)."""
        return [r for r in self.req.values() if r["due"] >= 0]

    def ttft_ms(self):
        """Due time to first token, per request; a request that failed
        or never produced one counts as the worst seen."""
        reqs = self.measured()
        got = [1e3 * (r["first"] - r["due"]) for r in reqs if "first" in r]
        worst = max(got) if got else float("inf")
        return got + [worst] * (len(reqs) - len(got))

    def tpot_ms(self):
        """(last token - first token) / (tokens - 1) per request: the
        mean gap a client sees (tokens arrive in bursts of a decode
        window)."""
        out = []
        for rid, r in self.req.items():
            c = self.done.get(rid)
            if r["due"] >= 0 and c is not None and c.tokens.size >= 2 \
                    and "last" in r:
                out.append(1e3 * (r["last"] - r["first"])
                           / (c.tokens.size - 1))
        return out

    def failed(self):
        """Requests that did not finish ``length`` or ``stop`` with the
        tokens asked."""
        bad = 0
        for rid, r in self.req.items():
            c = self.done.get(rid)
            if c is None or not c.ok or (
                    c.finish_reason == "length"
                    and c.tokens.size != r["max_new"]):
                bad += 1
        return bad

    def sample(self, k):
        """A sample of the finished requests drawn from the seed, the
        longest among them: [(prompt, tokens)]."""
        ok = sorted(rid for rid, c in self.done.items()
                    if c.ok and rid in self.req)
        if not ok:
            return []
        longest = max(ok, key=lambda r: self.done[r].sequence.size)
        rest = [r for r in ok if r != longest]
        rng = traffic_gen.rng_of(self.ctx.seed, 4)
        pick = [longest] + [rest[i] for i in rng.permutation(
            len(rest))[:max(0, k - 1)]]
        return [(np.asarray(self.done[r].prompt, np.int32),
                 np.asarray(self.done[r].tokens, np.int32)) for r in pick]

    def free(self):
        """Drop the engine and its model so that the reference has the
        chip's memory."""
        self.engine = None
        gc.collect()


def served_token_gaps(ctx, sample, mode="highest"):
    """Per sampled request, per served token: how far the token's
    reference logit lies below the reference's best at its position.

    With another ``mode`` the tokens judged are not the served ones
    but, at each of the same positions, the token that the reference
    computed in that lower precision puts first (the control)."""
    import jax
    import jax.numpy as jnp

    from perf.reference import common as C
    cfg, ref = ctx.cfg, ctx.reference
    weights = C.make_weights(ref.table(cfg), ctx.seed)
    width = ctx.traffic["requests"]["max_total"]

    # the weights are arguments: closed over, they would be baked into
    # the executable as constants and compiled anew in every run
    ref_logits = jax.jit(lambda w, ids: ref.logits(w, cfg, ids))
    low_logits = low_w = None
    if mode != "highest":
        low = C.Matmul(mode)
        low_w = jax.tree.map(low.act, weights)
        low_logits = jax.jit(lambda w, ids: ref.logits(w, cfg, ids, low))

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in sample:
            seq = np.concatenate([prompt, tokens])
            ids = np.zeros((1, width), np.int32)
            ids[0, :seq.size] = seq
            # the logits at position i predict token i + 1
            at = slice(prompt.size - 1, seq.size - 1)
            lg = np.asarray(ref_logits(weights, jnp.asarray(ids))[0, at])
            judged = tokens
            if low_logits is not None:
                judged = np.asarray(low_logits(low_w, jnp.asarray(ids))[0, at]
                                    ).argmax(-1)
            out.append(check.token_gaps(lg, judged))
    return out


def check_served(ctx, checks, sample):
    """The served tokens against the plain reference: the widest and
    the mean gap over the sample, and the gap of the first token after
    prefill and of the deepest decoded token of the longest request."""
    gaps = served_token_gaps(ctx, sample)
    flat = np.concatenate(gaps)
    print(f"check served tokens compared: {flat.size} of "
          f"{len(sample)} requests", flush=True)
    checks.add("token_gap_max", float(flat.max()))
    checks.add("token_gap_mean", float(flat.mean()))
    return flat.size


def controls(ctx_for, seeds, n_control, drive):
    """Per seed a short window at the cell's own load through ONE
    engine (the seed's weights are loaded into it, so its programs are
    compiled once), then, with the engine freed, the served tokens'
    gaps and for the first ``n_control`` seeds the bfloat16 control's
    at the same positions.  ``drive(served, ctx)`` runs the window."""
    from perf.models import common as M
    from perf.reference import common as C
    first = ctx_for(seeds[0])
    engine = build_engine(first)
    model = engine.model
    samples, warmed = [], False
    for seed in seeds:
        ctx = ctx_for(seed)
        M.load_weights(model, M.unstack(
            C.make_weights(ctx.reference.table(ctx.cfg), seed),
            ctx.models.program_name))
        served = Served(ctx, engine)
        if not warmed:
            served.warm()
            warmed = True
        before = ctx.compiles.programs
        drive(served, ctx)
        samples.append((ctx, served.sample(ctx.traffic["check_sample"]),
                        {"failed": served.failed(),
                         "finished": len(served.done),
                         "compiled": ctx.compiles.programs - before}))
        served.engine = None
    del engine, model, served
    gc.collect()
    for k, (ctx, sample, row) in enumerate(samples):
        gaps = np.concatenate(served_token_gaps(ctx, sample))
        row.update(seed=ctx.seed, tokens=int(gaps.size),
                   sound={"token_gap_max": float(gaps.max()),
                          "token_gap_mean": float(gaps.mean())})
        if k < n_control:
            low = np.concatenate(served_token_gaps(ctx, sample, "bfloat16"))
            row["control"] = {"token_gap_max": float(low.max()),
                              "token_gap_mean": float(low.mean())}
        yield row
