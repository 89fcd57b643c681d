"""SLO engine: declarative objectives judged over the live histograms.

PR8/PR12 built the telemetry pipes — histograms, rings, traces — but
nothing *evaluated* them: a TTFT p95 blowing through its objective was
a number in a snapshot, not a signal.  This module closes the loop
(ISSUE 14 tentpole, part 1): :class:`SLOSpec` objects declare
objectives over the existing metrics (``serving.ttft_ms`` p95,
``serving.tpot_ms`` p99, queue time, goodput fraction,
``train.step_ms`` p95 — anything recorded into a
:class:`~paddle_tpu.observability.metrics.Registry`), and
:class:`SLOEngine` evaluates them over SLIDING WINDOWS with
multi-window burn-rate alerting:

* Histograms are cumulative, so a sliding window is a DELTA between
  the current bucket counts and a retained snapshot at the window's
  start — no per-observation bookkeeping rides the hot path; the
  guardrail reads the same counters the timelines already write.
* Each spec carries an ERROR BUDGET (allowed violation fraction —
  ``1 - percentile`` by construction for a pN latency objective:
  "p95 <= X" *means* "at most 5% of observations above X").  The
  burn rate is ``bad_fraction / budget``: 1.0 = spending the budget
  exactly as fast as allowed.
* Breach fires only when the burn rate exceeds the threshold on BOTH
  the fast window (confirmation — is it happening *now*?) and the
  slow window (significance — has it been happening long enough to
  matter?), the standard SRE multi-window rule that filters blips
  without missing sustained burns.  The fast window defaults to 1/12
  of the slow one (the 5m/1h convention).
* On the not-breached -> breached transition the engine emits an
  ``slo.breach`` ring event, bumps the ``slo.breaches`` counter and
  calls ``on_breach`` (the serving engine's callback dumps a flight
  record, so the postmortem starts from the minutes that burned the
  budget).  Recovery emits ``slo.recovered``.
* ``slo.budget_remaining`` / ``slo.burn_rate`` gauges (labeled by
  spec name) land in the owning registry, so
  ``engine.render_prometheus()`` exposes budget state to scrapes.

Percentile math is :func:`metrics.percentile_from_counts` — the SAME
implementation :meth:`Histogram.percentile` reports through, so the
guardrail and a report can never disagree on what a p99 is.

Everything is gated on ``PDTPU_METRICS``: with metrics off the
histograms carry no data and ``maybe_evaluate``/``status`` return
nothing — bitwise pre-guardrail behavior.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import deque

from ..core import state as _state
from . import events as _events
from .metrics import (Counter, Registry, enabled,
                      percentile_from_counts)

__all__ = ["SLOSpec", "SLOEngine", "parse_slo", "SLO_SHORTHAND"]


# shorthand spec names accepted by the ``serving_slo`` flag / engine
# ``slo=`` string: name -> (kind, metric, percentile).  ``goodput`` is
# the ratio objective over the finish-reason-labeled retirement
# counters ("stop"/"length" = a request served within contract).
SLO_SHORTHAND = {
    "ttft_p95_ms": ("latency", "serving.ttft_ms", 0.95),
    "ttft_p99_ms": ("latency", "serving.ttft_ms", 0.99),
    "tpot_p95_ms": ("latency", "serving.tpot_ms", 0.95),
    "tpot_p99_ms": ("latency", "serving.tpot_ms", 0.99),
    "queue_p95_ms": ("latency", "serving.queue_ms", 0.95),
    "queue_p99_ms": ("latency", "serving.queue_ms", 0.99),
    "dispatch_p99_ms": ("latency", "serving.dispatch_ms", 0.99),
    "step_p95_ms": ("latency", "train.step_ms", 0.95),
    "step_p99_ms": ("latency", "train.step_ms", 0.99),
    "goodput": ("ratio", "serving.finished", None),
}


class SLOSpec:
    """One declarative objective.

    ``kind="latency"``: the windowed ``percentile`` of histogram
    ``metric`` must stay <= ``threshold`` (ms); the error budget is
    the allowed fraction of observations above the threshold
    (default ``1 - percentile`` — exactly what a pN objective means).

    ``kind="ratio"``: the windowed fraction of GOOD events among
    ``metric``'s labeled counters must stay >= ``objective``
    (``good_labels`` values of ``label_key`` count as good); the
    budget is ``1 - objective``.

    ``burn_threshold``: both windows' burn rate must exceed this for
    a breach (1.0 = burning the budget at exactly the allowed rate).
    """

    __slots__ = ("name", "metric", "kind", "percentile", "threshold",
                 "objective", "budget", "good_labels", "label_key",
                 "fast_window_s", "slow_window_s", "burn_threshold")

    def __init__(self, name, metric, *, kind="latency", percentile=0.95,
                 threshold=None, objective=None, budget=None,
                 good_labels=("stop", "length"), label_key="reason",
                 fast_window_s=None, slow_window_s=None,
                 burn_threshold=1.0):
        if kind not in ("latency", "ratio"):
            raise ValueError(f"SLOSpec kind must be 'latency' or "
                             f"'ratio', got {kind!r}")
        self.name = str(name)
        self.metric = str(metric)
        self.kind = kind
        self.percentile = float(percentile)
        if kind == "latency":
            if threshold is None:
                raise ValueError(f"latency SLO {name!r} needs a "
                                 "threshold (ms)")
            self.threshold = float(threshold)
            self.objective = None
            self.budget = float(budget if budget is not None
                                else 1.0 - self.percentile)
        else:
            if objective is None:
                raise ValueError(f"ratio SLO {name!r} needs an "
                                 "objective (good fraction)")
            self.objective = float(objective)
            if not 0.0 < self.objective < 1.0:
                raise ValueError(f"ratio SLO {name!r}: objective must "
                                 f"be in (0, 1), got {self.objective}")
            self.threshold = None
            self.budget = float(budget if budget is not None
                                else 1.0 - self.objective)
        if self.budget <= 0:
            raise ValueError(f"SLO {name!r}: error budget must be "
                             f"positive, got {self.budget}")
        self.good_labels = tuple(str(v) for v in good_labels)
        self.label_key = str(label_key)
        slow = float(_state.get_flag("serving_slo_window_s")
                     if slow_window_s is None else slow_window_s)
        self.slow_window_s = max(slow, 1e-9)
        self.fast_window_s = float(self.slow_window_s / 12.0
                                   if fast_window_s is None
                                   else fast_window_s)
        self.burn_threshold = float(burn_threshold)


def parse_slo(cfg) -> list:
    """Normalize an SLO configuration into ``[SLOSpec, ...]``.

    Accepts None/''/False (nothing armed), an :class:`SLOSpec`, a
    list of specs/strings, or the flag-style spec string
    ``"ttft_p95_ms=500,goodput=0.99"`` (``,`` or ``;`` separated;
    names from :data:`SLO_SHORTHAND`).  Unknown names raise — an SLO
    silently misspelled into nonexistence is the failure mode this
    subsystem exists to prevent."""
    if not cfg:
        return []
    if isinstance(cfg, SLOSpec):
        return [cfg]
    if isinstance(cfg, (list, tuple)):
        out = []
        for item in cfg:
            out.extend(parse_slo(item))
        return out
    if not isinstance(cfg, str):
        raise ValueError(f"slo spec must be a string, SLOSpec or list, "
                         f"got {type(cfg).__name__}")
    out = []
    for part in cfg.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, val = part.partition("=")
        name = name.strip()
        if not sep or name not in SLO_SHORTHAND:
            raise ValueError(
                f"unknown SLO spec {part!r}: expected name=value with "
                f"name one of {sorted(SLO_SHORTHAND)}")
        kind, metric, pct = SLO_SHORTHAND[name]
        v = float(val)
        if kind == "latency":
            out.append(SLOSpec(name, metric, kind="latency",
                               percentile=pct, threshold=v))
        else:
            out.append(SLOSpec(name, metric, kind="ratio", objective=v))
    return out


class _Sample:
    __slots__ = ("t", "total", "bad", "counts")

    def __init__(self, t, total, bad, counts):
        self.t = t
        self.total = total
        self.bad = bad
        self.counts = counts    # tuple for latency specs, None for ratio


class _SpecState:
    """Window bookkeeping for one spec: a deque of cumulative samples
    (newest last, plus one sample at/older than the slow window so the
    window base always exists) and the breach latch."""

    def __init__(self, spec: SLOSpec, registry: Registry, clock):
        self.spec = spec
        # METRIC SOURCE vs EXPOSURE registry: train.* telemetry lives
        # in the process-global default registry (StepTimer records
        # there), so a step_* spec armed on a serving engine must read
        # from it — judging a fresh empty train.step_ms histogram in
        # the engine's private registry would make the spec silently
        # inert, the exact failure parse_slo refuses to allow.  The
        # budget/burn gauges still land on the OWNING registry.
        from . import metrics as _metrics_mod
        self._reg = (_metrics_mod.registry()
                     if spec.metric.startswith("train.")
                     else registry)
        self.hist = None
        self.good_idx = 0
        if spec.kind == "latency":
            self.hist = self._reg.histogram(spec.metric)
            # good = observations <= threshold: every bucket whose
            # upper edge sits at or under it (bucket granularity is
            # the resolution of the judgment, same as the percentile)
            self.good_idx = bisect_right(self.hist.buckets,
                                         spec.threshold)
        self.samples: deque[_Sample] = deque()
        self.breached = False
        self.g_budget = registry.gauge(
            "slo.budget_remaining",
            "error budget left in the slow window (1 = untouched)",
            labels={"slo": spec.name})
        self.g_budget.set(1.0)
        self.g_burn_fast = registry.gauge(
            "slo.burn_rate", "error-budget burn rate",
            labels={"slo": spec.name, "window": "fast"})
        self.g_burn_slow = registry.gauge(
            "slo.burn_rate", "error-budget burn rate",
            labels={"slo": spec.name, "window": "slow"})
        self.c_breach = registry.counter(
            "slo.breaches", "multi-window burn-rate breaches",
            labels={"slo": spec.name})
        # seed the window base so the first real evaluation measures
        # everything since arming, not an empty self-delta
        self.samples.append(self._sample(clock()))

    def _sample(self, now) -> _Sample:
        sp = self.spec
        if sp.kind == "latency":
            snap = self.hist._snap()     # one locked, consistent read
            counts = tuple(snap["counts"])
            total = snap["count"]
            bad = total - sum(counts[:self.good_idx])
            return _Sample(now, total, bad, counts)
        good = total = 0
        for m in self._reg.metrics():
            if m.name != sp.metric or not isinstance(m, Counter):
                continue
            v = int(m.value or 0)
            total += v
            labels = dict(m.labels)
            if labels.get(sp.label_key) in sp.good_labels:
                good += v
        return _Sample(now, total, total - good, None)

    def _base(self, cutoff) -> _Sample:
        """Newest retained sample at/older than ``cutoff`` (falling
        back to the oldest — a young series' window is its lifetime)."""
        base = self.samples[0]
        for s in self.samples:
            if s.t <= cutoff:
                base = s
            else:
                break
        return base

    def evaluate(self, now) -> dict:
        sp = self.spec
        cur = self._sample(now)
        self.samples.append(cur)
        # retention: keep exactly one sample at/older than the slow
        # window so _base always has its anchor
        while len(self.samples) >= 2 \
                and self.samples[1].t <= now - sp.slow_window_s:
            self.samples.popleft()

        def window(w):
            base = self._base(now - w)
            total = cur.total - base.total
            bad = cur.bad - base.bad
            counts = None
            if cur.counts is not None and base.counts is not None:
                counts = [a - b for a, b in zip(cur.counts, base.counts)]
            frac = bad / total if total else 0.0
            return total, bad, counts, frac

        ft, fb, fc, ffrac = window(sp.fast_window_s)
        st, sb, sc, sfrac = window(sp.slow_window_s)
        burn_fast = ffrac / sp.budget
        burn_slow = sfrac / sp.budget
        if sp.kind == "latency":
            value = percentile_from_counts(
                self.hist.buckets, sc or (), st, sp.percentile)
            ok = st == 0 or value <= sp.threshold
            target = sp.threshold
        else:
            value = 1.0 - sfrac          # good fraction, slow window
            ok = st == 0 or value >= sp.objective
            target = sp.objective
        budget_remaining = 1.0
        if st:
            budget_remaining = max(
                0.0, 1.0 - sb / (sp.budget * st))
        breached = (ft > 0 and burn_fast > sp.burn_threshold
                    and burn_slow > sp.burn_threshold)
        self.g_budget.set(round(budget_remaining, 6))
        self.g_burn_fast.set(round(burn_fast, 6))
        self.g_burn_slow.set(round(burn_slow, 6))
        status = {
            "name": sp.name, "metric": sp.metric, "kind": sp.kind,
            "ok": bool(ok), "breached": bool(breached),
            "value": float(value), "target": float(target),
            "burn_fast": float(burn_fast), "burn_slow": float(burn_slow),
            "budget_remaining": float(budget_remaining),
            "window_total": int(st),
        }
        return status


class SLOEngine:
    """Evaluate a set of :class:`SLOSpec` over one registry.

    ``maybe_evaluate(now)`` is the hot-path entry (the serving engine
    calls it once per scheduling step): one clock compare when the
    evaluation interval hasn't elapsed, a locked counter read per spec
    when it has.  ``status()`` forces an evaluation and returns the
    per-spec status dicts.  ``on_breach(status)`` fires once per
    not-breached -> breached transition."""

    def __init__(self, registry: Registry, specs, *, clock=None,
                 on_breach=None, eval_interval_s=None):
        import time as _time
        self._clock = _time.monotonic if clock is None else clock
        self._reg = registry
        self._specs = [s for s in (specs or [])]
        self._on_breach = on_breach
        if eval_interval_s is None:
            fast = min((s.fast_window_s for s in self._specs),
                       default=1.0)
            eval_interval_s = max(fast / 4.0, 0.05)
        self._interval = float(eval_interval_s)
        self._next_eval = float("-inf")
        self._states = [_SpecState(s, registry, self._clock)
                        for s in self._specs]
        self._last: list[dict] = []

    @property
    def specs(self):
        return list(self._specs)

    def maybe_evaluate(self, now=None):
        """Throttled :meth:`evaluate`; None when the interval hasn't
        elapsed or metrics are off."""
        if not self._states or not enabled():
            return None
        if now is None:
            now = self._clock()
        if now < self._next_eval:
            return None
        return self.evaluate(now)

    def evaluate(self, now=None) -> list:
        """Evaluate every spec now; returns the status list (empty
        with metrics off — there is no data to judge)."""
        if not enabled():
            return []
        if now is None:
            now = self._clock()
        self._next_eval = now + self._interval
        out = []
        for st in self._states:
            status = st.evaluate(now)
            if status["breached"] and not st.breached:
                st.breached = True
                st.c_breach.inc()
                _events.emit("slo.breach", slo=status["name"],
                             metric=status["metric"],
                             value=round(status["value"], 4),
                             target=status["target"],
                             burn_fast=round(status["burn_fast"], 4),
                             burn_slow=round(status["burn_slow"], 4))
                if self._on_breach is not None:
                    try:
                        self._on_breach(status)
                    except Exception:
                        pass   # a breach hook must never fail the loop
            elif st.breached and not status["breached"]:
                st.breached = False
                _events.emit("slo.recovered", slo=status["name"],
                             metric=status["metric"])
            out.append(status)
        self._last = out
        return out

    def status(self) -> list:
        """Current per-spec status (forces an evaluation)."""
        return self.evaluate()
