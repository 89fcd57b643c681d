"""Metrics core: thread-safe Counter/Gauge/Histogram in named registries.

Design constraints (ISSUE 8 tentpole, part 1):

* ALWAYS-ON: the serving/training hot loops record through these on
  every step, so a record call is a flag check, a lock, and an int add.
  With ``PDTPU_METRICS=off`` every record call returns after ONE dict
  lookup — the off state restores pre-observability behavior (the on
  state's cost on the chip is PERF.md's reading, PR 40: ~1.5 us a
  compiled call, nothing end to end).
* Metrics whose values back a USER-VISIBLE contract (the serving
  engine's ``stats`` snapshot) are created with ``always=True`` and
  record regardless of the flag — ``stats`` returned those numbers
  before this subsystem existed, so the flag must not zero them.
* Histograms use FIXED log-spaced buckets (``LATENCY_BUCKETS_MS`` for
  latencies, ``COUNT_BUCKETS`` for small counts): merging snapshots
  across processes/ranks is elementwise addition, never re-bucketing.
* ``Registry.snapshot()`` returns plain nested JSON (dots in metric
  names nest); ``render_prometheus()`` emits the text exposition format
  with STABLE ordering (sorted by name, then label set) and standard
  escaping, so scrapes diff cleanly across runs.

Process-global named registries come from :func:`registry` (training
telemetry lands in the ``"default"`` one); subsystems that need private
metric namespaces — one serving engine's counters must not alias
another's — instantiate :class:`Registry` directly.
"""
from __future__ import annotations

import threading
from bisect import bisect_left

from ..core import state as _state

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "registry", "snapshot",
    "render_prometheus", "enabled", "LATENCY_BUCKETS_MS",
    "COUNT_BUCKETS", "percentile_from_counts",
]

# the flags dict itself (not a copy): set_flags mutates it in place, so
# caching the reference keeps the per-record check at one dict lookup
_FLAGS = _state._FLAGS


def enabled() -> bool:
    """The ``PDTPU_METRICS`` flag (``metrics`` in ``core/state.py``)."""
    return _FLAGS["metrics"]


# fixed log-spaced latency buckets (ms): 10 us .. ~56 s, 4 per decade.
# Fixed so histograms from different runs/ranks merge elementwise.
LATENCY_BUCKETS_MS = tuple(
    round(0.01 * 10 ** (i / 4), 6) for i in range(27))

# small-count buckets (tokens per window, preemptions per request, ...)
COUNT_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                 256.0, 1024.0, 4096.0)


def percentile_from_counts(buckets, counts, count, q) -> float:
    """Approximate percentile over fixed-bucket histogram state: the
    upper edge of the bucket holding the q-th observation (the fixed
    log-spaced buckets make this stable across runs).  ONE home for
    the math — :meth:`Histogram.percentile`, the SLO engine's windowed
    evaluation (``observability/slo.py``) and every report that quotes
    a percentile call here, so a report and a runtime guardrail can
    never disagree on what a p99 is.  The overflow bucket has no
    finite upper edge, so a percentile landing there is ``inf``; an
    empty histogram reads 0.0."""
    if not count or not buckets:
        return 0.0
    target = q * count
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target:
            return float(buckets[i]) if i < len(buckets) else float("inf")
    return float("inf")


class _Metric:
    __slots__ = ("name", "help", "labels", "_always", "_lock")

    def __init__(self, name, help="", labels=None, always=False):
        self.name = str(name)
        self.help = str(help)
        # sorted tuple of (k, v) pairs: the metric's identity key
        self.labels = tuple(sorted((str(k), str(v))
                            for k, v in (labels or {}).items()))
        self._always = bool(always)
        self._lock = threading.Lock()

    def _on(self) -> bool:
        return self._always or _FLAGS["metrics"]


class Counter(_Metric):
    """Monotone int counter. ``inc`` is the API; ``set`` exists for the
    registry-backed ``stats`` adapters that need max-tracking writes."""

    __slots__ = ("_value",)
    kind = "counter"

    def __init__(self, name, help="", labels=None, always=False):
        super().__init__(name, help, labels, always)
        self._value = 0

    def inc(self, n=1):
        if not self._on():
            return
        with self._lock:
            self._value += n

    def set(self, v):
        if not self._on():
            return
        with self._lock:
            self._value = v

    @property
    def value(self):
        return self._value

    def _snap(self):
        return self._value


class Gauge(_Metric):
    """Point-in-time value. ``set_function`` makes the gauge LAZY: the
    callable runs at snapshot/render time only, so gauges over device
    state never force a sync in the loop that owns them (the PDT112
    advice: lazily-read gauges instead of ``float(x)`` per step)."""

    __slots__ = ("_value", "_fn")
    kind = "gauge"

    def __init__(self, name, help="", labels=None, always=False):
        super().__init__(name, help, labels, always)
        self._value = 0.0
        self._fn = None

    def set(self, v):
        if not self._on():
            return
        with self._lock:
            self._value = v

    def set_function(self, fn):
        """Read ``fn()`` at snapshot time instead of a stored value."""
        self._fn = fn
        return self

    @property
    def value(self):
        if self._fn is not None:
            try:
                return self._fn()
            except Exception:
                return None
        return self._value

    def _snap(self):
        return self.value


class Histogram(_Metric):
    """Fixed-bucket histogram: ``counts[i]`` holds observations with
    ``value <= buckets[i]``; ``counts[-1]`` is the overflow bucket.
    Buckets are per-instance immutable, so :meth:`merge` is elementwise."""

    __slots__ = ("buckets", "counts", "sum", "count")
    kind = "histogram"

    def __init__(self, name, help="", buckets=None, labels=None,
                 always=False):
        super().__init__(name, help, labels, always)
        bk = tuple(float(b) for b in (buckets or LATENCY_BUCKETS_MS))
        if list(bk) != sorted(bk) or len(set(bk)) != len(bk):
            raise ValueError(f"histogram buckets must be strictly "
                             f"increasing, got {bk}")
        self.buckets = bk
        self.counts = [0] * (len(bk) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        if not self._on():
            return
        v = float(v)
        i = bisect_left(self.buckets, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def merge(self, other: "Histogram"):
        """Elementwise merge of another histogram's state (same bucket
        edges required — the point of fixing them)."""
        if other.buckets != self.buckets:
            raise ValueError(
                f"cannot merge histograms with different buckets: "
                f"{self.buckets} vs {other.buckets}")
        with self._lock:
            for i, c in enumerate(other.counts):
                self.counts[i] += c
            self.sum += other.sum
            self.count += other.count
        return self

    @property
    def mean(self):
        return self.sum / self.count if self.count else 0.0

    def percentile(self, q=0.99) -> float:
        """Approximate q-th percentile of everything observed so far
        (see :func:`percentile_from_counts` for the bucket semantics)."""
        with self._lock:
            counts = list(self.counts)
            n = self.count
        return percentile_from_counts(self.buckets, counts, n, q)

    def _snap(self):
        # under the lock: a concurrent observe must never yield a
        # snapshot whose count disagrees with its bucket counts (torn
        # reads would render invalid Prometheus histogram semantics)
        with self._lock:
            return {"count": self.count, "sum": self.sum,
                    "mean": (self.sum / self.count
                             if self.count else 0.0),
                    "buckets": list(self.buckets),
                    "counts": list(self.counts)}


class Registry:
    """Named metric registry. ``counter``/``gauge``/``histogram`` are
    get-or-create keyed on ``(name, labels)`` — calling twice with the
    same identity returns the SAME object (how shared counters like the
    StepGuard skip count work), with a conflicting kind it raises."""

    def __init__(self, name=None):
        self.name = name
        self._metrics: dict = {}
        self._lock = threading.Lock()

    def _get(self, cls, name, help, labels, always, **kw):
        key = (str(name), tuple(sorted((str(k), str(v)) for k, v in
                                       (labels or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help=help, labels=labels, always=always,
                        **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def counter(self, name, help="", labels=None, always=False
                ) -> Counter:
        return self._get(Counter, name, help, labels, always)

    def gauge(self, name, help="", labels=None, always=False) -> Gauge:
        return self._get(Gauge, name, help, labels, always)

    def histogram(self, name, help="", buckets=None, labels=None,
                  always=False) -> Histogram:
        h = self._get(Histogram, name, help, labels, always,
                      buckets=buckets)
        if buckets is not None and \
                tuple(float(b) for b in buckets) != h.buckets:
            # silently returning the existing object would land
            # observations in the wrong buckets; mismatched buckets
            # are a hard error, same contract as Histogram.merge
            raise ValueError(
                f"histogram {name!r} already registered with buckets "
                f"{h.buckets}, requested {tuple(buckets)}")
        return h

    def metrics(self):
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> dict:
        """Nested JSON: dots in metric names nest; labeled metrics nest
        one level further under ``"k=v,k2=v2"`` keys."""
        out: dict = {}
        for m in sorted(self.metrics(),
                        key=lambda m: (m.name, m.labels)):
            node = out
            parts = m.name.split(".")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            leaf = m._snap()
            if m.labels:
                slot = node.setdefault(parts[-1], {})
                slot[",".join(f"{k}={v}" for k, v in m.labels)] = leaf
            else:
                node[parts[-1]] = leaf
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition: families sorted by name, series
        sorted by label set, standard HELP/label-value escaping —
        STABLE output for golden tests and clean scrape diffs."""
        by_name: dict = {}
        for m in self.metrics():
            by_name.setdefault(m.name, []).append(m)
        lines = []
        for name in sorted(by_name):
            fam = sorted(by_name[name], key=lambda m: m.labels)
            pname = _prom_name(name)
            help_txt = next((m.help for m in fam if m.help), "")
            if help_txt:
                lines.append(f"# HELP {pname} {_esc_help(help_txt)}")
            lines.append(f"# TYPE {pname} {fam[0].kind}")
            for m in fam:
                lbl = _prom_labels(m.labels)
                if isinstance(m, Histogram):
                    snap = m._snap()   # one locked read: consistent
                    cum = 0
                    for edge, c in zip(snap["buckets"],
                                       snap["counts"]):
                        cum += c
                        lines.append(
                            f"{pname}_bucket"
                            f"{_prom_labels(m.labels, le=_fmt(edge))}"
                            f" {cum}")
                    lines.append(
                        f"{pname}_bucket"
                        f"{_prom_labels(m.labels, le='+Inf')}"
                        f" {snap['count']}")
                    lines.append(
                        f"{pname}_sum{lbl} {_fmt(snap['sum'])}")
                    lines.append(f"{pname}_count{lbl} {snap['count']}")
                else:
                    v = m._snap()
                    lines.append(
                        f"{pname}{lbl} {_fmt(v if v is not None else 0)}")
        return "\n".join(lines) + ("\n" if lines else "")


def _prom_name(name: str) -> str:
    out = "".join(c if (c.isalnum() or c in "_:") else "_"
                  for c in name)
    return out if not out[:1].isdigit() else "_" + out


def _esc_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _esc_label(s: str) -> str:
    return (s.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _fmt(v) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _prom_labels(pairs, **extra) -> str:
    items = list(pairs) + sorted(extra.items())
    if not items:
        return ""
    return ("{" + ",".join(f'{k}="{_esc_label(str(v))}"'
                           for k, v in items) + "}")


# ------------------------------------------------------------------
# process-global named registries
# ------------------------------------------------------------------
_registries: dict[str, Registry] = {}
_reg_lock = threading.Lock()


def registry(name: str = "default") -> Registry:
    """The process-global registry under ``name`` (created on demand).
    Training/runtime telemetry records into ``registry()``; serving
    engines keep private ``Registry()`` instances (exposed through
    ``engine.metrics()``) so per-engine counters never alias."""
    with _reg_lock:
        r = _registries.get(name)
        if r is None:
            r = _registries[name] = Registry(name)
        return r


def snapshot(name: str = "default") -> dict:
    return registry(name).snapshot()


def render_prometheus(name: str = "default") -> str:
    return registry(name).render_prometheus()
