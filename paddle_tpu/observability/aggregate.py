"""Fleet-wide metrics aggregation over the TCP store (ISSUE 12).

PR 8's registries are per-process islands: every rank of a DP×TP×PP
job (or every disaggregated prefill/decode worker) holds its own
``train.step_ms`` histogram and ``overlap_frac`` gauge, and nothing
answers "which rank is the straggler, and in which phase" without
ssh-ing around.  :func:`fleet_snapshot` closes that: every rank
publishes its registry snapshot through the rendezvous ``TCPStore``
(the transport ``distributed/rpc`` already bootstraps from; every
store op rides the store's own bounded ``resilience.retry``), gathers
the fleet's snapshots with a straggler-tolerant timeout (a dead rank
becomes a ``missing`` entry, not a hang), and merges them:

* **Counters** sum.
* **Histograms** merge elementwise — the fixed log-spaced buckets
  exist precisely so cross-rank merge is addition
  (``Histogram.merge`` semantics, applied to serialized snapshots).
* **Gauges** keep per-rank identity: a ``rank=N`` label is appended,
  because averaging ``overlap_frac`` across ranks would hide exactly
  the straggler the gauge exists to expose.

On top of the merge, :func:`derive_skew` computes the cross-rank
attribution the TPU-vs-GPU serving comparisons and disaggregated
prefill/decode designs (PAPERS.md #2/#4) frame their tuning in:
per-rank ``train.step_ms`` p50/mean, the p50 spread, the slowest
rank, its slowest *phase* (which ``train.*`` component histogram —
opt/comm/compile — exceeds the fleet median by the largest ratio)
and ``overlap_frac`` per rank.

Gating: with ``PDTPU_METRICS=off`` :func:`fleet_snapshot` returns
``{}`` without touching the store — the flag's cheap-no-op contract.

Single-controller note: one SPMD host is one rank; ``fleet_snapshot()``
with no store degenerates to the local snapshot, and multi-host jobs
pass the launcher's store + ``world_size``/``rank``.
"""
from __future__ import annotations

import json
import math

from . import metrics as _metrics
from .events import SCHEMA_VERSION
from .metrics import enabled
from .tracing import trace_host, trace_rank

__all__ = [
    "fleet_snapshot", "publish_snapshot", "gather_snapshots",
    "merge_snapshots", "derive_skew", "SNAP_PREFIX",
]

SNAP_PREFIX = "__obs/snap"

# the per-phase train component histograms derive_skew attributes a
# slow rank to (step_ms is the whole; these are its parts)
_PHASE_HISTS = ("train.opt_step_ms", "train.comm_ms",
                "train.compile_ms")


def _local_payload(registry=None, rank=None) -> dict:
    """This process's registry serialized for cross-rank merge: a FLAT
    metric list keeping each metric's ``kind`` — the nested
    ``snapshot()`` JSON drops the counter/gauge distinction the merge
    rules need."""
    reg = registry if registry is not None else _metrics.registry()
    mts = []
    for m in sorted(reg.metrics(), key=lambda m: (m.name, m.labels)):
        e = {"name": m.name, "kind": m.kind,
             "labels": [list(kv) for kv in m.labels]}
        if m.kind == "histogram":
            s = m._snap()
            e.update(count=s["count"], sum=s["sum"],
                     buckets=s["buckets"], counts=s["counts"])
        else:
            v = m._snap()
            e["value"] = v if isinstance(v, (int, float, bool)) \
                or v is None else str(v)
        mts.append(e)
    return {"schema_version": SCHEMA_VERSION,
            "rank": trace_rank() if rank is None else int(rank),
            "host": trace_host(), "metrics": mts}


def _key(prefix, generation, rank) -> str:
    return f"{prefix}/{generation}/{rank}" if generation is not None \
        else f"{prefix}/{rank}"


def publish_snapshot(store, rank, registry=None, *, generation=None,
                     prefix=SNAP_PREFIX):
    """Publish this rank's snapshot under the store key; ``set`` rides
    the store's bounded retry (``TCPStore._call``)."""
    payload = json.dumps(_local_payload(registry, rank=rank),
                         sort_keys=True)
    store.set(_key(prefix, generation, rank), payload.encode())


def gather_snapshots(store, world_size, *, timeout=5.0,
                     generation=None, prefix=SNAP_PREFIX):
    """Read every rank's published snapshot.  ``timeout`` is the
    per-rank straggler budget: a rank that never published lands in
    the returned ``missing`` list instead of stalling the fleet view
    (its counters are simply absent from the merge — counters and
    histograms only grow, so the merged view is a valid lower bound)."""
    snaps: dict[int, dict] = {}
    missing: list[int] = []
    for r in range(int(world_size)):
        try:
            raw = store.get(_key(prefix, generation, r),
                            timeout=timeout)
            snaps[r] = json.loads(raw.decode())
        except (TimeoutError, ValueError, KeyError):
            missing.append(r)
    return snaps, missing


# ------------------------------------------------------------- merge --
def _label_str(labels) -> str:
    return ",".join(f"{k}={v}" for k, v in labels)


def _nest(out, name, labels, leaf):
    node = out
    parts = name.split(".")
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    if labels:
        node.setdefault(parts[-1], {})[_label_str(labels)] = leaf
    else:
        node[parts[-1]] = leaf


def merge_snapshots(snaps: dict) -> dict:
    """Elementwise merge of ``{rank: payload}`` into one nested
    snapshot (``Registry.snapshot()`` shape): counters sum, histograms
    add bucket-for-bucket (mismatched buckets raise, the
    ``Histogram.merge`` contract), gauges fan out under an appended
    ``rank=N`` label."""
    counters: dict = {}
    hists: dict = {}
    gauges: dict = {}
    for r in sorted(snaps):
        for m in snaps[r].get("metrics", []):
            labels = tuple(tuple(kv) for kv in m.get("labels", []))
            key = (m["name"], labels)
            if m["kind"] == "counter":
                counters[key] = counters.get(key, 0) + m.get("value", 0)
            elif m["kind"] == "histogram":
                h = hists.get(key)
                if h is None:
                    hists[key] = {"count": m["count"], "sum": m["sum"],
                                  "buckets": list(m["buckets"]),
                                  "counts": list(m["counts"])}
                else:
                    if list(m["buckets"]) != h["buckets"]:
                        raise ValueError(
                            f"cannot merge histogram {m['name']!r}: "
                            f"rank {r} buckets {m['buckets']} != "
                            f"{h['buckets']}")
                    h["count"] += m["count"]
                    h["sum"] += m["sum"]
                    for i, c in enumerate(m["counts"]):
                        h["counts"][i] += c
            else:   # gauge: per-rank labels
                gauges[(m["name"],
                        labels + (("rank", str(r)),))] = m.get("value")
    out: dict = {}
    for (name, labels), v in sorted(counters.items()):
        _nest(out, name, labels, v)
    for (name, labels), h in sorted(hists.items()):
        h["mean"] = h["sum"] / h["count"] if h["count"] else 0.0
        _nest(out, name, labels, h)
    for (name, labels), v in sorted(gauges.items()):
        _nest(out, name, labels, v)
    return out


# -------------------------------------------------------------- skew --
def _find_metric(payload, name, kind):
    for m in payload.get("metrics", []):
        if m["name"] == name and m["kind"] == kind \
                and not m.get("labels"):
            return m
    return None


def _hist_quantile(m, q):
    """Bucket-resolution quantile: the upper edge of the first bucket
    whose cumulative count reaches ``q`` (inf for the overflow bucket)
    — deterministic, merge-consistent, good enough for spread/argmax."""
    if m is None or not m.get("count"):
        return None
    target = q * m["count"]
    cum = 0
    for edge, c in zip(m["buckets"], m["counts"]):
        cum += c
        if cum >= target:
            return float(edge)
    return math.inf


def derive_skew(snaps: dict, metric="train.step_ms") -> dict:
    """Cross-rank skew over ``{rank: payload}``: per-rank p50/mean of
    ``metric``, the p50 spread, slowest-rank attribution (rank AND the
    ``train.*`` phase histogram most above the fleet median), plus
    ``train.overlap_frac`` per rank."""
    p50: dict = {}
    mean: dict = {}
    phase_means: dict = {}
    overlap: dict = {}
    for r in sorted(snaps):
        m = _find_metric(snaps[r], metric, "histogram")
        qv = _hist_quantile(m, 0.5)
        if qv is not None:
            p50[r] = qv
            mean[r] = round(m["sum"] / m["count"], 4)
        for ph in _PHASE_HISTS:
            hm = _find_metric(snaps[r], ph, "histogram")
            if hm is not None and hm.get("count"):
                phase_means.setdefault(ph, {})[r] = \
                    hm["sum"] / hm["count"]
        g = _find_metric(snaps[r], "train.overlap_frac", "gauge")
        if g is not None:
            overlap[r] = g.get("value")
    out = {"metric": metric,
           "p50_ms": p50, "mean_ms": mean,
           "overlap_frac": overlap,
           "slowest_rank": None, "slowest_phase": None,
           "p50_spread_ms": 0.0}
    if p50:
        finite = {r: v for r, v in p50.items() if math.isfinite(v)}
        ranked = finite or p50
        # slowest by p50, ties broken by mean then lowest rank
        slowest = max(sorted(ranked),
                      key=lambda r: (ranked[r], mean.get(r, 0.0)))
        out["slowest_rank"] = slowest
        vals = list(finite.values())
        if vals:
            spread = max(vals) - min(vals)
            out["p50_spread_ms"] = round(spread, 4)
            if min(vals) > 0:
                out["p50_spread_frac"] = round(spread / min(vals), 4)
        # phase attribution: which component histogram of the slowest
        # rank sits furthest above the OTHER ranks' median of that
        # phase — the slowest rank's own value must be excluded or a
        # 2-rank fleet's median IS its max and every ratio caps at 1.0
        # (attribution would degenerate to _PHASE_HISTS order)
        worst_ratio = 0.0
        for ph, per_rank in phase_means.items():
            if slowest not in per_rank or len(per_rank) < 2:
                continue
            others = sorted(v for r2, v in per_rank.items()
                            if r2 != slowest)
            med = others[len(others) // 2]
            if med > 0:
                ratio = per_rank[slowest] / med
                if ratio > worst_ratio:
                    worst_ratio = ratio
                    out["slowest_phase"] = ph
        if out["slowest_phase"] is None and phase_means:
            # single-rank fleets / no comparable phase data: largest
            # absolute component of the slowest rank
            best = max((ph for ph in phase_means
                        if slowest in phase_means[ph]),
                       key=lambda ph: phase_means[ph][slowest],
                       default=None)
            out["slowest_phase"] = best
    return out


def fleet_snapshot(store=None, world_size=None, rank=None,
                   registry=None, *, timeout=5.0, generation=None,
                   prefix=SNAP_PREFIX) -> dict:
    """One call answers "which rank is the straggler, in which phase":
    publish this rank's registry snapshot, gather every rank's through
    the TCP store (straggler-tolerant ``timeout`` per rank), and
    return ``{merged, skew, ranks, missing, ...}``.

    Collective when ``store``+``world_size`` are given (every rank
    calls it; all ranks get the fleet view — store reads are cheap);
    with no store it degenerates to the local single-rank view.
    ``generation`` namespaces repeat collections; without it ranks
    overwrite their key in place (snapshots are monotone, so a mixed
    read is a valid lower bound).  Returns ``{}`` when metrics are
    off (cheap no-op)."""
    if not enabled():
        return {}
    rank = trace_rank() if rank is None else int(rank)
    if store is None or not world_size or int(world_size) <= 1:
        snaps = {rank: _local_payload(registry, rank=rank)}
        missing: list[int] = []
        world_size = 1
    else:
        publish_snapshot(store, rank, registry,
                         generation=generation, prefix=prefix)
        snaps, missing = gather_snapshots(
            store, world_size, timeout=timeout,
            generation=generation, prefix=prefix)
    return {
        "schema_version": SCHEMA_VERSION,
        "world_size": int(world_size),
        "rank": rank,
        "ranks": sorted(snaps),
        "missing": missing,
        "hosts": {r: snaps[r].get("host") for r in sorted(snaps)},
        "merged": merge_snapshots(snaps),
        "skew": derive_skew(snaps),
    }
