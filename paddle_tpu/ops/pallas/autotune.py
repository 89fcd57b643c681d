"""Kernel autotuning (SURVEY C14 — reference
``python/paddle/incubate/autotune.py`` set_config + the cached kernel
autotune of ``paddle/phi/kernels/autotune/switch_autotune.h``,
``cache.h``).

TPU shape: Pallas kernels have block-size free parameters; the autotuner
times each candidate configuration on the real shapes the model runs
and persists the winner per (device kind, op, shape signature) in a
JSON cache so later processes skip the sweep.

Tuned entries: ``flash_attention`` (forward block_q, block_k — see
flash_attention._autotuned_blocks), ``flash_attention_bwd`` (the FUSED
backward kernel's block pair, tuned separately over backward-specific
candidates — the backward's full-row q/do/dq VMEM buffers plus dk/dv
accumulators admit different winners than the forward, and the old
shared entry let the backward inherit forward-biased blocks — see
flash_attention._autotuned_bwd_blocks), ``paged_attention_ppb``
(pages_per_block of the ragged paged-KV serving kernel — see
paged_attention.pick_pages_per_block; candidates are powers of two
bounded by the block-table width and a VMEM cap, cache hits apply under
a trace, sweeps run on synthetic decode shapes when enabled),
``fused_optimizer_rows`` (row-block of the fused optimizer update —
fused_optimizer.pick_rows), ``quant_matmul_blocks`` ((bm, bn) output
tiling of the fused weight-only int8 matmul —
quant_matmul.pick_blocks) and ``fused_residual_norm_rows`` (row
block of the training glue kernels' fused residual-add+norm fwd/bwd
pair — fused_residual_norm.pick_glue_rows; the sweep times a full
grad-through-custom_vjp round trip since the bwd kernel replays the
same tile walk).

LIMITATION (measured, round 4): the sweep times candidates in an
isolated chained program; the winner inside a REAL train step can
differ by a few percent because XLA fuses/schedules the kernel
differently in context (e.g. the GPT-124M step runs fastest with
(256,512) although the isolated fwd+bwd chain ranks (512,1024) first).
The cache stores VALUES, so an end-to-end-measured winner can be pinned
by writing it into the cache file (``autotune.json`` under
``PDTPU_CACHE_DIR``); the repo ships none, and no cell of
``BENCHMARK.json`` turns the tuner on.

Disabled by default (the reference's autotune is also opt-in); enable
with ``paddle_tpu.incubate.autotune.set_config({"kernel": {"enable":
True}})`` or ``PDTPU_AUTOTUNE=1``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional, Sequence

_config = {"kernel": {"enable": os.environ.get("PDTPU_AUTOTUNE") == "1",
                      "tuning_range": [1, 10]}}
_cache: Optional[dict] = None
_CACHE_PATH = os.path.join(
    os.environ.get("PDTPU_CACHE_DIR",
                   os.path.expanduser("~/.cache/paddle_tpu")),
    "autotune.json")


def set_config(config=None):
    """Reference ``incubate/autotune.py set_config`` (kernel section)."""
    if config is None:
        _config["kernel"]["enable"] = True
        return
    if isinstance(config, str):  # file form
        with open(config) as f:
            config = json.load(f)
    if "kernel" in config:
        _config["kernel"].update(config["kernel"])


def enabled() -> bool:
    return bool(_config["kernel"]["enable"])


def _load_cache() -> dict:
    global _cache
    if _cache is None:
        try:
            with open(_CACHE_PATH) as f:
                _cache = json.load(f)
        except Exception:
            _cache = {}
    return _cache


def _store_cache():
    try:
        os.makedirs(os.path.dirname(_CACHE_PATH), exist_ok=True)
        with open(_CACHE_PATH, "w") as f:
            json.dump(_cache, f)
    except Exception:
        pass  # cache is an optimization, never an error


def _device_kind() -> str:
    import jax
    d = jax.devices()[0]
    return str(getattr(d, "device_kind", d.platform))


def _same_candidate(a, b):
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return list(a) == list(b)
    return a == b


def autotune(op: str, signature: str, candidates: Sequence,
             run: Callable, repeats: int = 3, measure: Callable = None,
             validate: Callable = None):
    """Pick the fastest candidate for ``run(candidate)`` and cache it.

    ``run`` must execute the kernel to completion (host-synced) — it is
    called once per candidate for warmup/compile and ``repeats`` times
    for timing. Failing candidates (e.g. VMEM overflow) are skipped.
    Returns the winning candidate (cached on later calls).

    ``measure``: optional ``cand -> seconds`` that owns its own timing
    (e.g. a dispatch-free scan-slope, as flash_attention._scan_slope —
    wall-timing individual dispatches over a network-attached chip is
    jitter-dominated and picks wrong winners). When given, ``run`` is
    not used. ``validate``: optional ``cand -> None`` called on each
    prospective winner in the caller's REAL execution context; if it
    raises (e.g. scoped-vmem overflow that the measuring context did
    not trigger), the candidate is discarded and the next-best wins."""
    key = f"{_device_kind()}|{op}|{signature}"
    cache = _load_cache()
    if key in cache:
        # the cached WINNER (value, not index: an index would silently
        # remap whenever the candidate list evolves); honor it only while
        # it is still a known candidate
        cached = cache[key]
        for cand in candidates:
            if _same_candidate(cand, cached):
                return cand
    scored = []
    for cand in candidates:
        try:
            if measure is not None:
                t = measure(cand)
            else:
                run(cand)  # compile + warm
                ts = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    run(cand)
                    ts.append(time.perf_counter() - t0)
                t = sorted(ts)[len(ts) // 2]
        except Exception:
            continue
        if t != float("inf"):  # inf = below timing resolution, not a score
            scored.append((t, cand))
    scored.sort(key=lambda tc: tc[0])
    best = None
    for _, cand in scored:
        if validate is not None:
            try:
                validate(cand)
            except Exception:
                continue
        best = cand
        break
    if best is None:
        raise RuntimeError(f"autotune: every candidate failed for {op} "
                           f"{signature}")
    cache[key] = list(best) if isinstance(best, (list, tuple)) else best
    _store_cache()
    return best


__all__ = ["set_config", "enabled", "autotune"]
