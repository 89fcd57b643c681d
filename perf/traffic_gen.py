"""The one general traffic generator: a traffic file's parameters and a
seed in, training batches or a request schedule out.

A seed changes the ORDER of the work and the tokens, never the amount:
lengths and inter-arrival gaps are a fixed set of quantiles of the
distributions the file names, shuffled by the seed, so that two seeds
offer the same load and differ only in how it falls.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """``--seed`` is any whole number, the driver's are above 2**31."""
    seed = int(seed) & ((1 << 63) - 1)
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


# ------------------------------------------------------------- training
def causal_lm_batch(rng, spec, vocab):
    """(ids, labels): ``labels`` are the next tokens."""
    tok = rng.integers(0, vocab, (spec["rows"], spec["seq_len"] + 1),
                       dtype=np.int32)
    return tok[:, :-1].copy(), tok[:, 1:].copy()


def mlm_batch(rng, spec, vocab):
    """(ids, segment ids, masked-LM labels, next-sentence labels):
    ``masked_per_row`` positions of every row carry a label, the rest
    -100; a row is segment 0 up to a random cut and segment 1 after."""
    rows, seq, k = spec["rows"], spec["seq_len"], spec["masked_per_row"]
    ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
    cut = rng.integers(seq // 4, 3 * seq // 4, (rows, 1))
    seg = (np.arange(seq)[None, :] >= cut).astype(np.int32)
    mlm = np.full((rows, seq), -100, np.int32)
    pos = np.argsort(rng.random((rows, seq)), axis=1)[:, :k]
    np.put_along_axis(mlm, pos, rng.integers(
        0, vocab, (rows, k), dtype=np.int32), axis=1)
    nsp = rng.integers(0, 2, (rows,), dtype=np.int32)
    return ids, seg, mlm, nsp


_TASKS = {"causal_lm": causal_lm_batch, "mlm": mlm_batch}


def train_batches(spec, vocab, seed, count):
    """``count`` distinct host batches for ``spec`` (a traffic file's
    ``batch``); ``vocab`` bounds the token ids (the published
    vocabulary, not a padded one)."""
    if spec["task"] not in _TASKS:
        raise ValueError(f"unknown training task {spec['task']!r}; "
                         f"known: {sorted(_TASKS)}")
    rng = rng_of(seed, 2)
    return [_TASKS[spec["task"]](rng, spec, vocab) for _ in range(count)]


def tokens_per_step(spec):
    return spec["rows"] * spec["seq_len"]


# -------------------------------------------------------------- serving
def lognormal_quantiles(spec, n):
    """``n`` lengths: the (i + 1/2)/n quantiles of a lognormal with the
    given median and sigma, clipped to [lo, hi]."""
    nd = NormalDist()
    out = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(
        (i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(out), spec["lo"], spec["hi"]).astype(np.int64)


def exponential_gaps(n, span):
    """``n`` inter-arrival gaps of a Poisson process, as the quantiles
    of the exponential distribution, scaled to sum to ``span``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (span / q.sum())


def requests(spec, vocab, seed, seconds):
    """The schedule of a serving mix.

    ``spec`` is a traffic file's ``requests``.  With a ``rate`` (open
    loop) it gives ``round(rate * seconds)`` requests whose due times
    are a Poisson process's over ``seconds``; with a ``backlog``
    (closed loop) that many requests, all due at 0, which the driver
    cycles through.  Returns a list of dicts: due, prompt (int32
    tokens), max_new."""
    rng = rng_of(seed, 3)
    if "rate" in spec:
        n = max(1, round(spec["rate"] * seconds))
        due = np.cumsum(rng.permutation(exponential_gaps(n, seconds)))
        due -= due[0] / 2          # the first is due just inside the window
    else:
        n = int(spec["backlog"])
        due = np.zeros(n)
    p_len = rng.permutation(lognormal_quantiles(spec["prompt_len"], n))
    o_len = rng.permutation(lognormal_quantiles(spec["output_len"], n))
    cap = spec["max_total"]
    out = []
    for i in range(n):
        p = int(p_len[i])
        o = int(min(o_len[i], cap - p))
        out.append({"due": float(due[i]),
                    "prompt": rng.integers(0, vocab, (p,), dtype=np.int32),
                    "max_new": o})
    return out
