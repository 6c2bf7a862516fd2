"""The one traffic generator: reads a mix file ``bench/traffic/<mix>.json``.

A mix file holds only parameters:

``rate_qps``
    Offered load.  Arrivals are open-loop: ``round(rate * seconds)``
    requests fall due inside the window, and their gaps are the
    exponential distribution's quantiles at that rate (a Poisson stream
    conditioned on its count), scaled to fill the window and shuffled
    in one fixed order.  Every seed so sends the same schedule; the
    seed draws what is asked, not when, so that the tail of the latency
    does not move with how one seed's gaps bunch.
``lengths``
    ``{terms per query: share}``.  Every seed sends the same multiset of
    lengths (largest-remainder rounding), shuffled.
``terms``
    How query terms are drawn, distinct within a query:
    ``{"rule": "df_band", "band": [lo, hi]}`` uniform over the terms
    whose document frequency lies in ``[lo, hi]`` of the collection;
    ``{"rule": "corpus_frequency", "skip_top": n}`` in proportion to
    corpus frequency, leaving out the ``n`` most frequent terms.
``cache_capacity``
    Result-cache entries of the served deployment under this mix.
``sample``
    How many answers of a run the reference checks (drawn from the
    seed; every cached answer is among them, up to this number).
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
_STREAM = 0x747266


def load(name: str, root: pathlib.Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _rng(seed: int, part: int) -> np.random.Generator:
    return np.random.default_rng([_STREAM, part, int(seed)])


def arrivals(mix: dict, seconds: float) -> np.ndarray:
    """f64[N] due times (seconds from the window's start), ascending,
    all inside ``[0, seconds)``."""
    n = max(int(round(float(mix["rate_qps"]) * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = _rng(0, 1).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def lengths(mix: dict, n: int, seed: int) -> np.ndarray:
    """i64[n] terms per query: the mix's shares, rounded to n exactly."""
    sizes = np.array(sorted(int(k) for k in mix["lengths"]), np.int64)
    share = np.array([float(mix["lengths"][str(s)]) for s in sizes])
    share = share / share.sum() * n
    count = np.floor(share).astype(np.int64)
    rest = np.argsort(-(share - count), kind="stable")[:n - count.sum()]
    count[rest] += 1
    return _rng(seed, 2).permutation(np.repeat(sizes, count))


def term_pool(mix: dict, df: np.ndarray, tokens: np.ndarray,
              num_docs: int) -> tuple[np.ndarray, np.ndarray]:
    """(term ids, draw weights) the mix's rule allows."""
    rule = mix["terms"]["rule"]
    if rule == "df_band":
        lo, hi = mix["terms"]["band"]
        frac = df / max(num_docs, 1)
        pool = np.flatnonzero((frac >= lo) & (frac <= hi))
        return pool, np.ones(len(pool))
    if rule == "corpus_frequency":
        order = np.argsort(-tokens, kind="stable")
        pool = np.sort(order[int(mix["terms"]["skip_top"]):])
        pool = pool[tokens[pool] > 0]
        return pool, tokens[pool].astype(np.float64)
    raise ValueError(f"unknown term rule {rule!r}")


def queries(mix: dict, df: np.ndarray, tokens: np.ndarray, num_docs: int,
            n: int, seed: int) -> list[np.ndarray]:
    """``n`` queries, each an i64 array of distinct term ids."""
    pool, w = term_pool(mix, df, tokens, num_docs)
    lens = lengths(mix, n, seed)
    if len(pool) < lens.max(initial=1):
        raise ValueError(f"term pool of {len(pool)} cannot fill a "
                         f"{lens.max()}-term query")
    rng = _rng(seed, 3)
    cdf = np.cumsum(w) / w.sum()
    out = []
    for length in lens:
        picked: list[int] = []
        while len(picked) < length:
            draw = pool[np.minimum(np.searchsorted(cdf, rng.random(
                2 * int(length))), len(pool) - 1)]
            for t in draw.tolist():
                if t not in picked:
                    picked.append(t)
                    if len(picked) == length:
                        break
        out.append(np.array(picked, np.int64))
    return out


def check_sample(mix: dict, n: int, cached: np.ndarray,
                 seed: int) -> np.ndarray:
    """Indices of the answers the reference checks: every cached one
    (up to the sample size), the rest drawn from the seed."""
    size = min(int(mix["sample"]), n)
    hits = np.flatnonzero(cached)[:size]
    rest = np.setdiff1d(np.arange(n), hits)
    more = _rng(seed, 4).choice(rest, size=size - len(hits), replace=False)
    return np.sort(np.concatenate([hits, more]))
