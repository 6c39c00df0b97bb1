"""The traffic generator: lognormal prompt and output lengths with open-loop
arrivals, every number taken from a mix file under bench/traffic/.

The lengths follow ``serving/workload.py``'s lognormal shape (a mean, a
sigma, clipped to a range); arrivals are either exponential gaps at a fixed
rate (``"poisson"``) or a backlog that is all due when the window opens
(``"backlog"``).

Every seed gets the same work in another order. A window of ``n`` requests
takes its lengths and gaps at the ``n`` stratified quantiles
``(i + 0.5) / n`` of their distributions, and the seed only permutes them
(and draws the prompt token ids). Two seeds therefore differ in which
requests meet, not in how much there is to do, so the spread between runs
measures the system and not the draw.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def lognormal_quantiles(n: int, mean: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal with this mean, clipped to
    [lo, hi] and rounded down to whole tokens (as ``workload.py`` does)."""
    mu = math.log(mean) - sigma ** 2 / 2
    q = [math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.asarray(q), lo, hi).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` stratified quantiles of the gap between Poisson arrivals."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, block: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, block])


def window_requests(mix: dict, seconds: float) -> int:
    """How many requests one window of ``seconds`` holds."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, round(arr["rate_per_s"] * seconds))
    if arr["kind"] == "backlog":
        return max(1, round(arr["requests_per_window_s"] * seconds))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def block(mix: dict, seconds: float, seed: int, index: int,
          vocab_size: int) -> list:
    """Requests of window-long block ``index`` (0 is the measured window,
    later blocks keep the load on while it drains), as dicts with ``due``
    (seconds from the block's start), ``prompt`` (token ids) and
    ``max_tokens``. Backlogs have one block only."""
    arr = mix["arrivals"]
    if arr["kind"] == "backlog" and index > 0:
        return []
    n = window_requests(mix, seconds)
    rng = _rng(seed, index)
    p, o = mix["prompt"], mix["output"]
    prompts = rng.permutation(lognormal_quantiles(
        n, p["mean"], p["sigma"], p["min"], p["max"]))
    outputs = rng.permutation(lognormal_quantiles(
        n, o["mean"], o["sigma"], o["min"], o["max"]))
    if arr["kind"] == "poisson":
        gaps = rng.permutation(exponential_gaps(n, arr["rate_per_s"]))
        # the block's n gaps, scaled to fill it exactly: request 0 is due at
        # the block's start and request n-1 one gap before its end
        due = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    else:
        due = np.zeros(n)
    return [{"due": float(d), "max_tokens": int(m),
             "prompt": rng.integers(1, vocab_size, int(pl)).tolist()}
            for d, pl, m in zip(due, prompts, outputs)]


def prompt_lengths(mix: dict) -> range:
    """Every prompt length the mix can send."""
    return range(mix["prompt"]["min"], mix["prompt"]["max"] + 1)
