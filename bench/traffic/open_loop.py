"""Open-loop request traffic: independent users sending on a schedule.

Reads a mix file (``bench/traffic/<mix>.json``) of parameters:

    arrivals        "poisson"
    prompt_tokens   {"median", "sigma", "min", "max"}  lognormal, clipped
    output_tokens   {"median", "sigma", "min", "max"}  lognormal, clipped
    order_seed      the seed of the order in which gaps and lengths come

The schedule is cut into segments (warm-up, window, drain).  Each segment
of ``L`` seconds holds exactly ``round(rate * L)`` requests whose
inter-arrival gaps are stratified quantiles of the exponential, scaled to
fill the segment, and whose prompt and output lengths are stratified
quantiles of the stated lognormals, in an order drawn from ``order_seed``.
The run's seed draws the token ids.  So every seed offers the same work at
the same times.  On a TPU v5e a seed-drawn order of the output lengths
moved the tokens released in a window by 9% from seed to seed, and one of
the prompt lengths alone moved a seed's p95 latency by 7%, against under
2% between runs of one seed: which requests meet near the window's close
decides both.
"""
from __future__ import annotations

import math

import numpy as np
from statistics import NormalDist


def _lognormal_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as integers."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _exponential_grid(mean: float, n: int) -> np.ndarray:
    """``n`` stratified quantiles of an exponential with ``mean``."""
    p = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-p)


def generate(mix: dict, rng: np.random.Generator, *, rate_per_s: float,
             segments: list, vocab_size: int) -> list:
    """Requests at ``rate_per_s`` over consecutive segments of the given
    lengths in seconds; ``rng`` draws the prompts' token ids.

    Returns dicts ``{"due_s", "prompt", "max_new_tokens"}`` sorted by due
    time; ``prompt`` is a list of token ids in ``[1, vocab_size)``."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"open_loop: unknown arrivals {mix['arrivals']!r}")
    order = np.random.default_rng(mix["order_seed"])
    out, start = [], 0.0
    for length in segments:
        n = int(round(rate_per_s * length))
        if n:
            gaps = order.permutation(_exponential_grid(1.0, n))
            gaps *= length / gaps.sum()
            due = start + np.cumsum(gaps) - gaps[0]
            prompts = order.permutation(
                _lognormal_grid(mix["prompt_tokens"], n))
            outputs = order.permutation(
                _lognormal_grid(mix["output_tokens"], n))
            for t, n_in, n_out in zip(due, prompts, outputs):
                out.append({"due_s": float(t),
                            "prompt": rng.integers(1, vocab_size,
                                                   int(n_in)).tolist(),
                            "max_new_tokens": int(n_out)})
        start += length
    return out
