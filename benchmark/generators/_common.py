"""Shared pieces of the generators: two random streams and fixed arrivals.

The shape of a schedule (how many requests, their sizes, their order and their
arrival times) comes from the traffic file's ``shape_seed``; the run's ``--seed``
draws the token ids, so every seed sends other prompts on the same schedule.
Permuting sizes and gaps by the seed was tried first (PERF.md, Findings, PR 23):
tokens a second then differed by 6% between seeds and by 0.2% between two runs of
one seed, because which requests straddle the window's edges changed the work.
"""

from __future__ import annotations

import numpy as np

FIRST_TOKEN_ID = 1000  # ids below are left to role words, eos and pad (a quarter of a tiny vocabulary)


def streams(shape_seed: int, seed: int):
    return (np.random.default_rng([int(shape_seed), 0x5AFE]),
            np.random.default_rng([int(seed), 0xBE7C]))


def fixed_arrivals(shape_rng, count: int, start_s: float, span_s: float) -> list[float]:
    """``count`` Poisson-like arrivals in [start_s, start_s + span_s): exponential
    gaps from the shape stream, scaled to fill the span."""
    if count <= 0:
        return []
    gaps = shape_rng.exponential(1.0, size=count + 1)
    gaps = gaps / gaps.sum() * span_s
    return [float(start_s + t) for t in np.cumsum(gaps)[:count]]


def clipped_lognormal(rng, count: int, median: float, sigma: float, lo: int, hi: int):
    return np.clip(np.rint(rng.lognormal(np.log(median), sigma, size=count)), lo, hi).astype(int)


def token_ids(rng, count: int, vocab_size: int) -> list[int]:
    return rng.integers(min(FIRST_TOKEN_ID, vocab_size // 4), vocab_size, size=int(count)).tolist()
