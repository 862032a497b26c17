"""The general generator of open-loop request traffic, driven by a traffic
file's parameters.

Every seed gets the same work at the same times: the arrival gaps, the
(prompt, output) length pairs and their order are drawn from the file's
``pool_seed``; ``--seed`` draws the prompt tokens (and, in the serve system's set-up,
the weights). With a few dozen requests in a window, the order alone
moves a tail percentile by a quarter from seed to seed, while two runs of
one schedule agree within a few percent (PERF.md); so the order
is part of the mix, as the rate and the lengths are.

Parameters (``loop: open``):

    rate_per_s          mean arrival rate (Poisson: gaps are exponential,
                        conditioned on the count that falls in the window)
    prompt, output      {"median", "sigma", "min", "max"}: log-normal
                        lengths in tokens, clipped to [min, max]
    pool_seed           seed of the gaps, the lengths and their order
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Arrival:
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # token ids
    max_new: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
    return np.clip(np.round(x), int(spec["min"]), int(spec["max"])).astype(
        np.int64)


def request_count(traffic: dict, seconds: float) -> int:
    return max(1, int(round(float(traffic["rate_per_s"]) * seconds)))


def schedule(traffic: dict, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """The window's arrivals, in due order."""
    n = request_count(traffic, seconds)
    pool = np.random.default_rng(int(traffic["pool_seed"]))
    gaps = pool.exponential(1.0, n + 1)
    plen = _lognormal(pool, traffic["prompt"], n)
    olen = _lognormal(pool, traffic["output"], n)
    # n arrivals of a Poisson process conditioned on n in the window
    due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
    rng = np.random.default_rng([seed, 11])
    return [Arrival(float(due[i]),
                    rng.integers(0, vocab, int(plen[i])).astype(np.int32),
                    int(olen[i])) for i in range(n)]


def prompt_buckets(traffic: dict, cap: int, minimum: int = 8) -> List[int]:
    """Padded prompt lengths (powers of two, at most ``cap``) that prompts
    of the file's length range fall into."""
    lo, hi = int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
    out, b = [], max(minimum, 1)
    while b < lo:
        b *= 2
    while True:
        out.append(min(b, cap))
        if b >= hi or b >= cap:
            break
        b *= 2
    return sorted(set(out))
