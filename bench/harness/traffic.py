"""The one traffic generator.  A mix is a JSON file of parameters:

    {"loop": "closed" | "open",
     "rate_per_s": 3.2,                 # open loop: mean arrivals per second
     "ramp_s": 4,                       # traffic runs this long before the
                                        # window opens (counted in set-up)
     "block": 8,                        # sizes come in blocks of this many
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 128, "max": 2048},
     "output": {"dist": "uniform", "min": 64, "max": 256}}

``dist`` is ``lognormal`` (median, sigma), ``loguniform`` or ``uniform``
over [min, max], clipped to [min, max].  Sizes and inter-arrival gaps are
not sampled: each block of ``block`` requests takes the evenly spaced
quantiles (i + 0.5) / block of each distribution, shuffled by a generator
fixed for the mix.  The offered sizes therefore stop at the top
quantile, (block - 0.5) / block: the longest request a mix offers, which
sizes the pool, is that quantile's.  Every seed offers the same schedule: which request comes when,
and how long it is, never depends on the seed, whose token ids change no
work.  (Shuffled by the seed, the open loop's queueing and a closed
loop's window each read as a different workload per seed.)
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles ((i + 0.5) / n) of one length
    distribution, rounded and clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(q)) for q in qs])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + qs * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + qs * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gap_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the exponential inter-arrival gap
    of a Poisson process at ``rate`` per second."""
    qs = (np.arange(n) + 0.5) / n
    return -np.log1p(-qs) / rate


class Traffic:
    """An endless stream of requests ``(prompt, n_tokens, gap_s)`` from one
    mix and one seed; ``gap_s`` is the open loop's wait before the
    request is due (0 in a closed loop)."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.vocab = int(vocab)
        self.block = int(mix["block"])
        self.rng = np.random.default_rng(seed)
        self._order = np.random.default_rng(0)
        self._p = quantiles(mix["prompt"], self.block)
        self._o = quantiles(mix["output"], self.block)
        self._g = (gap_quantiles(float(mix["rate_per_s"]), self.block)
                   if mix["loop"] == "open" else np.zeros(self.block))
        self._queue: List[Tuple[int, int, float]] = []

    def _refill(self) -> None:
        perm = [self._order.permutation(self.block) for _ in range(3)]
        self._queue = list(zip(self._p[perm[0]].tolist(),
                               self._o[perm[1]].tolist(),
                               self._g[perm[2]].tolist()))[::-1]

    def next(self) -> Tuple[np.ndarray, int, float]:
        if not self._queue:
            self._refill()
        s, n, gap = self._queue.pop()
        prompt = self.rng.integers(1, self.vocab, size=int(s),
                                   dtype=np.int64).astype(np.int32)
        return prompt, int(n), float(gap)
