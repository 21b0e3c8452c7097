"""What every traffic kind draws from: user histories and their sizes.

A traffic mix (``bench/traffic/<name>.json``) gives distributions; these
functions turn them into requests.  Sizes are stratified, not sampled: a
mix of ``n`` requests always holds the same ``n`` history lengths (the
quantiles of the distribution) and, for open loops, the same ``n``
inter-arrival gaps, and the seed only shuffles their order and draws the
item ids.  So two seeds do the same work in another order, and the spread
between runs is the system's, not the generator's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


@dataclass
class Work:
    """One request to send: a user's history (item ids, oldest first), the
    k asked for, and when it is due, in seconds after the window opens
    (closed loops send when a slot frees and leave ``due`` at 0)."""
    rid: int
    history: np.ndarray
    k: int
    due: float = 0.0


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed), sum(map(ord, stream))])


def quantiles(n: int) -> np.ndarray:
    """The midpoints of ``n`` equal-probability strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def history_lengths(spec: Dict[str, Any], n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at the quantiles of ``spec`` (``log_uniform`` on
    [min, max], whole numbers), in an order drawn from ``rng``."""
    if spec["dist"] != "log_uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"] + 1)
    lengths = np.floor(np.exp(lo + quantiles(n) * (hi - lo))).astype(np.int64)
    return rng.permutation(np.clip(lengths, spec["min"], spec["max"]))


def exponential_gaps(n: int, rate: float,
                     rng: np.random.Generator) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (seconds) at the quantiles of the
    exponential distribution of mean ``1 / rate``, in a drawn order."""
    return rng.permutation(-np.log1p(-quantiles(n)) / rate)


class ItemSampler:
    """Item ids 1..n_items drawn with Zipf popularity: id ``r`` has weight
    ``r ** -exponent`` (id 1 the most popular)."""

    def __init__(self, spec: Dict[str, Any], n_items: int):
        if spec["dist"] != "zipf":
            raise ValueError(f"unknown item distribution {spec['dist']!r}")
        w = np.arange(1, n_items + 1, dtype=np.float64) ** -spec["exponent"]
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        ids = np.searchsorted(self.cdf, rng.random(n), side="right") + 1
        return np.minimum(ids, len(self.cdf)).astype(np.int32)


def histories(traffic: Dict[str, Any], cfg: Dict[str, Any], n: int,
              seed: int) -> List[np.ndarray]:
    """``n`` user histories of the mix's lengths and item popularity."""
    lengths = history_lengths(traffic["history"], n, rng_for(seed, "lengths"))
    ids = ItemSampler(traffic["items"], cfg["n_items"]).draw(
        rng_for(seed, "items"), int(lengths.sum()))
    return np.split(ids, np.cumsum(lengths)[:-1])
