"""Seeded traffic: request sizes, prompts and arrival times.

A traffic mix is a JSON file under ``bench/traffic``; this module turns it
and a seed into a request pool. Every seed draws the same set of sizes and
gaps in another order: the pool is cut into blocks of ``block`` requests
and each block holds the distribution's ``block`` quantiles once. Within a
block, every run of ``sub`` consecutive requests holds one quantile of each
of the block's ``sub`` strata (its lowest ``block/sub`` quantiles, the
next, ...), and the seed picks which and in what order. So any stretch of
the pool that a window serves, not only a whole block, sees nearly the
same mix of lengths, and the spread between seeds measures the system,
not the draw.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_NORMAL = NormalDist()


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def quantile(dist: dict, u: float) -> float:
    """Inverse CDF of a length or gap distribution at ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"] + 1)
        v = math.floor(v)
    elif kind == "exponential":
        v = -math.log1p(-u) / dist["rate"]
        return v
    elif kind == "constant":
        v = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return float(min(max(round(v), dist["min"]), dist["max"]))


def stratified(dist: dict, n: int, seed: int, stream: int,
               block: int = 64, sub: int = 8) -> np.ndarray:
    """``n`` draws: each block of ``block`` holds every block quantile once,
    and each run of ``sub`` in it one quantile of every stratum, in an
    order set by the seed."""
    us = (np.arange(block) + 0.5) / block
    base = np.array([quantile(dist, float(u)) for u in us])
    strata = base.reshape(sub, block // sub)     # row i: the i-th stratum
    rng = _rng(seed, stream)
    out = []
    for _ in range(-(-n // block)):
        # column j of ``pick``: which member of each stratum run j takes
        pick = np.stack([rng.permutation(block // sub) for _ in range(sub)])
        for j in range(block // sub):
            run = strata[np.arange(sub), pick[:, j]]
            out.append(run[rng.permutation(sub)])
    return np.concatenate(out)[:n]


@dataclasses.dataclass
class Item:
    index: int
    prompt_len: int
    max_new: int
    due: float = 0.0          # open loop: offset from the loop's start (s)


@dataclasses.dataclass
class Pool:
    traffic: dict
    seed: int
    vocab: int
    items: List[Item]

    def prompt(self, item: Item) -> np.ndarray:
        """The prompt's token ids, drawn from (seed, request index)."""
        return _rng(self.seed, 3, item.index).integers(
            0, self.vocab, item.prompt_len, dtype=np.int32)


def make_pool(traffic: dict, seed: int, vocab: int, n: int,
              rate: float = 0.0) -> Pool:
    """The first ``n`` requests of the mix. ``rate`` (requests per second)
    sets the open loop's Poisson arrivals; a closed loop has no schedule."""
    prompts = stratified(traffic["prompt"], n, seed, 1)
    outs = stratified(traffic["output"], n, seed, 2)
    dues = np.zeros(n)
    if traffic["loop"] == "open":
        if rate <= 0:
            raise ValueError("an open loop needs a positive rate")
        gaps = stratified({"dist": "exponential", "rate": rate}, n, seed, 4)
        dues = np.cumsum(gaps)
    items = [Item(i, int(p), int(o), float(d))
             for i, (p, o, d) in enumerate(zip(prompts, outs, dues))]
    return Pool(traffic, seed, vocab, items)
