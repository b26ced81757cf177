"""Seeded traffic: the same seed gives the same pool, another seed another
order of the same sizes."""

import json
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen  # noqa: E402

MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))


def _pool(mix, seed, n=128):
    traffic = json.loads(mix.read_text())
    return loadgen.make_pool(traffic, seed, 1000, n, rate=4.0)


def _key(pool):
    return [(i.prompt_len, i.max_new, i.due) for i in pool.items]


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_schedule(mix):
    a, b = _pool(mix, 2**31 + 5), _pool(mix, 2**31 + 5)
    assert _key(a) == _key(b)
    assert (a.prompt(a.items[3]) == b.prompt(b.items[3])).all()


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_other_seed_other_order_same_sizes(mix):
    a, b = _pool(mix, 11), _pool(mix, 12)
    assert _key(a) != _key(b)
    assert (a.prompt(a.items[0])[:4] != b.prompt(b.items[0])[:4]).any()
    # each block of 64 holds the same quantiles in another order
    for block in range(2):
        sl = slice(64 * block, 64 * (block + 1))
        assert Counter(i.prompt_len for i in a.items[sl]) == \
            Counter(i.prompt_len for i in b.items[sl])


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_every_run_of_eight_holds_one_of_each_stratum(seed):
    """A window that serves any stretch of the pool sees the same mix:
    every 8 consecutive requests from a run boundary hold one quantile of
    each eighth of the distribution."""
    dist = {"dist": "lognormal", "median": 1000, "sigma": 0.8, "min": 1,
            "max": 10**9}
    base = sorted(loadgen.stratified(dist, 64, seed, 1))
    xs = loadgen.stratified(dist, 256, seed, 1)
    for r in range(0, 256, 8):
        run = sorted(xs[r:r + 8])
        for i, v in enumerate(run):
            assert base[8 * i] <= v <= base[8 * i + 7]


def test_lengths_respect_bounds_and_median():
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
            "max": 1024}
    xs = loadgen.stratified(dist, 640, 3, 1)
    assert xs.min() >= 32 and xs.max() <= 1024
    assert 240 <= sorted(xs)[320] <= 272
    uni = loadgen.stratified({"dist": "uniform", "min": 32, "max": 128},
                             64, 3, 2)
    assert uni.min() == 32 and uni.max() == 128


def test_open_loop_arrivals_follow_the_rate():
    traffic = {"loop": "open", "prompt": {"dist": "constant", "value": 8,
                                          "min": 8, "max": 8},
               "output": {"dist": "constant", "value": 4, "min": 4,
                          "max": 4}}
    pool = loadgen.make_pool(traffic, 1, 100, 640, rate=5.0)
    dues = [i.due for i in pool.items]
    assert all(b > a for a, b in zip(dues, dues[1:]))
    assert dues[-1] == pytest.approx(640 / 5.0, rel=0.05)
    with pytest.raises(ValueError):
        loadgen.make_pool(traffic, 1, 100, 8, rate=0.0)
