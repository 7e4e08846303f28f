"""The traffic generator: every seed offers the same work per block."""
import numpy as np

from harness.traffic import Traffic, quantiles

MIX = {"loop": "open", "rate_per_s": 4.0, "block": 8,
       "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.5,
                  "min": 20, "max": 400},
       "output": {"dist": "uniform", "min": 4, "max": 12}}


def _draw(seed, n):
    t = Traffic(MIX, seed, 1000)
    return [t.next() for _ in range(n)]


def test_same_schedule_every_seed_other_tokens():
    a, b = _draw(1, 16), _draw(2**31 + 5, 16)
    assert [(len(p), n, g) for p, n, g in a] == \
        [(len(p), n, g) for p, n, g in b]
    assert not np.array_equal(a[0][0], b[0][0])
    # each block holds every quantile once
    for blk in (slice(0, 8), slice(8, 16)):
        assert sorted(n for _, n, _ in a[blk]) == \
            sorted(quantiles(MIX["output"], 8).tolist())


def test_same_seed_same_inputs():
    a, b = _draw(7, 10), _draw(7, 10)
    assert all(np.array_equal(x[0], y[0]) and x[1:] == y[1:]
               for x, y in zip(a, b))


def test_quantiles_and_rate():
    q = quantiles({"dist": "loguniform", "min": 10, "max": 1000}, 4)
    assert q.min() >= 10 and q.max() <= 1000 and list(q) == sorted(q)
    gaps = [g for _, _, g in _draw(3, 8)]
    # the block's mean gap is near 1/rate
    assert abs(np.mean(gaps) - 1 / 4.0) < 0.05
