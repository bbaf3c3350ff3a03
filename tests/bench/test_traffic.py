"""The general traffic generator: every seed gets the same work."""
import numpy as np
import pytest

from bench import traffic

MIX = {"backlog": 128,
       "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                  "min": 64, "max": 1024},
       "output": {"dist": "lognormal", "median": 512, "sigma": 0.35,
                  "min": 256, "max": 1024}}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_same_lengths_every_seed(seed):
    reqs = traffic.backlog(MIX, seed, 32000, group=64)
    base = traffic.backlog(MIX, 1, 32000, group=64)
    assert len(reqs) == 128
    assert sorted(len(p) for p, _ in reqs) == sorted(len(p) for p, _ in base)
    assert sorted(o for _, o in reqs) == sorted(o for _, o in base)
    assert all(64 <= len(p) <= 1024 and 256 <= o <= 1024 for p, o in reqs)
    assert all(p.dtype == np.int32 and p.max() < 32000 for p, _ in reqs)


def test_every_group_holds_the_same_lengths_every_seed():
    a = traffic.backlog(MIX, 3, 32000, group=64)
    b = traffic.backlog(MIX, 2**35 + 1, 32000, group=64)
    for g in range(2):
        ga, gb = a[g * 64:(g + 1) * 64], b[g * 64:(g + 1) * 64]
        assert sorted(len(p) for p, _ in ga) == sorted(len(p) for p, _ in gb)
        assert sorted(o for _, o in ga) == sorted(o for _, o in gb)


def test_seed_changes_order_and_tokens():
    a = traffic.backlog(MIX, 3, 32000, group=64)
    b = traffic.backlog(MIX, 4, 32000, group=64)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    c = traffic.backlog(MIX, 3, 32000, group=64)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, c))


def test_each_group_spans_the_distribution():
    q = traffic.quantile_lengths(MIX["output"], 128)
    order = traffic.grouped_order(128, 64, np.random.default_rng(5))
    assert sorted(order) == list(range(128))
    for k, g in enumerate((1, 0)):
        block = order[k * 64:(k + 1) * 64]
        # one fixed quantile of each stratum of two neighbours
        assert sorted(block) == list(range(g, 128, 2))
    assert np.median(q) == pytest.approx(512, rel=0.02)
