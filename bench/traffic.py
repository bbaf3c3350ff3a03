"""The general traffic generator: requests from a mix's parameters and the
seed. Every seed gets the same multiset of lengths, in another order, so
the seed changes which tokens and which order, not how much work.

Lengths follow a lognormal (median, sigma) clipped to [min, max], taken at
stratified quantiles rather than drawn: n requests take the quantiles
(i + 1/2)/n. For a backlog served `group` at a time, the quantiles are cut
into `group` strata of n/group neighbours, and each consecutive group of
requests holds one fixed quantile of every stratum (the first group the
middle one): every group the
server admits together spans the whole distribution, and holds the same
lengths for every seed. The seed shuffles the order inside each group
(prompt and output lengths apart) and draws the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """n lengths at the stratified quantiles of a clipped lognormal."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    med, sig = float(dist["median"]), float(dist["sigma"])
    lo, hi = int(dist["min"]), int(dist["max"])
    out = [min(hi, max(lo, int(round(med * math.exp(
        sig * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]
    return np.asarray(out, np.int64)


def grouped_order(n: int, group: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """A permutation of quantile indices 0..n-1 (n a multiple of group):
    block k of `group` consecutive entries holds one fixed index of each
    stratum of n/group neighbouring quantiles, shuffled within the
    block."""
    if n % group:
        raise ValueError(f"backlog {n} is not a multiple of {group}")
    per = n // group
    # the first group holds each stratum's middle quantile
    firsts = np.roll(np.arange(per), -(per // 2))
    return np.concatenate([(np.arange(group) * per + g)[rng.permutation(group)]
                           for g in firsts]).astype(np.int64)


def backlog(traffic: Dict[str, Any], seed: int, vocab: int, group: int
            ) -> List[Tuple[np.ndarray, int]]:
    """An offline backlog: `traffic["backlog"]` requests of (prompt token
    ids, output length), every prompt drawn uniformly from the vocabulary."""
    n = int(traffic["backlog"])
    rng = np.random.default_rng(int(seed))
    p_len = quantile_lengths(traffic["prompt"], n)[grouped_order(n, group,
                                                                 rng)]
    o_len = quantile_lengths(traffic["output"], n)[grouped_order(n, group,
                                                                 rng)]
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(p_len, o_len)]
