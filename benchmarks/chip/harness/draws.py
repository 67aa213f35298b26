"""Seeded draws for traffic: Zipf popularity, lognormal lengths, Poisson
gaps, and the serve schedule one general generator builds from a traffic
file.

Every seed gets the same multiset of sizes and gaps: each is a set of
quantiles of its distribution, put into one fixed order (drawn from the
traffic file's own ``layout_seed``) and then permuted by ``--seed`` inside
blocks of ``block`` turns. So a seed changes the order of the work, not
its amount, and every block of turns offers the same load in every run.
The Zipf arithmetic follows ``benchmarks/bench_serve.py``'s churn
generator (``p_r ~ 1 / r**s``).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any non-negative int) and a stream id."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def lognormal_quantiles(n: int, median: float, sigma: float) -> np.ndarray:
    """The n mid-quantiles of a lognormal with this median and log-sigma."""
    nd = NormalDist()
    return np.array([median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
                     for i in range(n)])


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    """The n mid-quantiles of Exp(rate): a Poisson process's gaps."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def to_buckets(values: np.ndarray, buckets: Sequence[int]) -> np.ndarray:
    """Each value rounded to the nearest bucket on a log scale."""
    lb = np.log(np.asarray(buckets, np.float64))
    idx = np.abs(np.log(np.maximum(values, 1e-9))[:, None] - lb[None]
                 ).argmin(axis=1)
    return np.asarray(buckets)[idx]


def block_permute(n: int, block: int, g: np.random.Generator) -> np.ndarray:
    """A permutation of range(n) that moves items only inside consecutive
    blocks of ``block``."""
    out = np.arange(n)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        out[lo:hi] = lo + g.permutation(hi - lo)
    return out


@dataclasses.dataclass
class Turn:
    arrival: float          # seconds after the window opens
    new: bool               # opens a session (else resumes one)
    prompt_len: int         # 0 for a resumed turn
    n_out: int              # tokens to serve
    pick: float             # uniform draw for the Zipf choice of session


@dataclasses.dataclass
class ServeSchedule:
    pool_prompts: List[int]     # prompt lengths of the sessions opened in set-up
    turns: List[Turn]           # in arrival order


def serve_schedule(tr: Dict, seed: int, seconds: float,
                   rate: Optional[float] = None) -> ServeSchedule:
    """The turns of one run of a serve traffic file, from ``seed``."""
    rate = float(rate if rate is not None else tr["rate_per_s"])
    block = int(tr["block"])
    n = max(block, int(math.ceil(rate * seconds / block)) * block)
    fixed = rng(int(tr["layout_seed"]))
    own = rng(seed, 1)

    def arrange(values: np.ndarray) -> np.ndarray:
        values = values[fixed.permutation(len(values))]
        return values[block_permute(len(values), block, own)]

    gaps = arrange(exponential_quantiles(n, rate))
    out = np.clip(np.rint(arrange(lognormal_quantiles(
        n, tr["out_median"], tr["out_sigma"]))),
        tr["out_min"], tr["out_max"]).astype(int)
    # one new session in every ``block`` turns, at a seeded place
    new = np.zeros(n, bool)
    per_block = int(round(block * tr["new_share"]))
    for lo in range(0, n, block):
        new[lo + own.permutation(block)[:per_block]] = True
    n_new = int(new.sum())
    n_pool = int(tr["sessions"])
    prompts = arrange(to_buckets(lognormal_quantiles(
        n_new + n_pool, tr["prompt_median"], tr["prompt_sigma"]),
        tr["prompt_buckets"]))
    pool, fresh = prompts[:n_pool], list(prompts[n_pool:])
    picks = own.random(n)
    arrivals = np.cumsum(gaps)
    turns = [Turn(float(arrivals[i]), bool(new[i]),
                  int(fresh.pop()) if new[i] else 0, int(out[i]),
                  float(picks[i])) for i in range(n)]
    return ServeSchedule([int(p) for p in pool], turns)


def zipf_tokens(g: np.random.Generator, shape, vocab: int, s: float,
                perm: np.ndarray) -> np.ndarray:
    """Token ids with Zipf(s) rank frequencies, ranks mapped through a
    seeded permutation of the vocabulary."""
    ranks = g.zipf(s, size=shape) - 1
    return perm[ranks % vocab].astype(np.int32)
