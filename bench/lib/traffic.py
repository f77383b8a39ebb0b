"""One general generator for every traffic mix (``bench/traffic/*.json``).

A mix is data: a ``closed`` loop of ``clients``, the answer-length
distribution, and the serving shape (slots, ``ctx_len``, ``top_k``,
corpus layout).  Each client sends its next job the moment its previous
one is harvested, so jobs leave the schedule in order.  The schedule is
cut into blocks of ``clients`` jobs, and every block holds the same
stratified quantiles of the answer-length distribution in a
seed-dependent order: any run of consecutive jobs, and so any window,
asks for nearly the same number of tokens on every seed.  The seed also
draws which pooled query each job asks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass(frozen=True)
class Job:
    """One request of the schedule: which pooled query, and how many
    tokens the answer gets."""
    query: int
    max_new_tokens: int


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of the answer-length distribution."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(vals), lo, hi)
    if spec["dist"] == "uniform":
        return lo + np.floor(u * (hi - lo + 1))
    raise ValueError(f"unknown answer distribution {spec['dist']!r}")


def schedule(mix: Dict[str, Any], seed: int, stream: int, n: int,
             pool: int) -> List[Job]:
    """``n`` jobs of ``mix`` for ``seed``; ``stream`` separates the
    warm-up's jobs from the window's."""
    if mix["arrival"] != "closed":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rng = np.random.default_rng([seed, stream])
    block = quantiles(mix["answer"], int(mix["clients"]))
    lengths = np.concatenate([rng.permutation(block)
                              for _ in range(-(-n // len(block)))])[:n]
    queries = rng.integers(0, pool, size=n)
    return [Job(int(q), int(m)) for q, m in zip(queries, lengths)]
