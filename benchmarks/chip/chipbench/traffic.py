"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a run out.

Every seed gets the same prompt lengths, output lengths and
inter-arrival gaps in the same order, so the work offered in a window
does not change with the seed; the seed draws the token ids (and the
benchmark's weights).  A closed loop's window holds a stretch of the
schedule, not all of it, so an order drawn from the seed would change
the work in the window.  The lengths are stratified draws from the
stated distribution: request ``i`` of ``n`` takes the quantile
``(i + 0.5) / n``, clipped to ``[min, max]``, in an order drawn once from
``ORDER_SEED``.  Open-loop gaps are the same stratified draws of an
exponential distribution at ``rate_per_s``.  Prompt token ids are
uniform over ``[1, vocab)``, drawn from the seed, so prompts share no
prefix.

A traffic file::

    {"loop": "closed", "clients": 16,          # or "open", "rate_per_s"
     "requests": 128,                          # closed loop: how many
     "prompt_tokens": {"dist": "uniform", "min": 4096, "max": 12288},
     "output_tokens": {"dist": "lognormal", "median": 128,
                       "sigma": 0.8, "min": 16, "max": 512},
     "warmup_s": 15, "drain_s": 60,
     "engine": {...}, "server": {...}, "check": {...}}
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

DISTS = ("uniform", "lognormal", "fixed")
ORDER_SEED = 0       # the schedule's order, the same for every run seed


@dataclass(frozen=True)
class Request:
    prompt: List[int]
    max_new_tokens: int
    at: float            # due time (s) from the schedule's start; 0 in
                         # a closed loop, where a client sends on return


def quantile(dist: dict, u: float) -> int:
    """The ``u`` quantile of a length distribution, rounded and clipped
    to ``[min, max]``."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"]
                                      * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}; "
                         f"known: {DISTS}")
    return int(min(max(round(x), lo), hi))


def max_length(dist: dict) -> int:
    return int(dist["value"] if dist["dist"] == "fixed" else dist["max"])


def min_length(dist: dict) -> int:
    return int(dist["value"] if dist["dist"] == "fixed" else dist["min"])


def stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(traffic: dict, n: int, rng) -> tuple:
    us = stratified(n)
    prompts = [quantile(traffic["prompt_tokens"], u) for u in us]
    outputs = [quantile(traffic["output_tokens"], u) for u in us]
    # prompt and output lengths are permuted independently: the pairing
    # is the schedule's, the marginals are fixed
    return ([prompts[i] for i in rng.permutation(n)],
            [outputs[i] for i in rng.permutation(n)])


def _arrivals(n: int, rate: float, start: float, rng) -> np.ndarray:
    """``n`` arrivals from ``start`` with stratified exponential gaps at
    ``rate`` in the schedule's order; the first is due at ``start``."""
    gaps = -np.log1p(-stratified(n)) / rate
    return start + np.concatenate([[0.0], np.cumsum(
        gaps[rng.permutation(n)])[:-1]])


def generate(traffic: dict, seed: int, vocab: int,
             seconds: float) -> List[Request]:
    """The run's requests, in sending order, for ``seed``.

    Open loop: ``rate_per_s * warmup_s`` warm-up requests due from 0,
    then ``rate_per_s * seconds`` window requests due from ``warmup_s``:
    two multisets of their own, so the window's sizes and count are the
    same for every seed.  Closed loop: ``requests`` requests (rounded up
    to waves of ``clients``) that the clients take in order."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER_SEED)
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        warm = float(traffic["warmup_s"])
        parts = [(max(1, round(rate * warm)), 0.0),
                 (max(1, round(rate * seconds)), warm)]
    elif traffic["loop"] == "closed":
        # waves of one request per client, each wave the same multiset
        # of sizes: the clients' first requests, sent at once, carry the
        # same work for every seed
        c = int(traffic["clients"])
        parts = [(c, None)] * -(-int(traffic["requests"]) // c)
    else:
        raise ValueError(f"loop must be open or closed, "
                         f"not {traffic['loop']!r}")
    out: List[Request] = []
    for n, start in parts:
        prompts, outputs = _lengths(traffic, n, order)
        at = (np.zeros(n) if start is None
              else _arrivals(n, rate, start, order))
        out += [Request(prompt=rng.integers(1, vocab, p).tolist(),
                        max_new_tokens=o, at=float(t))
                for p, o, t in zip(prompts, outputs, at)]
    return out
