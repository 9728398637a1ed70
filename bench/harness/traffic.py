"""Traffic from a mix file and a seed.

One generator reads every mix (``bench/traffic/<mix>.json``):

* ``"loop": "closed"`` — ``clients`` callers, each sending its next request
  the moment the previous one finishes.
* ``"loop": "open"`` — independent users arriving as a Poisson process at
  ``rate`` requests per second, whatever the server does.

Every request is greedy.  Lengths are ``uniform`` over ``[lo, hi]`` or ``lognormal`` (``median``,
``sigma``) clipped to ``[lo, hi]``.  Sizes and gaps between arrivals are
stratified: the values are the distribution's quantiles at
``(k + 0.5) / n``, in one order that the mix's ``order_seed`` draws.
Every run of a cell thus offers the same work in the same order; the run's
seed draws the prompts' tokens (and the weights).  A seed-drawn order made
the closed loop's window hold one or two prefills more or fewer, which
moved its tokens per second by 10% from seed to seed.

The Poisson arrivals follow ``repro.serving.arrivals.poisson_times``
(exponential gaps at ``rate``), drawn by quantile instead of at random.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request the traffic will send."""

    prompt_len: int
    max_new: int
    due: float = 0.0  # open loop: seconds after the traffic starts


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of a run; any whole-number seed."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The distribution's values at the n stratified points (k + 0.5) / n,
    in increasing order, as whole numbers within [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length bounds must satisfy 1 <= lo <= hi: {dist}")
    if dist["dist"] == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n gaps of a Poisson process at ``rate``/s, at stratified quantiles."""
    if rate <= 0.0:
        raise ValueError(f"rate must be > 0, got {rate}")
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def closed_loop(mix: dict) -> List[List[Item]]:
    """Each client's requests in the order it sends them."""
    c, k = int(mix["clients"]), int(mix["requests_per_client"])
    rng = rng_for(mix["order_seed"], 1)
    prompts = rng.permutation(quantiles(mix["prompt_len"], c * k))
    outs = rng.permutation(quantiles(mix["output_len"], c * k))
    lanes = []
    for i in range(c):
        lane = [Item(int(prompts[i * k + j]), int(outs[i * k + j]))
                for j in range(k)]
        # stagger: client i's first request is (i + 1) / c of the way from
        # its end, so finishes spread evenly over one request's life
        first = lane[0]
        lane[0] = dataclasses.replace(
            first, max_new=max(1, math.ceil(first.max_new * (i + 1) / c)))
        lanes.append(lane)
    return lanes


def open_loop(mix: dict, seconds: float, rate: float = None) -> List[Item]:
    """Arrivals covering the pre-roll and the window with room to spare."""
    rate = float(mix["rate"] if rate is None else rate)
    span = float(mix["pre_roll_s"]) + float(seconds)
    n = int(math.ceil(rate * span * 1.25)) + 8
    rng = rng_for(mix["order_seed"], 2)
    due = np.cumsum(rng.permutation(exponential_gaps(rate, n)))
    prompts = rng.permutation(quantiles(mix["prompt_len"], n))
    outs = rng.permutation(quantiles(mix["output_len"], n))
    return [Item(int(prompts[i]), int(outs[i]), due=float(due[i])) for i in range(n)]


def prompt_tokens(seed: int, stream: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``index`` of one stream of a run: token ids
    from the seed."""
    return rng_for(seed, stream, index).integers(0, vocab, size=length).astype(np.int32)
