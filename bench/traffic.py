"""One generator for every traffic mix (``bench/traffic/<mix>.json``).

The seed orders the work; it does not choose it.  Every seed gets the
same multiset of prompt lengths, output lengths and inter-arrival gaps
(stratified quantiles of the mix's distributions), shuffled by the seed,
and its own token ids.  So runs with different seeds do the same work in
another order, and their spread is the system's, not the sampler's.

Prompt lengths are rounded to the mix's fixed ``lengths`` (nearest in log
space), each of which set-up warms; output lengths are not rounded.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    idx: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a lognormal (median, sigma), clipped."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def round_to(lengths: np.ndarray, allowed) -> np.ndarray:
    """Each length to the nearest allowed one, in log space."""
    allowed = np.asarray(sorted(allowed), np.int64)
    d = np.abs(np.log(lengths[:, None]) - np.log(allowed[None, :]))
    return allowed[np.argmin(d, axis=1)]


def exp_gaps(rate: float, n: int, span: float) -> np.ndarray:
    """``n`` stratified exponential gaps of mean 1/rate, scaled so that
    they sum to ``span`` exactly."""
    g = -np.log1p(-_quantiles(n)) / rate
    return g * (span / g.sum())


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_rps"] * seconds)))


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """The cell's requests for one run, open loop: due times spread over
    ``[0, seconds)``, the first at 0."""
    n = n_requests(traffic, seconds)
    prompts = round_to(lognormal_lengths(traffic["prompt"], n),
                       traffic["prompt"]["lengths"])
    outputs = lognormal_lengths(traffic["output"], n)
    prompts = prompts[_rng(seed, 1).permutation(n)]
    outputs = outputs[_rng(seed, 2).permutation(n)]
    gaps = exp_gaps(traffic["rate_rps"], n, seconds)
    gaps = gaps[_rng(seed, 3).permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tok = _rng(seed, 4)
    return [Req(i, float(due[i]),
                tok.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                int(outputs[i])) for i in range(n)]


def warm_lengths(traffic: dict) -> list[int]:
    """Every prompt length the mix can send: what set-up warms."""
    return sorted(int(x) for x in traffic["prompt"]["lengths"])


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation), ``nan`` when empty."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
