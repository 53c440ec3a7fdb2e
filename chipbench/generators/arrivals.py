"""Open-loop arrival schedules and key popularity, from a seed.

The arithmetic is copied from ``sentinel_tpu/frontend/workloads.py``
(Poisson inter-arrivals, exact inverse-CDF Zipf) so that a later change to
the program cannot move the yardstick. One general generator reads a
traffic file's parameters; a new mix is a new data file.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

#: seeds are any whole number up to a little over 2**31; numpy takes them
#: as they are, but they are mixed with a stream tag so that two streams
#: of one run never share draws
_ARRIVALS, _KEYS, _PERM = 1, 2, 3


class Schedule(NamedTuple):
    due_s: np.ndarray      # float64[n], seconds relative to the window start
    rank: np.ndarray       # int64[n], popularity rank, 0 = hottest


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def poisson_arrivals(rng, rate_per_s: float, start_s: float,
                     end_s: float) -> np.ndarray:
    """Arrival instants of a constant-rate Poisson process on
    ``[start_s, end_s)``: cumulative exponential gaps."""
    span = end_s - start_s
    if rate_per_s <= 0 or span <= 0:
        return np.zeros(0, np.float64)
    n_guess = int(rate_per_s * span * 1.2) + 64
    out = []
    t = start_s
    while True:
        gaps = rng.exponential(1.0 / rate_per_s, n_guess)
        ts = t + np.cumsum(gaps)
        out.append(ts[ts < end_s])
        if ts[-1] >= end_s:
            break
        t = ts[-1]
    return np.concatenate(out)


def zipf_ranks(rng, n: int, s: float, universe: int) -> np.ndarray:
    """``n`` Zipf(s) ranks in ``[0, universe)``: exact inverse CDF over the
    whole materialized weight vector (8 bytes a key; 1M keys = 8 MB)."""
    weights = 1.0 / np.power(np.arange(1, universe + 1, dtype=np.float64), s)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      universe - 1).astype(np.int64)


def rank_permutation(seed: int, universe: int) -> np.ndarray:
    """Popularity rank -> key id, from the seed (the deployment and the
    generator both call this, so rules sit on the hot keys)."""
    return rng_for(seed, _PERM).permutation(universe).astype(np.int64)


def poisson_zipf(params: dict, seed: int, seconds: float,
                 universe: int) -> Schedule:
    """Constant-rate Poisson arrivals from ``-warm_seconds`` to
    ``seconds`` (negative times are the warm phase, counted as set-up),
    keys Zipf(``zipf_s``) over ``universe`` ranks."""
    due = poisson_arrivals(rng_for(seed, _ARRIVALS),
                           float(params["rate_per_s"]),
                           -float(params.get("warm_seconds", 0.0)),
                           float(seconds))
    rank = zipf_ranks(rng_for(seed, _KEYS), due.size,
                      float(params["zipf_s"]), universe)
    return Schedule(due_s=due, rank=rank)


def zipf_stream(params: dict, seed: int, seconds: float,
                universe: int) -> Schedule:
    """``events`` keys for a closed loop, which takes them as fast as it
    can: Zipf(``zipf_s``) ranks and no arrival times (all 0)."""
    n = int(params["events"])
    return Schedule(due_s=np.zeros(n),
                    rank=zipf_ranks(rng_for(seed, _KEYS), n,
                                    float(params["zipf_s"]), universe))


GENERATORS = {"poisson_zipf": poisson_zipf, "zipf_stream": zipf_stream}
