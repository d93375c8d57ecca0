"""The one traffic generator: a mix's parameters in, a stream schedule out.

A mix is a JSON file under ``traffic/``.  Two kinds:

* ``live``: an open loop.  Streams arrive with exponential gaps at
  ``rate_per_s``; each stream's frames arrive in real time, one every
  ``frame_ms``.  Streams that would have arrived before the clock starts
  (up to the longest length back) enter in progress, with the frames still
  to come: the first tick then sees the rate's steady concurrency.
* ``backlog``: recorded audio waiting to be transcribed.  No arrival times;
  the loop keeps the queue ``queue_slots`` slot grids deep.  The first
  grid's worth of streams enter in progress, at a residual length, so
  completions do not come in one wave.

Every seed gets the same multiset of lengths and gaps (quantiles of the
stated distributions), shuffled by the seed, so seeds change the order and
the frames and not the amount of work.  Frames are views into one pool of
standard-normal features made on the host at set-up.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

POOL_FRAMES = 1 << 16


@dataclasses.dataclass
class Schedule:
    """Streams in submission order.  ``arrival_s`` is when the stream's
    first frame starts to arrive, relative to the loop's clock origin (NaN
    for a backlog); stream ``i`` is ``pool[offset[i]:offset[i] + length[i]]``."""
    pool: np.ndarray
    offset: np.ndarray
    length: np.ndarray
    arrival_s: np.ndarray

    def __len__(self) -> int:
        return len(self.length)

    def frames(self, i: int) -> np.ndarray:
        return self.pool[self.offset[i]:self.offset[i] + self.length[i]]


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths_s(dist: dict, n: int) -> np.ndarray:
    """``n`` stream lengths in seconds: quantiles of a lognormal given by
    ``mean`` or ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    sigma = dist['sigma']
    mu = (math.log(dist['median']) if 'median' in dist
          else math.log(dist['mean']) - sigma * sigma / 2)
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    return np.clip(np.exp(mu + sigma * z), dist['min'], dist['max'])


def _pool(rng: np.random.Generator, n_features: int, longest: int):
    return rng.standard_normal((POOL_FRAMES + longest, n_features),
                               dtype=np.float32)


def live(mix: dict, *, seed: int, n_features: int, horizon_s: float,
         frame_s: float) -> Schedule:
    """Streams arriving in [-longest, horizon_s); those arriving before 0
    are cut to the frames that arrive from 0 on."""
    rate = mix['rate_per_s']
    back = mix['length_s']['max']
    n = int(math.ceil(rate * (back + horizon_s) * 1.05)) + 16
    gaps = -np.log1p(-_quantiles(n)) / rate
    lens = np.round(lengths_s(mix['length_s'], n) / frame_s).astype(np.int64)
    rng = np.random.default_rng([seed, 1])
    arrival = -back + np.cumsum(rng.permutation(gaps))
    lens = rng.permutation(lens)
    keep = arrival < horizon_s
    arrival, lens = arrival[keep], lens[keep]
    # in progress at 0: drop the frames that arrived before the clock
    gone = np.where(arrival < 0, np.ceil(-arrival / frame_s - 1e-9), 0)
    gone = gone.astype(np.int64)
    alive = gone < lens
    arrival = np.where(gone > 0, arrival + gone * frame_s, arrival)[alive]
    lens = (lens - gone)[alive]
    order = np.argsort(arrival, kind='stable')
    arrival, lens = arrival[order], lens[order]
    pool = _pool(rng, n_features, int(lens.max()))
    offset = rng.integers(0, POOL_FRAMES, size=len(lens))
    return Schedule(pool, offset, lens, arrival)


def backlog(mix: dict, *, seed: int, n_features: int, slots: int,
            n_streams: int, frame_s: float) -> Schedule:
    """``n_streams`` streams; the first ``slots`` enter at a residual
    length (a length drawn in proportion to itself, cut at a uniform
    point), as a grid that has been busy for a while holds them."""
    rng = np.random.default_rng([seed, 2])
    base = lengths_s(mix['length_s'], slots) / frame_s
    reps = int(math.ceil(n_streams / slots))
    lens = np.concatenate([rng.permutation(base) for _ in range(reps)])
    lens = lens[:n_streams]
    ranked = np.sort(base)
    weight = np.cumsum(ranked) / ranked.sum()
    biased = ranked[np.searchsorted(weight, _quantiles(slots))]
    lens[:slots] = np.maximum(1, rng.permutation(biased)
                              * rng.permutation(_quantiles(slots)))
    lens = np.round(lens).astype(np.int64)
    pool = _pool(rng, n_features, int(lens.max()))
    offset = rng.integers(0, POOL_FRAMES, size=n_streams)
    return Schedule(pool, offset, lens, np.full(n_streams, np.nan))
