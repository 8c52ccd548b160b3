"""The one general generator of a serving cell's requests.  A traffic mix is
the ``traffic_mix`` block of a data file (``benchmark/workloads/<cell>.json``)::

    "traffic_mix": {
      "fill": {"requests": 64, "seconds": 2.0},
      "arrivals": {"kind": "levelled_poisson", "rate_per_s": 20.0},
      "prompt_tokens": {"median": 510, "sigma": 0.0, "min": 510, "max": 510},
      "output_tokens": {"median": 65, "sigma": 0.0, "min": 65, "max": 65}
    }

``fill`` (optional): ``requests`` requests that the entry submits one
before each engine step, ahead of everything else, so that a server's lanes
fill one a step; the arrivals then start ``seconds`` before the window
opens.  Their due times here are ``-inf``.

The arrivals are open loop: every request is due at a fixed time whether or
not earlier ones have finished, over ``[-fill seconds, window seconds)``,
``n = round(rate x span)`` of them.  ``levelled_poisson``: a levelled
Poisson process, not a draw from one: the ``n - 1`` gaps are the
exponential distribution's quantiles at ``(i + 1/2) / (n - 1)``, scaled so
that the last request is due inside the span (the mean gap stays
``1 / rate``), in an order the seed shuffles.  The lengths are the clipped
lognormal's quantiles at ``(i + 1/2) / n`` in an order the seed shuffles;
``sigma`` 0 gives every request the median.  The seed also draws the token
ids (uniform over the vocabulary).  So every seed offers the same set of
gaps and sizes, the same work, in another order: seeds differ by how the
work falls together, not by how much of it there is.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import NamedTuple

import numpy as np


class Schedule(NamedTuple):
    due_s: np.ndarray           # [n] float64, seconds after the window opens
    prompts: list               # n int32 arrays
    output_tokens: np.ndarray   # [n] int32, tokens each request asks for


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal of median
    ``median`` and log-spread ``sigma``, clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int32)


def arrival_gaps(rate: float, seconds: float, n: int) -> np.ndarray:
    """The ``n - 1`` gaps between ``n`` arrivals (the first due at 0), at
    the exponential's quantiles, scaled so that the last is due at
    ``seconds x (n - 1) / n``, inside the span."""
    if n < 2:
        return np.zeros((0,))
    gaps = -np.log1p(-_quantiles(n - 1)) / rate
    return gaps * (seconds * (n - 1) / n) / gaps.sum()


def arrival_times(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due times over ``[0, seconds)`` of the ``arrivals``."""
    if spec["kind"] != "levelled_poisson":
        raise ValueError(f"unknown arrivals kind {spec['kind']!r}")
    n = max(1, int(round(spec["rate_per_s"] * seconds)))
    gaps = rng.permutation(arrival_gaps(spec["rate_per_s"], seconds, n))
    return np.concatenate([[0.0], np.cumsum(gaps)])


def make_schedule(mix: dict, vocab: int, seed: int,
                  seconds: float) -> Schedule:
    """The fill and the requests due up to the close of a window of
    ``seconds``, from ``seed``; due times in seconds after the window
    opens."""
    rng = np.random.default_rng(seed)
    fill = mix.get("fill") or {"requests": 0, "seconds": 0.0}
    lead = float(fill["seconds"])
    due = np.concatenate([
        np.full((fill["requests"],), -np.inf),
        arrival_times(mix["arrivals"], lead + seconds, rng) - lead])
    n = len(due)
    prompt_lens = rng.permutation(lognormal_lengths(mix["prompt_tokens"], n))
    outputs = rng.permutation(lognormal_lengths(mix["output_tokens"], n))
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32)
               for p in prompt_lens]
    return Schedule(due, prompts, outputs)
