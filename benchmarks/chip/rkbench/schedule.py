"""Open-loop arrivals, query positions and percentiles, from the seed.

The Poisson arrivals follow the program's ``benchmarks/bench_load.py::
make_schedule`` and the percentile is ``benchmarks/bench_serving.py::
_pct`` (nearest rank), copied so that later program changes cannot move
the yardstick. One change: a run's arrival count is fixed by the mix
(rate x seconds), and a Poisson process is drawn conditioned on it
(arrival times are sorted uniform draws), so every seed offers the same
amount of work and only its order and spacing change.
"""

from __future__ import annotations

import numpy as np


def arrivals(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Intended arrival offsets in seconds, ascending, inside [0, seconds):
    ``round(rate * seconds)`` arrivals of a Poisson process."""
    kind = mix.get("arrivals", "poisson")
    if kind != "poisson":
        raise ValueError(f"arrivals must be poisson, got {kind!r}")
    rng = np.random.default_rng([seed, 1])
    n = max(1, round(float(mix["rate"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def positions(pool_size: int, n: int, seed: int) -> np.ndarray:
    """``n`` positions in a pool of ``pool_size`` ordered entries: evenly
    spaced with one seeded offset, so every seed covers the pool alike,
    then put in a seeded random order."""
    rng = np.random.default_rng([seed, 2])
    pos = np.floor((np.arange(n) + rng.uniform()) * pool_size / n)
    return rng.permutation(pos.astype(np.int64) % pool_size)


def pct(values, q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 1]."""
    s = sorted(values)
    return s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]
