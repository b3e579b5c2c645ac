"""Making runs agree without making the traffic unreal: a schedule holds a
fixed count, sizes that are the stated distribution's own quantiles, and a
fixed number of arrivals per short block of time, so that no run is offered
more or less than another in any few seconds. Pure Python + numpy."""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

BLOCK = 8  # arrivals per block of time
SCHEDULE_SEED = 0  # the one realisation every run of a serving cell is offered (PERF.md section 4 says why)


def quantile_points(n: int) -> List[float]:
    """The ``n`` evenly spaced quantiles (i + 0.5) / n."""
    return [(i + 0.5) / n for i in range(n)]


def stratified_sizes(dist: Dict[str, Any], n: int) -> List[int]:
    """The inverse CDF of ``dist`` at the ``n`` evenly spaced quantiles,
    clipped to [lo, hi] and rounded: the same multiset whatever the seed.

    ``{"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}`` or
    ``{"dist": "uniform", "lo": a, "hi": b}`` (``"round": false`` keeps
    floats, for durations)."""
    qs = quantile_points(n)
    if dist["dist"] == "lognormal":
        normal = NormalDist()
        xs = [dist["median"] * math.exp(dist["sigma"] * normal.inv_cdf(q)) for q in qs]
    elif dist["dist"] == "uniform":
        xs = [dist["lo"] + (dist["hi"] - dist["lo"]) * q for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    xs = [min(max(x, dist["lo"]), dist["hi"]) for x in xs]
    return [int(round(x)) for x in xs] if dist.get("round", True) else xs


def shuffled(rng: np.random.Generator, values: List) -> List:
    return [values[i] for i in rng.permutation(len(values))]


def stratified_arrivals(rng: np.random.Generator, n: int, rate: float, start: float) -> List[float]:
    """``n`` arrival times from ``start`` on: time is cut into blocks of
    ``BLOCK / rate`` seconds and each block gets exactly ``BLOCK`` arrivals,
    uniform within it (the last block holds the remainder over a span in
    proportion). Bursts survive; drifts of the offered load do not."""
    times: List[float] = []
    block_s = BLOCK / rate
    done = 0
    while done < n:
        k = min(BLOCK, n - done)
        span = block_s * k / BLOCK
        t0 = start + done / rate
        times.extend(sorted(float(t0 + span * u) for u in rng.random(k)))
        done += k
    return times
