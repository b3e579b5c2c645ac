"""The yardstick: peaks of the chips, quantiles, rates. Pure Python, no jax,
no ``ray_tpu``; copied here (``bench._PEAK_FLOPS``, ``bench.model_mfu``'s
arithmetic) so that it cannot change with the program."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

# Published peaks of one chip, keyed by a lower-case substring of jax's
# ``device_kind``. A kind that is not here is an error, never a default.
PEAKS = {
    "v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s',
    },
    "v5e": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e" (same chip, other spelling of device_kind)',
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key in sorted(PEAKS, key=len, reverse=True):
        if key in kind:
            return PEAKS[key]
    raise LookupError(
        f"no published peak on record for device_kind {device_kind!r}; add it to "
        "benchmark/yardstick.py PEAKS with its source"
    )


def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile (numpy's default, type 7), 0 <= q <= 1."""
    if not values:
        raise ValueError("quantile of no values")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values: Sequence[float]) -> float:
    """The driver's spread: distance between the first and third quartile as
    ``statistics.quantiles(values, n=4)`` gives them, over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def whole_steps_rate(steps: Sequence[Tuple[float, float]], window: Tuple[float, float],
                     units_per_step: float) -> Tuple[Optional[float], int]:
    """Units (tokens) per second over the whole steps that completed inside
    ``window``: their count times ``units_per_step`` over the time from the
    first of those steps' start to the last one's end. ``steps`` are
    (start, end) pairs in order, end taken after ``block_until_ready``.
    Never units over the window's nominal length, which quantises by one
    step. Returns (rate or None, number of steps counted)."""
    w0, w1 = window
    inside = [(s, e) for s, e in steps if s >= w0 and e <= w1]
    if not inside or inside[-1][1] <= inside[0][0]:
        return None, len(inside)
    return len(inside) * units_per_step / (inside[-1][1] - inside[0][0]), len(inside)


def gaps_ending_in(token_times: Sequence[float], window: Tuple[float, float]) -> List[float]:
    """Gaps between consecutive streamed tokens of one request that end
    inside the window."""
    w0, w1 = window
    return [b - a for a, b in zip(token_times, token_times[1:]) if w0 <= b <= w1]


def count_in(times: Sequence[float], window: Tuple[float, float]) -> int:
    """How many of ``times`` (streamed tokens' arrival stamps) lie inside the
    window, its edges included."""
    w0, w1 = window
    return sum(1 for x in times if w0 <= x <= w1)


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int, peak_flops: float) -> float:
    """Model FLOP/s utilisation in percent: needed FLOPs per token times
    tokens per second (of the whole cell) over chips times peak."""
    return 100.0 * flops_per_token * tokens_per_s / (chips * peak_flops)
