"""Arithmetic the benchmark owns: percentiles, spreads, bfloat16 rounding."""
from __future__ import annotations

import math
import statistics

import numpy as np


def to_bf16(x: np.ndarray) -> np.ndarray:
    """f32 -> nearest-even bfloat16 -> f32, in numpy."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) & \
        np.uint32(0xFFFF0000)
    return u.view(np.float32)


def percentile(values, q: float) -> float:
    """The q-quantile by nearest rank, as the sample holds it (no
    interpolation): the smallest value with a share q of the samples at or
    below it."""
    arr = np.sort(np.asarray(values, np.float64))
    if arr.size == 0:
        raise ValueError("percentile of no samples")
    return float(arr[max(0, _rank(arr.size, q) - 1)])


def _rank(n: int, q: float) -> int:
    return math.ceil(round(n * q, 9))


def supports(n: int, q: float, beyond: int = 10) -> bool:
    """A tail percentile is reported only with `beyond` samples past it:
    p99 wants 1,000 samples, p95 wants 200."""
    return n - _rank(n, q) >= beyond


def median(values) -> float:
    return float(statistics.median(values))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the rule BENCHMARK.json's bounds are set by."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
