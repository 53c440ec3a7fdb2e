"""The benchmark's arithmetic on samples: exact percentiles, due-time
latency, spreads. No estimate from buckets or chunks anywhere."""

from __future__ import annotations

import math
import statistics
from typing import Sequence

import numpy as np


def percentile_exact(samples, q: float) -> float:
    """Nearest-rank percentile of ALL samples: the smallest sample with at
    least ``q`` percent of the samples at or below it."""
    a = np.sort(np.asarray(samples, np.float64))
    if a.size == 0:
        raise ValueError("no samples")
    k = max(1, math.ceil(q / 100.0 * a.size))
    return float(a[k - 1])


def due_latency_ms(due_s, recv_s, timeout_ms: float) -> np.ndarray:
    """Client-side latency from the instant each request was DUE to the
    instant its answer was in hand. A request with no answer (NaN), or an
    answer after the client's timeout, reads twice the timeout: it sits
    in every tail and misses every limit, and the line stays JSON."""
    lat = (np.asarray(recv_s, np.float64) - np.asarray(due_s, np.float64)) * 1e3
    return np.where(np.isnan(lat) | (lat > timeout_ms), 2.0 * timeout_ms, lat)


def failed(latency_ms, timeout_ms: float) -> int:
    return int((np.asarray(latency_ms) > timeout_ms).sum())


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``) — the driver's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
