"""General readers: a per-layer metric file names one of these and gives
its parameters. A reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from chipbench import stats, trace
from chipbench.cell import Span


class Facts:
    """What one traced run left to read from."""

    def __init__(self, measured, reduced: Optional[dict], cell, peaks: dict):
        self.measured = measured      # cell.Measured
        self.trace = reduced          # trace.reduce(...)
        self.cell = cell              # spec.Cell
        self.peaks = peaks            # this device's row of peaks.json

    def spans(self, name: str) -> List[Span]:
        return self.measured.spans.get(name, [])

    def cycles(self, annotation: str):
        """``trace.cycles`` of the traced run: (n of each whole cycle,
        device-busy seconds over them), or ``None``."""
        return trace.cycles(self.trace, annotation) if self.trace else None


def span_percentile_ms(metric: dict, facts: Facts) -> Optional[float]:
    spans = facts.spans(metric["span"])
    if not spans:
        return None
    return stats.percentile_exact(
        [(s.end_s - s.start_s) * 1e3 for s in spans], metric["q"])


def span_mean_ms(metric: dict, facts: Facts) -> Optional[float]:
    spans = facts.spans(metric["span"])
    if not spans:
        return None
    return float(np.mean([(s.end_s - s.start_s) * 1e3 for s in spans]))


def span_mean_n(metric: dict, facts: Facts) -> Optional[float]:
    spans = facts.spans(metric["span"])
    if not spans:
        return None
    return float(np.mean([s.n for s in spans]))


def sample_percentile(metric: dict, facts: Facts) -> Optional[float]:
    a = facts.measured.samples.get(metric["sample"])
    if a is None or len(a) == 0:
        return None
    return stats.percentile_exact(a, metric["q"])


def sample_share_within(metric: dict, facts: Facts) -> Optional[float]:
    """Share of ALL attempted requests whose sample is inside the limit;
    a failed request reads twice the timeout and is outside."""
    a = facts.measured.samples.get(metric["sample"])
    if a is None or len(a) == 0:
        return None
    return 100.0 * float(np.mean(np.asarray(a) <= metric["limit"]))


def device_ms_per_span(metric: dict, facts: Facts) -> Optional[float]:
    """Device-busy time per call of the annotation ``span``, over the
    whole cycles of it that the trace holds, on the trace's clock."""
    found = facts.cycles(metric["span"])
    if found is None:
        return None
    ns, busy_s = found
    return busy_s * 1e3 / len(ns)


READERS = {
    "span_percentile_ms": span_percentile_ms,
    "span_mean_ms": span_mean_ms,
    "span_mean_n": span_mean_n,
    "sample_percentile": sample_percentile,
    "sample_share_within": sample_share_within,
    "device_ms_per_span": device_ms_per_span,
}
