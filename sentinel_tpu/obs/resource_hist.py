"""Device-resident per-resource RT histograms (round 20).

One fixed log-bucket cumulative histogram row per hot-tier resource
row, living INSIDE the engine state pytree (``SentinelState.rt_hist``,
``int32[rows, hb]``) so recording rides the fused single-dispatch
serving tick (round 16) for zero extra dispatches. Same geometry family
as the host-side interval histogram in :mod:`sentinel_tpu.obs.hist`,
but in milliseconds (the engine's RT unit) and sized for an int32
threshold table:

* bucket ``0`` covers ``[0, 1]`` ms,
* bucket ``i`` covers ``(2**(i-1), 2**i]`` ms,
* the top bucket is open above (quantile interpolation treats its upper
  edge as ``2**(hb-1)`` ms — no per-row max tracking device-side).

With the default ``hb = 32`` the table resolves ~1 ms → ~24 days, far
past any device RT the runtime can record; the clamp ceiling of 32
keeps every threshold (``2**(hb-2)``) inside int32.

Cumulative-forever semantics: counts only grow (they survive window
geometry changes and the demote→promote tiering round trip) and reset
only on row invalidation. That makes the vectors mergeable by plain
addition — across shards (device-side gather in obs/telemetry.py) and
across hosts (psum/allgather in multihost/obs_agg.py) — and lets the
controller recover *interval* tails from deltas between successive
snapshots (:class:`ResourceTailTracker`).

Env knobs (registered in tune/knobs.py; both trace-scope — they size
the state pytree, so changing one forces a fresh engine):

* ``SENTINEL_RESOURCE_HIST_DISABLE`` — drop the table entirely:
  ``rt_hist`` stays ``None``, every consumer compiles the feature away,
  and the jitted step programs are byte-identical to pre-r20 (the gate
  (n) bit-parity leg pins this).
* ``SENTINEL_RESOURCE_HIST_BUCKETS`` — bucket count, clamped [8, 32].
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

RESOURCE_HIST_DISABLE_ENV = "SENTINEL_RESOURCE_HIST_DISABLE"
RESOURCE_HIST_BUCKETS_ENV = "SENTINEL_RESOURCE_HIST_BUCKETS"

DEFAULT_BUCKETS = 32
MIN_BUCKETS = 8
MAX_BUCKETS = 32            # thresholds up to 2**30 — int32-safe

#: The quantiles the jitted per-tick extraction produces, in order —
#: the q_k output's last axis, the hot-entry ``rt_p{50,95,99}_ms``
#: fields, and the Prometheus ``quantile`` label values.
QUANTILES: Tuple[float, ...] = (0.50, 0.95, 0.99)

_BOOL_FALSE = ("0", "off", "false", "disable", "disabled")


def _env_int(name: str, default: int, lo: int, hi: int) -> int:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return min(hi, max(lo, int(raw)))
    except ValueError:
        return default


def resource_hist_disabled(default: bool = False) -> bool:
    """``SENTINEL_RESOURCE_HIST_DISABLE`` (same boolean spellings as the
    other engine switches: anything not in the false set reads on)."""
    raw = os.environ.get(RESOURCE_HIST_DISABLE_ENV, "")
    if not raw:
        return default
    return raw.lower() not in _BOOL_FALSE


def resource_hist_buckets(default: int = DEFAULT_BUCKETS) -> int:
    """``SENTINEL_RESOURCE_HIST_BUCKETS``, clamped to [8, 32]."""
    return _env_int(RESOURCE_HIST_BUCKETS_ENV, default, 8, 32)


def engine_hist_buckets() -> int:
    """The ``EngineSpec.hist_buckets`` value for a new engine: 0 when
    the feature is disabled (state leaf absent, programs unchanged),
    else the clamped bucket count."""
    return 0 if resource_hist_disabled() else resource_hist_buckets()


# ---- geometry ---------------------------------------------------------


def bucket_thresholds_ms(hb: int) -> np.ndarray:
    """int32[hb-1] upper edges ``[1, 2, 4, ..., 2**(hb-2)]`` ms; a value
    strictly above ``thresholds[i-1]`` lands at bucket >= i."""
    return (np.int32(1) << np.arange(hb - 1, dtype=np.int32))


def bucket_edges_ms(hb: int) -> np.ndarray:
    """float32[hb+1] bucket boundaries ``[0, 1, 2, 4, ..., 2**(hb-1)]``
    (the interpolation grid; the last edge caps the open top bucket)."""
    edges = np.zeros(hb + 1, dtype=np.float32)
    edges[1:] = np.ldexp(1.0, np.arange(hb)).astype(np.float32)
    return edges


def bucket_index(rt_ms, hb: int):
    """Traced bucket index per value: ``sum(v > thresholds)`` — 0 for
    v <= 1 ms, hb-1 for anything above ``2**(hb-2)`` ms. Works on any
    leading shape; negative inputs clamp to bucket 0."""
    th = jnp.asarray(bucket_thresholds_ms(hb))
    v = jnp.asarray(rt_ms)
    return jnp.sum((v[..., None] > th).astype(jnp.int32), axis=-1)


def np_bucket_index(rt_ms, hb: int) -> np.ndarray:
    """NumPy mirror of :func:`bucket_index` (bit-exact test reference)."""
    th = bucket_thresholds_ms(hb)
    v = np.asarray(rt_ms)
    return np.sum((v[..., None] > th).astype(np.int32), axis=-1)


# ---- quantile extraction ---------------------------------------------


def quantiles_from_counts(counts, quantiles: Sequence[float] = QUANTILES):
    """Traced ``int32[..., hb] → float32[..., len(quantiles)]`` ms.

    Mirrors ``obs.hist.LogHistogram.percentile``: 1-based rank
    ``max(1, p·total)``, landing bucket = first with ``cum >= rank``,
    linear interpolation between the bucket's edges. Empty rows
    (total == 0) yield 0.0 — "no signal", distinct from any recorded
    latency only together with the count, which callers carry.
    """
    # counts accumulate in int32 — exact, and the same in any summation
    # order — and become float32 only for the interpolation: a float32
    # sum is inexact past 2**24 and its order differs between backends
    c = jnp.asarray(counts).astype(jnp.int32)
    hb = c.shape[-1]
    total = jnp.sum(c, axis=-1).astype(jnp.float32)          # [...]
    cum = jnp.cumsum(c, axis=-1)                             # [..., hb]
    cum_f = cum.astype(jnp.float32)
    edges = bucket_edges_ms(hb)
    lo = jnp.asarray(edges[:-1])
    hi = jnp.asarray(edges[1:])
    outs = []
    for p in quantiles:
        rank = jnp.maximum(1.0, np.float32(p) * total)       # [...]
        idx = jnp.sum((cum_f < rank[..., None]).astype(jnp.int32), axis=-1)
        idx = jnp.minimum(idx, hb - 1)
        cb = jnp.take_along_axis(cum, idx[..., None], axis=-1)[..., 0]
        ci = jnp.take_along_axis(c, idx[..., None], axis=-1)[..., 0]
        frac = ((rank - (cb - ci).astype(jnp.float32))
                / jnp.maximum(ci, 1).astype(jnp.float32))
        v = lo[idx] + (hi[idx] - lo[idx]) * frac
        outs.append(jnp.where(total > 0, v, 0.0))
    return jnp.stack(outs, axis=-1).astype(jnp.float32)


def np_quantiles(counts, quantiles: Sequence[float] = QUANTILES
                 ) -> np.ndarray:
    """NumPy mirror of :func:`quantiles_from_counts`, same integer
    accumulation (int64 here: equal to the device's int32 wherever that
    has not wrapped, and exact for a multihost sum past it) and float32
    arithmetic — the bit-exact reference for the merge/extract
    tests and the host-side fallback (multihost aggregation, the
    controller's interval deltas)."""
    c = np.asarray(counts).astype(np.int64)
    hb = c.shape[-1]
    total = np.sum(c, axis=-1).astype(np.float32)
    cum = np.cumsum(c, axis=-1)
    cum_f = cum.astype(np.float32)
    edges = bucket_edges_ms(hb)
    lo, hi = edges[:-1], edges[1:]
    outs = []
    for p in quantiles:
        rank = np.maximum(np.float32(1.0), np.float32(p) * total)
        idx = np.sum((cum_f < rank[..., None]).astype(np.int32), axis=-1)
        idx = np.minimum(idx, hb - 1)
        cb = np.take_along_axis(cum, idx[..., None], axis=-1)[..., 0]
        ci = np.take_along_axis(c, idx[..., None], axis=-1)[..., 0]
        frac = ((rank - (cb - ci).astype(np.float32))
                / np.maximum(ci, 1).astype(np.float32))
        v = lo[idx] + (hi[idx] - lo[idx]) * frac
        outs.append(np.where(total > 0, v, np.float32(0.0)))
    return np.stack(outs, axis=-1).astype(np.float32)


# ---- controller interval tails ---------------------------------------


class ResourceTailTracker:
    """Interval p99 per resource from cumulative-vector deltas.

    The device table is cumulative-forever; the controller wants the
    tail of the LAST interval. This keeps the previous snapshot per
    resource name and differences successive vectors — the histogram
    analog of ``control.policy.HistDeltaP99``, but per resource and in
    the ms geometry. A shrinking count (row invalidated and re-enrolled
    between ticks) resets the baseline: the full vector is treated as
    the interval. The name map is bounded: names absent from an update
    are evicted once the map exceeds ``cap`` (hot sets are small — K
    entries — so in practice eviction only fires across hot-set churn).
    """

    def __init__(self, cap: int = 256) -> None:
        self._prev: Dict[str, np.ndarray] = {}
        self._cap = int(cap)

    def update(self, entries) -> Tuple[Tuple[str, float], ...]:
        """``[(name, cumulative counts)]`` → ``((name, interval_p99_ms),
        ...)`` for every resource with interval samples."""
        out: List[Tuple[str, float]] = []
        seen = set()
        for name, counts in entries:
            c = np.asarray(counts, dtype=np.int64)
            if c.ndim != 1 or c.shape[0] < MIN_BUCKETS:
                continue
            seen.add(name)
            prev = self._prev.get(name)
            if prev is None or prev.shape != c.shape or np.any(c < prev):
                delta = c
            else:
                delta = c - prev
            self._prev[name] = c
            if int(delta.sum()) > 0:
                p99 = float(np_quantiles(delta[None, :])[0, -1])
                out.append((name, p99))
        if len(self._prev) > self._cap:
            for stale in [n for n in self._prev if n not in seen]:
                del self._prev[stale]
                if len(self._prev) <= self._cap:
                    break
        return tuple(out)
