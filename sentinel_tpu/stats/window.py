"""Sliding-window counters as dense tensors — the LeapArray analog.

Reference design (``sentinel-core/.../slots/statistic/base/LeapArray.java``):
a circular array of B time buckets of length ``win`` ms; bucket index for time
t is ``(t / win) % B``; a bucket is deprecated when ``t - windowStart > B*win``
(``isWindowDeprecated``); ``currentWindow`` lazily CAS-creates/resets buckets
on touch (``LeapArray.java:128-225``); reads skip deprecated buckets
(``values()``, ``LeapArray.java:304-369``).

TPU-native rewrite: one tensor per concern instead of one LeapArray object per
resource —

* ``counters: int32[R, B, E]``  — all resources × buckets × events,
* ``stamps:   int32[R, B]``     — the *window index* (``t // win``) written last,
* ``rt_sum:   float32[R, B]``   — response-time sum (float: the ENTRY_NODE
  aggregate row would overflow int32 at high throughput),
* ``min_rt:   int32[R, B]``     — per-bucket min RT (scatter-min).

Bucket validity is purely functional and **wraparound-safe**: bucket b of row
r is live at window index ``now_idx`` iff ``0 <= now_idx - stamp < B``, with
the subtraction done in int32 two's-complement (a written stamp always
satisfies ``stamp % B == b``, so positional equality is implied). Lazy reset
becomes a branchless masked multiply *before* the scatter-add — idempotent
under duplicate rows in one batch, which is what makes batched semantics exact
(SURVEY §7 hard-part 2): all events in a device step share one ``now``, so the
reset decision is identical for every duplicate.

Time discipline (important): window indices are computed **on the host** from
exact Python ints (``WindowSpec.index_of``) and passed to device code as int32
scalars. Epoch-milliseconds never enter device arithmetic — ``epoch_ms//500``
already exceeds int32, and JAX without x64 silently truncates int64, so doing
the division device-side is a correctness trap. Device-side comparisons only
ever use int32 *differences*, which are exact as long as true gaps are under
2^31 windows (~6.8 years at the smallest 100 ms window).

All functions are pure (state in / state out) and jit-safe with static shapes.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sentinel_tpu.stats import events as ev

INT32_MAX = jnp.iinfo(jnp.int32).max
# Stamp value meaning "never written": far enough behind any real index that
# (now - stamp) is huge-positive for the first ~6.8 years, and the wraparound
# beyond that still reads as dead for any B < 2^30. A numpy (not jnp)
# scalar: materializing a device constant at import time would
# initialize the backend, which must not happen before
# jax.distributed.initialize in multi-process runs (multihost/bootstrap).
NEVER = np.int32(-(2 ** 30))


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    """Static geometry (hashable → usable as a jit static arg).

    Reference defaults: the "second" window is sampleCount=2 × 500 ms
    (``SampleCountProperty``/``IntervalProperty``), the "minute" window is
    60 × 1000 ms (``StatisticNode.java:97-111``).
    """

    buckets: int
    win_ms: int
    track_rt: bool = True

    @property
    def interval_ms(self) -> int:
        return self.buckets * self.win_ms

    def index_of(self, now_ms: int) -> int:
        """HOST-side: exact window index of absolute time ``now_ms``.

        Result is reduced mod 2^32 into int32 range; all device comparisons
        are difference-based so the reduction is harmless.
        """
        idx = now_ms // self.win_ms
        return int((idx + 2 ** 31) % 2 ** 32 - 2 ** 31)


SECOND_SPEC = WindowSpec(buckets=2, win_ms=500)
# rt tracked so the metric-file pipeline can report per-second average RT
# (the reference's rollingCounterInMinute feeds MetricTimerListener)
MINUTE_SPEC = WindowSpec(buckets=60, win_ms=1000, track_rt=True)


class WindowState(NamedTuple):
    counters: jnp.ndarray          # int32[R, B, E]
    stamps: jnp.ndarray            # int32[R, B]
    rt_sum: jnp.ndarray            # float32[R, B] (or [R, 0] when untracked)
    min_rt: jnp.ndarray            # int32[R, B]   (or [R, 0] when untracked)


def init_window(spec: WindowSpec, rows: int, num_events: int = ev.NUM_EVENTS) -> WindowState:
    b_rt = spec.buckets if spec.track_rt else 0
    return WindowState(
        counters=jnp.zeros((rows, spec.buckets, num_events), jnp.int32),
        stamps=jnp.full((rows, spec.buckets), NEVER, jnp.int32),
        rt_sum=jnp.zeros((rows, b_rt), jnp.float32),
        min_rt=jnp.full((rows, b_rt), INT32_MAX, jnp.int32),
    )


def valid_mask(spec: WindowSpec, stamps: jnp.ndarray, now_idx: jnp.ndarray) -> jnp.ndarray:
    """Live-bucket mask, same shape as ``stamps`` (wraparound-safe diffs)."""
    delta = now_idx - stamps  # int32 two's-complement difference
    return (delta >= 0) & (delta < spec.buckets)


def window_sum_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                    event: int, now_idx: jnp.ndarray) -> jnp.ndarray:
    """Sum of ``event`` over live buckets for each row in ``rows`` → int32[N]."""
    sub = state.counters[rows, :, event]                 # [N, B]
    mask = valid_mask(spec, state.stamps[rows], now_idx)  # [N, B]
    return jnp.sum(jnp.where(mask, sub, 0), axis=1)


def window_sum_all(spec: WindowSpec, state: WindowState, event: int,
                   now_idx: jnp.ndarray) -> jnp.ndarray:
    """Sum of ``event`` over live buckets for every row → int32[R]."""
    mask = valid_mask(spec, state.stamps, now_idx)        # [R, B]
    return jnp.sum(jnp.where(mask, state.counters[:, :, event], 0), axis=1)


def rolling_totals(spec: WindowSpec, state: WindowState, now_idx: jnp.ndarray) -> jnp.ndarray:
    """All events, all rows → int32[R, E]; one pass for metric reporting."""
    mask = valid_mask(spec, state.stamps, now_idx)        # [R, B]
    return jnp.sum(jnp.where(mask[:, :, None], state.counters, 0), axis=1)


def rolling_load(spec: WindowSpec, state: WindowState,
                 now_idx: jnp.ndarray) -> jnp.ndarray:
    """Rolling pass+block total per row → int32[R] — the hot-resource
    ranking key of the telemetry tick (obs/telemetry.py): one masked
    sweep over two lanes instead of :func:`rolling_totals`' full event
    axis when only the ranking is needed."""
    mask = valid_mask(spec, state.stamps, now_idx)        # [R, B]
    sub = state.counters[:, :, ev.PASS] + state.counters[:, :, ev.BLOCK]
    return jnp.sum(jnp.where(mask, sub, 0), axis=1)


def rt_totals(spec: WindowSpec, state: WindowState, now_idx: jnp.ndarray) -> jnp.ndarray:
    """RT sum over live buckets for every row → float32[R]."""
    if not spec.track_rt:
        raise ValueError("rt untracked for this window spec")
    mask = valid_mask(spec, state.stamps, now_idx)
    return jnp.sum(jnp.where(mask, state.rt_sum, 0.0), axis=1)


def prev_window_sum_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                         event: int, now_idx: jnp.ndarray) -> jnp.ndarray:
    """Value of ``event`` in the *previous* window (index ``now_idx - 1``) per
    row → int32[N]. Reference: ``StatisticNode.previousPassQps`` /
    ``LeapArray.getPreviousWindow`` — zero if that bucket was never written or
    has been recycled since."""
    k = _bucket_of(spec, now_idx - 1)
    vals = state.counters[rows, k, event]
    live = state.stamps[rows, k] == (now_idx - 1)
    return jnp.where(live, vals, 0)


def refresh_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                 now_idx: jnp.ndarray) -> WindowState:
    """Lazy-reset the *current* bucket of each touched row.

    The branchless equivalent of ``LeapArray.currentWindow`` case 3
    (deprecated → tryLock + reset): where the stamp differs from ``now_idx``
    the bucket restarts from zero; multiply by {0,1} then stamp-set are both
    idempotent for duplicate rows in one batch.
    """
    k = _bucket_of(spec, now_idx)
    keep = (state.stamps[rows, k] == now_idx).astype(jnp.int32)   # [N]
    counters = state.counters.at[rows, k, :].multiply(keep[:, None], mode="drop")
    stamps = state.stamps.at[rows, k].set(now_idx, mode="drop")
    rt_sum, min_rt = state.rt_sum, state.min_rt
    if spec.track_rt:
        rt_sum = rt_sum.at[rows, k].multiply(keep.astype(jnp.float32), mode="drop")
        min_rt = min_rt.at[rows, k].set(
            jnp.where(keep == 1, state.min_rt[rows, k], INT32_MAX), mode="drop")
    return WindowState(counters, stamps, rt_sum, min_rt)


class Bucket(NamedTuple):
    """The current bucket of EVERY row of a window, sliced out of the ring
    (:func:`open_bucket`): one plane per tensor, ``k = now_idx % B`` fixed."""

    counters: jnp.ndarray          # int32[R, E]
    stamps: jnp.ndarray            # int32[R]
    rt_sum: Optional[jnp.ndarray]  # float32[R] (None when rt is untracked)
    min_rt: Optional[jnp.ndarray]  # int32[R]   (None when rt is untracked)


def open_bucket(spec: WindowSpec, state: WindowState, now_idx: jnp.ndarray,
                reset: bool = True) -> Bucket:
    """Slice the current bucket's plane out of the ring and lazy-reset it —
    the hot-path form of :func:`refresh_rows`, for every row at once.

    A step records into the plane (:func:`bucket_add_events` and its
    siblings) and :func:`close_bucket` writes it back with one in-place
    dynamic-update-slice, so every scatter of the record stage has a
    ``[R, E]`` operand and never the ring. That matters from 1,024 indices
    up: there the TPU compiler lowers a scatter by flattening its WHOLE
    operand to a row-major 1-D array and rebuilding the tiled array
    afterwards — four to nine passes over the 2 GB minute ring a step when
    the operand is the ring, whatever the indices touch.

    The reset equals ``LeapArray.currentWindow(now)`` applied to all rows:
    at bucket position ``k`` the only LIVE stamp is ``now_idx`` itself (any
    other stamp at that position differs by a multiple of B and reads as
    dead), so zero+restamp changes no window read. That needs
    ``buckets >= 2``: with B == 1 the previous window shares the current
    bucket position, and restamping untouched rows would erase their
    ``prev_window_sum`` (warm-up's previousPassQps) — there callers run
    :func:`refresh_rows` on the rows they touch and open with
    ``reset=False`` (STATIC), which slices and nothing else.
    """
    assert spec.buckets >= 2 or not reset, \
        "a full reset needs B >= 2 (see docstring)"
    k = _bucket_of(spec, now_idx)

    def plane(ring):
        return lax.dynamic_index_in_dim(ring, k, axis=1, keepdims=False)

    counters, stamps = plane(state.counters), plane(state.stamps)
    rt_sum = min_rt = None
    if spec.track_rt:
        rt_sum, min_rt = plane(state.rt_sum), plane(state.min_rt)
    if reset:
        keep = stamps == now_idx                                # [R]
        counters = counters * keep[:, None].astype(jnp.int32)
        stamps = jnp.full_like(stamps, now_idx)
        if spec.track_rt:
            rt_sum = rt_sum * keep.astype(jnp.float32)
            min_rt = jnp.where(keep, min_rt, INT32_MAX)
    return Bucket(counters, stamps, rt_sum, min_rt)


def close_bucket(spec: WindowSpec, state: WindowState, bucket: Bucket,
                 now_idx: jnp.ndarray) -> WindowState:
    """Write an :func:`open_bucket` plane back to its place in the ring."""
    k = _bucket_of(spec, now_idx)

    def put(ring, plane):
        return lax.dynamic_update_index_in_dim(ring, plane, k, axis=1)

    rt_sum, min_rt = state.rt_sum, state.min_rt
    if spec.track_rt:
        rt_sum = put(rt_sum, bucket.rt_sum)
        min_rt = put(min_rt, bucket.min_rt)
    return WindowState(put(state.counters, bucket.counters),
                       put(state.stamps, bucket.stamps), rt_sum, min_rt)


def bucket_add_events(bucket: Bucket, rows: jnp.ndarray, event_ids,
                      amounts: jnp.ndarray) -> Bucket:
    """Scatter-add ``amounts`` into lane ``event_ids`` of ``rows`` —
    ``event_ids`` is one event for the whole batch (a Python int) or one
    per element (int32[N], the fused multi-event record). Padding rows
    must use row id >= R (dropped by ``mode='drop'``); negative ids wrap
    in JAX and must not be used as padding."""
    return bucket._replace(counters=bucket.counters.at[rows, event_ids].add(
        amounts, mode="drop"))


def bucket_add_vecs(bucket: Bucket, rows: jnp.ndarray, payload: jnp.ndarray,
                    rt_ms: Optional[jnp.ndarray] = None,
                    rt_valid: Optional[jnp.ndarray] = None) -> Bucket:
    """Scatter-add a full event-lane vector per row: ``payload[N, E]`` lands
    on ``rows`` in one scatter pass where per-event adds would pay one pass
    each (an element contributing to several lanes, e.g. SUCCESS+EXCEPTION
    at exit, still costs one pass). ``rt_ms`` (where the bucket tracks rt)
    rides along, counted where ``rt_valid``. Padding as
    :func:`bucket_add_events`."""
    counters = bucket.counters.at[rows, :].add(payload, mode="drop")
    rt_sum, min_rt = bucket.rt_sum, bucket.min_rt
    if rt_sum is not None and rt_ms is not None:
        amt = (rt_ms if rt_valid is None
               else jnp.where(rt_valid, rt_ms, 0)).astype(jnp.float32)
        rt_sum = rt_sum.at[rows].add(amt, mode="drop")
        mn = (rt_ms if rt_valid is None
              else jnp.where(rt_valid, rt_ms, INT32_MAX))
        min_rt = min_rt.at[rows].min(mn, mode="drop")
    return bucket._replace(counters=counters, rt_sum=rt_sum, min_rt=min_rt)


def bucket_add_row(bucket: Bucket, row: int, vec: jnp.ndarray,
                   rt_add: Optional[jnp.ndarray] = None,
                   rt_min: Optional[jnp.ndarray] = None,
                   sharded: bool = False) -> Bucket:
    """Add a pre-reduced event vector to ONE row.

    The global ENTRY row receives a contribution from every inbound event;
    as a scatter that doubles the index count of each recording pass — as a
    reduction + this single-row update it is one cheap elementwise op.

    ``sharded`` (STATIC): the row axis is split over a device mesh. The
    TPU compiler turns a ONE-index update into a dynamic slice of the row
    axis, and the SPMD partitioner answers a dynamic slice of a sharded
    axis by gathering the WHOLE operand onto every device. With a second,
    out-of-range index (dropped, as padding rows are) the update stays a
    scatter, which the partitioner keeps on the shard that owns the row
    like the per-event scatters beside it."""
    counters, rt_sum, min_rt = bucket.counters, bucket.rt_sum, bucket.min_rt
    track = rt_sum is not None and rt_add is not None
    if sharded:
        rows = jnp.array([row, counters.shape[0]], jnp.int32)
        counters = counters.at[rows, :].add(
            jnp.stack([vec, jnp.zeros_like(vec)]), mode="drop")
        if track:
            rt_sum = rt_sum.at[rows].add(
                jnp.stack([rt_add.astype(jnp.float32), jnp.float32(0)]),
                mode="drop")
            if rt_min is not None:
                min_rt = min_rt.at[rows].min(
                    jnp.stack([rt_min, INT32_MAX]), mode="drop")
    else:
        counters = counters.at[row, :].add(vec)
        if track:
            rt_sum = rt_sum.at[row].add(rt_add.astype(jnp.float32))
            if rt_min is not None:
                min_rt = min_rt.at[row].min(rt_min)
    return bucket._replace(counters=counters, rt_sum=rt_sum, min_rt=min_rt)


def _bucket_of(spec: WindowSpec, now_idx: jnp.ndarray) -> jnp.ndarray:
    # Python-style mod keeps the bucket position consistent across the int32
    # wrap for power-of-two-free B too: jnp '%' already yields non-negative
    # for positive divisor with floor semantics.
    return now_idx % spec.buckets


def add_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
             event: int, amounts: jnp.ndarray, now_idx: jnp.ndarray,
             rt_ms: Optional[jnp.ndarray] = None) -> WindowState:
    """Scatter-add ``amounts`` of ``event`` into the current bucket of ``rows``.

    Caller must have run :func:`refresh_rows` for these rows at this
    ``now_idx`` first (the pipeline refreshes once per step). Padding rows must
    use row id >= R (dropped by ``mode='drop'``); negative ids wrap in JAX and
    must not be used as padding.
    """
    k = _bucket_of(spec, now_idx)
    counters = state.counters.at[rows, k, event].add(amounts, mode="drop")
    rt_sum, min_rt = state.rt_sum, state.min_rt
    if spec.track_rt and rt_ms is not None:
        rt_sum = rt_sum.at[rows, k].add(rt_ms.astype(jnp.float32), mode="drop")
        min_rt = min_rt.at[rows, k].min(rt_ms, mode="drop")
    return WindowState(counters, state.stamps, rt_sum, min_rt)


def hist_add_fits(n: int, chunk: int = 1 << 15) -> bool:
    """True when an ``n``-element :func:`bucket_add_hist` stays inside the
    f32-exactness bound EVEN AFTER chunk padding (the padding adds up to
    ``chunk - 1`` drop-class rows, so callers guarding on the raw ``n``
    alone can still trip the assert below). The one predicate both the
    dispatch guard (engine/pipeline.py fast-flow path) and the assert use."""
    return n + chunk <= (1 << 24)


def bucket_add_hist(bucket: Bucket, rows: jnp.ndarray,
                    event_ids: jnp.ndarray, amount: jnp.ndarray,
                    chunk: int = 1 << 15) -> Bucket:
    """:func:`bucket_add_events` for SMALL row tables with heavy index
    collisions (the alt origin/chain table): per-(row, lane) counts via a
    chunked one-hot matmul on the MXU, then ONE dense add to the plane —
    measured 10.1 → 3.3 ms against the colliding [2B]-index scatter at
    1M updates into 1024 rows on the v5 chip (BASELINE round-5
    continuation A/B).

    ``amount`` is the batch's single UNIFORM acquire (int32 scalar, may
    be traced): the matmul counts pure 0/1 one-hots (bf16 operands are
    exact, f32 accumulation is exact below 2^24 — asserted) and the
    scaling happens in int32 afterwards, so the result is bit-identical
    to the scatter for any uniform-acquire batch. Padding rows == R drop
    via the extra one-hot class."""
    R, n_ev = bucket.counters.shape
    n = rows.shape[0]
    ch = min(chunk, n)
    pad = (-n) % ch          # fill the last chunk with drop-class rows —
    if pad:                  # bit-identical, and non-power-of-2 batches
        rows = jnp.concatenate(   # keep full-width matmul chunks
            [rows, jnp.full(pad, R, rows.dtype)])
        event_ids = jnp.concatenate(
            [event_ids, jnp.zeros(pad, event_ids.dtype)])
        n += pad
    assert n < (1 << 24), \
        "histogram add needs count sums exact in f32 (gate callers on " \
        "hist_add_fits(n), which accounts for this chunk padding)"

    def _chunk(carry, xs):
        r, e = xs
        oh = jax.nn.one_hot(r, R + 1, dtype=jnp.bfloat16)
        v = jax.nn.one_hot(e, n_ev, dtype=jnp.bfloat16)
        return carry + jnp.dot(oh.T, v,
                               preferred_element_type=jnp.float32), None

    delta, _ = lax.scan(
        _chunk, jnp.zeros((R + 1, n_ev), jnp.float32),
        (rows.reshape(n // ch, ch), event_ids.reshape(n // ch, ch)))
    counts = delta.astype(jnp.int32)[:R] * amount
    return bucket._replace(counters=bucket.counters + counts)


def uncount_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                 idxs: jnp.ndarray, event: int,
                 amounts: jnp.ndarray) -> WindowState:
    """Subtract ``amounts`` of ``event`` from the bucket at window index
    ``idxs`` per row — ONLY where that bucket still carries the stamp for
    ``idxs`` (live). Reverses a reservation recorded earlier in the same
    ring lap (host lease pre-charges returning unused tokens); a rotated
    bucket already reads as zero, so no reversal is needed (or safe)
    there. Padding: rows >= R."""
    k = idxs % spec.buckets
    live = state.stamps[rows.clip(0, state.stamps.shape[0] - 1), k] == idxs
    amt = jnp.where(live, amounts, 0)
    counters = state.counters.at[rows, k, event].add(-amt, mode="drop")
    return state._replace(counters=counters)


def extract_rows(spec: WindowSpec, state: WindowState,
                 rows: jnp.ndarray) -> WindowState:
    """Gather the full window slice of each row in ``rows`` → a
    WindowState whose leading axis is ``len(rows)`` (tier demotion
    snapshot). Stamps are ABSOLUTE window indices, so the slice is
    self-contained: restored into any row at any later time it reads
    exactly as it read here (stale buckets stay stale by the validity
    arithmetic, not by position). Out-of-range rows (padding) gather
    row 0's slice — callers mask them at restore via ``mode='drop'``."""
    r = rows.clip(0, state.stamps.shape[0] - 1)
    return WindowState(counters=state.counters[r], stamps=state.stamps[r],
                       rt_sum=state.rt_sum[r], min_rt=state.min_rt[r])


def restore_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                 payload: WindowState) -> WindowState:
    """Scatter a :func:`extract_rows` payload back into ``rows`` (tier
    promotion). Overwrites the destination rows completely — the caller
    just invalidated them (registry re-allocation), so the set is exact:
    the row reads bit-identically to one that never left the device.
    Padding: rows >= R drop."""
    return WindowState(
        counters=state.counters.at[rows].set(payload.counters, mode="drop"),
        stamps=state.stamps.at[rows].set(payload.stamps, mode="drop"),
        rt_sum=state.rt_sum.at[rows].set(payload.rt_sum, mode="drop"),
        min_rt=state.min_rt.at[rows].set(payload.min_rt, mode="drop"))


def invalidate_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray) -> WindowState:
    """Forget all history of ``rows`` (registry eviction → row reuse).

    Stamps go to NEVER so every bucket reads as deprecated; counters/rt need
    no touch (refresh_rows zeroes them on next write). Without this, a row
    recycled to a new resource would inherit the evicted resource's live
    counts and could be instantly flow-blocked on another resource's traffic.
    """
    stamps = state.stamps.at[rows, :].set(NEVER, mode="drop")
    return state._replace(stamps=stamps)


def settle_occupied(spec: WindowSpec, state: WindowState,
                    occ_cnt: jnp.ndarray, occ_win: jnp.ndarray,
                    now_idx: jnp.ndarray, event: int):
    """Materialize occupy bookings into the window so the booking ring can
    be reset (rule reload rebuilds ``FlowDynState``) without forgetting
    admissions already granted.

    A LANDED booking (target window reached, still inside the rolling
    interval: ``0 <= now - w < buckets``) is credited as ``event`` counts
    into its target bucket ``w % buckets`` — every rolling sum over a span
    containing ``w`` then reads the identical total it read from the
    booking ring, so post-reload admission math is unchanged. A dead or
    rotated target bucket is fully reset (all lanes + rt) and restamped to
    ``w`` first, exactly as ``refresh_rows`` would on a write. A PENDING
    booking (``now - w == -1``: target window not reached yet) cannot land
    in a bucket that does not exist — it is returned for carry into the
    fresh booking ring instead. Anything older is expired and dropped.

    Returns ``(state', pend_cnt, pend_win)`` with the pending arrays
    shaped like the booking ring (zero / NEVER where not pending).
    """
    R = state.stamps.shape[0]
    B = spec.buckets
    rr = jnp.arange(R)
    counters, stamps = state.counters, state.stamps
    rt_sum, min_rt = state.rt_sum, state.min_rt
    pend_cnt = jnp.zeros_like(occ_cnt)
    pend_win = jnp.full_like(occ_win, NEVER)
    for s in range(occ_cnt.shape[1]):       # S = buckets + 1, static
        w = occ_win[:, s]
        c = occ_cnt[:, s]
        age = now_idx - w
        landed = (age >= 0) & (age < B) & (c > 0)
        pending = (age == -1) & (c > 0)
        k = jnp.where(landed, w % B, 0)
        live = stamps[rr, k] == w
        bsel = jnp.arange(B)[None, :] == k[:, None]          # [R, B]
        reset_rb = (landed & ~live)[:, None] & bsel
        counters = jnp.where(reset_rb[:, :, None], 0, counters)
        if spec.track_rt:
            rt_sum = jnp.where(reset_rb, 0, rt_sum)
            min_rt = jnp.where(reset_rb, INT32_MAX, min_rt)
        stamps = jnp.where(landed[:, None] & bsel, w[:, None], stamps)
        add_rb = jnp.where(landed[:, None] & bsel,
                           c.astype(jnp.int32)[:, None], 0)
        counters = counters.at[:, :, event].add(add_rb)
        pend_cnt = pend_cnt.at[:, s].set(jnp.where(pending, c, 0.0))
        pend_win = pend_win.at[:, s].set(jnp.where(pending, w, NEVER))
    state = state._replace(counters=counters, stamps=stamps)
    if spec.track_rt:
        state = state._replace(rt_sum=rt_sum, min_rt=min_rt)
    return state, pend_cnt, pend_win


def bucket_snapshot(spec: WindowSpec, state: WindowState, idx: jnp.ndarray):
    """All rows' counters (+ rt sum) for the bucket at window index ``idx`` —
    zeros where that bucket is dead. The per-second aggregation read the
    metric-file pipeline makes (``MetricTimerListener`` pulls each node's
    per-second ``metrics()``)."""
    k = _bucket_of(spec, idx)
    live = state.stamps[:, k] == idx                        # [R]
    counters = jnp.where(live[:, None], state.counters[:, k, :], 0)
    if spec.track_rt:
        rt = jnp.where(live, state.rt_sum[:, k], 0.0)
    else:
        rt = jnp.zeros(live.shape, jnp.float32)
    return counters, rt


def min_rt_rows(spec: WindowSpec, state: WindowState, rows: jnp.ndarray,
                now_idx: jnp.ndarray, default_rt: int) -> jnp.ndarray:
    """Min RT over live buckets per row (reference ``ArrayMetric.minRt`` —
    returns ``statisticMaxRt`` when nothing recorded)."""
    if not spec.track_rt:
        raise ValueError("rt untracked for this window spec")
    mask = valid_mask(spec, state.stamps[rows], now_idx)
    vals = jnp.where(mask, state.min_rt[rows], INT32_MAX)
    m = jnp.min(vals, axis=1)
    return jnp.where(m == INT32_MAX, default_rt, m).astype(jnp.int32)
