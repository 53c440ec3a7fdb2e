"""Decision counters — the runtime's own "why did that happen" tallies.

One flat monotonically-increasing integer per named decision outcome,
mutated per BATCH (not per event) on the hot path so the instrumented
dispatch stays within the 2% observability budget (benchmarks/ci_gate.py
``obs_overhead`` gate). Families:

* ``split_route.*`` — which dispatch path a batch took
  (:meth:`~sentinel_tpu.runtime.Sentinel.decide_raw_nowait` path
  selection): ``scalar`` / ``fast`` / ``fast_occupy`` /
  ``general_sorted``, plus ``split_fired`` when a mixed batch was
  per-event split (``_decide_split_nowait``), ``meshed`` when the
  dispatch ran on a row-sharded engine (alongside its route counter:
  meshed_total/route_total attributes how much traffic the mesh path
  carries), ``sortfree`` when the dispatch's flow programs grouped
  segments sort-free (alongside its route counter, same pattern), and
  ``single_dispatch`` when a whole-batch decide program carried the
  tiering sketch observe inside itself (the batch cost ONE device
  dispatch instead of decide + observe). ``fused_exit`` is a catalog
  key nothing increments (its program family went in PR 31; the
  catalog is append-only).
* ``sortfree.bucket_overflow`` — claim-cascade overflow total: elements
  whose step fell back to the sorted branch (ops/sortfree.py); sustained
  growth means the bucket table is undersized for the key distribution.
* ``compile_cache.*`` — first-dispatch program accounting per (variant,
  geometry, statics) combo: ``hit`` / ``miss``. ``first_fetch_retry`` is
  retired (nothing ticks it); it keeps its catalog slot because the
  wire-order manifest is append-only.
* ``occupy.*`` — priority booking lifecycle: ``granted`` (PriorityWait
  admissions), ``carried`` / ``settled`` (bookings surviving /
  landing at rule reload), ``evicted`` (cleared by row eviction).
* ``pipeline.*`` — dispatch-pipeline health (sentinel_tpu/serving.py):
  ``depth`` (sum of in-flight handles observed at each enqueue — divide
  by enqueue count for the achieved average depth), ``stall`` (submits
  that had to settle the oldest in-flight batch first),
  ``leaked_handles`` (PendingVerdicts settled by the GC finalizer
  because ``.result()`` was never called), ``meshed_dispatch``
  (submits whose backing Sentinel is row-sharded over a mesh), and
  ``dispatches`` (device dispatches issued by the serving hot path and
  its tickers — dispatches/batch is the round-16 single-dispatch
  headline, gated at 1.0 by benchmarks/ci_gate.py gate (m)).
* ``frontend.*`` — the ingest tier (sentinel_tpu/frontend/):
  ``enqueue`` (requests accepted), ``queue_depth`` (sum of pending
  queue length sampled at each enqueue — divide by enqueues for the
  achieved average depth), ``shed`` (requests rejected at the
  ``queue_max`` backpressure bound), and ``flush_reason.{full,
  deadline, idle}`` (why each device batch was cut).
* ``block_reason.<ExceptionName>`` — per-reason denial breakdown keyed
  by the int8 verdict codes (``exception_name_for`` /
  ``slot_name_for_code`` for custom slots).
* ``verdict.paced`` / ``verdict.passed_now`` — admitted events with
  and without a ``wait_ms``, counted beside ``block_reason.*``.
* ``breaker.*`` — the circuit breakers as each telemetry tick finds
  them: ``seen_open`` / ``seen_closed`` (active breakers not CLOSED /
  CLOSED, added at every tick) and ``opened`` / ``half_opened`` /
  ``closed`` (tick-to-tick changes; an arc faster than a tick is
  missed).
* ``obs.span_ring_wrap`` — spans/links lost to per-thread ring wrap
  (a ring too small for the sustained span rate; previously a silent
  overwrite).
* ``cluster.server.*`` — the token server's cycle (cluster/server.py
  ``_batch_loop``; they live in the ENGINE's bundle, ``engine.obs``):
  ``cycles`` (batching windows that took requests), ``taken`` (requests
  handed to the engine) and ``queue_wait_us`` (summed at the take: each
  request's wait since ``_dispatch`` queued it). Mean queue wait =
  ``queue_wait_us / taken``; requests per engine call ≈ ``taken /
  cycles``.
* ``flight.*`` — the SLO flight recorder (obs/flight.py): ``pinned``
  (chains persisted to the ``<app>-trace`` log) and
  ``trigger.{deadline_miss, shed, p99, block_burst}`` (which SLO
  trigger fired, after per-kind rate limiting).
* ``tune.*`` — the serving autotuner (sentinel_tpu/tune/):
  ``config_loaded`` / ``fingerprint_fallback`` (startup resolution of
  the ``SENTINEL_TUNED_CONFIG`` artifact), ``knob_rejected`` (unknown
  or out-of-clamp ``SENTINEL_*`` env keys found at construction),
  ``trial`` (sweep episodes scored against this engine's obs) and
  ``parity_fail`` (verdict bit-parity spot-check failures).
* ``telemetry.*`` — the device-resident hot-resource telemetry layer
  (obs/telemetry.py): ``tick`` (device reads dispatched) and
  ``readback_drop`` (ticks dropped because async host readback fell
  behind — the drop-and-count policy that keeps telemetry off the
  dispatch path).
* ``exporter.label_overflow`` — Prometheus label-cardinality guard
  (metrics/exporter.py): resource-labeled samples dropped at the
  per-family label cap.

:data:`CATALOG` is the fixed, ordered multihost-aggregatable key set:
every process packs its snapshot into one int64 vector
(:func:`catalog_vector`) for a single ``process_allgather``
(multihost/obs_agg.py) — dynamic keys (custom-slot block reasons)
aggregate only through the transport surface.
"""

from __future__ import annotations

import threading
from typing import Dict, Mapping

ROUTE_SCALAR = "split_route.scalar"
ROUTE_FAST = "split_route.fast"
ROUTE_FAST_OCCUPY = "split_route.fast_occupy"
ROUTE_GENERAL = "split_route.general_sorted"
ROUTE_SPLIT = "split_route.split_fired"

CACHE_HIT = "compile_cache.hit"
CACHE_MISS = "compile_cache.miss"
CACHE_RETRY = "compile_cache.first_fetch_retry"    # retired, slot kept

OCCUPY_GRANTED = "occupy.granted"
OCCUPY_CARRIED = "occupy.carried"
OCCUPY_SETTLED = "occupy.settled"
OCCUPY_EVICTED = "occupy.evicted"

ROUTE_FUSED = "split_route.fused_exit"

PIPE_DEPTH = "pipeline.depth"
PIPE_STALL = "pipeline.stall"
PIPE_LEAKED = "pipeline.leaked_handles"

FE_ENQUEUE = "frontend.enqueue"
FE_QUEUE_DEPTH = "frontend.queue_depth"
FE_SHED = "frontend.shed"
FE_FLUSH_FULL = "frontend.flush_reason.full"
FE_FLUSH_DEADLINE = "frontend.flush_reason.deadline"
FE_FLUSH_IDLE = "frontend.flush_reason.idle"

BLOCK_PREFIX = "block_reason."

# PR 8 — tracing / flight-recorder health
SPAN_RING_WRAP = "obs.span_ring_wrap"     # spans/links lost to ring wrap
FLIGHT_PINNED = "flight.pinned"           # chains pinned by an SLO trigger
FLIGHT_TRIGGER_PREFIX = "flight.trigger."  # per-kind trigger tallies

# PR 9 — meshed serving hot path: dispatches decided by a row-sharded
# engine (one per decide/split/fused dispatch alongside its route
# counter) and pipeline submits whose backing Sentinel is meshed
ROUTE_MESHED = "split_route.meshed"
PIPE_MESHED = "pipeline.meshed_dispatch"

# PR 10 — sort-free general path: dispatches whose flow programs grouped
# segments via the hash-bucketed claim cascade (one per decide/split/
# fused dispatch alongside its route counter, like ROUTE_MESHED), and
# the per-step claim-cascade overflow tally (elements that took the
# sorted fallback branch under lax.cond — sustained growth means the
# bucket table is undersized for the live key distribution; see
# docs/OPERATIONS.md "Sort-free general path")
ROUTE_SORTFREE = "split_route.sortfree"
SORTFREE_OVERFLOW = "sortfree.bucket_overflow"

# PR 11 — serving autotuner (sentinel_tpu/tune/): startup resolution of
# the SENTINEL_TUNED_CONFIG artifact (loaded vs fingerprint-mismatch
# fallback to defaults), the knob-registry validation warnings (unknown
# or out-of-clamp SENTINEL_* env keys — one tick per finding at Sentinel
# construction), and sweep health (trials run on this engine's obs,
# verdict bit-parity spot-check failures — any nonzero parity_fail
# disqualifies the sweep)
TUNE_LOADED = "tune.config_loaded"
TUNE_FALLBACK = "tune.fingerprint_fallback"
TUNE_KNOB_REJECTED = "tune.knob_rejected"
TUNE_TRIAL = "tune.trial"
TUNE_PARITY_FAIL = "tune.parity_fail"

# PR 12 — device-resident hot-resource telemetry (obs/telemetry.py):
# ``tick`` counts telemetry reads dispatched over the live window state,
# ``readback_drop`` counts ticks skipped because the asynchronous host
# readback fell PENDING_MAX behind (drop-and-count: the dispatch path is
# never blocked on a telemetry sync — sustained growth means the
# telemetry thread is starved). ``label_overflow`` is the exporter's
# label-cardinality guard (metrics/exporter.py): per-resource label
# values beyond the cap are dropped from the scrape and counted here.
TELEMETRY_TICK = "telemetry.tick"
TELEMETRY_DROP = "telemetry.readback_drop"
EXPORTER_LABEL_OVERFLOW = "exporter.label_overflow"

# PR 15 — tiered resource state (sentinel_tpu/tiering/): ``hot_hit`` /
# ``cold_miss`` classify interns of keys the tier system already knows
# (resident row vs cold-tier restore — brand-new keys tick NEITHER, so
# the hit rate measures hot-tier sizing rather than keyspace size);
# ``promoted`` / ``demoted`` count row migrations between the device hot
# tier and the host cold tier (``demoted`` ticks on the invalidation
# drain as each recycled row's state is snapshotted out; with tiering
# disabled the drain is the pre-round-15 lossy invalidate and only
# ``occupy.evicted`` ticks);
# ``sketch_overflow`` counts count-min table halvings (estimates are
# relative, halving preserves the hot/cold ranking — sustained growth
# just means a long-lived process, not a fault). Exported as
# ``sentinel_tier_total{event=...}``; see docs/OPERATIONS.md
# "Tiered resource state (round 15)".
TIER_HOT_HIT = "tier.hot_hit"
TIER_COLD_MISS = "tier.cold_miss"
TIER_PROMOTED = "tier.promoted"
TIER_DEMOTED = "tier.demoted"
TIER_SKETCH_OVERFLOW = "tier.sketch_overflow"
# PR 33 — what the two classification counters leave out, and where a
# landing ran: ``first_sight`` counts the distinct NAMES interned that
# neither tier knew (each takes a row and, with the table full, evicts
# one: the rate the cold tier grows at); ``land_inline`` counts the
# victims whose demote payload was landed into the cold tier on the
# engine's own thread, under its lock — a promotion that found its
# payload still in flight, or the backlog bound ``PENDING_LAND_MAX`` —
# instead of on the tiering thread.
TIER_FIRST_SIGHT = "tier.first_sight"
TIER_LAND_INLINE = "tier.land_inline"
# PR 34 — a demotion record lands as one columnar block and promotion
# gathers from the blocks, so no per-name object exists on the serving
# path; ``materialized`` counts the ``ColdEntry`` objects that WERE
# built from a block row — a by-name read of a cold key
# (``TierManager.cold_entry``) or the replay of a flow-rule reload a
# key slept through. The slow form: it should stand still under
# traffic that neither reads cold keys by name nor reloads rules.
TIER_MATERIALIZED = "tier.materialized"

# PR 35 — what the verdicts and the breakers did, beside the reasons
# (``block_reason.*``). ``verdict.paced`` / ``verdict.passed_now``:
# admitted events of the batch door with and without a wait (``wait_ms``
# > 0: a RateLimiter or WarmUpRateLimiter rule spaced the event out, or
# an occupy booking landed it in the next window), counted where
# ``block_reason.*`` is, when a batch's verdicts settle; their sum is the
# admitted events. ``breaker.seen_open`` / ``breaker.seen_closed``: each
# telemetry tick (``obs/telemetry.py``, on the scheduler's thread) reads
# the breaker-state column once and adds the active breakers it found
# not CLOSED (OPEN or HALF_OPEN) / CLOSED — ``seen_open / (seen_open +
# seen_closed)`` is the share of breaker x tick readings that found the
# dependency cut off. ``breaker.opened`` / ``half_opened`` / ``closed``:
# breakers whose state differs from the previous tick's reading, by the
# state they are in now — an arc that starts and ends between two ticks
# (a probe that fails at once: OPEN → HALF_OPEN → OPEN) is missed, and
# the counts restart with a rule reload. Exported as
# ``sentinel_verdict_total{event=...}`` and
# ``sentinel_breaker_total{event=...}``.
VERDICT_PACED = "verdict.paced"
VERDICT_PASSED_NOW = "verdict.passed_now"
BREAKER_SEEN_OPEN = "breaker.seen_open"
BREAKER_SEEN_CLOSED = "breaker.seen_closed"
BREAKER_OPENED = "breaker.opened"
BREAKER_HALF_OPENED = "breaker.half_opened"
BREAKER_CLOSED = "breaker.closed"

# PR 36 — what the tiering tick dispatched: ``tier.tick`` counts the
# ticks (sketch decay + the read of its largest counter, a 64 KB
# program whatever the table's size); ``tier.tick_estimate`` counts
# those that ALSO dispatched every row's estimate (``SR x R`` gathered
# lanes and an ``int32[R]`` readback) — only when proactive demotion
# can use it: ``SENTINEL_HOT_ROWS`` set on the Python registry. 0 on a
# default deployment.
TIER_TICK = "tier.tick"
TIER_TICK_ESTIMATE = "tier.tick_estimate"

# ``pipeline.dispatches`` counts DEVICE DISPATCHES issued by the
# serving hot path and its tickers (decide = 1, split = 2, exit = 1, a
# standalone sketch observe = 1, a telemetry or tiering tick = 1, one
# more for a tiering tick that also dispatches every row's estimate;
# cold-path programs — invalidation drains, promotions/restores, rule
# reloads — are deliberately NOT counted: the key exists so
# dispatches-per-batch is measurable from obs plumbing alone, and the
# cold path is not per-batch). ``split_route.single_dispatch`` ticks
# once per whole-batch dispatch that carried the tiering sketch update
# inside the decide program itself (alongside its route counter, like
# ROUTE_MESHED/ROUTE_SORTFREE); the per-sub-batch split pipeline fuses
# the sketch too but keeps its two dispatches, so it never ticks this
# key.
PIPE_DISPATCH = "pipeline.dispatches"
ROUTE_SINGLE_DISPATCH = "split_route.single_dispatch"

# PR 17 — closed-loop overload controller (sentinel_tpu/control/):
# ``tick`` counts policy evaluations (one per ControlLoop cadence slot
# with a fresh observation); the three ``action.*`` keys count APPLIED
# interventions by type (shed-fraction change, batcher retune, forced
# degrade transition — every one is also pinned in the flight recorder
# with its triggering evidence, trigger kind ``controller_action``);
# ``admission_dropped`` counts requests the frontend refused under a
# controller-set admission fraction < 1 (deterministic seeded-hash
# shed, BEFORE batches form — distinct from ``frontend.shed``, the
# queue-overflow backpressure). Exported as
# ``sentinel_control_total{action=...}``; see docs/OPERATIONS.md
# "Self-driving overload protection (round 17)".
CONTROL_TICK = "control.tick"
CONTROL_SHED_ACTION = "control.action.shed_rate"
CONTROL_RETUNE_ACTION = "control.action.retune_batcher"
CONTROL_DEGRADE_ACTION = "control.action.degrade"
CONTROL_DROPPED = "control.admission_dropped"

# PR 20 — device-resident per-resource RT histograms
# (sentinel_tpu/obs/resource_hist.py): ``telemetry.hist_tick`` counts
# telemetry landings that carried per-resource histogram vectors and
# quantiles (0 while ``SENTINEL_RESOURCE_HIST_DISABLE`` drops the
# table — the delta against ``telemetry.tick`` shows the feature
# switch state from the scrape alone); ``control.tail_signal`` counts
# controller ticks whose degrade evaluation ran on per-resource
# interval p99 deltas rather than the pre-r20 hot-set mean RT
# fallback. Exported under the existing ``sentinel_telemetry_total``
# / ``sentinel_control_total`` families; see docs/OBSERVABILITY.md
# "Per-resource RT histograms (round 20)".
TELEMETRY_HIST_TICK = "telemetry.hist_tick"
CONTROL_TAIL_SIGNAL = "control.tail_signal"

# PR 26 — the cluster token server's cycle, counted where the work
# happens (cluster/server.py ``_batch_loop``, into ``engine.obs``).
CLUSTER_SERVER_CYCLES = "cluster.server.cycles"
CLUSTER_SERVER_TAKEN = "cluster.server.taken"
CLUSTER_SERVER_QUEUE_WAIT_US = "cluster.server.queue_wait_us"

# PR 32 — the batch doors' one dedup (runtime.Sentinel._intern_batch):
# ``names`` counts the names handed in as strings to
# ``entry_batch_nowait`` / ``intern_resources``, ``distinct`` the
# distinct names among them, batch by batch. The registry's FFI call
# and tiering's classification run per DISTINCT name, so
# ``distinct / names`` is the share of the per-name work that traffic
# still pays: ~0.26 for Zipf 1.1 over 1M names in batches of 65,536,
# 1.0 for traffic that never repeats a name inside a batch. Batches of
# pre-interned int32 rows add to neither. Exported as
# ``sentinel_intern_total{event=...}``.
INTERN_NAMES = "intern.names"
INTERN_DISTINCT = "intern.distinct"

#: Fixed aggregation catalog (order is the wire format of the multihost
#: counter vector — append only, never reorder).
CATALOG = (
    ROUTE_SCALAR, ROUTE_FAST, ROUTE_FAST_OCCUPY, ROUTE_GENERAL, ROUTE_SPLIT,
    CACHE_HIT, CACHE_MISS, CACHE_RETRY,
    OCCUPY_GRANTED, OCCUPY_CARRIED, OCCUPY_SETTLED, OCCUPY_EVICTED,
    BLOCK_PREFIX + "FlowException",
    BLOCK_PREFIX + "DegradeException",
    BLOCK_PREFIX + "SystemBlockException",
    BLOCK_PREFIX + "AuthorityException",
    BLOCK_PREFIX + "ParamFlowException",
    ROUTE_FUSED,
    PIPE_DEPTH, PIPE_STALL, PIPE_LEAKED,
    FE_ENQUEUE, FE_QUEUE_DEPTH, FE_SHED,
    FE_FLUSH_FULL, FE_FLUSH_DEADLINE, FE_FLUSH_IDLE,
    SPAN_RING_WRAP, FLIGHT_PINNED,
    FLIGHT_TRIGGER_PREFIX + "deadline_miss",
    FLIGHT_TRIGGER_PREFIX + "shed",
    FLIGHT_TRIGGER_PREFIX + "p99",
    FLIGHT_TRIGGER_PREFIX + "block_burst",
    ROUTE_MESHED, PIPE_MESHED,
    ROUTE_SORTFREE, SORTFREE_OVERFLOW,
    TUNE_LOADED, TUNE_FALLBACK, TUNE_KNOB_REJECTED,
    TUNE_TRIAL, TUNE_PARITY_FAIL,
    TELEMETRY_TICK, TELEMETRY_DROP, EXPORTER_LABEL_OVERFLOW,
    TIER_HOT_HIT, TIER_COLD_MISS, TIER_PROMOTED, TIER_DEMOTED,
    TIER_SKETCH_OVERFLOW,
    PIPE_DISPATCH, ROUTE_SINGLE_DISPATCH,
    CONTROL_TICK, CONTROL_SHED_ACTION, CONTROL_RETUNE_ACTION,
    CONTROL_DEGRADE_ACTION, CONTROL_DROPPED,
    TELEMETRY_HIST_TICK, CONTROL_TAIL_SIGNAL,
    CLUSTER_SERVER_CYCLES, CLUSTER_SERVER_TAKEN,
    CLUSTER_SERVER_QUEUE_WAIT_US,
    INTERN_NAMES, INTERN_DISTINCT,
    TIER_FIRST_SIGHT, TIER_LAND_INLINE,
    TIER_MATERIALIZED,
    VERDICT_PACED, VERDICT_PASSED_NOW,
    BREAKER_SEEN_OPEN, BREAKER_SEEN_CLOSED,
    BREAKER_OPENED, BREAKER_HALF_OPENED, BREAKER_CLOSED,
    TIER_TICK, TIER_TICK_ESTIMATE,
)


class CounterSet:
    """Locked flat dict of monotonic counters.

    One uncontended ``lock + dict.get + add`` per increment; increments
    happen once per batch on the dispatch path, so the cost is amortized
    over thousands of events."""

    __slots__ = ("_lock", "_c")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._c: Dict[str, int] = {}

    def add(self, key: str, n: int = 1) -> None:
        if n == 0:
            return
        with self._lock:
            self._c[key] = self._c.get(key, 0) + int(n)

    def get(self, key: str) -> int:
        with self._lock:
            return self._c.get(key, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._c)

    def merge(self, counts: Mapping[str, int]) -> None:
        """Fold another snapshot in (multihost coordinator aggregation)."""
        with self._lock:
            for k, v in counts.items():
                self._c[k] = self._c.get(k, 0) + int(v)

    def clear(self) -> None:
        with self._lock:
            self._c.clear()


def catalog_vector(counts: Mapping[str, int]):
    """Snapshot → int64 vector over :data:`CATALOG` (allgather payload)."""
    import numpy as np
    return np.asarray([int(counts.get(k, 0)) for k in CATALOG], np.int64)


def vector_counts(vec) -> Dict[str, int]:
    """Inverse of :func:`catalog_vector` (tolerates longer vectors from a
    newer peer — extra trailing entries are unknown keys and dropped)."""
    return {k: int(vec[i]) for i, k in enumerate(CATALOG) if i < len(vec)}
