"""Tiered resource state (round 15): device hot tier + host cold tier.

Every dispatch path used to assume the whole keyspace fits the pre-sized
device table (ROADMAP item 2's scaling wall). This package breaks that:
the existing sharded ``WindowState`` rows are the HOT tier (hot-path
math unchanged), evicted rows' window counters, occupy bookings and
thread gauges spill to a host-memory COLD tier
(:class:`~sentinel_tpu.tiering.coldtier.ColdTier`), and a re-interned
cold key is promoted back bit-identically
(:class:`~sentinel_tpu.tiering.manager.TierManager`) — total key
cardinality is unbounded while the device table stays fixed-size.

Hot-set discovery runs on-device: a conservative-update count-min
sketch (:mod:`~sentinel_tpu.tiering.sketch`) is updated from each
batch's resource rows under the engine lock (dispatch-only, no sync),
and the tiering ticker thread — modeled on the round-12 telemetry
ticker — decays it and, with a ``SENTINEL_HOT_ROWS`` target set, reads
the rows' estimates and proactively demotes low-estimate rows so LRU
pressure never lands on a hot row.

See docs/OPERATIONS.md "Tiered resource state (round 15)" for the
operational runbook and the slow-path caveat.
"""

from sentinel_tpu.tiering.coldtier import ColdBlock, ColdEntry, ColdTier
from sentinel_tpu.tiering.manager import (
    HOT_ROWS_ENV, SKETCH_BITS_ENV, SKETCH_ROWS_ENV, TIER_TICK_MS_ENV,
    TIERING_DISABLE_ENV, TierManager, tier_hot_rows, tier_sketch_bits,
    tier_sketch_rows, tier_tick_ms, tiering_disabled,
)
from sentinel_tpu.tiering.sketch import (
    SKETCH_IMPLS, decay_sketch, estimate_all, init_sketch, update_sketch,
)

__all__ = [
    "ColdBlock", "ColdEntry", "ColdTier", "TierManager",
    "HOT_ROWS_ENV", "SKETCH_BITS_ENV", "SKETCH_ROWS_ENV",
    "TIER_TICK_MS_ENV", "TIERING_DISABLE_ENV",
    "tier_hot_rows", "tier_sketch_bits", "tier_sketch_rows",
    "tier_tick_ms", "tiering_disabled",
    "SKETCH_IMPLS", "init_sketch", "update_sketch", "decay_sketch",
    "estimate_all",
]
