"""Conservative-update count-min sketch over resource ROW ids (device).

Hot-set discovery for the tiered state machine (Cormode & Muthukrishnan
2005, with the conservative-update variant: a counter only rises to the
new minimum estimate, which tightens over-estimation for skewed
streams). The sketch is tiny — ``SR`` hash rows × ``W = 2**bits``
buckets of int32 — and is updated from each decide batch's row array
UNDER the engine lock as a dispatch-only jitted op (no host sync, the
telemetry-tick discipline); the tiering ticker decays it and reads
back its largest counter, and every row's estimate only for the
proactive demotion that ranks by it.

Access shape honesty (the ops/pallas_kernels.py methodology): the
update is a scatter-max of ``N`` batch elements into an ``[SR, W]``
table. Three implementations of the identical math live behind
:data:`SKETCH_IMPLS` — ``scatter`` (native ``.at[].max``), ``onehot``
(masked one-hot reduce-max, the MXU-shaped candidate) and ``segment``
(``jax.ops.segment_max``) — and ``benchmarks/sketch_ab.py`` times them
on the real device before a kernel is committed. On every shape
measured so far (CPU only) the XLA scatter path wins, so
``DEFAULT_IMPL = "scatter"`` and no Pallas kernel ships; the seam stays
so a future chip profile can flip one string.

Hash family: multiply-shift over odd 32-bit constants
(``h_s(x) = ((x * C_s) >> 15) & (W - 1)``) — int32 overflow wraps,
which is exactly the mod-2^32 arithmetic the scheme wants.

Decay: the ticker applies ``c -= max(c >> DECAY_SHIFT, 1)`` (floored
at zero) per tick so the sketch tracks the RECENT hot set, not
all-time counts — the ``min 1`` term matters: a pure shift-decay can
never move a counter below ``2**DECAY_SHIFT - 1``, leaving permanent
floor estimates on cold rows. Overflow: :func:`update_sketch` halves
the whole table INSIDE the jitted op whenever any estimate crosses
:data:`OVERFLOW_CAP` (frequencies are relative, halving preserves
ranking), so counters are bounded — and can never wrap int32 — even on
an engine that never starts the ticker; the returned overflow flag and
the ticker's readback of the largest counter only drive the
``tier.sketch_overflow`` accounting.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BITS = 12        # W = 4096 buckets per hash row
DEFAULT_ROWS = 4         # SR hash rows
DECAY_SHIFT = 3          # per-tick decay: c -= c >> 3 (~12%/tick)
OVERFLOW_CAP = 1 << 30   # halve the table past this estimate

# odd multiply-shift constants (Knuth/Dietzfelbinger family); 8 rows max
_HASH_CONSTS = np.array(
    [0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F,
     0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09], np.uint32)


def init_sketch(sketch_rows: int = DEFAULT_ROWS,
                bits: int = DEFAULT_BITS) -> jnp.ndarray:
    """Fresh zero table int32[SR, W]."""
    sketch_rows = max(1, min(int(sketch_rows), len(_HASH_CONSTS)))
    return jnp.zeros((sketch_rows, 1 << int(bits)), jnp.int32)


def _bucket_idx(counts: jnp.ndarray, items: jnp.ndarray) -> jnp.ndarray:
    """[SR, N] bucket index per (hash row, item) — multiply-shift."""
    sr, w = counts.shape
    consts = jnp.asarray(_HASH_CONSTS[:sr].astype(np.int32))
    prod = items[None, :].astype(jnp.int32) * consts[:, None]  # wraps mod 2^32
    return jax.lax.shift_right_logical(prod, 15) & jnp.int32(w - 1)


def _estimates(counts: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Count-min read: min over hash rows of the addressed buckets."""
    sr = counts.shape[0]
    gathered = counts[jnp.arange(sr)[:, None], idx]            # [SR, N]
    return jnp.min(gathered, axis=0)                           # [N]


def _update_scatter(counts, idx, target):
    """Native scatter-max (XLA scatter; the measured winner)."""
    sr = counts.shape[0]
    rr = jnp.broadcast_to(jnp.arange(sr)[:, None], idx.shape)
    return counts.at[rr, idx].max(jnp.broadcast_to(target[None, :],
                                                   idx.shape))


def _update_onehot(counts, idx, target):
    """Masked one-hot reduce-max — the MXU-shaped candidate: builds the
    [N, W] one-hot per hash row and reduces. Memory-bound at real batch
    sizes; kept as the A/B foil."""
    sr, w = counts.shape
    out = []
    for s in range(sr):
        oh = jax.nn.one_hot(idx[s], w, dtype=jnp.int32)        # [N, W]
        cand = jnp.max(oh * target[:, None], axis=0)           # [W]
        out.append(jnp.maximum(counts[s], cand))
    return jnp.stack(out)


def _update_segment(counts, idx, target):
    """segment_max over flattened (hash row, bucket) segments."""
    sr, w = counts.shape
    flat_idx = (jnp.arange(sr)[:, None] * w + idx).reshape(-1)
    flat_val = jnp.broadcast_to(target[None, :], idx.shape).reshape(-1)
    cand = jax.ops.segment_max(flat_val, flat_idx, num_segments=sr * w)
    return jnp.maximum(counts, cand.reshape(sr, w))

# A/B seam (ops/pallas_kernels.py precedent): identical math, one string
# picks the shipped path; benchmarks/sketch_ab.py is the evidence.
SKETCH_IMPLS = {
    "scatter": _update_scatter,
    "onehot": _update_onehot,
    "segment": _update_segment,
}
DEFAULT_IMPL = "scatter"


def update_sketch(counts: jnp.ndarray, items: jnp.ndarray,
                  valid: jnp.ndarray,
                  impl: str = DEFAULT_IMPL
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Conservative-update: each valid item raises its buckets to
    ``min-estimate + 1`` (never higher). Duplicate items within one
    batch under-count by design — the error is in the conservative
    direction (a hot row's estimate can only lag, never spuriously
    spike another row hot). Invalid (padding) lanes write 0 — a no-op
    under max. Returns ``(counts', overflow)`` with ``overflow`` a bool
    scalar: any estimate crossed :data:`OVERFLOW_CAP`. The halving
    happens HERE, inside the jitted op, so the table is self-clamping
    on engines with no running ticker (dispatch-only callers may drop
    the flag; it only feeds ``tier.sketch_overflow`` accounting)."""
    idx = _bucket_idx(counts, items)                           # [SR, N]
    est = _estimates(counts, idx)                              # [N]
    target = jnp.where(valid, est + 1, 0)
    counts = SKETCH_IMPLS[impl](counts, idx, target)
    overflow = jnp.any(target >= OVERFLOW_CAP)
    counts = jnp.where(overflow, halve_sketch(counts), counts)
    return counts, overflow


def decay_sketch(counts: jnp.ndarray) -> jnp.ndarray:
    """Per-tick exponential decay (recency weighting). Nonzero counters
    lose at least 1 per tick — ``c >> DECAY_SHIFT`` alone is 0 for
    ``c < 2**DECAY_SHIFT``, which would pin cold rows at a permanent
    nonzero floor estimate forever."""
    dec = jnp.maximum(jax.lax.shift_right_logical(counts, DECAY_SHIFT),
                      jnp.minimum(counts, 1))
    return counts - dec


def halve_sketch(counts: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.shift_right_logical(counts, 1)


def estimate_all(counts: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """Estimates for every main-table row id [0, n_rows) → int32[R] —
    the ranking proactive demotion evicts by, and its only reader: a
    gather of ``SR x R`` lanes, so the ticker dispatches it only when
    that demotion can run (:meth:`TierManager.tick`)."""
    items = jnp.arange(n_rows, dtype=jnp.int32)
    return _estimates(counts, _bucket_idx(counts, items))


@functools.lru_cache(maxsize=None)
def jit_update(impl: str = DEFAULT_IMPL):
    return jax.jit(functools.partial(update_sketch, impl=impl))


def tick_read(counts: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One ticker read as pure math: decay, then the table's largest
    counter → ``(counts', int32 scalar)``.

    That scalar IS the largest estimate :func:`estimate_all` would find
    over the rows the sketch was fed, without the ``SR x R`` gather: a
    conservative update writes a bucket as ``est(x) + 1`` and leaves
    every other bucket of ``x`` at or above that value, and the update's
    max, this decay and :func:`halve_sketch` are monotone — so whichever
    row last wrote the table's largest bucket still has all its buckets
    at that value (tests/test_tiering.py holds the two equal)."""
    counts = decay_sketch(counts)
    return counts, jnp.max(counts)


jit_tick_read = jax.jit(tick_read)
jit_estimate_all = jax.jit(estimate_all, static_argnames="n_rows")


_jit_halve = jax.jit(halve_sketch)
