"""Sort-free hash-bucketed scatter aggregation (round 10).

The general admission path's only superlinear stage is the composite-key
sort that groups (rule, stat-row) pairs into segments
(``ops/segments.py`` ``sort_by_keys`` — n·log n; its share of the general
step on a host-attached chip: not measured). Everything
downstream of the sort — prefix sums, greedy fixed point, unsorts — is
linear. This module removes the sort:

1. **Claim cascade** (``build_pair_plan`` / ``build_key_plan``): each
   distinct segment key claims a private bucket in a power-of-two table
   of ``T = 2^bits`` slots. Per round (3 rounds, independent
   multiplicative hashes) every unsettled key scatter-mins its
   coordinates into its hashed bucket; a key *settles* in the first
   round where it reads its own coordinates back (it won the claim).
   The effective bucket id ``round·T + bucket`` is therefore injective
   over distinct keys — two keys can share a bucket only across
   different rounds. Keys still unsettled after 3 rounds raise the
   plan's ``overflow`` flag: the caller falls back to the sorted
   reference via ``lax.cond`` (graceful fallback, never wrong answers)
   and the count feeds the ``sortfree.bucket_overflow`` counter.

2. **Scatter ranks** (``scatter_ranks``): arrival rank within bucket in
   ORIGINAL batch order, without sorting — a ``lax.scan`` over fixed-size
   chunks carrying a ``[num_buckets]`` running count: each chunk reads
   its pre-chunk counts (gather), adds its within-chunk triangular
   equality counts (dense [m, m] compare, VPU-friendly), and scatter-adds
   its histogram into the carry. O(n·m) dense work and O(num_buckets)
   memory replace the n·log n sort.

3. **Counting order** (``counting_order``): the stable counting-sort
   permutation ``offsets[bucket] + rank`` — buckets made contiguous,
   batch arrival order preserved inside each bucket. The general path
   feeds this permutation into its UNCHANGED segment machinery
   (prefix sums / ``greedy_admit`` / unsorts), so bit-parity with the
   sorted reference needs no second implementation of the admission
   math: within a segment the element order is identical (stability),
   and across segments the cumsum-minus-leader-base prefix form is
   exact for the integer-valued float32 amounts both paths already
   require (the documented < 2^24 envelope — see
   ``flow_check_scalar``'s parity contract), so segment ORDER cannot
   change any admitted bit.

Env knobs: ``SENTINEL_SORTFREE`` (runtime routing — see runtime.py),
``SENTINEL_SORTFREE_BITS`` (claim-table size override, mainly for the
collision-forcing tests), ``SENTINEL_SORTFREE_CHUNK`` (scan chunk).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# Claim rounds: 3 independent hashes drive the per-key settle probability
# low enough that overflow is a counter-visible rarity at the default
# table load (~n distinct keys into 2n buckets), while the lax.cond
# fallback keeps correctness unconditional.
ROUNDS = 3

# Odd 32-bit mixing constants (Knuth / xxhash family), one (A, B) pair
# per round so a pair of keys colliding in round r is independently
# re-scattered in round r+1.
_HASH_A = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)
_HASH_B = (0x27D4EB2F, 0x165667B1, 0x7FEB352D)
_HASH_MIX = 0x2C1B3C6D

_I32_MAX = 2 ** 31 - 1


def table_bits(n: int) -> int:
    """Claim-table size exponent for an n-element batch (STATIC, read at
    trace time). Default sizes the table to ~2 buckets per element
    (distinct keys <= elements), clamped to [6, 18];
    ``SENTINEL_SORTFREE_BITS`` overrides — the collision-forcing parity
    tests pin it tiny to exercise the overflow fallback."""
    raw = os.environ.get("SENTINEL_SORTFREE_BITS", "")
    if raw:
        try:
            return max(1, min(int(raw), 18))
        except ValueError:
            pass
    bits = 1
    while (1 << bits) < 2 * max(n, 2):
        bits += 1
    return max(6, min(bits, 18))


def chunk_size() -> int:
    """``lax.scan`` chunk for :func:`scatter_ranks` (STATIC). Each scan
    step does one [m, m] dense compare; ``SENTINEL_SORTFREE_CHUNK``
    overrides, clamped to [16, 4096]."""
    raw = os.environ.get("SENTINEL_SORTFREE_CHUNK", "")
    try:
        m = int(raw) if raw else 256
    except ValueError:
        return 256
    return max(16, min(m, 4096))


class BucketPlan(NamedTuple):
    """Output of the claim cascade.

    ``bucket[i]`` is element i's effective bucket in ``[0, num_buckets)``
    — injective over distinct keys when ``overflow`` is False (settled
    keys only; unsettled elements hold bucket 0, but then ``overflow``
    is True and the caller must take the sorted fallback branch).
    The LAST bucket (``num_buckets - 1``) is reserved for the caller's
    sentinel key so the padding segment never contests the hash table.
    """

    bucket: jnp.ndarray          # int32[n]
    overflow: jnp.ndarray        # bool scalar
    overflow_count: jnp.ndarray  # int32 scalar — unsettled elements
    num_buckets: int             # STATIC: ROUNDS * 2^bits + 1


def _bucket_of(mix: jnp.ndarray, bits: int) -> jnp.ndarray:
    h = (mix ^ (mix >> jnp.uint32(15))) * jnp.uint32(_HASH_MIX)
    return (h >> jnp.uint32(32 - bits)).astype(jnp.int32)


def _cascade(n: int, bits: int, sentinel_mask: jnp.ndarray,
             round_bucket, claim_and_win) -> BucketPlan:
    """Shared cascade body: per round, unsettled elements hash
    (``round_bucket``), claim (``claim_and_win`` → winner mask), and
    settled winners freeze ``r * T + bucket_r``."""
    T = 1 << bits
    settled = sentinel_mask
    bucket = jnp.where(sentinel_mask, jnp.int32(ROUNDS * T), jnp.int32(0))
    for r in range(ROUNDS):
        b_r = round_bucket(r)
        # settled elements sit out: their claim target T is out of range
        # for the [T] claim arrays (mode="drop")
        tgt = jnp.where(settled, jnp.int32(T), b_r)
        win = (~settled) & claim_and_win(tgt, b_r)
        bucket = jnp.where(win, r * T + b_r, bucket)
        settled = settled | win
    overflow_count = jnp.sum((~settled).astype(jnp.int32))
    return BucketPlan(bucket=bucket, overflow=overflow_count > 0,
                      overflow_count=overflow_count,
                      num_buckets=ROUNDS * T + 1)


def build_pair_plan(k1: jnp.ndarray, k2: jnp.ndarray,
                    sentinel_mask: jnp.ndarray, bits: int) -> BucketPlan:
    """Claim cascade over PAIR keys (k1, k2) — the general path's
    (rule, stat-row) segment key, which need not fit a single int32
    (this path is exactly the one the runtime routes to when the fast
    path's composite key does NOT fit).

    Two independent scatter-mins claim each bucket; an element wins iff
    it reads BOTH its coordinates back. Sound: the winning pair per
    bucket is (min k1, min k2) over the bucket's contenders, and only
    one distinct key can equal that pair — so at most one KEY settles
    per (round, bucket), preserving injectivity. (The combined minima
    may belong to no contender at all; then nobody wins the bucket this
    round and its contenders rehash — progress is probabilistic,
    correctness is not.)
    """
    T = 1 << bits
    u1 = k1.astype(jnp.uint32)
    u2 = k2.astype(jnp.uint32)

    def round_bucket(r: int) -> jnp.ndarray:
        return _bucket_of(u1 * jnp.uint32(_HASH_A[r])
                          + u2 * jnp.uint32(_HASH_B[r]), bits)

    def claim_and_win(tgt: jnp.ndarray, b_r: jnp.ndarray) -> jnp.ndarray:
        claim1 = jnp.full((T,), _I32_MAX, jnp.int32).at[tgt].min(
            k1, mode="drop")
        claim2 = jnp.full((T,), _I32_MAX, jnp.int32).at[tgt].min(
            k2, mode="drop")
        return (claim1[b_r] == k1) & (claim2[b_r] == k2)

    return _cascade(k1.shape[0], bits, sentinel_mask, round_bucket,
                    claim_and_win)


def build_key_plan(key: jnp.ndarray, sentinel_mask: jnp.ndarray,
                   bits: int) -> BucketPlan:
    """Claim cascade over single int32 keys (the fast path's composite
    key, host-verified < 2^31). One scatter-min per round: an element
    wins its bucket iff it reads its own key back."""
    T = 1 << bits
    u = key.astype(jnp.uint32)

    def round_bucket(r: int) -> jnp.ndarray:
        return _bucket_of(u * jnp.uint32(_HASH_A[r]) + jnp.uint32(_HASH_B[r]),
                          bits)

    def claim_and_win(tgt: jnp.ndarray, b_r: jnp.ndarray) -> jnp.ndarray:
        claim = jnp.full((T,), _I32_MAX, jnp.int32).at[tgt].min(
            key, mode="drop")
        return claim[b_r] == key

    return _cascade(key.shape[0], bits, sentinel_mask, round_bucket,
                    claim_and_win)


def bucket_histogram(bucket: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Per-bucket element counts → int32[num_buckets]; out-of-range
    buckets (>= num_buckets, e.g. padding) are dropped."""
    return jnp.zeros((num_buckets,), jnp.int32).at[bucket].add(
        1, mode="drop")


def scatter_ranks(bucket: jnp.ndarray, num_buckets: int,
                  chunk: Optional[int] = None) -> jnp.ndarray:
    """Arrival rank within bucket, ORIGINAL order → int32[n].

    ``rank[i]`` = number of earlier elements (batch order) in i's bucket
    — :func:`ops.segments.ranks_by_key` without the sort, valid whenever
    the bucket assignment is injective over keys (claim cascade, or an
    identity mapping for small key spaces). A ``lax.scan`` over chunks
    of ``m`` carries the ``[num_buckets]`` running counts; each chunk's
    within-chunk ranks come from one dense [m, m] triangular equality
    compare.
    """
    n = bucket.shape[0]
    m = min(chunk if chunk is not None else chunk_size(), max(n, 1))
    c = -(-n // m)
    pad = c * m - n
    b_p = bucket
    if pad:
        # padding targets num_buckets: dropped by the carry scatter, and
        # the padded lanes' outputs are sliced away below
        b_p = jnp.concatenate(
            [bucket, jnp.full((pad,), num_buckets, jnp.int32)])
    chunks = b_p.reshape(c, m)
    tri = jnp.tril(jnp.ones((m, m), jnp.bool_), k=-1)

    def step(state, b_chunk):
        pre = state[b_chunk]            # OOB padding gathers clamp; sliced
        eq = b_chunk[:, None] == b_chunk[None, :]
        within = jnp.sum((eq & tri).astype(jnp.int32), axis=1)
        return state.at[b_chunk].add(1, mode="drop"), pre + within

    _, ranks = lax.scan(step, jnp.zeros((num_buckets,), jnp.int32), chunks)
    return ranks.reshape(-1)[:n]


def counting_order(bucket: jnp.ndarray, num_buckets: int,
                   ranks: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Stable counting-sort permutation by bucket → int32[n], drop-in for
    ``seg.sort_by_keys`` when buckets are injective over segment keys:
    groups are contiguous and batch arrival order is preserved inside
    each group, which is all the downstream segment machinery assumes
    (the cross-group order differs from the key-sorted reference, which
    cannot change any admitted bit — see the module docstring)."""
    n = bucket.shape[0]
    hist = bucket_histogram(bucket, num_buckets)
    offsets = jnp.cumsum(hist) - hist
    if ranks is None:
        ranks = scatter_ranks(bucket, num_buckets)
    pos = offsets[bucket] + ranks
    return jnp.zeros((n,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))


def ranks2d_ident(key2d: jnp.ndarray, num_keys: int) -> jnp.ndarray:
    """Sort-free :func:`ops.segments.ranks_per_slot` for SMALL key spaces
    (the scalar path: key = rule id in [0, num_keys)) — identity buckets,
    so no cascade, no collisions, no overflow. → int32[B, K]."""
    return jax.vmap(
        lambda col: scatter_ranks(col, num_keys))(key2d.T).T


def ranks2d_hashed(key2d: jnp.ndarray, sentinel_value: int,
                   bits: int):
    """Sort-free :func:`ops.segments.ranks_per_slot` for LARGE key spaces
    (the fast path's composite key) → (ranks int32[B, K], overflow_count
    int32 scalar).

    Slot columns carry disjoint key groups (the ranks_per_slot contract),
    so each column runs its own claim cascade; the shared cross-slot
    sentinel key is routed to the reserved bucket per column (its ranks
    are per-slot, matching the sorted per-slot reference — callers never
    consume sentinel ranks either way). On ``overflow_count > 0`` the
    ranks are NOT valid — the caller must ``lax.cond`` to the sorted
    reference."""
    def one(col):
        plan = build_key_plan(col, col == jnp.int32(sentinel_value), bits)
        return (scatter_ranks(plan.bucket, plan.num_buckets),
                plan.overflow_count)

    ranks, ovf = jax.vmap(one)(key2d.T)
    return ranks.T, jnp.sum(ovf)
