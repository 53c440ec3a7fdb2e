"""Pallas TPU kernel for the streaming counter update — A/B'd and RETIRED
from the hot path (kept as the reference MXU formulation).

The engine's per-batch counter update is a high-fan-in scatter-add: N events
→ ``counters[K, E]``. The TPU-native alternative formulated here is one-hot
matmul accumulation on the MXU::

    counters[K, E] += onehot(keys)[N, K]ᵀ · (onehot(events)[N, E] · amounts)

tiled over (K, N) grid cells with VMEM one-hots and ``jnp.dot``
accumulation — no atomics, deterministic (SURVEY §2.8.1 → §7 Phase 1).

**Measured outcome (round 3, one v5 lite chip; the figures themselves now
live in git history): XLA's native scatter won at every product
shape**, by more the larger K, because each K-tile of the
one-hot kernel must scan the whole event stream (O(K/tile · N) MACs vs
XLA's O(N)). :func:`scatter_add` therefore dispatches to XLA everywhere;
the kernel stays as a tested reference implementation and the benchmark
harness (``BENCH_SCATTER={xla,pallas}`` on ``bench.py``,
``benchmarks/scatter_ab.py`` for the sweep) re-runs the comparison on any
future hardware where the balance may shift.

On CPU (tests, virtual mesh) the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# lane width: last-dim tiles are 128 on TPU
_LANE = 128


def scatter_add_xla(counters: jnp.ndarray, keys: jnp.ndarray,
                    events: jnp.ndarray,
                    amounts: jnp.ndarray) -> jnp.ndarray:
    """Reference semantics: ``counters[K, E] += Σ`` over the event stream.
    Out-of-range keys (>= K, e.g. padding) are dropped."""
    return counters.at[keys, events].add(amounts, mode="drop")


def _tile_kernel(keys_ref, events_ref, amounts_ref, counters_ref, out_ref,
                 *, tile_k: int, tile_n: int, num_events: int):
    """Grid cell (tk, tn): counter rows [tk·tile_k, (tk+1)·tile_k) ×
    stream chunk [tn·tile_n, (tn+1)·tile_n).

    one_hot_k: [tile_n, tile_k]  — event i hits local key col (keys[i]-base)
    one_hot_e: [tile_n, E]       — event i's event lane, scaled by amounts
    partial = one_hot_kᵀ @ one_hot_e  → [tile_k, E] on the MXU, accumulated
    across tn steps (tn is the innermost grid dim, so out_ref persists for
    a fixed k-tile; tn==0 seeds it from the current counters).

    The stream operands arrive as [tile_n, 1] blocks: Mosaic (the TPU
    Pallas backend) has no general 1D→2D vector reshape, so the host
    wrapper feeds column vectors and everything here broadcasts [tile_n, 1]
    against [tile_n, tile_k] (lane broadcast, no reshape ops). The N axis
    is tiled because a full-stream one-hot would blow scoped VMEM.
    """
    tk = pl.program_id(0)
    tn = pl.program_id(1)
    base = tk * tile_k
    keys = keys_ref[:, :]                    # [tile_n, 1]
    events = events_ref[:, :]
    amounts = amounts_ref[:, :]

    local = keys - base
    in_tile = (local >= 0) & (local < tile_k)
    local = jnp.where(in_tile, local, 0)

    col_k = jax.lax.broadcasted_iota(jnp.int32, (tile_n, tile_k), 1)
    one_hot_k = (col_k == local) & in_tile   # [tile_n,1] broadcasts lanes

    col_e = jax.lax.broadcasted_iota(jnp.int32, (tile_n, num_events), 1)
    one_hot_e = jnp.where(col_e == events, amounts, 0)

    partial = jnp.dot(one_hot_k.astype(jnp.float32).T,
                      one_hot_e.astype(jnp.float32),
                      preferred_element_type=jnp.float32)

    @pl.when(tn == 0)
    def _seed():
        out_ref[:, :] = counters_ref[:, :]

    out_ref[:, :] += partial.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_add_pallas(counters: jnp.ndarray, keys: jnp.ndarray,
                       events: jnp.ndarray, amounts: jnp.ndarray,
                       *, interpret: bool = False) -> jnp.ndarray:
    """MXU scatter-add: ``counters[K, E] += stream``. K must be a multiple
    of the tile (pad the table, harmless); out-of-range keys are dropped
    because no tile claims them."""
    orig_k, e = counters.shape
    orig_n = keys.shape[0]
    tile_k = min(orig_k, 512)
    tile_n = min(max(orig_n, 8), 2048)
    k = ((orig_k + tile_k - 1) // tile_k) * tile_k
    if k != orig_k:
        # pad the table to a tile multiple and route any out-of-range key
        # (padding convention: key >= orig_k) past the padded rows too
        counters = jnp.pad(counters, ((0, k - orig_k), (0, 0)))
        keys = jnp.where(keys < orig_k, keys, k)
    n = ((orig_n + tile_n - 1) // tile_n) * tile_n
    if n != orig_n:
        # padded stream slots target key k (no tile owns it) with amount 0
        pad_n = n - orig_n
        keys = jnp.concatenate([keys, jnp.full((pad_n,), k, keys.dtype)])
        events = jnp.concatenate([events, jnp.zeros((pad_n,), events.dtype)])
        amounts = jnp.concatenate([amounts,
                                   jnp.zeros((pad_n,), amounts.dtype)])
    grid = (k // tile_k, n // tile_n)        # tn innermost: accumulation

    # column-vector stream operands (see _tile_kernel: Mosaic needs 2D)
    keys2 = keys.reshape(-1, 1)
    events2 = events.reshape(-1, 1)
    amounts2 = amounts.reshape(-1, 1)

    kernel = functools.partial(_tile_kernel, tile_k=tile_k, tile_n=tile_n,
                               num_events=e)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, 1), lambda tk, tn: (tn, 0)),
            pl.BlockSpec((tile_n, 1), lambda tk, tn: (tn, 0)),
            pl.BlockSpec((tile_n, 1), lambda tk, tn: (tn, 0)),
            pl.BlockSpec((tile_k, e), lambda tk, tn: (tk, 0)),  # my tile
        ],
        out_specs=pl.BlockSpec((tile_k, e), lambda tk, tn: (tk, 0)),
        out_shape=jax.ShapeDtypeStruct(counters.shape, counters.dtype),
        interpret=interpret,
    )(keys2, events2, amounts2, counters)
    return out[:orig_k] if k != orig_k else out



def scatter_add(counters: jnp.ndarray, keys: jnp.ndarray,
                events: jnp.ndarray, amounts: jnp.ndarray) -> jnp.ndarray:
    """Backend dispatch — currently XLA scatter on every backend: the
    round-3 A/B on real TPU hardware measured XLA ahead at all product
    shapes, so the MXU kernel is not selected.
    Kept as the dispatch seam so a future measurement can flip it
    per-shape without touching callers."""
    return scatter_add_xla(counters, keys, events, amounts)
