"""A/B: arrival-rank-within-key implementations (VERDICT r3 #1c).

The scalar admission path's only cross-pair computation is
``ranks_by_key`` (one stable argsort + scan + one unsort scatter —
~25 ms of the ~49 ms step at B=512k). The sort-free candidate is the
"binned / segment-scan" formulation for NF << B: stream the batch in
C-sized chunks under ``lax.scan``, carry per-key counts, and compute
within-chunk ranks with a strictly-lower-triangular one-hot matmul
(own-column extraction is a product with the one-hot; the carry lookup
stays a small [C] gather — counts exceed the bf16-exact integer range,
so an `oh @ counts` matvec would silently truncate):

    oh     = onehot(keys_chunk)            [C, NK]   bf16
    within = tril_ones @ oh                [C, NK]   f32 accum (exact ints)
    r_in   = rowsum(within * oh)           [C]
    base   = counts[keys_chunk]            [C]       gather
    counts += colsum(oh)

Plus an NK-free equality-matrix variant (``ranks_eqmat_scan``). Measured
as chained scans with one readback at bench shapes. Knobs: RANK_N,
RANK_NK, RANK_STEPS.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def ranks_onehot_scan(key, num_keys: int, chunk: int):
    """Sort-free ranks via chunked one-hot matmul scan (see module doc).
    ``key`` int32[n] in [0, num_keys); n % chunk == 0."""
    import jax
    import jax.numpy as jnp

    n = key.shape[0]
    nk = ((num_keys + 127) // 128) * 128
    k2 = key.reshape(n // chunk, chunk)
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.bfloat16), k=-1)
    iota = jnp.arange(nk, dtype=jnp.int32)

    def body(counts, kc):
        oh = (kc[:, None] == iota[None, :]).astype(jnp.bfloat16)
        within = jax.lax.dot(tril, oh,
                             preferred_element_type=jnp.float32)
        r_in = jnp.sum(within * oh.astype(jnp.float32),
                       axis=1).astype(jnp.int32)
        base = counts[kc]                      # small [C] gather — counts
        # exceed bf16-exact range, so no matvec trick here
        ranks_c = base + r_in
        counts = counts + jnp.sum(oh, axis=0,
                                  dtype=jnp.float32).astype(jnp.int32)
        return counts, ranks_c

    _, ranks = jax.lax.scan(body, jnp.zeros((nk,), jnp.int32), k2)
    return ranks.reshape(n)


def ranks_eqmat_scan(key, num_keys: int, chunk: int):
    """NK-free sort-free variant: within-chunk ranks from the [C, C]
    equality matrix (no one-hot, no matmul), carry via a per-chunk
    scatter. Trades the C x NK matmul for C^2 elementwise + a C-index
    scatter per chunk."""
    import jax.numpy as jnp
    from jax import lax

    n = key.shape[0]
    k2 = key.reshape(n // chunk, chunk)
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1)

    def body(counts, kc):
        eq = (kc[:, None] == kc[None, :]) & tril
        r_in = jnp.sum(eq, axis=1, dtype=jnp.int32)
        base = counts[kc]
        counts = counts.at[kc].add(1)
        return counts, base + r_in

    _, ranks = lax.scan(body, jnp.zeros((num_keys,), jnp.int32), k2)
    return ranks.reshape(n)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.ops.segments import ranks_by_key

    N = int(os.environ.get("RANK_N", str(1 << 19)))
    NK = int(os.environ.get("RANK_NK", "4097"))
    STEPS = int(os.environ.get("RANK_STEPS", "20"))
    rng = np.random.default_rng(0)
    # bench-shaped key mix: 25% over the first NK-1 keys, rest sentinel
    hot = rng.integers(0, NK - 1, N // 4)
    cold = np.full(N - N // 4, NK - 1)
    key0 = np.concatenate([hot, cold]).astype(np.int32)
    rng.shuffle(key0)
    key0 = jnp.asarray(key0)

    # correctness first — every chunk size that gets a timing row
    ref = np.asarray(ranks_by_key(key0))
    for chunk in (256, 512, 1024, 2048):
        got = np.asarray(ranks_onehot_scan(key0, NK, chunk))
        assert np.array_equal(ref, got), f"onehot chunk={chunk} wrong"
    print("correctness OK (all chunk sizes match argsort ranks)",
          file=sys.stderr)

    def bench(name, fn):
        # chained: feed ranks back into the key mix so the device must
        # execute every step; one readback before + after timing
        step = jax.jit(lambda k: (fn(k) + k) % NK)
        k = key0
        k = step(k)
        _ = np.asarray(k[:1])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            k = step(k)
        jax.block_until_ready(k)
        _ = np.asarray(k[:1])
        dt = (time.perf_counter() - t0) / STEPS * 1000
        print(json.dumps({"variant": name, "ms_per_call": round(dt, 2),
                          "n": N, "nk": NK}))

    for chunk in (1024, 2048, 4096):
        got = np.asarray(ranks_eqmat_scan(key0, NK, chunk))
        assert np.array_equal(ref, got), f"eqmat chunk={chunk} wrong"

    bench("argsort", ranks_by_key)
    for chunk in (256, 512, 1024, 2048):
        bench(f"onehot_c{chunk}",
              functools.partial(ranks_onehot_scan, num_keys=NK,
                                chunk=chunk))
    for chunk in (1024, 2048, 4096):
        bench(f"eqmat_c{chunk}",
              functools.partial(ranks_eqmat_scan, num_keys=NK,
                                chunk=chunk))


if __name__ == "__main__":
    main()
