"""Count-min sketch update A/B: scatter-max vs one-hot-matmul vs
segment-max formulations (tiering/sketch.py ``SKETCH_IMPLS``) across
sketch widths and batch sizes.

Round-15 methodology note (the ops/pallas_kernels.py precedent): the
conservative-update sketch is a scatter-shaped op on a [rows, 2^bits]
table, exactly the shape class the round-3 scatter A/B retired the
Pallas kernel for — so the tiering manager commits to a formulation
only on these measurements, not on intuition. Run on the real TPU:
``python benchmarks/sketch_ab.py``; one JSON line per (impl, bits,
batch) cell plus a winner summary. CPU numbers are recorded as such
and never extrapolated to TPU (PR 10 precedent).

The shapes bracket the real deployment: bits 12–16 (4k–64k counters
per hash row, the SENTINEL_SKETCH_BITS clamp midrange) × the serving
batch sizes the decide path actually dispatches (256–4096).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from sentinel_tpu.tiering import sketch as sk  # noqa: E402

BITS = (12, 14, 16)
BATCHES = (256, 1024, 4096)
ROWS = sk.DEFAULT_ROWS
N_KEYS = 1 << 20            # row-id universe the batches draw from
WARMUP = 3
STEPS = 30


def bench_impl(impl: str, bits: int, batch: int, seed: int = 7) -> dict:
    rng = np.random.default_rng(seed)
    counts = sk.init_sketch(ROWS, bits)
    # Zipf-ish skew so conservative update sees realistic collisions
    items = jax.numpy.asarray(
        (rng.zipf(1.3, size=batch) % N_KEYS).astype(np.int32))
    valid = jax.numpy.asarray(np.ones(batch, np.bool_))
    step = sk.jit_update(impl)
    for _ in range(WARMUP):
        counts, _ = step(counts, items, valid)
    jax.block_until_ready(counts)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        counts, _ = step(counts, items, valid)
    jax.block_until_ready(counts)
    dt = (time.perf_counter() - t0) / STEPS
    return {"impl": impl, "bits": bits, "batch": batch,
            "us_per_update": round(dt * 1e6, 2),
            "updates_per_sec": round(batch / dt, 1)}


def main() -> None:
    platform = jax.devices()[0].platform
    print(json.dumps({"platform": platform, "rows": ROWS,
                      "steps": STEPS}), flush=True)
    winners = {}
    for bits in BITS:
        for batch in BATCHES:
            cells = {}
            for impl in sk.SKETCH_IMPLS:
                cell = bench_impl(impl, bits, batch)
                cells[impl] = cell["us_per_update"]
                print(json.dumps(cell), flush=True)
            win = min(cells, key=cells.get)
            winners[f"bits{bits}/b{batch}"] = win
            print(json.dumps({"cell": f"bits{bits}/b{batch}",
                              "winner": win, "us": cells}), flush=True)
    print(json.dumps({"summary": winners,
                      "default": sk.DEFAULT_IMPL,
                      "platform": platform}))


if __name__ == "__main__":
    main()
