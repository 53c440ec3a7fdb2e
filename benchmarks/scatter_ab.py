"""Scatter-add A/B sweep: XLA native scatter vs the Pallas one-hot-MXU
kernel (ops/pallas_kernels.py) across counter-table sizes.

Run on the real TPU: ``python benchmarks/scatter_ab.py``. One JSON line per
(backend, K, N) cell plus a winner summary.

The shapes bracket the real tables: K=4k ≈ hot-param key table /
cluster flow rows; K=64k-1M ≈ the main resource table (where the per-tile
full-stream pass makes the one-hot formulation O(K/tile · N) vs XLA's
O(N) serialized scatter).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

SHAPES = [
    (1 << 10, 1 << 14),     # 1k-row table (small cluster tables)
    (1 << 12, 1 << 16),     # 4k rows: param-key / cluster-flow scale
    (1 << 16, 1 << 16),     # 64k rows
    (1 << 20, 1 << 16),     # 1M rows: the main resource table scale
]


def run(backend: str, k: int, n: int) -> float:
    env = {**os.environ, "BENCH_SCATTER": backend,
           "BENCH_SCATTER_K": str(k), "BENCH_SCATTER_N": str(n),
           "BENCH_STEPS": "30"}
    out = subprocess.run(
        [sys.executable, str(HERE.parent / "bench.py")], env=env,
        capture_output=True, text=True, timeout=900, check=True)
    return float(json.loads(out.stdout.strip().splitlines()[-1])["value"])


def main() -> None:
    rows = []
    for k, n in SHAPES:
        cell = {"K": k, "N": n}
        for backend in ("xla", "pallas"):
            cell[backend] = run(backend, k, n)
        cell["winner"] = max(("xla", "pallas"), key=lambda b: cell[b])
        cell["ratio_pallas_over_xla"] = round(cell["pallas"] / cell["xla"], 3)
        rows.append(cell)
        print(json.dumps(cell), flush=True)
    print(json.dumps({"summary": {
        f"K{c['K']}": c["winner"] for c in rows}}))


if __name__ == "__main__":
    main()
