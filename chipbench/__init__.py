"""chipbench — the benchmark of the served path on the chip.

Everything a cell needs is data (``configs/``, ``traffic/``, ``metrics/``)
plus builders, generators and readers found by name (``registry.py``).
This package imports neither JAX nor ``sentinel_tpu`` at import time: the
load-generator child process imports it and must never touch the chip.
"""

from pathlib import Path

ROOT = Path(__file__).resolve().parent          # chipbench/
CHECKOUT = ROOT.parent                          # where BENCHMARK.json lives
