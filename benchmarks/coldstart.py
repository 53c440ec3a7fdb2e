"""Cold/warm start-to-first-verdict measurement.

Spawns a FRESH interpreter (the number that matters is per-process) and
times phases inside it: imports, backend init, engine construction,
first entry+exit. Run twice to see cold (empty cache) vs warm.

Usage: python benchmarks/coldstart.py            # one child run, phase table
       JAX_COMPILATION_CACHE_DIR=dir ...         # place the cache
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

CHILD = r"""
import json, time
t0 = time.perf_counter()
import jax
import sentinel_tpu as stpu
t_import = time.perf_counter()
jax.devices()                         # backend init
t_backend = time.perf_counter()
sph = stpu.Sentinel(stpu.load_config(
    app_name="coldstart", host_fast_path=False))
sph.load_flow_rules([stpu.FlowRule(resource="hello", count=100.0)])
t_engine = time.perf_counter()
e = sph.entry("hello")
e.exit()
t_first = time.perf_counter()
print(json.dumps({
    "imports_s": round(t_import - t0, 2),
    "backend_s": round(t_backend - t_import, 2),
    "engine_s": round(t_engine - t_backend, 2),
    "first_entry_exit_s": round(t_first - t_engine, 2),
    "total_s": round(t_first - t0, 2),
}))
"""


def main() -> None:
    env = dict(os.environ)
    repo = str(Path(__file__).resolve().parent.parent)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    sys.stderr.write(out.stderr[-2000:])
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise SystemExit(
            f"coldstart child failed (rc={out.returncode}); stderr tail "
            f"above")
    print(lines[-1])


if __name__ == "__main__":
    main()
