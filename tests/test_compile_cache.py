"""Cold-start: the persistent XLA compilation cache makes the second
process's startup-to-first-verdict a disk hit (reference parity target:
``Env.java`` static init — agents start in milliseconds, so ours must at
least start warm across processes).

The cache is placed from outside: ``JAX_COMPILATION_CACHE_DIR`` where it
is set (the code then never touches ``jax_compilation_cache_dir``),
``<checkout>/.jax_cache`` on an accelerator where it is not, nothing on
the CPU where it is not; ``SENTINEL_COMPILE_CACHE=off`` disables."""

import json
import os
import subprocess
import sys

from sentinel_tpu.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import json, os, time
import jax
jax.config.update("jax_platforms", "cpu")
t0 = time.perf_counter()
import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
cfg = stpu.load_config(max_resources=256, max_flow_rules=16,
                       max_degrade_rules=16, max_authority_rules=16,
                       host_fast_path=False)
sph = stpu.Sentinel(config=cfg, clock=ManualClock(start_ms=10_000_000))
sph.load_flow_rules([stpu.FlowRule(resource="x", count=5.0)])
e = sph.entry("x"); e.exit()          # first verdict = first step compile
from sentinel_tpu.core.compile_cache import active_cache_dir
print(json.dumps({"secs": time.perf_counter() - t0,
                  "cache": active_cache_dir(),
                  "jax_dir": jax.config.jax_compilation_cache_dir}))
# tear the engine down BEFORE interpreter exit: without this the
# daemon executors race jax's atexit teardown and the warm child
# occasionally dies with SIGSEGV after printing its (valid) result
sph.close()
"""


def _run(**env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "SENTINEL_COMPILE_CACHE")}
    env.update(PYTHONPATH=REPO, **env_over)
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_starts_from_cache(tmp_path):
    cache = tmp_path / "xla-cache"
    cold = _run(JAX_COMPILATION_CACHE_DIR=str(cache))
    # JAX read the variable itself; we report what JAX has
    assert cold["cache"] == cold["jax_dir"] == str(cache)
    entries = set(os.listdir(cache))
    assert entries, "first process wrote no cache entries"

    warm = _run(JAX_COMPILATION_CACHE_DIR=str(cache))
    entries2 = set(os.listdir(cache))
    # identical geometry + workload ⇒ pure cache hits: no new entries,
    # and startup-to-first-verdict beats the cold process
    assert entries2 == entries, entries2 - entries
    assert warm["secs"] < cold["secs"], (warm, cold)


def test_cpu_without_the_variable_has_no_cache():
    out = _run()
    assert out["cache"] is None and out["jax_dir"] is None


def test_cache_can_be_disabled(tmp_path):
    out = _run(SENTINEL_COMPILE_CACHE="off",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    assert out["cache"] is None


def test_accelerator_default_is_inside_the_checkout():
    assert compile_cache.checkout_cache_dir() == os.path.join(
        REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_variable_set_means_config_untouched(monkeypatch, tmp_path):
    """With the JAX variable set, no code path may call
    ``jax.config.update("jax_compilation_cache_dir", ...)``."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_enabled", False)
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        seen.append(name)
        if name != "jax_compilation_cache_dir":
            real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    before = (jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        compile_cache.enable_persistent_cache()
        assert "jax_compilation_cache_dir" not in seen
        assert set(seen) == {"jax_persistent_cache_min_compile_time_secs",
                             "jax_persistent_cache_min_entry_size_bytes"}
    finally:
        real_update("jax_persistent_cache_min_compile_time_secs", before[0])
        real_update("jax_persistent_cache_min_entry_size_bytes", before[1])
