"""Persistent XLA compilation cache — the cold-start story.

The reference agent is usable at the first ``SphU.entry`` (static init,
``Env.java`` — milliseconds). A JAX engine instead pays an XLA compile of
the fused decision step per (geometry, variant) per process. This module
turns that into a once-per-geometry cost per cache directory: every
``Sentinel`` construction enables JAX's persistent compilation cache
(content-addressed by HLO, so identical geometry + jaxlib + flags ⇒ disk
hit), making every process after the first start in warm time. Ops
guidance lives in ``docs/OPERATIONS.md`` ("Cold start").

Where the cache lives — one rule:

* ``JAX_COMPILATION_CACHE_DIR`` set → JAX reads the variable itself; this
  module never touches ``jax_compilation_cache_dir`` and only lowers the
  two ``jax_persistent_cache_min_*`` thresholds so every step program is
  cached. Works on every backend (the CPU opt-in).
* unset, accelerator backend → ``<checkout>/.jax_cache`` (a fixed path
  derived from this file: the directory is part of the cache key, so a
  path that moves never hits).
* unset, CPU backend → no cache: this jaxlib's CPU AOT loader logs a
  machine-feature-mismatch warning for every entry it loads
  (``cpu_aot_loader.cc`` — the compile records ``+prefer-no-scatter``-
  style pseudo-features host detection lacks), ~44 stderr lines per warm
  start, which is not an acceptable default for a serving process's logs.

``SENTINEL_COMPILE_CACHE=off`` (or ``0``) disables the cache outright.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Mapping, Optional

JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_enabled = False


def checkout_cache_dir() -> str:
    """The accelerator default: ``.jax_cache`` at the root of the checkout
    this package was imported from (git-ignored)."""
    return str(Path(__file__).resolve().parents[2] / ".jax_cache")


def cache_disabled() -> bool:
    return os.environ.get("SENTINEL_COMPILE_CACHE", "").lower() in (
        "0", "off", "disable", "disabled")


def enable_persistent_cache() -> Optional[str]:
    """Idempotently enable JAX's persistent compilation cache → the active
    cache dir (None when disabled or on the CPU default).

    Safe to call before or after backend initialization (the cache is
    consulted per compilation, not at client creation)."""
    global _enabled
    if cache_disabled():
        return None
    import jax
    with _lock:
        if not _enabled:
            if not os.environ.get(JAX_CACHE_ENV):
                if jax.default_backend() == "cpu":
                    return None
                cache_dir = checkout_cache_dir()
                os.makedirs(cache_dir, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", cache_dir)
            # cache everything: the engine's step compiles are the cost we
            # exist to amortize, and even "fast" (>0.1 s) entries add up
            # across the variant set
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            _enabled = True
    return active_cache_dir()


def active_cache_dir() -> Optional[str]:
    """The directory this process's compiles are cached in (as JAX sees
    it), or None while :func:`enable_persistent_cache` has not enabled
    one."""
    if not _enabled:
        return None
    import jax
    return jax.config.jax_compilation_cache_dir


def program_key(kind: str, step_id: int, geometry, statics: Mapping,
                columns=()) -> tuple:
    """Hashable identity of one compiled program variant for first-dispatch
    bookkeeping (``Sentinel._fetched_programs`` / ``compile_cache.hit`` /
    ``.miss`` counters).

    ``kind`` names the program family (``"decide"``, ``"decide_sd"``);
    ``step_id`` is ``id()`` of the jitted callable, so rebuilt jits
    (rule reload, geometry change) key fresh; ``geometry`` is the padded
    batch-shape tuple; ``statics`` the static-arg flags the
    variant was specialized on; ``columns`` which of the batch's optional
    columns are present (``None`` or an array is part of the pytree
    structure jit specializes on: two batches that differ only there are
    two programs)."""
    return (kind, int(step_id), tuple(geometry),
            tuple(sorted(statics.items())), tuple(columns))
