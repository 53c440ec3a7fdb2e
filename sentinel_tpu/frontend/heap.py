"""The front end's hold on the collector: the set-up heap out of every
collection while a batcher lives, and the gen-2 pauses counted.

A service builds its heap once — imports, compiled programs, rules, a
registry of a million names — and then serves. A full (generation 2)
collection walks every object the process tracks, with the interpreter
lock held: every thread of the served path, the event loop included,
stops for as long as that walk takes, and the walk grows with the
set-up, not with the traffic. So when the first live
:class:`~sentinel_tpu.frontend.batcher.AdaptiveBatcher` of the process
is built — before its first request, so that no request waits for it —
one full collection runs and :func:`gc.freeze` moves what survived into
the permanent generation; later collections walk only what serving
allocates. When the last live batcher closes,
:func:`gc.unfreeze` hands the heap back, so a process that builds and
closes many front ends (a test run) leaves no frozen garbage behind.
``gc.unfreeze`` thaws the whole permanent generation: what the process
froze before the first batcher served thaws with it (the interpreter's
immortal objects, which CPython 3.12's collector parks there, go back at
the next collection). Nothing is frozen while :mod:`gc` is disabled.

While a batcher lives, every gen-2 collection of the process ticks
``gc.gen2`` and adds its pause in microseconds to ``gc.gen2_us`` in the
counters of that batcher's engine (``obs.counters``), from one
:data:`gc.callbacks` hook installed once per process.
"""

from __future__ import annotations

import gc
import threading
import time

from sentinel_tpu.obs import counters as obs_keys

_lock = threading.Lock()
_live: list = []       # each live batcher's engine obs bundle
#: the distinct bundles the hook adds to — a tuple the hook reads without
#: a lock (it may run between any two bytecodes of any thread)
_counted: tuple = ()
_froze = False
_gen2_t0_ns = 0


def _on_collect(phase: str, info: dict) -> None:
    global _gen2_t0_ns
    if info["generation"] != 2:
        return
    if phase == "start":
        _gen2_t0_ns = time.perf_counter_ns()
        return
    us = (time.perf_counter_ns() - _gen2_t0_ns) // 1000
    for obs in _counted:
        if obs.enabled:
            obs.counters.add(obs_keys.GC_GEN2)
            obs.counters.add(obs_keys.GC_GEN2_US, us)


def serve(obs) -> None:
    """A batcher over the engine whose bundle is ``obs`` was built: the
    first live one of the process freezes the set-up heap after one full
    collection."""
    global _counted, _froze
    with _lock:
        if _on_collect not in gc.callbacks:
            gc.callbacks.append(_on_collect)
        _live.append(obs)
        _counted = tuple(dict.fromkeys(_live))
        if len(_live) == 1 and gc.isenabled():
            gc.collect()
            gc.freeze()
            _froze = True


def release(obs) -> None:
    """A batcher :func:`serve` was told of closed: the last live one
    thaws what was frozen."""
    global _counted, _froze
    with _lock:
        _live.remove(obs)
        _counted = tuple(dict.fromkeys(_live))
        if not _live and _froze:
            gc.unfreeze()
            _froze = False
