"""Depth-k dispatch pipelining over the runtime's nowait tier.

The synchronous serving loop — ``entry_batch_nowait(...).result()`` per
step — pays the full host dispatch cost on every batch (the floor on a
host-attached chip: not measured): the host prepares batch N, dispatches
it, then idles until N's verdicts materialize before touching N+1.
:class:`DispatchPipeline` keeps up to ``depth`` batches in flight:
``submit`` dispatches batch N+1 while N still runs on device and
settles N-k only when the window is full, so the host's prep/dispatch
cost overlaps device execution instead of adding to it.

Ordering semantics are UNCHANGED from the sequential loop: the runtime
advances engine state at dispatch time under its own lock (submission
order == state order), and the pipeline settles handles strictly in
submission order — ``PipelinedVerdicts.result()`` for batch N first
settles every older in-flight batch, so deferred host bookkeeping
(blocked-pin release, block log, breaker diffs) also lands in dispatch
order. ``tests/test_dispatch_pipeline.py`` pins
``pipelined(depth=k) == sequential`` bit-parity.

Self-telemetry (obs/): ``pipeline.enqueue`` / ``pipeline.settle`` spans
on sampled batches, ``pipeline.depth`` (sum of in-flight counts at each
enqueue — divide by enqueues for the achieved average depth),
``pipeline.stall`` (submits that had to settle the oldest batch first)
and ``pipeline.meshed_dispatch`` (submits whose backing runtime is
row-sharded over a mesh) counters. Knob: ``SENTINEL_PIPELINE_DEPTH``
(default 2).
"""

from __future__ import annotations

import collections
import logging
import threading
from typing import Optional

from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.runtime import (   # noqa: F401 - re-exported knob
    PIPELINE_DEPTH_ENV, PendingVerdicts, Sentinel, pipeline_depth,
)

_MISSING = object()
_log = logging.getLogger("sentinel_tpu.serving")


class PipelinedVerdicts:
    """Ticket for one submitted batch: ``result()`` settles every older
    in-flight batch first (strict in-order settle), then memoizes this
    batch's :class:`~sentinel_tpu.engine.pipeline.Verdicts`. Safe to call
    out of submission order and more than once. ``rows`` is the handle's
    (:class:`~sentinel_tpu.runtime.PendingVerdicts`): for a batch of names
    the row each event was admitted on, there from the submit on — what
    the caller hands ``exit_batch`` for the entries that pass."""

    __slots__ = ("_pipe", "_seq", "_done", "_res", "rows")

    def __init__(self, pipe: "DispatchPipeline", seq: int, rows=None):
        self._pipe = pipe
        self._seq = seq
        self._done = False
        self._res = None
        self.rows = rows

    @property
    def seq(self) -> int:
        return self._seq

    def result(self):
        if not self._done:
            self._res = self._pipe._settle_through(self._seq)
            self._done = True
            self._pipe = None
        return self._res


class DispatchPipeline:
    """Depth-k dispatch window over one :class:`Sentinel`.

    Typical serving loop (rows pre-interned once via
    ``Sentinel.intern_resources``)::

        pipe = DispatchPipeline(sentinel)          # depth from env, or pass
        tickets = collections.deque()
        for step_rows in traffic:
            tickets.append(pipe.submit(step_rows))
            if len(tickets) > pipe.depth:
                verdicts = tickets.popleft().result()
                ...
        pipe.flush()

    ``depth=1`` degenerates to the synchronous loop (every submit settles
    the previous batch). The pipeline serializes submits under its own
    lock; use one pipeline per dispatcher thread.
    """

    def __init__(self, sentinel: Sentinel, depth: Optional[int] = None,
                 on_settle=None):
        self._s = sentinel
        # row-sharded runtime underneath: each submit also lands a
        # pipeline.meshed_dispatch counter so the scrape can attribute
        # pipeline traffic to the mesh path without reading the runtime
        self._meshed = sentinel.mesh is not None
        if depth is None:
            # default depth: the engine's tuned-config resolution
            # (round 11 — SENTINEL_TUNED_CONFIG, env-unset knobs only)
            # falls back to the SENTINEL_PIPELINE_DEPTH env clamp
            tuned = getattr(sentinel, "_tuned", None) or {}
            depth = tuned.get(PIPELINE_DEPTH_ENV, pipeline_depth())
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        # (seq, PendingVerdicts) in submission order
        self._inflight: "collections.deque" = collections.deque()
        # seq → settled Verdicts awaiting its ticket's result()
        self._results: dict = {}
        self._next_seq = 0
        # on_settle(seq, verdicts): fired after EVERY settle — stall,
        # result() drain, or flush — so an overlay (the frontend ingest
        # batcher) learns a batch landed at the earliest possible moment,
        # whichever call settled it. Called with the pipeline lock held:
        # keep it quick, and never call back into this pipeline from it
        # (the frontend hands off via loop.call_soon_threadsafe).
        self._on_settle = on_settle

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, resources, **entry_kwargs) -> PipelinedVerdicts:
        """Dispatch one entry batch through
        :meth:`Sentinel.entry_batch_nowait` (all its kwargs pass
        through: origins, acquire, prioritized, args_list, ...).
        ``trace_id`` threads a caller-minted trace (the frontend's batch
        trace) through the pipeline AND the runtime dispatch, so the
        whole lifecycle records under one id."""
        n = len(resources)
        trace_id = entry_kwargs.get("trace_id", 0)
        return self._submit(
            lambda: self._s.entry_batch_nowait(resources, **entry_kwargs),
            n, trace_id=trace_id)

    def submit_raw(self, *args, **kwargs) -> PipelinedVerdicts:
        """Dispatch through :meth:`Sentinel.decide_raw_nowait` (the
        registry-free tier: pre-resolved rows/ids in, verdicts out)."""
        n = args[0].shape[0] if args else 0
        return self._submit(
            lambda: self._s.decide_raw_nowait(*args, **kwargs), n,
            trace_id=kwargs.get("trace_id", 0))

    def _submit(self, dispatch, n: int,
                trace_id: int = 0) -> PipelinedVerdicts:
        obs = self._s.obs
        obs_on = obs.enabled
        tr = (trace_id or obs.spans.maybe_trace()) if obs_on else 0
        t0 = obs.spans.now_ns() if tr else 0
        with self._lock:
            # make room BEFORE dispatching: settling the oldest here (a
            # stall) keeps at most `depth` batches in flight and bounds
            # how long deferred bookkeeping can wait
            while len(self._inflight) >= self.depth:
                if obs_on:
                    obs.counters.add(obs_keys.PIPE_STALL)
                self._settle_oldest_locked()
            handle = dispatch()
            seq = self._next_seq
            self._next_seq += 1
            # the batch's trace id rides the in-flight entry so the
            # settle span lands on the SAME chain as the enqueue span
            self._inflight.append((seq, handle, tr))
            if obs_on:
                obs.counters.add(obs_keys.PIPE_DEPTH, len(self._inflight))
                if self._meshed:
                    obs.counters.add(obs_keys.PIPE_MESHED)
        if tr:
            obs.spans.record(tr, "pipeline.enqueue", t0, obs.spans.now_ns(),
                             n=n, note=f"seq={seq}")
        # getattr: a caller may have wrapped entry_batch_nowait with a
        # handle of its own (the benchmark's tests do)
        return PipelinedVerdicts(self, seq, getattr(handle, "rows", None))

    # ------------------------------------------------------------------
    # settlement
    # ------------------------------------------------------------------

    def _settle_oldest_locked(self) -> None:
        seq, handle, tr = self._inflight.popleft()
        obs = self._s.obs
        with obs.phase("pipeline.settle", trace=tr, note=f"seq={seq}"):
            self._results[seq] = handle.result()
        if self._on_settle is not None:
            self._on_settle(seq, self._results[seq])

    def _settle_through(self, seq: int):
        with self._lock:
            res = self._results.pop(seq, _MISSING)
            if res is not _MISSING:
                return res
            while self._inflight and self._inflight[0][0] <= seq:
                self._settle_oldest_locked()
            res = self._results.pop(seq, _MISSING)
        if res is _MISSING:
            raise KeyError(f"unknown or already-consumed batch seq {seq}")
        return res

    @property
    def in_flight(self) -> int:
        with self._lock:
            return len(self._inflight)

    def flush(self) -> None:
        """Settle every in-flight batch (their verdicts stay claimable
        via the corresponding tickets)."""
        with self._lock:
            while self._inflight:
                self._settle_oldest_locked()

    def __enter__(self) -> "DispatchPipeline":
        return self

    def __exit__(self, *exc) -> bool:
        self.flush()
        return False


class CadenceScheduler:
    """The one clock of both ticks: one thread in place of the two
    per-service ticker daemons (``telemetry.start`` + ``tiering.start``).

    Every ``poll()`` ticks a service once ``now − last_tick_ms()`` has
    reached :data:`IDLE_FACTOR` × its interval, then drains both
    services' queued readbacks off the engine lock; the thread polls
    every ``max(0.02, min(intervals) / 2000)`` s. So the EFFECTIVE tick
    period is 1.5 × the configured interval plus up to one poll: at the
    defaults ≈ 0.35 s for the tiering tick (1.5 × 200 ms + ≤ 100 ms) and
    ≈ 1.52 s for the telemetry tick. Choosing another factor is a
    measurement question — it moves how often the telemetry tick's
    top-K pass and, where proactive demotion is on, the tiering tick's
    estimate of every row hold the device — so it is a constant here,
    not a knob.

    ``poll()`` is the thread body and is callable directly in tests;
    start/stop are idempotent and ``stop`` is registered with
    ``Sentinel.register_shutdown``.
    """

    #: a service is ticked once this many of its intervals have passed
    #: since its last tick
    IDLE_FACTOR = 1.5

    def __init__(self, sentinel: Sentinel,
                 telemetry_interval_sec: float = 1.0,
                 tiering_interval_sec: Optional[float] = None):
        from sentinel_tpu.tiering.manager import tier_tick_ms
        self._s = sentinel
        if tiering_interval_sec is None:
            tiering_interval_sec = tier_tick_ms() / 1000.0
        self._tel_ms = max(1, int(telemetry_interval_sec * 1000))
        self._tier_ms = max(1, int(tiering_interval_sec * 1000))
        # drain at twice the fastest cadence so readbacks land with at
        # most half an interval of extra latency
        self._poll_s = max(0.02, min(self._tel_ms, self._tier_ms) / 2000.0)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: polls the daemon survived by catching an exception (each one
        #: is logged; a healthy engine keeps this at 0)
        self.errors = 0
        reg = getattr(sentinel, "register_shutdown", None)
        if reg is not None:
            reg(self)

    def poll(self) -> int:
        """One scheduler pass: tick any service that is due, then drain
        both; → entries drained."""
        sn = self._s
        n = 0
        tel = sn.telemetry
        tier = sn.tiering
        if tel.enabled:
            now = sn.clock.now_ms()
            if now - tel.last_tick_ms() >= self._tel_ms * self.IDLE_FACTOR:
                tel.tick()
            n += tel.drain()
        if tier.enabled:
            now = sn.clock.now_ms()
            if (now - tier.last_tick_ms()
                    >= self._tier_ms * self.IDLE_FACTOR):
                tier.tick()
            n += tier.drain()
        # round 17: the overload controller rides the same daemon at its
        # own exact interval (pure host observe+decide, no device tick)
        ctl = getattr(sn, "control", None)
        if ctl is not None and ctl.enabled:
            now = sn.clock.now_ms()
            if now - ctl.last_tick_ms() >= ctl.interval_ms:
                ctl.tick()
            n += ctl.drain()
        return n

    def start(self) -> None:
        """Count both tick intervals from now and start the daemon
        (idempotent)."""
        if self._thread is not None:
            return
        self._s.telemetry.stamp_last_tick()
        self._s.tiering.stamp_last_tick()
        self._stop.clear()

        def loop():
            while not self._stop.wait(self._poll_s):
                try:
                    self.poll()
                except Exception:  # pragma: no cover — keep daemon alive
                    self.errors += 1
                    _log.exception("cadence poll failed")

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="sentinel-cadence")
        self._thread.start()

    def stop(self) -> None:
        """Join the daemon (idempotent; the services' own registered
        stops handle their final drains)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
