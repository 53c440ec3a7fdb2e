"""Runtime self-telemetry: spans, decision counters, latency histograms,
block-event log (docs/OBSERVABILITY.md).

The runtime owns one :class:`RuntimeObs` (``Sentinel.obs``) and guards
every instrumentation site with its single ``enabled`` flag, so the hot
path pays one attribute check when observability is off and stays within
2% of the uninstrumented headline when it is on (the ``obs_overhead``
gate in benchmarks/ci_gate.py). Everything here is host-side: no device
work, no background threads — :meth:`RuntimeObs.close` is a pure state
transition and is called by ``Sentinel.close()``.

Env knobs (read at ``RuntimeObs`` construction):

* ``SENTINEL_OBS_DISABLE`` — ``1``/``true`` turns all self-telemetry
  off (spans, counters, histograms, block events);
* ``SENTINEL_TRACE_SAMPLE`` — span/block-event sampling rate in
  ``[0, 1]`` (default 1.0 = every dispatch eligible; rendered as a
  deterministic stride, see obs/spans.py);
* ``SENTINEL_FLIGHT_DISABLE`` / ``SENTINEL_FLIGHT_WINDOW_MS`` /
  ``SENTINEL_FLIGHT_P99_MS`` / ``SENTINEL_FLIGHT_BLOCK_BURST`` — the
  SLO flight recorder (obs/flight.py);
* ``SENTINEL_TELEMETRY_K`` / ``SENTINEL_TELEMETRY_DISABLE`` — the
  device-resident hot-resource telemetry layer (obs/telemetry.py,
  ``Sentinel.telemetry``) — its tick runs on its own thread, not here:
  RuntimeObs itself stays thread-free.

Surfaces: the Prometheus collector (metrics/exporter.py), the ``obs``
transport command (transport/handlers.py), the dashboard
``/obs/telemetry.json`` endpoint + panel, and —  multihost — the
coordinator-side counter aggregation in multihost/obs_agg.py.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from sentinel_tpu.obs import counters as counters_mod
from sentinel_tpu.obs.counters import CounterSet
from sentinel_tpu.obs.eventlog import BlockEventLog
from sentinel_tpu.obs.flight import FlightRecorder
from sentinel_tpu.obs.hist import LogHistogram, bucket_bounds_ns
from sentinel_tpu.obs.spans import OPEN_PHASE, SpanRecorder

OBS_DISABLE_ENV = "SENTINEL_OBS_DISABLE"
TRACE_SAMPLE_ENV = "SENTINEL_TRACE_SAMPLE"



class _NullPhase:
    """The shared no-op context of a disabled bundle: what ``annotate``
    and ``phase`` return then. Sites that set ``n``/``note`` on an open
    phase write to it unharmed."""

    n = 0
    note = ""

    def start(self) -> "_NullPhase":
        return self

    def stop(self) -> None:
        pass

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullPhase()


def obs_disabled() -> bool:
    return os.environ.get(OBS_DISABLE_ENV, "").lower() in (
        "1", "true", "on", "yes")


def trace_sample_rate() -> float:
    raw = os.environ.get(TRACE_SAMPLE_ENV, "")
    if not raw:
        return 1.0
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return 1.0


def trace_annotation(name: str, n: int = 0):
    """A ``jax.profiler.TraceAnnotation`` context (names the enclosed
    dispatch in profiler/XProf timelines); ``n`` rides as a stat of the
    event (how many requests or events the call carried)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, n=n) if n else TraceAnnotation(name)


class _Phase:
    """One open :meth:`RuntimeObs.phase`: the same enter and exit write
    the interval to the profiler's trace and to the span recorder, so
    the two records of a phase can never disagree about its extent.
    ``n``, ``note`` and ``trace`` may be set while it is open (a route,
    a count or the chain it belongs to, known only at the end; trace 0
    records no span)."""

    __slots__ = ("spans", "name", "n", "trace", "note", "id", "parent",
                 "_obs", "_t0", "_prev", "_ann")

    def __init__(self, obs: "RuntimeObs", name: str, n: int, trace: int,
                 note: str) -> None:
        self._obs, self.spans = obs, obs.spans
        self.name, self.n, self.trace, self.note = name, n, trace, note
        self.id = self.parent = 0

    def start(self) -> "_Phase":
        """``with`` does this; a site whose phase ends inside a block it
        cannot wrap (the wait for a lock ends inside the lock) calls
        ``start``/``stop`` itself."""
        spans = self.spans
        prev = self._prev = OPEN_PHASE.get()
        if prev is not None and prev.spans is spans:
            self.parent = prev.id
            self.trace = self.trace or prev.trace
        elif not self.trace:
            self.trace = self._obs.request_trace()
        self.id = spans.next_span_id()
        OPEN_PHASE.set(self)
        # the profiler stamps an annotation when it is made: the span's
        # start is read first and its end last, so it holds the annotation
        self._t0 = spans.now_ns()
        self._ann = trace_annotation("sentinel_tpu." + self.name, self.n)
        self._ann.__enter__()
        return self

    def stop(self) -> None:
        self._ann.__exit__(None, None, None)
        spans = self.spans
        end = spans.now_ns()
        # not reset(token): the exit may run in another context than the
        # enter did (a phase handed across threads), where a token raises
        OPEN_PHASE.set(self._prev)
        spans.record(self.trace, self.name, self._t0, end, n=self.n,
                     note=self.note, span_id=self.id, parent=self.parent)

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class RuntimeObs:
    """The per-``Sentinel`` (and per-``ClusterEngine``) telemetry bundle.

    Attributes the runtime's instrumentation sites touch directly:
    ``enabled`` (the one hot-path guard), ``spans``, ``counters``,
    ``hist_entry`` (entry→verdict ns), ``hist_dispatch``
    (host ns from the dispatch returning to the caller's read of the
    verdicts — not device time), ``hist_request`` (per-REQUEST
    ingest→verdict ns through the serving front end — the end-to-end
    latency a service owner sees; recorded by frontend/batcher.py),
    ``block_events``."""

    def __init__(self, clock=None, enabled: Optional[bool] = None,
                 sample: Optional[float] = None) -> None:
        if sample is None:
            sample = trace_sample_rate()
        self.enabled = (not obs_disabled()) if enabled is None else enabled
        self.sample = sample
        self.clock = clock
        self.counters = CounterSet()
        # ring wrap is an operator signal, not a silent overwrite: each
        # span/link lost to a wrapped per-thread ring ticks the counter
        self.spans = SpanRecorder.for_clock(
            clock, sample=sample,
            on_wrap=lambda: self.counters.add(counters_mod.SPAN_RING_WRAP))
        self.hist_entry = LogHistogram()
        self.hist_dispatch = LogHistogram()
        self.hist_request = LogHistogram()
        self.block_events = BlockEventLog(sample=sample)
        # tail-based SLO capture (obs/flight.py); inert when the bundle
        # is disabled, individually removable via SENTINEL_FLIGHT_DISABLE
        self.flight = FlightRecorder(self)
        self._closed = False

    # ---- hot-path helpers -------------------------------------------

    def request_trace(self) -> int:
        """Trace id for one ingest request/flush: the flight recorder's
        always-on tier mints unconditionally (an SLO trigger must be able
        to pin ANY chain retroactively); otherwise the stride sampler
        decides. → 0 when telemetry is off."""
        if not self.enabled:
            return 0
        if self.flight.active:
            return self.spans.mint()
        return self.spans.maybe_trace()

    def annotate(self, name: str, n: int = 0):
        """Profiler trace annotation for a jitted step — a shared no-op
        context when disabled (one truthiness check, no allocation)."""
        if not self.enabled:
            return _NULL_CTX
        return trace_annotation(name, n)

    def phase(self, name: str, n: int = 0, trace: int = 0,
              note: str = ""):
        """One named interval of host work, from ONE call site to both
        sinks: the profiler annotation ``sentinel_tpu.<name>`` (an event
        in the same ``.xplane.pb``, on the same clock, as the device's
        operations — it names the idle gaps under it) and a span
        ``name`` with ``id``/``parent`` (the phase open in this task or
        thread when it began; ``asyncio.to_thread`` carries it into the
        worker). ``trace`` is the batch's trace id; without one the
        phase joins the enclosing phase's trace, or asks
        :meth:`request_trace` for a fresh one. Per engine call and per
        batch, never per request. The shared no-op context when
        disabled."""
        if not self.enabled:
            return _NULL_CTX
        return _Phase(self, name, n, trace, note)

    # ---- export surface ---------------------------------------------

    def payload(self, span_limit: int = 256,
                event_limit: int = 64) -> Dict:
        """The ``obs`` transport command / dashboard JSON body."""
        return {
            "enabled": self.enabled,
            "sample": self.sample,
            "counters": self.counters.snapshot(),
            "hist": {
                "entry_to_verdict": self.hist_entry.snapshot(),
                "dispatch_device": self.hist_dispatch.snapshot(),
                "request_to_verdict": self.hist_request.snapshot(),
                "bucket_bounds_ns": bucket_bounds_ns(),
            },
            "spans": self.spans.snapshot(limit=span_limit),
            "block_events": self.block_events.snapshot(limit=event_limit),
            "flight": {
                "active": self.flight.active,
                "window_ms": self.flight.window_ms,
                "pinned": self.flight.snapshot(),
            },
        }

    def flush(self) -> int:
        """Flush buffered block events + pinned flight chains to their
        writers (ridden by the metric timer's tick and by close)."""
        return self.block_events.flush() + self.flight.flush()

    def close(self) -> None:
        """Idempotent teardown: disable, drop span rings, flush + close
        the block-event and flight-recorder writers. Safe across
        repeated open/close."""
        if self._closed:
            return
        self._closed = True
        self.enabled = False
        self.flight.close()
        self.spans.close()
        self.block_events.close()


__all__ = [
    "OBS_DISABLE_ENV", "TRACE_SAMPLE_ENV", "RuntimeObs", "CounterSet",
    "LogHistogram", "SpanRecorder", "BlockEventLog", "FlightRecorder",
    "obs_disabled", "trace_sample_rate", "trace_annotation", "counters_mod",
]
