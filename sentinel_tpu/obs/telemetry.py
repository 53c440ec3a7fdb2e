"""Device-resident hot-resource telemetry: sharded top-K + per-second
timeline over the live window state (docs/OBSERVABILITY.md).

The reference Sentinel's flagship observability surface is per-resource
second-level metrics — every dashboard curve is built from a host-side
sweep over all StatisticNodes. At this repo's scale (1M resource rows
sharded across a mesh) that sweep is impossible; instead ONE jitted
telemetry tick runs over the live sharded ``WindowState`` without
touching the serving path:

* **(a) sharded top-K** — rolling pass+block load per row
  (:func:`sentinel_tpu.stats.window.rolling_load`, valid-mask-aware over
  the second window), the global ENTRY row masked out, then per-shard
  ``lax.top_k`` merged device-side across the mesh under the
  ``parallel/local_shard.py`` layout authority
  (:func:`~sentinel_tpu.parallel.local_shard.topk_layout`). The merge is
  EXACT, not approximate: row shards are disjoint, so every global
  winner is some shard's local winner; ``lax.top_k`` breaks ties by
  lowest index, and the gathered candidates preserve globally-increasing
  row order among equal loads, so the merged result is bit-identical to
  a host ``argsort(-load, kind="stable")`` (pinned by
  tests/test_telemetry.py on an 8-virtual-device mesh).
* **(b) per-second timeline** — the ENTRY row's completed-second bucket
  (pass/block/rt-sum/occupy lanes) appended into a small device ring
  buffer (:class:`TelemetryRing`) once per wall second.
* **(c) asynchronous host readback** — the tick only *dispatches* under
  the engine lock (fresh output buffers, donation-safe — the
  ``_jit_copy_column`` discipline); ``np.asarray`` happens later on the
  telemetry thread, overlapped with the ``DispatchPipeline``. There is
  never a blocking device sync on a dispatch path. When readback falls
  behind, new ticks are dropped and counted
  (``telemetry.readback_drop``), bounded by :data:`PENDING_MAX`.

Host surfaces: per-resource second lines for the top-K only, riding the
``metrics/writer.py`` rotation as ``<app>-metric`` (read back by
``metrics/searcher.py``); the ``topk`` transport command; the dashboard
``/obs/topk.json`` + hot-resources panel; a bounded-cardinality
Prometheus family (``sentinel_resource_qps`` — top-K labels only); and
the flight recorder's pinned hot-set snapshots (obs/flight.py
``hot_provider``).

Env knobs (construction time; kwargs override):
``SENTINEL_TELEMETRY_K`` — hot-set size, default 16, clamped to
[1, :data:`MAX_K`] and to the row count; ``SENTINEL_TELEMETRY_DISABLE``
— turn the telemetry layer off entirely (the obs master switch
``SENTINEL_OBS_DISABLE`` also turns it off).
"""

from __future__ import annotations

import collections
import functools
import os
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from sentinel_tpu.core.registry import ENTRY_NODE_ROW
from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.obs import resource_hist
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats import window
from sentinel_tpu.parallel.local_shard import MESH_AXIS, topk_layout


TELEMETRY_K_ENV = "SENTINEL_TELEMETRY_K"
TELEMETRY_DISABLE_ENV = "SENTINEL_TELEMETRY_DISABLE"

DEFAULT_K = 16
MAX_K = 128
RING_SLOTS = 64          # device timeline ring depth (~1 min at 1 Hz)
PENDING_MAX = 2          # un-drained device readbacks before drop-and-count
HOT_TIMELINE_CAP = 120   # host-side timeline tail kept for the command/SPA
FLIGHT_HOT_N = 8         # hot entries pinned into flight trigger records


def telemetry_disabled() -> bool:
    return os.environ.get(TELEMETRY_DISABLE_ENV, "").lower() in (
        "1", "true", "on", "yes")


def telemetry_k(default: int = DEFAULT_K) -> int:
    raw = os.environ.get(TELEMETRY_K_ENV, "")
    if not raw:
        return default
    try:
        return max(1, min(MAX_K, int(raw)))
    except ValueError:
        return default


class TelemetryRing(NamedTuple):
    """Device-resident per-second timeline ring (replicated — it is a few
    KB; only the write index moves)."""

    seconds: jnp.ndarray   # int32[S] minute-window idx written (NEVER=empty)
    lanes: jnp.ndarray     # int32[S, E] ENTRY-row completed-second lanes
    rt: jnp.ndarray        # float32[S] ENTRY-row completed-second rt sum
    cursor: jnp.ndarray    # int32[] total appends (slot = cursor % S)


def init_ring(slots: int = RING_SLOTS,
              num_events: int = ev.NUM_EVENTS) -> TelemetryRing:
    return TelemetryRing(
        seconds=jnp.full((slots,), window.NEVER, jnp.int32),
        lanes=jnp.zeros((slots, num_events), jnp.int32),
        rt=jnp.zeros((slots,), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
    )


def _sharded_topk(load: jnp.ndarray, k: int, mesh,
                  rows_per_shard: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact device-side top-K merge over disjoint row shards.

    Each shard ranks its own rows (``k_local = min(k, rows_per_shard)``
    candidates are enough: at most k global winners live in one shard),
    candidates gather across the mesh, and one final ``top_k`` ranks the
    ``n_shards × k_local`` survivors — O(n·k) gathered instead of the
    full row axis. Tie-break equals the host stable argsort: within a
    shard ``top_k`` prefers the lowest row, the gather concatenates
    shards in row order, so equal-load candidates stay in ascending
    global-row order and the final ``top_k`` keeps the lowest rows.
    """
    k_local = min(k, rows_per_shard)

    def body(l):
        vals, idx = lax.top_k(l, k_local)
        rows = idx.astype(jnp.int32) + lax.axis_index(MESH_AXIS) * rows_per_shard
        vals = lax.all_gather(vals, MESH_AXIS)   # [n, k_local]
        rows = lax.all_gather(rows, MESH_AXIS)
        mv, mi = lax.top_k(vals.reshape(-1), k)
        return mv, rows.reshape(-1)[mi]

    return shard_map(body, mesh=mesh, in_specs=P(MESH_AXIS),
                     out_specs=(P(), P()), check_vma=False)(load)


def telemetry_tick(second_spec: window.WindowSpec,
                   minute_spec: Optional[window.WindowSpec],
                   k: int, mesh, rows_per_shard: int,
                   second: window.WindowState,
                   minute: window.WindowState,
                   rt_hist,
                   ring: TelemetryRing,
                   now_idx_s: jnp.ndarray, sec_idx_m: jnp.ndarray,
                   append: jnp.ndarray):
    """ONE fused telemetry read over the live state (pure; jitted by
    :class:`HotTelemetry`). Returns fresh output buffers only — safe to
    read back asynchronously while later steps donate the state.

    ``rt_hist`` is the round-20 per-resource cumulative RT histogram
    table (``SentinelState.rt_hist``; None when the engine has no
    table). When present, the hot set's histogram rows gather alongside
    the rolling lanes (disjoint row shards — same GSPMD pattern as
    ``rolling_totals``) and the jitted quantile extraction
    (:func:`sentinel_tpu.obs.resource_hist.quantiles_from_counts`)
    rides the same dispatch; when None both extra outputs are
    zero-width, keeping every downstream tuple shape static."""
    rows_total = second.stamps.shape[0]
    load = window.rolling_load(second_spec, second, now_idx_s)
    # the global ENTRY aggregate row receives every inbound event — it is
    # the timeline source, never a "hot resource"
    load = jnp.where(
        jnp.arange(rows_total, dtype=jnp.int32) == ENTRY_NODE_ROW,
        jnp.int32(-1), load)
    if mesh is not None and mesh.shape[MESH_AXIS] > 1:
        vals, rows = _sharded_topk(load, k, mesh, rows_per_shard)
    else:
        vals, rows = lax.top_k(load, k)
        rows = rows.astype(jnp.int32)
    roll_lanes = window.rolling_totals(second_spec, second, now_idx_s)[rows]
    if minute_spec is not None:
        mc, mrt = window.bucket_snapshot(minute_spec, minute, sec_idx_m)
        sec_lanes, sec_rt = mc[rows], mrt[rows]
        entry_lanes, entry_rt = mc[ENTRY_NODE_ROW], mrt[ENTRY_NODE_ROW]
    else:   # minute ring disabled: hot set only, no per-second surfaces
        sec_lanes = jnp.zeros_like(roll_lanes)
        sec_rt = jnp.zeros((k,), jnp.float32)
        entry_lanes = jnp.zeros((ring.lanes.shape[1],), jnp.int32)
        entry_rt = jnp.zeros((), jnp.float32)
    if rt_hist is not None:
        hist_k = rt_hist[rows]                       # [k, HB] cumulative
        q_k = resource_hist.quantiles_from_counts(hist_k)   # [k, 3] ms
    else:
        hist_k = jnp.zeros((k, 0), jnp.int32)
        q_k = jnp.zeros((k, 0), jnp.float32)
    slots = ring.seconds.shape[0]
    slot = ring.cursor % slots
    keep = append > 0
    ring = TelemetryRing(
        seconds=ring.seconds.at[slot].set(
            jnp.where(keep, sec_idx_m, ring.seconds[slot])),
        lanes=ring.lanes.at[slot].set(
            jnp.where(keep, entry_lanes, ring.lanes[slot])),
        rt=ring.rt.at[slot].set(jnp.where(keep, entry_rt, ring.rt[slot])),
        cursor=ring.cursor + keep.astype(jnp.int32),
    )
    return (vals, rows, roll_lanes, sec_lanes, sec_rt,
            entry_lanes, entry_rt, hist_k, q_k), ring


class HotTelemetry:
    """The per-``Sentinel`` hot-resource telemetry service
    (``Sentinel.telemetry``).

    Host-side contract: :meth:`tick` dispatches the device read under the
    engine lock (no sync); :meth:`drain` resolves queued readbacks OFF the
    lock; :meth:`poll` is the ticker-thread body. All reads
    (:meth:`snapshot`, :meth:`hot_entries`) serve from the last drained
    host view under a telemetry-local lock — never from device state.
    Landing a readback costs O(K), whatever the registry holds: the
    device's top-K names the candidate rows and the host asks the
    registry for those rows' names only.
    """

    def __init__(self, sentinel, *, k: Optional[int] = None,
                 enabled: Optional[bool] = None,
                 ring_slots: int = RING_SLOTS) -> None:
        self._sentinel = sentinel
        self._obs = sentinel.obs
        if enabled is None:
            enabled = sentinel.obs.enabled and not telemetry_disabled()
        self.enabled = enabled
        spec = sentinel.spec
        self.k = max(1, min(k if k is not None else telemetry_k(),
                            MAX_K, spec.rows))
        self.ring_slots = int(ring_slots)
        self._n_shards, self._rows_per_shard = topk_layout(
            spec, sentinel.mesh)
        self._lock = threading.Lock()          # telemetry-local host state
        self._pending: "collections.deque" = collections.deque()
        self._drops = 0
        self._ticks = 0
        self._ring: Optional[TelemetryRing] = None
        self._tick_fn = None
        self._hot: List[Dict] = []
        self._timeline: "collections.deque" = collections.deque(
            maxlen=HOT_TIMELINE_CAP)
        self._last_raw: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._last_ts_ms = 0
        # the breaker-state column as the previous tick read it, with the
        # compiled rule set it belongs to (a reload starts the diff anew)
        self._breakers_prev: Optional[Tuple[object, np.ndarray]] = None
        # the first completed second is the one the clock is currently in
        # minus one; earlier seconds pre-date this service
        self._last_sec = sentinel.clock.now_ms() // 1000 - 1
        self._last_tick_ms = int(sentinel.clock.now_ms())
        self.writer = None
        self.base_name: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        reg = getattr(sentinel, "register_shutdown", None)
        if reg is not None:
            reg(self)
        if self.enabled:
            # flight triggers pin the hot set as seen at trigger time
            sentinel.obs.flight.hot_provider = self.flight_hot

    # ---- persistence wiring (bootstrap / tests) ----------------------

    def configure(self, base_dir: str, app_name: str, *,
                  single_file_size: int = 50 * 1024 * 1024,
                  total_file_count: int = 6) -> str:
        """Attach the rolling ``<app>-metric`` writer (idempotent per
        instance); → the on-disk base name the searcher should use."""
        from sentinel_tpu.metrics.writer import MetricWriter, \
            form_metric_file_name
        if self.writer is None:
            self.writer = MetricWriter(
                base_dir, app_name + "-metric",
                single_file_size=single_file_size,
                total_file_count=total_file_count)
            self.base_name = form_metric_file_name(app_name + "-metric")
        return self.base_name

    # ---- device side -------------------------------------------------

    def _build_tick(self):
        spec = self._sentinel.spec
        return jax.jit(functools.partial(
            telemetry_tick, spec.second, spec.minute, self.k,
            self._sentinel.mesh, self._rows_per_shard))

    def tick(self) -> bool:
        """Dispatch one telemetry read; → True when a readback was
        queued (False: disabled, closed, or dropped because the drain
        side is :data:`PENDING_MAX` behind)."""
        if not self.enabled or self._closed:
            return False
        with self._lock:
            if len(self._pending) >= PENDING_MAX:
                self._drops += 1
                drop = True
            else:
                drop = False
        if drop:
            self._obs.counters.add(obs_keys.TELEMETRY_DROP)
            return False
        sn = self._sentinel
        now_ms = sn.clock.now_ms()
        sec = now_ms // 1000 - 1               # last COMPLETED second
        append = 1 if sec > self._last_sec else 0
        spec = sn.spec
        idx_s = jnp.int32(spec.second.index_of(now_ms))
        sec_idx_m = jnp.int32(spec.minute.index_of(sec * 1000)
                              if spec.minute is not None else 0)
        with sn._lock:
            if self._tick_fn is None:
                self._tick_fn = self._build_tick()
            if self._ring is None:
                self._ring = init_ring(self.ring_slots)
            outs, self._ring = self._tick_fn(
                sn._state.second, sn._state.minute, sn._state.rt_hist,
                self._ring, idx_s, sec_idx_m, np.int32(append))
            # the breakers, once a tick: one small column (4 bytes a rule
            # of capacity), read only while degrade rules are loaded
            deg = sn._deg
            breakers = (deg, sn._breaker_snapshot_locked()) \
                if deg.num_active else None
        if append:
            self._last_sec = sec
        with self._lock:
            self._pending.append((now_ms, sec, append, outs, breakers))
            self._ticks += 1
            self._last_tick_ms = int(now_ms)
        self._obs.counters.add(obs_keys.TELEMETRY_TICK)
        if self._obs.enabled:
            self._obs.counters.add(obs_keys.PIPE_DISPATCH)
        return True

    # ---- the scheduler's clock (CadenceScheduler, serving.py) --------

    def last_tick_ms(self) -> int:
        with self._lock:
            return self._last_tick_ms

    def stamp_last_tick(self) -> None:
        """Count the tick interval from now (``CadenceScheduler.start``)."""
        with self._lock:
            self._last_tick_ms = int(self._sentinel.clock.now_ms())

    # ---- host side ---------------------------------------------------

    def drain(self) -> int:
        """Resolve every queued device readback into the host view (and
        the ``<app>-metric`` log); → entries drained. Runs OFF the engine
        lock: ``np.asarray`` here blocks only the telemetry thread. Each
        landing is one ``telemetry.land`` phase whose ``n`` is the rows
        it resolved to names (the loaded top-K candidates, ≤ K)."""
        with self._lock:
            batch = list(self._pending)
            self._pending.clear()
        for now_ms, sec, append, outs, breakers in batch:
            host = tuple(np.asarray(o) for o in outs)
            with self._obs.phase("telemetry.land",
                                 n=int(np.count_nonzero(host[0] > 0))):
                self._land(now_ms, sec, append, host)
            if breakers is not None:
                self._count_breakers(*breakers)
        return len(batch)

    def _count_breakers(self, deg, column) -> None:
        """One tick's reading of the breaker-state column into the
        ``breaker.*`` counters: how many active breakers are not CLOSED /
        CLOSED now, and how many entered each state since the previous
        tick's reading of the same rule set. A breaker that left a state
        and came back between two ticks reads as unchanged."""
        from sentinel_tpu.rules.degrade import (
            STATE_CLOSED, STATE_HALF_OPEN, STATE_OPEN,
        )
        # rules are packed from slot 0: the active ones come first
        state = np.asarray(column)[: deg.num_active]
        counters = self._obs.counters
        n_open = int(np.count_nonzero(state != STATE_CLOSED))
        counters.add(obs_keys.BREAKER_SEEN_OPEN, n_open)
        counters.add(obs_keys.BREAKER_SEEN_CLOSED, state.size - n_open)
        prev = self._breakers_prev
        self._breakers_prev = (deg, state)
        if prev is None or prev[0] is not deg:
            return
        entered = state[state != prev[1]]
        for key, code in ((obs_keys.BREAKER_OPENED, STATE_OPEN),
                          (obs_keys.BREAKER_HALF_OPENED, STATE_HALF_OPEN),
                          (obs_keys.BREAKER_CLOSED, STATE_CLOSED)):
            counters.add(key, int(np.count_nonzero(entered == code)))

    def _land(self, now_ms: int, sec: int, append: int, outs) -> None:
        """Land one readback into the host view. O(K): only the loaded
        candidate rows of the device's top-K are resolved to names
        (``name_of``, at land time), never the resident names."""
        (vals, rows, roll_lanes, sec_lanes, sec_rt,
         entry_lanes, entry_rt, hist_k, q_k) = outs
        has_hist = hist_k.shape[1] > 0
        name_of = self._sentinel.resources.name_of
        interval_s = self._sentinel.spec.second.interval_ms / 1000.0
        hot: List[Dict] = []
        for i in range(len(vals)):
            load = int(vals[i])
            if load <= 0:
                continue
            row = int(rows[i])
            name = name_of(row)
            if name is None:        # stale row (evicted since the tick)
                continue
            lanes = roll_lanes[i]
            succ_s = int(sec_lanes[i][ev.SUCCESS])
            entry = {
                "resource": name, "row": row, "load": load,
                "qps": round(load / interval_s, 3),
                "pass": int(lanes[ev.PASS]), "block": int(lanes[ev.BLOCK]),
                "success": int(lanes[ev.SUCCESS]),
                "exception": int(lanes[ev.EXCEPTION]),
                # device-measured mean RT over the landed second — the
                # pre-r20 degrade signal, kept as the hist-off fallback
                "rt_ms": round(float(sec_rt[i]) / succ_s, 3) if succ_s
                         else 0.0,
            }
            if has_hist:
                # round 20: lifetime-cumulative tail view (display /
                # Prometheus); the controller differences the raw
                # vector itself for interval tails
                entry["rt_p50_ms"] = round(float(q_k[i][0]), 3)
                entry["rt_p95_ms"] = round(float(q_k[i][1]), 3)
                entry["rt_p99_ms"] = round(float(q_k[i][2]), 3)
                entry["rt_hist"] = hist_k[i].tolist()
            hot.append(entry)
        if has_hist and hot:
            self._obs.counters.add(obs_keys.TELEMETRY_HIST_TICK)
        timeline_entry = None
        nodes = []
        if append and self._sentinel.spec.minute is not None:
            timeline_entry = {
                "sec": int(sec),
                "pass": int(entry_lanes[ev.PASS]),
                "block": int(entry_lanes[ev.BLOCK]),
                "success": int(entry_lanes[ev.SUCCESS]),
                "exception": int(entry_lanes[ev.EXCEPTION]),
                "occupied_pass": int(entry_lanes[ev.OCCUPIED_PASS]),
                "rt_sum": round(float(entry_rt), 3),
            }
            if self.writer is not None:
                from sentinel_tpu.metrics.node import MetricNode
                rtypes = self._sentinel.resource_types
                for i, h in enumerate(hot):
                    c = sec_lanes[i]
                    if not (c[ev.PASS] or c[ev.BLOCK] or c[ev.SUCCESS]
                            or c[ev.EXCEPTION]):
                        continue
                    succ = int(c[ev.SUCCESS])
                    nodes.append(MetricNode(
                        timestamp=sec * 1000, resource=h["resource"],
                        pass_qps=int(c[ev.PASS]),
                        block_qps=int(c[ev.BLOCK]), success_qps=succ,
                        exception_qps=int(c[ev.EXCEPTION]),
                        rt=int(float(sec_rt[i]) / succ) if succ else 0,
                        occupied_pass_qps=int(c[ev.OCCUPIED_PASS]),
                        classification=rtypes.get(h["resource"], 0)))
                nodes.sort(key=lambda n: n.resource)
        with self._lock:
            self._hot = hot
            self._last_raw = (vals, rows)
            self._last_ts_ms = int(now_ms)
            if timeline_entry is not None:
                self._timeline.append(timeline_entry)
        if nodes:   # writer.write serializes internally; seconds ascend
            self.writer.write(sec * 1000, nodes)

    def poll(self) -> int:
        """Ticker-thread body (callable directly in tests): one dispatch
        plus the drain of everything queued so far."""
        self.tick()
        return self.drain()

    # ---- read surface ------------------------------------------------

    def snapshot(self, timeline_limit: int = 60) -> Dict:
        """The ``topk`` transport command / ``/obs/topk.json`` body."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "k": self.k,
                "ts_ms": self._last_ts_ms,
                "n_shards": self._n_shards,
                "rows_per_shard": self._rows_per_shard,
                "hot": list(self._hot),
                "timeline": list(self._timeline)[-timeline_limit:],
                "ticks": self._ticks,
                "drops": self._drops,
            }

    def hot_entries(self, n: Optional[int] = None) -> List[Dict]:
        with self._lock:
            hot = list(self._hot)
        return hot if n is None else hot[:n]

    def flight_hot(self) -> List[Dict]:
        """Compact hot-set view pinned into flight trigger records."""
        return [{"resource": h["resource"], "qps": h["qps"]}
                for h in self.hot_entries(FLIGHT_HOT_N)]

    @property
    def last_topk(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(loads, rows) of the last drained tick, raw and unfiltered —
        the exactness probe the tests compare against a host recompute."""
        with self._lock:
            return self._last_raw

    # ---- lifecycle ---------------------------------------------------

    def start(self, interval_sec: float = 1.0) -> None:
        """Start the telemetry daemon (no-op when disabled/running)."""
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_sec):
                try:
                    self.poll()
                except Exception:  # pragma: no cover — keep daemon alive
                    pass

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="sentinel-telemetry")
        self._thread.start()

    def stop(self) -> None:
        """Idempotent: join the daemon, drain what is queued, close the
        writer. Registered with ``Sentinel.register_shutdown``."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
        if self._closed:
            return
        self._closed = True
        try:
            self.drain()
        except Exception:   # teardown must not depend on device health
            pass
        self.enabled = False
        if self.writer is not None:
            self.writer.close()


__all__ = [
    "TELEMETRY_K_ENV", "TELEMETRY_DISABLE_ENV", "DEFAULT_K", "MAX_K",
    "RING_SLOTS", "PENDING_MAX", "FLIGHT_HOT_N", "TelemetryRing",
    "init_ring", "telemetry_tick", "telemetry_disabled", "telemetry_k",
    "HotTelemetry",
]
