"""TierManager: the hot/cold state machine around the device row table.

Lifecycle of a key under tiering (default ON; ``SENTINEL_TIERING_DISABLE``
reverts to the pre-round-15 lossy eviction):

* **resident (hot)** — a registry row; the dispatch paths are unchanged.
* **demotion** — when the registry recycles a row (LRU overflow, or the
  ticker's proactive ``evict_name``), the engine's eviction drain FIRST
  dispatches a jitted gather of the row's complete state
  (``engine.pipeline.extract_resource_rows`` — fresh output buffers,
  dispatch-only under the engine lock) and queues it as ONE record.
  THEN the usual invalidate runs. ``tier.demoted`` ticks. The record
  LANDS — its host arrays become one
  :class:`~sentinel_tpu.tiering.coldtier.ColdBlock`, its names index
  into it; nothing is built per victim — on the tiering thread, or
  inline when a promotion of the same drain needs one of its names.
* **cold** — host memory only; unbounded cardinality.
* **promotion (the documented slow path)** — when a cold key is
  interned again, the NEXT eviction drain (which runs under the engine
  lock before every decide) scatters the cold payload back into the
  freshly allocated row (``restore_resource_rows``) — gathered from
  the blocks column by column — after replaying any flow-rule reloads
  the key slept through
  (:func:`~sentinel_tpu.tiering.coldtier.settle_entry_np`). The decide
  that triggered the intern therefore sees the row EXACTLY as if it had
  never left the device — verdict bit-parity is by construction
  (window stamps and booking windows are absolute indices, so the
  payload is time-portable), at the cost of one synchronous
  host→device scatter on that batch (``tier.cold_miss`` +
  ``tier.promoted`` tick; latency lands in
  :attr:`TierManager.migration_hist`).

Hot-set discovery: a conservative-update count-min sketch
(:mod:`~sentinel_tpu.tiering.sketch`) over the batch's resource rows,
updated under the engine lock inside the decide paths (dispatch-only).
The ticker (modeled on the round-12 telemetry ticker: dispatch under
the lock, land off-lock) decays the sketch and reads back its largest
counter (the overflow accounting). With a ``SENTINEL_HOT_ROWS`` target
set it also reads every row's estimate and demotes the lowest-estimate
unpinned rows whenever the resident count exceeds the target — so LRU
pressure from new keys lands on sketch-cold rows, never on the
measured hot set. Proactive demotion requires the Python registry's
``evict_name``; on the native C++ table only LRU-overflow demotion
runs, and no estimate is computed (documented in OPERATIONS.md).

Demotion attribution: the registry eviction queue carries row IDS (the
name is already gone by then), so the manager keeps a shadow
``row → name`` map maintained at every intern site
(:meth:`TierManager.note_interned`). Rows reallocated by paths that
bypass interning (rule-compile pins) resync from ``registry.name_of``
at drain time and their previous owner's state is dropped
unattributed — the pre-round-15 behavior, counted but not restored.
"""

from __future__ import annotations

import collections
import functools
import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sentinel_tpu.core.batching import pad_pow2
from sentinel_tpu.core.pending import start_host_copy
from sentinel_tpu.core.registry import ENTRY_NODE_ROW
from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.obs.hist import LogHistogram
from sentinel_tpu.stats import events as ev
from sentinel_tpu.tiering import sketch as sk
from sentinel_tpu.tiering.coldtier import (
    ColdBlock, ColdEntry, ColdTier, fresh_window, settle_entry_np,
)

HOT_ROWS_ENV = "SENTINEL_HOT_ROWS"
SKETCH_BITS_ENV = "SENTINEL_SKETCH_BITS"
SKETCH_ROWS_ENV = "SENTINEL_SKETCH_ROWS"
TIER_TICK_MS_ENV = "SENTINEL_TIER_TICK_MS"
TIERING_DISABLE_ENV = "SENTINEL_TIERING_DISABLE"
TIER_COLD_MAX_ENV = "SENTINEL_TIER_COLD_MAX"

DEFAULT_TICK_MS = 200
# un-landed demote payloads tolerated before the drain side force-lands
# inline (the ticker normally lands them; this bounds device-buffer
# retention when no ticker runs, e.g. short-lived test engines)
PENDING_LAND_MAX = 64

NEVER = -(2 ** 30)
_I32MAX = np.iinfo(np.int32).max


def _env_int(env: str, default: Optional[int], lo: int,
             hi: int) -> Optional[int]:
    raw = os.environ.get(env, "")
    if not raw:
        return default
    try:
        return max(lo, min(hi, int(raw)))
    except ValueError:
        return default


def tier_hot_rows(default: Optional[int] = None) -> Optional[int]:
    """Resident-row target for the ticker's proactive demotion; default
    None = the full table (LRU-overflow demotion only)."""
    return _env_int(HOT_ROWS_ENV, default, 64, 1 << 24)


def tier_sketch_bits(default: int = sk.DEFAULT_BITS) -> int:
    return _env_int(SKETCH_BITS_ENV, default, 4, 22)


def tier_sketch_rows(default: int = sk.DEFAULT_ROWS) -> int:
    return _env_int(SKETCH_ROWS_ENV, default, 1, 8)


def tier_tick_ms(default: int = DEFAULT_TICK_MS) -> int:
    return _env_int(TIER_TICK_MS_ENV, default, 10, 60000)


def tier_cold_max(default: int = 0) -> int:
    """Cold-tier entry bound; 0 = unbounded (the default)."""
    return _env_int(TIER_COLD_MAX_ENV, default, 0, 1 << 31)


def tiering_disabled() -> bool:
    return os.environ.get(TIERING_DISABLE_ENV, "").lower() in (
        "1", "true", "on", "yes")


@functools.lru_cache(maxsize=None)
def _jit_extract(spec):
    from sentinel_tpu.engine.pipeline import extract_resource_rows
    from sentinel_tpu.runtime import named_partial
    return jax.jit(named_partial("tier_extract", extract_resource_rows, spec))


class TierManager:
    """Per-:class:`~sentinel_tpu.runtime.Sentinel` tiering service
    (``Sentinel.tiering``). Host structures live under a manager-local
    lock; the ``*_locked`` hooks additionally run under the ENGINE lock
    (they touch ``sentinel._state``). Lock order is always engine lock
    → manager lock, never the reverse."""

    def __init__(self, sentinel, *, enabled: Optional[bool] = None) -> None:
        self._sentinel = sentinel
        self._obs = sentinel.obs
        if enabled is None:
            enabled = not tiering_disabled()
        self.enabled = bool(enabled)
        self.hot_rows = tier_hot_rows()
        self.cold = ColdTier(tier_cold_max() or None)
        self.migration_hist = LogHistogram()
        self._lock = threading.Lock()
        # row → current owner name, maintained at every intern site
        self._shadow: Dict[int, str] = {}
        # row → FIRST victim name since the last eviction drain (later
        # victims of the same row lived entirely between drains: no
        # decide ever saw them, nothing on-device to save)
        self._pending_demote: Dict[int, str] = {}
        # name → row awaiting a cold→hot restore at the next drain
        self._pending_promote: Dict[str, int] = {}
        # names whose demote payload is dispatched but not yet landed
        self._pending_land: Dict[str, dict] = {}
        self._land_q: "collections.deque" = collections.deque()
        # one (largest counter, every row's estimate or None) per tick
        self._tick_q: "collections.deque" = collections.deque()
        # flow-rule reload log: second-window now_idx per reload; a cold
        # entry replays the tail it slept through at promote time
        self._reload_idxs: List[int] = []
        self._sketch = None
        self._sketch_update = None
        self._ticks = 0
        self._last_tick_ms = int(sentinel.clock.now_ms())
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        if self.enabled:
            self._sketch = sk.init_sketch(tier_sketch_rows(),
                                          tier_sketch_bits())
            self._sketch_update = sk.jit_update()
        # demote listeners (frontend/batcher.py prunes its name→row
        # cache so a demoted key re-interns — and promotes — instead of
        # dispatching against a recycled row)
        self._demote_listeners: list = []
        reg = getattr(sentinel, "register_shutdown", None)
        if reg is not None:
            reg(self)

    # ---- intern-time hooks (outside the engine lock) ------------------

    def note_interned(self, names, rows, counts=None,
                      tick: bool = True) -> None:
        """Record name→row ownership for just-interned names and classify
        each NAME once: ``names`` are DISTINCT (an
        :class:`~sentinel_tpu.core.registry.InternedBatch`'s ``names_u``
        / ``rows_u`` / ``counts``; ``counts=None`` is one occurrence
        each). Resident name → ``tier.hot_hit``; name the cold tier (or
        an in-flight demote) knows → ``tier.cold_miss`` + queued
        promotion; first-sight name → neither (a brand-new key is not a
        *miss* of anything — see the hit-rate note in OPERATIONS.md) but
        one ``tier.first_sight``, per NAME. The two classification
        counters add the name's OCCURRENCES. O(distinct names)
        Python, nothing per occurrence. ``tick=False`` (rule-load pin
        paths, runtime._update_rule_pins_locked) keeps the shadow map
        and promotion queue exact without counting control-plane interns
        into the serving hit rate."""
        if not self.enabled:
            return
        if isinstance(rows, np.ndarray):
            rows = rows.tolist()        # Python ints: no NumPy scalars below
        counts = (itertools.repeat(1) if counts is None
                  else np.asarray(counts).tolist())
        hot = cold = first = 0
        with self._lock:
            shadow = self._shadow
            # Two passes so classification cannot depend on intra-batch
            # ORDER: when name A's fresh row displaced name B and B is
            # ALSO in this batch at a new row (a rule reload re-interning
            # a full pinned set does exactly this), B's cold-miss test
            # must see the demote intent A's displacement records — in
            # one pass that held only if A happened to come first, and
            # the pin path feeds this from a Python set, so B's window
            # state was dropped or kept by hash order (the real cause of
            # the seed-1602 tiered-vs-resident divergence once blamed on
            # the staging ring).
            fresh: List[Tuple[str, int, int]] = []
            for name, row, cnt in zip(names, rows, counts):
                prev = shadow.get(row)
                if prev == name:
                    hot += cnt
                    continue
                shadow[row] = name
                if prev is not None:
                    self._pending_demote.setdefault(row, prev)
                fresh.append((name, row, cnt))
            if fresh:
                demoting = set(self._pending_demote.values())
                for name, row, cnt in fresh:
                    if (name in self.cold or name in self._pending_land
                            or name in demoting):
                        self._pending_promote[name] = row
                        cold += cnt
                    else:
                        first += 1
        if tick and self._obs.enabled:
            if hot:
                self._obs.counters.add(obs_keys.TIER_HOT_HIT, hot)
            if cold:
                self._obs.counters.add(obs_keys.TIER_COLD_MISS, cold)
            if first:
                self._obs.counters.add(obs_keys.TIER_FIRST_SIGHT, first)

    def note_hot_hits(self, n: int) -> None:
        """Frontend name→row cache hits: resident by construction (the
        cache is pruned on demotion), counted here so the hit rate
        covers the whole serving path."""
        if self.enabled and n and self._obs.enabled:
            self._obs.counters.add(obs_keys.TIER_HOT_HIT, n)

    def add_demote_listener(self, fn) -> None:
        """``fn(names: List[str])`` fires when keys leave the hot tier
        (called from the eviction drain, still under the engine lock —
        keep it O(names))."""
        self._demote_listeners.append(fn)

    # ---- engine-lock hooks -------------------------------------------

    def observe_locked(self, rows_dev, valid_dev) -> bool:
        """Sketch update from a decide batch's device row array —
        dispatch-only (conservative-update count-min; see sketch.py).
        The update op halves the table inside the jit when an estimate
        crosses the overflow cap, so counters stay bounded even on an
        engine that never starts the ticker; the flag is dropped here
        (syncing it would stall the decide) and the overflow COUNTER is
        ticked host-side from the ticker's readback of the largest
        counter.

        Round 16: this standalone dispatch is the DISABLED/FALLBACK path
        — with ``SENTINEL_SINGLE_DISPATCH`` on, the runtime fuses the
        identical :func:`sketch.update_sketch` into the decide program
        (see :meth:`sketch_for_fuse_locked`) and never calls this.
        Returns whether a dispatch was actually issued (the runtime's
        ``pipeline.dispatches`` accounting)."""
        if self._sketch is None:
            return False
        self._sketch, _overflow = self._sketch_update(
            self._sketch, rows_dev, valid_dev)
        return True

    # ---- sketch-fused decide surface ----------------------------------

    def sketch_for_fuse_locked(self):
        """Engine lock held: the sketch table to thread through a
        sketch-fused decide dispatch, or None when tiering (or its
        sketch) is off — None tells the runtime to fall back to the
        legacy program + :meth:`observe_locked` composition."""
        if not self.enabled or self._closed:
            return None
        return self._sketch

    def set_sketch_locked(self, sketch) -> None:
        """Engine lock held: store the donated-output sketch returned by
        a sketch-fused dispatch."""
        self._sketch = sketch

    def last_tick_ms(self) -> int:
        with self._lock:
            return self._last_tick_ms

    def stamp_last_tick(self) -> None:
        """Count the tick interval from now (``CadenceScheduler.start``)."""
        with self._lock:
            self._last_tick_ms = int(self._sentinel.clock.now_ms())

    def pre_invalidate_locked(self, evicted: List[int], now_ms: int) -> None:
        """Demote snapshot: gather the evicted rows' state BEFORE the
        invalidate destroys it. Dispatch + queue only; ``np.asarray``
        happens on the tiering thread (or force-lands at promote).
        Stream ordering guarantees the gather reads pre-invalidate
        values even though the invalidate is dispatched right after."""
        if not self.enabled:
            return
        sn = self._sentinel
        victims: List[Tuple[str, int]] = []
        with self._lock:
            for row in evicted:
                name = self._pending_demote.pop(row, None)
                from_queue = name is not None
                if name is None:
                    name = self._shadow.get(row)
                cur = sn.resources.name_of(row)
                if cur is not None:
                    self._shadow[row] = cur
                else:
                    self._shadow.pop(row, None)
                if name is None or row == ENTRY_NODE_ROW:
                    continue    # unattributable (pin-path reallocation)
                if not from_queue and name == cur:
                    continue    # stale duplicate queue entry; still owned
                victims.append((name, row))
        if not victims:
            return
        # alt slots: hashed (resource × origin/context) slices travel
        # with their HOST identity so the promote can re-hash them
        alt_ids: List[Tuple[int, int, int]] = []   # (victim_i, kind, key)
        alt_slots: List[int] = []
        for vi, (_name, row) in enumerate(victims):
            slots = sn._alt_rows_by_row.get(row, {})
            items = (slots.items() if isinstance(slots, dict)
                     else ((s, None) for s in slots))
            for slot, ident in items:
                if ident is None:
                    continue    # identity unknown: slice not portable
                alt_ids.append((vi, ident[0], ident[1]))
                alt_slots.append(slot)
        k = len(victims)
        # the invalidate's padding (Sentinel._pad), so one size warms all
        # three migration programs (warm_migration)
        kp = pad_pow2(k)
        ka = pad_pow2(len(alt_slots))
        rows_arr = np.full(kp, sn.spec.rows, np.int32)    # pad → dropped
        rows_arr[:k] = [r for _n, r in victims]
        alt_arr = np.full(ka, sn.spec.alt_rows, np.int32)
        if alt_slots:
            alt_arr[:len(alt_slots)] = alt_slots
        payload = _jit_extract(sn.spec)(
            sn._state, jnp.asarray(rows_arr), jnp.asarray(alt_arr))
        start_host_copy(tuple(jax.tree_util.tree_leaves(payload)))
        with self._lock:
            rec = {"victims": victims, "alt_ids": alt_ids,
                   "payload": payload, "now_ms": now_ms,
                   "gen": len(self._reload_idxs), "landed": False,
                   "lock": threading.Lock()}
            for name, _row in victims:
                self._pending_land[name] = rec
            self._land_q.append(rec)
            force = len(self._land_q) > PENDING_LAND_MAX
        if self._obs.enabled:
            self._obs.counters.add(obs_keys.TIER_DEMOTED, k)
        if force:
            self._land_all(inline=True)
        if self._demote_listeners:
            names = [n for n, _r in victims]
            for fn in self._demote_listeners:
                try:
                    fn(names)
                except Exception:
                    pass

    def post_invalidate_locked(self, now_ms: int) -> None:
        """Promote every pending cold key into its freshly allocated
        (and just-invalidated) row — the synchronous half of the slow
        path. Runs under the engine lock so the decide that interned
        the key sees the restored row."""
        if not self.enabled:
            return
        with self._lock:
            if not self._pending_promote:
                return
            todo = list(self._pending_promote.items())
            self._pending_promote.clear()
        with self._obs.phase("tier.promote", n=len(todo)) as phase:
            phase.n = self._promote_locked(todo)

    def _promote_locked(self, todo: List[Tuple[str, int]]) -> int:
        """→ rows restored (≤ ``len(todo)``: a row recycled again before
        this drain, or a name the bounded cold tier dropped, is not)."""
        sn = self._sentinel
        t0 = time.monotonic_ns()
        with self._lock:
            # a row recycled again before this drain is skipped; its
            # state stays cold for the next intern of the name
            todo = [(n, r) for n, r in todo if self._shadow.get(r) == n]
            pending = self._pending_recs_locked(n for n, _r in todo)
            reloads = list(self._reload_idxs)
        for rec in pending:
            # force-land THIS rec directly — a queue-level _land_all
            # would no-op if the tiering thread already dequeued it but
            # hasn't finished landing, and the pop below would then miss
            # the name and silently serve a zeroed row; _land_one's
            # per-rec lock instead blocks until the in-flight land is
            # done
            self._land_one(rec, inline=True)
        rows = np.fromiter((r for _n, r in todo), np.int32, len(todo))
        B = sn.spec.second.buckets
        parts: List[Tuple[ColdBlock, np.ndarray, np.ndarray]] = []
        replayed = 0
        for block, src, js in self.cold.pop_rows([n for n, _r in todo]):
            if block.second[1].shape[1] != B:
                # extracted under a previous window geometry and missed
                # by the geometry-change conversion (a straggler that
                # landed after it): restoring would scatter mismatched
                # shapes, and the cold-reset semantic says its second
                # windows are void anyway — drop; the keys re-enter
                # fresh, exactly like a resident row post-change
                continue
            missed = reloads[block.reload_gen:]
            if not missed:
                parts.append((block, src, rows[js]))
                continue
            # replay the flow reloads these keys slept through, each with
            # THAT reload's now_idx — bit-parity with the resident
            # settle; per name on a materialised row (no cell reloads
            # rules while names are cold)
            for i, j in zip(src.tolist(), js.tolist()):
                entry = block.entry(i)
                for idx in missed:
                    settle_entry_np(B, entry, idx, ev.PASS)
                parts.append((ColdBlock.of_entry(entry),
                              np.zeros(1, np.int64), rows[j:j + 1]))
            replayed += src.size
        self._note_materialized(replayed)
        n = sum(r.size for _b, _s, r in parts)
        if not n:
            return 0
        self._restore_locked(parts)
        if self._obs.enabled:
            self._obs.counters.add(obs_keys.TIER_PROMOTED, n)
        self.migration_hist.record(time.monotonic_ns() - t0)
        return n

    def _pending_recs_locked(self, names) -> list:
        """Manager lock held: the demote records still in flight that
        hold any of ``names``, each once."""
        if not self._pending_land:
            return []
        return list({id(rec): rec for rec in
                     map(self._pending_land.get, names)
                     if rec is not None}.values())

    def _note_materialized(self, n: int) -> None:
        if n and self._obs.enabled:
            self._obs.counters.add(obs_keys.TIER_MATERIALIZED, n)

    def _restore_locked(self, parts, rows: int = 0) -> None:
        """One jitted scatter for the whole promote batch, padded to a
        power of two (``rows``: at least that many — the warm-up's, with
        no part at all). ``parts``: ``(block, src, dst)`` — rows ``src``
        of ``block`` go to table rows ``dst``; each payload column is
        filled with one gather per block."""
        from sentinel_tpu.engine.pipeline import ResourceRowSlice
        from sentinel_tpu.runtime import _alt_hash
        from sentinel_tpu.stats.window import WindowState
        sn = self._sentinel
        spec, st = sn.spec, sn._state
        kp = pad_pow2(max(sum(d.size for _b, _s, d in parts), rows))
        B = spec.second.buckets
        ne = st.second.counters.shape[-1]
        brt = st.second.rt_sum.shape[1]
        # minute ring disabled: the demotion's placeholder slice, ignored
        mb, mbrt = st.minute.stamps.shape[1], st.minute.rt_sum.shape[1]
        second = fresh_window(kp, B, ne, brt)
        minute = fresh_window(kp, mb, ne, mbrt)
        thr = np.zeros(kp, np.int32)
        occ_c = np.zeros((kp, B + 1), np.float32)
        occ_w = np.full((kp, B + 1), NEVER, np.int32)
        hb = spec.hist_buckets
        # zeros for rows that predate the histogram table (a row demoted
        # before the feature was enabled restores with an empty — not
        # stale — tail view)
        rt_h = np.zeros((kp, hb), np.int32) if hb else None
        rows_arr = np.full(kp, spec.rows, np.int32)
        alt_slots: List[int] = []
        alt_parts: List[Tuple[ColdBlock, List[int]]] = []
        at = 0
        for block, src, dst in parts:
            to = slice(at, at + dst.size)
            at = to.stop
            rows_arr[to] = dst
            for out, col in zip(second, block.second):
                out[to] = col[src]
            if spec.minute:
                for out, col in zip(minute, block.minute):
                    out[to] = col[src]
            thr[to] = block.threads[src]
            occ_c[to], occ_w[to] = block.occ_cnt[src], block.occ_win[src]
            if rt_h is not None and block.rt_hist is not None \
                    and block.rt_hist.shape[1] == hb:
                rt_h[to] = block.rt_hist[src]
            if not block.alt_ids:
                continue
            # alt slices re-hash onto the new rows' slots
            row_of = dict(zip(src.tolist(), dst.tolist()))
            alt_src: List[int] = []
            for j, (vi, kind, key_id) in enumerate(block.alt_ids):
                row = row_of.get(vi)
                if row is None:
                    continue
                slot = _alt_hash(row, kind, key_id, spec.alt_rows)
                slots = sn._alt_rows_by_row.setdefault(row, {})
                if isinstance(slots, dict):
                    slots[slot] = (kind, key_id)
                else:
                    slots.add(slot)
                alt_slots.append(slot)
                alt_src.append(j)
            alt_parts.append((block, alt_src))
        ka = pad_pow2(len(alt_slots))
        alt_arr = np.full(ka, spec.alt_rows, np.int32)
        alt_arr[:len(alt_slots)] = alt_slots
        alt_second = fresh_window(ka, B, ne, brt)
        alt_thr = np.zeros(ka, np.int32)
        at = 0
        for block, alt_src in alt_parts:
            to = slice(at, at + len(alt_src))
            at = to.stop
            for out, col in zip(alt_second, block.alt_second):
                out[to] = col[alt_src]
            alt_thr[to] = block.alt_threads[alt_src]
        payload = ResourceRowSlice(
            second=WindowState(*map(jnp.asarray, second)),
            minute=WindowState(*map(jnp.asarray, minute)),
            threads=jnp.asarray(thr),
            occ_cnt=jnp.asarray(occ_c), occ_win=jnp.asarray(occ_w),
            alt_second=WindowState(*map(jnp.asarray, alt_second)),
            alt_threads=jnp.asarray(alt_thr),
            rt_hist=jnp.asarray(rt_h) if rt_h is not None else None)
        # the engine's own step: donated, and on a mesh pinned to the
        # state's shardings (runtime._build_steps)
        sn._state = sn._jit_restore(
            sn._state, jnp.asarray(rows_arr), payload, jnp.asarray(alt_arr))

    def on_rules_reloaded_locked(self, now_idx: int) -> None:
        """Flow-rule reload: resident rows just had their landed
        bookings settled at ``now_idx``; log it so cold entries replay
        the same settle at promote time."""
        if not self.enabled:
            return
        with self._lock:
            self._reload_idxs.append(int(now_idx))

    def on_geometry_changed_locked(self) -> None:
        """Live second-window geometry change
        (``runtime.update_window_geometry``, engine lock held): every
        cold entry and in-flight demote payload was extracted under the
        OLD bucket count — promoting one later would scatter mismatched
        shapes into the new state (numpy shape error / IndexError on
        the serving path). Land every in-flight payload first (host
        numpy, still old-geometry — the per-rec lock in ``_land_one``
        covers recs the tiering thread holds mid-land), then cold-reset
        each entry's second windows + booking ring to the new bucket
        count, minute ring and thread gauge carrying over — exactly
        what resident rows get, so demote→change→promote stays
        bit-identical to staying resident. The reload-replay log
        restarts: pre-change reloads settled into buckets that no
        longer exist and every entry is reset-empty."""
        if not self.enabled:
            return
        with self._lock:
            recs = list({id(r): r for r in
                         self._pending_land.values()}.values())
        for rec in recs:
            self._land_one(rec, inline=True)
        with self._lock:
            self._land_q.clear()    # all landed (or marked) above
            self._reload_idxs.clear()
        self.cold.convert_geometry(self._sentinel.spec.second.buckets)

    # ---- landing (tiering thread / forced) ----------------------------

    def _land_all(self, inline: bool = False) -> int:
        with self._lock:
            batch = list(self._land_q)
            self._land_q.clear()
        for rec in batch:
            self._land_one(rec, inline)
        return len(batch)

    def _land_one(self, rec, inline: bool = False) -> None:
        """``inline``: the engine side is landing it, under its lock."""
        # per-rec lock: the engine side (post_invalidate_locked,
        # on_geometry_changed_locked) may force-land a rec the tiering
        # thread has already dequeued from _land_q — whoever arrives
        # second blocks until the first fully lands (put_block done),
        # then no-ops, so a force-land always leaves every name visible
        # to the pop_rows that follows it
        with rec["lock"]:
            if rec["landed"]:
                return
            k = len(rec["victims"])
            with self._obs.phase("tier.land", n=k,
                                 note="inline=1" if inline else ""):
                self._land_one_held(rec)
            if inline and self._obs.enabled:
                self._obs.counters.add(obs_keys.TIER_LAND_INLINE, k)

    def _land_one_held(self, rec) -> None:
        """The record's host arrays become ONE block and its names index
        into it: no copy and no object per victim, one acquisition of
        the cold tier's lock and one of the manager's."""
        p = rec["payload"]
        block = ColdBlock(
            second=tuple(np.asarray(x) for x in p.second),
            minute=tuple(np.asarray(x) for x in p.minute),
            threads=np.asarray(p.threads),
            occ_cnt=np.asarray(p.occ_cnt), occ_win=np.asarray(p.occ_win),
            rt_hist=None if p.rt_hist is None else np.asarray(p.rt_hist),
            alt_second=tuple(np.asarray(x) for x in p.alt_second),
            alt_threads=np.asarray(p.alt_threads),
            alt_ids=rec["alt_ids"],
            reload_gen=rec["gen"], demoted_ms=rec["now_ms"])
        names = [name for name, _row in rec["victims"]]
        self.cold.put_block(block, names)
        with self._lock:
            pending = self._pending_land
            for name in names:
                if pending.get(name) is rec:
                    del pending[name]
        rec["payload"] = None       # the block holds the host arrays now
        rec["landed"] = True

    # ---- ticker -------------------------------------------------------

    def tick(self) -> bool:
        """Dispatch one sketch decay + the read of its largest counter
        under the engine lock (no sync) and queue the readback; every
        row's estimate is dispatched with it only when proactive
        demotion will rank by it."""
        if not self.enabled or self._closed or self._sketch is None:  # graftlint: disable=LOCK002 -- lock-free early-out; a stale read only skips one tick and the next tick re-reads
            return False
        sn = self._sentinel
        est = None
        with sn._lock:
            self._sketch, top = sk.jit_tick_read(self._sketch)
            # the estimate's one reader is _demote_cold_rows, which needs
            # a hot-rows target and a registry that evicts by name
            if (self.hot_rows is not None
                    and hasattr(sn.resources, "evict_name")):
                est = sk.jit_estimate_all(self._sketch, n_rows=sn.spec.rows)
        start_host_copy((top,) if est is None else (top, est))
        if self._obs.enabled:
            c = self._obs.counters
            c.add(obs_keys.PIPE_DISPATCH, 1 if est is None else 2)
            c.add(obs_keys.TIER_TICK)
            if est is not None:
                c.add(obs_keys.TIER_TICK_ESTIMATE)
        with self._lock:
            self._tick_q.append((top, est))
            self._ticks += 1
            self._last_tick_ms = int(sn.clock.now_ms())
        return True

    def drain(self) -> int:
        """Land queued demote payloads + tick readbacks OFF the engine
        lock; handle sketch overflow; run proactive demotion against
        the hot-rows target."""
        n = self._land_all()
        with self._lock:
            ticks = list(self._tick_q)
            self._tick_q.clear()
        if ticks:
            top, est = ticks[-1]
            # update_sketch already halved inline at the cap (decide
            # paths never sync); a counter still >= cap/2 means an
            # overflow happened since the last tick — tick the counter
            # and halve again to keep headroom
            if int(top) >= sk.OVERFLOW_CAP // 2:
                with self._sentinel._lock:
                    self._sketch = sk._jit_halve(self._sketch)
                if self._obs.enabled:
                    self._obs.counters.add(obs_keys.TIER_SKETCH_OVERFLOW)
            if est is not None:
                self._demote_cold_rows(np.asarray(est))
        return n + len(ticks)

    def _demote_cold_rows(self, est: np.ndarray) -> None:
        """Evict the lowest-estimate unpinned residents down to the
        ``SENTINEL_HOT_ROWS`` target, round-robin across mesh shards
        (parallel/local_shard.py row ownership) so no shard's hot set
        thins faster than its peers'. Python registry only (the native
        table has no targeted evict; LRU-overflow demotion still
        applies there)."""
        target = self.hot_rows
        reg = self._sentinel.resources
        evict = getattr(reg, "evict_name", None)
        if target is None or evict is None:
            return
        items = reg.items()
        over = len(items) - int(target)
        if over <= 0:
            return
        from sentinel_tpu.parallel.local_shard import shard_of_rows
        cand = [(int(est[row]), name, row) for name, row in items
                if row != ENTRY_NODE_ROW and row < len(est)]
        cand.sort()
        shards = shard_of_rows(self._sentinel.spec.rows,
                               self._sentinel.mesh,
                               np.asarray([c[2] for c in cand], np.int32))
        by_shard: Dict[int, collections.deque] = {}
        for c, s in zip(cand, shards):
            by_shard.setdefault(int(s), collections.deque()).append(c)
        done = 0
        while done < over and by_shard:
            for s in list(by_shard):
                q = by_shard[s]
                while q:
                    _e, name, row = q.popleft()
                    # record intent BEFORE evict_name frees the row: a
                    # re-intern of this name in the window after the
                    # registry pops the row but before intent lands
                    # would otherwise classify hot against the stale
                    # shadow entry, and the next drain would invalidate
                    # the row without queuing its promotion — silently
                    # zeroing a resident key
                    with self._lock:
                        if self._shadow.get(row) != name:
                            continue    # re-owned since the estimate
                        claimed = row not in self._pending_demote
                        if claimed:
                            self._pending_demote[row] = name
                        del self._shadow[row]
                    if evict(name):
                        done += 1
                        break
                    # evict refused (pinned / raced away): roll back so
                    # the name doesn't look cold while still resident
                    with self._lock:
                        if (claimed and
                                self._pending_demote.get(row) == name):
                            del self._pending_demote[row]
                        self._shadow.setdefault(row, name)
                if not q:
                    del by_shard[s]
                if done >= over:
                    break

    def poll(self) -> int:
        self.tick()
        return self.drain()

    def start(self, interval_sec: Optional[float] = None) -> None:
        """Start the tiering daemon (no-op when disabled/running)."""
        if not self.enabled or self._thread is not None or self._closed:
            return
        if interval_sec is None:
            interval_sec = tier_tick_ms() / 1000.0
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_sec):
                try:
                    self.poll()
                except Exception:   # pragma: no cover — keep daemon alive
                    pass

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="sentinel-tiering")
        self._thread.start()

    def stop(self) -> None:
        """Idempotent; registered with ``Sentinel.register_shutdown``."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
        if self._closed:
            return
        self._closed = True
        try:
            self._land_all()
        except Exception:   # teardown must not depend on device health
            pass

    # ---- read surface -------------------------------------------------

    def cold_entry(self, name: str) -> Optional[ColdEntry]:
        """A demoted name's state as an entry of its own, the row left
        in the cold tier (a by-name read: ``Sentinel.node_totals``); a
        demote payload still in flight is landed first. None for a
        resident or unknown name. ``tier.materialized`` ticks."""
        if not self.enabled:
            return None
        with self._lock:
            pend = self._pending_land.get(name)
        if pend is not None:
            self._land_one(pend)
        entry = self.cold.get(name)
        if entry is not None:
            self._note_materialized(1)
        return entry

    def cold_rt_hist(self, names, hist_buckets: int) -> np.ndarray:
        """``int32[len(names), hist_buckets]``: the cumulative RT
        histogram of each demoted name, read as ONE column out of the
        blocks — no entry is built, whatever the number of names
        (``Sentinel.rt_hist_by_name``); demote payloads still in flight
        are landed first. Zeros for a resident or unknown name."""
        if not self.enabled:
            return np.zeros((len(names), hist_buckets), np.int32)
        with self._lock:
            pending = self._pending_recs_locked(names)
        for rec in pending:
            self._land_one(rec)
        return self.cold.rt_hist_rows(names, hist_buckets)

    def warm_migration(self, sizes) -> None:
        """Compile the three migration programs (the demotion's gather,
        the invalidate, the promotion's scatter) for evictions and
        promotions of up to each of ``sizes`` rows a drain — they are
        padded to powers of two — before traffic, as a service warms its
        exit sizes: a drain that meets a new size otherwise compiles under
        the engine lock, in the middle of a decide. Pad rows only: the
        state is rewritten with what it held."""
        if not self.enabled:
            return
        sn = self._sentinel
        spec = sn.spec
        no_alt = np.full(pad_pow2(0), spec.alt_rows, np.int32)
        for kp in sorted({pad_pow2(int(k)) for k in sizes}):
            pad_rows = np.full(kp, spec.rows, np.int32)
            with sn._lock:
                _jit_extract(spec)(sn._state, jnp.asarray(pad_rows),
                                   jnp.asarray(no_alt))
                sn._state = sn._jit_invalidate(
                    sn._state, jnp.asarray(pad_rows), jnp.asarray(no_alt))
                self._restore_locked([], rows=kp)
                jax.block_until_ready(sn._state)

    def snapshot(self) -> Dict:
        """The serving-bench artifact / transport-command body."""
        c = self._obs.counters
        with self._lock:
            pend = len(self._land_q)
        p50 = self.migration_hist.percentile(0.50)
        p99 = self.migration_hist.percentile(0.99)
        return {
            "enabled": self.enabled,
            "hot_rows_target": self.hot_rows,
            "resident": len(self._sentinel.resources),
            "cold": len(self.cold),
            "cold_dropped": self.cold.dropped,
            "pending_land": pend,
            "ticks": self._ticks,
            "hot_hit": c.get(obs_keys.TIER_HOT_HIT),
            "cold_miss": c.get(obs_keys.TIER_COLD_MISS),
            "promoted": c.get(obs_keys.TIER_PROMOTED),
            "demoted": c.get(obs_keys.TIER_DEMOTED),
            "materialized": c.get(obs_keys.TIER_MATERIALIZED),
            "sketch_overflow": c.get(obs_keys.TIER_SKETCH_OVERFLOW),
            "tick_estimates": c.get(obs_keys.TIER_TICK_ESTIMATE),
            "migrate_p50_ms": None if p50 is None else p50 / 1e6,
            "migrate_p99_ms": None if p99 is None else p99 / 1e6,
        }

    def hit_rate(self) -> Optional[float]:
        """hot_hit / (hot_hit + cold_miss) — None before any classified
        intern. First-sight registrations count as neither (a brand-new
        key never had state to miss; ``tier.cold_miss`` measures
        hot-tier sizing, not keyspace size — see OPERATIONS.md)."""
        c = self._obs.counters
        h = c.get(obs_keys.TIER_HOT_HIT)
        m = c.get(obs_keys.TIER_COLD_MISS)
        return h / (h + m) if (h + m) else None
