"""Host-memory cold tier: evicted rows' complete state, by name.

A demotion record lands as ONE :class:`ColdBlock` — the host arrays of
``engine.pipeline.extract_resource_rows`` as they come off the device,
row ``i`` of every column being victim ``i``'s slice of everything
``invalidate_resource_rows`` would have destroyed: second/minute window
slices, the thread gauge, the occupy booking ring, the cumulative RT
histogram, and the hashed alt (resource × origin/context) slices with
their HOST identity ``(victim, kind, key_id)`` so promotion can re-hash
them onto the new row's slots. :class:`ColdTier` indexes ``name →
(block, row)``; nothing is copied or built per victim. Promotion pops
row references and gathers each payload column block by block
(tiering/manager.py); a by-name read materialises ONE
:class:`ColdEntry` from its block row. Window stamps and booking target
windows are absolute indices, so a row is time-portable: restored at
any later instant it reads exactly as the live row would have.

The one transform a row may need before restore is the rule-reload
replay: ``Sentinel.load_flow_rules`` settles every RESIDENT row's
landed occupy bookings into its second window (``settle_occupied``)
and carries pending ones into the fresh ring. A row that was cold at
reload time missed that settle, so :func:`settle_entry_np` replays it
host-side on the materialised entry — a numpy port of
``stats.window.settle_occupied`` (integer and float32 adds only,
bit-identical by construction; pinned by tests/test_tiering.py) — once
per reload the row slept through, each with THAT reload's ``now_idx``.
After the replay the restored row is bit-identical to one that stayed
resident.

Capacity: unbounded by default (the whole point — key cardinality is no
longer table-bound); ``SENTINEL_TIER_COLD_MAX`` bounds the NAMES the
index holds by dropping the oldest (a dropped key re-enters as a fresh
resource, the pre-round-15 behavior). A block is released when its last
name has left; blocks are not compacted.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

NEVER = -(2 ** 30)
_I32MAX = np.iinfo(np.int32).max


@dataclass
class ColdEntry:
    """One demoted resource's host-side state (numpy, device-free)."""

    # second window slice: counters [B, E], stamps [B], rt_sum/min_rt [B_rt]
    sec_counters: np.ndarray
    sec_stamps: np.ndarray
    sec_rt_sum: np.ndarray
    sec_min_rt: np.ndarray
    # minute window slice (empty arrays when the minute ring is disabled)
    min_counters: np.ndarray
    min_stamps: np.ndarray
    min_rt_sum: np.ndarray
    min_min_rt: np.ndarray
    threads: int
    occ_cnt: np.ndarray            # float32 [B+1]
    occ_win: np.ndarray            # int32 [B+1]
    # (kind, key_id) → (counters [B,E], stamps [B], rt_sum, min_rt, threads)
    alts: Dict[Tuple[int, int], tuple] = field(default_factory=dict)
    reload_gen: int = 0            # flow reloads seen BEFORE demotion
    demoted_ms: int = 0
    # round 20: cumulative per-resource RT histogram row (int32 [HB]);
    # None when the engine has no histogram table or the entry predates
    # the feature. Time-portable by construction (no stamps): it rides
    # demote→promote untouched, and reset_entry_geometry_np deliberately
    # carries it over — the table is cumulative-forever, not windowed.
    rt_hist: Optional[np.ndarray] = None

    def rolling_totals(self, buckets: int,
                       now_idx: int) -> Tuple[np.ndarray, float]:
        """What ``stats.window.rolling_totals`` / ``rt_totals`` would read
        from this entry's row at ``now_idx`` had it stayed resident →
        (int[E] event totals over the live second buckets, their RT sum).
        A bucket is live while ``0 <= now_idx - stamp < buckets`` (int32
        wraparound-safe, as on the device)."""
        delta = np.int32(now_idx) - self.sec_stamps
        live = (delta >= 0) & (delta < buckets)
        rt = float(self.sec_rt_sum[live].sum()) if self.sec_rt_sum.size \
            else 0.0
        return self.sec_counters[live].sum(axis=0), rt


def settle_entry_np(buckets: int, entry: ColdEntry, now_idx: int,
                    event: int) -> None:
    """In-place replay of one missed flow-rule reload on a cold entry —
    the numpy mirror of ``stats.window.settle_occupied`` for a single
    row. LANDED bookings (``0 <= now_idx - w < buckets``) credit
    ``event`` counts into their target bucket (dead buckets reset and
    restamp first), PENDING ones (``now_idx - w == -1``) survive in the
    ring, anything older expires — exactly what the resident rows got
    from ``_jit_settle_occupied`` at that reload."""
    B = buckets
    track_rt = entry.sec_rt_sum.shape[0] > 0
    pend_cnt = np.zeros_like(entry.occ_cnt)
    pend_win = np.full_like(entry.occ_win, NEVER)
    for s in range(entry.occ_cnt.shape[0]):
        w = int(entry.occ_win[s])
        c = entry.occ_cnt[s]
        age = np.int32(now_idx) - np.int32(w)   # wraparound-safe diff
        if age >= 0 and age < B and c > 0:      # landed
            k = w % B
            if entry.sec_stamps[k] != np.int32(w):   # dead bucket: reset
                entry.sec_counters[k, :] = 0
                if track_rt:
                    entry.sec_rt_sum[k] = 0.0
                    entry.sec_min_rt[k] = _I32MAX
                entry.sec_stamps[k] = np.int32(w)
            entry.sec_counters[k, event] += np.int32(c)
        elif age == -1 and c > 0:               # pending: carry
            pend_cnt[s] = c
            pend_win[s] = w
    entry.occ_cnt = pend_cnt
    entry.occ_win = pend_win


def fresh_window(n: int, buckets: int, events: int, rt_buckets: int) -> tuple:
    """``n`` rows of a never-written second window (``init_window``'s
    values): counters, stamps, rt_sum, min_rt."""
    return (np.zeros((n, buckets, events), np.int32),
            np.full((n, buckets), NEVER, np.int32),
            np.zeros((n, rt_buckets), np.float32),
            np.full((n, rt_buckets), _I32MAX, np.int32))


@dataclass(eq=False, slots=True, kw_only=True)
class ColdBlock:
    """One demotion record on the host, columnar: row ``i`` of every
    array is victim ``i``'s slice (rows past the victims are the
    gather's padding). ``second`` / ``minute`` / ``alt_second`` are
    ``(counters, stamps, rt_sum, min_rt)``; ``alt_ids[j]`` is the
    ``(victim, kind, key_id)`` identity of alt row ``j``. The arrays are
    the device readback itself — read-only, shared by every name of the
    record, never copied while the names stay cold."""

    second: tuple
    minute: tuple
    threads: np.ndarray            # int32[n]
    occ_cnt: np.ndarray            # float32[n, B+1]
    occ_win: np.ndarray            # int32[n, B+1]
    rt_hist: Optional[np.ndarray]  # int32[n, HB]; None: no histogram table
    alt_second: tuple
    alt_threads: np.ndarray        # int32[alt rows]
    alt_ids: list
    reload_gen: int = 0            # flow reloads seen BEFORE demotion
    demoted_ms: int = 0

    @classmethod
    def of_entry(cls, e: ColdEntry) -> "ColdBlock":
        """A block of one row: ``e``'s arrays under a leading axis
        (views), its alt slices stacked."""
        alts = list(e.alts.items())
        if alts:
            alt_second = tuple(np.stack([a[c] for _ident, a in alts])
                               for c in range(4))
        else:
            alt_second = fresh_window(0, *e.sec_counters.shape,
                                       e.sec_rt_sum.shape[0])
        return cls(
            second=(e.sec_counters[None], e.sec_stamps[None],
                    e.sec_rt_sum[None], e.sec_min_rt[None]),
            minute=(e.min_counters[None], e.min_stamps[None],
                    e.min_rt_sum[None], e.min_min_rt[None]),
            threads=np.array([e.threads], np.int32),
            occ_cnt=e.occ_cnt[None], occ_win=e.occ_win[None],
            rt_hist=None if e.rt_hist is None else e.rt_hist[None],
            alt_second=alt_second,
            alt_threads=np.array([a[4] for _ident, a in alts], np.int32),
            alt_ids=[(0, kind, key_id) for (kind, key_id), _a in alts],
            reload_gen=e.reload_gen, demoted_ms=e.demoted_ms)

    def entry(self, i: int) -> ColdEntry:
        """Row ``i`` as a :class:`ColdEntry` of its own (copies): the
        slow form, for a by-name read or a reload replay."""
        sec, mnt, alt = self.second, self.minute, self.alt_second
        return ColdEntry(
            sec_counters=sec[0][i].copy(), sec_stamps=sec[1][i].copy(),
            sec_rt_sum=sec[2][i].copy(), sec_min_rt=sec[3][i].copy(),
            min_counters=mnt[0][i].copy(), min_stamps=mnt[1][i].copy(),
            min_rt_sum=mnt[2][i].copy(), min_min_rt=mnt[3][i].copy(),
            threads=int(self.threads[i]),
            occ_cnt=self.occ_cnt[i].copy(), occ_win=self.occ_win[i].copy(),
            alts={(kind, key_id): (alt[0][j].copy(), alt[1][j].copy(),
                                   alt[2][j].copy(), alt[3][j].copy(),
                                   int(self.alt_threads[j]))
                  for j, (vi, kind, key_id) in enumerate(self.alt_ids)
                  if vi == i},
            reload_gen=self.reload_gen, demoted_ms=self.demoted_ms,
            rt_hist=None if self.rt_hist is None else self.rt_hist[i].copy())

    def reset_geometry(self, buckets: int) -> None:
        """Second-window cold-reset of every row to a NEW bucket count —
        the cold-tier mirror of ``runtime.update_window_geometry``,
        which swaps fresh second windows, booking rings, and flow
        shaping state into every RESIDENT row while the minute ring,
        thread gauges and the cumulative RT histogram carry over. A cold
        row gets exactly the same treatment so a later promote (a)
        scatters shapes that match the new spec and (b) restores the row
        bit-identical to one that stayed resident through the change.
        ``reload_gen`` rewinds to 0: the manager clears its
        reload-replay log at a geometry change (pre-change reloads
        settled into buckets that no longer exist, and the reset rows
        have nothing left to settle)."""
        B = int(buckets)
        n, _b, ne = self.second[0].shape
        brt = B if self.second[2].shape[1] else 0
        self.second = fresh_window(n, B, ne, brt)
        self.occ_cnt = np.zeros((n, B + 1), np.float32)
        self.occ_win = np.full((n, B + 1), NEVER, np.int32)
        self.alt_second = fresh_window(self.alt_threads.shape[0], B, ne, brt)
        self.reload_gen = 0


# An index value is ``block number << _ROW_BITS | row``: an int, not a
# (block, row) tuple — the index holds every cold name and grows by
# thousands a batch, and an int is not an object the cycle collector
# has to walk.
_ROW_BITS = 32
_ROW_MASK = (1 << _ROW_BITS) - 1


class ColdTier:
    """Locked ``name → (block, row)`` index over the landed
    :class:`ColdBlock` s, with an optional bound on the names it holds
    (oldest dropped first)."""

    def __init__(self, max_entries: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        self._index: Dict[str, int] = {}        # oldest name first
        # block number → [block, names still indexed into it]
        self._blocks: Dict[int, list] = {}
        self._next_no = 0
        self._max = max_entries if max_entries and max_entries > 0 else None
        self._dropped = 0

    def put_block(self, block: ColdBlock, names) -> None:
        """Index ``names[i]`` → row ``i`` of ``block``, all under one
        acquisition of the lock. A name the index already holds (demoted
        again before it came back) moves to the newer state."""
        with self._lock:
            self._put_locked(block, names)

    def put(self, name: str, entry: ColdEntry) -> None:
        """One name's state: a block of one row, cut from ``entry`` when
        a block reader first asks for it."""
        with self._lock:
            self._put_locked(entry, (name,))

    def _put_locked(self, block, names) -> None:
        no = self._next_no
        base = no << _ROW_BITS
        refs = dict(zip(names, range(base, base + len(names))))
        if not refs:
            return
        self._next_no += 1
        idx = self._index
        for old in [idx.pop(n) for n in refs if n in idx]:
            self._release_locked(old >> _ROW_BITS, 1)
        idx.update(refs)
        self._blocks[no] = [block, len(refs)]
        if self._max is not None and len(idx) > self._max:
            over = len(idx) - self._max
            for name in list(itertools.islice(idx, over)):
                self._release_locked(idx.pop(name) >> _ROW_BITS, 1)
            self._dropped += over

    def _release_locked(self, no: int, k: int) -> None:
        """``k`` names left block ``no``; the last one out frees it."""
        slot = self._blocks[no]
        slot[1] -= k
        if not slot[1]:
            del self._blocks[no]

    def _block_locked(self, no: int) -> ColdBlock:
        slot = self._blocks[no]
        if not isinstance(slot[0], ColdBlock):  # put()'s entry, still uncut
            slot[0] = ColdBlock.of_entry(slot[0])
        return slot[0]

    def _entry_locked(self, ref: int) -> ColdEntry:
        block = self._blocks[ref >> _ROW_BITS][0]
        if not isinstance(block, ColdBlock):
            return block                        # as put() was given it
        return block.entry(ref & _ROW_MASK)

    def _group_locked(self, refs: np.ndarray):
        """``refs[j]`` (-1: unknown name) → ``(block number, rows,
        js)`` per block, ``js`` the positions in ``refs``."""
        js = np.nonzero(refs >= 0)[0]
        nos = refs[js] >> _ROW_BITS
        order = np.argsort(nos, kind="stable")
        js, nos = js[order], nos[order]
        for part in np.split(js, np.nonzero(np.diff(nos))[0] + 1):
            if part.size:
                yield (int(refs[part[0]] >> _ROW_BITS),
                       refs[part] & _ROW_MASK, part)

    def pop(self, name: str) -> Optional[ColdEntry]:
        with self._lock:
            ref = self._index.pop(name, None)
            if ref is None:
                return None
            entry = self._entry_locked(ref)
            self._release_locked(ref >> _ROW_BITS, 1)
            return entry

    def get(self, name: str) -> Optional[ColdEntry]:
        """The name's state as an entry of its own, the row left where
        it is (by-name reads of a cold key)."""
        with self._lock:
            ref = self._index.get(name)
            return None if ref is None else self._entry_locked(ref)

    def pop_rows(
            self, names) -> List[Tuple[ColdBlock, np.ndarray, np.ndarray]]:
        """Take ``names`` out of the index → ``(block, rows, js)`` per
        block: ``names[js[k]]`` was row ``rows[k]`` of ``block``. A name
        the index does not hold (dropped by the bound) is in no group.
        The promotion's read: no entry is built."""
        with self._lock:
            pop = self._index.pop
            refs = np.fromiter((pop(n, -1) for n in names), np.int64,
                               len(names))
            out = []
            for no, rows, js in self._group_locked(refs):
                out.append((self._block_locked(no), rows, js))
                self._release_locked(no, js.size)
            return out

    def rt_hist_rows(self, names, hist_buckets: int) -> np.ndarray:
        """``int32[len(names), hist_buckets]``: each name's cumulative RT
        histogram read as one column, block by block; zeros for a name
        the index does not hold (or a block without the table)."""
        out = np.zeros((len(names), hist_buckets), np.int32)
        with self._lock:
            get = self._index.get
            refs = np.fromiter((get(n, -1) for n in names), np.int64,
                               len(names))
            groups = [(self._block_locked(no), rows, js)
                      for no, rows, js in self._group_locked(refs)]
        for block, rows, js in groups:      # the column is never rewritten
            col = block.rt_hist
            if col is not None and col.shape[1] == hist_buckets:
                out[js] = col[rows]
        return out

    def convert_geometry(self, buckets: int) -> None:
        """Cold-reset every block's second windows + booking ring to a
        new bucket count (live geometry change); see
        :meth:`ColdBlock.reset_geometry`."""
        with self._lock:
            for no in list(self._blocks):
                self._block_locked(no).reset_geometry(buckets)

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._index

    def __len__(self) -> int:
        with self._lock:
            return len(self._index)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def names(self, limit: int = 32) -> List[str]:
        """The newest ``limit`` names, newest first."""
        with self._lock:
            return list(itertools.islice(reversed(self._index), limit))
