"""Resource / origin registries: string name → dense row id.

The reference keys everything by string resource name inside copy-on-write
maps (``CtSph.lookProcessChain``, ``ClusterBuilderSlot`` resource→ClusterNode)
and hard-caps at 6,000 chains / 2,000 contexts (``Constants.java:37-38``),
silently skipping checks beyond the cap. Here the registry maps names to rows
of the dense counter tensors. Capacity is pre-allocated (tensor shapes are
static under jit); on overflow we evict the least-recently-entered unpinned
row instead of silently disabling checks — strictly better than the
reference's behavior.

Evicted row ids are queued; the runtime drains them via :meth:`drain_evicted`
and invalidates those rows' window state on the next device step (see
``stats.window.invalidate_rows``) so a recycled row never inherits the evicted
resource's live counters.

Row 0 is reserved for the global inbound aggregate (reference
``Constants.ENTRY_NODE``), used by the system-adaptive slot.
"""

from __future__ import annotations

import collections
import threading
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np

ENTRY_NODE_ROW = 0
ENTRY_NODE_NAME = "__entry_node__"


class InternedBatch(NamedTuple):
    """A batch of names after interning, per occurrence and per name."""
    rows: np.ndarray        # int32[n]: the row of every occurrence
    names_u: List[str]      # the d distinct names, first-occurrence order
    rows_u: np.ndarray      # int32[d]: the row of each distinct name
    counts: np.ndarray      # int[d]: occurrences of each distinct name


def intern_batch(names: Sequence[str],
                 intern_one: Callable[[str], int],
                 intern_many: Callable[[Sequence[str]], np.ndarray],
                 distinct_pays: bool) -> InternedBatch:
    """THE dedup of a batch of names — every door that interns a batch
    (``Sentinel.entry_batch_nowait``, ``Sentinel.intern_resources``,
    ``NativeRegistry.get_or_create_batch``) comes through here, and what
    is per NAME downstream (tiering's classification, the counters) runs
    over ``names_u``. Per OCCURRENCE there are two C-speed passes (the
    dict of distinct names, the inverse index) and one NumPy gather; no
    Python-level loop.

    What the registry sees: an all-identical batch (per-resource serving
    loops send ONE name 4k times) is one ``intern_one``; where
    ``distinct_pays`` (hashing a name is ~30x cheaper than encoding and
    marshalling it for the C++ table) a batch of more than 64 names that
    repeats each at least twice on average interns its distinct names in
    first-occurrence order; every other batch interns every occurrence in
    order, and ``rows_u`` is ``rows`` at the first occurrences (a name
    evicted and re-interned inside the batch keeps each occurrence's own
    row)."""
    n = len(names)
    # names.count is a C-speed scan, but of the whole list: look at the
    # far end before paying it on a batch that is not identical
    if (n > 64 and isinstance(names, list) and names[-1] == names[0]
            and names.count(names[0]) == n):
        row = intern_one(names[0])
        return InternedBatch(np.full(n, row, np.int32), names[:1],
                             np.array([row], np.int32), np.array([n]))
    names_u = list(dict.fromkeys(names))
    d = len(names_u)
    pos = dict(zip(names_u, range(d)))
    inv = np.fromiter(map(pos.__getitem__, names), np.intp, count=n)
    if distinct_pays and n > 64 and 2 * d < n:
        rows_u = intern_many(names_u)
        rows = rows_u[inv]
    else:
        rows = intern_many(names)
        first_at = np.empty(d, np.intp)
        # a repeated index keeps its LAST assignment: walk backwards
        first_at[inv[::-1]] = np.arange(n - 1, -1, -1)
        rows_u = rows[first_at]
    return InternedBatch(rows, names_u, rows_u, np.bincount(inv, minlength=d))


class Registry:
    """Thread-safe name→id allocator, O(1) LRU eviction on overflow."""

    def __init__(self, capacity: int, reserved: Iterable[str] = ()):  # rows [0, capacity)
        reserved = tuple(reserved)
        if capacity < 1 + len(reserved):
            raise ValueError("capacity too small")
        self._capacity = capacity
        self._lock = threading.Lock()
        # OrderedDict in LRU order: oldest first; move_to_end on touch.
        self._name_to_id: "collections.OrderedDict[str, int]" = collections.OrderedDict()
        self._id_to_name: List[Optional[str]] = [None] * capacity
        self._next = 0
        self._free: List[int] = []
        self._pinned: set = set()
        self._evicted_pending: List[int] = []
        for name in reserved:
            rid = self._alloc_locked(name)
            self._pinned.add(rid)

    @property
    def capacity(self) -> int:
        return self._capacity

    def _alloc_locked(self, name: str) -> int:
        if self._free:
            rid = self._free.pop()
        elif self._next < self._capacity:
            rid = self._next
            self._next += 1
        else:
            rid = self._evict_locked()
        self._name_to_id[name] = rid
        self._id_to_name[rid] = name
        return rid

    def _evict_locked(self) -> int:
        for victim, rid in self._name_to_id.items():
            if rid not in self._pinned:
                del self._name_to_id[victim]
                self._id_to_name[rid] = None
                self._evicted_pending.append(rid)
                return rid
        raise RuntimeError("registry full and all rows pinned")

    def _get_or_create_locked(self, name: str) -> int:
        rid = self._name_to_id.get(name)
        if rid is None:
            rid = self._alloc_locked(name)
        else:
            self._name_to_id.move_to_end(name)
        return rid

    def get_or_create(self, name: str) -> int:
        with self._lock:
            return self._get_or_create_locked(name)

    def intern_batch(self, names: Sequence[str]) -> InternedBatch:
        """Intern a batch under ONE lock hold, every occurrence in order
        (the LRU order a caller's loop of :meth:`get_or_create` leaves),
        with the distinct view beside the rows: :func:`intern_batch`."""
        return intern_batch(names, self.get_or_create, self._intern_each,
                            distinct_pays=False)

    def _intern_each(self, names: Sequence[str]) -> np.ndarray:
        with self._lock:
            return np.fromiter(map(self._get_or_create_locked, names),
                               np.int32, count=len(names))

    def lookup(self, name: str) -> Optional[int]:
        with self._lock:
            return self._name_to_id.get(name)

    def name_of(self, rid: int) -> Optional[str]:
        with self._lock:
            if 0 <= rid < self._capacity:
                return self._id_to_name[rid]
            return None

    def pin(self, name: str) -> int:
        """Allocate and protect from eviction (rule-referenced resources)."""
        with self._lock:
            rid = self._name_to_id.get(name)
            if rid is None:
                rid = self._alloc_locked(name)
            self._pinned.add(rid)
            return rid

    def unpin(self, name: str) -> None:
        with self._lock:
            rid = self._name_to_id.get(name)
            if rid is not None:
                self._pinned.discard(rid)

    def evict_name(self, name: str) -> bool:
        """Targeted eviction (the tiering ticker's proactive demotion):
        drop ``name``'s row to the free list and queue it for the next
        invalidation drain, exactly as an LRU overflow would. Refuses
        pinned or unknown names."""
        with self._lock:
            rid = self._name_to_id.get(name)
            if rid is None or rid in self._pinned:
                return False
            del self._name_to_id[name]
            self._id_to_name[rid] = None
            self._evicted_pending.append(rid)
            self._free.append(rid)
            return True

    def drain_evicted(self) -> List[int]:
        """Row ids recycled since the last drain; caller must invalidate their
        window state before the rows serve a new resource's decisions."""
        with self._lock:
            out = self._evicted_pending
            self._evicted_pending = []
            return out

    def items(self) -> List[Tuple[str, int]]:
        with self._lock:
            return list(self._name_to_id.items())

    def __len__(self) -> int:
        with self._lock:
            return len(self._name_to_id)


class ResourceRegistry(Registry):
    def __init__(self, capacity: int):
        super().__init__(capacity, reserved=(ENTRY_NODE_NAME,))


class OriginRegistry(Registry):
    """Origin "" (unknown caller) is id 0, parity with empty-origin checks."""

    DEFAULT_ORIGIN_ID = 0

    def __init__(self, capacity: int):
        super().__init__(capacity, reserved=("",))


def make_registry(capacity: int, reserved: Iterable[str] = ()):
    """Registry factory: the C++ table when buildable (g++, cached .so),
    else the pure-Python implementation — identical semantics either way.
    ``SENTINEL_TPU_NATIVE=0`` forces Python."""
    try:
        from sentinel_tpu.native import NativeRegistry, native_available
        if native_available():
            return NativeRegistry(capacity, reserved)
    except Exception:
        pass
    return Registry(capacity, reserved)


def make_resource_registry(capacity: int):
    return make_registry(capacity, reserved=(ENTRY_NODE_NAME,))


def make_origin_registry(capacity: int):
    return make_registry(capacity, reserved=("",))
