"""One dedup for a batch of names (PR 32): ``core.registry.intern_batch``
behind ``Registry.intern_batch`` / ``NativeRegistry.intern_batch``, the
distinct view it hands ``TierManager.note_interned``, and the counters
that say how often it engages.

* the registry sees what it saw before: against a reference that calls
  ``get_or_create`` one name at a time (in the order the parent's
  ``get_or_create_batch`` touched the registry), rows, evictions and the
  table's contents are equal for both registries over seven batch shapes;
* ``note_interned`` over the distinct view leaves the shadow map, the
  demote / promote queues and ``tier.hot_hit`` / ``tier.cold_miss`` where
  the parent's per-occurrence loop (kept here as the reference) leaves
  them, with demotions and promotions in flight;
* one ``entry_batch_nowait`` of 65,536 names with 1,000 distinct counts
  65,536 / 1,000 and says ``distinct=1000`` on its ``entry.prep`` span.
"""

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.core.registry import InternedBatch, Registry
from sentinel_tpu.obs import RuntimeObs
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.tiering.manager import TierManager

RESERVED = ("__r__",)


def _native_registry(capacity):
    native = pytest.importorskip("sentinel_tpu.native")
    if not native.native_available():
        pytest.skip("native library unavailable")
    return native.NativeRegistry(capacity, reserved=RESERVED)


def _python_registry(capacity):
    return Registry(capacity, reserved=RESERVED)


REGISTRIES = {"native": _native_registry, "python": _python_registry}


def _zipf(rng, n, universe, a=1.1):
    p = np.arange(1, universe + 1) ** -a
    return rng.choice(universe, size=n, p=p / p.sum())


# ---- batch shapes: (capacity, names already resident, the batch) --------

def _all_identical(rng):
    return 64, [f"res-{i}" for i in range(20)], ["hot"] * 4096


def _sixty_four(rng):
    return 256, [], [f"res-{i}" for i in rng.integers(0, 24, 64)]


def _zipf_over_full_table(rng):
    universe = [f"key-{i}" for i in range(16384)]
    resident = universe[:4095]                  # + the reserved row: full
    return 4096, resident, [universe[i] for i in _zipf(rng, 8192, 16384)]


def _few_repeats(rng):
    return 512, [f"res-{i}" for i in range(100)], \
        [f"res-{i}" for i in rng.integers(0, 400, 300)]


def _distinct_overflow_capacity(rng):
    # 400 distinct names, three occurrences each, through 127 free rows:
    # names interned early in the batch are evicted by later ones
    return 128, [f"old-{i}" for i in range(127)], \
        [f"new-{i}" for i in rng.permutation(np.repeat(np.arange(400), 3))]


def _evicted_and_reinterned(rng):
    # 7 free rows: "a" is the oldest when x6 needs a row, and comes back
    tail = [f"x{i}" for i in range(7)]
    return 8, [], ["a"] + tail + ["a"] + tail[:3] + ["a", "a"] + tail


def _unicode(rng):
    pool = ["rés-é", "资源-一", "ключ", "🔥-hot", "naïve/path?q=1",
            "a" * 300 + "ß", ""]
    return 64, ["rés-é"], [pool[i] for i in rng.integers(0, len(pool), 500)]


SHAPES = {
    "all_identical_4096": _all_identical,
    "64_names": _sixty_four,
    "zipf_8192_over_full_4096": _zipf_over_full_table,
    "few_repeats": _few_repeats,
    "distinct_overflow_capacity": _distinct_overflow_capacity,
    "evicted_and_reinterned": _evicted_and_reinterned,
    "unicode": _unicode,
}


def _reference(reg, names, dedup_first):
    """The parent's observable behaviour, one ``get_or_create`` at a
    time: every occurrence in order — or, where the registry deduplicated
    before it marshalled (the C++ table, more than 64 names, each
    repeated at least twice on average), the distinct names in
    first-occurrence order."""
    distinct, first_at, counts = [], {}, {}
    for i, s in enumerate(names):
        if s not in first_at:
            first_at[s] = i
            distinct.append(s)
        counts[s] = counts.get(s, 0) + 1
    if dedup_first and len(names) > 64 and 2 * len(distinct) < len(names):
        row_of = {}
        for s in distinct:
            row_of[s] = reg.get_or_create(s)
        rows = [row_of[s] for s in names]
    else:
        rows = [reg.get_or_create(s) for s in names]
    return InternedBatch(
        np.array(rows, np.int32), distinct,
        np.array([rows[first_at[s]] for s in distinct], np.int32),
        np.array([counts[s] for s in distinct]))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", REGISTRIES)
def test_intern_batch_matches_one_name_at_a_time(kind, shape):
    capacity, resident, names = SHAPES[shape](np.random.default_rng(32))
    new, ref = REGISTRIES[kind](capacity), REGISTRIES[kind](capacity)
    for reg in (new, ref):
        for s in resident:
            reg.get_or_create(s)
        assert reg.drain_evicted() == []
    got = new.intern_batch(names)
    want = _reference(ref, names, dedup_first=kind == "native")
    assert got.rows.dtype == np.int32 and got.rows_u.dtype == np.int32
    np.testing.assert_array_equal(got.rows, want.rows)
    assert got.names_u == want.names_u
    np.testing.assert_array_equal(got.rows_u, want.rows_u)
    np.testing.assert_array_equal(got.counts, want.counts)
    assert int(got.counts.sum()) == len(names)
    assert new.drain_evicted() == ref.drain_evicted()
    assert new.items() == ref.items()
    assert len(new) == len(ref)
    if kind == "native":        # the rows alone, for the callers that want them
        twin = REGISTRIES[kind](capacity)
        for s in resident:
            twin.get_or_create(s)
        np.testing.assert_array_equal(twin.get_or_create_batch(names),
                                      want.rows)


def test_shapes_take_the_routes_they_are_named_for():
    """The shapes above are only worth their names if they land on both
    sides of the registry's rule and really evict inside the batch."""
    rng = np.random.default_rng(32)
    _, _, zipf = _zipf_over_full_table(rng)
    assert 2 * len(set(zipf)) < len(zipf)
    _, _, few = _few_repeats(rng)
    assert len(few) > 64 and len(set(few)) < len(few) <= 2 * len(set(few))
    cap, _, over = _distinct_overflow_capacity(rng)
    assert len(set(over)) > cap and 2 * len(set(over)) < len(over)
    cap, _, back = _evicted_and_reinterned(rng)
    got = _python_registry(cap).intern_batch(back)
    rows_of_a = {int(r) for r, s in zip(got.rows, back) if s == "a"}
    assert len(rows_of_a) > 1           # "a" came back at another row
    assert got.rows_u[0] == got.rows[0]


@pytest.mark.parametrize("kind", REGISTRIES)
def test_all_rows_pinned_raises(kind):
    reg = REGISTRIES[kind](2)
    reg.pin("ruled")
    with pytest.raises(RuntimeError, match="all rows pinned"):
        reg.intern_batch(["ruled", "newcomer"])
    with pytest.raises(RuntimeError, match="all rows pinned"):
        reg.intern_batch(["newcomer"] * 100 + ["ruled"] * 100)


@pytest.mark.parametrize("kind", REGISTRIES)
def test_empty_and_non_list_batches(kind):
    reg = REGISTRIES[kind](16)
    got = reg.intern_batch([])
    assert got.rows.shape == (0,) and got.names_u == [] \
        and got.rows_u.shape == (0,) and got.counts.shape == (0,)
    got = reg.intern_batch(("a", "b", "a") * 40)        # a tuple
    assert got.names_u == ["a", "b"] and got.counts.tolist() == [80, 40]
    assert got.rows.tolist() == [reg.lookup("a"), reg.lookup("b"),
                                 reg.lookup("a")] * 40


# ---- note_interned over the distinct view --------------------------------

class _Owner:
    """What a TierManager asks of its Sentinel at intern time."""

    def __init__(self):
        self.clock = ManualClock(start_ms=1_785_000_000_000)
        self.obs = RuntimeObs(clock=self.clock, enabled=True)


def _manager():
    return TierManager(_Owner(), enabled=True)


def _parent_note_interned(t, names, rows, tick=True):
    """``TierManager.note_interned`` as the parent commit had it: one
    pass over the OCCURRENCES. The reference for the distinct view."""
    hot = cold = 0
    seen = {}
    fresh = []
    for i, name in enumerate(names):
        rec = seen.get(name)
        if rec is not None:
            rec[0] += 1
            continue
        row = int(rows[i])
        prev = t._shadow.get(row)
        if prev == name:
            seen[name] = [1, "hot"]
            continue
        t._shadow[row] = name
        if prev is not None:
            t._pending_demote.setdefault(row, prev)
        seen[name] = [1, "new"]
        fresh.append((name, row))
    for name, row in fresh:
        if (name in t.cold or name in t._pending_land
                or any(v == name for v in t._pending_demote.values())):
            t._pending_promote[name] = row
            seen[name][1] = "cold"
    for _name, (cnt, kind) in seen.items():
        if kind == "hot":
            hot += cnt
        elif kind == "cold":
            cold += cnt
    if tick:
        t._obs.counters.add(ck.TIER_HOT_HIT, hot)
        t._obs.counters.add(ck.TIER_COLD_MISS, cold)


def _tier_state(t):
    return (dict(t._shadow), dict(t._pending_demote),
            dict(t._pending_promote),
            t._obs.counters.get(ck.TIER_HOT_HIT),
            t._obs.counters.get(ck.TIER_COLD_MISS))


def _drain(t, reg_evicted, move):
    """What the eviction drain and the ticker do to the host queues, cut
    to what classification reads: a drained victim's payload is in
    flight (``_pending_land``) or landed (``cold``), a queued promotion
    is consumed. ``move`` decides, the same for both twins."""
    for row in reg_evicted:
        name = t._pending_demote.pop(row, None)
        if name is None:
            continue
        if move[row % len(move)]:
            t._pending_land[name] = {}
        else:
            t.cold.put(name, object())
    for name in list(t._pending_land):
        if move[len(name) % len(move)]:
            t.cold.put(name, t._pending_land.pop(name))
    for name in list(t._pending_promote):
        t._pending_promote.pop(name)
        t.cold.pop(name)
        t._pending_land.pop(name, None)


@pytest.mark.parametrize("kind", REGISTRIES)
@pytest.mark.parametrize("seed", [1, 2, 3, 1602])
def test_note_interned_distinct_view_matches_parent_loop(kind, seed):
    """A hot tier of 24 rows under Zipf batches over 96 names: every
    batch evicts, some names leave and come back inside one batch, and
    the drain between batches leaves demotes in flight, landed, and
    promotions queued."""
    rng = np.random.default_rng(seed)
    reg = REGISTRIES[kind](24)
    new, ref = _manager(), _manager()
    for step in range(60):
        n = int(rng.choice([1, 8, 64, 65, 200, 600]))
        names = [f"k{i}" for i in _zipf(rng, n, 96)]
        batch = reg.intern_batch(names)
        new.note_interned(batch.names_u, batch.rows_u, batch.counts,
                          tick=step % 7 != 3)
        _parent_note_interned(ref, names, batch.rows, tick=step % 7 != 3)
        assert _tier_state(new) == _tier_state(ref), step
        if step % 3 == 2:
            evicted = reg.drain_evicted()
            move = rng.random(5) < 0.5
            _drain(new, evicted, move)
            _drain(ref, evicted, move)
    hot, cold = _tier_state(new)[3:]
    assert hot > 0 and cold > 0         # the sequence reached both classes
    assert len(new.cold) == len(ref.cold)


ORDERS = {   # occurrences (name, row) → the distinct view of the same batch
    "displacer_first": (["A", "B", "A", "B", "A"], [5, 9, 5, 9, 5],
                        ["A", "B"], [5, 9], [3, 2]),
    "displaced_first": (["B", "A", "B", "A", "A"], [9, 5, 9, 5, 5],
                        ["B", "A"], [9, 5], [2, 3]),
}


@pytest.mark.parametrize("order", ORDERS)
def test_classification_does_not_depend_on_order_inside_the_batch(order):
    """A's fresh row displaces B, and B is in the same batch at a new
    row: B must read as a cold miss (its state is about to be demoted
    from the row A took) whichever of the two comes first."""
    names, rows, names_u, rows_u, counts = ORDERS[order]
    new, ref = _manager(), _manager()
    for t in (new, ref):
        t.note_interned(["B"], [5])                     # B owns row 5
    new.note_interned(names_u, np.array(rows_u, np.int32), np.array(counts))
    _parent_note_interned(ref, names, rows)
    assert _tier_state(new) == _tier_state(ref)
    assert new._pending_demote == {5: "B"}
    assert new._pending_promote == {"B": 9}
    assert new._shadow == {5: "A", 9: "B"}
    assert new._obs.counters.get(ck.TIER_COLD_MISS) == 2    # B's two
    assert new._obs.counters.get(ck.TIER_HOT_HIT) == 0      # A is new


def test_small_callers_pass_one_occurrence_each():
    """The scalar door and the rule-pin path hand plain tuples / lists and
    no counts: one occurrence a name; ``tick=False`` counts nothing."""
    t = _manager()
    t.note_interned(("api",), (3,))
    t.note_interned(("api",), (3,))
    t.note_interned(["api", "other"], [3, 4], tick=False)
    assert t._shadow == {3: "api", 4: "other"}
    assert t._obs.counters.get(ck.TIER_HOT_HIT) == 1
    off = TierManager(_Owner(), enabled=False)
    off.note_interned(("api",), (3,))
    assert off._shadow == {}


# ---- the mechanism engages, and is counted -------------------------------

def _prep_spans(sph):
    return [s for s in sph.obs.spans.snapshot(limit=4096)
            if s["name"] == "entry.prep"]


def test_entry_batch_counts_names_and_distinct_and_notes_the_span():
    clk = ManualClock(start_ms=1_785_000_000_000)
    sph = stpu.Sentinel(config=stpu.load_config(
        max_resources=2048, max_origins=32, max_flow_rules=32,
        max_degrade_rules=16, max_authority_rules=16,
        host_fast_path=False), clock=clk)
    try:
        sph.load_flow_rules([stpu.FlowRule(resource="n0", count=5.0)])
        rng = np.random.default_rng(32)
        names = [f"n{i}" for i in rng.integers(0, 1000, 65536)]
        names[:1000] = [f"n{i}" for i in range(1000)]   # every one of them
        verdicts = sph.entry_batch_nowait(names).result()
        assert np.asarray(verdicts.allow).shape == (65536,)
        counts = sph.obs.counters
        assert counts.get(ck.INTERN_NAMES) == 65536
        assert counts.get(ck.INTERN_DISTINCT) == 1000
        (span,) = _prep_spans(sph)
        assert span["n"] == 65536 and span["note"] == "distinct=1000"
        # the same names again: all resident now, every occurrence a hit
        hits = counts.get(ck.TIER_HOT_HIT)
        rows = sph.intern_resources(names)
        assert counts.get(ck.TIER_HOT_HIT) - hits == 65536
        assert counts.get(ck.INTERN_NAMES) == 2 * 65536
        assert counts.get(ck.INTERN_DISTINCT) == 2000
        assert rows.dtype == np.int32 and rows.shape == (65536,)
        assert [sph.resources.name_of(int(r)) for r in rows[:50]] \
            == names[:50]
        # pre-interned rows skip interning: neither counter moves, and
        # the span says nothing of distinct names
        sph.entry_batch_nowait(rows[:4096]).result()
        assert counts.get(ck.INTERN_NAMES) == 2 * 65536
        assert counts.get(ck.INTERN_DISTINCT) == 2000
        assert [s["note"] for s in _prep_spans(sph)] == ["distinct=1000", ""]
    finally:
        sph.close()
