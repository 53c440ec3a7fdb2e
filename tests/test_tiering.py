"""Round-15 tiered resource state (tiering/): sketch math, cold-entry
reload replay parity against the device settle, registry targeted
eviction, rule-pin refcounts across families, lifecycle counters, and
the load-bearing property — a small tiered engine is BIT-IDENTICAL in
verdicts to an all-resident engine under churn, flow rules, occupy
bookings, per-origin alt rows, and a mid-run rule reload.
"""

import re

import numpy as np
import pytest
import jax.numpy as jnp

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.core.config import load_config
from sentinel_tpu.core.registry import ENTRY_NODE_ROW, Registry
from sentinel_tpu.runtime import Sentinel
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    INT32_MAX, NEVER, WindowSpec, WindowState, settle_occupied,
)
from sentinel_tpu.tiering import sketch as sk
from sentinel_tpu.tiering.coldtier import (
    ColdBlock, ColdEntry, ColdTier, settle_entry_np,
)


# ---------------------------------------------------------------------------
# sketch
# ---------------------------------------------------------------------------

def test_sketch_never_underestimates():
    # count-min guarantee: estimate(x) >= true count (one occurrence per
    # update so conservative-update's in-batch dedup doesn't apply)
    counts = sk.init_sketch(4, 8)
    rng = np.random.default_rng(3)
    true = {}
    for _ in range(200):
        item = int(rng.integers(0, 50))
        true[item] = true.get(item, 0) + 1
        counts, _ = sk.update_sketch(
            counts, jnp.asarray([item], jnp.int32),
            jnp.asarray([True]))
    items = jnp.asarray(sorted(true), jnp.int32)
    est = np.asarray(sk._estimates(counts, sk._bucket_idx(counts, items)))
    for i, item in enumerate(sorted(true)):
        assert est[i] >= true[item]


def test_sketch_impls_identical():
    rng = np.random.default_rng(9)
    items = jnp.asarray(rng.integers(0, 1 << 16, size=64), jnp.int32)
    valid = jnp.asarray(rng.random(64) < 0.9)
    outs = []
    for impl in sk.SKETCH_IMPLS:
        counts = sk.init_sketch(4, 10)
        for _ in range(3):
            counts, _ = sk.update_sketch(counts, items, valid, impl=impl)
        outs.append(np.asarray(counts))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_sketch_invalid_lanes_are_noops():
    counts = sk.init_sketch(2, 6)
    items = jnp.asarray([5, 7], jnp.int32)
    counts, _ = sk.update_sketch(counts, items,
                                 jnp.asarray([False, False]))
    assert int(np.asarray(counts).max()) == 0


def test_sketch_decay_and_halve():
    counts = jnp.full((2, 16), 800, jnp.int32)
    decayed = np.asarray(sk.decay_sketch(counts))
    np.testing.assert_array_equal(decayed, 800 - (800 >> sk.DECAY_SHIFT))
    halved = np.asarray(sk.halve_sketch(counts))
    np.testing.assert_array_equal(halved, 400)
    # zero stays zero under both (idle buckets never go negative)
    z = jnp.zeros((2, 16), jnp.int32)
    assert int(np.asarray(sk.decay_sketch(z)).max()) == 0


def test_sketch_overflow_flag():
    counts = jnp.full((2, 16), sk.OVERFLOW_CAP - 1, jnp.int32)
    _, overflow = sk.update_sketch(counts, jnp.asarray([3], jnp.int32),
                                   jnp.asarray([True]))
    assert bool(overflow)
    counts = jnp.zeros((2, 16), jnp.int32)
    _, overflow = sk.update_sketch(counts, jnp.asarray([3], jnp.int32),
                                   jnp.asarray([True]))
    assert not bool(overflow)


# ---------------------------------------------------------------------------
# cold-entry reload replay: numpy mirror vs device settle, bit-identical
# ---------------------------------------------------------------------------

def _entry_from_row(counters, stamps, rt_sum, min_rt, occ_cnt, occ_win):
    z = np.zeros(0, np.int32)
    return ColdEntry(
        sec_counters=counters.copy(), sec_stamps=stamps.copy(),
        sec_rt_sum=rt_sum.copy(), sec_min_rt=min_rt.copy(),
        min_counters=z.reshape(0, 0, 0).astype(np.int32),
        min_stamps=z, min_rt_sum=z.astype(np.float32), min_min_rt=z,
        threads=0, occ_cnt=occ_cnt.copy(), occ_win=occ_win.copy())


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_settle_entry_np_matches_device_settle(seed):
    """settle_entry_np is pinned bit-identical to stats.window
    settle_occupied for a single row across landed-live, landed-dead,
    pending, and expired bookings."""
    spec = WindowSpec(buckets=4, win_ms=500, track_rt=True)
    B = spec.buckets
    rng = np.random.default_rng(seed)
    now = 3_570_000 + int(rng.integers(0, 100))
    counters = rng.integers(0, 50, size=(1, B, ev.NUM_EVENTS)).astype(np.int32)
    # each bucket: stamped near now, or dead (stale stamp), or NEVER
    stamps = np.empty((1, B), np.int32)
    for k in range(B):
        stamps[0, k] = rng.choice(
            [now - rng.integers(0, B), now - 2 * B, NEVER])
    rt_sum = rng.random((1, B)).astype(np.float32) * 100
    min_rt = rng.integers(1, 1000, size=(1, B)).astype(np.int32)
    # bookings spanning expired (<= now-B), landed, pending (now+1)
    occ_win = (now + rng.integers(-2 * B, 2, size=(1, B + 1))).astype(np.int32)
    occ_cnt = rng.integers(0, 4, size=(1, B + 1)).astype(np.float32)

    state = WindowState(jnp.asarray(counters), jnp.asarray(stamps),
                        jnp.asarray(rt_sum), jnp.asarray(min_rt))
    ref_state, ref_pc, ref_pw = settle_occupied(
        spec, state, jnp.asarray(occ_cnt), jnp.asarray(occ_win),
        jnp.int32(now), ev.PASS)

    entry = _entry_from_row(counters[0], stamps[0], rt_sum[0], min_rt[0],
                            occ_cnt[0], occ_win[0])
    settle_entry_np(B, entry, now, ev.PASS)

    np.testing.assert_array_equal(entry.sec_counters,
                                  np.asarray(ref_state.counters)[0])
    np.testing.assert_array_equal(entry.sec_stamps,
                                  np.asarray(ref_state.stamps)[0])
    np.testing.assert_array_equal(entry.sec_rt_sum,
                                  np.asarray(ref_state.rt_sum)[0])
    np.testing.assert_array_equal(entry.sec_min_rt,
                                  np.asarray(ref_state.min_rt)[0])
    np.testing.assert_array_equal(entry.occ_cnt, np.asarray(ref_pc)[0])
    np.testing.assert_array_equal(entry.occ_win, np.asarray(ref_pw)[0])


def test_settle_entry_np_dead_bucket_reset():
    # a landed booking into a rotated bucket resets ALL lanes + rt first
    B = 2
    now = 1000
    entry = _entry_from_row(
        np.full((B, ev.NUM_EVENTS), 7, np.int32),
        np.asarray([now - 2 * B, now - 2 * B], np.int32),   # both dead
        np.asarray([5.0, 5.0], np.float32),
        np.asarray([9, 9], np.int32),
        np.asarray([3.0, 0.0, 0.0], np.float32),
        np.asarray([now, NEVER, NEVER], np.int32))
    settle_entry_np(B, entry, now, ev.PASS)
    k = now % B
    assert entry.sec_stamps[k] == now
    assert entry.sec_counters[k, ev.PASS] == 3        # reset then credited
    assert entry.sec_counters[k, ev.BLOCK] == 0
    assert entry.sec_rt_sum[k] == 0.0
    assert entry.sec_min_rt[k] == INT32_MAX
    # untouched bucket keeps its (stale) contents
    other = 1 - k
    assert entry.sec_counters[other, ev.PASS] == 7
    assert not entry.occ_cnt.any()


# ---------------------------------------------------------------------------
# registry: targeted eviction + cross-family pin refcounts
# ---------------------------------------------------------------------------

def test_registry_evict_name():
    reg = Registry(8, reserved=("E",))
    ra, rb = reg.get_or_create("a"), reg.get_or_create("b")
    reg.pin("a")
    assert not reg.evict_name("a")          # pinned
    assert not reg.evict_name("ghost")      # unknown
    assert reg.evict_name("b")
    assert reg.lookup("b") is None
    assert rb in reg.drain_evicted()        # queued for invalidate
    assert reg.get_or_create("c") == rb     # freed row is reused
    reg.unpin("a")
    assert reg.evict_name("a")
    assert ra in reg.drain_evicted()


def test_rule_pins_are_refcounted_across_families(monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=16, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        s.load_flow_rules([stpu.FlowRule(resource="k", count=10.0)])
        s.load_degrade_rules([stpu.DegradeRule(
            resource="k", grade=stpu.GRADE_RT, count=50.0, time_window=5)])
        assert not s.resources.evict_name("k")      # pinned by both
        s.load_flow_rules([])
        assert not s.resources.evict_name("k")      # degrade still holds
        s.load_degrade_rules([])
        assert s.resources.evict_name("k")          # last family released
    finally:
        s.close()


# ---------------------------------------------------------------------------
# cold tier store
# ---------------------------------------------------------------------------

def _dummy_entry():
    return _entry_from_row(
        np.zeros((2, ev.NUM_EVENTS), np.int32),
        np.full(2, NEVER, np.int32), np.zeros(2, np.float32),
        np.full(2, INT32_MAX, np.int32),
        np.zeros(3, np.float32), np.full(3, NEVER, np.int32))


def test_cold_tier_lru_bound():
    tier = ColdTier(max_entries=2)
    for n in ("a", "b", "c"):
        tier.put(n, _dummy_entry())
    assert len(tier) == 2
    assert tier.dropped == 1
    assert "a" not in tier                  # oldest dropped
    assert tier.pop("a") is None
    assert tier.pop("c") is not None
    # unbounded by default
    tier = ColdTier(None)
    for i in range(64):
        tier.put(f"n{i}", _dummy_entry())
    assert len(tier) == 64 and tier.dropped == 0


# ---------------------------------------------------------------------------
# lifecycle counters: first-sight neither, hit, demote → cold → promote
# ---------------------------------------------------------------------------

def test_lifecycle_counters_and_hit_rate(monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=32, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        t = s.tiering
        assert t.enabled
        names = [f"r{i}" for i in range(6)]
        s.entry_batch(names, acquire=[1] * 6)
        snap = t.snapshot()
        # brand-new keys are neither hits nor misses
        assert snap["hot_hit"] == 0 and snap["cold_miss"] == 0
        assert t.hit_rate() is None
        s.entry_batch(names, acquire=[1] * 6)
        snap = t.snapshot()
        assert snap["hot_hit"] == 6 and snap["cold_miss"] == 0
        assert t.hit_rate() == 1.0
        # demote r0: targeted evict, then any entry call runs the drain
        assert s.resources.evict_name("r0")
        s.entry_batch(["r1"], acquire=[1])
        assert t.snapshot()["demoted"] == 1
        t.poll()                             # land the payload off-lock
        assert "r0" in t.cold
        # re-intern: cold miss, promoted inside the SAME entry call
        s.entry_batch(["r0"], acquire=[1])
        snap = t.snapshot()
        assert snap["cold_miss"] == 1
        assert snap["promoted"] == 1
        assert "r0" not in t.cold
        assert snap["migrate_p50_ms"] is not None
    finally:
        s.close()


def test_tiering_disable_env(monkeypatch):
    monkeypatch.setenv("SENTINEL_TIERING_DISABLE", "1")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=8, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        assert not s.tiering.enabled
        s.tiering.start()
        assert s.tiering._thread is None     # start is a no-op
        with s.entry("a"):
            pass
        snap = s.tiering.snapshot()
        assert snap["enabled"] is False
        assert snap["demoted"] == 0 and snap["promoted"] == 0
    finally:
        s.close()


# ---------------------------------------------------------------------------
# the load-bearing property: tiered == all-resident, bit for bit
# ---------------------------------------------------------------------------

def _run_engine(capacity, steps, batch, keys, rules, reload_rules,
                seed, origins=None, geometry_step=None):
    """Seeded churn traffic against one engine; returns (verdict triples,
    tiering snapshot). Reload fires mid-run; ~25% of requests are
    prioritized so occupy bookings ride through demote/promote."""
    clk = ManualClock(start_ms=1_785_000_000_000)
    s = Sentinel(load_config(max_resources=capacity, max_flow_rules=16,
                             max_degrade_rules=16, max_authority_rules=16,
                             host_fast_path=False), clock=clk)
    try:
        s.load_flow_rules(rules)
        rng = np.random.default_rng(seed)
        verdicts = []
        for step in range(steps):
            if step == steps // 2:
                s.load_flow_rules(reload_rules)
            if geometry_step is not None and step == geometry_step:
                s.update_window_geometry(sample_count=4)
            names = list(rng.choice(keys, size=batch, replace=False))
            prio = list(rng.random(batch) < 0.25)
            kw = {}
            if origins is not None:
                kw["origins"] = list(rng.choice(origins, size=batch))
            v = s.entry_batch(names, acquire=[1] * batch,
                              prioritized=prio, **kw)
            verdicts.append((np.asarray(v.allow).copy(),
                             np.asarray(v.reason).copy(),
                             np.asarray(v.wait_ms).copy()))
            clk.advance_ms(25)
        return verdicts, s.tiering.snapshot()
    finally:
        s.close()


def _assert_parity(small, big):
    for step, (a, b) in enumerate(zip(small, big)):
        assert np.array_equal(a[0], b[0]), f"allow diverged @ step {step}"
        assert np.array_equal(a[1], b[1]), f"reason diverged @ step {step}"
        assert np.array_equal(a[2], b[2]), f"wait_ms diverged @ step {step}"


@pytest.mark.parametrize("seed", [1501, 2026])
def test_parity_fuzz_small_vs_resident(monkeypatch, seed):
    """A 24-row tiered engine must issue bit-identical verdicts to a
    512-row all-resident engine under flow rules, prioritized acquires,
    and a mid-run rule reload — while actually demoting and promoting
    (the run is vacuous otherwise, so that is asserted too)."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    ruled = [f"zk{i}" for i in range(8)]
    keys = [f"zk{i}" for i in range(48)]
    rules = [stpu.FlowRule(resource=r, count=3.0) for r in ruled]
    reload_rules = ([stpu.FlowRule(resource=r, count=3.0)
                     for r in ruled[:4]]
                    + [stpu.FlowRule(resource=f"zk{i}", count=2.0)
                       for i in range(8, 12)])
    # 24 rows = ENTRY + 8 rule pins + 15 free >= the 12-name batches
    # (a batch wider than the free rows would alias within itself —
    # pre-existing registry behavior, out of tiering's scope)
    small, ssnap = _run_engine(24, 32, 12, keys, rules, reload_rules, seed)
    big, bsnap = _run_engine(512, 32, 12, keys, rules, reload_rules, seed)
    _assert_parity(small, big)
    blocked = sum(int((~a).sum()) for a, _r, _w in small)
    assert blocked > 0                       # the rules actually bit
    assert ssnap["demoted"] > 0 and ssnap["promoted"] > 0
    assert bsnap["demoted"] == 0             # the control really is resident
    assert ssnap["migrate_p50_ms"] is not None


def test_parity_alt_rows_carry_through_churn(monkeypatch):
    """Per-origin (limit_app) alt-row state survives demote → promote:
    the small engine's per-origin verdicts match the resident engine's."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    ruled = [f"ak{i}" for i in range(6)]
    keys = [f"ak{i}" for i in range(24)]
    rules = [stpu.FlowRule(resource=r, count=3.0, limit_app="app-a")
             for r in ruled]
    reload_rules = [stpu.FlowRule(resource=r, count=2.0, limit_app="app-a")
                    for r in ruled[:4]]
    small, ssnap = _run_engine(16, 24, 8, keys, rules, reload_rules,
                               711, origins=["app-a", "app-b"])
    big, bsnap = _run_engine(256, 24, 8, keys, rules, reload_rules,
                             711, origins=["app-a", "app-b"])
    _assert_parity(small, big)
    blocked = sum(int((~a).sum()) for a, _r, _w in small)
    assert blocked > 0
    assert ssnap["demoted"] > 0 and ssnap["promoted"] > 0
    assert bsnap["demoted"] == 0


# ---------------------------------------------------------------------------
# review round: sketch self-clamp/decay floor, geometry change vs cold
# tier, force-land race, proactive-demote TOCTOU rollback
# ---------------------------------------------------------------------------

def test_sketch_inline_halve_at_cap():
    # the update op self-clamps at OVERFLOW_CAP inside the jit: no
    # running ticker is needed to keep counters from wrapping int32
    counts = jnp.full((2, 16), sk.OVERFLOW_CAP - 1, jnp.int32)
    out, overflow = sk.update_sketch(counts, jnp.asarray([3], jnp.int32),
                                     jnp.asarray([True]))
    assert bool(overflow)
    assert int(np.asarray(out).max()) <= sk.OVERFLOW_CAP // 2


def test_sketch_decay_reaches_zero():
    # counters below 2**DECAY_SHIFT must still decay away (a pure
    # shift-decay leaves a permanent nonzero floor on cold rows)
    counts = jnp.full((1, 4), 7, jnp.int32)
    for _ in range(7):
        counts = sk.decay_sketch(counts)
    assert int(np.asarray(counts).max()) == 0
    assert int(np.asarray(counts).min()) == 0


def test_geometry_change_converts_cold_entries(monkeypatch):
    """A live update_window_geometry must not strand old-geometry state
    in the cold tier or the in-flight demote queue: entries land, get
    cold-reset to the new bucket count (the same reset resident rows
    receive), and promote cleanly afterwards."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=32, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        t = s.tiering
        s.entry_batch(["a", "b"], acquire=[1, 1])
        # demote "a" (payload landed) and "b" (payload left in-flight:
        # the tiering thread never runs in this test)
        assert s.resources.evict_name("a")
        s.entry_batch(["x"], acquire=[1])
        t._land_all()
        assert "a" in t.cold
        assert s.resources.evict_name("b")
        s.entry_batch(["x"], acquire=[1])       # dispatches b's snapshot
        assert "b" in t._pending_land
        s.update_window_geometry(sample_count=4)
        B = s.spec.second.buckets
        assert B == 4
        for name in ("a", "b"):                 # both landed + converted
            assert name in t.cold
        e = t.cold.get("a")
        assert e.sec_counters.shape[0] == B
        assert e.occ_cnt.shape[0] == B + 1
        assert not t._pending_land and not t._land_q
        # promotion under the new geometry, same entry call, no crash
        v = s.entry_batch(["a", "b"], acquire=[1, 1])
        assert np.asarray(v.allow).all()
        assert t.snapshot()["promoted"] == 2
        assert "a" not in t.cold and "b" not in t.cold
    finally:
        s.close()


def test_parity_through_geometry_change(monkeypatch):
    """Verdict parity tiered vs all-resident THROUGH a live
    update_window_geometry: both sides cold-reset second windows, and
    the tiered side must convert its cold tier too (an old-geometry
    entry promoted after the change used to crash the serving path)."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    ruled = [f"gk{i}" for i in range(8)]
    keys = [f"gk{i}" for i in range(48)]
    rules = [stpu.FlowRule(resource=r, count=3.0) for r in ruled]
    reload_rules = ([stpu.FlowRule(resource=r, count=3.0)
                     for r in ruled[:4]]
                    + [stpu.FlowRule(resource=f"gk{i}", count=2.0)
                       for i in range(8, 12)])
    small, ssnap = _run_engine(24, 32, 12, keys, rules, reload_rules, 77,
                               geometry_step=20)
    big, bsnap = _run_engine(512, 32, 12, keys, rules, reload_rules, 77,
                             geometry_step=20)
    _assert_parity(small, big)
    blocked = sum(int((~a).sum()) for a, _r, _w in small)
    assert blocked > 0
    assert ssnap["demoted"] > 0 and ssnap["promoted"] > 0
    assert bsnap["demoted"] == 0


def test_promote_force_lands_dequeued_record(monkeypatch):
    """The promote path force-lands via the demote RECORD, not the land
    queue: when the tiering thread has dequeued the record but not yet
    landed it, the promotion must still restore the key's state (not
    serve a zeroed row) and must not strand an orphaned cold entry."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=32, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        t = s.tiering
        s.entry_batch(["k"], acquire=[1])
        assert s.resources.evict_name("k")
        s.entry_batch(["x"], acquire=[1])       # dispatch k's snapshot
        with t._lock:
            rec = t._land_q.popleft()           # thread dequeues...
        assert not rec["landed"]                # ...but has not landed
        s.entry_batch(["k"], acquire=[1])       # re-intern → promote
        assert t.snapshot()["promoted"] == 1
        assert rec["landed"]                    # force-landed directly
        t._land_all()
        assert "k" not in t.cold                # no orphaned entry
        # the restored row really carried its counters: demote again
        # and inspect the fresh cold entry — both decides of "k" landed
        # in the same second bucket, so a zeroed restore would show 1
        assert s.resources.evict_name("k")
        s.entry_batch(["x"], acquire=[1])
        t._land_all()
        e = t.cold.get("k")
        assert int(e.sec_counters[:, ev.PASS].sum()) == 2
    finally:
        s.close()


def test_proactive_demote_rolls_back_when_evict_refused(monkeypatch):
    """_demote_cold_rows records demote intent BEFORE evict_name frees
    the row (so a racing re-intern classifies cold, not hot against the
    stale shadow) and rolls the intent back when the evict is refused —
    a pinned key must not be left looking cold while still resident."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=16, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        t = s.tiering
        s.entry_batch(["a", "b"], acquire=[1, 1])
        ra, rb = s.resources.lookup("a"), s.resources.lookup("b")
        s.resources.pin("a")
        t.hot_rows = 1
        est = np.zeros(s.spec.rows, np.int32)
        est[rb] = 5                     # "a" is coldest → tried first
        t._demote_cold_rows(est)
        with t._lock:
            # pinned "a": refused → intent rolled back, still resident
            assert t._shadow.get(ra) == "a"
            assert ra not in t._pending_demote
            # unpinned "b": demoted with intent recorded up front
            assert t._pending_demote.get(rb) == "b"
            assert rb not in t._shadow
    finally:
        s.close()


# ---------------------------------------------------------------------------
# PR 34: a demotion record lands as ONE columnar block; the cold tier
# indexes (block, row) and promotion gathers from the blocks
# ---------------------------------------------------------------------------

BLOCK_NAMES = [f"bk{i}" for i in range(16)]
# three records over three drains, then a promotion that spans all three
BLOCK_WAVES = (BLOCK_NAMES[0:6], BLOCK_NAMES[6:12], BLOCK_NAMES[12:16])
BLOCK_BACK = ["bk2", "bk4", "bk7", "bk8", "bk13", "bk14"]


def _row_state(s, name):
    """Everything a demotion carries of ``name``'s row, read off the
    device: both windows, the gauge, the booking ring, the histogram,
    and the alt slices by their host identity (their slots differ with
    the row)."""
    row = s.resources.lookup(name)
    st = s._state
    out = {}
    for ring in ("second", "minute"):
        for f, x in zip(WindowState._fields, getattr(st, ring)):
            out[f"{ring}.{f}"] = np.asarray(x)[row]
    out["threads"] = np.asarray(st.threads)[row]
    out["occ_cnt"] = np.asarray(st.flow_dyn.occupied_count)[row]
    out["occ_win"] = np.asarray(st.flow_dyn.occupied_window)[row]
    out["rt_hist"] = np.asarray(st.rt_hist)[row]
    for slot, ident in s._alt_rows_by_row.get(row, {}).items():
        for f, x in zip(WindowState._fields, st.alt_second):
            out[f"alt{ident}.{f}"] = np.asarray(x)[slot]
        out[f"alt{ident}.threads"] = np.asarray(st.alt_threads)[slot]
    return out


def _drive_blocks(evict, between=None):
    """One engine under a fixed script that fills every column a
    demotion carries — per-origin entries with timed exits and some left
    open (histogram, minute ring, gauge, alt slices), then prioritized
    batches over flow rules (bookings in the ring), then a reload that
    lifts the rules' pins off BLOCK_NAMES — and, with ``evict``, demotes
    BLOCK_WAVES over three drains, runs ``between(s)`` while they are
    cold, and promotes BLOCK_BACK in one drain. → (the engine, the
    batches' verdicts, what the landed blocks booked). The twin
    (``evict=False``) sees the same calls and keeps every row."""
    clk = ManualClock(start_ms=1_785_000_000_000)
    s = Sentinel(load_config(max_resources=64, max_flow_rules=16,
                             max_degrade_rules=16, max_authority_rules=16,
                             host_fast_path=False, thread_gauge_always=True),
                 clock=clk)
    rng = np.random.default_rng(34)
    verdicts = []

    def batch(names, prioritized=False):
        v = s.entry_batch(
            names, acquire=[1] * len(names),
            origins=list(rng.choice(["app-a", "app-b"], size=len(names))),
            prioritized=[prioritized] * len(names))
        verdicts.append((np.asarray(v.allow).copy(),
                         np.asarray(v.reason).copy(),
                         np.asarray(v.wait_ms).copy()))

    held = []
    for i, name in enumerate(BLOCK_NAMES):
        e = s.entry(name, origin="app-a", sleep=False)
        if i % 4 == 0:
            held.append(e)              # a gauge that rides the move
        else:
            clk.advance_ms(1 + 37 * i)
            e.exit()
    s.load_flow_rules([stpu.FlowRule(resource=n, count=2.0)
                       for n in BLOCK_NAMES[::2] + ["pin0", "pin1"]])
    # a ruled name's count passes in one bucket; in the next a
    # prioritized acquire finds the window full and books the bucket
    # after: every ruled row's ring holds a pending booking
    clk.advance_ms(520)
    batch(BLOCK_NAMES + BLOCK_NAMES[::2] + ["pin0"])
    clk.advance_ms(520)
    batch(BLOCK_NAMES + ["pin0"], prioritized=True)
    # BLOCK_NAMES lose their rules, and with them their pins; pending
    # bookings ride the reload and then the demotion
    s.load_flow_rules([stpu.FlowRule(resource="pin0", count=2.0)])
    for wave in BLOCK_WAVES:
        if evict:
            for name in wave:
                assert s.resources.evict_name(name)
        batch(["other"])                # the drain: one record a wave
        clk.advance_ms(40)
    booked = 0.0
    if evict:
        s.tiering._land_all()
        booked = sum(float(b.occ_cnt.sum())
                     for b, _live in s.tiering.cold._blocks.values())
    clk.advance_ms(600)                 # the bookings' window opens
    if between is not None:
        between(s)
    # the names come back, and a drain restores them, with no decide on
    # them yet: what is compared is what the promotion wrote
    s.intern_resources(BLOCK_BACK)
    batch(["other"])
    return s, verdicts, booked


def _assert_rows_equal(tiered, resident, names):
    for name in names:
        a, b = _row_state(tiered, name), _row_state(resident, name)
        assert a.keys() == b.keys(), name
        for k in a:
            assert np.array_equal(a[k], b[k]), (name, k)


def _reload(s):
    s.load_flow_rules([stpu.FlowRule(resource="pin1", count=4.0)])


def _regeometry(s):
    s.update_window_geometry(sample_count=4)


@pytest.mark.parametrize("between", [None, _reload, _regeometry],
                         ids=["plain", "flow_reload", "geometry_change"])
def test_block_promotion_restores_rows_bit_identical(monkeypatch, between):
    """Three records demoted over three drains, a promotion whose names
    span all three blocks — straight, across a flow-rule reload (the
    replay the cold rows slept through), and across a geometry change
    (every block cold-reset): each restored row reads bit for bit as the
    row of the twin that never left the table, in every column a
    demotion carries."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    tiered, tv, booked = _drive_blocks(True, between)
    resident, rv, _ = _drive_blocks(False, between)
    try:
        _assert_parity(tv, rv)
        assert booked > 0               # the ring moved with something in it
        t = tiered.tiering
        snap = t.snapshot()
        assert snap["demoted"] == len(BLOCK_NAMES)
        assert snap["promoted"] == len(BLOCK_BACK)
        assert resident.tiering.snapshot()["demoted"] == 0
        state = _row_state(resident, "bk7")
        assert tiered.spec.minute and tiered.spec.hist_buckets
        assert state["threads"] > 0 and state["rt_hist"].sum() > 0
        assert any(k.startswith("alt") for k in state)
        _assert_rows_equal(tiered, resident, BLOCK_BACK)
        # the names left behind are still indexed, three blocks live
        assert len(t.cold) == len(BLOCK_NAMES) - len(BLOCK_BACK)
        assert len(t.cold._blocks) == 3
        # only a reload replays, and only that builds entries
        replayed = len(BLOCK_BACK) if between is _reload else 0
        assert snap["materialized"] == replayed
        # a by-name read of a name still cold answers what its row held
        left = [n for n in BLOCK_NAMES if n not in BLOCK_BACK]
        hist = tiered.rt_hist_by_name(left)
        want = resident.rt_hist_by_name(left)
        assert np.array_equal(hist, want) and hist.sum() > 0
        assert t.snapshot()["materialized"] == replayed
    finally:
        tiered.close()
        resident.close()


def _block_of(n, tag=0):
    """A landed record's stand-in: ``n`` rows, row ``i`` marked
    ``tag + i`` in its gauge and histogram."""
    z4 = (np.zeros((n, 2, ev.NUM_EVENTS), np.int32),
          np.full((n, 2), NEVER, np.int32),
          np.zeros((n, 2), np.float32), np.full((n, 2), INT32_MAX, np.int32))
    return ColdBlock(
        second=z4, minute=z4, threads=np.arange(tag, tag + n, dtype=np.int32),
        occ_cnt=np.zeros((n, 3), np.float32),
        occ_win=np.full((n, 3), NEVER, np.int32),
        rt_hist=np.arange(tag, tag + n, dtype=np.int32)[:, None]
        * np.ones((1, 4), np.int32),
        alt_second=tuple(x[:0] for x in z4),
        alt_threads=np.zeros(0, np.int32), alt_ids=[])


def test_cold_max_drops_names_out_of_the_middle_of_a_block():
    """The bound is on NAMES, oldest first, wherever their block is:
    ``dropped`` and ``len()`` count names, a dropped name is unknown (it
    re-enters fresh), the rest of its block still reads right."""
    tier = ColdTier(max_entries=5)
    tier.put_block(_block_of(4, 10), ["a0", "a1", "a2", "a3"])
    tier.put_block(_block_of(4, 20), ["b0", "b1", "b2", "b3"])
    assert len(tier) == 5 and tier.dropped == 3
    assert [n in tier for n in ("a0", "a1", "a2", "a3")] == \
        [False, False, False, True]
    assert tier.pop("a1") is None and tier.get("a0") is None
    assert tier.get("a3").threads == 13 and tier.get("b2").threads == 22
    assert tier.names(2) == ["b3", "b2"]
    # a re-demotion moves the name to the newer state and the newer end
    tier.put_block(_block_of(2, 30), ["a3", "c1"])
    assert len(tier) == 5 and tier.dropped == 4     # b0 went, a3 moved
    assert "b0" not in tier and tier.get("a3").threads == 30
    assert len(tier._blocks) == 2                   # a's block: no name left
    assert np.array_equal(
        tier.rt_hist_rows(["b1", "zz", "c1", "a3"], 4)[:, 0], [21, 0, 31, 30])
    # the dropped name re-enters through put() like any first demotion
    tier.put("a0", _dummy_entry())
    assert "a0" in tier and len(tier) == 5 and tier.dropped == 5


def test_block_released_once_its_last_name_is_popped():
    tier = ColdTier(None)
    tier.put_block(_block_of(3, 10), ["a0", "a1", "a2"])
    tier.put_block(_block_of(2, 20), ["b0", "b1"])
    assert len(tier._blocks) == 2
    groups = tier.pop_rows(["b1", "a0", "nope", "a2", "a0"])
    got = {int(block.threads[i]): int(j)
           for block, rows, js in groups for i, j in zip(rows, js)}
    assert got == {21: 0, 10: 1, 12: 3}      # second "a0": already taken
    assert len(tier) == 2 and len(tier._blocks) == 2
    assert tier.pop("a1").threads == 11
    assert len(tier._blocks) == 1            # a's block went with a1
    assert tier.pop("b0").threads == 20
    assert len(tier) == 0 and not tier._blocks


def test_landing_builds_no_entry_and_copies_no_row(monkeypatch):
    """The mechanism, pinned: across demote → inline landing → promote
    of one batch no ``ColdEntry`` is built (``tier.materialized`` stays
    0) and the landed columns ARE the record's host arrays; one
    ``cold_entry`` read builds one."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    built = []
    init = ColdEntry.__init__

    def counting_init(self, *a, **kw):
        built.append(1)
        init(self, *a, **kw)

    monkeypatch.setattr(ColdEntry, "__init__", counting_init)
    clk = ManualClock(start_ms=1_000_000)
    s = Sentinel(load_config(max_resources=32, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=clk)
    try:
        t = s.tiering
        names = [f"v{i}" for i in range(8)]
        s.entry_batch(names, acquire=[1] * 8)
        for name in names:
            assert s.resources.evict_name(name)
        s.entry_batch(["x"], acquire=[1])       # the drain: one record of 8
        (rec,) = t._land_q
        host = [np.asarray(x) for x in rec["payload"].second]
        assert not rec["landed"] and "v3" in t._pending_land
        # a victim asked for again: its promotion lands the WHOLE record
        # inline, then gathers the one row
        s.entry_batch(["v3"], acquire=[1])
        snap = t.snapshot()
        assert rec["landed"] and not t._pending_land
        assert snap["promoted"] == 1 and snap["materialized"] == 0
        assert s.obs.counters.get("tier.land_inline") == 8
        assert not built
        ((block, live),) = t.cold._blocks.values()
        assert live == 7
        for col, arr in zip(block.second, host):
            assert np.shares_memory(col, arr)
        assert len(t.cold) == 7 and "v3" not in t.cold
        # the slow form, counted: a by-name read builds ONE entry
        e = t.cold_entry("v5")
        assert int(e.sec_counters[:, ev.PASS].sum()) == 1
        assert len(built) == 1 and t.snapshot()["materialized"] == 1
        assert s.obs.counters.get("tier.materialized") == 1
        assert "v5" in t.cold                   # left where it is
    finally:
        s.close()


def test_block_index_holds_under_concurrent_landing_and_promotion():
    """Landings, promotions and column reads from three threads: every
    name comes out exactly once, the live counts add up to the index,
    and only blocks with a name left are kept."""
    import sys
    import threading
    tier = ColdTier(None)
    blocks, per = 120, 64
    taken = []
    stop = threading.Event()
    errors = []

    def guard(fn):
        def run():
            try:
                fn()
            except Exception as exc:     # surfaced by the assert below
                errors.append(exc)
        return threading.Thread(target=run)

    def land():
        for b in range(blocks):
            tier.put_block(_block_of(per, b * per),
                           [f"n{b}-{i}" for i in range(per)])

    def promote():
        rng = np.random.default_rng(7)
        while not stop.is_set():
            names = [f"n{rng.integers(blocks)}-{rng.integers(per)}"
                     for _ in range(32)]
            for block, rows, _js in tier.pop_rows(names):
                taken.extend(block.threads[rows].tolist())

    def read():
        while not stop.is_set():
            names = [f"n{b}-3" for b in range(0, blocks, 5)]
            hist = tier.rt_hist_rows(names, 4)
            for b, h in zip(range(0, blocks, 5), hist[:, 0].tolist()):
                assert h in (0, b * per + 3)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [guard(promote), guard(read), guard(promote)]
        lander = guard(land)
        for w in workers + [lander]:
            w.start()
        lander.join(timeout=60)
        stop.set()
        for w in workers:
            w.join(timeout=60)
        assert not lander.is_alive() and not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(was)
    assert not errors, errors
    assert len(taken) == len(set(taken))            # no row came out twice
    assert len(taken) + len(tier) == blocks * per
    assert sum(live for _b, live in tier._blocks.values()) == len(tier)
    assert set(tier._blocks) == {ref >> 32 for ref in tier._index.values()}


# ---------------------------------------------------------------------------
# PR 36: the tick decays the sketch and reads back ONE number, its
# largest counter; every row's estimate runs only for proactive demotion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 4, 64), (4, 12, 1 << 14)],
                         ids=["collides", "default"])
@pytest.mark.parametrize("impl", sorted(sk.SKETCH_IMPLS))
def test_largest_counter_is_the_largest_estimate(impl, shape):
    """What lets the tick drop the gather: after every update (invalid
    lanes, duplicates within a batch), decay and halving of a seeded
    stream, the largest estimate over the rows equals the table's
    largest counter — on a table of 16 buckets a hash row, where 64 rows
    collide all the time, and on the default ``[4, 4096]``."""
    sketch_rows, bits, n_rows = shape
    rng = np.random.default_rng(36 + bits)
    counts = sk.init_sketch(sketch_rows, bits)
    update = sk.jit_update(impl)
    hot = rng.permutation(n_rows)[:24]
    for step in range(60):
        op = rng.random()
        if op < 0.7:
            # a skewed batch: most lanes from 24 hot rows, so duplicates
            # are the rule, a fifth of the lanes invalid
            items = np.where(rng.random(48) < 0.7, rng.choice(hot, 48),
                             rng.integers(0, n_rows, 48))
            counts, _ = update(counts, jnp.asarray(items, jnp.int32),
                               jnp.asarray(rng.random(48) < 0.8))
        elif op < 0.9:
            counts = sk.decay_sketch(counts)
        else:
            counts = sk.halve_sketch(counts)
        est = sk.jit_estimate_all(counts, n_rows=n_rows)
        assert int(est.max()) == int(counts.max()), (impl, shape, step)
    assert int(counts.max()) > 0


def _tick_engine(monkeypatch, registry="native", rows=256):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE",
                       "1" if registry == "native" else "0")
    monkeypatch.setenv("SENTINEL_SKETCH_BITS", "4")    # int32[4, 16]
    s = Sentinel(load_config(max_resources=rows, max_flow_rules=8,
                             max_degrade_rules=8, max_authority_rules=8),
                 clock=ManualClock(start_ms=1_000_000))
    assert type(s.resources).__name__ == (
        "NativeRegistry" if registry == "native" else "Registry")
    return s


def test_default_tick_reads_back_one_number(monkeypatch):
    """On an engine with every default the tick dispatches the decay and
    a scalar — no estimate, nothing of the table's size in its program."""
    s = _tick_engine(monkeypatch)
    monkeypatch.setattr(sk, "jit_estimate_all", None)   # a call would raise
    try:
        t = s.tiering
        names = [f"r{i}" for i in range(40)]
        for k in range(1, 4):
            s.entry_batch(names[:13 * k], acquire=[1] * (13 * k))
        before = np.asarray(t._sketch)
        assert before.max() > 0
        assert t.tick()
        (top, est), = t._tick_q
        assert est is None and top.shape == () and top.dtype == jnp.int32
        after = np.asarray(sk.decay_sketch(jnp.asarray(before)))
        np.testing.assert_array_equal(np.asarray(t._sketch), after)
        assert int(top) == after.max()
        snap = t.snapshot()
        assert snap["ticks"] == 1 and snap["tick_estimates"] == 0
        assert s.obs.counters.get("tier.tick") == 1
        assert s.obs.counters.get("tier.tick_estimate") == 0
        assert t.drain() == 1 and not t._tick_q
        # no operand, temporary or result beyond the [4, 16] sketch:
        # R = 264 rows and SR x R lanes would both show
        text = sk.jit_tick_read.lower(t._sketch).as_text()
        sizes = [int(np.prod([int(d) for d in dims.split("x") if d] or [1]))
                 for dims in re.findall(r"tensor<((?:\d+x)*)i\d+>", text)]
        assert sizes and max(sizes) == before.size < s.spec.rows
    finally:
        s.close()


@pytest.mark.parametrize("over", [True, False], ids=["at-half-cap", "under"])
def test_drain_counts_an_overflow_from_the_largest_counter(monkeypatch, over):
    """A row whose buckets the tick leaves at ``OVERFLOW_CAP // 2`` makes
    ``drain`` halve the table and tick ``tier.sketch_overflow`` once;
    one count less and it does neither."""
    s = _tick_engine(monkeypatch)
    try:
        t = s.tiering
        half = sk.OVERFLOW_CAP // 2
        near = np.arange(half * 8 // 7 - 8, half * 8 // 7 + 8, dtype=np.int32)
        decayed = np.asarray(sk.decay_sketch(jnp.asarray(near)))
        first = int(near[np.argmax(decayed >= half)])
        planted = first if over else first - 1
        idx = np.asarray(sk._bucket_idx(t._sketch, jnp.asarray([7])))[:, 0]
        table = np.zeros(t._sketch.shape, np.int32)
        table[np.arange(len(idx)), idx] = planted       # row 7's buckets
        with s._lock:
            t._sketch = jnp.asarray(table)
        want = sk.decay_sketch(jnp.asarray(table))
        assert int(want.max()) == (half if over else half - 1)
        assert t.poll() == 1
        if over:
            want = sk.halve_sketch(want)
        np.testing.assert_array_equal(np.asarray(t._sketch), np.asarray(want))
        assert s.obs.counters.get("tier.sketch_overflow") == int(over)
        assert t.snapshot()["sketch_overflow"] == int(over)
    finally:
        s.close()


@pytest.mark.parametrize("registry", ["python", "native"])
def test_estimate_is_dispatched_only_for_proactive_demotion(monkeypatch,
                                                            registry):
    """With a hot-rows target the Python registry's tick also reads every
    row's estimate and ``drain`` demotes the coldest rows by it; the
    native registry cannot evict by name, so it dispatches none."""
    s = _tick_engine(monkeypatch, registry, rows=32)
    try:
        t = s.tiering
        names = [f"r{i}" for i in range(6)]
        for k in range(6, 0, -1):                   # r0 hottest … r5 coldest
            s.entry_batch(names[:k], acquire=[1] * k)
        t.hot_rows = len(s.resources) - 2
        resident = len(s.resources)
        assert t.tick()
        (top, est), = t._tick_q
        python = registry == "python"
        assert s.obs.counters.get("tier.tick") == 1
        assert s.obs.counters.get("tier.tick_estimate") == int(python)
        assert t.snapshot()["tick_estimates"] == int(python)
        if python:
            want = sk.estimate_all(t._sketch, s.spec.rows)
            np.testing.assert_array_equal(np.asarray(est), np.asarray(want))
            assert int(top) == int(want.max())
        else:
            assert est is None
        t.drain()
        gone = [n for n in names if s.resources.lookup(n) is None]
        assert gone == (["r4", "r5"] if python else [])
        assert len(s.resources) == resident - len(gone)
    finally:
        s.close()
