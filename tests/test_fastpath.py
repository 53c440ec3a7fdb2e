"""Host-side fast path (SURVEY §7 hard-part 1, VERDICT round-1 item #2):
rule-free resources decide on host with batched device stat recording;
single-simple-QPS resources serve from a device-pre-charged token lease.
Over-admission beyond the leased budget must be structurally impossible,
and all statistics must still land on device."""

import time

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock

T0 = 1_785_000_000_000


@pytest.fixture
def clk():
    return ManualClock(start_ms=T0)


def make(clk, **over):
    kw = dict(max_resources=64, max_flow_rules=16, max_degrade_rules=16,
              max_authority_rules=16, minute_enabled=True)
    kw.update(over)
    return stpu.Sentinel(config=stpu.load_config(**kw), clock=clk)


def _count_decides(sph):
    """Wrap the jitted decide steps (all four static variants: occupy ×
    alt-free, plus the round-16 sketch-fused set) to count device
    dispatches."""
    counter = {"n": 0}

    def wrap(fn):
        def inner(*a, **k):
            counter["n"] += 1
            return fn(*a, **k)
        return inner

    for attr in ("_jit_decide", "_jit_decide_prio",
                 "_jit_decide_noalt", "_jit_decide_prio_noalt"):
        setattr(sph, attr, wrap(getattr(sph, attr)))

    orig_sd = sph._sd_steps_locked

    def sd_wrapped():
        return tuple(wrap(f) for f in orig_sd())

    sph._sd_steps_locked = sd_wrapped
    return counter


def drain(sph, resource, n, advance_ms=0):
    out = []
    for _ in range(n):
        try:
            with sph.entry(resource):
                out.append("p")
        except stpu.BlockException:
            out.append("b")
        if advance_ms:
            sph.clock.advance_ms(advance_ms)
    return out


# ---------------------------------------------------------------- FREE tier

def test_free_resource_stats_land_on_device(clk):
    sph = make(clk)
    for _ in range(40):
        with sph.entry("free"):
            clk.advance_ms(3)
    t = sph.node_totals("free")
    assert t["pass"] == 40 and t["success"] == 40
    assert t["threads"] == 0          # all exited
    assert sph._fast.fast_admits == 40


def test_free_resource_no_per_call_device_dispatch(clk):
    sph = make(clk)
    with sph.entry("warm"):           # prime buffers/caches
        pass
    sph.node_totals("warm")           # flush
    counter = _count_decides(sph)
    for _ in range(100):
        with sph.entry("free"):
            pass
    # 100 entries, zero flushes due (no clock movement, buffer < cap)
    assert counter["n"] == 0
    sph.node_totals("free")           # forced flush → exactly one decide
    assert counter["n"] == 1


def test_free_thread_gauge_tracks_inflight(clk):
    # gauge maintenance is elided when nothing reads it (thread-gauge
    # elision, VERDICT r4 #2); thread_gauge_always restores the
    # reference's always-on curThreadNum observability
    sph = make(clk, thread_gauge_always=True)
    entries = [sph.entry("free") for _ in range(5)]
    t = sph.node_totals("free")       # forces flush of buffered passes
    assert t["threads"] == 5
    for e in entries:
        e.exit()
    assert sph.node_totals("free")["threads"] == 0


def test_thread_gauge_live_when_a_reader_rule_is_loaded(clk):
    """A THREAD-grade rule anywhere flips gauge maintenance on for every
    resource (the gauge is global state; the rule must read true
    concurrency)."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="guarded", count=50.0,
                                       grade=stpu.GRADE_THREAD)])
    entries = [sph.entry("free") for _ in range(3)]
    t = sph.node_totals("free")
    assert t["threads"] == 3
    for e in entries:
        e.exit()
    assert sph.node_totals("free")["threads"] == 0


def test_thread_gauge_elided_reads_zero_without_readers(clk):
    """Contract pin: with no gauge readers loaded, the gauge is NOT
    maintained (reads 0) — the documented observability trade."""
    sph = make(clk)
    entries = [sph.entry("free") for _ in range(4)]
    assert sph.node_totals("free")["threads"] == 0
    for e in entries:
        e.exit()


def test_thread_gauge_no_leak_across_elision_flips(clk):
    """Entries counted while maintenance was ON must not leak a permanent
    over-count when their exits happen elided (review finding r5): unload
    the THREAD rule mid-flight, exit, reload — gauge must read 0, and a
    tight THREAD rule must not block on phantom concurrency."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="thr", count=50.0,
                                       grade=stpu.GRADE_THREAD)])
    entries = [sph.entry("free") for _ in range(5)]
    assert sph.node_totals("free")["threads"] == 5
    # unload the reader → elision flips on; the 5 exits are elided
    sph.load_flow_rules([stpu.FlowRule(resource="other", count=5.0)])
    for e in entries:
        e.exit()
    # reload a tight THREAD rule on the same row: no phantom concurrency
    sph.load_flow_rules([stpu.FlowRule(resource="free", count=3.0,
                                       grade=stpu.GRADE_THREAD)])
    assert sph.node_totals("free")["threads"] == 0
    fresh = [sph.entry("free") for _ in range(3)]
    with pytest.raises(stpu.BlockException):
        sph.entry("free")                 # 4th concurrent blocked (count=3)
    for e in fresh:
        e.exit()
    assert sph.node_totals("free")["threads"] == 0
    sph.entry("free").exit()              # admits again


def test_free_with_origin_records_origin_stats(clk):
    sph = make(clk)
    with sph.entry("free", origin="app-a"):
        pass
    with sph.entry("free", origin="app-a"):
        pass
    ot = sph.origin_totals("free")
    assert ot and ot[0]["origin"] == "app-a" and ot[0]["passQps"] == 2


def test_entry_latency_sub_ms_on_cpu(clk):
    """VERDICT done-bar: config-1 p50 < 1 ms on the CPU backend."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=1e9)])
    for _ in range(20):               # warm lease + caches
        with sph.entry("api"):
            pass
    lat = []
    for _ in range(200):
        t0 = time.perf_counter()
        with sph.entry("api"):
            pass
        lat.append(time.perf_counter() - t0)
    assert np.percentile(lat, 50) < 1e-3


# ---------------------------------------------------------------- leases

def test_lease_enforces_exact_qps(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=10.0)])
    assert drain(sph, "api", 25).count("p") == 10
    clk.advance_ms(1000)
    assert drain(sph, "api", 25).count("p") == 10
    t = sph.node_totals("api")
    # probe denials record no phantom blocks: rolling window holds the
    # last second's 10 passes / 15 real denials
    assert t["pass"] == 10 and t["block"] == 15


def test_lease_never_overadmits_under_uneven_arrival(clk):
    """Admissions across arbitrary arrival patterns stay <= count per
    rolling window — the pre-charge makes over-admission structural."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=20.0)])
    admitted = 0
    for burst in (7, 1, 13, 30, 2):
        admitted += drain(sph, "api", burst).count("p")
        clk.advance_ms(100)
    assert admitted <= 20


def test_lease_stats_match_admissions(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=6.0)])
    res = drain(sph, "api", 9)
    t = sph.node_totals("api")
    assert t["pass"] == res.count("p") == 6
    assert t["block"] == res.count("b") == 3


def test_leased_with_origin_takes_device_path(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=100.0)])
    counter = _count_decides(sph)
    with sph.entry("api", origin="caller"):
        pass
    assert counter["n"] >= 1          # per-event device decide
    ot = sph.origin_totals("api")
    assert ot and ot[0]["origin"] == "caller" and ot[0]["passQps"] == 1


def test_rule_reload_drops_leases(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=100.0)])
    assert drain(sph, "api", 5).count("p") == 5
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=2.0)])
    # old lease (98 remaining) must not serve the new, tighter rule
    assert drain(sph, "api", 6).count("p") <= 2


# ------------------------------------------------------------- exclusions

def test_degrade_rule_disables_fast_path(clk):
    from sentinel_tpu.rules.degrade import GRADE_EXCEPTION_RATIO, DegradeRule

    sph = make(clk)
    sph.load_degrade_rules([DegradeRule(
        resource="svc", grade=GRADE_EXCEPTION_RATIO, count=0.5,
        time_window=10)])
    counter = _count_decides(sph)
    with sph.entry("svc"):
        pass
    assert counter["n"] >= 1          # device path (breaker gate must run)


def test_system_rules_disable_inbound_fast_path(clk):
    from sentinel_tpu.rules.system import SystemRule

    sph = make(clk)
    sph.load_system_rules([SystemRule(qps=1e9)])
    counter = _count_decides(sph)
    with sph.entry("free"):
        pass
    assert counter["n"] >= 1          # IN entries gate through SystemSlot
    sph.load_system_rules([])
    sph.node_totals("free")
    counter["n"] = 0
    with sph.entry("free"):
        pass
    assert counter["n"] == 0          # fast again after rules clear


def test_complex_flow_rules_ineligible(clk):
    """Two rules, warm-up behavior, origin-specific limits → device path."""
    from sentinel_tpu.rules.flow import BEHAVIOR_WARM_UP

    sph = make(clk)
    sph.load_flow_rules([
        stpu.FlowRule(resource="warm", count=100.0,
                      control_behavior=BEHAVIOR_WARM_UP),
        stpu.FlowRule(resource="two", count=100.0),
        stpu.FlowRule(resource="two", count=50.0),
        stpu.FlowRule(resource="orig", count=100.0, limit_app="caller"),
    ])
    counter = _count_decides(sph)
    for r in ("warm", "two", "orig"):
        with sph.entry(r):
            pass
    assert counter["n"] >= 3


def test_batch_tier_unaffected(clk):
    """entry_batch keeps exact device semantics regardless of fast path."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=5.0)])
    v = sph.entry_batch(["api"] * 8)
    assert int(np.sum(v.allow)) == 5


def test_rule_load_flushes_buffered_passes_first(clk):
    """Passes admitted while a resource was rule-free must be recorded as
    PASSES even if a rule lands before the flush — re-deciding them under
    the new table would turn them into phantom blocks."""
    sph = make(clk)
    for _ in range(6):
        with sph.entry("r"):
            pass
    # 6 passes buffered, not yet flushed; now a tight rule arrives
    sph.load_flow_rules([stpu.FlowRule(resource="r", count=1.0)])
    t = sph.node_totals("r")
    assert t["pass"] == 6 and t["block"] == 0


def test_concurrent_lease_renewals_single_precharge(clk):
    """Only one renewal pre-charge may be in flight per row — concurrent
    renewals double-spend the window budget (under-admission)."""
    import threading

    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=100.0)])
    admitted = []
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        got = 0
        for _ in range(10):
            try:
                with sph.entry("api"):
                    got += 1
            except stpu.BlockException:
                pass
        admitted.append(got)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # 40 requests against count=100 (window budget 50): all must pass —
    # racing renewals that each burn a 25-token chunk would deny some
    assert sum(admitted) == 40


def test_in_out_alternation_does_not_burn_budget(clk):
    """Alternating ENTRY_TYPE_IN/OUT must not trigger a pre-charge per
    event (a mismatched live lease routes to the device path instead)."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=40.0)])
    admitted = 0
    for i in range(20):
        et = stpu.ENTRY_TYPE_IN if i % 2 == 0 else stpu.ENTRY_TYPE_OUT
        try:
            with sph.entry("api", entry_type=et):
                admitted += 1
        except stpu.BlockException:
            pass
    # window budget = 20; all 20 must be admitted, and at most ~2 chunks
    # (one per direction at most... the OUT side goes device path)
    assert admitted == 20
    assert sph._fast.lease_renewals <= 2


def test_expired_lease_returns_unused_tokens_to_metrics(clk):
    """A lease pre-charge fronts PASS for the whole chunk (the admission
    ledger must see reservations), but once the bucket rotates the unused
    remainder is subtracted back — pass metrics count ADMISSIONS."""
    sph = make(clk, minute_enabled=True)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=100.0)])
    for _ in range(5):                    # chunk=25 pre-charged, 5 used
        with sph.entry("api"):
            pass
    clk.advance_ms(600)                   # bucket rotates
    with sph.entry("api"):                # triggers expiry + new lease
        pass
    clk.advance_ms(600)
    sph._flush_fast()
    clk.advance_ms(1500)
    # minute-ring per-second view shows true admissions for the T0 second:
    # 5 at T0 plus 1 at T0+600 — NOT the 25-token chunk reservations
    nodes = {n.resource: n for n in sph.metrics_snapshot(T0)}
    assert nodes["api"].pass_qps == 6


def test_mixed_fast_and_batch_traffic_consistent(clk):
    """Host-admitted passes are visible to later device decides after the
    flush (bounded staleness, conservative direction)."""
    sph = make(clk)
    for _ in range(4):
        with sph.entry("free"):
            pass
    sph._flush_fast()
    sph.load_flow_rules([stpu.FlowRule(resource="free", count=5.0)])
    # rule load makes the row LEASED; prior 4 passes are in the window
    assert drain(sph, "free", 5).count("p") == 1


def test_threaded_leased_path_never_overadmits(clk):
    """8 threads hammering one simple-QPS resource through the host fast
    path: admissions per window must never exceed the configured count
    (the structural no-over-admission claim, under real concurrency).

    Deterministic harness (round 11 deflake): the old version ran 2.5 s
    on the REAL clock and bucketed admissions by a timestamp taken AFTER
    admission — under CI load a thread could be preempted between the
    charge and the stamp, misattributing the admission to the next
    window and tripping the pair bound spuriously. Here the ManualClock
    is held FIXED for an entire phase, so every admission in a phase is
    in one window bucket by construction — no stamping race exists —
    and the clock only advances between phases, from the main thread,
    with no workers running. The interleaving of the 8 threads within a
    phase stays genuinely nondeterministic (that is the point: the
    device pre-charge must bound admissions under ANY interleaving);
    only the time axis is pinned."""
    import threading

    sph = make(clk, max_resources=32, max_flow_rules=8,
               minute_enabled=False, host_fast_path=True)
    COUNT = 40
    N_THREADS = 8
    ATTEMPTS = 3 * COUNT          # per thread: 24× oversubscribed total
    sph.load_flow_rules([stpu.FlowRule(resource="hot", count=float(COUNT))])
    win_ms = sph.spec.second.win_ms

    def run_phase():
        """All threads released by one barrier, each makes ATTEMPTS
        entry attempts at the frozen clock; returns total admissions."""
        admitted = [0] * N_THREADS
        barrier = threading.Barrier(N_THREADS)

        def worker(i):
            barrier.wait()
            for _ in range(ATTEMPTS):
                try:
                    with sph.entry("hot"):
                        admitted[i] += 1
                except stpu.BlockException:
                    pass

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "worker wedged"
        return sum(admitted)

    for phase in range(3):
        got = run_phase()
        # the sliding window spans 2 adjacent buckets; the clock sits in
        # exactly one bucket all phase, so the bound is strict
        assert 0 < got <= COUNT, f"phase {phase}: {got} admissions"
        # step fully past the sliding window (both buckets) between
        # phases — the lease must replenish and the next phase re-admits
        clk.advance_ms(2 * win_ms)


def test_threaded_free_path_thread_gauge_returns_to_zero():
    """Concurrent entry/exit churn on a rule-free resource with aggressive
    flushing: after the dust settles the device thread gauge must be 0 —
    the drain→dispatch ordering guarantee of the flush lock (a reordered
    exit-before-pass would leave a permanent +1)."""
    import threading

    import sentinel_tpu as stpu

    sph = stpu.Sentinel(stpu.load_config(
        max_resources=32, max_flow_rules=8, max_degrade_rules=8,
        max_authority_rules=8, host_fast_path=True,
        fast_path_flush_events=4, fast_path_flush_ms=1))
    with sph.entry("free-res"):
        pass

    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with sph.entry("free-res"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    stop.wait(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    sph._flush_fast()
    totals = sph.node_totals("free-res")
    assert totals["threads"] == 0, totals
    assert totals["pass"] >= 0          # and no negative counters anywhere
