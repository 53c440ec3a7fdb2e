"""End-to-end pipeline tests over virtual time — parity targets:
FlowPartialIntegrationTest / CircuitBreakingIntegrationTest /
SystemGuardIntegrationTest and the controller unit tests (reference
sentinel-core test tiers 2-3)."""

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock

# core-path subset: the CI quick tier (PRs) runs only these files
pytestmark = pytest.mark.quick


def make_sentinel(clock, **cfg_over):
    cfg = stpu.load_config(max_resources=64, max_origins=32, max_flow_rules=16,
                           max_degrade_rules=16, max_authority_rules=16,
                           minute_enabled=True, **cfg_over)
    return stpu.Sentinel(config=cfg, clock=clock)


@pytest.fixture
def clk():
    return ManualClock(start_ms=1_785_000_000_000)


def burst(sph, resource, n, **kw):
    """n sequential entry attempts; returns (passed, blocked)."""
    p = b = 0
    for _ in range(n):
        try:
            with sph.entry(resource, **kw):
                p += 1
        except stpu.BlockException:
            b += 1
    return p, b


# ---------------------------------------------------------------- flow: QPS

def test_flow_qps_default_controller(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="HelloWorld", count=20)])
    assert burst(sph, "HelloWorld", 30) == (20, 10)
    clk.advance_ms(1000)
    assert burst(sph, "HelloWorld", 5) == (5, 0)


def test_flow_qps_batch_greedy(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="r", count=20)])
    v = sph.entry_batch(["r"] * 30)
    assert int(np.sum(v.allow)) == 20
    # FIFO: the first 20 pass, the last 10 block
    assert bool(np.all(v.allow[:20])) and not bool(np.any(v.allow[20:]))
    assert all(int(r) == stpu.BlockReason.FLOW for r in v.reason[20:])


def test_flow_unrelated_resource_not_limited(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="limited", count=1)])
    assert burst(sph, "limited", 3) == (1, 2)
    assert burst(sph, "free", 50) == (50, 0)


# ------------------------------------------------------------- flow: THREAD

def test_flow_thread_grade_concurrency(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="db", count=2,
                                       grade=stpu.GRADE_THREAD)])
    e1 = sph.entry("db")
    e2 = sph.entry("db")
    with pytest.raises(stpu.FlowException):
        sph.entry("db")
    e1.exit()
    e3 = sph.entry("db")  # slot freed
    e2.exit()
    e3.exit()


# --------------------------------------------------------- flow: RateLimiter

def test_flow_rate_limiter_paces_and_blocks(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource="q", count=10, control_behavior=stpu.BEHAVIOR_RATE_LIMITER,
        max_queueing_time_ms=300)])
    v = sph.entry_batch(["q"] * 6)
    # cost = 100ms/permit: waits 0,100,200,300 pass; 400,500 exceed 300 → block
    assert list(np.asarray(v.allow)) == [True, True, True, True, False, False]
    assert list(np.asarray(v.wait_ms[:4])) == [0, 100, 200, 300]


def test_flow_rate_limiter_sequential_pacing(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource="q2", count=10, control_behavior=stpu.BEHAVIOR_RATE_LIMITER,
        max_queueing_time_ms=1000)])
    t0 = clk.now_ms()
    for _ in range(4):
        with sph.entry("q2"):
            pass
    # entry() sleeps the wait on the ManualClock: 3 × 100ms pacing
    assert clk.now_ms() - t0 == 300


# ------------------------------------------------------------- flow: WarmUp

def test_flow_warmup_ramp(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource="w", count=30, control_behavior=stpu.BEHAVIOR_WARM_UP,
        warm_up_period_sec=4)])
    passes = []
    for _ in range(7):
        p, _ = burst(sph, "w", 20)
        passes.append(p)
        clk.advance_ms(1000)
    # cold limit = count/coldFactor = 10, ramping to the offered 20
    assert passes[0] == 10
    assert all(passes[i] <= passes[i + 1] for i in range(5))
    assert passes[-1] == 20
    assert passes[2] > 10


# ------------------------------------------- flow: origin & strategy variants

def test_flow_origin_specific_rule(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="svc", count=2, limit_app="appA")])
    with stpu.ContextScope("ctx", origin="appA"):
        assert burst(sph, "svc", 5) == (2, 3)
    with stpu.ContextScope("ctx", origin="appB"):
        assert burst(sph, "svc", 5) == (5, 0)  # rule not applicable


def test_flow_limit_app_other(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([
        stpu.FlowRule(resource="svc2", count=10, limit_app="appA"),
        stpu.FlowRule(resource="svc2", count=1, limit_app="other"),
    ])
    with stpu.ContextScope("c", origin="appA"):
        assert burst(sph, "svc2", 5) == (5, 0)   # matches specific rule (10)
    with stpu.ContextScope("c", origin="appB"):
        assert burst(sph, "svc2", 3) == (1, 2)   # falls into "other" (1)


def test_flow_relate_strategy(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource="write_db", count=3, strategy=stpu.STRATEGY_RELATE,
        ref_resource="read_db")])
    # no read traffic → writes flow
    assert burst(sph, "write_db", 2) == (2, 0)
    # read traffic saturates the related resource → writes blocked
    burst(sph, "read_db", 5)
    assert burst(sph, "write_db", 2) == (0, 2)


# ------------------------------------------------------------------ degrade

def test_degrade_slow_ratio_trip_and_recover(clk):
    sph = make_sentinel(clk)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="slow", grade=stpu.GRADE_RT, count=50, time_window=2,
        min_request_amount=5, slow_ratio_threshold=0.5)])
    for _ in range(5):
        e = sph.entry("slow")
        clk.advance_ms(100)  # rt = 100ms > 50 → slow
        e.exit()
    with pytest.raises(stpu.DegradeException):
        sph.entry("slow")
    # retry window not elapsed yet
    clk.advance_ms(1000)
    with pytest.raises(stpu.DegradeException):
        sph.entry("slow")
    # elapsed → HALF_OPEN probe admitted; fast completion closes the breaker
    clk.advance_ms(1100)
    e = sph.entry("slow")
    clk.advance_ms(10)
    e.exit()
    assert burst(sph, "slow", 3) == (3, 0)


def test_degrade_half_open_probe_failure_reopens(clk):
    sph = make_sentinel(clk)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="flaky", grade=stpu.GRADE_RT, count=50, time_window=1,
        min_request_amount=3, slow_ratio_threshold=0.4)])
    for _ in range(3):
        e = sph.entry("flaky")
        clk.advance_ms(200)
        e.exit()
    with pytest.raises(stpu.DegradeException):
        sph.entry("flaky")
    clk.advance_ms(1200)
    e = sph.entry("flaky")   # probe
    clk.advance_ms(200)      # still slow
    e.exit()                 # probe fails → OPEN again
    with pytest.raises(stpu.DegradeException):
        sph.entry("flaky")


def test_degrade_exception_ratio(clk):
    sph = make_sentinel(clk)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="errsvc", grade=stpu.GRADE_EXCEPTION_RATIO, count=0.5,
        time_window=2, min_request_amount=4)])
    for i in range(4):
        e = sph.entry("errsvc")
        if i % 2 == 0:
            e.trace(RuntimeError("boom"))
        e.exit()
    # ratio 0.5 is NOT > 0.5 → still closed
    e = sph.entry("errsvc")
    e.trace(RuntimeError("boom"))
    e.exit()  # 3/5 = 0.6 > 0.5 → trip
    with pytest.raises(stpu.DegradeException):
        sph.entry("errsvc")


def test_degrade_exception_count(clk):
    sph = make_sentinel(clk)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="cnt", grade=stpu.GRADE_EXCEPTION_COUNT, count=3,
        time_window=5, min_request_amount=1)])
    for _ in range(3):
        e = sph.entry("cnt")
        e.trace(ValueError("x"))
        e.exit()
    with pytest.raises(stpu.DegradeException):
        sph.entry("cnt")


def test_degrade_exception_via_context_manager(clk):
    """The with-block auto-traces business exceptions (aspect parity)."""
    sph = make_sentinel(clk)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="auto", grade=stpu.GRADE_EXCEPTION_COUNT, count=1,
        time_window=5, min_request_amount=1)])
    with pytest.raises(ValueError):
        with sph.entry("auto"):
            raise ValueError("business failure")
    with pytest.raises(stpu.DegradeException):
        sph.entry("auto")


# ---------------------------------------------------------------- authority

def test_authority_white_black(clk):
    sph = make_sentinel(clk)
    sph.load_authority_rules([
        stpu.AuthorityRule(resource="adm", limit_app="appA,appB",
                           strategy=stpu.STRATEGY_WHITE),
        stpu.AuthorityRule(resource="blk", limit_app="evil",
                           strategy=stpu.STRATEGY_BLACK),
    ])
    with stpu.ContextScope("c", origin="appA"):
        assert burst(sph, "adm", 1) == (1, 0)
    with stpu.ContextScope("c", origin="stranger"):
        with pytest.raises(stpu.AuthorityException):
            sph.entry("adm")
    # empty origin always passes (AuthorityRuleChecker early return)
    assert burst(sph, "adm", 1) == (1, 0)
    with stpu.ContextScope("c", origin="evil"):
        with pytest.raises(stpu.AuthorityException):
            sph.entry("blk")
    with stpu.ContextScope("c", origin="friend"):
        assert burst(sph, "blk", 1) == (1, 0)


# ------------------------------------------------------------------- system

def test_system_qps_gate_inbound_only(clk):
    sph = make_sentinel(clk)
    sph.load_system_rules([stpu.SystemRule(qps=5)])
    p, b = burst(sph, "in_res", 8)
    assert (p, b) == (5, 3)
    with pytest.raises(stpu.SystemBlockException):
        sph.entry("other_in")
    # OUT traffic is exempt (checkSystem gates EntryType.IN only)
    assert burst(sph, "out_res", 4, entry_type=stpu.ENTRY_TYPE_OUT) == (4, 0)


def test_system_thread_gate(clk):
    """Reference checkSystem: block when curThread > threshold (strict >), so
    the entry that *reaches* the threshold is admitted, the next is not."""
    sph = make_sentinel(clk)
    sph.load_system_rules([stpu.SystemRule(max_thread=2)])
    e1 = sph.entry("a")
    e2 = sph.entry("b")
    e3 = sph.entry("c")   # curThread=2, 2 > 2 is false → admitted
    with pytest.raises(stpu.SystemBlockException):
        sph.entry("d")    # curThread=3 > 2 → blocked
    e1.exit()
    sph.entry("d").exit()
    e2.exit()
    e3.exit()


# ------------------------------------------------------------------ plumbing

def test_global_switch_off_bypasses_everything(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="sw", count=0)])
    with pytest.raises(stpu.FlowException):
        sph.entry("sw")
    sph.set_global_switch(False)
    assert burst(sph, "sw", 5) == (5, 0)
    sph.set_global_switch(True)
    with pytest.raises(stpu.FlowException):
        sph.entry("sw")


def test_rule_reload_resets_shaping_state(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="r1", count=1)])
    assert burst(sph, "r1", 2) == (1, 1)
    sph.load_flow_rules([stpu.FlowRule(resource="r1", count=100)])
    assert burst(sph, "r1", 10) == (10, 0)


def test_property_cell_drives_rules(clk):
    sph = make_sentinel(clk)
    sph.flow_property.update_value([stpu.FlowRule(resource="p", count=2)])
    assert burst(sph, "p", 4) == (2, 2)


def test_double_exit_raises(clk):
    sph = make_sentinel(clk)
    e = sph.entry("x")
    e.exit()
    with pytest.raises(stpu.BlockException.__mro__[1]):  # SentinelError base
        e.exit()


def test_block_exception_carries_metadata(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="meta", count=0)])
    with stpu.ContextScope("c", origin="caller"):
        with pytest.raises(stpu.FlowException) as ei:
            sph.entry("meta")
    assert ei.value.resource == "meta"
    assert ei.value.origin == "caller"


# ------------------------------------------- review-finding regressions

def test_batch_denied_event_does_not_consume_quota(clk):
    """A denied request must not eat quota for later batch peers
    (DefaultController: only admitted requests increment pass)."""
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="h", count=10)])
    v = sph.entry_batch(["h"] * 3, acquire=[8, 5, 2])
    assert list(np.asarray(v.allow)) == [True, False, True]


def test_system_qps_denied_event_does_not_consume(clk):
    sph = make_sentinel(clk)
    sph.load_system_rules([stpu.SystemRule(qps=10)])
    v = sph.entry_batch(["a", "b", "c"], acquire=[8, 5, 2])
    assert list(np.asarray(v.allow)) == [True, False, True]


def test_two_breakers_probe_blocked_by_sibling_no_halfopen_strand(clk):
    """A rule must not strand in HALF_OPEN when its probe event is blocked by
    a sibling breaker with a longer OPEN window."""
    sph = make_sentinel(clk)
    sph.load_degrade_rules([
        stpu.DegradeRule(resource="dual", grade=stpu.GRADE_EXCEPTION_COUNT,
                         count=1, time_window=1, min_request_amount=1),
        stpu.DegradeRule(resource="dual", grade=stpu.GRADE_EXCEPTION_COUNT,
                         count=1, time_window=60, min_request_amount=1),
    ])
    e = sph.entry("dual")
    e.trace(ValueError("x"))
    e.exit()  # both rules trip
    with pytest.raises(stpu.DegradeException):
        sph.entry("dual")
    clk.advance_ms(1500)  # rule1 retry due, rule2 not
    with pytest.raises(stpu.DegradeException):
        sph.entry("dual")  # rule1 wants a probe but rule2 blocks → no strand
    # rule1 must still be OPEN (not HALF_OPEN): verify by checking that once
    # rule2's window also elapses, a probe IS admitted (HALF_OPEN would block)
    clk.advance_ms(60_000)
    e = sph.entry("dual")
    e.exit()  # clean probe closes both
    assert burst(sph, "dual", 2) == (2, 0)


def test_rate_limiter_pacing_is_per_rule_across_origins(clk):
    """Pacing clock is per rule (one latestPassedTime per controller), not
    per origin stat row."""
    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource="rl", count=10, limit_app="other",
        control_behavior=stpu.BEHAVIOR_RATE_LIMITER, max_queueing_time_ms=10_000)])
    v = sph.entry_batch(["rl"] * 4,
                        origins=["appA", "appB", "appA", "appB"])
    # one shared 100ms pacing ladder, not two independent ones
    assert sorted(np.asarray(v.wait_ms).tolist()) == [0, 100, 200, 300]


def test_alt_free_variant_matches_full_on_originless_batch(clk):
    """record_alt=False (the runtime's choice for batches with no
    origin/chain rows) must produce identical verdicts and main-table
    state; alt tables pass through untouched."""
    import functools

    import jax
    import jax.numpy as jnp

    from sentinel_tpu.engine.pipeline import (
        EntryBatch, ExitBatch, decide_entries, record_exits,
    )

    sph = make_sentinel(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="f", count=3.0)])
    spec, rules, state = sph.spec, sph._ruleset, sph._state
    row = sph.resources.get_or_create("f")
    B = 8
    eb = EntryBatch(
        rows=jnp.full(B, row, jnp.int32),
        origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),   # all padding
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32), is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    times = sph._time_scalars(clk.now_ms())
    sysv = jnp.asarray(np.array([0.1, 0.1], np.float32))
    full = jax.jit(functools.partial(decide_entries, spec,
                                     enable_occupy=False))
    noalt = jax.jit(functools.partial(decide_entries, spec,
                                      enable_occupy=False,
                                      record_alt=False))
    s1, v1 = full(rules, state, eb, times, sysv)
    s2, v2 = noalt(rules, state, eb, times, sysv)
    assert np.array_equal(v1.allow, v2.allow)
    assert np.array_equal(np.asarray(s1.second.counters),
                          np.asarray(s2.second.counters))
    assert np.array_equal(np.asarray(s1.threads), np.asarray(s2.threads))
    # alt tables pass through unchanged in the noalt variant; in the full
    # variant the refresh may restamp but records nothing
    assert np.asarray(s2.alt_threads).sum() == 0

    xb = ExitBatch(
        rows=jnp.full(B, row, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        rt_ms=jnp.full(B, 7, jnp.int32),
        error=jnp.zeros(B, jnp.bool_),
        is_in=jnp.ones(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    xfull = jax.jit(functools.partial(record_exits, spec))
    xnoalt = jax.jit(functools.partial(record_exits, spec,
                                       record_alt=False))
    e1 = xfull(rules, s1, xb, times)
    e2 = xnoalt(rules, s2, xb, times)
    assert np.array_equal(np.asarray(e1.second.counters),
                          np.asarray(e2.second.counters))
    assert np.array_equal(np.asarray(e1.threads), np.asarray(e2.threads))


def test_runtime_selects_alt_free_variant(clk):
    """decide_raw on an origin-less batch dispatches the *_noalt step; a
    batch with a real origin row dispatches the full one."""
    sph = make_sentinel(clk, host_fast_path=False)
    hits = {"noalt": 0, "full": 0}
    orig_noalt, orig_full = sph._jit_decide_noalt, sph._jit_decide

    def w(fn, key):
        def inner(*a, **k):
            hits[key] += 1
            return fn(*a, **k)
        return inner
    sph._jit_decide_noalt = w(orig_noalt, "noalt")
    sph._jit_decide = w(orig_full, "full")
    # with SENTINEL_SINGLE_DISPATCH on (the default) the dispatch goes
    # through the sketch-fused tuple instead — same variant layout:
    # indices 0/1 carry alt recording, 2/3 are the *_noalt pair
    orig_sd = sph._sd_steps_locked

    def sd_wrapped():
        d = orig_sd()
        return (w(d[0], "full"), w(d[1], "full"),
                w(d[2], "noalt"), w(d[3], "noalt"))

    sph._sd_steps_locked = sd_wrapped
    with sph.entry("plain"):
        pass
    assert hits == {"noalt": 1, "full": 0}
    with sph.entry("plain", origin="up-a"):
        pass
    assert hits == {"noalt": 1, "full": 1}


def test_sample_count_one_engine_full_arc(clk):
    """B=1 second window (sampleCount=1, a reference-supported config):
    exercises the refresh_rows fallback branches in decide/exit/blocks —
    flow admission, warm-up prev-window pacing, origin stats, and exits all
    behave across window rotation."""
    sph = make_sentinel(clk, second_sample_count=1, second_interval_ms=1000)
    assert sph.spec.second.buckets == 1
    sph.load_flow_rules([
        stpu.FlowRule(resource="b1", count=3.0),
        stpu.FlowRule(resource="wu", count=100.0,
                      control_behavior=stpu.BEHAVIOR_WARM_UP,
                      warm_up_period_sec=10),
    ])
    for step in range(3):
        p, b = burst(sph, "b1", 5, origin="up-a")
        assert (p, b) == (3, 2), (step, p, b)
        clk.advance_ms(1000)
    # warm-up ramp needs prev-window pass counts (prev_window_sum_rows):
    # cold start must throttle well below the full count
    p, _ = burst(sph, "wu", 60)
    assert 0 < p < 60
    tot = sph.node_totals("b1")
    assert tot["block"] == 0 and tot["pass"] == 0   # rotated out
    e = sph.entry("b1")
    e.exit()
    assert sph.node_totals("b1")["success"] == 1


def test_sample_count_one_outbound_batch_keeps_entry_prev_window(clk):
    """B=1 second window: a batch with no IN events must NOT restamp the
    ENTRY node's single bucket — with sampleCount=1 the current and
    previous windows share the bucket position, so an unconditional
    refresh would erase ENTRY's previousPassQps (warm-up rules reading the
    entry node). Advisor finding r3-1."""
    from sentinel_tpu.core.registry import ENTRY_NODE_ROW
    from sentinel_tpu.stats import events as ev
    from sentinel_tpu.stats.window import prev_window_sum_rows

    sph = make_sentinel(clk, second_sample_count=1, second_interval_ms=1000,
                        host_fast_path=False)
    assert sph.spec.second.buckets == 1
    # window W: 4 IN passes land on ENTRY
    for _ in range(4):
        sph.entry("r_in").exit()
    clk.advance_ms(1000)
    # window W+1: outbound-only traffic (no IN events) — entry() and
    # exit() both dispatch device steps whose batches carry no IN event
    e = sph.entry("r_out", entry_type=stpu.ENTRY_TYPE_OUT)
    e.exit()
    now_idx = sph.spec.second.index_of(clk.now_ms())
    prev = prev_window_sum_rows(
        sph.spec.second, sph._state.second,
        np.array([ENTRY_NODE_ROW], np.int32), ev.PASS, now_idx)
    assert int(np.asarray(prev)[0]) == 4


def test_init_state_np_parity():
    """The numpy mirror used for transfer-based cold start must be
    bit-identical to the traced init (drift pin for pipeline._init_state_np
    vs _init_state_traced)."""
    import jax
    import numpy as np
    from sentinel_tpu.engine.pipeline import (
        EngineSpec, _init_state_np, _init_state_traced,
    )
    from sentinel_tpu.stats.window import WindowSpec
    spec = EngineSpec(rows=32, alt_rows=16, second=WindowSpec(2, 500),
                      minute=WindowSpec(60, 1000), statistic_max_rt=5000,
                      param_keys=8, param_pairs=2)
    a = _init_state_np(spec, 5, 3)
    b = _init_state_traced(spec, 5, 3)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert la.dtype == np.asarray(lb).dtype
        assert la.shape == np.asarray(lb).shape
        assert np.array_equal(la, np.asarray(lb))
