"""All four traffic-shaping controllers and both ratio circuit breakers on
the normal path, against the plain sequential reference
(``chipbench/reference/shaping.py``): 1,024 rows, a manual clock, Zipf
batches of 64 through ``DispatchPipeline``, every admitted entry exited.
Reasons AND ``wait_ms`` agree event for event for each controller alone,
each breaker grade alone, and all together with sick completions; where a
count-bound name meets a breaker that is not CLOSED (the fault ROADMAP R4
described: an event the breaker refuses was charged to the flow budget) the
three decide routes answer as the sequence does; and the decide program of
an all-CLOSED deployment is no larger than it was."""

import collections
import re

import jax.numpy as jnp
import numpy as np
import pytest

import sentinel_tpu as stpu
from chipbench.generators.arrivals import rng_for, zipf_ranks
from chipbench.reference import shaping
from chipbench.reference.shaping import (
    DEGRADE, FLOW, PASS, Breaker, FlowShape, ShapingReference,
)
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.rules.degrade import GRADE_EXCEPTION_RATIO, GRADE_RT

ROWS, NAMES, BATCH, STEPS, STEP_MS = 1024, 256, 64, 130, 70
UNIVERSE = np.array([f"r{i}" for i in range(NAMES)], object)


def _engine(flow, breakers):
    clk = ManualClock(start_ms=1_785_000_000_123)
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=ROWS, max_flow_rules=128, max_degrade_rules=128,
        host_fast_path=False), clock=clk)
    sph.load_flow_rules([stpu.FlowRule(
        resource=n, count=float(s.count), control_behavior=s.behavior,
        warm_up_period_sec=s.warm_up_period_s,
        max_queueing_time_ms=s.max_queue_ms) for n, s in flow.items()])
    sph.load_degrade_rules([stpu.DegradeRule(
        resource=n,
        grade=GRADE_RT if b.grade == shaping.SLOW_RATIO
        else GRADE_EXCEPTION_RATIO,
        count=b.max_rt_ms if b.grade == shaping.SLOW_RATIO else b.threshold,
        time_window=b.retry_ms // 1000, min_request_amount=b.min_requests,
        stat_interval_ms=b.interval_ms,
        slow_ratio_threshold=b.threshold
        if b.grade == shaping.SLOW_RATIO else 1.0)
        for n, b in breakers.items()])
    return sph, clk


def _exit(sph, rows, rt, err):
    n = rows.shape[0]
    pad = np.full(n, sph.spec.alt_rows, np.int32)
    sph.exit_batch(rows=rows, origin_rows=pad, chain_rows=pad,
                   acquire=np.ones(n, np.int32), rt_ms=rt, error=err,
                   is_in=np.ones(n, bool))


def _drive(sph, clk, flow, breakers, seed=11, incident=False):
    """The closed loop of a batch-tier caller → (reasons that differ,
    waits that differ, the reference). With ``incident`` a completion of a
    sick name (rank ``r`` with ``(r + k // 40) % 4 == 0`` in submit ``k``)
    is slow and fails 60 % of the time."""
    ref = ShapingReference(flow, breakers, sph.epoch_ms)
    pipe = stpu.DispatchPipeline(sph)
    rng = rng_for(seed, 9)
    ranks = zipf_ranks(rng_for(seed, 2), STEPS * BATCH, 1.1, NAMES)
    wrong = wait_wrong = 0
    prev = None

    def settle(ticket, names, idx, k, want, want_wait):
        nonlocal wrong, wait_wrong
        v = ticket.result()
        allow = np.asarray(v.allow)
        got = np.where(allow, 0, np.asarray(v.reason))
        wrong += int((got != np.asarray(want)).sum())
        wait_wrong += int((np.asarray(v.wait_ms)
                           != np.asarray(want_wait)).sum())
        passed = np.nonzero(allow)[0]
        n = passed.size
        sick = ((idx[passed] + k // 40) % 4 == 0) & incident
        rt = np.maximum(1, rng.lognormal(
            np.log(np.where(sick, 80.0, 5.0)), 0.5)).astype(np.int32)
        err = rng.random(n) < np.where(sick, 0.6, 0.01)
        _exit(sph, ticket.rows[passed], rt, err)
        ref.exits([names[i] for i in passed], rt.tolist(), err.tolist(),
                  clk.now_ms())

    for k in range(STEPS):
        idx = ranks[k * BATCH:(k + 1) * BATCH]
        names = UNIVERSE[idx].tolist()
        ticket = pipe.submit(names)
        want, want_wait = ref.entries(names, clk.now_ms())
        if prev is not None:
            settle(*prev)
        prev = (ticket, names, idx, k, want, want_wait)
        clk.advance_ms(STEP_MS)
    settle(*prev)
    return wrong, wait_wrong, ref


def _flow(behavior, names=range(24), count=7, period=2):
    return {f"r{i}": FlowShape(count, behavior, warm_up_period_s=period,
                               max_queue_ms=300) for i in names}


def _breakers(grade, names=range(48)):
    return {f"r{i}": (
        Breaker(shaping.SLOW_RATIO, 0.6, 1000, max_rt_ms=50, min_requests=3,
                interval_ms=1000) if grade == shaping.SLOW_RATIO else
        Breaker(shaping.ERROR_RATIO, 0.5, 1000, min_requests=3,
                interval_ms=1000)) for i in names}


def _mixed_flow():
    return {f"r{i}": FlowShape((5, 7, 20)[i % 3], i % 4,
                               warm_up_period_s=(2, 3)[i % 2],
                               max_queue_ms=300) for i in range(32)}


def _mixed_breakers():
    return {**_breakers(shaping.SLOW_RATIO, range(1, 64, 2)),
            **_breakers(shaping.ERROR_RATIO, range(0, 64, 2))}


CASES = {
    "default": (lambda: _flow(shaping.DEFAULT), dict, False, ()),
    "warm_up": (lambda: _flow(shaping.WARM_UP), dict, False, ("cold_block",)),
    "rate_limiter": (lambda: _flow(shaping.RATE_LIMITER), dict, False,
                     ("paced_pass",)),
    "warm_up_rate_limiter": (lambda: _flow(shaping.WARM_UP_RATE_LIMITER),
                             dict, False, ("paced_pass",)),
    # names that see an event every second or two: the original syncs a
    # warm-up rule's tokens when an event asks, not every second
    "warm_up_idle_seconds": (
        lambda: _flow(shaping.WARM_UP, names=range(40, 140), count=3), dict,
        False, ("cold_block",)),
    "warm_up_rate_limiter_idle_seconds": (
        lambda: _flow(shaping.WARM_UP_RATE_LIMITER, names=range(40, 140),
                      count=3), dict, False, ("paced_pass",)),
    "slow_ratio": (dict, lambda: _breakers(shaping.SLOW_RATIO), True,
                   ("slow_ratio_trip", "probe_closed", "probe_reopened")),
    "error_ratio": (dict, lambda: _breakers(shaping.ERROR_RATIO), True,
                    ("error_ratio_trip", "probe_closed", "probe_reopened")),
    "all_together": (_mixed_flow, _mixed_breakers, True, shaping.EXERCISES),
}


@pytest.mark.parametrize("case", list(CASES))
def test_reasons_and_waits_equal_the_sequential_reference(case):
    make_flow, make_breakers, incident, exercised = CASES[case]
    flow, breakers = make_flow(), make_breakers()
    sph, clk = _engine(flow, breakers)
    try:
        wrong, wait_wrong, ref = _drive(sph, clk, flow, breakers,
                                        incident=incident)
        assert (wrong, wait_wrong) == (0, 0)
        assert all(ref.seen[e] > 0 for e in exercised), ref.seen
        assert sph.obs.counters.get(ck.ROUTE_SCALAR) == STEPS
        paced = sph.obs.counters.get(ck.VERDICT_PACED)
        assert paced == ref.seen["paced_pass"]
        assert (paced > 0) == ("paced_pass" in exercised)
    finally:
        sph.close()


def test_a_warm_up_rate_limiter_paces_at_the_warm_up_rate():
    """Cold, a count-7 rule over 2 s runs at 7/3 a second: the cost of a
    token is round(1000 / nextUp(1 / (7 * slope + 1/7))) = 429 ms, not
    1000/7 = 143."""
    flow = _flow(shaping.WARM_UP_RATE_LIMITER, names=[0])
    sph, clk = _engine(flow, {})
    try:
        v = sph.entry_batch_nowait(["r0"] * 3).result()
        assert np.asarray(v.allow).tolist() == [True, False, False]
        clk.advance_ms(200)
        v = sph.entry_batch_nowait(["r0"] * 2).result()
        assert np.asarray(v.allow).tolist() == [True, False]
        assert np.asarray(v.wait_ms).tolist() == [229, 0]
    finally:
        sph.close()


# -- where a count-bound name meets a breaker that is not CLOSED (R4) ------

X = "r0"
MEET_FLOW = {X: FlowShape(3)}
MEET_BREAKER = {X: Breaker(shaping.ERROR_RATIO, 0.5, 1000, min_requests=2)}


def _submit(sph, route, names):
    """One batch on the named decide route. ``fast``: every event carries
    an origin. ``general``: one event on an unruled name acquires 2, so
    the acquire is not uniform."""
    if route == "fast":
        h = sph.entry_batch_nowait(names, origins=["app"] * len(names))
    elif route == "general":
        h = sph.entry_batch_nowait(
            names + ["other"], acquire=[1] * len(names) + [2])
    else:
        h = sph.entry_batch_nowait(names)
    v = h.result()
    n = len(names)
    return (np.where(np.asarray(v.allow), 0, np.asarray(v.reason))[:n].tolist(),
            h.rows[:n])


@pytest.mark.parametrize("route,counter", [
    ("scalar", ck.ROUTE_SCALAR), ("fast", ck.ROUTE_FAST),
    ("general", ck.ROUTE_GENERAL)])
def test_an_event_the_breaker_refuses_spends_nothing_of_the_count(
        route, counter):
    """A name with ``count`` 3 whose breaker is OPEN: the window holds 2
    passes, so each of six events passes the flow slot (2 + 1 <= 3) and is
    refused by the breaker — DEGRADE six times, where the program used to
    charge the first to the budget and answer FLOW to the other five. Once
    the retry is due the first event is the probe and passes, and each of
    the other five meets 1 pass in the window and a HALF_OPEN breaker:
    DEGRADE, where the program used to answer FLOW from the fourth on."""
    sph, clk = _engine(MEET_FLOW, MEET_BREAKER)
    ref = ShapingReference(MEET_FLOW, MEET_BREAKER, sph.epoch_ms)
    try:
        got, rows = _submit(sph, route, [X] * 2)
        assert got == ref.entries([X] * 2, clk.now_ms())[0] == [PASS] * 2
        _exit(sph, rows, np.array([5, 5], np.int32), np.array([True, True]))
        ref.exits([X] * 2, [5, 5], [True, True], clk.now_ms())   # trips
        clk.advance_ms(100)
        got, _ = _submit(sph, route, [X] * 6)
        assert got == ref.entries([X] * 6, clk.now_ms())[0] == [DEGRADE] * 6
        clk.advance_ms(1000)                # the retry is due, window empty
        got, rows = _submit(sph, route, [X] * 6)
        assert got == ref.entries([X] * 6, clk.now_ms())[0] \
            == [PASS] + [DEGRADE] * 5
        # the probe fails: OPEN again, and a spent budget still reads FLOW
        _exit(sph, rows[:1], np.array([5], np.int32), np.array([True]))
        ref.exits([X], [5], [True], clk.now_ms())
        clk.advance_ms(1000)
        got, _ = _submit(sph, route, [X] * 6)
        assert got == ref.entries([X] * 6, clk.now_ms())[0] \
            == [PASS] + [DEGRADE] * 5
        assert ref.seen["refused_on_spent_budget"] >= 5 + 3
        assert ref.seen["probe_reopened"] == 1
        assert sph.obs.counters.get(counter) == 4
        assert sph.obs.counters.get(ck.ROUTE_SPLIT) == 0
    finally:
        sph.close()


def test_a_spent_budget_still_reads_flow_under_an_open_breaker():
    """The other side of the meeting: what HAS passed counts. Three passes
    fill ``count`` 3; with the breaker open the next events read FLOW,
    as in sequence, not DEGRADE."""
    sph, clk = _engine(MEET_FLOW, MEET_BREAKER)
    ref = ShapingReference(MEET_FLOW, MEET_BREAKER, sph.epoch_ms)
    try:
        got, rows = _submit(sph, "scalar", [X] * 4)
        assert got == ref.entries([X] * 4, clk.now_ms())[0] \
            == [PASS] * 3 + [FLOW]
        _exit(sph, rows[:3], np.full(3, 5, np.int32), np.ones(3, bool))
        ref.exits([X] * 3, [5] * 3, [True] * 3, clk.now_ms())
        got, _ = _submit(sph, "scalar", [X] * 2)
        assert got == ref.entries([X] * 2, clk.now_ms())[0] == [FLOW] * 2
    finally:
        sph.close()


def test_a_pacing_rule_spends_its_slot_on_an_event_the_breaker_refuses():
    """``RateLimiterController`` moves ``latestPassedTime`` inside
    ``canPass``, before ``DegradeSlot`` is asked: under an open breaker
    the first seven events of a burst at ``count`` 20 / 300 ms take the
    slots (waits 0 … 300) and read DEGRADE, the rest FLOW — and the next
    batch finds the queue full."""
    flow = {X: FlowShape(20, shaping.RATE_LIMITER, max_queue_ms=300)}
    sph, clk = _engine(flow, MEET_BREAKER)
    ref = ShapingReference(flow, MEET_BREAKER, sph.epoch_ms)
    try:
        got, rows = _submit(sph, "scalar", [X] * 2)
        _exit(sph, rows, np.array([5, 5], np.int32), np.array([True, True]))
        ref.entries([X] * 2, clk.now_ms())
        ref.exits([X] * 2, [5, 5], [True, True], clk.now_ms())
        clk.advance_ms(400)
        got, _ = _submit(sph, "scalar", [X] * 9)
        assert got == ref.entries([X] * 9, clk.now_ms())[0] \
            == [DEGRADE] * 7 + [FLOW] * 2
        clk.advance_ms(40)
        got, _ = _submit(sph, "scalar", [X] * 2)
        assert got == ref.entries([X] * 2, clk.now_ms())[0] == [FLOW] * 2
    finally:
        sph.close()


# -- the all-CLOSED deployment's program ------------------------------------

#: the scalar decide step of the engine below as the parent of PR 35
#: lowered it (StableHLO operations in all, and the three kinds that cost
#: on the chip)
PARENT_OPS = {"all": 813, "gather": 24, "scatter": 20, "sort": 0}


def test_the_all_closed_decide_program_is_no_larger_than_it_was():
    """Default rules and breakers that stay CLOSED — the four resident
    cells' population: the gate rides the breaker look-up the entry check
    made anyway (one gather, two bits), and with no warm-up rule loaded
    the warm-up block compiles away."""
    n = 64
    sph = stpu.Sentinel(
        config=stpu.load_config(max_resources=1024, max_flow_rules=64,
                                max_degrade_rules=64, minute_enabled=True),
        clock=ManualClock(start_ms=1_785_000_000_000))
    try:
        sph.load_flow_rules([stpu.FlowRule(resource=f"r{i}", count=3.0)
                             for i in range(16)])
        sph.load_degrade_rules([stpu.DegradeRule(
            resource=f"r{i}", grade=GRADE_EXCEPTION_RATIO, count=0.5,
            time_window=10) for i in range(8)])
        rows = np.asarray(sph.intern_resources(
            [f"r{i % 16}" for i in range(n)]), np.int32)
        pad = np.full(n, sph.spec.alt_rows, np.int32)
        zeros = np.zeros(n, np.int32)
        batch = sph._build_entry_batch(
            rows, zeros, pad, zeros, pad, np.ones(n, np.int32),
            np.ones(n, bool), np.zeros(n, bool), np.ones(n, bool),
            None, None, None, None, None)
        flags = {"skip_auth": sph._skip_auth, "skip_sys": sph._skip_sys,
                 "skip_threads": sph._skip_threads, "scalar_flow": True,
                 "scalar_has_rl": sph._scalar_has_rl}
        if sph._sortfree:
            flags["sortfree"] = True
        text = sph._jit_decide_noalt.lower(
            sph._ruleset, sph._state, batch,
            sph._time_scalars(sph.clock.now_ms()),
            jnp.asarray(np.zeros(2, np.float32)), **flags).as_text()
        ops = collections.Counter(re.findall(r"stablehlo\.(\w+)", text))
        assert sum(ops.values()) <= PARENT_OPS["all"]
        for kind in ("gather", "scatter", "sort"):
            assert ops[kind] <= PARENT_OPS[kind], kind
    finally:
        sph.close()


# -- the breakers as a telemetry tick finds them ------------------------------

def test_a_telemetry_tick_counts_the_breakers_and_misses_a_fast_arc():
    names = ["r0", "r1", "r2"]
    rule = Breaker(shaping.ERROR_RATIO, 0.5, 1000, min_requests=2)
    sph, clk = _engine({}, {n: rule for n in names})
    count = sph.obs.counters.get
    keys = (ck.BREAKER_SEEN_OPEN, ck.BREAKER_SEEN_CLOSED, ck.BREAKER_OPENED,
            ck.BREAKER_HALF_OPENED, ck.BREAKER_CLOSED)

    def fail(name, n=2):
        h = sph.entry_batch_nowait([name] * n)
        passed = np.nonzero(np.asarray(h.result().allow))[0]
        _exit(sph, h.rows[passed], np.full(passed.size, 5, np.int32),
              np.ones(passed.size, bool))

    def succeed(name):
        h = sph.entry_batch_nowait([name])
        assert np.asarray(h.result().allow).all()
        _exit(sph, h.rows, np.array([5], np.int32), np.array([False]))
    try:
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [0, 3, 0, 0, 0]
        fail("r0")                          # trips
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [1, 5, 1, 0, 0]
        clk.advance_ms(1000)
        h = sph.entry_batch_nowait(["r0"])  # the probe: HALF_OPEN
        assert np.asarray(h.result().allow).all()
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [2, 7, 1, 1, 0]
        _exit(sph, h.rows, np.array([5], np.int32), np.array([False]))
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [2, 10, 1, 1, 1]
        # a whole arc between two ticks — trip, probe, close — is missed
        fail("r1")
        clk.advance_ms(1000)
        succeed("r1")
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [2, 13, 1, 1, 1]
        # a reload starts the comparison anew: nothing "closed" by it
        fail("r2")
        sph.telemetry.poll()
        assert count(ck.BREAKER_OPENED) == 2
        sph.load_degrade_rules(sph._deg.rules)
        sph.telemetry.poll()
        assert [count(k) for k in keys] == [3, 18, 2, 1, 1]
    finally:
        sph.close()


def test_no_degrade_rule_no_breaker_reading():
    sph, clk = _engine(_flow(shaping.DEFAULT), {})
    try:
        sph.telemetry.poll()
        assert sph.obs.counters.get(ck.BREAKER_SEEN_CLOSED) == 0
        assert sph.telemetry._breakers_prev is None
    finally:
        sph.close()
