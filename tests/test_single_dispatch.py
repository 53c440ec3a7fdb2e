"""Sketch-fused decide (``SENTINEL_SINGLE_DISPATCH``) and the one clock
of the telemetry + tiering ticks (``CadenceScheduler``).

Pins: verdict AND sketch-table bit-parity between
``SENTINEL_SINGLE_DISPATCH`` on and off (tiered engine, mid-run rule
reload, prioritized traffic, per-origin alt rows); tiered-vs-resident
parity with the fused observe on; the schedule — a service is ticked by
``CadenceScheduler.poll`` when 1.5 × its interval has passed since its
last tick, and by nothing else; and the disable env restoring the
legacy decide + observe composition verbatim.
"""

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.core.config import load_config
from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.runtime import Sentinel
from sentinel_tpu.serving import CadenceScheduler

T0 = 1_785_000_000_000


@pytest.fixture
def clk():
    return ManualClock(start_ms=T0)


def make(clk, **over):
    kw = dict(max_resources=64, max_flow_rules=16, max_degrade_rules=16,
              max_authority_rules=16, minute_enabled=True)
    kw.update(over)
    return stpu.Sentinel(config=stpu.load_config(**kw), clock=clk)


# ---------------------------------------------------------------------------
# parity fuzz: on vs off, tiered vs resident
# ---------------------------------------------------------------------------

def _run_engine(capacity, steps, batch, keys, rules, reload_rules, seed,
                origins=None):
    """tests/test_tiering.py's churn harness, plus the final sketch
    table: (verdict triples, tiering snapshot, sketch, counter map)."""
    clk = ManualClock(start_ms=T0)
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
        sketch = (None if s.tiering._sketch is None
                  else np.asarray(s.tiering._sketch).copy())
        counts = {k: s.obs.counters.get(k) for k in obs_keys.CATALOG}
        return verdicts, s.tiering.snapshot(), sketch, counts
    finally:
        s.close()


def _assert_parity(a_run, b_run):
    for step, (a, b) in enumerate(zip(a_run, b_run)):
        assert np.array_equal(a[0], b[0]), f"allow diverged @ step {step}"
        assert np.array_equal(a[1], b[1]), f"reason diverged @ step {step}"
        assert np.array_equal(a[2], b[2]), f"wait_ms diverged @ step {step}"


RULED = [f"zk{i}" for i in range(8)]
KEYS = [f"zk{i}" for i in range(48)]
RULES = [stpu.FlowRule(resource=r, count=3.0) for r in RULED]
RELOAD = ([stpu.FlowRule(resource=r, count=3.0) for r in RULED[:4]]
          + [stpu.FlowRule(resource=f"zk{i}", count=2.0)
             for i in range(8, 12)])


@pytest.mark.parametrize("origins", [None, ("app-a", "app-b")],
                         ids=["plain", "origins"])
def test_parity_on_vs_off_bitwise(monkeypatch, origins):
    """Verdicts AND the final count-min table must be bit-identical
    between the fused observe and the legacy standalone-dispatch
    composition — same tiered 24-row engine, same churn, mid-run
    reload, ~25% prioritized (the origins variant drives the general /
    split side so the sketch threads through multi-program steps).

    Staging stays ON: round 17 tied staging-slot reuse to dispatch
    settlement, so bit-parity holds with the ring engaged (the old
    ``SENTINEL_HOST_STAGING=0`` pin is gone — ROADMAP issue 5)."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    monkeypatch.setenv("SENTINEL_SINGLE_DISPATCH", "1")
    on, _snap_on, sk_on, c_on = _run_engine(
        24, 32, 12, KEYS, RULES, RELOAD, 1601, origins=origins)
    monkeypatch.setenv("SENTINEL_SINGLE_DISPATCH", "0")
    off, _snap_off, sk_off, c_off = _run_engine(
        24, 32, 12, KEYS, RULES, RELOAD, 1601, origins=origins)
    _assert_parity(on, off)
    assert sk_on is not None and sk_off is not None
    np.testing.assert_array_equal(sk_on, sk_off)
    blocked = sum(int((~a).sum()) for a, _r, _w in on)
    assert blocked > 0                       # the rules actually bit
    # the two runs really took different routes
    assert c_on[obs_keys.ROUTE_SINGLE_DISPATCH] > 0
    assert c_off[obs_keys.ROUTE_SINGLE_DISPATCH] == 0


def test_parity_tiered_vs_resident_single_dispatch(monkeypatch):
    """tests/test_tiering.py's load-bearing property survives the fused
    observe: a 24-row tiered engine == a 512-row resident engine, bit
    for bit, with both on the sketch-fused decide. Staging stays ON
    (settlement-tied slot reuse — see test_parity_on_vs_off_bitwise)."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    monkeypatch.setenv("SENTINEL_SINGLE_DISPATCH", "1")
    small, ssnap, _sk, sc = _run_engine(24, 32, 12, KEYS, RULES, RELOAD,
                                        1602)
    big, bsnap, _bk, _bc = _run_engine(512, 32, 12, KEYS, RULES, RELOAD,
                                       1602)
    _assert_parity(small, big)
    assert ssnap["demoted"] > 0 and ssnap["promoted"] > 0
    assert bsnap["demoted"] == 0
    assert sc[obs_keys.ROUTE_SINGLE_DISPATCH] > 0


# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------

def _serve_one(s, names):
    """One serving step in the two-call form: decide, then the exits."""
    rows = np.asarray(s.intern_resources(names), np.int32)
    n = len(names)
    s.entry_batch(names, acquire=[1] * n)
    pad = np.full(n, s.spec.alt_rows, np.int32)
    s.exit_batch(rows=rows, origin_rows=pad, chain_rows=pad,
                 acquire=np.ones(n, np.int32), rt_ms=np.full(n, 3, np.int32),
                 error=np.zeros(n, np.bool_), is_in=np.ones(n, np.bool_))


@pytest.mark.parametrize("due", [False, True], ids=["just_under", "at"])
@pytest.mark.parametrize("service,interval_ms",
                         [("telemetry", 1000), ("tiering", 200)])
def test_scheduler_is_the_one_clock(clk, service, interval_ms, due):
    """``start()`` stamps both services' last tick; from there a service
    is ticked by the first ``poll()`` at or after 1.5 × its interval —
    not one millisecond before — and serving traffic between polls
    neither ticks it nor moves ``last_tick_ms()``."""
    s = make(clk, host_fast_path=False)
    try:
        sched = CadenceScheduler(s, telemetry_interval_sec=1.0,
                                 tiering_interval_sec=0.2)
        svc = getattr(s, service)
        assert svc.enabled
        clk.advance_ms(777)                  # the stamp is start()'s, not
        sched.start()                        # the constructor's
        sched.stop()                         # poll() below is the body
        t_start = T0 + 777
        assert s.telemetry.last_tick_ms() == t_start
        assert s.tiering.last_tick_ms() == t_start
        ticks0 = svc.snapshot()["ticks"]
        threshold = int(interval_ms * CadenceScheduler.IDLE_FACTOR)
        for _ in range(3):                   # traffic, no poll
            clk.advance_ms(threshold // 4)
            _serve_one(s, ["a", "b", "c"])
        assert svc.snapshot()["ticks"] == ticks0
        assert svc.last_tick_ms() == t_start
        target = t_start + threshold - (0 if due else 1)
        clk.advance_ms(target - clk.now_ms())
        sched.poll()
        assert svc.snapshot()["ticks"] == ticks0 + (1 if due else 0)
        assert svc.last_tick_ms() == (target if due else t_start)
        assert sched.errors == 0
    finally:
        s.close()


def test_scheduler_self_dispatch_on_idle(clk, monkeypatch):
    """Zero traffic: the CadenceScheduler ticks a service once
    ``IDLE_FACTOR`` × its interval has passed since its last tick, and
    each tick re-bases that service's interval."""
    monkeypatch.setenv("SENTINEL_SINGLE_DISPATCH", "1")
    s = make(clk)
    try:
        sched = CadenceScheduler(s, telemetry_interval_sec=1.0,
                                 tiering_interval_sec=0.2)
        # no wall-clock thread — poll() is the body
        s.intern_resources(["a"])            # give the hot set a row
        tel0 = s.telemetry.snapshot()["ticks"]
        tier0 = s.tiering.snapshot()["ticks"]
        sched.poll()                         # fresh: nothing due
        assert s.telemetry.snapshot()["ticks"] == tel0
        assert s.tiering.snapshot()["ticks"] == tier0
        clk.advance_ms(350)                  # tiering due (>= 1.5x200)
        sched.poll()
        assert s.tiering.snapshot()["ticks"] == tier0 + 1
        assert s.telemetry.snapshot()["ticks"] == tel0
        clk.advance_ms(1200)                 # both due now
        sched.poll()
        assert s.telemetry.snapshot()["ticks"] == tel0 + 1
        assert s.tiering.snapshot()["ticks"] == tier0 + 2
        clk.advance_ms(250)                  # 250 < 300 since that tick
        sched.poll()
        assert s.tiering.snapshot()["ticks"] == tier0 + 2
        sched.stop()                         # idempotent without a thread
    finally:
        s.close()


def test_scheduler_start_stop_thread(monkeypatch):
    """start() stamps both services + spawns one daemon; stop() joins
    it. Registered with the engine's shutdown hooks (close() stops
    it)."""
    clk = ManualClock(start_ms=T0)
    s = make(clk)
    try:
        sched = CadenceScheduler(s)
        clk.advance_ms(40)
        sched.start()
        assert sched._thread is not None and sched._thread.is_alive()
        assert sched._thread.name == "sentinel-cadence"
        assert s.telemetry.last_tick_ms() == T0 + 40
        assert s.tiering.last_tick_ms() == T0 + 40
        sched.start()                        # idempotent
        sched.stop()
        assert sched._thread is None
        sched.stop()                         # idempotent
    finally:
        s.close()


def test_disable_env_restores_legacy_composition(clk, monkeypatch):
    """``SENTINEL_SINGLE_DISPATCH=0``: no sketch-fused programs are ever
    built, every decide pays the standalone observe dispatch again, and
    the single-dispatch route counter stays zero."""
    monkeypatch.setenv("SENTINEL_SINGLE_DISPATCH", "0")
    s = make(clk, host_fast_path=False)
    try:
        assert s.tiering.enabled
        for _ in range(3):
            s.entry_batch(["a", "b"], acquire=[1, 1])
            clk.advance_ms(25)
        assert s._sd_steps is None           # never built
        assert s.obs.counters.get(obs_keys.ROUTE_SINGLE_DISPATCH) == 0
        # decide + standalone observe = 2 dispatches per batch
        assert s.obs.counters.get(obs_keys.PIPE_DISPATCH) == 6
    finally:
        s.close()


def test_single_dispatch_default_on(clk, monkeypatch):
    monkeypatch.delenv("SENTINEL_SINGLE_DISPATCH", raising=False)
    s = make(clk, host_fast_path=False)
    try:
        assert s._single_dispatch
        s.entry_batch(["a"], acquire=[1])
        assert s.obs.counters.get(obs_keys.ROUTE_SINGLE_DISPATCH) == 1
        assert s.obs.counters.get(obs_keys.PIPE_DISPATCH) == 1
    finally:
        s.close()
