"""``rules-110k.batch-faulty`` on the CPU at a tiny size: 4,096 rows, 64
flow rules over the four controllers, 256 breakers of both grades with a
1 s window, batches of 64 in a temporary checkout of its own. A sound run
agrees with the plain sequential reference in reasons AND waits and
contains everything the configuration is for; the control (refused events
charged to the flow budget) does not agree; a dropped ``wait_ms`` is
caught; the per-layer line fills from the run's spans and counters and a
stand-in trace; and the reference's controllers follow hand-worked
sequences."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import registry, run, spec, trace
from chipbench.deployments.embed_rules import rule_population
from chipbench.readers.common import Facts
from chipbench.reference import shaping
from chipbench.reference.shaping import (
    DEGRADE, FLOW, PASS, Breaker, FlowShape, ShapingReference,
)

REPO = Path(__file__).resolve().parents[2]
CELL = "rules-110k.batch-faulty"
TINY = dict(rows=4096, flow_rules=64, degrade_rules=256, max_flow_rules=128,
            max_degrade_rules=512, degrade_window_s=1,
            degrade_stat_interval_ms=1000)
TINY_MIX = dict(batch=64, events=64 * 512, warm_seconds=0.5,
                warm_exit_sizes=[8, 16, 32, 64],
                sick_moves_every_submits=800)
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def rules_checkout(tmp_path_factory, make_tiny_checkout):
    checkout = make_tiny_checkout(tmp_path_factory.mktemp("checkout"))
    for folder, name, cut in (("configs", "rules-110k", TINY),
                              ("traffic", "batch-faulty", TINY_MIX)):
        path = checkout / "chipbench" / folder / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **cut}))
    return checkout


@pytest.fixture(scope="module")
def sound(rules_checkout):
    return run.run_cell(CELL, 2**31 + 11, 4.0, False, checkout=rules_checkout,
                        require_chip=False, control=True, keep=True)


def test_the_cell_is_the_projects_configs_2_and_3_on_the_twins_table():
    cell = spec.resolve(REPO, CELL)
    twin = spec.resolve(REPO, "embed-1m.batch-scalar")
    cfg, mix = cell.config, cell.traffic
    assert cell.chips == 1 and cfg["builder"] == "embedded_engine_rules"
    assert cfg["reduced"] == [] and cfg["rows"] == twin.config["rows"]
    assert (cfg["flow_rules"], cfg["degrade_rules"]) == (10_000, 100_000)
    assert (cfg["max_flow_rules"], cfg["max_degrade_rules"]) \
        == (20_000, 200_000)
    assert cfg["guarantees"][:3] == twin.config["guarantees"]
    assert len(cfg["guarantees"]) == 7 and len(cfg["assumed"]) >= 6
    flow, breakers = rule_population(cfg)
    assert [flow[f"r{i}"].behavior for i in range(4)] == [
        shaping.DEFAULT, shaping.WARM_UP, shaping.RATE_LIMITER,
        shaping.WARM_UP_RATE_LIMITER]
    assert set(flow.values()) == {FlowShape(20, b, 10, 500, 3)
                                  for b in range(4)}
    assert breakers["r1"] == Breaker(shaping.SLOW_RATIO, 0.6, 10_000, 50,
                                     5, 10_000)
    assert breakers["r99998"] == Breaker(shaping.ERROR_RATIO, 0.5, 10_000,
                                         0.0, 5, 10_000)
    assert len(flow) == 10_000 and len(breakers) == 100_000
    # the mix is batch-scalar's loop with the sick completions added
    same = ("drives", "loop", "generator", "batch", "events", "zipf_s",
            "acquire", "rt_median_ms", "rt_sigma", "error_rate",
            "warm_seconds", "warm_exit_sizes")
    assert all(mix[k] == twin.traffic[k] for k in same)
    assert (mix["sick_rt_median_ms"], mix["sick_error_rate"],
            mix["sick_one_in"], mix["sick_moves_every_submits"]) \
        == (80.0, 0.6, 10, 256)
    assert {m["name"] for m in cell.end_to_end} == {"decisions_per_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [
        "prep_ms.batch", "settle_ms.batch", "device_ms.batch",
        "decide_batch_roofline", "paced_share.rules",
        "breaker_open_share.rules", "degrade_block_share.rules"]
    assert {m["layer"] for m in cell.per_layer[4:]} == {"rules"}


def test_a_sound_run_is_correct_in_reasons_and_waits(sound):
    r = sound
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["checks"]) == {"engine_wrong", "caller_wrong", "wait_wrong",
                                "unexercised"}
    assert all(c == {"value": 0, "limit": 0} for c in r["checks"].values())
    assert set(r["metrics"]) == {"decisions_per_s", "setup_s"}
    assert r["compilations_in_window"] == 0
    c = r["_measured"].counters
    assert c["verdict.paced"] > 0 and c["verdict.passed_now"] > 0
    assert c["block_reason.FlowException"] > 0
    assert c["block_reason.DegradeException"] > 0
    assert c["breaker.seen_open"] > 0 and c["breaker.seen_closed"] > 0
    assert c["breaker.opened"] > 0


def test_the_control_charges_refused_events_and_reads_wrong(sound):
    control = sound["control"]
    assert control["engine_wrong"]["value"] > 0
    assert control["caller_wrong"] == control["engine_wrong"]
    assert set(control) == {"engine_wrong", "caller_wrong", "wait_wrong"}


def test_the_counters_the_three_shares_read_are_there(sound):
    m = sound["_measured"]
    for name in ("bench.entry", "bench.exit", "entry.prep",
                 "pipeline.settle"):
        assert m.spans[name], name
    cell = spec.resolve(REPO, CELL)
    facts = Facts(m, None, cell, PEAKS)
    readers = registry.load("readers")
    by_name = {x["name"]: x for x in cell.per_layer}
    c = m.counters
    for name, hit, miss in (
            ("paced_share.rules", "verdict.paced", "verdict.passed_now"),
            ("breaker_open_share.rules", "breaker.seen_open",
             "breaker.seen_closed"),
            ("degrade_block_share.rules", "block_reason.DegradeException",
             "block_reason.FlowException")):
        share = readers[by_name[name]["reader"]](by_name[name], facts)
        assert 0 < share < 100
        assert share == pytest.approx(100 * c[hit] / (c[hit] + c[miss]))
    # every event of the window's batches is admitted with or without a
    # wait, or refused (the counters' deltas are read a batch apart)
    answered = (c["verdict.paced"] + c["verdict.passed_now"]
                + c["block_reason.FlowException"]
                + c["block_reason.DegradeException"])
    assert abs(answered - sound["attempted"]) <= 4 * 64


def test_a_dropped_wait_is_caught(rules_checkout):
    """A serving layer that hands the verdicts on without their waits:
    every reason is right and ``wait_wrong`` is not."""
    def drop_waits(obj):
        inner = obj.tap._entry

        class _Handle:
            def __init__(self, h):
                self._h, self.rows = h, h.rows

            def result(self):
                v = self._h.result()
                return v._replace(wait_ms=np.zeros_like(v.wait_ms))

        obj.tap._entry = lambda resources, **kw: _Handle(
            inner(resources, **kw))
    r = run.run_cell(CELL, 43, 1.5, False, checkout=rules_checkout,
                     require_chip=False, sabotage=drop_waits)
    assert r["correct"] is False
    assert r["checks"]["wait_wrong"]["value"] > 0
    assert r["checks"]["engine_wrong"]["value"] == 0
    assert r["checks"]["caller_wrong"]["value"] == 0


def test_the_per_layer_line_fills_from_a_traced_run(rules_checkout,
                                                    monkeypatch):
    """``--trace 1`` without a chip: the trace's reduction is stood in
    for, everything else is the run's own."""
    def fake_trace(trace_dir):
        # ten batches of 64, 300 ns of device work under each entry
        host = [["bench.entry", 1000 * k - 100, 800, 64] for k in range(10)]
        return trace.reduce([
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
                ["%copy.1 = s32[8]{0} copy(%p)", 1000 * k, 300]
                for k in range(10)]}]},
            {"name": "/host:CPU", "lines": [{"name": "caller",
                                             "events": host}]}])

    class _Tracer(run.Tracer):
        def _trace(self, t0):               # the clock alone, no profiler
            import time
            time.sleep(max(0.0, t0 + self.start_s + self.length_s
                           - time.monotonic()))
    monkeypatch.setattr(run, "Tracer", _Tracer)
    r = run.run_cell(CELL, 97, 2.5, True, checkout=rules_checkout,
                     require_chip=False, read_trace=fake_trace)
    cell = spec.resolve(rules_checkout, CELL)
    assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
    assert len(r["metrics"]) == 7 and "breakdown" in r     # eight in all
    assert r["metrics"]["device_ms.batch"]["value"] == pytest.approx(300e-6)
    from chipbench.readers.decide_roofline import decide_min_bytes
    assert r["metrics"]["decide_batch_roofline"]["value"] == pytest.approx(
        100 * 9 * decide_min_bytes(64) / 819e9 / (9 * 300e-9), rel=0.2)
    for name in ("paced_share.rules", "breaker_open_share.rules",
                 "degrade_block_share.rules"):
        assert 0 < r["metrics"][name]["value"] < 100, name
    assert all(r["checks"][k]["value"] == 0
               for k in ("engine_wrong", "caller_wrong", "wait_wrong"))


# -- the reference's controllers against hand-worked sequences --------------

T0 = 1_785_000_000_000


def _ref(shape=None, breaker=None, **kw):
    return ShapingReference({"x": shape} if shape else {},
                            {"x": breaker} if breaker else {}, T0, **kw)


def test_the_pacing_ladder_at_count_20():
    """A burst of twelve at one instant: cost 50 ms a token, waits 0, 50,
    … 500 for the first eleven, the twelfth is past ``maxQueueingTimeMs``
    and refused; 25 ms later one slot has not come free, 50 ms later one
    has."""
    ref = _ref(FlowShape(20, shaping.RATE_LIMITER, max_queue_ms=500))
    reasons, waits = ref.entries(["x"] * 12, T0)
    assert reasons == [PASS] * 11 + [FLOW]
    assert waits == list(range(0, 501, 50)) + [0]
    assert ref.entries(["x"], T0 + 25) == ([FLOW], [0])
    assert ref.entries(["x", "x"], T0 + 50) == ([PASS, FLOW], [500, 0])
    assert ref.seen["paced_pass"] == 10 + 1
    # an idle rule starts over: no wait, whatever it owed long ago
    assert ref.entries(["x", "x"], T0 + 5000) == ([PASS, PASS], [0, 50])


def test_warm_ups_cold_limit_and_its_drain():
    """count 20 over 10 s at cold factor 3: warningToken 100, maxToken
    200, slope 1/1000. Cold, 1 / (100/1000 + 1/20) = 6.67: six a second.
    A second that passed six does not refill ((int)20 / 3 = 6 is not
    above 6) and drains six tokens, so the rate climbs: 10 a second at
    150 tokens, 20 below 100."""
    ref = _ref(FlowShape(20, shaping.WARM_UP))
    st = ref.names["x"]
    assert (st.warning, st.max_token, st.slope) == (100, 200, 0.001)
    passed = []
    for sec in range(30):
        reasons, waits = ref.entries(["x"] * 25, T0 + 1000 * sec)
        assert waits == [0] * 25
        n = reasons.count(PASS)
        assert reasons == [PASS] * n + [FLOW] * (25 - n)
        passed.append(n)
    assert passed[:13] == [6, 6, 7, 7, 8, 8, 9, 10, 11, 12, 15, 19, 20]
    assert set(passed[13:]) == {20}
    # every event refused under the warm-up limit where count had room
    assert ref.seen["cold_block"] == sum(25 - n for n in passed if n < 20)
    # idle for a minute: the tokens refill and the rule is cold again
    reasons, _ = ref.entries(["x"] * 25, T0 + 90_000)
    assert reasons.count(PASS) == 6
    # at 150 tokens the rate is 10 exactly, and nextUp keeps the tenth
    st.stored = 150
    assert 10.0 <= ref._warm_qps(st) < 10.0 + 1e-9


def test_a_warm_up_rate_limiter_costs_what_the_warm_up_rate_says():
    ref = _ref(FlowShape(20, shaping.WARM_UP_RATE_LIMITER, max_queue_ms=500))
    # cold: 6.67 a second, 150 ms a token; 0, 150, 300, 450, then past 500
    reasons, waits = ref.entries(["x"] * 5, T0)
    assert (reasons, waits) == ([PASS] * 4 + [FLOW], [0, 150, 300, 450, 0])


@pytest.mark.parametrize("slow,trips", [(3, False), (4, True)])
def test_a_slow_ratio_breaker_holds_at_3_of_5_and_trips_at_4(slow, trips):
    rule = Breaker(shaping.SLOW_RATIO, 0.6, 10_000, max_rt_ms=50,
                   min_requests=5, interval_ms=10_000)
    ref = _ref(breaker=rule)
    assert ref.entries(["x"] * 5, T0) == ([PASS] * 5, [0] * 5)
    rts = [51] * slow + [50] * (5 - slow)   # exactly 50 ms is not slow
    ref.exits(["x"] * 5, rts, [True] * 5, T0 + 10)      # errors: no matter
    assert ref.seen["slow_ratio_trip"] == int(trips)
    want = DEGRADE if trips else PASS
    assert ref.entries(["x"] * 2, T0 + 9_000)[0] == [want] * 2
    if trips:
        # the retry is due: one probe passes; a fast completion closes it
        assert ref.entries(["x"] * 3, T0 + 10_010)[0] \
            == [PASS, DEGRADE, DEGRADE]
        ref.exits(["x"], [5], [False], T0 + 10_020)
        assert ref.seen["probe_closed"] == 1
        assert ref.entries(["x"], T0 + 10_030)[0] == [PASS]


def test_an_error_ratio_breaker_reopens_on_a_failed_probe():
    rule = Breaker(shaping.ERROR_RATIO, 0.5, 1000, min_requests=5)
    ref = _ref(breaker=rule)
    ref.entries(["x"] * 6, T0)
    ref.exits(["x"] * 6, [5] * 6, [True] * 3 + [False] * 3, T0)  # 0.5: holds
    assert ref.seen["error_ratio_trip"] == 0
    ref.exits(["x"], [5], [True], T0 + 1)                        # 4 of 7
    assert ref.seen["error_ratio_trip"] == 1
    assert ref.entries(["x"] * 2, T0 + 1001)[0] == [PASS, DEGRADE]
    ref.exits(["x"], [5], [True], T0 + 1002)
    assert ref.seen["probe_reopened"] == 1
    assert ref.entries(["x"], T0 + 2001)[0] == [DEGRADE]         # due 2002
    assert ref.entries(["x"], T0 + 2002)[0] == [PASS]


def test_the_planted_fault_charges_what_the_breaker_refuses():
    """The control's reference and the sound one on the meeting itself."""
    shape = FlowShape(3)
    rule = Breaker(shaping.ERROR_RATIO, 0.5, 1000, min_requests=2)
    answers = {}
    for charge in (False, True):
        ref = _ref(shape, rule, charge_refused=charge)
        ref.entries(["x"] * 2, T0)
        ref.exits(["x"] * 2, [5, 5], [True, True], T0)
        answers[charge] = ref.entries(["x"] * 6, T0 + 100)[0]
        assert ref.seen["refused_on_spent_budget"] == (0 if charge else 5)
    assert answers[False] == [DEGRADE] * 6
    assert answers[True] == [DEGRADE] + [FLOW] * 5
