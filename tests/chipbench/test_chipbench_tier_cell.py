"""``tier-16m.batch-scalar`` on the CPU at a tiny size: 4,096 rows over
65,536 names in a temporary checkout of its own, batches of 32. A sound
run agrees with the plain reference in verdicts AND in what every name
owns, over names that were demoted and promoted; the control (tiering
off) does not; the per-layer line fills from the run's spans and counters
and a stand-in trace; the roofline's bytes match a hand count."""

import json
from pathlib import Path

import pytest

from chipbench import run, spec, trace
from chipbench.readers import tiering
from chipbench.readers.common import Facts
from chipbench.reference.tiered import HIST_BUCKETS, TieredReference, rt_bucket

REPO = Path(__file__).resolve().parents[2]
CELL = "tier-16m.batch-scalar"
TINY_TIER = dict(rows=4096, names=65536, flow_rules=64, flow_count=40,
                 degrade_rules=16, warm_migrate_rows=[8, 16, 32, 64])
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tier_checkout(tmp_path_factory, make_tiny_checkout):
    checkout = make_tiny_checkout(tmp_path_factory.mktemp("checkout"))
    path = checkout / "chipbench" / "configs" / "tier-16m.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TINY_TIER}))
    return checkout


@pytest.fixture(scope="module")
def sound(tier_checkout):
    return run.run_cell(CELL, 2**31 + 11, 3.0, False, checkout=tier_checkout,
                        require_chip=False, control=True, keep=True)


def test_the_cell_is_the_resident_twins_with_a_universe():
    cell = spec.resolve(REPO, CELL)
    twin = spec.resolve(REPO, "embed-1m.batch-scalar")
    assert cell.chips == 1
    assert cell.config["builder"] == "embedded_engine_tiered"
    assert cell.traffic == twin.traffic            # the mix file, unedited
    same = ("rows", "flow_rules", "flow_count", "degrade_rules",
            "degrade_ratio", "degrade_window_s", "window_buckets",
            "window_ms", "minute_ring")
    assert all(cell.config[k] == twin.config[k] for k in same)
    assert cell.config["names"] == 16 * cell.config["rows"] == 16_777_216
    assert cell.config["reduced"] == []
    assert cell.config["guarantees"][:3] == twin.config["guarantees"]
    assert {m["name"] for m in cell.end_to_end} == {"decisions_per_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [
        "prep_ms.batch", "settle_ms.batch", "device_ms.batch",
        "demote_ms.tier", "promote_ms.tier", "cold_land_ms.tier",
        "hot_hit_share.tier", "migrate_roofline.tier"]
    # its busy time is mostly migration: the decide's share would mislead
    assert "decide_batch_roofline" not in {m["name"] for m in cell.per_layer}


def test_a_sound_run_is_correct_over_names_that_migrated(sound, capfd):
    r = sound
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["checks"]) == {"engine_wrong", "caller_wrong",
                                "state_wrong", "unmigrated"}
    assert all(c == {"value": 0, "limit": 0} for c in r["checks"].values())
    assert set(r["metrics"]) == {"decisions_per_s", "setup_s"}
    assert r["compilations_in_window"] == 0
    c = r["_measured"].counters
    assert c["tier.demoted"] > 0 and c["tier.promoted"] > 0
    assert c["tier.first_sight"] > 0
    assert c["tier.hot_hit"] + c["tier.cold_miss"] <= c["intern.names"]


def test_the_control_loses_what_evicted_names_owned(sound):
    control = sound["control"]
    assert control["state_wrong"]["value"] > 0
    # no ruled name is ever evicted, so the verdicts hold without tiering
    assert control["engine_wrong"]["value"] == 0
    assert control["caller_wrong"]["value"] == 0


def test_the_phases_and_counters_the_readers_need_are_there(sound):
    m = sound["_measured"]
    for name in ("bench.entry", "bench.exit", "entry.prep",
                 "pipeline.settle", "tier.demote", "tier.promote",
                 "tier.land"):
        assert m.spans[name], name
    assert sum(s.n for s in m.spans["tier.demote"]) \
        <= m.counters["tier.demoted"] + 64
    cell = spec.resolve(REPO, CELL)
    facts = Facts(m, None, cell, PEAKS)
    by_name = {x["name"]: x for x in cell.per_layer}
    from chipbench import registry
    readers = registry.load("readers")
    for name in ("demote_ms.tier", "promote_ms.tier", "cold_land_ms.tier"):
        assert readers[by_name[name]["reader"]](by_name[name], facts) > 0
    share = readers["counter_share"](by_name["hot_hit_share.tier"], facts)
    assert 0 < share < 100
    assert share == pytest.approx(100 * m.counters["tier.hot_hit"] / (
        m.counters["tier.hot_hit"] + m.counters["tier.cold_miss"]))
    # no trace, nothing to read: the line leaves the share out
    assert tiering.migrate_roofline(by_name["migrate_roofline.tier"],
                                    facts) is None


def test_an_exit_on_a_stale_row_is_caught(tier_checkout):
    """The fault the ticket's rows exist to prevent: exits on the rows the
    names held a while ago land on whoever holds them now."""
    def stale_rows(obj):
        inner, held = obj.tap._exit, {}

        def exit_late(**kw):
            rows = kw["rows"]
            if rows.size and rows[0] != obj.pad_row:
                held.setdefault("rows", rows.copy())
                n = min(rows.size, held["rows"].size)
                kw["rows"] = rows.copy()
                kw["rows"][:n] = held["rows"][:n]
            return inner(**kw)
        obj.tap._exit = exit_late
    r = run.run_cell(CELL, 43, 1.5, False, checkout=tier_checkout,
                     require_chip=False, sabotage=stale_rows)
    assert r["correct"] is False
    assert r["checks"]["state_wrong"]["value"] > 0


def test_the_per_layer_line_fills_from_a_traced_run(tier_checkout,
                                                    monkeypatch):
    """``--trace 1`` without a chip: the trace's reduction and its module
    line are stood in for, everything else is the run's own."""
    def fake_trace(trace_dir):
        # ten batches: 300 ns of device work under each entry; 64 rows
        # evicted and 16 brought back in each
        host = [["bench.entry", 1000 * k - 100, 800, 32] for k in range(10)]
        host += [["sentinel_tpu.tier.demote", 1000 * k, 50, 64]
                 for k in range(10)]
        host += [["sentinel_tpu.tier.promote", 1000 * k + 60, 50, 16]
                 for k in range(10)]
        return trace.reduce([
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
                ["%copy.1 = s32[8]{0} copy(%p)", 1000 * k, 300]
                for k in range(10)]}]},
            {"name": "/host:CPU", "lines": [{"name": "caller",
                                             "events": host}]}])
    # the three programs took 40 us of device time between them
    monkeypatch.setattr(
        "chipbench.deployments.embed_tiered.program_seconds",
        lambda trace_dir, programs: {p: 40e-6 / 3 for p in programs})

    class _Tracer(run.Tracer):
        def _trace(self, t0):               # the clock alone, no profiler
            import time
            time.sleep(max(0.0, t0 + self.start_s + self.length_s
                           - time.monotonic()))
    monkeypatch.setattr(run, "Tracer", _Tracer)
    r = run.run_cell(CELL, 97, 1.5, True, checkout=tier_checkout,
                     require_chip=False, read_trace=fake_trace)
    assert r["correct"] is True
    cell = spec.resolve(tier_checkout, CELL)
    assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
    assert len(r["metrics"]) == 8
    assert r["metrics"]["device_ms.batch"]["value"] == pytest.approx(300e-6)
    want = 100 * tiering.migrate_min_bytes(640, 160, cell.config) \
        / 819e9 / 40e-6
    assert r["metrics"]["migrate_roofline.tier"]["value"] \
        == pytest.approx(want)
    assert 0 < want <= 100


def test_the_rooflines_bytes_against_a_hand_count():
    cfg = spec.resolve(REPO, CELL).config
    # second window: 2 buckets of 8 counters, a stamp, an RT sum, a least
    # RT; minute ring: 60 of the same; the gauge; 3 booking slots of a
    # count and a window; 32 histogram buckets
    second = 2 * (8 * 4 + 4 + 4 + 4)
    minute = 60 * (8 * 4 + 4 + 4 + 4)
    payload = second + minute + 4 + 3 * 8 + 32 * 4
    assert (second, minute, payload) == (88, 2640, 2884)
    assert tiering.row_payload_bytes(cfg) == payload
    reset = (2 + 60) * 4 + 4 + 3 * 8 + 32 * 4
    assert tiering.row_reset_bytes(cfg) == reset == 404
    # the issue's batch: 6,700 rows out, 2,200 back
    assert tiering.migrate_min_bytes(6700, 2200, cfg) \
        == 6700 * (2 * 2884 + 404) + 2200 * 2 * 2884 == 54_042_000
    assert tiering.migrate_min_bytes(0, 0, cfg) == 0
    # without a minute ring a row is its second window and the rest
    assert tiering.row_payload_bytes({**cfg, "minute_ring": False}) \
        == payload - minute


def test_device_seconds_by_program_from_a_module_line():
    class Ev:
        def __init__(self, name, ns):
            self.name, self.duration_ns = name, ns

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines
    planes = [
        Plane("/device:TPU:0", [
            Line("XLA Ops", [Ev("%fusion.1 = s32[8]", 999)]),
            Line("XLA Modules", [
                Ev("jit_tier_extract(123)", 1000),
                Ev("jit_tier_restore(77)", 2000),
                Ev("jit_tier_restore(78)", 500),
                Ev("jit_step(5)", 9000)])]),
        Plane("/host:CPU", [Line("XLA Modules", [
            Ev("jit_tier_extract(123)", 10**9)])])]
    events = tiering.module_events(planes)
    assert len(events) == 4
    got = tiering.seconds_by_program(
        events, ["jit_tier_extract", "jit_tier_invalidate",
                 "jit_tier_restore"])
    assert got == {"jit_tier_extract": pytest.approx(1e-6),
                   "jit_tier_restore": pytest.approx(2.5e-6)}
    assert tiering.program_seconds("/nonexistent", ["x"]) is None


def test_the_reference_counts_a_completion_in_its_names_bucket():
    assert [rt_bucket(rt) for rt in (0, 1, 2, 3, 4, 5, 8, 9, 1024, 1025)] \
        == [0, 0, 1, 2, 2, 3, 3, 4, 10, 11]
    assert rt_bucket(2**40) == HIST_BUCKETS - 1
    ref = TieredReference({}, {}, epoch_ms=0)
    ref.completions(["a", "b", "a"], [1, 5, 900], [False, True, False], 10)
    ref.completions(["a"], [900], [False], 20)
    assert ref.histogram("a")[0] == 1 and ref.histogram("a")[10] == 2
    assert sum(ref.histogram("a")) == 3 and sum(ref.histogram("b")) == 1
    assert ref.histogram("never") == [0] * HIST_BUCKETS
    assert list(ref.completed) == ["a", "b"]
