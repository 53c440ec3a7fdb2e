"""The reduction from a trace to idle share, per-operation time and named
idle gaps, on a small trace kept with the tests."""

import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data"


def small():
    return json.loads((DATA / "trace_small.json").read_text())["planes"]


def test_busy_time_is_the_union_of_device_operations():
    r = trace.reduce(small())
    # [1000,4800) and [11000,14000) and [15000,15500): overlap counted once
    assert r["busy_s"] == pytest.approx((3800 + 3000 + 500) * 1e-9)
    assert r["n_devices"] == 1


def test_the_window_is_the_devices_own_and_bounds_the_busy_time():
    """First device operation to the end of the last, on the trace's
    clock: busy time and idle gaps add up to it, whatever the host's clock
    said of the traced interval."""
    r = trace.reduce(small(), top=10**6)
    assert r["window_s"] == pytest.approx((15500 - 1000) * 1e-9)
    assert r["busy_s"] + sum(s for _, s in r["idle_gaps"]) == \
        pytest.approx(r["window_s"])
    assert 0 < 1 - r["busy_s"] / r["window_s"] < 1
    busy = [p for p in small() if p["name"].startswith("/device")]
    busy[0]["lines"][0]["events"] = [["%x = s32[8]{0} copy(%p)", 50, 900]]
    r = trace.reduce(busy)
    assert r["busy_s"] == r["window_s"] == pytest.approx(900e-9)


def test_device_work_is_counted_over_whole_cycles_of_an_annotation():
    planes = small()
    planes[1]["lines"][0]["events"] = [
        ["bench.token_step", 500, 4000, 3],      # the work of [1000,4800)
        ["bench.token_step", 10000, 6000, 5],    # cut off by the trace's end
    ]
    r = trace.reduce(planes)
    assert r["marks"]["bench.token_step"] == [(500, 3), (10000, 5)]
    assert r["marks"]["sentinel_tpu.decide"] == [(4900, 1)]   # no n: 1
    ns, busy_s = trace.cycles(r, "bench.token_step")
    assert ns == [3] and busy_s == pytest.approx(3800e-9)
    assert trace.cycles(r, "sentinel_tpu.decide") is None      # one start
    assert trace.cycles(r, "bench.nothing") is None
    assert trace.busy_between(r, 4500, 12000) == pytest.approx(1300e-9)
    assert trace.busy_between(r, 0, 10**6) == pytest.approx(r["busy_s"])


def test_per_operation_sums_and_names():
    ops = dict(trace.reduce(small())["device_ops"])
    assert ops["copy.130_s32_1048576_10_8"] == pytest.approx(6000e-9)
    assert ops["fusion.39_s32_1048576_10_8"] == pytest.approx(1000e-9)
    assert ops["fusion.41_s32_8"] == pytest.approx(600e-9)
    first = trace.reduce(small(), top=1)["device_ops"]
    assert [n for n, _ in first] == ["copy.130_s32_1048576_10_8"]


def test_idle_gaps_are_shared_out_among_the_annotations_over_them():
    gaps = dict(trace.reduce(small())["idle_gaps"])
    # [4800,11000): the decide annotation covers 100 ns of it, the token
    # step the last 1000 ns, nothing the rest; [14000,15000) lies wholly
    # under bench.token_step
    assert gaps == {"idle_under_bench.token_step": pytest.approx(2000e-9),
                    "idle_under_sentinel_tpu.decide": pytest.approx(100e-9),
                    "idle__no_annotation": pytest.approx(5100e-9)}
    bare = [p for p in small() if p["name"].startswith("/device")]
    gaps = dict(trace.reduce(bare)["idle_gaps"])
    assert gaps == {"idle__no_annotation": pytest.approx(7200e-9)}


def test_a_nested_annotation_takes_its_part_of_the_gap():
    planes = small()
    planes[1]["lines"][1]["events"].append(["sentinel_tpu.exit", 10500, 300])
    gaps = dict(trace.reduce(planes)["idle_gaps"])
    assert gaps["idle_under_sentinel_tpu.exit"] == pytest.approx(300e-9)
    assert gaps["idle_under_bench.token_step"] == pytest.approx(1700e-9)
    assert sum(gaps.values()) == pytest.approx(7200e-9)


def test_two_devices_average_busy_and_sum_operations():
    planes = small()
    twin = json.loads(json.dumps(planes[0]))
    twin["name"] = "/device:TPU:1"
    twin["lines"][0]["events"] = twin["lines"][0]["events"][:1]
    r = trace.reduce(planes + [twin])
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx((7300 + 3000) / 2 * 1e-9)
    assert dict(r["device_ops"])["copy.130_s32_1048576_10_8"] == \
        pytest.approx(9000e-9)


def test_a_trace_with_no_device_work_is_refused():
    host_only = [p for p in small() if not p["name"].startswith("/device")]
    with pytest.raises(ValueError):
        trace.reduce(host_only)
    empty = [{"name": "/device:TPU:0", "lines": []}]
    with pytest.raises(ValueError):
        trace.reduce(empty)


def test_the_recorded_chip_trace_reduces_to_what_it_holds():
    """A 40 ms slice of a v5e trace of ``token-1m.tcp-steady`` (PR 25),
    kept as the plain planes ``load_xplane`` made of it."""
    path = DATA / "trace_v5e_slice.json"
    planes = json.loads(path.read_text())["planes"]
    r = trace.reduce(planes)
    dev = [p for p in planes if p["name"].startswith("/device:TPU:")]
    events = [e for ln in dev[0]["lines"] for e in ln["events"]]
    assert r["n_devices"] == len(dev) >= 1
    assert 0 < r["busy_s"] <= sum(e[2] for e in events) / 1e9 + 1e-12
    assert r["busy_s"] < r["window_s"] <= 0.040
    ns, busy_s = trace.cycles(r, "bench.token_step")
    assert len(ns) == 1 and 0 < busy_s < r["busy_s"]
    total = sum(s for _, s in trace.reduce(planes, top=10**6)["device_ops"])
    assert total == pytest.approx(
        sum(e[2] for p in dev for ln in p["lines"] for e in ln["events"]) / 1e9)
    assert all(n.startswith("idle_") for n, _ in r["idle_gaps"])


@pytest.mark.parametrize("raw,want", [
    ("%copy.130 = s32[1048576,10,8]{2,1,0:T(8,128)} copy(%p)",
     "copy.130_s32_1048576_10_8"),
    ("fusion.39", "fusion.39"),
    ("%all-reduce.1 = f32[] all-reduce(%x)", "all-reduce.1_f32"),
])
def test_operation_names_are_cut_from_the_hlo_text(raw, want):
    assert trace.op_name(raw) == want
