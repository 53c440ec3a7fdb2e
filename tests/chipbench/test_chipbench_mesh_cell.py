"""``mesh-4m.batch-scalar`` on the CPU at a tiny size: 4,096 rows split
over 4 of the suite's virtual devices, its own cut of ``mesh-4m.json`` in
a temporary checkout. A sound run agrees with the plain reference, the
control does not, a fault planted under the timed path is caught, and the
spans the cell's readers need are there. Then ``decide_mesh_roofline`` on
a stand-in trace with four device planes."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import run, spec, trace
from chipbench.readers.common import READERS as COMMON, Facts
from chipbench.readers.decide_mesh_roofline import decide_mesh_roofline
from chipbench.readers.decide_roofline import decide_min_bytes, decide_roofline

REPO = Path(__file__).resolve().parents[2]
CELL = "mesh-4m.batch-scalar"
TINY_MESH = dict(rows=4096, flow_rules=64, flow_count=40, degrade_rules=16)
PEAKS = {"hbm_bytes_per_s": 819e9}


def _metric(name):
    return json.loads((REPO / "chipbench" / "metrics" / f"{name}.json")
                      .read_text())


@pytest.fixture(scope="module")
def mesh_checkout(tmp_path_factory, make_tiny_checkout):
    checkout = make_tiny_checkout(tmp_path_factory.mktemp("checkout"))
    path = checkout / "chipbench" / "configs" / "mesh-4m.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TINY_MESH}))
    return checkout


@pytest.fixture(scope="module")
def sound(mesh_checkout):
    """Long enough to cross second boundaries, where the control's window
    differs from the configuration's."""
    seen = {}

    def keep_engine(obj):
        ring = obj.sph._state.minute.counters
        seen.update(mesh=obj.sph.mesh, spec=str(ring.sharding.spec),
                    devices=len(ring.sharding.device_set),
                    shard_rows={s.data.shape[0]
                                for s in ring.addressable_shards})
    r = run.run_cell(CELL, 2**31 + 7, 3.5, False, checkout=mesh_checkout,
                     require_chip=False, control=True, keep=True,
                     sabotage=keep_engine)
    return r, seen


def test_the_cell_resolves_to_the_mesh_builder_and_the_twins_mix():
    cell = spec.resolve(REPO, CELL)
    twin = spec.resolve(REPO, "embed-1m.batch-scalar")
    assert cell.chips == 4 and cell.config["builder"] == "embedded_engine_mesh"
    assert cell.traffic == twin.traffic
    same = ("flow_rules", "flow_count", "degrade_rules", "degrade_ratio",
            "degrade_window_s", "window_buckets", "window_ms", "minute_ring",
            "guarantees")
    assert all(cell.config[k] == twin.config[k] for k in same)
    assert cell.config["rows"] == 4 * twin.config["rows"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["prep_ms.batch", "settle_ms.batch", "device_ms.batch",
                     "place_ms.mesh", "decide_mesh_roofline"]
    # one chip's bandwidth against busy time averaged over four planes
    # would read four times too much
    assert "decide_batch_roofline" not in names


def test_a_sound_run_on_the_mesh_is_correct(sound):
    r, seen = sound
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["checks"]) == {"engine_wrong", "caller_wrong"}
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert set(r["metrics"]) == {"decisions_per_s", "setup_s"}
    assert r["compilations_in_window"] == 0
    # the builder's engine is the meshed one: rows split over four devices
    assert seen["mesh"].devices.size == 4 and seen["devices"] == 4
    assert seen["spec"] == "PartitionSpec('rows',)"
    assert seen["shard_rows"] == {TINY_MESH["rows"] // 4}


def test_the_control_on_the_mesh_is_not_correct(sound):
    r, _ = sound
    assert r["control"]["engine_wrong"]["value"] > 0
    assert r["control"]["caller_wrong"] == r["control"]["engine_wrong"]


def test_the_spans_the_readers_need_are_there(sound):
    r, _ = sound
    m = r["_measured"]
    for name in ("bench.entry", "bench.exit", "entry.prep",
                 "pipeline.settle", "batch.place"):
        assert m.spans[name], name
    # one placement per entry batch and one per exit batch
    placed = len(m.spans["batch.place"])
    calls = len(m.spans["bench.entry"]) + len(m.spans["bench.exit"])
    assert calls - 2 <= placed <= calls + 2
    facts = Facts(m, None, None, PEAKS)
    place = _metric("place_ms.mesh")
    assert COMMON[place["reader"]](place, facts) > 0
    assert decide_mesh_roofline(_metric("decide_mesh_roofline"), facts) is None


def test_an_answer_altered_under_the_timed_path_is_caught(mesh_checkout):
    def flip_one_answer(obj):
        inner, state = obj.tap._entry, {"n": 0}

        class Bent:
            def __init__(self, handle):
                self._handle = handle

            def result(self):
                v = self._handle.result()
                allow = np.array(v.allow, copy=True)
                allow[0] = not allow[0]
                return v._replace(allow=allow)

        def flipped(resources, **kw):
            state["n"] += 1
            handle = inner(resources, **kw)
            return Bent(handle) if state["n"] % 5 == 0 else handle
        obj.tap._entry = flipped
    r = run.run_cell(CELL, 41, 1.5, False, checkout=mesh_checkout,
                     require_chip=False, sabotage=flip_one_answer)
    assert r["correct"] is False
    assert r["checks"]["engine_wrong"]["value"] > 0


def test_the_one_chip_builder_does_not_get_a_mesh(mesh_checkout):
    """``embed_mesh.py`` binds the mesh for its own set-up only."""
    seen = {}
    run.run_cell("embed-1m.batch-scalar", 42, 0.5, False,
                 checkout=mesh_checkout, require_chip=False,
                 sabotage=lambda obj: seen.update(mesh=obj.sph.mesh))
    assert seen["mesh"] is None


# -- decide_mesh_roofline ------------------------------------------------------

def _stand_in(n_devices, busy_ns=600_000):
    """Three ``bench.entry`` calls of 64 events, 1 ms apart; every device
    busy for ``busy_ns`` inside each cycle."""
    planes = [{"name": f"/device:TPU:{d}", "lines": [{
        "name": "XLA Ops", "events": [
            ["%fusion.1 = s32[64]{0} fusion(%p)", 1_000_000 * k + 100_000,
             busy_ns] for k in range(3)]}]} for d in range(n_devices)]
    planes.append({"name": "/host:CPU", "lines": [{
        "name": "caller", "events": [
            ["bench.entry", 1_000_000 * k, 50_000, 64] for k in range(3)]}]})
    return planes


def test_four_planes_read_a_quarter_of_one_chips_share():
    four = Facts(None, trace.reduce(_stand_in(4)), None, PEAKS)
    one = Facts(None, trace.reduce(_stand_in(1)), None, PEAKS)
    metric = _metric("decide_mesh_roofline")
    twin = _metric("decide_batch_roofline")
    # the same busy time (mean over the planes) in both traces
    assert four.cycles("bench.entry")[1] == pytest.approx(
        one.cycles("bench.entry")[1])
    mesh = decide_mesh_roofline(metric, four)
    assert mesh == pytest.approx(decide_roofline(twin, four) / 4)
    assert mesh == pytest.approx(
        100 * 2 * decide_min_bytes(64) / (4 * 819e9) / (2 * 600e-6))
    # on one plane the two readers are the same number
    assert decide_mesh_roofline(metric, one) == pytest.approx(
        decide_roofline(twin, one))
    assert 0 < mesh < 100


def test_no_cycles_or_no_trace_reads_nothing_never_zero():
    metric = _metric("decide_mesh_roofline")
    assert decide_mesh_roofline(metric, Facts(None, None, None, PEAKS)) is None
    # one mark is no whole cycle
    planes = _stand_in(4)
    planes[-1]["lines"][0]["events"] = planes[-1]["lines"][0]["events"][:1]
    facts = Facts(None, trace.reduce(planes), None, PEAKS)
    assert decide_mesh_roofline(metric, facts) is None
    # cycles in which the devices did nothing: no share, not a share of 0
    planes = _stand_in(4)
    for p in planes[:-1]:
        p["lines"][0]["events"] = [
            ["%fusion.1 = s32[64]{0} fusion(%p)", 5_000_000, 1000]]
    facts = Facts(None, trace.reduce(planes), None, PEAKS)
    assert facts.cycles("bench.entry")[1] == 0
    assert decide_mesh_roofline(metric, facts) is None
