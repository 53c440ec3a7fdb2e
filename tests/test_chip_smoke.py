"""The chip check, rehearsed on the CPU: ``chip_smoke.py``'s phases at a
tiny geometry (256 rows, batches ≤ 64) with the same host-computed verdict
checks — including the four-device mesh phase, on the virtual devices the
harness forces — and ``main()`` refusing to run anywhere but on a TPU."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.quick


def test_phases_pass_at_tiny_geometry(tmp_path, monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_LOG_DIR", str(tmp_path))
    out = chip_smoke.run(chip_smoke.TINY, seed=3)
    assert out["ok"] is True and out["claim"] is None
    assert list(out)[-1] == "claim"
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    # the driver's line carries these two keys and no other
    assert json.loads(chip_smoke.result_line(out)) == {
        "ok": True, "device": out["device"]}
    assert out["rows"] == 256 and out["tick"] == 64
    # TINY's rank key fits int32, so nothing is reduced: the fast, split-
    # eligible and occupy routes all run here
    assert out["reduced"] == []
    served = [name for name, _fn, _loop in chip_smoke.SERVED_PHASES]
    want = (["set_up"] + served + ["token_door"]
            + ["mesh_" + n for n in served[:3]] + ["mesh", "mesh_token_door"])
    assert sorted(out["phases"]) == sorted(want)
    assert all(p["check"] == "ok" for p in out["phases"].values())
    assert out["phases"]["set_up"]["minute_ring"] is True
    assert out["phases"]["scalar"]["hello"] == {
        "events": 50, "allowed": 20, "blocked": 30}
    assert out["phases"]["token_door"]["ok"] == 160
    assert out["mesh"] == {"devices": 4}


def test_host_reference_is_greedy_in_arrival_order():
    events = [("a", 1), ("a", 2), ("a", 1), ("free", 5), ("a", 1)]
    assert chip_smoke.flow_reference(events, {"a": 3}) == [
        True, True, False, True, False]
    # a blocked large acquire does not stop a later small one
    assert chip_smoke.flow_reference([("a", 2), ("a", 2), ("a", 1)],
                                     {"a": 3}) == [True, False, True]


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""               # no result line
    assert "'cpu'" in captured.err
