"""Fixtures for the benchmark's own tests: a temporary checkout holding
``BENCHMARK.json`` and the benchmark's DATA directories, cut to a size the
CPU can hold (the code is the repo's own; only data is copied)."""

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "token-1m": dict(flows=4096, hot_flows=64, hot_count=5, cold_count=1000),
    "embed-1m": dict(rows=4096, flow_rules=64, flow_count=40,
                     degrade_rules=16),
}
TINY_TRAFFIC = {
    "tcp-steady": dict(rate_per_s=800, warm_seconds=0.3, warm_max_batch=256,
                       grace_s=5),
    "batch-scalar": dict(batch=32, events=32 * 512, warm_seconds=0.5,
                         warm_exit_sizes=[8, 16, 32], error_rate=0.0),
    "req-steady": dict(rate_per_s=500, warm_seconds=0.5,
                       warm_entry_sizes=[8, 16, 32, 64],
                       warm_exit_sizes=[8, 16, 32, 64, 128], grace_s=5,
                       error_rate=0.0),
}
# At this size a flow rule and an open breaker cannot be allowed to meet
# on one name: events of a name whose breaker is open and whose flow
# window is nearly full read FLOW in the engine from the event at which
# the window plus the batch's own earlier events reach the count, and
# DEGRADE in sequence (a fault of the program, PERF.md open questions;
# the cells' own sizes cannot reach it, a tiny one does in one run of
# ten or so, by the host's speed). So the tiny embedded engine comes in
# two kinds: "flow" (above: the count binds, nothing errs, no breaker
# opens) and "breakers" (the count never binds, 30 % of exits err).
BREAKERS = {"embed-1m": dict(flow_count=1_000_000),
            "batch-scalar": dict(error_rate=0.3),
            "req-steady": dict(error_rate=0.3)}


def make_checkout(tmp: Path, kind: str = "flow") -> Path:
    (tmp / "chipbench").mkdir()
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(REPO / "chipbench" / d, tmp / "chipbench" / d)
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for folder, table in (("configs", TINY), ("traffic", TINY_TRAFFIC)):
        for name, cut in table.items():
            path = tmp / "chipbench" / folder / f"{name}.json"
            data = json.loads(path.read_text())
            data.update(cut)
            if kind == "breakers":
                data.update(BREAKERS.get(name, {}))
            path.write_text(json.dumps(data))
    return tmp


@pytest.fixture
def tiny_checkout(tmp_path):
    return make_checkout(tmp_path)


@pytest.fixture(scope="module")
def tiny_checkout_module(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="session")
def make_tiny_checkout():
    """``make_checkout(tmp, kind)`` for a fixture that needs both kinds."""
    return make_checkout
