"""``BENCHMARK.json`` resolves, keeps to the contract's limits, and takes a
new configuration, mix and metric as new files alone."""

import json
import re
import shutil
from pathlib import Path

import pytest

from chipbench import registry, spec

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit the driver's day
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves_to_files_that_exist(w):
    cell = spec.resolve(REPO, w["name"])
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert cell.chips in (1, 4) and len(w["why"]) <= 200
    assert cell.config["builder"] in registry.load("deployments")
    assert cell.traffic["generator"] in registry.load("generators")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    readers = registry.load("readers")
    for m in cell.per_layer:
        assert m["reader"] in readers
        assert m["moves"] in names


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_their_source(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    data = json.loads((REPO / c["file"]).read_text())
    assert data["source"] == c["source"] and len(c["source"]) <= 200
    assert data["reduced"] == c["reduced"] and data["guarantees"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize(
    "m", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entries_keep_to_the_contract(m):
    e2e = {x["name"] for x in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert set(m.get("workloads", cells)) <= cells
    if m["name"] in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert "share" not in m["name"]      # no share at a latency limit
    else:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        mover = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(mover.get("workloads", cells))
        data = json.loads(
            (REPO / "chipbench" / "metrics" / f"{m['name']}.json").read_text())
        for k in ("unit", "better", "source", "layer", "moves"):
            assert data[k] == m[k], k
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_end_to_end_metrics_are_the_issues_and_no_other():
    assert {m["name"] for m in BENCH["end_to_end"]} <= {
        "decisions_per_s", "grant_p50_ms", "grant_p99_ms", "setup_s"}
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25


def test_peaks_know_the_v5e_and_nothing_is_a_default():
    peaks = json.loads((REPO / "chipbench" / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert peaks["source"] and "default" not in peaks["devices"]


def test_a_config_a_mix_and_a_metric_are_added_as_new_files(tmp_path):
    """A later PR adds files and entries and edits no file that is there:
    a temporary copy of the benchmark gains a dummy of each kind."""
    root = tmp_path / "chipbench"
    shutil.copytree(REPO / "chipbench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    (root / "configs" / "dummy-1k.json").write_text(json.dumps({
        "name": "dummy-1k", "source": "a test", "builder": "dummy_builder",
        "chips": 1, "guarantees": ["none"], "reduced": []}))
    (root / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "name": "dummy-mix", "generator": "dummy_generator", "loop": "closed"}))
    (root / "metrics" / "dummy_ms.json").write_text(json.dumps({
        "name": "dummy_ms", "unit": "ms", "better": "lower", "layer": "dummy",
        "source": "host_clock", "moves": "grant_p50_ms",
        "reader": "dummy_reader"}))
    (root / "deployments" / "dummy.py").write_text(
        "def build(ctx):\n    return ctx\nBUILDERS = {'dummy_builder': build}\n")
    (root / "generators" / "dummy.py").write_text(
        "GENERATORS = {'dummy_generator': lambda *a: 'made'}\n")
    (root / "readers" / "dummy.py").write_text(
        "READERS = {'dummy_reader': lambda metric, facts: 1.5}\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy-1k", "source": "a test",
                             "file": "chipbench/configs/dummy-1k.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy-1k.dummy-mix",
                               "config": "dummy-1k", "traffic": "dummy-mix",
                               "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "grant_p50_ms":
            m["workloads"].append("dummy-1k.dummy-mix")
    bench["per_layer"].append({
        "name": "dummy_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "dummy", "moves": "grant_p50_ms",
        "workloads": ["dummy-1k.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve(tmp_path, "dummy-1k.dummy-mix")
    assert [m["name"] for m in cell.per_layer] == ["dummy_ms"]
    assert {m["name"] for m in cell.end_to_end} == {"grant_p50_ms", "setup_s"}
    # the scan is of the COPY: the registries find the new files by name
    import importlib
    import sys
    sys.path.insert(0, str(tmp_path))
    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "chipbench" or k.startswith("chipbench.")}
    try:
        reg = importlib.import_module("chipbench.registry")
        assert reg.find("deployments", cell.config["builder"])("x") == "x"
        assert reg.find("generators", cell.traffic["generator"])() == "made"
        assert reg.find("readers", cell.per_layer[0]["reader"])({}, None) == 1.5
        assert "token_server" in reg.load("deployments")
    finally:
        for k in [k for k in sys.modules
                  if k == "chipbench" or k.startswith("chipbench.")]:
            del sys.modules[k]
        sys.modules.update(saved)
        sys.path.remove(str(tmp_path))
    after = {p: p.read_bytes() for p in before}
    assert after == before                   # nothing that was there changed
    # an old cell does not pick the new metric up
    old = spec.resolve(tmp_path, BENCH["workloads"][0]["name"])
    assert "dummy_ms" not in {m["name"] for m in old.per_layer}
