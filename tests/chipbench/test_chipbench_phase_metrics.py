"""The per-layer metrics that read the program's phases (PR 26): the one
new reader on a small recorded trace, the entries as ``spec.resolve``
hands them to each cell, and rehearsals on the CPU of the runs that print
them.

The four metrics of ``token-1m.tcp-steady`` have their files here and are
not listed in ``BENCHMARK.json`` yet: a test that was there pins that
cell's traced line to what a trace without the program's annotations can
feed (PERF.md §7 says which edit lists them). The rehearsals below list
them in a temporary copy."""

import json
import time
from pathlib import Path

import pytest

from chipbench import run, spec, trace
from chipbench.readers.common import Facts
from chipbench.readers.phase_gaps import READERS, idle_ms_per_call

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
TOKEN, BATCH, REQ = (w["name"] for w in BENCH["workloads"])
TOKEN_METRICS = ["token_route_idle_ms", "token_put_idle_ms",
                 "token_readback_idle_ms", "token_respond_idle_ms"]
REQ_METRICS = ["slot_wait_p50_ms.req", "fanout_ms.req",
               "lock_wait_p95_ms.req", "land_ms.req"]
#: what the accepted benchmark listed before this PR, in its order
ACCEPTED = [
    "token_batch_mean", "token_device_ms", "token_gen_late_p99_ms",
    "token_step_p50_ms", "token_step_roofline", "token_within_20ms_share",
    "prep_ms.batch", "settle_ms.batch", "device_ms.batch",
    "decide_batch_roofline", "queue_wait_p50_ms.req", "batch_mean.req",
    "gen_late_p99_ms.req", "token_grant_p99_ms", "grant_p50_ms.req"]


def _metric(name):
    return json.loads((REPO / "chipbench" / "metrics" / f"{name}.json")
                      .read_text())


def _facts(planes):
    return Facts(None, trace.reduce(planes) if planes else None, None, {})


def _planes(name):
    return json.loads((DATA / name).read_text())["planes"]


def _with_token_metrics(checkout):
    """The temporary copy's ``BENCHMARK.json`` with the four token
    metrics appended, as the ``benchmark`` PR that lists them will."""
    path = checkout / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for name in TOKEN_METRICS:
        m = _metric(name)
        bench["per_layer"].append({
            **{k: m[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")},
            "workloads": [TOKEN]})
    path.write_text(json.dumps(bench))
    return checkout


class _ClockOnlyTracer(run.Tracer):
    def _trace(self, t0):                   # the clock alone, no profiler
        time.sleep(max(0.0, t0 + self.start_s + self.length_s
                       - time.monotonic()))


@pytest.fixture
def clock_only_tracer(monkeypatch):
    monkeypatch.setattr(run, "Tracer", _ClockOnlyTracer)


# -- the reader --------------------------------------------------------------

@pytest.mark.parametrize("name,want_ns", [
    # route, put and gather: the device does nothing under them, so the
    # gap is the phase; two calls each
    ("token_route_idle_ms", 800), ("token_put_idle_ms", 1000),
    # readback [3200,7000) less the device's work [3200,4800) under it
    ("token_readback_idle_ms", 2200),
    # respond: 2600 and 2400 ns, the second cut by nothing
    ("token_respond_idle_ms", 2500)])
def test_idle_ms_per_call_on_a_small_recorded_trace(name, want_ns):
    facts = _facts(_planes("trace_phases_small.json"))
    m = _metric(name)
    assert m["reader"] == "idle_ms_per_call" and READERS[m["reader"]]
    assert idle_ms_per_call(m, facts) == pytest.approx(want_ns * 1e-6)


def test_the_small_trace_shares_the_call_out_to_the_programs_names():
    r = trace.reduce(_planes("trace_phases_small.json"))
    gaps = dict(r["idle_gaps"])
    # between the phases of a call almost nothing is left to the outer
    # names. Of annotations that all began before a gap did, the
    # reduction keeps the last by NAME (their starts are cut to the
    # gap's): the 100 ns after token.gather go to server.step, not to
    # bench.token_step, which keeps the 100 ns before token.route
    assert gaps["idle_under_bench.token_step"] == pytest.approx(2 * 100e-9)
    assert gaps["idle_under_sentinel_tpu.server.step"] == \
        pytest.approx(2 * (400 + 300) * 1e-9)   # the hop there and back
    assert gaps["idle_under_sentinel_tpu.token.gather"] == \
        pytest.approx(2 * 800e-9)
    assert len(r["marks"]["sentinel_tpu.token.route"]) == 2
    assert len(gaps) <= 10


def test_no_mark_no_gap_or_no_trace_reads_nothing():
    """The parent commit's trace holds ``bench.token_step`` alone."""
    m = _metric("token_route_idle_ms")
    assert idle_ms_per_call(m, _facts(_planes("trace_small.json"))) is None
    assert idle_ms_per_call(m, _facts(None)) is None
    # the mark is there and its gap fell off the kept list: nothing too
    reduced = trace.reduce(_planes("trace_phases_small.json"), top=1)
    assert "sentinel_tpu.token.route" in reduced["marks"]
    assert idle_ms_per_call(m, Facts(None, reduced, None, {})) is None


# -- the entries -------------------------------------------------------------

def test_what_was_there_is_unchanged_and_the_new_entries_come_last():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names == ACCEPTED + REQ_METRICS
    for m in BENCH["per_layer"][len(ACCEPTED):]:
        assert m["workloads"] == [REQ] and m["moves"] == "grant_p99_ms"
        assert m["source"] == "program_span" and m["unit"] == "ms"


@pytest.mark.parametrize("cell,new", [(TOKEN, []), (BATCH, []),
                                      (REQ, REQ_METRICS)])
def test_resolve_lists_each_new_metric_in_its_cell_alone(cell, new):
    got = [m["name"] for m in spec.resolve(REPO, cell).per_layer]
    assert [n for n in got if n not in ACCEPTED] == new
    for m in spec.resolve(REPO, cell).per_layer:
        if m["name"] in new:
            assert m["reader"] in ("span_percentile_ms", "span_mean_ms")
            assert m["span"] and m["what"]


def test_the_token_metrics_resolve_once_they_are_listed(tiny_checkout):
    cell = spec.resolve(_with_token_metrics(tiny_checkout), TOKEN)
    new = [m for m in cell.per_layer if m["name"] in TOKEN_METRICS]
    assert [m["name"] for m in new] == TOKEN_METRICS
    assert {m["layer"] for m in new} == {"token engine", "ingest"}
    assert all(m["reader"] == "idle_ms_per_call"
               and m["span"].startswith("sentinel_tpu.")
               and m["source"] == "device_trace" for m in new)


# -- rehearsals on the CPU ---------------------------------------------------

def _one_device_op(trace_dir):
    return trace.reduce([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["%copy.1 = s32[8]{0} copy(%p)",
                                        1000, 300]]}]}])


def test_the_request_mix_prints_its_four_span_metrics(
        tiny_checkout, clock_only_tracer):
    r = run.run_cell(REQ, 2**31 + 26, 2.5, True, checkout=tiny_checkout,
                     require_chip=False, read_trace=_one_device_op,
                     keep=True)
    assert r["correct"] is True
    for name in REQ_METRICS:
        assert r["metrics"][name]["value"] >= 0, name
        assert r["metrics"][name]["unit"] == "ms"
    spans = r["_measured"].spans
    # the per-batch spans cover the whole window: one fan-out per flush
    # that settled inside it, not the last few
    flushes = len(spans["frontend.flush"])
    assert flushes > 20
    assert abs(len(spans["frontend.fanout"]) - flushes) <= 2
    assert abs(len(spans["frontend.slot_wait"]) - flushes) <= 2
    assert len(spans["engine.lock_wait"]) >= flushes
    assert spans["telemetry.land"]


def test_the_batch_mix_reads_prep_and_settle_as_before(
        tiny_checkout, clock_only_tracer):
    r = run.run_cell(BATCH, 2**31 + 27, 1.5, True, checkout=tiny_checkout,
                     require_chip=False, read_trace=_one_device_op,
                     keep=True)
    assert r["correct"] is True
    assert r["metrics"]["prep_ms.batch"]["value"] > 0
    assert r["metrics"]["settle_ms.batch"]["value"] > 0
    assert not set(r["metrics"]) & set(REQ_METRICS)
    spans = r["_measured"].spans
    assert len(spans["entry.prep"]) == len(spans["decide.dispatch"]) \
        >= len(spans["bench.entry"])


def test_the_token_cells_real_annotations_give_at_most_ten_gap_names(
        tiny_checkout):
    """The profiler's own trace of the tiny token cell on the CPU (host
    annotations as the program really writes them) over a stand-in for
    the device plane, which a CPU trace lacks: one short operation every
    50 µs, so every host phase has gaps under it."""
    def read(trace_dir):
        planes = trace.load_xplane(trace.find_xplane(trace_dir))
        starts = [ev[1] for p in planes for line in p["lines"]
                  for ev in line["events"]]
        lo, hi = min(starts), max(starts)
        planes.append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%copy.1 = s32[8]{0} copy(%p)", t, 5_000]
                for t in range(lo, hi, 50_000)]}]})
        return trace.reduce(planes)

    r = run.run_cell(TOKEN, 2**31 + 28, 2.0, True,
                     checkout=_with_token_metrics(tiny_checkout),
                     require_chip=False, read_trace=read)
    assert r["correct"] is True
    gaps = dict(r["breakdown"]["idle_gaps"])
    named = {"idle_under_sentinel_tpu." + p for p in (
        "token.route", "token.put", "token.dispatch", "token.readback",
        "token.gather", "server.step", "server.respond")}
    assert set(gaps) == named | {"idle_under_bench.token_step",
                                 "idle__no_annotation"}
    assert len(gaps) <= 10
    # what the benchmark's outer annotation still holds is what lies
    # between the program's phases
    inside = sum(v for k, v in gaps.items()
                 if k.startswith("idle_under_sentinel_tpu.token."))
    assert gaps["idle_under_bench.token_step"] < 0.1 * inside
    for name in TOKEN_METRICS:
        assert r["metrics"][name]["value"] > 0, name
