"""``token-1m.tcp-steady`` end to end on the CPU at a tiny size: the whole
of a run but the look for a chip, with the reference agreeing — and
disagreeing once the timed path is broken underneath."""

import io
import json
from contextlib import redirect_stderr, redirect_stdout

import pytest

from chipbench import run, trace
from chipbench.reference.token import BLOCKED, NO_RULE, OK, TokenReference

CELL = "token-1m.tcp-steady"
SECONDS = 1.5


def _run(checkout, seed, **kw):
    return run.run_cell(CELL, seed, SECONDS, False, checkout=checkout,
                        require_chip=False, **kw)


@pytest.fixture(scope="module")
def sound(tiny_checkout_module):
    return _run(tiny_checkout_module, 2**31 + 77, control=True, keep=True)


def test_a_sound_run_is_correct_and_reports_its_metrics(sound):
    # late on a loaded host is failed, not wrong
    assert sound["correct"] is True and sound["failed"] < sound["attempted"]
    assert sound["attempted"] > 800
    # p99 is a per-layer metric in this cell (PERF.md section 2)
    assert set(sound["metrics"]) == {"grant_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    assert list(sound)[-2] == "checks"           # last but the tests' extra
    assert {k: v["value"] for k, v in sound["checks"].items()} == {
        "engine_wrong": 0, "client_wrong": 0, "unanswered": 0, "xid_faults": 0}
    assert sound["compilations_in_window"] == 0


def test_the_traffic_blocks_on_the_hot_flows_and_grants_the_rest(sound):
    m = sound["_measured"]
    spans = m.spans["bench.token_step"]
    assert spans and sum(s.n for s in spans) <= sound["attempted"]
    assert all(0 <= s.start_s <= s.end_s <= SECONDS for s in spans)
    assert (m.samples["late_ms"] >= 0).all()


def test_the_control_comes_out_as_not_correct(sound):
    control = sound["control"]
    assert control["engine_wrong"]["value"] > 0
    assert control["client_wrong"]["value"] > 0


def _flip_one_answer(obj):
    inner = obj.log._inner
    state = {"calls": 0}

    def flipped(flow_ids, acquire, prioritized=None, *, now_ms):
        res = list(inner(flow_ids, acquire, prioritized, now_ms=now_ms))
        state["calls"] += 1
        if state["calls"] % 7 == 0:
            s, w, r = res[0]
            res[0] = (BLOCKED if s == OK else OK, w, r)
        return res
    obj.log._inner = flipped


def _state_left_unchanged(obj):
    engine = obj.engine
    inner = obj.log._inner

    def frozen(flow_ids, acquire, prioritized=None, *, now_ms):
        import jax
        keep = jax.tree.map(lambda x: x.copy(), engine.state)
        res = inner(flow_ids, acquire, prioritized, now_ms=now_ms)
        engine.state = keep
        return res
    obj.log._inner = frozen


def _half_the_batch_left_out(obj):
    inner = obj.log._inner

    def halved(flow_ids, acquire, prioritized=None, *, now_ms):
        n = (len(flow_ids) + 1) // 2
        res = inner(flow_ids[:n], acquire[:n], None, now_ms=now_ms)
        return list(res) + [(OK, 0, 0)] * (len(flow_ids) - n)
    obj.log._inner = halved


@pytest.mark.parametrize("fault,number", [
    (_flip_one_answer, "engine_wrong"),
    (_state_left_unchanged, "engine_wrong"),
    (_half_the_batch_left_out, "engine_wrong"),
], ids=["answer_altered", "state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tiny_checkout, fault, number):
    r = _run(tiny_checkout, 91, sabotage=fault, keep=True)
    calls = len(r["_measured"].spans["bench.token_step"])
    if r["correct"] and calls < 10:
        # inside one engine call the step counts its own earlier requests,
        # so a state left unchanged shows only from call to call
        pytest.skip(f"a starved host handed the server {calls} batches")
    assert r["correct"] is False
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]


def test_an_answer_altered_on_the_wire_is_caught_at_the_client(tiny_checkout):
    def corrupt(obj):
        from sentinel_tpu.cluster import codec
        real = codec.encode_response
        state = {"n": 0}

        def bent(resp):
            state["n"] += 1
            if resp.type == codec.MSG_TYPE_FLOW and state["n"] % 50 == 0:
                resp = codec.Response(resp.xid, resp.type,
                                      1 - resp.status if resp.status in (0, 1)
                                      else resp.status, resp.data)
            return real(resp)
        obj._undo = (codec, real)
        codec.encode_response = bent
    try:
        holder = {}

        def sabotage(obj):
            holder["obj"] = obj
            corrupt(obj)
        r = _run(tiny_checkout, 92, sabotage=sabotage)
    finally:
        codec, real = holder["obj"]._undo
        codec.encode_response = real
    assert r["correct"] is False
    assert r["checks"]["engine_wrong"]["value"] == 0
    assert r["checks"]["client_wrong"]["value"] > 0


def test_reference_slides_ten_buckets_of_100ms():
    ref = TokenReference({7: (3, 0)}, ns_qps=100)
    t = 1_700_000_000_000
    assert [s for s, _, _ in ref.step([7] * 4, [1] * 4, t)] == [OK, OK, OK, BLOCKED]
    assert ref.step([7], [1], t)[0] == (BLOCKED, 0, 0)
    assert ref.step([9], [1], t)[0] == (NO_RULE, 0, 0)
    # 999 ms later the first bucket is still inside the second...
    assert ref.step([7], [1], t + 999)[0][0] == BLOCKED
    # ...and one bucket on it has slid out: the whole count is back
    assert ref.step([7], [1], t + 1000)[0] == (OK, 0, 2)


def test_the_controls_window_tumbles_and_grants_twice_the_count():
    t = 1_700_000_000_900
    sliding = TokenReference({7: (3, 0)}, ns_qps=100)
    tumbling = TokenReference({7: (3, 0)}, ns_qps=100, buckets=1, win_ms=1000)
    for ref in (sliding, tumbling):
        ref.step([7] * 3, [1] * 3, t)
    assert sliding.step([7], [1], t + 100)[0][0] == BLOCKED
    assert tumbling.step([7], [1], t + 100)[0][0] == OK      # 4 in 100 ms


def test_namespace_limiter_refuses_past_its_qps():
    ref = TokenReference({1: (100, 0), 2: (100, 1)}, ns_qps=2)
    got = ref.step([1, 1, 1, 2], [1] * 4, 5_000)
    assert [s for s, _, _ in got] == [OK, OK, -2, OK]


def test_no_chip_means_no_result(tiny_checkout, capsys, monkeypatch):
    """On the CPU the command exits non-zero and prints no result line."""
    with pytest.raises(run.NoChip):
        run.device_row(1, require_chip=True)
    monkeypatch.setattr(run, "CHECKOUT", tiny_checkout)
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        monkeypatch.setattr(
            run, "run_cell",
            lambda *a, **k: run.device_row(1, True) and {})
        rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    assert rc != 0 and out.getvalue() == ""
    assert "not a TPU" in err.getvalue()


def test_readers_fill_the_per_layer_line_from_a_traced_run(tiny_checkout):
    """``--trace 1`` without a chip: the trace reduction is stood in for,
    everything else is the run's own."""
    def fake_trace(trace_dir):
        # ten engine calls of 4 requests, 300 ns of device work under each
        return trace.reduce([
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
                ["%copy.1 = s32[8]{0} copy(%p)", 1000 * k, 300]
                for k in range(10)]}]},
            {"name": "/host:CPU", "lines": [{"name": "server", "events": [
                ["bench.token_step", 1000 * k - 100, 800, 4]
                for k in range(10)]}]}])

    class _Tracer(run.Tracer):
        def _trace(self, t0):               # the clock alone, no profiler
            import time
            time.sleep(max(0.0, t0 + self.start_s + self.length_s
                           - time.monotonic()))
    real = run.Tracer
    run.Tracer = _Tracer
    try:
        r = run.run_cell(CELL, 93, SECONDS, True, checkout=tiny_checkout,
                         require_chip=False, read_trace=fake_trace)
    finally:
        run.Tracer = real
    assert r["correct"] is True
    cell_metrics = {m["name"] for m in json.loads(
        (tiny_checkout / "BENCHMARK.json").read_text())["per_layer"]
        if CELL in m["workloads"]}
    assert set(r["metrics"]) == cell_metrics
    assert 0 < r["metrics"]["token_step_roofline"]["value"] < 100
    # nine whole cycles, 300 ns of device work in each
    assert r["metrics"]["token_device_ms"]["value"] == pytest.approx(300e-6)
    assert r["metrics"]["token_batch_mean"]["value"] >= 1
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_sweeps_planted_stall_stops_the_generators_process(tiny_checkout):
    """``tools.sweep --stall-ms`` on a generator in a child process: it is
    stopped and continued, so requests due meanwhile go out late, and all
    of them are still answered right."""
    from chipbench import tools
    r = run.run_cell(
        CELL, 94, SECONDS, False, checkout=tiny_checkout, require_chip=False,
        keep=True, sabotage=lambda obj: tools.plant_stall(obj, 1.2, 300.0))
    assert r["correct"] is True
    assert r["_measured"].samples["late_ms"].max() >= 200.0
