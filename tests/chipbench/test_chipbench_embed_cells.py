"""``embed-1m.batch-scalar`` and ``embed-1m.req-steady`` end to end on the
CPU at a tiny size, errors turned up so that breakers trip: the reference
agrees with a sound run, the control does not, and each fault planted
under the timed path comes out as not correct."""

import numpy as np
import pytest

from chipbench import run
from chipbench.reference.engine import (
    CLOSED, DEGRADE, FLOW, HALF_OPEN, OPEN, PASS, BreakerRule,
    EngineReference)

BATCH, REQ = "embed-1m.batch-scalar", "embed-1m.req-steady"
SECONDS, LONG = 1.5, 3.5


def _run(cell, checkout, seed, **kw):
    return run.run_cell(cell, seed, SECONDS, False, checkout=checkout,
                        require_chip=False, **kw)


@pytest.fixture(scope="module", params=[
    (BATCH, "flow"), (BATCH, "breakers"), (REQ, "flow"), (REQ, "breakers")],
    ids=lambda p: "-".join(p))
def sound(request, tmp_path_factory, make_tiny_checkout):
    """Long enough to cross second boundaries, where the control's window
    differs; once with the flow count binding, once with breakers tripping
    (``conftest.BREAKERS`` says why not both at once)."""
    cell, kind = request.param
    checkout = make_tiny_checkout(tmp_path_factory.mktemp("checkout"), kind)
    return cell, kind, run.run_cell(
        cell, 2**31 + 5, LONG, False, checkout=checkout,
        require_chip=False, control=True, keep=True)


def test_a_sound_run_is_correct(sound):
    cell, kind, r = sound
    # a request that a loaded host answers past the client's timeout is
    # failed, not wrong: only the closed loop can promise there is none
    assert r["correct"] is True and (cell == REQ or r["failed"] == 0)
    assert r["failed"] < r["attempted"]
    assert all(c["value"] == 0 for c in r["checks"].values())
    want = ({"decisions_per_s", "setup_s"} if cell == BATCH
            else {"grant_p99_ms", "setup_s"})   # its p50 is per layer
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["checks"]) == (
        {"engine_wrong", "caller_wrong"} if cell == BATCH
        else {"engine_wrong", "client_wrong", "unanswered"})


def test_the_control_comes_out_as_not_correct(sound):
    """The control coarsens the flow window: it shows where the count
    binds, and agrees where it never does."""
    cell, kind, r = sound
    wrong = r["control"]["engine_wrong"]["value"]
    assert wrong > 0 if kind == "flow" else wrong == 0
    if cell == BATCH:       # what the control decides is what its caller gets
        assert r["control"]["caller_wrong"] == r["control"]["engine_wrong"]


def test_spans_and_samples_are_there_for_the_readers(sound):
    cell, _, r = sound
    m = r["_measured"]
    assert m.spans["bench.entry"] and m.spans["bench.exit"]
    assert m.spans["entry.prep"] and m.spans["pipeline.settle"]
    if cell == BATCH:
        # whole batches, from a verdict in hand to a verdict in hand
        assert m.counters["batches"] * 32 == r["attempted"]
        assert LONG <= m.window_s < LONG + 2.0
        assert r["metrics"]["decisions_per_s"]["value"] == \
            pytest.approx(r["attempted"] / m.window_s)
        entries = [s for s in m.spans["bench.entry"]]
        assert m.counters["batches"] - 2 <= len(entries) \
            <= m.counters["batches"] + 1
    else:
        assert len(m.samples["queue_wait_ms"]) > 0
        assert (m.samples["queue_wait_ms"] >= 0).all()
        assert (m.samples["late_ms"] >= 0).all()


class _Bent:
    """A verdict handle whose result is altered where it is produced."""

    def __init__(self, inner, bend):
        self._inner, self._bend = inner, bend

    def result(self):
        return self._bend(self._inner.result())


def _flip_one_answer(obj):
    inner, state = obj.tap._entry, {"n": 0}

    def flipped(resources, **kw):
        state["n"] += 1
        handle = inner(resources, **kw)
        if state["n"] % 5:
            return handle

        def bend(v):
            allow = np.array(v.allow, copy=True)
            allow[0] = not allow[0]
            return v._replace(allow=allow)
        return _Bent(handle, bend)
    obj.tap._entry = flipped


def _state_left_unchanged(obj):
    import jax
    inner, sph = obj.tap._entry, obj.sph

    def frozen(resources, **kw):
        keep = jax.tree.map(lambda x: x.copy(), sph._state)
        handle = inner(resources, **kw)
        handle.result()
        sph._state = keep
        return handle
    obj.tap._entry = frozen


def _half_the_batch_left_out(obj):
    inner = obj.tap._entry

    def halved(resources, **kw):
        n = len(resources)
        if n < 2:
            return inner(resources, **kw)
        kw = {k: (v[: n // 2] if hasattr(v, "__len__") else v)
              for k, v in kw.items()}
        handle = inner(resources[: n // 2], **kw)

        def bend(v):
            pad = n - n // 2
            return v._replace(
                allow=np.concatenate([np.asarray(v.allow), np.ones(pad, bool)]),
                reason=np.concatenate([np.asarray(v.reason),
                                       np.zeros(pad, np.int8)]),
                wait_ms=np.concatenate([np.asarray(v.wait_ms),
                                        np.zeros(pad, np.int32)]))
        return _Bent(handle, bend)
    obj.tap._entry = halved


@pytest.mark.parametrize("cell", [BATCH, REQ])
@pytest.mark.parametrize("fault", [
    _flip_one_answer, _state_left_unchanged, _half_the_batch_left_out],
    ids=["answer_altered", "state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tiny_checkout, cell, fault):
    r = _run(cell, tiny_checkout, 31, sabotage=fault)
    assert r["correct"] is False
    assert r["checks"]["engine_wrong"]["value"] > 0


def test_tickets_handed_to_the_wrong_caller_are_caught_at_the_caller(
        tiny_checkout, monkeypatch):
    """The pipeline settles out of order: every fifth ticket answers with
    the batch before it. The engine's own handles stay right, so only the
    comparison of what the caller received can see it."""
    import sentinel_tpu as stpu
    submit = stpu.DispatchPipeline.submit

    class _Swapped:
        def __init__(self, other):
            self.result = other.result

    def swapping(self, *a, **kw):
        ticket = submit(self, *a, **kw)
        last, self._last = getattr(self, "_last", None), ticket
        self._n = getattr(self, "_n", 0) + 1
        return _Swapped(last) if last is not None and self._n % 5 == 0 \
            else ticket
    monkeypatch.setattr(stpu.DispatchPipeline, "submit", swapping)
    r = _run(BATCH, tiny_checkout, 34)
    assert r["correct"] is False
    assert r["checks"]["engine_wrong"]["value"] == 0
    assert r["checks"]["caller_wrong"]["value"] > 0


def test_a_verdict_altered_in_the_fan_out_is_caught_at_the_caller(
        tiny_checkout):
    def sabotage(obj):
        make = obj.sph.frontend

        def frontend(**kw):
            fe = make(**kw)
            submit, state = fe.submit, {"n": 0}

            async def bent(resource, **k):
                v = await submit(resource, **k)
                state["n"] += 1
                if state["n"] % 40 == 0:
                    v = v._replace(allow=not v.allow,
                                   reason=0 if not v.allow else FLOW)
                return v
            fe.submit = bent
            return fe
        obj.sph.frontend = frontend
    r = _run(REQ, tiny_checkout, 32, sabotage=sabotage)
    assert r["correct"] is False
    assert r["checks"]["engine_wrong"]["value"] == 0
    assert r["checks"]["client_wrong"]["value"] > 0


# -- the plain reference on its own -------------------------------------

def _ref(**kw):
    return EngineReference({"r": 3}, {"r": BreakerRule(0.5, 10_000)},
                           epoch_ms=1_000_000, **kw)


def test_flow_slides_two_buckets_of_500ms():
    ref, t = _ref(), 1_000_000
    assert ref.entries(["r"] * 4 + ["free"], t) == [PASS] * 3 + [FLOW, PASS]
    assert ref.entries(["r"], t + 999) == [FLOW]
    assert ref.entries(["r"], t + 1000) == [PASS]       # first bucket gone
    tumbling = _ref(buckets=1, win_ms=1000)
    tumbling.entries(["r"] * 3, t + 900)
    ref2 = _ref()
    ref2.entries(["r"] * 3, t + 900)
    assert ref2.entries(["r"], t + 1000) == [FLOW]
    assert tumbling.entries(["r"], t + 1000) == [PASS]   # the control's


def test_breaker_trips_after_the_call_opens_probes_and_closes():
    ref, t = _ref(), 1_000_000
    br = ref.breakers["r"]
    ref.exits(["r"] * 4, [True] * 4, t + 10)             # under 5 requests
    assert br.state == CLOSED
    ref.exits(["r"] * 2, [False, False], t + 20)          # 4 of 6 > 0.5
    assert br.state == OPEN and ref.trips == 1
    assert ref.entries(["r", "free"], t + 5000) == [DEGRADE, PASS]
    # past the time window: one probe, the rest still refused
    assert ref.entries(["r", "r"], t + 10_020) == [PASS, DEGRADE]
    assert br.state == HALF_OPEN
    ref.exits(["r"], [True], t + 10_030)                  # the probe failed
    assert br.state == OPEN
    assert ref.entries(["r"], t + 20_030) == [PASS]
    ref.exits(["r"], [False], t + 20_040)                 # the probe passed
    assert br.state == CLOSED and br.total == 1


def test_breaker_window_tumbles_from_the_engines_start():
    ref, t = _ref(), 1_000_000
    ref.exits(["r"] * 3, [True] * 3, t + 990)
    ref.exits(["r"] * 3, [True] * 3, t + 1010)            # a new window
    assert ref.breakers["r"].state == CLOSED
    ref.exits(["r"] * 2, [True] * 2, t + 1020)
    assert ref.breakers["r"].state == OPEN


def test_a_flow_block_comes_before_the_breaker_and_takes_no_probe():
    ref, t = _ref(), 1_000_000
    ref.exits(["r"] * 5, [True] * 5, t + 1)
    assert ref.entries(["r"], t + 2) == [DEGRADE]
    ref.window.add(ref._key("r"), t + 10_000, 3)          # the flow is full
    assert ref.entries(["r"], t + 10_010) == [FLOW]
    assert ref.breakers["r"].state == OPEN


def test_the_sweeps_planted_stall_holds_the_generator_back(tiny_checkout):
    """``tools.sweep --stall-ms``: the generator sends nothing for that
    long and then its backlog at once; every request is still answered
    and still right. The timed generators themselves have no such option."""
    from chipbench import tools
    r = run.run_cell(
        REQ, 35, SECONDS, False, checkout=tiny_checkout, require_chip=False,
        keep=True, sabotage=lambda obj: tools.plant_stall(obj, 1.2, 300.0))
    assert r["correct"] is True
    assert r["_measured"].samples["late_ms"].max() >= 250.0
