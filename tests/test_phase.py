"""``RuntimeObs.phase``: one call site writes an interval to the profiler's
trace (an annotation ``sentinel_tpu.<name>``) and to the span recorder (a
span ``name`` with ``id``/``parent``).

One short CPU profiler trace is recorded for the whole file and read back
with the benchmark's own ``chipbench.trace.load_xplane``: it settles, by
test and not by belief, that two tasks interleaving two phases on one
asyncio thread both come back whole, so a phase may span an ``await``."""

import asyncio
import threading

import pytest

from sentinel_tpu.obs import OBS_DISABLE_ENV, RuntimeObs, _NULL_CTX
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.obs.spans import OPEN_PHASE, SpanRecorder

PREFIX = "sentinel_tpu."


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(annotations by name -> [(start, dur, n...)], spans by name, ids of
    the threads) of one traced scenario: ``outer`` > ``mid`` > ``leaf`` with
    the leaf in a worker thread, a root phase on a plain thread, and two
    tasks whose phases ``task.a`` and ``task.b`` interleave on the loop's
    thread (a opens, b opens, a closes, b closes)."""
    import jax
    from chipbench.trace import find_xplane, load_xplane

    obs = RuntimeObs()
    seen = {}

    def leaf():
        seen["worker"] = threading.get_ident()
        with obs.phase("leaf", n=3):
            pass

    def alone():
        with obs.phase("alone"):
            pass

    async def task(name, before, inside):
        await asyncio.sleep(before)
        with obs.phase(name, n=7):
            await asyncio.sleep(inside)

    async def scenario():
        seen["loop"] = threading.get_ident()
        with obs.phase("outer", n=2):
            with obs.phase("mid"):
                await asyncio.to_thread(leaf)
        t = threading.Thread(target=alone)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        await asyncio.gather(task("task.a", 0.0, 0.06),
                             task("task.b", 0.02, 0.08))

    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        asyncio.run(scenario())
    finally:
        jax.profiler.stop_trace()
    notes = {}
    for plane in load_xplane(find_xplane(str(out))):
        for line in plane["lines"]:
            for name, *rest in line["events"]:
                notes.setdefault(name, []).append(tuple(rest))
    spans = _by_name(obs.spans.snapshot())
    obs.close()
    return notes, spans, seen


def test_one_call_writes_the_span_and_the_annotation(traced):
    notes, spans, _ = traced
    for name in ("outer", "mid", "leaf", "alone", "task.a", "task.b"):
        (span,), (note,) = spans[name], notes[PREFIX + name]
        # the same enter and exit: the annotation lies inside the span,
        # a few microseconds shorter at most
        assert 0 <= span["dur_ns"] - note[1] < 2_000_000


def test_n_reaches_the_annotation_and_the_span(traced):
    notes, spans, _ = traced
    assert notes[PREFIX + "outer"][0][2] == 2 == spans["outer"][0]["n"]
    assert notes[PREFIX + "leaf"][0][2] == 3 == spans["leaf"][0]["n"]
    assert len(notes[PREFIX + "mid"][0]) == 2        # no n given: no stat


def test_parents_nest_across_two_levels_and_two_threads(traced):
    _, spans, seen = traced
    outer, mid, leaf = (spans[k][0] for k in ("outer", "mid", "leaf"))
    assert outer["parent"] == 0 and outer["id"] > 0
    assert mid["parent"] == outer["id"]
    assert leaf["parent"] == mid["id"]          # asyncio.to_thread carried it
    assert leaf["thread"] == seen["worker"] != seen["loop"] == mid["thread"]
    assert outer["trace"] == mid["trace"] == leaf["trace"] > 0
    assert len({s[0]["id"] for s in spans.values()}) == len(spans)
    # a thread of its own starts with no phase open: a root, a fresh trace
    alone = spans["alone"][0]
    assert alone["parent"] == 0 and alone["trace"] != outer["trace"]
    # self time: the parent's duration less what its children cover
    assert outer["dur_ns"] - mid["dur_ns"] >= 0
    assert mid["start_ns"] >= outer["start_ns"]
    assert mid["end_ns"] <= outer["end_ns"]


def test_two_tasks_interleaving_on_one_thread_both_come_back_whole(traced):
    """a: [0, 60) ms, b: [20, 100) ms on the loop's thread, neither inside
    the other. Both intervals are in the xplane with their full length, so
    a phase may be held open across an ``await``."""
    notes, spans, _ = traced
    (a0, a_dur, a_n), (b0, b_dur, b_n) = (notes[PREFIX + "task.a"][0],
                                          notes[PREFIX + "task.b"][0])
    assert a0 < b0 < a0 + a_dur < b0 + b_dur         # interleaved, not nested
    assert 55e6 < a_dur < 500e6 and 75e6 < b_dur < 500e6
    assert a_n == b_n == 7
    sa, sb = spans["task.a"][0], spans["task.b"][0]
    assert abs(sa["dur_ns"] - a_dur) < 2e6 and abs(sb["dur_ns"] - b_dur) < 2e6
    assert sa["parent"] == sb["parent"] == 0         # a task's own context


def test_a_disabled_bundle_gives_the_shared_no_op(monkeypatch):
    monkeypatch.setenv(OBS_DISABLE_ENV, "1")
    obs = RuntimeObs()
    assert obs.enabled is False
    assert obs.phase("a", n=5) is obs.phase("b") is obs.annotate("c") \
        is _NULL_CTX                                  # nothing allocated
    with obs.phase("a", n=5) as ph:
        ph.note = "x"
        assert OPEN_PHASE.get() is None
    obs.phase("b").start().stop()
    assert obs.spans.snapshot() == []


def test_a_phase_that_raises_is_recorded_and_closed():
    obs = RuntimeObs()
    with pytest.raises(KeyError):
        with obs.phase("outer"):
            with obs.phase("broken"):
                raise KeyError("x")
    assert OPEN_PHASE.get() is None
    spans = _by_name(obs.spans.snapshot())
    assert spans["broken"][0]["parent"] == spans["outer"][0]["id"]


def test_note_and_n_may_be_set_while_open_and_start_stop_is_the_with():
    obs = RuntimeObs()
    with obs.phase("route", trace=41) as ph:
        ph.note, ph.n = "scalar", 9
    wait = obs.phase("wait", trace=41).start()
    assert OPEN_PHASE.get() is wait
    wait.stop()
    assert OPEN_PHASE.get() is None
    spans = _by_name(obs.spans.snapshot())
    assert (spans["route"][0]["note"], spans["route"][0]["n"]) == ("scalar", 9)
    assert spans["route"][0]["trace"] == spans["wait"][0]["trace"] == 41


def test_a_plain_record_names_the_open_phase_of_its_own_recorder():
    obs, other = RuntimeObs(), RuntimeObs()
    with obs.phase("outer") as outer:
        obs.spans.record(outer.trace, "inside", 1, 2)
        other.spans.record(5, "elsewhere", 1, 2)
        with other.phase("foreign") as foreign:
            pass
    obs.spans.record(7, "after", 1, 2)
    spans = _by_name(obs.spans.snapshot())
    assert spans["inside"][0]["parent"] == outer.id
    assert spans["after"][0]["parent"] == 0
    assert other.spans.snapshot(trace_id=5)[0]["parent"] == 0
    assert foreign.parent == 0              # another recorder's phase


def test_request_spans_do_not_evict_batch_spans():
    """Per-request records go to a ring of their own: a thread that
    records thousands of them keeps its per-batch spans, and every
    overwrite still ticks ``obs.span_ring_wrap``."""
    obs = RuntimeObs()
    cap = obs.spans.request_capacity
    with obs.phase("first.flush", n=1):
        pass
    for i in range(cap + 10):
        obs.spans.record(1000 + i, "frontend.enqueue", i, i + 1,
                         request=True)
    names = _by_name(obs.spans.snapshot())
    assert len(names["first.flush"]) == 1
    assert len(names["frontend.enqueue"]) == cap
    assert names["frontend.enqueue"][0]["parent"] == 0
    assert obs.counters.get(ck.SPAN_RING_WRAP) == 10
    assert obs.spans.last_trace_id() == 1000 + cap + 9
    # the read side merges both rings: a request's chain has its span
    assert [s["name"] for s in obs.spans.chain(1000 + cap)] == [
        "frontend.enqueue"]


def test_the_batch_ring_holds_a_whole_window():
    assert SpanRecorder().capacity == 8192
    assert SpanRecorder(capacity=64).request_capacity == 64
