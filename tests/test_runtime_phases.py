"""The phases of the embedded runtime and its front end: per-batch spans
outlive the per-request ones, each phase names its parent, the lock wait
is its own phase, and the compile-cache accounting tells apart programs
that differ only in an optional batch column."""

import asyncio
import threading

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import counters as ck

pytestmark = pytest.mark.quick


@pytest.fixture
def clk():
    return ManualClock(start_ms=1_785_000_000_000)


def make(clk, **over):
    kw = dict(max_resources=64, max_origins=32, max_flow_rules=16,
              max_degrade_rules=16, max_authority_rules=16)
    kw.update(over)
    return stpu.Sentinel(config=stpu.load_config(**kw), clock=clk)


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def test_the_first_flush_outlives_more_requests_than_the_request_ring_holds(
        clk):
    """With the flight recorder on every request records two spans on the
    loop's thread. They fill a ring of their own: the first flush's
    per-batch phases are still there after it has wrapped."""
    sph = make(clk)
    sph.obs.spans.request_capacity = 64      # wraps after 32 requests
    fe = sph.frontend(batch_max=8, idle_ms=0.0, queue_max=1024)
    total = 160

    async def drive():
        first = await fe.submit("api")
        rest = await asyncio.gather(*(fe.submit("api")
                                      for _ in range(total - 1)))
        await fe.drain()
        return [first] + rest

    verdicts = asyncio.run(drive())
    assert len(verdicts) == total and all(v.trace_id for v in verdicts)
    by = _by_name(sph.obs.spans.snapshot())
    assert sph.obs.counters.get(ck.SPAN_RING_WRAP) >= 2 * total - 64
    assert len(by.get("frontend.enqueue", ())) \
        + len(by["frontend.settle"]) == 64           # the last ones only
    flushes = sph.obs.counters.get(ck.FE_FLUSH_FULL) \
        + sph.obs.counters.get(ck.FE_FLUSH_IDLE) \
        + sph.obs.counters.get(ck.FE_FLUSH_DEADLINE)
    # one of each per flush, the very first among them
    for name in ("frontend.flush", "frontend.slot_wait",
                 "frontend.dispatch", "frontend.result_wait",
                 "frontend.fanout"):
        assert len(by[name]) == flushes, name
    first = min(by["frontend.flush"], key=lambda s: s["start_ns"])
    assert first["n"] == 1                   # the lone first request
    assert sum(s["n"] for s in by["frontend.fanout"]) == total
    sph.close()


def test_the_front_ends_phases_name_their_parents_across_the_thread_hop(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=3.0)])
    fe = sph.frontend(batch_max=4, idle_ms=0.0)

    async def drive():
        out = await asyncio.gather(*(fe.submit("api") for _ in range(4)))
        await fe.drain()
        return out

    verdicts = asyncio.run(drive())
    assert sum(v.allow for v in verdicts) == 3
    spans = sph.obs.spans.snapshot()
    by = _by_name(spans)
    ids = {s["id"]: s for s in spans}

    def parent(name):
        return ids[by[name][0]["parent"]]["name"]

    assert by["frontend.flush"][0]["parent"] == 0
    assert parent("frontend.slot_wait") == "frontend.flush"
    assert parent("frontend.dispatch") == "frontend.flush"
    assert by["frontend.dispatch"][0]["thread"] \
        != by["frontend.flush"][0]["thread"]
    assert parent("pipeline.enqueue") == "frontend.dispatch"
    # (a plain record, not a phase: it is nobody's parent)
    assert parent("entry.prep") == "frontend.dispatch"
    assert parent("decide.dispatch") == "frontend.dispatch"
    assert parent("engine.lock_wait") == "decide.dispatch"
    assert parent("pipeline.settle") == "frontend.result_wait"
    assert by["frontend.fanout"][0]["parent"] == 0
    # one batch, one trace: every per-batch span of it shares the id
    batch = by["frontend.flush"][0]["trace"]
    for name in ("frontend.slot_wait", "frontend.dispatch", "entry.prep",
                 "decide.dispatch", "engine.lock_wait", "pipeline.settle",
                 "frontend.result_wait", "frontend.fanout"):
        assert {s["trace"] for s in by[name]} == {batch}, name
    assert by["decide.dispatch"][0]["note"] in (
        "scalar", "fast", "fast_occupy", "general_sorted")
    # the request's chain still reaches the batch's spans
    chain = {s["name"] for s in sph.obs.spans.chain(verdicts[0].trace_id)}
    assert {"frontend.enqueue", "frontend.flush", "entry.prep",
            "frontend.settle"} <= chain
    sph.close()


def test_the_lock_wait_is_the_time_from_asking_to_holding(clk):
    """A thread holds the engine lock for ~80 ms while an exit asks for
    it: ``engine.lock_wait`` reads that wait, inside ``exit.dispatch``."""
    import time
    sph = make(ManualClock(start_ms=1_785_000_000_000))
    sph.obs.spans._time_ns = time.perf_counter_ns    # real durations
    rows = np.asarray(sph.intern_resources(["api"]), np.int32)
    pad = np.full(1, sph.spec.alt_rows, np.int32)
    held = threading.Event()

    def hold():
        with sph._lock:
            held.set()
            time.sleep(0.08)

    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(timeout=10)
    sph.exit_batch(rows=rows, origin_rows=pad, chain_rows=pad,
                   acquire=np.ones(1, np.int32), rt_ms=np.ones(1, np.int32),
                   error=np.zeros(1, bool), is_in=np.ones(1, bool))
    t.join(timeout=10)
    assert not t.is_alive()
    by = _by_name(sph.obs.spans.snapshot())
    (wait,), (exit_,) = by["engine.lock_wait"], by["exit.dispatch"]
    assert wait["parent"] == exit_["id"] and wait["n"] == exit_["n"] == 1
    assert 40e6 < wait["dur_ns"] <= exit_["dur_ns"]
    sph.close()


def test_telemetry_land_is_a_phase_of_the_telemetry_tick(clk):
    """``n`` is the work the landing did — the top-K rows it resolved to
    names — not what the registry holds."""
    sph = make(clk)
    sph.intern_resources([f"idle-{i}" for i in range(20)])
    clk.advance_ms(600)                 # stay inside the rolling second
    sph.entry_batch(["api"] * 8 + ["web"] * 3)
    clk.advance_ms(450)
    assert sph.telemetry.poll() >= 1
    (land,) = _by_name(sph.obs.spans.snapshot())["telemetry.land"]
    assert [h["resource"] for h in sph.telemetry.hot_entries()] \
        == ["api", "web"]
    assert land["n"] == 2 < len(sph.resources) and land["parent"] == 0
    clk.advance_ms(2000)                # the window empties: nothing to name
    assert sph.telemetry.poll() >= 1
    assert _by_name(sph.obs.spans.snapshot())["telemetry.land"][-1]["n"] == 0
    sph.close()


def test_an_optional_batch_column_is_part_of_the_programs_identity(clk):
    """``count_thread`` present or ``None`` is pytree structure: jit
    compiles two programs, and ``compile_cache.miss`` counts both."""
    sph = make(clk)
    n = 8
    rows = np.asarray(sph.intern_resources(["api"] * n), np.int32)
    zeros = np.zeros(n, np.int32)
    pad = np.full(n, sph.spec.alt_rows, np.int32)
    args = (rows, zeros, pad, zeros, pad, np.ones(n, np.int32),
            np.ones(n, bool), np.zeros(n, bool))

    def misses():
        return sph.obs.counters.get(ck.CACHE_MISS)

    sph.decide_raw_nowait(*args).result()
    one = misses()
    sph.decide_raw_nowait(*args).result()
    assert misses() == one                              # the same program
    sph.decide_raw_nowait(*args, count_thread=np.ones(n, bool)).result()
    assert misses() == one + 1                          # one more column
    sph.decide_raw_nowait(*args, count_thread=np.ones(n, bool)).result()
    assert misses() == one + 1
    sph.close()


def test_program_key_tells_column_patterns_apart():
    from sentinel_tpu.core.compile_cache import program_key
    base = program_key("decide", 1, (64,), {"a": True})
    assert base == program_key("decide", 1, (64,), {"a": True}, ())
    assert program_key("decide", 1, (64,), {"a": True}, (True, False)) \
        != program_key("decide", 1, (64,), {"a": True}, (True, True))


def test_migration_is_three_phases_under_the_dispatch_that_drained(
        clk, monkeypatch):
    """``tier.demote`` (n = rows evicted) and ``tier.promote`` (n = rows
    restored) are children of the ``decide.dispatch`` / ``exit.dispatch``
    whose eviction drain ran them; ``tier.land`` (n = victims) is the
    cold tier's landing, on whichever thread lands the record — inline on
    the engine's when a promotion needs its payload at once."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    sph = make(clk, max_resources=16)        # ENTRY + 15 names
    first = [f"a{i}" for i in range(15)]
    sph.entry_batch(first)                   # the table is full
    h = sph.entry_batch_nowait(["b0", "b1", "b2"])   # evicts a0, a1, a2
    assert np.asarray(h.result().allow).all()
    by = _by_name(sph.obs.spans.snapshot())
    ids = {s["id"]: s for s in sph.obs.spans.snapshot()}
    (demote,) = by["tier.demote"]
    assert demote["n"] == 3
    assert ids[demote["parent"]]["name"] == "decide.dispatch"
    assert "tier.promote" not in by and "tier.land" not in by
    assert sph.tiering.poll() >= 1           # the ticker's thread lands it
    (land,) = _by_name(sph.obs.spans.snapshot())["tier.land"]
    assert land["n"] == 3 and land["note"] == "" and land["parent"] == 0
    assert sph.obs.counters.get(ck.TIER_LAND_INLINE) == 0
    # a1 comes back while a3's payload (it is evicted for a1) is in flight;
    # then an exit drains: a3 returns at once, its payload landed inline
    sph.entry_batch(["a1"])
    rows = np.asarray(sph.intern_resources(["a3"]), np.int32)
    pad = np.full(1, sph.spec.alt_rows, np.int32)
    sph.exit_batch(rows=rows, origin_rows=pad, chain_rows=pad,
                   acquire=np.ones(1, np.int32), rt_ms=np.ones(1, np.int32),
                   error=np.zeros(1, bool), is_in=np.ones(1, bool))
    spans = sph.obs.spans.snapshot()
    by, ids = _by_name(spans), {s["id"]: s for s in spans}
    promotes = by["tier.promote"]
    assert [p["n"] for p in promotes] == [1, 1]
    assert [ids[p["parent"]]["name"] for p in promotes] \
        == ["decide.dispatch", "exit.dispatch"]
    inline = [s for s in by["tier.land"] if s["note"] == "inline=1"]
    assert len(inline) == 1 and inline[0]["n"] == 1
    assert inline[0]["parent"] == promotes[1]["id"]
    assert sph.obs.counters.get(ck.TIER_LAND_INLINE) == 1
    assert sph.obs.counters.get(ck.TIER_PROMOTED) == 2
    assert sph.obs.counters.get(ck.TIER_DEMOTED) == 3 + 1 + 1
    # 15 + 3 names nobody knew; a1 and a3 were cold misses
    assert sph.obs.counters.get(ck.TIER_FIRST_SIGHT) == 18
    assert sph.obs.counters.get(ck.TIER_COLD_MISS) == 2
    sph.close()


def test_the_new_tier_counters_are_in_the_catalog_and_its_manifest():
    import os
    manifest = os.path.join(os.path.dirname(ck.__file__),
                            "counters_catalog.txt")
    with open(manifest) as f:
        names = [ln.strip() for ln in f
                 if ln.strip() and not ln.startswith("#")]
    assert names == list(ck.CATALOG)
    assert {ck.TIER_FIRST_SIGHT, ck.TIER_LAND_INLINE} <= set(names)
