"""Depth-k dispatch pipelining (sentinel_tpu/serving.py): bit-parity
pins against the sequential serving loop, strict in-order settle under
out-of-order ``result()`` calls, the leaked-handle GC guard, and
host-staging reuse parity.

All quick-tier, CPU: the pipeline changes HOST scheduling only — the
device-visible dispatch order is pinned unchanged, so every verdict and
every engine-state leaf must be bit-equal to the synchronous loop."""

import gc

import jax
import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import counters as obs_keys

T0 = 1_785_000_000_000


@pytest.fixture
def clk():
    return ManualClock(start_ms=T0)


def make(clk, **over):
    kw = dict(max_resources=64, max_flow_rules=16, max_degrade_rules=16,
              max_authority_rules=16, minute_enabled=True)
    kw.update(over)
    return stpu.Sentinel(config=stpu.load_config(**kw), clock=clk)


def _assert_state_equal(s1, s2):
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s2)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "state leaf diverged"


RULES = [stpu.FlowRule(resource="r0", count=30.0),
         stpu.FlowRule(resource="r1", count=5.0),
         stpu.FlowRule(resource="r2", count=12.0)]


def _traffic(rng, step):
    names = [f"r{int(i)}" for i in rng.integers(0, 4, 24)]
    prio = (rng.random(24) < 0.3) if step % 2 else np.zeros(24, np.bool_)
    return names, prio


# ---------------------------------------------------------------------------
# bit-parity: pipelined(depth=k) == sequential
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipelined_matches_sequential(clk, depth):
    """Interacting steps (QPS rules deplete across batches, prioritized
    events book occupy slots): every verdict and the full engine state
    must be bit-equal to the synchronous loop, at any depth."""
    clk2 = ManualClock(start_ms=T0)
    seq_s = make(clk)
    pipe_s = make(clk2)
    seq_s.load_flow_rules(RULES)
    pipe_s.load_flow_rules(RULES)
    rng = np.random.default_rng(7)
    traffic = [_traffic(rng, step) for step in range(8)]

    seq_out = []
    for names, prio in traffic:
        seq_out.append(seq_s.entry_batch_nowait(
            names, prioritized=prio).result())
        clk.advance_ms(120)

    pipe = stpu.DispatchPipeline(pipe_s, depth=depth)
    tickets = []
    for names, prio in traffic:
        tickets.append(pipe.submit(names, prioritized=prio))
        clk2.advance_ms(120)
    pipe.flush()
    pipe_out = [t.result() for t in tickets]

    for step, (v1, v2) in enumerate(zip(seq_out, pipe_out)):
        assert np.array_equal(v1.allow, v2.allow), f"allow @ step {step}"
        assert np.array_equal(v1.reason, v2.reason), f"reason @ step {step}"
        assert np.array_equal(v1.wait_ms, v2.wait_ms), \
            f"wait_ms @ step {step}"
    _assert_state_equal(seq_s._state, pipe_s._state)
    for r in ("r0", "r1", "r2"):
        assert seq_s.node_totals(r) == pipe_s.node_totals(r)


def test_pipelined_origin_batches_match(clk):
    """Origin-bearing traffic (alt-row scatters live) through the
    pipeline: same parity bar."""
    clk2 = ManualClock(start_ms=T0)
    seq_s = make(clk)
    pipe_s = make(clk2)
    rules = [stpu.FlowRule(resource="r1", count=8.0, limit_app="app-a")]
    seq_s.load_flow_rules(rules)
    pipe_s.load_flow_rules(rules)
    rng = np.random.default_rng(8)
    traffic = []
    for _ in range(6):
        names = [f"r{int(i)}" for i in rng.integers(0, 3, 16)]
        origins = [("app-a" if rng.random() < 0.5 else "app-b")
                   for _ in names]
        traffic.append((names, origins))

    seq_out = [seq_s.entry_batch_nowait(n, origins=o).result()
               for n, o in traffic]
    with stpu.DispatchPipeline(pipe_s, depth=2) as pipe:
        tickets = [pipe.submit(n, origins=o) for n, o in traffic]
        pipe_out = [t.result() for t in tickets]

    for v1, v2 in zip(seq_out, pipe_out):
        assert np.array_equal(v1.allow, v2.allow)
        assert np.array_equal(v1.wait_ms, v2.wait_ms)
    _assert_state_equal(seq_s._state, pipe_s._state)


# ---------------------------------------------------------------------------
# in-order settle + pipeline counters
# ---------------------------------------------------------------------------

def test_in_order_settle_under_out_of_order_results(clk):
    """Calling the LAST ticket's result() first must settle every older
    handle first — deferred bookkeeping lands in dispatch order."""
    sph = make(clk)
    pipe = stpu.DispatchPipeline(sph, depth=4)
    tickets = [pipe.submit(["a", "b"]) for _ in range(3)]
    order = []
    with pipe._lock:
        for seq, h, _tr in pipe._inflight:
            fn = h._cell.fn

            def spied(f=fn, s=seq):
                order.append(s)
                return f()
            h._cell.fn = spied
    v_last = tickets[2].result()
    assert order == [0, 1, 2]
    assert np.array_equal(tickets[0].result().allow, v_last.allow)
    # ticket results are memoized
    assert tickets[2].result() is v_last


def test_pipeline_counters_and_stall(clk):
    sph = make(clk)
    pipe = stpu.DispatchPipeline(sph, depth=2)
    for _ in range(5):
        pipe.submit(["a"])
    pipe.flush()
    c = sph.obs.counters
    # depth sum: 1 + 2 + 2 + 2 + 2; stalls on submits 3..5
    assert c.get(obs_keys.PIPE_DEPTH) == 9
    assert c.get(obs_keys.PIPE_STALL) == 3
    assert pipe.in_flight == 0


def test_pipeline_depth_env_knob(clk, monkeypatch):
    monkeypatch.setenv(stpu.serving.PIPELINE_DEPTH_ENV, "5")
    assert stpu.pipeline_depth() == 5
    sph = make(clk)
    assert stpu.DispatchPipeline(sph).depth == 5
    monkeypatch.setenv(stpu.serving.PIPELINE_DEPTH_ENV, "not-a-number")
    assert stpu.pipeline_depth() == 2


# ---------------------------------------------------------------------------
# leaked-handle guard
# ---------------------------------------------------------------------------

def test_leaked_handle_settled_and_counted(clk):
    """Dropping a handle without result() must still run its deferred
    bookkeeping (the block log write below) and bump the leak counter."""
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="q", count=1.0)])
    h = sph.entry_batch_nowait(["q", "q", "q"])
    del h
    gc.collect()
    assert sph.obs.counters.get(obs_keys.PIPE_LEAKED) == 1
    # a consumed handle must NOT count as leaked
    h2 = sph.entry_batch_nowait(["q"])
    h2.result()
    del h2
    gc.collect()
    assert sph.obs.counters.get(obs_keys.PIPE_LEAKED) == 1


def test_leaked_nested_handle_counts_once(clk):
    """entry_batch_nowait wraps decide_raw_nowait's handle — leaking the
    outer one settles the whole chain exactly once."""
    sph = make(clk)
    h = sph.entry_batch_nowait(["a", "b"])
    del h
    gc.collect()
    assert sph.obs.counters.get(obs_keys.PIPE_LEAKED) == 1


# ---------------------------------------------------------------------------
# host staging
# ---------------------------------------------------------------------------

def test_staging_reuse_parity(clk):
    """Serving-sized batches reuse preallocated staging slots; verdicts
    must match a staging-disabled twin re-dispatching fresh arrays."""
    import sentinel_tpu.runtime as rt
    clk2 = ManualClock(start_ms=T0)
    on_s = make(clk)
    assert on_s._staging_on     # default on
    off_s = make(clk2)
    off_s._staging_on = False
    on_s.load_flow_rules([stpu.FlowRule(resource="r0", count=900.0)])
    off_s.load_flow_rules([stpu.FlowRule(resource="r0", count=900.0)])
    rng = np.random.default_rng(11)
    b = max(600, rt.Sentinel._STAGING_MIN_B + 88)
    for step in range(4):
        names = [f"r{int(i)}" for i in rng.integers(0, 3, b)]
        v1 = on_s.entry_batch_nowait(names).result()
        v2 = off_s.entry_batch_nowait(names).result()
        assert np.array_equal(v1.allow, v2.allow), f"step {step}"
        assert np.array_equal(v1.wait_ms, v2.wait_ms)
        clk.advance_ms(90)
        clk2.advance_ms(90)
    _assert_state_equal(on_s._state, off_s._state)
    assert on_s._staging, "staging ring was never engaged"
    assert not off_s._staging


def test_staging_ring_settlement_freelist(clk):
    """Slot reuse is settlement-tied (ROADMAP issue 5): a held slot is
    never handed out again, acquire grows the pool past its depth, and
    released slots are recycled."""
    from sentinel_tpu.runtime import _StagingRing
    ring = _StagingRing(1024, 4)
    held = [ring.acquire() for _ in range(4)]
    assert len({id(s["rows"]) for s in held}) == 4
    extra = ring.acquire()     # pool exhausted: fresh slot, never reuse
    assert ring.grown == 1
    assert id(extra["rows"]) not in {id(s["rows"]) for s in held}
    ring.release(held[0])
    assert id(ring.acquire()["rows"]) == id(held[0]["rows"])


def test_staging_inflight_slots_never_rewritten(clk, monkeypatch):
    """ROADMAP issue 5 regression: with MORE unsettled dispatches in
    flight than the ring has slots, the old round-robin ring handed an
    in-flight slot out again (silently corrupting that dispatch's
    operands on backends with deferred host→device copies). The
    settlement-tied ring must instead grow — no two in-flight batches
    may alias a staging buffer — and recycle every slot after settle.
    Verdicts must stay bit-identical to a staging-off twin."""
    import sentinel_tpu.runtime as rt
    monkeypatch.setattr(rt.Sentinel, "_STAGING_MIN_B", 8)
    clk2 = ManualClock(start_ms=T0)
    on_s = make(clk)
    off_s = make(clk2)
    off_s._staging_on = False
    for s in (on_s, off_s):
        s.load_flow_rules(RULES)
    depth = on_s._staging_depth
    rng_a, rng_b = (np.random.default_rng(1602) for _ in range(2))
    handles, expected = [], []
    for step in range(depth + 3):   # strictly deeper than the free list
        names = [f"r{int(i)}" for i in rng_a.integers(0, 4, 12)]
        handles.append(on_s.entry_batch_nowait(names))
        expected.append(off_s.entry_batch_nowait(
            [f"r{int(i)}" for i in rng_b.integers(0, 4, 12)]).result())
    (ring,) = on_s._staging.values()
    assert ring.grown >= 3          # grew instead of reusing in-flight
    assert not ring._free           # every slot owned by a live handle
    for h, want in zip(handles, expected):
        got = h.result()
        assert np.array_equal(np.asarray(got.allow),
                              np.asarray(want.allow))
        assert np.array_equal(np.asarray(got.wait_ms),
                              np.asarray(want.wait_ms))
    assert len(ring._free) == depth + ring.grown   # all recycled
    on_s.close()
    off_s.close()


def test_donation_escape_hatch(clk, monkeypatch):
    """SENTINEL_DONATE=0 keeps the undonated steps working (external
    callers of the _jit_* steps may re-read their inputs)."""
    monkeypatch.setenv("SENTINEL_DONATE", "0")
    sph = make(clk)
    assert not sph._donate
    state_before = sph._state
    v = sph.entry_batch_nowait(["a", "b"]).result()
    assert v.allow.all()
    # undonated: the pre-dispatch state's buffers are still readable
    np.asarray(jax.tree_util.tree_leaves(state_before)[0])
