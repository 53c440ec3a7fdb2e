"""Per-event split dispatch: a mixed batch (scalar-eligible + origin-
bearing events) is split into two sub-steps (scalar, then fast general)
under one dispatch-lock hold. The defined semantics: identical to
processing the two sub-batches as two consecutive decide_raw calls at the
same timestamp. One origin event must no longer demote 512k events to the
sorted general path (VERDICT r4 #1b).

Reference anchor: FlowRuleChecker.selectNodeByRequesterAndStrategy
(FlowRuleChecker.java:129-161) — origin-scoped rules are the feature that
forces the general path in the first place.
"""

import numpy as np
import pytest

import jax

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock


def make_sentinel(clock, **cfg_over):
    cfg = stpu.load_config(max_resources=64, max_origins=32,
                           max_flow_rules=32, max_degrade_rules=16,
                           max_authority_rules=16, host_fast_path=False,
                           **cfg_over)
    return stpu.Sentinel(config=cfg, clock=clock)


@pytest.fixture
def clk():
    return ManualClock(start_ms=1_785_000_000_000)


RULES = [
    stpu.FlowRule(resource="api", count=500.0),
    stpu.FlowRule(resource="api", count=3.0, limit_app="app-a"),
    stpu.FlowRule(resource="paced", count=10.0,
                  control_behavior=stpu.BEHAVIOR_RATE_LIMITER,
                  max_queueing_time_ms=400),
    stpu.FlowRule(resource="rel", count=4.0, strategy=stpu.STRATEGY_RELATE,
                  ref_resource="api"),
]

DEG = [stpu.DegradeRule(resource="api", grade=stpu.GRADE_EXCEPTION_RATIO,
                        count=0.5, time_window=2, min_request_amount=3)]


def _mixed_raw(sph, rng, n, origin_ids, origin_frac=0.25):
    """Raw numpy arrays for a mixed batch over the loaded resources."""
    names = ["api", "paced", "rel", "free"]
    rows = np.array([sph.resources.get_or_create(names[i])
                     for i in rng.integers(0, len(names), n)], np.int32)
    pad_a = sph.spec.alt_rows
    has_o = rng.random(n) < origin_frac
    oid = np.where(has_o, origin_ids[rng.integers(0, len(origin_ids), n)],
                   0).astype(np.int32)
    orow = np.full(n, pad_a, np.int32)
    for i in np.nonzero(has_o)[0]:
        orow[i] = sph._alt_row(int(rows[i]), 0, int(oid[i]))
    valid = rng.random(n) > 0.1
    return dict(rows=rows, origin_ids=oid, origin_rows=orow,
                context_ids=np.zeros(n, np.int32),
                chain_rows=np.full(n, pad_a, np.int32),
                acquire=np.ones(n, np.int32),
                is_in=np.ones(n, bool),
                prioritized=np.zeros(n, bool), valid=valid)


def _state_leaves_equal(s1, s2):
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "state leaf diverged"


def test_split_equals_sequential_subbatches(clk):
    """decide_raw on a big mixed batch (split path) == two consecutive
    decide_raw calls on the scalar / general sub-batches at the same
    timestamp: per-event verdicts AND final device state bit-equal."""
    A = make_sentinel(clk)
    B = make_sentinel(clk)
    for e in (A, B):
        e.load_flow_rules(RULES)
        e.load_degrade_rules(DEG)
    oids = np.array([A.origins.pin("app-a"), A.origins.pin("app-b")],
                    np.int32)
    oids_b = np.array([B.origins.pin("app-a"), B.origins.pin("app-b")],
                      np.int32)
    assert np.array_equal(oids, oids_b)

    rng = np.random.default_rng(21)
    n = 8192                     # ~6100 scalar-valid > the 4096 threshold
    raw = _mixed_raw(A, rng, n, oids)
    # mirror rows into B's registry (same order → same row ids)
    for r in ["api", "paced", "rel", "free"]:
        B.resources.get_or_create(r)

    now = clk.now_ms()
    split_calls = []
    orig = A._decide_split_nowait

    def spy(*a, **k):
        split_calls.append(1)
        return orig(*a, **k)

    A._decide_split_nowait = spy
    vA = A.decide_raw(raw["rows"], raw["origin_ids"], raw["origin_rows"],
                      raw["context_ids"], raw["chain_rows"], raw["acquire"],
                      raw["is_in"], raw["prioritized"],
                      valid=raw["valid"], at_ms=now)
    assert split_calls, "mixed batch did not take the split path"

    # B: the exact sub-batches the split forms, as two sequential calls
    ev_scalar = ((raw["origin_ids"] == 0)
                 & (raw["origin_rows"] >= A.spec.alt_rows)
                 & (raw["chain_rows"] >= A.spec.alt_rows)) | ~raw["valid"]
    idx_s = np.nonzero(ev_scalar)[0]
    idx_g = np.nonzero(~ev_scalar)[0]
    outs = {}
    for name, idx in (("s", idx_s), ("g", idx_g)):
        outs[name] = B.decide_raw(
            raw["rows"][idx], raw["origin_ids"][idx],
            raw["origin_rows"][idx], raw["context_ids"][idx],
            raw["chain_rows"][idx], raw["acquire"][idx],
            raw["is_in"][idx], raw["prioritized"][idx],
            valid=raw["valid"][idx], at_ms=now)
    assert np.array_equal(vA.allow[idx_s], outs["s"].allow)
    assert np.array_equal(vA.wait_ms[idx_s], outs["s"].wait_ms)
    assert np.array_equal(vA.reason[idx_s], outs["s"].reason)
    assert np.array_equal(vA.allow[idx_g], outs["g"].allow)
    assert np.array_equal(vA.wait_ms[idx_g], outs["g"].wait_ms)
    assert np.array_equal(vA.reason[idx_g], outs["g"].reason)
    _state_leaves_equal(A._state, B._state)


def test_small_mixed_batch_takes_fast_general_whole(clk):
    """Below the split threshold a mixed batch runs the fast general path
    whole-batch — and enforces origin-scoped limits correctly."""
    sph = make_sentinel(clk)
    sph.load_flow_rules(RULES)
    oid = sph.origins.pin("app-a")
    row = sph.resources.get_or_create("api")
    n = 16
    rows = np.full(n, row, np.int32)
    pad_a = sph.spec.alt_rows
    # 8 events from app-a (origin rule count=3), 8 origin-free
    oids = np.array([oid] * 8 + [0] * 8, np.int32)
    orow = np.array([sph._alt_row(row, 0, oid)] * 8 + [pad_a] * 8,
                    np.int32)
    split_calls = []
    orig = sph._decide_split_nowait
    sph._decide_split_nowait = lambda *a, **k: (split_calls.append(1),
                                                orig(*a, **k))[1]
    v = sph.decide_raw(rows, oids, orow, np.zeros(n, np.int32),
                       np.full(n, pad_a, np.int32), np.ones(n, np.int32),
                       np.ones(n, bool), np.zeros(n, bool))
    assert not split_calls, "small batch should not split"
    # origin rule: exactly 3 of the 8 app-a events pass; default rule
    # (count=500) admits all 8 origin-free events
    assert int(v.allow[:8].sum()) == 3
    assert v.allow[8:].all()
    assert (np.asarray(v.reason[:8])[~v.allow[:8]]
            == int(stpu.BlockReason.FLOW)).all()


def test_split_preserves_breaker_observer_events(clk):
    """Breaker transitions caused within a split dispatch still fire
    exactly once through the observer readback path."""
    sph = make_sentinel(clk)
    sph.load_flow_rules(RULES)
    sph.load_degrade_rules([stpu.DegradeRule(
        resource="api", grade=stpu.GRADE_EXCEPTION_COUNT, count=1,
        time_window=1, min_request_amount=1)])
    oid = sph.origins.pin("app-b")
    seen = []
    sph.add_breaker_observer(lambda res, old, new: seen.append((res, old,
                                                                new)))
    # trip the breaker with an error exit first
    e = sph.entry("api")
    e.trace(RuntimeError("x"))
    e.exit()
    assert seen, "trip not observed"
    n_seen = len(seen)
    # now a big mixed batch: blocked by the OPEN breaker either way; the
    # split dispatch must still ride its readback through the diff
    row = sph.resources.get_or_create("api")
    n = 8192
    rng = np.random.default_rng(5)
    has_o = rng.random(n) < 0.2
    oids = np.where(has_o, oid, 0).astype(np.int32)
    pad_a = sph.spec.alt_rows
    orow = np.where(has_o, sph._alt_row(row, 0, int(oid)),
                    pad_a).astype(np.int32)
    v = sph.decide_raw(np.full(n, row, np.int32), oids, orow,
                       np.zeros(n, np.int32), np.full(n, pad_a, np.int32),
                       np.ones(n, np.int32), np.ones(n, bool),
                       np.zeros(n, bool))
    assert not v.allow.any()
    assert len(seen) == n_seen      # no transition, no spurious event


def test_split_with_prio_and_live_bookings_equals_sequential(clk):
    """Mixed batches carrying prioritized events + live occupy bookings:
    the split path (scalar side folds bookings via occupy_base, general
    side books via flow_check_fast_occupy) stays bit-exact with two
    sequential decide_raw calls on the same partition — across steps, so
    step k's bookings shape step k+1's admissions. Also pins the r6
    tentpole: prioritized events must NOT disable the split (the pre-r6
    whole-batch demotion was a 16x cliff)."""
    A = make_sentinel(clk)
    B = make_sentinel(clk)
    for e in (A, B):
        e.load_flow_rules(RULES)
        e.load_degrade_rules(DEG)
    oids = np.array([A.origins.pin("app-a"), A.origins.pin("app-b")],
                    np.int32)
    assert np.array_equal(
        oids, np.array([B.origins.pin("app-a"), B.origins.pin("app-b")],
                       np.int32))
    for r in ["api", "paced", "rel", "free"]:
        A.resources.get_or_create(r)
        B.resources.get_or_create(r)

    rng = np.random.default_rng(31)
    n = 8192
    split_calls = []
    orig = A._decide_split_nowait

    def spy(*a, **k):
        split_calls.append(1)
        return orig(*a, **k)

    A._decide_split_nowait = spy
    pad_a = A.spec.alt_rows
    saw_booking = False
    for step in range(5):
        raw = _mixed_raw(A, rng, n, oids, origin_frac=0.2)
        raw["prioritized"] = rng.random(n) < 0.05
        now = clk.now_ms()
        vA = A.decide_raw(raw["rows"], raw["origin_ids"],
                          raw["origin_rows"], raw["context_ids"],
                          raw["chain_rows"], raw["acquire"], raw["is_in"],
                          raw["prioritized"], valid=raw["valid"],
                          at_ms=now)
        assert len(split_calls) == step + 1, \
            "prioritized events demoted the batch off the split path"
        # B: the exact sub-batches the split forms (prioritized events
        # ride the general side), as two sequential calls
        ev_scalar = (((raw["origin_ids"] == 0)
                      & (raw["origin_rows"] >= pad_a)
                      & (raw["chain_rows"] >= pad_a)
                      & ~raw["prioritized"]) | ~raw["valid"])
        outs = {}
        for name, idx in (("s", np.nonzero(ev_scalar)[0]),
                          ("g", np.nonzero(~ev_scalar)[0])):
            outs[name] = B.decide_raw(
                raw["rows"][idx], raw["origin_ids"][idx],
                raw["origin_rows"][idx], raw["context_ids"][idx],
                raw["chain_rows"][idx], raw["acquire"][idx],
                raw["is_in"][idx], raw["prioritized"][idx],
                valid=raw["valid"][idx], at_ms=now)
        idx_s = np.nonzero(ev_scalar)[0]
        idx_g = np.nonzero(~ev_scalar)[0]
        for field in ("allow", "wait_ms", "reason"):
            assert np.array_equal(getattr(vA, field)[idx_s],
                                  getattr(outs["s"], field)), \
                f"scalar-side {field} diverged step {step}"
            assert np.array_equal(getattr(vA, field)[idx_g],
                                  getattr(outs["g"], field)), \
                f"general-side {field} diverged step {step}"
        _state_leaves_equal(A._state, B._state)
        saw_booking = saw_booking or bool(
            (np.asarray(A._state.flow_dyn.occupied_count) > 0).any())
        clk.advance_ms(int(rng.integers(100, 400)))
    assert saw_booking, "no occupy booking exercised — weak test"


# ---------------------------------------------------------------------------
# the route table: which program family one raw batch is dispatched to
# ---------------------------------------------------------------------------

def _route_cols(sph, n):
    """A pure-scalar raw batch of ``n`` lanes over the loaded resources."""
    names = ["api", "paced", "rel", "free"]
    rows = np.array([sph.resources.get_or_create(names[i % len(names)])
                     for i in range(n)], np.int32)
    pad_a = sph.spec.alt_rows
    return dict(rows=rows, origin_ids=np.zeros(n, np.int32),
                origin_rows=np.full(n, pad_a, np.int32),
                context_ids=np.zeros(n, np.int32),
                chain_rows=np.full(n, pad_a, np.int32),
                acquire=np.ones(n, np.int32), is_in=np.ones(n, bool),
                prioritized=np.zeros(n, bool))


def _with_origin(sph, c, lanes, alt_rows=True):
    oid = sph.origins.pin("app-a")
    c["origin_ids"][lanes] = oid
    if alt_rows:
        for i in np.arange(c["rows"].shape[0])[lanes]:
            c["origin_rows"][i] = sph._alt_row(int(c["rows"][i]), 0, oid)


def _origin_ids_only(sph, c):
    _with_origin(sph, c, slice(None), alt_rows=False)


def _alt_rows(sph, c):
    _with_origin(sph, c, slice(None))


def _prioritized(sph, c):
    c["prioritized"][::7] = True


def _non_uniform_acquire(sph, c):
    c["acquire"][::3] = 2


def _garbage_on_invalid_lanes(sph, c):
    c["valid"] = np.ones(c["rows"].shape[0], bool)
    c["valid"][::2] = False
    c["acquire"][::2] = 5
    c["origin_ids"][::2] = sph.origins.pin("app-a")


def _zero_cluster_bits(sph, c):
    c["cluster_fallback"] = np.zeros(c["rows"].shape[0], np.int32)


def _mixed(sph, c):
    _with_origin(sph, c, slice(0, 32))


SCALAR, FAST, FAST_OCC, GENERAL, SPLIT = (
    "split_route.scalar", "split_route.fast", "split_route.fast_occupy",
    "split_route.general_sorted", "split_route.split_fired")

#: id → (lanes, batch mutation, env, rank key fits, route, dispatches,
#: sketch-fused). ``dispatches`` is what ``pipeline.dispatches`` rises by.
ROUTE_TABLE = {
    "pure_scalar": (64, None, {}, True, SCALAR, 1, True),
    "origin_ids_only": (64, _origin_ids_only, {}, True, FAST, 1, True),
    "alt_rows": (64, _alt_rows, {}, True, FAST, 1, True),
    "prioritized": (64, _prioritized, {}, True, FAST_OCC, 1, True),
    "non_uniform_acquire": (64, _non_uniform_acquire, {}, True, GENERAL,
                            1, True),
    "invalid_lanes_do_not_count": (64, _garbage_on_invalid_lanes, {}, True,
                                   SCALAR, 1, True),
    "cluster_bits_present": (64, _zero_cluster_bits, {}, True, FAST, 1,
                             True),
    "rank_key_too_wide": (64, _alt_rows, {}, False, GENERAL, 1, True),
    "rank_key_too_wide_scalar": (64, None, {}, False, SCALAR, 1, True),
    "mixed_small": (4095 + 32, _mixed, {}, True, FAST, 1, True),
    "mixed_split": (4096 + 32, _mixed, {}, True, SPLIT, 2, False),
    "mixed_split_rank_key_too_wide": (4096 + 32, _mixed, {}, False,
                                      GENERAL, 1, True),
    "untiered_scalar": (64, None, {"SENTINEL_TIERING_DISABLE": "1"}, True,
                        SCALAR, 1, False),
    "untiered_split": (4096 + 32, _mixed,
                       {"SENTINEL_TIERING_DISABLE": "1"}, True, SPLIT, 2,
                       False),
    "standalone_observe": (64, None, {"SENTINEL_SINGLE_DISPATCH": "0"},
                           True, SCALAR, 2, False),
}


@pytest.mark.parametrize("case", list(ROUTE_TABLE))
def test_route_table(clk, monkeypatch, case):
    """One raw batch → exactly one route counter, the dispatches that
    route costs, and ``split_route.single_dispatch`` only when a
    whole-batch decide carried the sketch observe. The rank key that does
    not fit int32 is faked through the eligibility helper's inputs."""
    from sentinel_tpu.obs import counters as ck
    n, mutate, env, key_fits, route, dispatches, fused = ROUTE_TABLE[case]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    sph = make_sentinel(clk)
    try:
        sph.load_flow_rules(RULES)
        cols = _route_cols(sph, n)
        if mutate is not None:
            mutate(sph, cols)
        if not key_fits:
            real = sph._lane_eligibility
            monkeypatch.setattr(
                sph, "_lane_eligibility",
                lambda n, oid, acq, prio, valid, _slots, pad_a: real(
                    n, oid, acq, prio, valid, 2 ** 31, pad_a))
        args = [cols.pop(k) for k in (
            "rows", "origin_ids", "origin_rows", "context_ids",
            "chain_rows", "acquire", "is_in", "prioritized")]
        routes = (SCALAR, FAST, FAST_OCC, GENERAL, SPLIT)
        c = sph.obs.counters
        before = {k: c.get(k) for k in routes}
        disp0 = c.get(ck.PIPE_DISPATCH)
        sd0 = c.get(ck.ROUTE_SINGLE_DISPATCH)
        v = sph.decide_raw_nowait(*args, **cols).result()
        assert v.allow.shape == (n,)
        fired = {k: c.get(k) - before[k] for k in routes}
        assert fired == {k: int(k == route) for k in routes}
        assert c.get(ck.PIPE_DISPATCH) - disp0 == dispatches
        assert c.get(ck.ROUTE_SINGLE_DISPATCH) - sd0 == int(fused)
        assert c.get(ck.ROUTE_FUSED) == 0
    finally:
        sph.close()
