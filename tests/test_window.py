"""Window-tensor tests — parity targets: LeapArrayTest / BucketLeapArrayTest /
ArrayMetricTest semantics (reference sentinel-core test tier 1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    SECOND_SPEC, WindowSpec, add_rows, init_window, invalidate_rows,
    min_rt_rows, refresh_rows, rolling_totals, rt_totals, valid_mask,
    window_sum_all, window_sum_rows,
)

# core-path subset: the CI quick tier (PRs) runs only these files
pytestmark = pytest.mark.quick


def _add(spec, st, row, event, n, now_ms, rt=None):
    idx = spec.index_of(now_ms)
    rows = jnp.array([row], jnp.int32)
    st = refresh_rows(spec, st, rows, idx)
    rt_arr = None if rt is None else jnp.array([rt], jnp.int32)
    return add_rows(spec, st, rows, event, jnp.array([n], jnp.int32), idx, rt_ms=rt_arr)


def _sum(spec, st, row, event, now_ms):
    return int(window_sum_rows(spec, st, jnp.array([row], jnp.int32), event,
                               spec.index_of(now_ms))[0])


def test_single_bucket_add_and_sum():
    spec = SECOND_SPEC  # 2 × 500ms
    st = init_window(spec, rows=4)
    st = _add(spec, st, 1, ev.PASS, 3, now_ms=1000)
    assert _sum(spec, st, 1, ev.PASS, 1000) == 3
    assert _sum(spec, st, 0, ev.PASS, 1000) == 0


def test_window_rolls_across_buckets():
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    st = _add(spec, st, 0, ev.PASS, 5, now_ms=1000)   # window idx 2 (k=0)
    st = _add(spec, st, 0, ev.PASS, 7, now_ms=1500)   # window idx 3 (k=1)
    assert _sum(spec, st, 0, ev.PASS, 1500) == 12
    # at t=2000 the 1000-bucket is exactly interval-old → deprecated
    assert _sum(spec, st, 0, ev.PASS, 2000) == 7
    assert _sum(spec, st, 0, ev.PASS, 2500) == 0


def test_epoch_scale_timestamps():
    """Regression: real wall-clock epoch ms (~1.78e12) must work; window index
    math happens host-side in Python ints (device int32 would overflow)."""
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    t0 = 1_785_324_450_225  # actual epoch ms from the build machine
    st = _add(spec, st, 0, ev.PASS, 4, now_ms=t0)
    st = _add(spec, st, 0, ev.PASS, 6, now_ms=t0 + 499)
    assert _sum(spec, st, 0, ev.PASS, t0 + 499) == 10
    assert _sum(spec, st, 0, ev.PASS, t0 + 2000) == 0


def test_lazy_reset_on_reuse():
    spec = SECOND_SPEC
    st = init_window(spec, rows=1)
    st = _add(spec, st, 0, ev.PASS, 5, now_ms=1000)
    st = _add(spec, st, 0, ev.PASS, 2, now_ms=2000)  # same physical bucket
    assert _sum(spec, st, 0, ev.PASS, 2000) == 2


def test_duplicate_rows_in_one_batch_reset_idempotent():
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    st = _add(spec, st, 0, ev.PASS, 5, now_ms=1000)
    idx = spec.index_of(2000)
    rows = jnp.array([0, 0, 0], jnp.int32)
    st = refresh_rows(spec, st, rows, idx)  # stale bucket zeroed exactly once
    st = add_rows(spec, st, rows, ev.PASS, jnp.array([1, 1, 1], jnp.int32), idx)
    assert _sum(spec, st, 0, ev.PASS, 2000) == 3


def test_padding_rows_dropped():
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    idx = spec.index_of(1000)
    rows = jnp.array([0, 2, 5], jnp.int32)  # row ids >= R are padding
    st = refresh_rows(spec, st, rows, idx)
    st = add_rows(spec, st, rows, ev.PASS, jnp.array([1, 9, 9], jnp.int32), idx)
    assert int(jnp.sum(st.counters[:, :, ev.PASS])) == 1


def test_min_rt_and_rt_sum():
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    st = _add(spec, st, 0, ev.SUCCESS, 1, now_ms=1000, rt=40)
    st = _add(spec, st, 0, ev.SUCCESS, 1, now_ms=1200, rt=15)
    rows = jnp.array([0, 1], jnp.int32)
    idx = spec.index_of(1200)
    m = min_rt_rows(spec, st, rows, idx, default_rt=5000)
    assert int(m[0]) == 15
    assert int(m[1]) == 5000  # untouched row → statisticMaxRt default
    rt = rt_totals(spec, st, idx)
    assert float(rt[0]) == 55.0
    # after the window passes, both reset
    st = _add(spec, st, 0, ev.SUCCESS, 1, now_ms=3000, rt=99)
    idx3 = spec.index_of(3000)
    assert int(min_rt_rows(spec, st, rows, idx3, default_rt=5000)[0]) == 99
    assert float(rt_totals(spec, st, idx3)[0]) == 99.0


def test_minute_window_spec():
    spec = WindowSpec(buckets=60, win_ms=1000, track_rt=False)
    st = init_window(spec, rows=1)
    st = _add(spec, st, 0, ev.PASS, 1, now_ms=5_000)
    st = _add(spec, st, 0, ev.PASS, 1, now_ms=30_000)
    assert _sum(spec, st, 0, ev.PASS, 35_000) == 2
    # 5s bucket dies at t=65s (60s interval), 30s bucket survives
    assert _sum(spec, st, 0, ev.PASS, 65_500) == 1


def test_rolling_totals_and_all_rows():
    spec = SECOND_SPEC
    st = init_window(spec, rows=3)
    st = _add(spec, st, 1, ev.PASS, 4, now_ms=1000)
    st = _add(spec, st, 2, ev.BLOCK, 2, now_ms=1000)
    idx = spec.index_of(1200)
    tot = rolling_totals(spec, st, idx)
    assert tot.shape == (3, ev.NUM_EVENTS)
    assert int(tot[1, ev.PASS]) == 4 and int(tot[2, ev.BLOCK]) == 2
    np.testing.assert_array_equal(
        np.asarray(window_sum_all(spec, st, ev.PASS, idx)), [0, 4, 0])


def test_valid_mask_never_written():
    spec = SECOND_SPEC
    st = init_window(spec, rows=1)
    assert not bool(valid_mask(spec, st.stamps, spec.index_of(0)).any())
    # ...and at epoch-scale time too
    assert not bool(valid_mask(spec, st.stamps, spec.index_of(1_785_324_450_225)).any())


def test_invalidate_rows_forgets_history():
    """Regression: recycled registry rows must not inherit old counters."""
    spec = SECOND_SPEC
    st = init_window(spec, rows=2)
    st = _add(spec, st, 1, ev.PASS, 50, now_ms=1000)
    st = invalidate_rows(spec, st, jnp.array([1], jnp.int32))
    assert _sum(spec, st, 1, ev.PASS, 1000) == 0
    # row is immediately usable for a fresh resource
    st = _add(spec, st, 1, ev.PASS, 2, now_ms=1100)
    assert _sum(spec, st, 1, ev.PASS, 1100) == 2


def test_entry_rt_sum_no_int32_overflow_in_large_batch():
    """The ENTRY-row RT reduction must accumulate in float32: a single large
    exit batch with big rt values would wrap int32 (reproduced at 512k
    events x ~4.9s rt before the fix)."""
    import functools

    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.registry import ENTRY_NODE_ROW
    from sentinel_tpu.engine.pipeline import (
        EngineSpec, ExitBatch, RuleSet, init_state, record_exits,
    )
    from sentinel_tpu.rules import authority as auth_mod
    from sentinel_tpu.rules import degrade as deg_mod
    from sentinel_tpu.rules import flow as flow_mod
    from sentinel_tpu.rules import param_flow as pf_mod
    from sentinel_tpu.rules import system as sys_mod
    from sentinel_tpu.core.registry import (
        OriginRegistry, Registry, ResourceRegistry,
    )

    R, B = 64, 4096
    spec = EngineSpec(rows=R, alt_rows=128, second=WindowSpec(2, 500),
                      minute=None, statistic_max_rt=5000)
    res = ResourceRegistry(R)
    org = OriginRegistry(8)
    ctxr = Registry(8, reserved=("c",))
    flow = flow_mod.compile_flow_rules(
        [], resource_registry=res, context_registry=ctxr, capacity=4,
        k_per_resource=2, num_rows=R, origin_registry=org)
    deg = deg_mod.compile_degrade_rules([], resource_registry=res,
                                        capacity=4, k_per_resource=2,
                                        num_rows=R)
    auth = auth_mod.compile_authority_rules(
        [], resource_registry=res, origin_registry=org, capacity=4,
        k_per_resource=2, num_rows=R)
    param = pf_mod.compile_param_rules([], resource_registry=res,
                                       capacity=1, k_per_resource=2)
    rules = RuleSet(flow.table, flow.rule_idx, deg.table, deg.rule_idx,
                    auth.table, auth.rule_idx,
                    sys_mod.compile_system_rules([]), param.table)
    state = init_state(spec, 4, 4)
    rt = 1_000_000           # 4096 * 1e6 = 4.1e9 >> int32 max
    batch = ExitBatch(
        rows=jnp.full(B, 2, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        rt_ms=jnp.full(B, rt, jnp.int32),
        error=jnp.zeros(B, jnp.bool_),
        is_in=jnp.ones(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    times = jnp.asarray(np.array([100, 0, 1000, 0], np.int32))
    out = jax.jit(functools.partial(record_exits, spec))(rules, state, batch,
                                                         times)
    got = float(out.second.rt_sum[ENTRY_NODE_ROW, 100 % 2])
    assert got == float(B) * rt, got      # would be negative on overflow


def test_late_dispatch_within_ring_preserves_newer_buckets():
    """open_bucket (full-table lazy reset) must not clobber newer-stamped
    buckets when a LATE batch (historical at_ms within one window ring —
    the fast-path flush case) dispatches after live traffic: the safe-late
    guard keeps dispatch indices within one ring of the max, under which a
    full restamp at the old index can only touch dead buckets."""
    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock

    clk = ManualClock(start_ms=1_785_000_000_000)
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=64, max_flow_rules=16, max_degrade_rules=16,
        max_authority_rules=16, host_fast_path=False), clock=clk)
    t0 = clk.now_ms()

    # live traffic at NOW (window index I)
    v = sph.decide_raw(np.array([5], np.int32), np.zeros(1, np.int32),
                       np.array([sph.spec.alt_rows], np.int32),
                       np.zeros(1, np.int32),
                       np.array([sph.spec.alt_rows], np.int32),
                       np.array([3], np.int32), np.ones(1, np.bool_),
                       np.zeros(1, np.bool_))
    assert bool(v.allow[0])
    # LATE batch at I-1 (one 500ms bucket back — within the B=2 ring)
    sph.decide_raw(np.array([6], np.int32), np.zeros(1, np.int32),
                   np.array([sph.spec.alt_rows], np.int32),
                   np.zeros(1, np.int32),
                   np.array([sph.spec.alt_rows], np.int32),
                   np.array([2], np.int32), np.ones(1, np.bool_),
                   np.zeros(1, np.bool_), at_ms=t0 - 500)
    # the NEWER bucket's stats survive, and the late stats landed in the
    # previous bucket — both visible in the rolling second
    tot5 = sph.node_totals_by_row(5)
    tot6 = sph.node_totals_by_row(6)
    assert tot5["pass"] == 3, tot5          # not clobbered by the late group
    assert tot6["pass"] == 2, tot6          # late group recorded
    # half a window later the late bucket rotates out, the live one stays
    clk.advance_ms(500)
    assert sph.node_totals_by_row(6)["pass"] == 0
    assert sph.node_totals_by_row(5)["pass"] == 3


def test_bucket_add_hist_matches_scatter_bitwise():
    """The MXU histogram add (bucket_add_hist) must be bit-identical to the
    index scatter (bucket_add_events) for uniform amounts — including
    padding rows (dropped), collision pileups, and every event lane."""
    from sentinel_tpu.stats.window import (
        bucket_add_events, bucket_add_hist, close_bucket, open_bucket,
    )

    rng = np.random.default_rng(5)
    spec = SECOND_SPEC
    R = 64
    n = 1 << 12
    st = init_window(spec, rows=R)
    idx = spec.index_of(1_700_000_000_250)
    rows_np = rng.integers(0, R + 1, n).astype(np.int32)   # R = padding
    rows_np[: n // 2] = 3          # heavy collision pileup on one row
    rows = jnp.asarray(rows_np)
    evs = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))

    def both(m, amount, **kw):
        bucket = open_bucket(spec, st, idx)
        got = bucket_add_hist(bucket, rows[:m], evs[:m], jnp.int32(amount),
                              **kw)
        want = bucket_add_events(bucket, rows[:m], evs[:m],
                                 jnp.full(m, amount, jnp.int32))
        return (close_bucket(spec, st, got, idx),
                close_bucket(spec, st, want, idx))

    for amount in (1, 7):
        got, want = both(n, amount)
        assert np.array_equal(np.asarray(got.counters),
                              np.asarray(want.counters)), amount
        assert np.array_equal(np.asarray(got.stamps),
                              np.asarray(want.stamps))
        assert int(np.asarray(got.counters).sum()) == \
            amount * int((rows_np < R).sum())
    # non-power-of-2 n exercises the drop-class padding of the last chunk
    got, want = both(3000, 2, chunk=1024)
    assert np.array_equal(np.asarray(got.counters),
                          np.asarray(want.counters))


def test_hist_add_fits_accounts_for_chunk_padding():
    """Regression for the fast-flow dispatch guard (engine/pipeline.py):
    bucket_add_hist pads the batch to a full chunk with drop-class rows, so
    a caller gating on raw ``n < 2**24`` can still trip the f32-exactness
    assert. hist_add_fits is the shared predicate that budgets for the
    padding — pin both sides of its boundary against the real kernel."""
    import jax

    from sentinel_tpu.stats.window import (
        bucket_add_hist, hist_add_fits, open_bucket,
    )

    CH = 1 << 15
    LIM = 1 << 24
    assert hist_add_fits(LIM - CH)          # largest admissible n
    assert not hist_add_fits(LIM - CH + 1)  # padding would reach 2**24
    # the engine guard passes 2*B (pass+block lanes concatenated): a
    # 2**23-row batch is exactly the first size the guard must refuse
    assert not hist_add_fits(2 * (1 << 23))
    assert hist_add_fits(2 * (1 << 23) - CH)

    spec = SECOND_SPEC
    st = init_window(spec, rows=4)

    def trace(n):
        # eval_shape: the assert fires at trace time, nothing allocates
        jax.eval_shape(
            lambda r, e: bucket_add_hist(
                open_bucket(spec, st, jnp.int32(0)), r, e, jnp.int32(1)),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32))

    trace(LIM - CH)                          # boundary size traces clean
    with pytest.raises(AssertionError, match="hist_add_fits"):
        trace(LIM - CH + 1)                  # raw-n guards admit this one


# ---------------------------------------------------------------------------
# The plane form of the record stage (open_bucket → bucket_add_* →
# close_bucket, driven through the pipeline's one call site) against a plain
# NumPy replay, bit-exact for all four tensors after every step.
# ---------------------------------------------------------------------------

PLANE_R, PLANE_E = 8, ev.NUM_EVENTS
I32_MAX = np.iinfo(np.int32).max


class _NpWindow:
    """A window as plain arrays; one event at a time, in batch order."""

    def __init__(self, spec, state):
        self.spec = spec
        self.counters, self.stamps, self.rt_sum, self.min_rt = (
            np.array(x) for x in state)

    def _reset(self, r, k, now_idx):
        if self.stamps[r, k] != now_idx:
            self.counters[r, k, :] = 0
            self.stamps[r, k] = now_idx
            if self.spec.track_rt:
                self.rt_sum[r, k] = 0.0
                self.min_rt[r, k] = I32_MAX

    def replay(self, now_idx, touched, adds, entry):
        """``adds``: (row, lane vector[E], rt or None) per element;
        ``entry``: (lane vector[E], rt sum or None, rt min or None)."""
        B = self.spec.buckets
        k = now_idx % B
        for r in (range(PLANE_R) if B >= 2 else touched):
            if r < PLANE_R:
                self._reset(r, k, now_idx)
        for r, vec, rt in adds:
            if r >= PLANE_R:                      # padding: dropped
                continue
            self.counters[r, k, :] += vec
            if rt is not None and self.spec.track_rt:
                self.rt_sum[r, k] = np.float32(self.rt_sum[r, k]
                                               + np.float32(rt))
                self.min_rt[r, k] = min(self.min_rt[r, k], rt)
        vec, rt_add, rt_min = entry
        self.counters[0, k, :] += vec
        if rt_add is not None and self.spec.track_rt:
            self.rt_sum[0, k] = np.float32(self.rt_sum[0, k]
                                           + np.float32(rt_add))
            self.min_rt[0, k] = min(self.min_rt[0, k], rt_min)

    def assert_equals(self, state, at):
        for name, want, got in zip(state._fields, (
                self.counters, self.stamps, self.rt_sum, self.min_rt), state):
            got = np.asarray(got)
            assert got.dtype == want.dtype and np.array_equal(got, want), \
                (name, at)


def _plane_steps(buckets):
    """(window index, rows) of each step: the same bucket twice, the bucket
    rolling between two steps, then a lap and more later — row 5 is touched
    in the first step only, so its buckets sit untouched for more than B
    windows and the last steps reuse their positions. Every batch holds
    duplicate rows and two padding rows (id >= R)."""
    i0 = SECOND_SPEC.index_of(1_785_324_450_225)
    pad = [PLANE_R, PLANE_R + 3]
    return [(i0, [1, 5, 1, 1, 3] + pad),
            (i0, [1, 2, 2, 7] + pad),
            (i0 + 1, [1, 1, 4, 7] + pad),
            (i0 + buckets + 3, [2, 2, 6] + pad),
            (i0 + 2 * buckets + 3, [1, 6, 6, 6] + pad)]


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["entry-one-index", "entry-rows-sharded"])
@pytest.mark.parametrize("track_rt", [True, False], ids=["rt", "no-rt"])
@pytest.mark.parametrize("buckets", [2, 60, 1], ids=["B2", "B60", "B1"])
@pytest.mark.parametrize("form", ["events", "event", "vecs", "hist"])
def test_plane_record_matches_numpy_replay(form, buckets, track_rt, sharded):
    """decide's fused per-element record (``events``), record_blocks' one
    event (``event``), exit's lane vectors with rt (``vecs``) and the alt
    table's histogram add (``hist``), each followed by the ENTRY row's
    pre-reduced vector in both of its forms — B == 1 on refresh_rows."""
    import jax

    from sentinel_tpu.engine.pipeline import _record_window
    from sentinel_tpu.stats.window import (
        bucket_add_events, bucket_add_hist, bucket_add_row, bucket_add_vecs,
    )

    spec = WindowSpec(buckets=buckets, win_ms=500, track_rt=track_rt)
    rng = np.random.default_rng(buckets * 8 + track_rt * 2 + sharded)
    state = init_window(spec, rows=PLANE_R)
    model = _NpWindow(spec, state)

    @jax.jit
    def record(state, now_idx, rows, lanes, amounts, payload, rt, entry_vec,
               entry_rt_add, entry_rt_min):
        def adds(bucket):
            if form == "events":
                bucket = bucket_add_events(bucket, rows, lanes, amounts)
            elif form == "event":
                bucket = bucket_add_events(bucket, rows, ev.BLOCK, amounts)
            elif form == "hist":
                bucket = bucket_add_hist(bucket, rows, lanes, amounts[0],
                                         chunk=4)
            else:
                bucket = bucket_add_vecs(bucket, rows, payload, rt_ms=rt,
                                         rt_valid=rows < PLANE_R)
            return bucket_add_row(
                bucket, 0, entry_vec, sharded=sharded,
                **(dict(rt_add=entry_rt_add, rt_min=entry_rt_min)
                   if form == "vecs" else {}))
        return _record_window("t", "w", spec, state, now_idx, rows, adds)

    for at, (now_idx, rows) in enumerate(_plane_steps(buckets)):
        n = len(rows)
        lanes = rng.integers(0, 3, n).astype(np.int32)
        amounts = (np.full(n, 3, np.int32) if form == "hist"
                   else rng.integers(1, 5, n).astype(np.int32))
        payload = rng.integers(0, 4, (n, PLANE_E)).astype(np.int32)
        rt = rng.integers(1, 900, n).astype(np.int32)
        entry_vec = rng.integers(0, 9, PLANE_E).astype(np.int32)
        entry_rt = rng.integers(1, 900, 2).astype(np.int32)

        def lane_vec(i):
            if form == "vecs":
                return payload[i]
            vec = np.zeros(PLANE_E, np.int32)
            vec[ev.BLOCK if form == "event" else lanes[i]] = amounts[i]
            return vec

        with_rt = form == "vecs"
        model.replay(
            now_idx, rows,
            [(r, lane_vec(i), int(rt[i]) if with_rt else None)
             for i, r in enumerate(rows)],
            (entry_vec, float(entry_rt[0]) if with_rt else None,
             int(entry_rt[1])))
        state = record(state, jnp.int32(now_idx),
                       jnp.asarray(rows, jnp.int32), jnp.asarray(lanes),
                       jnp.asarray(amounts), jnp.asarray(payload),
                       jnp.asarray(rt), jnp.asarray(entry_vec),
                       jnp.float32(entry_rt[0]), jnp.int32(entry_rt[1]))
        model.assert_equals(state, at)


def test_plane_reset_keeps_the_other_buckets_and_reads():
    """open/close touches bucket ``k`` alone: after a roll the older
    bucket's counts, stamps and rt still read through the window sums."""
    from sentinel_tpu.stats.window import (
        bucket_add_vecs, close_bucket, open_bucket,
    )

    spec = SECOND_SPEC
    st = init_window(spec, rows=4)
    rows = jnp.array([2, 2, 9], jnp.int32)
    payload = jnp.zeros((3, ev.NUM_EVENTS), jnp.int32).at[:, ev.SUCCESS].set(1)
    for now_ms, rt in ((1000, 40), (1500, 15)):
        idx = spec.index_of(now_ms)
        bucket = bucket_add_vecs(open_bucket(spec, st, idx), rows, payload,
                                 rt_ms=jnp.full(3, rt, jnp.int32),
                                 rt_valid=rows < 4)
        st = close_bucket(spec, st, bucket, idx)
    idx = spec.index_of(1500)
    assert _sum(spec, st, 2, ev.SUCCESS, 1500) == 4
    assert float(rt_totals(spec, st, idx)[2]) == 110.0
    assert int(min_rt_rows(spec, st, jnp.array([2], jnp.int32), idx,
                           default_rt=5000)[0]) == 15
    assert _sum(spec, st, 2, ev.SUCCESS, 2000) == 2     # the 1000 bucket died


def test_open_bucket_refuses_a_full_reset_of_a_one_bucket_window():
    from sentinel_tpu.stats.window import open_bucket

    spec = WindowSpec(buckets=1, win_ms=1000)
    st = init_window(spec, rows=2)
    with pytest.raises(AssertionError, match="B >= 2"):
        open_bucket(spec, st, jnp.int32(3))
    assert open_bucket(spec, st, jnp.int32(3), reset=False).stamps.shape == (2,)
