"""Round 20: device-resident per-resource RT histograms
(obs/resource_hist.py, docs/OBSERVABILITY.md "Per-resource RT
histograms"):

* geometry + quantile extraction: the traced kernels are bit-exact
  against their NumPy mirrors, including bucket-edge ranks and the
  empty-row sentinel;
* merge math: cumulative count vectors sum associatively (shard gather
  and multihost psum orders agree, bit for bit) and quantiles of the
  sum equal the fleet truth;
* the engine hot path: ``record_exits`` scatters exits into the row's
  histogram with ZERO extra dispatches, telemetry surfaces
  ``rt_p50/95/99_ms`` + the raw vector, and row invalidation resets;
* bit-parity: ``SENTINEL_RESOURCE_HIST_DISABLE=1`` reproduces the
  enabled run's verdicts and dispatch count exactly;
* tiering: counts survive the demote → promote round trip;
* the controller: interval-p99 deltas trip the degrade tracker on a
  slow-consumer episode the old MEAN signal provably cannot see;
* the f32-exactness guard boundary (``stats.window.hist_add_fits`` —
  ADVICE round 5).

All quick-tier, CPU; virtual time rides the ManualClock.
"""

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.core.errors import BlockException
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.obs import resource_hist as rh
from sentinel_tpu.stats.window import hist_add_fits

pytestmark = pytest.mark.quick

T0 = 1_785_000_000_000


def _cfg(**over):
    base = dict(max_resources=64, max_flow_rules=16,
                max_degrade_rules=16, max_authority_rules=16,
                host_fast_path=False)
    base.update(over)
    return stpu.load_config(**base)


def _make(**over):
    return stpu.Sentinel(_cfg(**over), clock=ManualClock(start_ms=T0))


def _timed_exit(s, name, rt_ms):
    e = s.entry(name)
    if rt_ms:
        s.clock.advance_ms(rt_ms)
    e.exit()


# ---------------------------------------------------------------------------
# geometry: bucket index, thresholds, edges
# ---------------------------------------------------------------------------

def test_bucket_index_edges():
    hb = 32
    # bucket 0 = [0, 1], bucket i = (2^(i-1), 2^i]; top bucket open above
    cases = {0: 0, 1: 0, 2: 1, 3: 2, 4: 2, 5: 3, 8: 3, 9: 4,
             (1 << 30): 30, (1 << 30) + 1: 31, -5: 0}
    for v, want in cases.items():
        assert int(rh.np_bucket_index(v, hb)) == want, v
        assert int(rh.bucket_index(v, hb)) == want, v


def test_bucket_index_traced_matches_numpy():
    rng = np.random.default_rng(3)
    for hb in (8, 16, 32):
        v = rng.integers(0, 1 << 20, size=257).astype(np.int32)
        assert np.array_equal(np.asarray(rh.bucket_index(v, hb)),
                              rh.np_bucket_index(v, hb))


def test_threshold_table_is_int32_safe():
    th = rh.bucket_thresholds_ms(rh.MAX_BUCKETS)
    assert th.dtype == np.int32 and th.shape == (rh.MAX_BUCKETS - 1,)
    assert int(th[-1]) == 1 << 30          # no overflow at the cap
    edges = rh.bucket_edges_ms(rh.MAX_BUCKETS)
    assert edges.shape == (rh.MAX_BUCKETS + 1,)
    assert edges[0] == 0.0 and edges[1] == 1.0
    assert float(edges[-1]) == float(1 << 31)


# ---------------------------------------------------------------------------
# quantile extraction: traced == NumPy mirror, known values, edge ranks
# ---------------------------------------------------------------------------

def test_quantiles_traced_bit_equal_to_numpy():
    rng = np.random.default_rng(11)
    for hb in (8, 32):
        counts = rng.integers(0, 1000, size=(17, hb)).astype(np.int32)
        counts[3] = 0                                  # an empty row
        dev = np.asarray(rh.quantiles_from_counts(counts))
        host = rh.np_quantiles(counts)
        assert dev.dtype == host.dtype == np.float32
        assert np.array_equal(dev, host)               # BIT-exact
        assert np.all(dev[3] == 0.0)                   # empty → no signal


def test_quantiles_known_values():
    hb = 32
    # all mass in bucket 0 ([0,1] ms): p50 rank 50/100 → 0.5 ms
    c = np.zeros(hb, np.int32)
    c[0] = 100
    q = rh.np_quantiles(c[None])[0]
    assert q[0] == np.float32(0.5)
    # the smoke scenario: 100 fast + 2 in (256, 512] — p99 rank 100.98
    # interpolates 0.49 into bucket 9 → 256 + 0.49·256 = 381.44 ms
    c[9] = 2
    q = rh.np_quantiles(c[None])[0]
    assert q[2] == pytest.approx(381.44, abs=0.01)
    assert q[0] == np.float32(0.51)


def test_quantile_rank_at_exact_bucket_boundary():
    hb = 16
    # 10 in bucket 2, 10 in bucket 4: p50 rank = 10 lands EXACTLY on
    # bucket 2's cumulative edge — must stay in bucket 2 at its top edge
    c = np.zeros(hb, np.int32)
    c[2], c[4] = 10, 10
    q = rh.np_quantiles(c[None], quantiles=(0.5,))[0]
    assert q[0] == np.float32(4.0)                     # bucket 2 hi edge
    # one sample: every quantile clamps to rank 1 inside its bucket
    c = np.zeros(hb, np.int32)
    c[5] = 1
    q = rh.np_quantiles(c[None])[0]
    assert np.all(q == q[0]) and 16.0 < float(q[0]) <= 32.0


def test_top_bucket_open_above_caps_at_last_edge():
    hb = 8
    c = np.zeros(hb, np.int32)
    c[hb - 1] = 4                  # all mass above the threshold table
    q = rh.np_quantiles(c[None])[0]
    edges = rh.bucket_edges_ms(hb)
    assert np.all(q > edges[-2]) and np.all(q <= edges[-1])


# ---------------------------------------------------------------------------
# merge math: shard / fleet sums are associative and quantile-faithful
# ---------------------------------------------------------------------------

def test_merge_is_associative_and_order_free():
    rng = np.random.default_rng(5)
    shards = rng.integers(0, 10_000, size=(6, 32)).astype(np.int64)
    fwd = shards[0]
    for s in shards[1:]:
        fwd = fwd + s
    rev = shards[-1]
    for s in shards[-2::-1]:
        rev = rev + s
    pairwise = (shards[0] + shards[1]) + (shards[2] + shards[3]) \
        + (shards[4] + shards[5])
    assert np.array_equal(fwd, rev) and np.array_equal(fwd, pairwise)
    assert np.array_equal(fwd, shards.sum(axis=0))
    # quantiles of the sum == the fleet truth (and NOT, in general, any
    # average of per-shard quantiles — that's the point of shipping
    # histograms instead of quantiles)
    assert np.array_equal(rh.np_quantiles(fwd[None]),
                          rh.np_quantiles(shards.sum(axis=0)[None]))


def test_device_sum_matches_host_sum_bit_exact():
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    shards = rng.integers(0, 1 << 20, size=(8, 32)).astype(np.int32)
    dev = np.asarray(jnp.sum(jnp.asarray(shards), axis=0))  # psum mirror
    assert np.array_equal(dev, shards.sum(axis=0).astype(np.int32))
    assert np.array_equal(
        np.asarray(rh.quantiles_from_counts(dev[None])),
        rh.np_quantiles(shards.sum(axis=0)[None]))


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_knob_envs(monkeypatch):
    assert rh.engine_hist_buckets() == rh.DEFAULT_BUCKETS
    monkeypatch.setenv(rh.RESOURCE_HIST_BUCKETS_ENV, "12")
    assert rh.engine_hist_buckets() == 12
    monkeypatch.setenv(rh.RESOURCE_HIST_BUCKETS_ENV, "2")
    assert rh.engine_hist_buckets() == rh.MIN_BUCKETS       # clamped
    monkeypatch.setenv(rh.RESOURCE_HIST_BUCKETS_ENV, "99")
    assert rh.engine_hist_buckets() == rh.MAX_BUCKETS
    monkeypatch.setenv(rh.RESOURCE_HIST_DISABLE_ENV, "1")
    assert rh.engine_hist_buckets() == 0                    # feature off
    monkeypatch.setenv(rh.RESOURCE_HIST_DISABLE_ENV, "off")
    assert rh.engine_hist_buckets() == rh.MAX_BUCKETS


# ---------------------------------------------------------------------------
# the f32-exactness guard boundary (ADVICE round 5)
# ---------------------------------------------------------------------------

def test_hist_add_fits_accounts_for_chunk_padding():
    """The guard must bound n PLUS the up-to-chunk padding bucket_add_hist
    appends (2**24 is where f32 scatter-add loses integer exactness) —
    the raw ``2*B <= 2**24`` form was off by the padding."""
    chunk = 1 << 15
    edge = (1 << 24) - chunk
    assert hist_add_fits(edge)
    assert not hist_add_fits(edge + 1)
    assert hist_add_fits(0) and hist_add_fits(1)
    # a custom chunk shifts the boundary with it
    assert hist_add_fits(edge + chunk // 2, chunk=chunk // 2)
    assert not hist_add_fits(edge + chunk // 2 + 1, chunk=chunk // 2)


# ---------------------------------------------------------------------------
# engine hot path: record → gather → quantiles → hot entries
# ---------------------------------------------------------------------------

def test_engine_records_and_surfaces_quantiles():
    s = _make()
    try:
        assert s.spec.hist_buckets == rh.DEFAULT_BUCKETS
        assert s._state.rt_hist is not None
        for _ in range(100):
            _timed_exit(s, "api", 1)
        for _ in range(2):
            _timed_exit(s, "api", 400)
        row = s.resources.lookup("api")
        vec = np.asarray(s._state.rt_hist)[row]
        # host reference: 100 exits at 1 ms → bucket 0, 2 at 400 ms →
        # bucket 9 ((256, 512])
        assert vec[0] == 100 and vec[9] == 2 and vec.sum() == 102
        assert s.telemetry.poll() == 1
        hot = {h["resource"]: h for h in s.telemetry.hot_entries()}
        h = hot["api"]
        assert h["rt_hist"][0] == 100 and h["rt_hist"][9] == 2
        want = rh.np_quantiles(vec[None].astype(np.int64))[0]
        assert h["rt_p50_ms"] == round(float(want[0]), 3)
        assert h["rt_p95_ms"] == round(float(want[1]), 3)
        assert h["rt_p99_ms"] == round(float(want[2]), 3)
        assert s.obs.counters.get(ck.TELEMETRY_HIST_TICK) == 1
    finally:
        s.close()


def test_invalidation_resets_and_fresh_rows_start_zero(monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")   # evict_name seam
    s = _make()
    try:
        _timed_exit(s, "other", 0)         # pre-interned on its own row
        _timed_exit(s, "gone", 3)
        row = s.resources.lookup("gone")
        orow = s.resources.lookup("other")
        assert row != orow
        assert np.asarray(s._state.rt_hist)[row].sum() == 1
        assert s.resources.evict_name("gone")
        s.entry("other").exit()            # drains the evict
        assert np.asarray(s._state.rt_hist)[row].sum() == 0
        assert np.asarray(s._state.rt_hist)[orow][0] == 2
    finally:
        s.close()


def test_disable_env_compiles_the_feature_away(monkeypatch):
    monkeypatch.setenv(rh.RESOURCE_HIST_DISABLE_ENV, "1")
    s = _make()
    try:
        assert s.spec.hist_buckets == 0
        assert s._state.rt_hist is None
        _timed_exit(s, "api", 5)
        assert s.telemetry.poll() == 1
        h = s.telemetry.hot_entries()[0]
        assert "rt_p99_ms" not in h and "rt_hist" not in h
        assert s.obs.counters.get(ck.TELEMETRY_HIST_TICK) == 0
    finally:
        s.close()


def _drive_verdicts(s, n=120):
    """Deterministic mixed stream against a 1-permit flow rule: some
    entries block. Returns the verdict bit-string + dispatch count."""
    s.load_flow_rules([stpu.FlowRule(resource="lim", count=3)])
    out = []
    for i in range(n):
        name = "lim" if i % 3 else "free"
        try:
            e = s.entry(name)
            s.clock.advance_ms(1 + (i % 7))
            e.exit()
            out.append(True)
        except BlockException:
            out.append(False)
    return out, s.obs.counters.get(ck.PIPE_DISPATCH)


def test_disable_bit_parity_and_dispatch_count(monkeypatch):
    """The gate (n) parity leg in miniature: verdict-for-verdict AND
    dispatch-for-dispatch, the histogram table is free."""
    s = _make()
    try:
        v_on, d_on = _drive_verdicts(s)
    finally:
        s.close()
    monkeypatch.setenv(rh.RESOURCE_HIST_DISABLE_ENV, "1")
    s = _make()
    try:
        v_off, d_off = _drive_verdicts(s)
    finally:
        s.close()
    assert v_on == v_off
    assert d_on == d_off          # dispatches_per_batch unchanged


# ---------------------------------------------------------------------------
# tiering: counts ride demote → promote
# ---------------------------------------------------------------------------

def test_demoted_cold_entry_carries_the_vector(monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    s = _make(max_resources=32)
    try:
        t = s.tiering
        assert t.enabled
        for _ in range(5):
            _timed_exit(s, "r0", 2)          # bucket 1 ((1, 2])
        _timed_exit(s, "r0", 300)            # bucket 9 ((256, 512])
        row0 = s.resources.lookup("r0")
        before = np.asarray(s._state.rt_hist)[row0].copy()
        assert before[1] == 5 and before[9] == 1
        assert s.resources.evict_name("r0")
        s.entry("keepalive").exit()          # run the demote drain
        t.poll()                             # land the payload
        entry = t.cold.pop("r0")
        assert entry is not None and entry.rt_hist is not None
        assert np.array_equal(entry.rt_hist, before)
    finally:
        s.close()


def test_cold_entry_vector_round_trips_bit_exact(monkeypatch):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    s = _make(max_resources=32)
    try:
        t = s.tiering
        assert t.enabled
        for _ in range(5):
            _timed_exit(s, "r0", 2)
        _timed_exit(s, "r0", 300)
        row0 = s.resources.lookup("r0")
        before = np.asarray(s._state.rt_hist)[row0].copy()
        assert s.resources.evict_name("r0")
        s.entry("keepalive").exit()
        t.poll()
        assert "r0" in t.cold
        # re-intern: cold miss → promote inside the same entry call
        s.entry_batch(["r0"], acquire=[1])
        assert t.snapshot()["promoted"] >= 1
        row1 = s.resources.lookup("r0")
        after = np.asarray(s._state.rt_hist)[row1]
        # the promoted row carries every pre-demote count, plus the
        # promote call's own exit-free entry adds nothing
        assert np.array_equal(after, before)
        # and keeps counting from there
        _timed_exit(s, "r0", 2)
        assert np.asarray(s._state.rt_hist)[row1].sum() == before.sum() + 1
    finally:
        s.close()


# ---------------------------------------------------------------------------
# controller: interval tails from cumulative vectors
# ---------------------------------------------------------------------------

def test_tail_tracker_isolates_the_interval():
    tr = rh.ResourceTailTracker()
    hb = 32
    c = np.zeros(hb, np.int64)
    c[0] = 10_000                            # a fast epoch...
    assert dict(tr.update([("svc", c)]))["svc"] <= 1.0
    c2 = c.copy()
    c2[9] += 20                              # ...then a slow interval
    p99 = dict(tr.update([("svc", c2)]))["svc"]
    assert p99 > 256.0                       # the delta is ALL slow
    # the cumulative view still says fast: 20/10020 ≈ 0.2% < 1%
    assert float(rh.np_quantiles(c2[None])[0, -1]) <= 1.0
    # no new samples → no signal
    assert tr.update([("svc", c2)]) == ()
    # a shrinking vector (row invalidated + re-enrolled) resets baseline
    c3 = np.zeros(hb, np.int64)
    c3[2] = 4
    out = dict(tr.update([("svc", c3)]))
    assert 2.0 < out["svc"] <= 4.0


def test_tail_tracker_cap_evicts_stale_names():
    tr = rh.ResourceTailTracker(cap=4)
    c = np.zeros(32, np.int64)
    c[0] = 1
    for i in range(8):
        tr.update([(f"r{i}", c)])
    assert len(tr._prev) <= 5               # cap + the live name


def test_policy_prefers_tail_signal_over_mean():
    """The acceptance scenario the mean CANNOT pass: bimodal victim RT
    with mean ≈ 10 ms under a 100 ms bound but interval p99 ≈ 230 ms
    above it. The p99 signal trips the victim's tracker; the steady
    resource stays closed; and the SAME observations with only the mean
    signal provably decide nothing."""
    from sentinel_tpu.control import Degrade, Observation, OverloadPolicy, \
        PolicyConfig
    cfg = PolicyConfig(cooldown_ms=0, degrade_rt_ms=100.0,
                       degrade_bad_ticks=2, degrade_hold_ms=1000)

    def ob(ts, p99_pairs, mean_pairs):
        return Observation(ts_ms=ts, pass_per_s=100.0, block_per_s=0.0,
                           rt_avg_ms=10.0, p99_ms=0.0, queue_depth=0,
                           queue_max=0, resource_rt=mean_pairs,
                           resource_p99=p99_pairs)

    mean = (("victim", 10.5), ("steady", 0.6))       # both under bound
    tail = (("victim", 229.1), ("steady", 0.6))      # victim over bound
    pol = OverloadPolicy(cfg)
    assert pol.observe(ob(0, tail, mean)) == []
    assert pol.observe(ob(100, tail, mean)) == [Degrade("victim", "open")]
    # mean-only (hists disabled → resource_p99 empty): never trips
    pol2 = OverloadPolicy(cfg)
    for ts in range(0, 1000, 100):
        assert pol2.observe(ob(ts, (), mean)) == []


def test_control_loop_force_opens_slow_consumer(monkeypatch):
    """End-to-end slow-consumer episode against a real engine: bimodal
    victim traffic whose MEAN stays under the bound, tail over it — the
    tick must wire device histogram deltas into the policy, and drain
    must force the victim's real breaker while the steady resource
    keeps serving."""
    monkeypatch.setenv("SENTINEL_CONTROL_DEGRADE_RT_MS", "100")
    from sentinel_tpu.control import ControlLoop
    s = _make()
    try:
        s.load_degrade_rules([
            stpu.DegradeRule(resource="victim",
                             grade=stpu.GRADE_EXCEPTION_COUNT,
                             count=10_000, time_window=5),
            stpu.DegradeRule(resource="steady",
                             grade=stpu.GRADE_EXCEPTION_COUNT,
                             count=10_000, time_window=5)])
        ctl = ControlLoop(s, interval_ms=50)
        assert ctl.enabled and ctl.policy.cfg.degrade_rt_ms == 100.0
        # the tracker trips on the Nth consecutive bad tick; the breaker
        # is forced by that iteration's drain, so victim traffic never
        # has to thread a DegradeException
        for tick in range(ctl.policy.cfg.degrade_bad_ticks):
            for _ in range(40):
                _timed_exit(s, "victim", 1)
                _timed_exit(s, "steady", 1)
            for _ in range(2):
                _timed_exit(s, "victim", 200)
            assert s.telemetry.poll() == 1
            hot = {h["resource"]: h for h in s.telemetry.hot_entries()}
            # the mean signal itself is under the bound every tick
            assert float(hot["victim"].get("rt_ms", 0.0)) < 100.0
            assert hot["victim"]["rt_p99_ms"] > 100.0
            ctl.tick()
            ctl.drain()
        assert s.obs.counters.get(ck.CONTROL_TAIL_SIGNAL) >= 1
        assert s.obs.counters.get(ck.CONTROL_DEGRADE_ACTION) >= 1
        assert ctl.policy.snapshot()["degrade"].get("victim") == "open"
        with pytest.raises(stpu.DegradeException):
            s.entry("victim")                # breaker really forced
        with s.entry("steady"):
            pass                             # steady tenant unharmed
    finally:
        s.close()


# ---------------------------------------------------------------------------
# multihost: fleet merge (1-process identity path)
# ---------------------------------------------------------------------------

def test_aggregate_resource_hist_single_process():
    from sentinel_tpu.multihost.obs_agg import aggregate_resource_hist
    s = _make()
    try:
        for _ in range(50):
            _timed_exit(s, "api", 1)
        _timed_exit(s, "api", 60)
        s.telemetry.poll()
        agg = aggregate_resource_hist(s)
        assert agg["process_count"] == 1
        assert agg["hist_buckets"] == rh.DEFAULT_BUCKETS
        by_name = {h["resource"]: h for h in agg["hot"]}
        a = by_name["api"]
        assert a["hosts"] == 1 and a["total"] == 51
        vec = np.asarray(a["rt_hist"], np.int64)
        want = rh.np_quantiles(vec[None])[0]
        assert a["rt_p99_ms"] == round(float(want[2]), 3)
    finally:
        s.close()


def test_aggregate_resource_hist_merges_by_name():
    """The fleet merge itself, exercised host-side: two synthetic host
    payloads with an overlapping name must sum vectors and re-extract —
    the true fleet p99, not a per-host average."""
    from sentinel_tpu.multihost import obs_agg

    class _Tel:
        k = 4

        def __init__(self, entries):
            self._e = entries

        def hot_entries(self, k=None):
            return self._e

    class _Sn:
        def __init__(self, entries, hb):
            self.telemetry = _Tel(entries)
            from types import SimpleNamespace
            self.spec = SimpleNamespace(hist_buckets=hb)

    hb = 16
    fast = np.zeros(hb, np.int64)
    fast[0] = 95
    slow = np.zeros(hb, np.int64)
    slow[8] = 5
    names_a, hist_a = obs_agg._resource_hist_payload(
        _Sn([{"resource": "api", "rt_hist": fast.tolist()}], hb), 4, hb)
    names_b, hist_b = obs_agg._resource_hist_payload(
        _Sn([{"resource": "api", "rt_hist": slow.tolist()}], hb), 4, hb)
    assert hist_a[1, 0] == -1               # empty slots marked
    # merge exactly as aggregate_resource_hist does post-allgather
    merged = fast + slow
    q = rh.np_quantiles(merged[None])[0]
    assert float(q[2]) > 128.0              # fleet p99 sees host B's tail
    # host A alone would report a sub-ms p99 — averaging would too
    assert float(rh.np_quantiles(fast[None])[0, 2]) <= 1.0


def test_aggregate_resource_hist_disabled_is_empty(monkeypatch):
    monkeypatch.setenv(rh.RESOURCE_HIST_DISABLE_ENV, "1")
    from sentinel_tpu.multihost.obs_agg import aggregate_resource_hist
    s = _make()
    try:
        s.entry("api").exit()
        s.telemetry.poll()
        agg = aggregate_resource_hist(s)
        assert agg["hist_buckets"] == 0 and agg["hot"] == []
    finally:
        s.close()
