"""Observability layer (sentinel_tpu/obs/ — docs/OBSERVABILITY.md):

* span recorder lifecycle + deterministic sampling under the manual
  clock (virtual-time ns timestamps);
* log-histogram percentiles pinned by the interpolation formula;
* counter parity against the runtime's actual routing decisions (the
  ``split_fired`` count must equal the observed ``_decide_split_nowait``
  calls — same spy technique as test_split_dispatch.py);
* block-event log round trip through metrics/searcher.py;
* Sentinel.close() idempotency + no thread leak across open/close with
  the metric timer registered;
* Prometheus export families, heartbeat exporterPort, the ``obs``
  transport command, and the single-process multihost aggregation.
"""

import os
import threading

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import (
    OBS_DISABLE_ENV, TRACE_SAMPLE_ENV, RuntimeObs,
)
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.obs.eventlog import BlockEventLog
from sentinel_tpu.obs.hist import (
    BASE_NS, NUM_BUCKETS, LogHistogram, bucket_bounds_ns, bucket_index,
)
from sentinel_tpu.obs.spans import SpanRecorder


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
    stpu.FlowRule(resource="api", count=100.0),
    stpu.FlowRule(resource="api", count=3.0, limit_app="app-a"),
]


def mixed_batch(sph, rng, n=8192, origin_frac=0.1):
    """(resources, origins) for an entry batch that takes the split path:
    the scalar side stays above the 4096-row threshold and the origin
    side is non-empty."""
    sph.load_flow_rules(RULES)
    resources = ["api"] * n
    origins = ["app-a" if x else ""
               for x in (rng.random(n) < origin_frac)]
    return resources, origins


# ---------------------------------------------------------------- spans

def test_span_recorder_virtual_clock_lifecycle(clk):
    rec = SpanRecorder.for_clock(clk)
    tr = rec.maybe_trace()
    assert tr == 1                      # sample=1.0: first dispatch sampled
    t0 = rec.now_ns()
    clk.advance_ms(3)
    t1 = rec.now_ns()
    rec.record(tr, "entry.total", t0, t1, n=128, note="x")
    assert t1 - t0 == 3_000_000        # virtual ns follow the manual clock
    (span,) = rec.snapshot()
    assert span == {"trace": 1, "name": "entry.total",
                    "start_ns": t0, "end_ns": t1, "dur_ns": 3_000_000,
                    "thread": threading.get_ident(), "n": 128, "note": "x",
                    "id": 1, "parent": 0}
    assert rec.chain(tr) == [span]
    assert rec.last_trace_id() == 1
    # unsampled (trace 0) records are dropped without touching the ring
    rec.record(0, "noise", t0, t1)
    assert len(rec.snapshot()) == 1


def test_span_sampling_stride_is_deterministic(clk):
    rec = SpanRecorder.for_clock(clk, sample=0.25)   # stride 4
    ids = [rec.maybe_trace() for _ in range(12)]
    assert [bool(i) for i in ids] == [True, False, False, False] * 3
    assert [i for i in ids if i] == [1, 2, 3]        # fresh id per sample
    # rate 0 disables tracing entirely
    assert SpanRecorder.for_clock(clk, sample=0.0).maybe_trace() == 0


def test_span_recorder_close_is_idempotent(clk):
    rec = SpanRecorder.for_clock(clk)
    tr = rec.maybe_trace()
    rec.record(tr, "s", 0, 1)
    rec.close()
    rec.close()
    assert rec.snapshot() == []
    assert rec.maybe_trace() == 0      # disabled stays disabled
    rec.record(99, "after-close", 0, 1)
    assert rec.snapshot() == []


def test_ring_wraps_at_capacity(clk):
    rec = SpanRecorder(capacity=16, time_ns=lambda: 7)
    for i in range(40):
        rec.record(rec.maybe_trace(), f"s{i}", i, i + 1)
    spans = rec.snapshot()
    assert len(spans) == 16
    assert min(s["trace"] for s in spans) == 25   # oldest 24 overwritten


# ----------------------------------------------------------- histograms

def test_bucket_geometry():
    assert bucket_index(0) == 0
    assert bucket_index(BASE_NS) == 0
    assert bucket_index(BASE_NS + 1) == 1
    assert bucket_index(2 * BASE_NS) == 1
    assert bucket_index(2 * BASE_NS + 1) == 2
    assert bucket_index(1 << 62) == NUM_BUCKETS - 1
    bounds = bucket_bounds_ns()
    assert len(bounds) == NUM_BUCKETS
    assert bounds[0] == BASE_NS and bounds[1] == 2 * BASE_NS


def test_percentiles_interpolate_deterministically():
    h = LogHistogram()
    for _ in range(100):
        h.record(2048)                 # all in bucket 1: (1024, 2048]
    # rank r of 100 lands at lo + (hi-lo) * r/100 inside the bucket
    assert h.percentile(0.50) == pytest.approx(1024 + 1024 * 0.50)
    assert h.percentile(0.95) == pytest.approx(1024 + 1024 * 0.95)
    assert h.percentile(0.99) == pytest.approx(1024 + 1024 * 0.99)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum_ns"] == 100 * 2048
    assert snap["max_ns"] == 2048
    assert snap["buckets"][1] == 100
    assert snap["p95_ms"] == pytest.approx((1024 + 1024 * 0.95) / 1e6)


def test_percentiles_across_buckets_and_empty():
    h = LogHistogram()
    assert h.percentile(0.99) is None
    assert h.snapshot()["p50_ms"] is None
    for _ in range(90):
        h.record(512)                  # bucket 0: [0, 1024]
    for _ in range(10):
        h.record(4000)                 # bucket 2: (2048, 4096]
    # p50: rank 50 inside bucket 0 → 0 + 1024 * 50/90
    assert h.percentile(0.50) == pytest.approx(1024 * 50 / 90)
    # p95: rank 95 is the 5th of 10 samples in bucket 2
    assert h.percentile(0.95) == pytest.approx(2048 + 2048 * 5 / 10)


def test_histogram_merge_matches_union():
    a, b, u = LogHistogram(), LogHistogram(), LogHistogram()
    for v in (100, 5000, 70_000):
        a.record(v)
        u.record(v)
    for v in (800, 800, 9_000_000):
        b.record(v)
        u.record(v)
    a.merge(b)
    assert a.snapshot() == u.snapshot()
    # merge_counts folds a raw bucket vector (multihost payload)
    c = LogHistogram()
    sb = b.snapshot()
    c.merge_counts(sb["buckets"], sb["sum_ns"], sb["max_ns"])
    assert c.snapshot() == b.snapshot()


def test_last_bucket_percentile_clamps_to_max():
    h = LogHistogram()
    big = BASE_NS << 45                 # far past the last bucket bound
    h.record(big)
    assert h.percentile(0.99) <= big


# ------------------------------------------------- counters vs routing

def test_split_fired_counter_matches_actual_split_calls(clk):
    sph = make_sentinel(clk)
    rng = np.random.default_rng(3)
    resources, origins = mixed_batch(sph, rng)
    calls = []
    orig = sph._decide_split_nowait
    sph._decide_split_nowait = lambda *a, **k: (calls.append(1),
                                                orig(*a, **k))[1]
    for _ in range(3):
        sph.entry_batch(resources, origins=origins)
        clk.advance_ms(50)
    assert len(calls) == 3, "fixture no longer takes the split path"
    assert sph.obs.counters.get(ck.ROUTE_SPLIT) == len(calls)
    # entry→verdict histogram saw exactly one record per batch
    assert sph.obs.hist_entry.count == 3
    assert sph.obs.hist_dispatch.count == 3
    # the origin-scoped count=3 rule denied events → FlowException tally
    assert sph.obs.counters.get(
        ck.BLOCK_PREFIX + "FlowException") > 0
    sph.close()


def test_fast_and_scalar_route_counters(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules(RULES)
    # origin-free uniform batch below the split threshold → one fast or
    # scalar route per dispatch, never the split
    for _ in range(2):
        sph.entry_batch(["api"] * 64)
        clk.advance_ms(10)
    c = sph.obs.counters.snapshot()
    assert c.get(ck.ROUTE_SPLIT, 0) == 0
    assert (c.get(ck.ROUTE_SCALAR, 0) + c.get(ck.ROUTE_FAST, 0)
            + c.get(ck.ROUTE_FAST_OCCUPY, 0)) == 2
    sph.close()


def test_compile_cache_hit_miss_counters(clk):
    sph = make_sentinel(clk)
    sph.load_flow_rules(RULES)
    sph.entry_batch(["api"] * 64)
    c0 = sph.obs.counters.snapshot()
    assert c0.get(ck.CACHE_MISS, 0) >= 1       # first dispatch of the combo
    clk.advance_ms(10)
    sph.entry_batch(["api"] * 64)              # same (dec, B, flags) combo
    c1 = sph.obs.counters.snapshot()
    assert c1.get(ck.CACHE_HIT, 0) > c0.get(ck.CACHE_HIT, 0)
    assert c1.get(ck.CACHE_MISS, 0) == c0.get(ck.CACHE_MISS, 0)
    sph.close()


def test_obs_disable_env_turns_instrumentation_off(clk, monkeypatch):
    monkeypatch.setenv(OBS_DISABLE_ENV, "1")
    sph = make_sentinel(clk)
    assert not sph.obs.enabled
    sph.load_flow_rules(RULES)
    sph.entry_batch(["api"] * 64)
    assert sph.obs.counters.snapshot() == {}
    assert sph.obs.spans.snapshot() == []
    assert sph.obs.hist_entry.count == 0
    sph.close()


def test_trace_sample_env(clk, monkeypatch):
    monkeypatch.setenv(TRACE_SAMPLE_ENV, "0.5")
    obs = RuntimeObs(clock=clk)
    assert obs.sample == 0.5
    assert obs.spans._stride == 2


# -------------------------------------------------- span chain end-to-end

def test_batch_records_full_span_chain(clk):
    sph = make_sentinel(clk)
    rng = np.random.default_rng(5)
    resources, origins = mixed_batch(sph, rng)
    sph.entry_batch(resources, origins=origins)
    tr = sph.obs.spans.last_trace_id()
    assert tr > 0
    names = [s["name"] for s in sph.obs.spans.chain(tr)]
    for expected in ("entry.prep", "decide.split_decision",
                     "split.dispatch", "split.device", "entry.settle",
                     "entry.total"):
        assert expected in names, f"span chain missing {expected}: {names}"
    total = [s for s in sph.obs.spans.chain(tr)
             if s["name"] == "entry.total"]
    assert total[0]["n"] == len(resources)
    sph.close()


# ------------------------------------------------------ block-event log

def test_block_event_log_roundtrip_via_searcher(tmp_path):
    from sentinel_tpu.metrics.searcher import MetricSearcher

    log = BlockEventLog()
    base_name = log.configure(str(tmp_path), "appx")
    t = 1_785_000_000_000
    log.log(t, "api", int(stpu.BlockReason.FLOW),
            reason_name="FlowException", count=7)
    log.log(t + 1000, "api", int(stpu.BlockReason.DEGRADE),
            reason_name="DegradeException", origin="app-a", count=2)
    assert log.flush() == 2
    found = MetricSearcher(str(tmp_path), base_name).find(
        t - 1000, t + 5000)
    assert len(found) == 2
    by_res = {n.resource: n for n in found}
    assert by_res["api"].block_qps == 7
    assert by_res["api"].classification == int(stpu.BlockReason.FLOW)
    # origin rides as resource@origin (survives the writer's sanitizer)
    assert by_res["api@app-a"].block_qps == 2
    assert by_res["api@app-a"].classification == int(
        stpu.BlockReason.DEGRADE)
    # identity search still hits the origin-less record exactly
    assert len(MetricSearcher(str(tmp_path), base_name).find(
        t - 1000, t + 5000, identity="api")) == 1
    log.close()
    log.close()                         # idempotent


def test_block_events_buffer_before_configure(clk):
    sph = make_sentinel(clk)
    rng = np.random.default_rng(5)
    resources, origins = mixed_batch(sph, rng)
    sph.entry_batch(resources, origins=origins)
    recent = sph.obs.block_events.snapshot()
    assert recent, "denials produced no sampled block events"
    ev = recent[-1]
    assert ev["resource"] == "api"
    assert ev["reason_name"] == "FlowException"
    assert ev["count"] >= 1
    # no writer attached → flush is a no-op, nothing crashes
    assert sph.obs.block_events.flush() == 0
    sph.close()


# ------------------------------------------- shutdown / thread hygiene

def test_close_is_idempotent_and_leaks_no_threads(clk, tmp_path):
    from sentinel_tpu.metrics.timer import MetricTimerListener

    def cycle():
        sph = make_sentinel(clk, app_name="leakcheck",
                            metric_log_dir=str(tmp_path))
        timer = MetricTimerListener(sph)
        timer.start()
        sph.load_flow_rules(RULES)
        sph.entry_batch(["api"] * 32)
        sph.close()
        sph.close()                     # second close is a no-op
        assert timer._thread is None    # shutdown hook stopped the daemon

    cycle()                             # warm jax's own worker pools first
    baseline = threading.active_count()
    for _ in range(3):
        cycle()
    for t in threading.enumerate():
        assert not t.name.startswith("sentinel-metric-timer")
    assert threading.active_count() <= baseline


def test_context_manager_closes(clk):
    with make_sentinel(clk) as sph:
        sph.load_flow_rules(RULES)
        sph.entry_batch(["api"] * 16)
    assert not sph.obs.enabled


# ------------------------------------------------------------ exporters

def test_prometheus_obs_families(clk):
    from prometheus_client import CollectorRegistry, generate_latest
    from sentinel_tpu.metrics.exporter import PrometheusExporter

    sph = make_sentinel(clk)
    rng = np.random.default_rng(9)
    resources, origins = mixed_batch(sph, rng)
    registry = CollectorRegistry()
    exporter = PrometheusExporter(sph, registry=registry)
    sph.entry_batch(resources, origins=origins)
    clk.advance_ms(20)
    sph.entry_batch(resources, origins=origins)
    text = generate_latest(registry).decode()
    assert 'sentinel_split_route_total{route="split_fired"} 2.0' in text
    assert "sentinel_compile_cache_hits_total" in text
    assert "sentinel_rt_p99_ms" in text
    assert 'sentinel_rt_quantile_ms{quantile="0.99"}' in text
    assert 'sentinel_block_reason_total{reason="FlowException"}' in text
    sph.close()                         # unregisters via shutdown hook
    text2 = generate_latest(registry).decode()
    assert "sentinel_split_route_total" not in text2
    exporter.close()                    # idempotent


def test_heartbeat_advertises_exporter_port():
    from sentinel_tpu.transport.heartbeat import HeartbeatSender

    hb = HeartbeatSender("127.0.0.1:9999", app_name="a",
                         exporter_port=9464)
    assert hb.message()["exporterPort"] == "9464"
    hb2 = HeartbeatSender("127.0.0.1:9999", app_name="a")
    assert "exporterPort" not in hb2.message()


def test_obs_transport_command(clk):
    from sentinel_tpu.transport.command import CommandCenter, CommandRequest
    from sentinel_tpu.transport.handlers import register_default_handlers
    import json

    sph = make_sentinel(clk)
    rng = np.random.default_rng(13)
    resources, origins = mixed_batch(sph, rng)
    sph.entry_batch(resources, origins=origins)
    center = CommandCenter()
    register_default_handlers(center, sph)
    resp = center.handle("obs", CommandRequest())
    assert resp.success
    payload = json.loads(resp.result)
    assert payload["enabled"]
    assert payload["counters"][ck.ROUTE_SPLIT] == 1
    assert payload["hist"]["entry_to_verdict"]["count"] == 1
    assert payload["spans"]
    tr = payload["spans"][-1]["trace"]
    resp2 = center.handle("obs", CommandRequest(
        parameters={"trace": str(tr)}))
    chain = json.loads(resp2.result)["trace"]
    assert chain and all(s["trace"] == tr for s in chain)
    assert not center.handle(
        "obs", CommandRequest(parameters={"spans": "zap"})).success
    sph.close()


# ------------------------------------------------------------ multihost

def test_multihost_counter_aggregation_single_process(clk):
    from sentinel_tpu.multihost.obs_agg import aggregate_counters

    sph = make_sentinel(clk)
    rng = np.random.default_rng(17)
    resources, origins = mixed_batch(sph, rng)
    sph.entry_batch(resources, origins=origins)
    agg = aggregate_counters(sph)
    assert agg["process_count"] == 1
    assert agg["per_process"][0] == agg["total"]
    local = sph.obs.counters.snapshot()
    for key in ck.CATALOG:
        assert agg["total"].get(key, 0) == local.get(key, 0)
    sph.close()


def test_catalog_vector_roundtrip():
    counts = {ck.ROUTE_SPLIT: 5, ck.CACHE_HIT: 2,
              ck.BLOCK_PREFIX + "FlowException": 9}
    vec = ck.catalog_vector(counts)
    assert vec.dtype == np.int64 and len(vec) == len(ck.CATALOG)
    back = ck.vector_counts(vec)
    for k, v in counts.items():
        assert back[k] == v
    # newer-peer vectors (extra trailing keys) aggregate on the prefix
    longer = np.concatenate([vec, np.array([42], np.int64)])
    assert ck.vector_counts(longer) == back


def test_catalog_is_append_only_with_pr36_keys_last():
    """The multihost allgather aggregates CATALOG by POSITION (prefix
    compatibility with older peers), so the catalog may only ever grow at
    the tail. Pin the newest (``gc.gen2`` / ``gc.gen2_us``, then ``tier.tick`` / ``tier.tick_estimate``)
    keys to the end, with the PR 35 ``verdict.*`` / ``breaker.*``, the PR 34 ``tier.materialized``, the PR 33 tier first-sight / inline-landing, PR 32 batch-dedup, PR 26 cluster-server cycle, round-20 resource-histogram, round-17
    overload-controller, round-16 single-dispatch, round-15 tiering,
    round-12 telemetry/exporter, round-11 tune, round-10 sortfree and
    round-9 mesh keys immediately above them — an insertion above any
    group (or a re-ordering) would silently mis-attribute every counter
    on a mixed-version fleet."""
    assert ck.CATALOG[-2:] == (ck.GC_GEN2, ck.GC_GEN2_US) == (
        "gc.gen2", "gc.gen2_us")
    catalog = ck.CATALOG[:-2]
    assert catalog[-2:] == (ck.TIER_TICK, ck.TIER_TICK_ESTIMATE) == (
        "tier.tick", "tier.tick_estimate")
    catalog = catalog[:-2]
    assert catalog[-7:] == (
        ck.VERDICT_PACED, ck.VERDICT_PASSED_NOW, ck.BREAKER_SEEN_OPEN,
        ck.BREAKER_SEEN_CLOSED, ck.BREAKER_OPENED, ck.BREAKER_HALF_OPENED,
        ck.BREAKER_CLOSED) == (
        "verdict.paced", "verdict.passed_now", "breaker.seen_open",
        "breaker.seen_closed", "breaker.opened", "breaker.half_opened",
        "breaker.closed")
    catalog = catalog[:-7]
    assert catalog[-1] == ck.TIER_MATERIALIZED == "tier.materialized"
    assert catalog[-3:-1] == (ck.TIER_FIRST_SIGHT, ck.TIER_LAND_INLINE) \
        == ("tier.first_sight", "tier.land_inline")
    catalog = catalog[:-3]
    assert catalog[-2:] == (ck.INTERN_NAMES, ck.INTERN_DISTINCT)
    assert (ck.INTERN_NAMES, ck.INTERN_DISTINCT) == (
        "intern.names", "intern.distinct")
    assert catalog[-5:-2] == (ck.CLUSTER_SERVER_CYCLES,
                                 ck.CLUSTER_SERVER_TAKEN,
                                 ck.CLUSTER_SERVER_QUEUE_WAIT_US)
    assert catalog[-7:-5] == (ck.TELEMETRY_HIST_TICK,
                                 ck.CONTROL_TAIL_SIGNAL)
    assert catalog[-12:-7] == (ck.CONTROL_TICK, ck.CONTROL_SHED_ACTION,
                                  ck.CONTROL_RETUNE_ACTION,
                                  ck.CONTROL_DEGRADE_ACTION,
                                  ck.CONTROL_DROPPED)
    assert catalog[-14:-12] == (ck.PIPE_DISPATCH,
                                   ck.ROUTE_SINGLE_DISPATCH)
    assert catalog[-19:-14] == (ck.TIER_HOT_HIT, ck.TIER_COLD_MISS,
                                   ck.TIER_PROMOTED, ck.TIER_DEMOTED,
                                   ck.TIER_SKETCH_OVERFLOW)
    assert catalog[-22:-19] == (ck.TELEMETRY_TICK, ck.TELEMETRY_DROP,
                                   ck.EXPORTER_LABEL_OVERFLOW)
    assert catalog[-27:-22] == (ck.TUNE_LOADED, ck.TUNE_FALLBACK,
                                   ck.TUNE_KNOB_REJECTED, ck.TUNE_TRIAL,
                                   ck.TUNE_PARITY_FAIL)
    assert catalog[-29:-27] == (ck.ROUTE_SORTFREE, ck.SORTFREE_OVERFLOW)
    assert catalog[-31:-29] == (ck.ROUTE_MESHED, ck.PIPE_MESHED)
    assert ck.CLUSTER_SERVER_CYCLES == "cluster.server.cycles"
    assert ck.CLUSTER_SERVER_TAKEN == "cluster.server.taken"
    assert ck.CLUSTER_SERVER_QUEUE_WAIT_US == "cluster.server.queue_wait_us"
    assert ck.TELEMETRY_HIST_TICK == "telemetry.hist_tick"
    assert ck.CONTROL_TAIL_SIGNAL == "control.tail_signal"
    assert ck.CONTROL_TICK == "control.tick"
    assert ck.CONTROL_SHED_ACTION == "control.action.shed_rate"
    assert ck.CONTROL_RETUNE_ACTION == "control.action.retune_batcher"
    assert ck.CONTROL_DEGRADE_ACTION == "control.action.degrade"
    assert ck.CONTROL_DROPPED == "control.admission_dropped"
    assert ck.PIPE_DISPATCH == "pipeline.dispatches"
    assert ck.ROUTE_SINGLE_DISPATCH == "split_route.single_dispatch"
    assert ck.TIER_HOT_HIT == "tier.hot_hit"
    assert ck.TIER_COLD_MISS == "tier.cold_miss"
    assert ck.TIER_PROMOTED == "tier.promoted"
    assert ck.TIER_DEMOTED == "tier.demoted"
    assert ck.TIER_SKETCH_OVERFLOW == "tier.sketch_overflow"
    assert ck.TELEMETRY_TICK == "telemetry.tick"
    assert ck.TELEMETRY_DROP == "telemetry.readback_drop"
    assert ck.EXPORTER_LABEL_OVERFLOW == "exporter.label_overflow"
    assert ck.ROUTE_SORTFREE == "split_route.sortfree"
    assert ck.SORTFREE_OVERFLOW == "sortfree.bucket_overflow"
    assert ck.ROUTE_MESHED == "split_route.meshed"
    assert ck.PIPE_MESHED == "pipeline.meshed_dispatch"
    assert ck.TUNE_LOADED == "tune.config_loaded"
    assert ck.TUNE_KNOB_REJECTED == "tune.knob_rejected"
    assert len(ck.CATALOG) == len(set(ck.CATALOG))
