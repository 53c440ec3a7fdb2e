"""Request-scoped tracing (PR 8 — docs/OBSERVABILITY.md "Request
tracing"):

* causal-link closure semantics on the bare recorder (fan-in reaches
  the batch, fan-out expands only from the root — sibling requests
  stay out of each other's chains);
* trace-id threading through the DispatchPipeline into the device
  spans, on the split route;
* the full request lifecycle chain through the real AdaptiveBatcher
  (enqueue → flush → pipeline → device → settle) with per-request
  fan-out links;
* the SLO flight recorder: an induced deadline miss pins the offending
  chain, rate limiting, and the ``<app>-trace`` persistence round trip
  through MetricWriter/MetricSearcher (``load_pinned``);
* Chrome-trace-event export: duration events + flow-arrow pairs that
  survive ``json.loads``;
* the ``trace`` transport command, the ``obs.span_ring_wrap`` counter,
  and the CATALOG↔Prometheus coverage walk (every fixed counter key
  must reach some exported family).

All quick-tier, CPU; virtual-time policy values ride the ManualClock.
"""

import asyncio
import json
import threading

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.frontend.batcher import AdaptiveBatcher
from sentinel_tpu.obs import RuntimeObs
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.obs import traceexport
from sentinel_tpu.obs.flight import (MAX_CHAIN_SPANS, MAX_WINDOW_ROOTS,
                                     RECENT_CAP, FlightRecorder,
                                     load_pinned)
from sentinel_tpu.obs.spans import LINK_FLUSH, LINK_VERDICT, SpanRecorder

pytestmark = pytest.mark.quick

T0 = 1_785_000_000_000


@pytest.fixture
def clk():
    return ManualClock(start_ms=T0)


def make(clk, **over):
    kw = dict(max_resources=64, max_origins=32, max_flow_rules=32,
              max_degrade_rules=16, max_authority_rules=16,
              minute_enabled=True)
    kw.update(over)
    return stpu.Sentinel(config=stpu.load_config(**kw), clock=clk)


# ---------------------------------------------------------------------------
# causal closure on the bare recorder
# ---------------------------------------------------------------------------

def test_causal_closure_isolates_siblings(clk):
    rec = SpanRecorder.for_clock(clk)
    req_a, req_b, batch = rec.mint(), rec.mint(), rec.mint()
    ns = rec.now_ns()
    rec.record(req_a, "frontend.enqueue", ns, ns)
    rec.record(req_b, "frontend.enqueue", ns, ns)
    rec.link(req_a, batch, LINK_FLUSH)
    rec.link(req_b, batch, LINK_FLUSH)
    rec.record(batch, "frontend.flush", ns, ns, n=2)
    rec.link(batch, req_a, LINK_VERDICT)
    rec.link(batch, req_b, LINK_VERDICT)
    rec.record(req_a, "frontend.settle", ns, ns)
    rec.record(req_b, "frontend.settle", ns, ns)

    # request root: reaches its batch, NOT the sibling request
    ca = rec.causal(req_a)
    traces = {s["trace"] for s in ca["spans"]}
    assert traces == {req_a, batch}
    assert {(ln["src"], ln["dst"]) for ln in ca["links"]} == {
        (req_a, batch), (batch, req_a)}

    # batch root: verdict fan-out expands to EVERY settled request
    cb = rec.causal(batch)
    assert {s["trace"] for s in cb["spans"]} == {req_a, req_b, batch}
    rec.close()


def test_mint_bypasses_sampling_stride(clk):
    rec = SpanRecorder.for_clock(clk, sample=0.01)
    assert rec.maybe_trace() > 0          # seq 0 is sampled
    assert rec.maybe_trace() == 0         # seq 1 is not
    assert rec.mint() > 0                 # mint never consults the stride
    rec.enabled = False
    assert rec.mint() == 0
    rec.close()


# ---------------------------------------------------------------------------
# trace-id threading through the pipeline into the device spans
# ---------------------------------------------------------------------------

def test_pipeline_threads_trace_through_split_route(clk):
    sph = make(clk, host_fast_path=False)
    sph.load_flow_rules([
        stpu.FlowRule(resource="api", count=1e9),
        stpu.FlowRule(resource="api", count=1e9, limit_app="app-a"),
    ])
    rng = np.random.default_rng(3)
    n = 8192                    # scalar side above the 4096 split threshold
    resources = ["api"] * n
    origins = ["app-a" if x else "" for x in (rng.random(n) < 0.1)]
    pipe = stpu.DispatchPipeline(sph, depth=2)
    tr = sph.obs.spans.mint()
    pipe.submit(resources, origins=origins, trace_id=tr).result()
    names = [s["name"] for s in sph.obs.spans.chain(tr)]
    for expected in ("pipeline.enqueue", "entry.prep",
                     "decide.split_decision", "split.dispatch",
                     "split.device", "pipeline.settle"):
        assert expected in names, f"chain missing {expected}: {names}"
    assert all(s["trace"] == tr for s in sph.obs.spans.chain(tr))
    sph.close()


# ---------------------------------------------------------------------------
# the full lifecycle through the real front end
# ---------------------------------------------------------------------------

def test_request_chain_end_to_end_through_batcher(clk):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=1e9)])

    async def run():
        b = AdaptiveBatcher(sph, batch_max=4, deadline_ms=10_000,
                            idle_ms=60_000)
        verdicts = await asyncio.gather(
            *(b.submit("api") for _ in range(4)))   # full-batch flush
        b.close()
        return verdicts

    verdicts = asyncio.run(run())
    assert all(v.allow for v in verdicts)
    ids = [v.trace_id for v in verdicts]
    assert all(ids) and len(set(ids)) == 4   # flight tier mints per request

    va = sph.obs.spans.causal(ids[0])
    names = [s["name"] for s in va["spans"]]
    for expected in ("frontend.enqueue", "frontend.flush",
                     "pipeline.enqueue", "entry.prep", "pipeline.settle",
                     "frontend.settle"):
        assert expected in names, f"lifecycle missing {expected}: {names}"
    # sibling isolation: request 0's closure holds none of 1..3's spans
    traces = {s["trace"] for s in va["spans"]}
    assert traces.isdisjoint(ids[1:])
    # the batch id is whatever the flush edge fanned into
    batch_tr = next(ln["dst"] for ln in va["links"]
                    if ln["kind"] == LINK_FLUSH)
    # batch root fans out to all four requests
    fan = {s["trace"] for s in sph.obs.spans.causal(batch_tr)["spans"]}
    assert set(ids) <= fan
    sph.close()


# ---------------------------------------------------------------------------
# flight recorder: induced deadline miss → pinned + persisted chain
# ---------------------------------------------------------------------------

def test_flight_pins_induced_deadline_miss(clk, tmp_path):
    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=1e9)])
    sph.obs.flight.configure(str(tmp_path), "traceapp")

    async def run():
        b = AdaptiveBatcher(sph, batch_max=8, deadline_ms=10_000,
                            budget_ms=0, idle_ms=25)
        task = asyncio.ensure_future(b.submit("api", deadline_ms=5))
        for _ in range(4):                 # let submit reach its future
            await asyncio.sleep(0)
        assert b.pending == 1
        clk.advance_ms(60_000)             # blow WAY past the 5 ms budget
        v = await task
        b.close()
        return v

    v = asyncio.run(run())
    assert v.allow and v.trace_id > 0
    rec = sph.obs.flight.pinned(v.trace_id)
    assert rec is not None and rec["kind"] == "deadline_miss"
    assert rec["worst_ms"] >= 59_000
    names = {s["name"] for s in rec["spans"]}
    assert {"frontend.enqueue", "frontend.flush",
            "frontend.settle"} <= names
    assert any(ln["kind"] == LINK_FLUSH for ln in rec["links"])
    assert sph.obs.counters.get(ck.FLIGHT_PINNED) == 1
    assert sph.obs.counters.get(
        ck.FLIGHT_TRIGGER_PREFIX + "deadline_miss") == 1
    # per-kind rate limit: a second miss inside the window pins nothing
    assert not sph.obs.flight.trigger("deadline_miss", root=v.trace_id)

    # persistence round trip: flush → MetricSearcher read-back parses
    assert sph.obs.flight.flush() == 1
    loaded = load_pinned(str(tmp_path), "traceapp")
    assert len(loaded) == 1
    assert loaded[0]["root"] == v.trace_id
    assert {s["name"] for s in loaded[0]["spans"]} == names
    sph.close()                            # idempotent writer close


def test_flight_rootless_trigger_pins_window_and_payload(clk):
    obs = RuntimeObs(clock=clk)
    tr = obs.spans.mint()
    ns = obs.spans.now_ns()
    obs.spans.record(tr, "frontend.enqueue", ns, ns)
    assert obs.flight.trigger("block_burst", note="blocks_1s>=512")
    recs = obs.flight.snapshot(full=True)
    assert recs and recs[-1]["root"] == tr     # retro window found it
    # payload() carries the metadata view for the dashboard
    meta = obs.payload()["flight"]
    assert meta["active"] and meta["pinned"][-1]["kind"] == "block_burst"
    obs.close()


# ---------------------------------------------------------------------------
# a pin costs what it keeps, and keeps what the snapshot-then-filter
# closure kept: the reference below is that closure, as it was written
# ---------------------------------------------------------------------------

def _ref_snapshot(rec):
    with rec._rings_lock:
        rings = list(rec._rings)
    spans = []
    for ring in rings:
        spans.extend(list(ring.buf))
    spans.sort(key=lambda s: (s[0], s[2]))
    return [{"trace": s[0], "name": s[1], "start_ns": s[2],
             "end_ns": s[3], "dur_ns": s[3] - s[2], "thread": s[4],
             "n": s[5], "note": s[6], "id": s[7], "parent": s[8]}
            for s in spans]


def _ref_causal(rec, trace_id):
    with rec._rings_lock:
        link_rings = list(rec._link_rings)
    raw = []
    for ring in link_rings:
        raw.extend(list(ring.buf))
    ids = {int(trace_id)}
    changed = True
    while changed:
        changed = False
        for src, dst, kind, _ts in raw:
            if (src in ids and dst not in ids
                    and (kind != LINK_VERDICT or src == trace_id)):
                ids.add(dst)
                changed = True
    spans = [s for s in _ref_snapshot(rec) if s["trace"] in ids]
    spans.sort(key=lambda s: s["start_ns"])
    links = [{"src": e[0], "dst": e[1], "kind": e[2], "ts_ns": e[3]}
             for e in sorted(raw, key=lambda e: e[3])
             if e[0] in ids and e[1] in ids]
    return {"root": int(trace_id), "spans": spans, "links": links}


def _ref_pins(obs, root):
    """What one trigger pinned, by the reference: (root, spans, links,
    truncated) per pinned chain."""
    if root:
        roots = [root]
    else:
        cutoff = obs.spans.now_ns() - int(obs.flight.window_ms * 1e6)
        roots = sorted({s["trace"] for s in _ref_snapshot(obs.spans)
                        if s["start_ns"] >= cutoff},
                       reverse=True)[:MAX_WINDOW_ROOTS]
    out = []
    for r in roots:
        c = _ref_causal(obs.spans, r)
        if c["spans"]:
            out.append((r, c["spans"][:MAX_CHAIN_SPANS], c["links"],
                        len(c["spans"]) > MAX_CHAIN_SPANS))
    return out


def _run_in_thread(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()


def _busy_recorder(clk):
    """A recorder as a serving process leaves it: thousands of unrelated
    spans over six threads, a batch ring and a request ring that wrapped,
    links both ways (fan-in, fan-out, a cycle), one chain longer than a
    pin keeps, and traces that straddle the retro window's edge."""
    obs = RuntimeObs(clock=clk)
    rec = obs.spans
    now = rec.now_ns()
    ms = 1_000_000

    def traffic(k):
        def body():
            for i in range(1200):
                end = now - 3000 * ms + i * 2 * ms + k
                rec.record(rec.mint(), f"work.{k}", end - 4 * ms, end, n=i)
        return body

    for k in range(6):
        _run_in_thread(traffic(k))

    # one thread whose batch ring (8192) and request ring (2048) wrap;
    # equal starts across traces test the (start, trace) order
    def wrapping():
        for i in range(9000):
            end = now - 2900 * ms + i * 320_000
            rec.record(rec.mint(), "wrap.batch", end - ms, end)
        for i in range(2500):
            end = now - 2500 * ms + i * ms
            rec.record(rec.mint(), "wrap.req", end - 3 * ms, end,
                       request=True)
    _run_in_thread(wrapping)

    # the chains, minted last so that they are the window's newest
    def chains():
        t = now - 1500 * ms
        for r in (req_a, req_b):
            rec.record(r, "frontend.enqueue", t, t + ms, request=True)
            rec.link(r, batch, LINK_FLUSH)
        for i in range(250):            # longer than MAX_CHAIN_SPANS
            rec.record(batch, f"step.{i % 7}", t + ms, t + 2 * ms + i)
        rec.link(batch, echo, LINK_FLUSH)       # onward, and back: a cycle
        rec.link(echo, batch, LINK_FLUSH)
        rec.record(echo, "echo", t + 2 * ms, t + 3 * ms)
        for r in (req_a, req_b):
            rec.link(batch, r, LINK_VERDICT)
            rec.record(r, "frontend.settle", t, t + 4 * ms, request=True)
        rec.link(batch2, req_a, LINK_VERDICT)   # a second fan-out
        rec.record(batch2, "frontend.flush", t + 5 * ms, t + 6 * ms)
        # the newest trace of all started before the window: no root
        rec.record(straddle, "straddle", now - 2001 * ms, now)

    req_a, req_b, batch, batch2, echo, straddle = (rec.mint()
                                                   for _ in range(6))
    _run_in_thread(chains)
    return obs, (req_a, req_b, batch, batch2, echo, straddle)


def _pinned_since(obs, k):
    recs = obs.flight.snapshot(limit=RECENT_CAP, full=True)
    return [(r["root"], r["spans"], r["links"], r["truncated"])
            for r in recs[len(recs) - k:]] if k else []


def test_pins_equal_the_snapshot_closure_rooted_and_rootless(clk):
    obs, (req_a, req_b, batch, batch2, echo, straddle) = \
        _busy_recorder(clk)
    assert obs.counters.get(ck.SPAN_RING_WRAP) > 0     # rings did wrap
    cutoff = obs.spans.now_ns() - int(obs.flight.window_ms * 1e6)
    assert obs.spans.view().traces_since(cutoff) == {
        s["trace"] for s in _ref_snapshot(obs.spans)
        if s["start_ns"] >= cutoff}
    want = _ref_pins(obs, 0)
    # the newest traces with a span that STARTED in the window: not
    # `straddle`, whose span began before it
    assert [w[0] for w in want] == [echo, batch2, batch, req_b]
    assert obs.flight.trigger("block_burst", note="blocks_1s>=512")
    assert _pinned_since(obs, len(want)) == want
    for root in (req_a, batch, batch2, echo, req_b, 10 ** 9):
        assert obs.spans.causal(root) == _ref_causal(obs.spans, root)
        want = _ref_pins(obs, root)
        got = obs.flight.trigger("deadline_miss", root=root, force=True)
        assert got == bool(want)
        assert _pinned_since(obs, len(want)) == want
    assert _ref_pins(obs, batch)[0][3]                  # truncated
    obs.close()


def test_a_pin_never_takes_a_snapshot(clk, monkeypatch):
    obs, (req_a, *_rest) = _busy_recorder(clk)

    def boom(*a, **k):
        raise AssertionError("a pin walked every span of every ring")

    monkeypatch.setattr(SpanRecorder, "snapshot", boom)
    assert obs.flight.trigger("deadline_miss", root=req_a)
    assert obs.flight.trigger("block_burst")
    assert obs.counters.get(ck.FLIGHT_PINNED) == 1 + MAX_WINDOW_ROOTS
    obs.close()


def test_the_pin_is_a_phase_that_counts_what_it_pinned(clk):
    obs, (req_a, req_b, batch, *_rest) = _busy_recorder(clk)
    assert obs.flight.trigger("deadline_miss", root=batch)
    (rec,) = _pinned_since(obs, 1)
    (pin,) = [s for s in obs.spans.snapshot() if s["name"] == "flight.pin"]
    assert pin["n"] == len(rec[1]) == MAX_CHAIN_SPANS
    assert pin["note"] == "deadline_miss" and pin["trace"] == batch
    # rate-limited: no second pin, no second phase
    assert not obs.flight.trigger("deadline_miss", root=req_a)
    assert [s["name"] for s in obs.spans.snapshot()].count("flight.pin") == 1
    obs.close()


def test_flight_disable_env(clk, monkeypatch):
    monkeypatch.setenv("SENTINEL_FLIGHT_DISABLE", "1")
    obs = RuntimeObs(clock=clk)
    assert not obs.flight.active
    assert not obs.flight.trigger("deadline_miss", root=1)
    obs.close()


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_json_roundtrip(clk):
    rec = SpanRecorder.for_clock(clk)
    req, batch = rec.mint(), rec.mint()
    t = rec.now_ns()
    rec.record(req, "frontend.enqueue", t, t + 2_000_000)
    rec.link(req, batch, LINK_FLUSH)
    clk.advance_ms(5)
    t2 = rec.now_ns()
    rec.record(batch, "frontend.flush", t2, t2 + 1_000_000, n=3)

    doc = json.loads(traceexport.dumps(
        traceexport.export_chain(rec, req)))
    events = doc["traceEvents"]
    assert doc["otherData"]["root"] == req
    assert doc["displayTimeUnit"] == "ms"
    x = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"frontend.enqueue",
                                      "frontend.flush"}
    enq = next(e for e in x if e["name"] == "frontend.enqueue")
    assert enq["ts"] == t / 1000.0 and enq["dur"] == 2000.0   # µs
    # one flow pair per link, matching ids, finish bound to enclosing
    starts = [e for e in events if e["ph"] == "s"]
    finishes = [e for e in events if e["ph"] == "f"]
    assert len(starts) == len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    assert finishes[0]["bp"] == "e"
    assert starts[0]["name"] == "link." + LINK_FLUSH
    rec.close()


def test_chrome_trace_tolerates_zero_duration_manual_spans(clk):
    rec = SpanRecorder.for_clock(clk)
    tr = rec.mint()
    ns = rec.now_ns()
    rec.record(tr, "instant", ns, ns)          # ManualClock: start == end
    doc = traceexport.export_chain(rec, tr)
    assert doc["traceEvents"][0]["dur"] > 0    # still a visible slice
    rec.close()


# ---------------------------------------------------------------------------
# transport command + dashboard surface
# ---------------------------------------------------------------------------

def test_trace_transport_command(clk):
    from sentinel_tpu.transport.command import CommandCenter, CommandRequest
    from sentinel_tpu.transport.handlers import register_default_handlers

    sph = make(clk)
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=1e9)])
    tr = sph.obs.spans.mint()
    ns = sph.obs.spans.now_ns()
    sph.obs.spans.record(tr, "frontend.enqueue", ns, ns)
    sph.obs.flight.trigger("deadline_miss", root=tr, worst_ms=7.0)
    center = CommandCenter()
    register_default_handlers(center, sph)

    resp = center.handle("trace", CommandRequest())
    assert resp.success
    pinned = json.loads(resp.result)["pinned"]
    assert pinned and pinned[-1]["root"] == tr

    resp2 = center.handle("trace", CommandRequest(
        parameters={"id": str(tr)}))
    doc = json.loads(resp2.result)
    assert doc["otherData"]["root"] == tr
    assert doc["otherData"]["kind"] == "deadline_miss"   # pinned record won
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    assert not center.handle(
        "trace", CommandRequest(parameters={"id": "zap"})).success
    sph.close()


# ---------------------------------------------------------------------------
# counters: ring-wrap signal + CATALOG↔Prometheus coverage
# ---------------------------------------------------------------------------

def test_span_ring_wrap_counter(clk):
    obs = RuntimeObs(clock=clk)
    tr = obs.spans.mint()
    cap = obs.spans.capacity
    for _ in range(cap + 3):
        obs.spans.record(tr, "x", 0, 1)
    assert obs.counters.get(ck.SPAN_RING_WRAP) == 3
    obs.close()


def test_every_catalog_key_reaches_prometheus(clk):
    """Satellite guard: a key appended to the fixed CATALOG without a
    matching exporter family must fail HERE, not become a silent
    observability gap. Each key gets a distinct sentinel value; every
    value must surface in some scraped sample."""
    from prometheus_client import CollectorRegistry
    from sentinel_tpu.metrics.exporter import PrometheusExporter

    sph = make(clk)
    registry = CollectorRegistry()
    PrometheusExporter(sph, registry=registry)
    want = {}
    for i, key in enumerate(ck.CATALOG):
        sph.obs.counters.add(key, 100_000 + i)
        want[key] = float(100_000 + i)
    exported = {s.value for fam in registry.collect() for s in fam.samples}
    for key, val in want.items():
        assert val in exported, (
            f"CATALOG key {key!r} (sentinel value {val}) reached no "
            f"Prometheus family — add an export in metrics/exporter.py")
    sph.close()
