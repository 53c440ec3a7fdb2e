"""Device-resident hot-resource telemetry (PR 12 — obs/telemetry.py,
docs/OBSERVABILITY.md "Hot-resource telemetry"):

* the sharded device top-K is EXACT: bit-equal to a host numpy
  recompute (stable argsort over the same rolling load, ENTRY row
  masked) on seeded Zipf traffic over an 8-virtual-device mesh, and on
  the single-device path;
* the per-second timeline ring wraps correctly past RING_SLOTS and the
  host tail mirrors the appended seconds;
* ManualClock determinism: two engines fed the same seeded stream land
  identical hot views;
* the readback-drop path: ticks beyond PENDING_MAX un-drained
  readbacks are dropped and counted (``telemetry.readback_drop``);
* the ``<app>-metric`` persistence round trip through
  MetricWriter/MetricSearcher, the ``topk`` transport command, the env
  knobs, and the flight recorder's pinned hot-set snapshot.

All quick-tier, CPU; virtual time rides the ManualClock.
"""

import json

import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.core.errors import BlockException
from sentinel_tpu.core.registry import ENTRY_NODE_ROW
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.obs.telemetry import (
    PENDING_MAX, TELEMETRY_DISABLE_ENV, TELEMETRY_K_ENV,
)
from sentinel_tpu.parallel.local_shard import local_mesh

pytestmark = pytest.mark.quick

T0 = 1_785_000_000_000
N_DEV = 8


def _cfg(**over):
    return stpu.load_config(max_resources=64, max_flow_rules=16,
                            max_degrade_rules=16, max_authority_rules=16,
                            host_fast_path=False, **over)


def _make(mesh=None, ring_slots=None, **over):
    s = stpu.Sentinel(_cfg(**over), clock=ManualClock(start_ms=T0),
                      mesh=mesh)
    if ring_slots is not None:
        s.telemetry.ring_slots = ring_slots
    return s


def _zipf_drive(s, n=300, n_res=20, seed=7):
    """Seeded Zipf-ish stream over ``n_res`` resources (rule-free: every
    entry passes, so load is a pure function of the stream)."""
    rng = np.random.default_rng(seed)
    for z in rng.zipf(1.5, size=n):
        name = f"res-{min(int(z) - 1, n_res - 1)}"
        try:
            s.entry(name).exit()
        except BlockException:
            pass


def _host_topk(s, k):
    """Host recompute of the device ranking key: rolling pass+block over
    the live second window, ENTRY masked, stable argsort."""
    spec = s.spec.second
    stamps = np.asarray(s._state.second.stamps)
    counters = np.asarray(s._state.second.counters)
    diff = np.int32(spec.index_of(s.clock.now_ms())) - stamps
    mask = (diff >= 0) & (diff < spec.buckets)
    load = np.where(mask, counters[:, :, 0] + counters[:, :, 1], 0) \
        .sum(axis=1).astype(np.int64)
    load[ENTRY_NODE_ROW] = -1
    order = np.argsort(-load, kind="stable")[:k]
    return load[order], order


# ---------------------------------------------------------------------------
# exactness: device top-K == host recompute
# ---------------------------------------------------------------------------

def test_sharded_topk_bit_equal_to_host_recompute():
    s = _make(mesh=local_mesh(N_DEV))
    assert s.telemetry.enabled and s.telemetry._n_shards == N_DEV
    _zipf_drive(s)
    s.clock.advance_ms(100)
    assert s.telemetry.poll() == 1
    loads, rows = s.telemetry.last_topk
    h_loads, h_rows = _host_topk(s, s.telemetry.k)
    assert list(rows) == list(h_rows)
    assert list(loads) == list(h_loads)
    # the filtered host view names only live, positive-load rows
    hot = s.telemetry.hot_entries()
    assert hot and hot[0]["load"] == int(h_loads[0])
    assert all(h["load"] > 0 for h in hot)
    assert all(h["resource"] != "" for h in hot)
    s.close()


def test_single_device_topk_matches_host():
    s = _make(mesh=None)
    assert s.telemetry._n_shards == 1
    _zipf_drive(s, seed=11)
    s.clock.advance_ms(50)
    assert s.telemetry.poll() == 1
    loads, rows = s.telemetry.last_topk
    h_loads, h_rows = _host_topk(s, s.telemetry.k)
    assert list(rows) == list(h_rows) and list(loads) == list(h_loads)
    s.close()


def test_manual_clock_determinism():
    snaps = []
    for _ in range(2):
        s = _make(mesh=local_mesh(N_DEV))
        _zipf_drive(s, seed=3)
        s.clock.advance_ms(1500)        # one completed second → timeline
        s.telemetry.poll()
        snap = s.telemetry.snapshot()
        snaps.append((snap["hot"], snap["timeline"]))
        s.close()
    assert snaps[0] == snaps[1]
    assert snaps[0][1]                  # timeline actually populated


# ---------------------------------------------------------------------------
# timeline ring
# ---------------------------------------------------------------------------

def test_timeline_ring_wraps_past_slots():
    s = _make(mesh=None, ring_slots=8)
    slots = 8
    appends = slots + 5
    for i in range(appends):
        try:
            s.entry("svc").exit()
        except BlockException:
            pass
        s.clock.advance_ms(1000)        # completes second i
        assert s.telemetry.poll() == 1
    ring = s.telemetry._ring
    assert int(ring.cursor) == appends
    # ring holds the last `slots` completed seconds (minute idx == epoch
    # sec for the 1 s minute buckets), wrapped at cursor % slots
    got = sorted(int(x) for x in np.asarray(ring.seconds))
    first_kept = T0 // 1000 + appends - slots
    assert got == list(range(first_kept, first_kept + slots))
    # host tail mirrors every appended second in order
    tl = s.telemetry.snapshot(timeline_limit=appends)["timeline"]
    assert [e["sec"] for e in tl] == \
        [T0 // 1000 + i for i in range(appends)]
    assert all(e["pass"] == 1 for e in tl)
    s.close()


def test_tick_appends_once_per_second():
    s = _make(mesh=None)
    try:
        s.entry("svc").exit()
    except BlockException:
        pass
    s.clock.advance_ms(1200)
    s.telemetry.poll()
    s.clock.advance_ms(100)             # same wall second
    s.telemetry.poll()
    tl = s.telemetry.snapshot()["timeline"]
    assert len(tl) == 1 and tl[0]["sec"] == T0 // 1000
    s.close()


# ---------------------------------------------------------------------------
# async readback: drop-and-count
# ---------------------------------------------------------------------------

def test_readback_drop_counts_when_drain_falls_behind():
    s = _make(mesh=None)
    for _ in range(PENDING_MAX):
        assert s.telemetry.tick()
    assert not s.telemetry.tick()       # queue full → dropped, not synced
    snap = s.telemetry.snapshot()
    assert snap["drops"] == 1 and snap["ticks"] == PENDING_MAX
    assert s.obs.counters.get(ck.TELEMETRY_DROP) == 1
    assert s.obs.counters.get(ck.TELEMETRY_TICK) == PENDING_MAX
    assert s.telemetry.drain() == PENDING_MAX
    assert s.telemetry.tick()           # drained → accepts again
    s.close()


# ---------------------------------------------------------------------------
# knobs + lifecycle
# ---------------------------------------------------------------------------

def test_knob_envs(monkeypatch):
    monkeypatch.setenv(TELEMETRY_K_ENV, "4")
    s = _make(mesh=None)
    assert s.telemetry.k == 4
    s.close()
    monkeypatch.setenv(TELEMETRY_DISABLE_ENV, "1")
    s2 = _make(mesh=None)
    assert not s2.telemetry.enabled
    assert not s2.telemetry.tick()
    s2.close()


def test_stop_is_idempotent_and_close_stops_it():
    s = _make(mesh=None)
    s.telemetry.start(interval_sec=60)
    assert s.telemetry._thread is not None
    s.close()                           # shutdown hook stops the ticker
    assert s.telemetry._thread is None and not s.telemetry.enabled
    s.telemetry.stop()                  # second stop is a no-op


# ---------------------------------------------------------------------------
# persistence: <app>-metric lines ride the writer rotation
# ---------------------------------------------------------------------------

def test_metric_lines_roundtrip_for_topk_only(tmp_path):
    from sentinel_tpu.metrics.searcher import MetricSearcher

    s = _make(mesh=local_mesh(N_DEV))
    base = s.telemetry.configure(str(tmp_path), "telapp")
    assert base.startswith("telapp-metric")
    # drive LATE in the second and tick just past the boundary: the hot
    # set is the live rolling window, so the traffic must still be
    # inside it when the completed second lands
    s.clock.advance_ms(600)
    for _ in range(5):
        try:
            s.entry("hot-res").exit()
        except BlockException:
            pass
    try:
        s.entry("cold-res").exit()
    except BlockException:
        pass
    s.clock.advance_ms(450)             # completes second T0/1000
    assert s.telemetry.poll() == 1
    found = MetricSearcher(str(tmp_path), base).find(
        T0 - 1000, T0 + 10_000)
    by_res = {n.resource: n for n in found}
    assert by_res["hot-res"].pass_qps == 5
    assert by_res["cold-res"].pass_qps == 1
    assert all(n.timestamp == (T0 // 1000) * 1000 for n in found)
    s.close()


# ---------------------------------------------------------------------------
# transport command + flight pinning
# ---------------------------------------------------------------------------

def test_topk_transport_command():
    from sentinel_tpu.transport import (
        CommandCenter, CommandRequest, register_default_handlers,
    )
    s = _make(mesh=None)
    center = CommandCenter()
    register_default_handlers(center, s)
    _zipf_drive(s, n=60, seed=5)
    s.clock.advance_ms(100)
    # tick=1 forces one poll inline — no background ticker in this test
    resp = center.handle("topk", CommandRequest(parameters={"tick": "1"}))
    assert resp.success
    body = json.loads(resp.result)
    assert body["enabled"] and body["hot"]
    assert body["hot"][0]["load"] >= body["hot"][-1]["load"]
    bad = center.handle("topk", CommandRequest(
        parameters={"timeline": "x"}))
    assert not bad.success and bad.code == 400
    s.close()


def test_flight_trigger_pins_hot_set():
    s = _make(mesh=None)
    assert s.obs.flight.hot_provider is not None
    _zipf_drive(s, n=80, seed=9)
    s.clock.advance_ms(10)
    s.telemetry.poll()
    tr = s.obs.spans.mint()
    ns = s.obs.spans.now_ns()
    s.obs.spans.record(tr, "frontend.enqueue", ns, ns)
    assert s.obs.flight.trigger("block_burst", note="test")
    rec = s.obs.flight.snapshot(full=True)[-1]
    assert rec["hot"], "trigger record must pin the hot set"
    assert rec["hot"][0]["resource"].startswith("res-")
    assert all(set(h) == {"resource", "qps"} for h in rec["hot"])
    s.close()


# ---------------------------------------------------------------------------
# the landing is O(K): it asks the registry for the top-K rows' names and
# for nothing else, on either registry (PR 27)
# ---------------------------------------------------------------------------

@pytest.fixture(params=["python", "native"])
def registry_kind(request, monkeypatch):
    if request.param == "python":
        monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    else:
        from sentinel_tpu.native import native_available
        if not native_available():
            pytest.skip("the native registry does not build here")
    return request.param


def _check_kind(s, kind):
    from sentinel_tpu.core.registry import Registry
    assert isinstance(s.resources, Registry) == (kind == "python")


class _ResidentView:
    """What the landing used before PR 27: a row → name dict built from a
    snapshot of every resident name."""

    def __init__(self, registry):
        self._names = dict((row, name) for name, row in registry.items())

    def name_of(self, row):
        return self._names.get(row)


class _Written:
    """Stands in for the ``<app>-metric`` writer: keeps what it is given."""

    def __init__(self):
        self.calls = []

    def write(self, ts, nodes):
        self.calls.append((ts, list(nodes)))


def _drive_second(s, traffic):
    """Traffic late in the second, then just past its end: the names are
    in the live window AND in the completed second (module docstring)."""
    s.clock.advance_ms(600)
    for name, n, rtype in traffic:
        for _ in range(n):
            s.entry(name, resource_type=rtype).exit()
    s.clock.advance_ms(450)


def _spans(s, name):
    return [x for x in s.obs.spans.snapshot() if x["name"] == name]


def test_landing_resolves_the_topk_rows_and_never_walks_the_registry(
        registry_kind, monkeypatch):
    monkeypatch.setenv(TELEMETRY_K_ENV, "4")
    s = _make()
    _check_kind(s, registry_kind)
    s.intern_resources([f"idle-{i}" for i in range(30)])
    _drive_second(s, [("a", 5, 0), ("b", 3, 0), ("c", 1, 0)])
    walked, asked = [], []
    items, name_of = s.resources.items, s.resources.name_of
    monkeypatch.setattr(s.resources, "items",
                        lambda: walked.append(1) or items())
    monkeypatch.setattr(s.resources, "name_of",
                        lambda row: asked.append(row) or name_of(row))
    assert s.telemetry.poll() == 1
    assert not walked
    hot = s.telemetry.hot_entries()
    assert [h["resource"] for h in hot] == ["a", "b", "c"]
    assert asked == [h["row"] for h in hot]
    (land,) = _spans(s, "telemetry.land")
    assert land["n"] == len(asked) == 3 < s.telemetry.k < len(s.resources)
    s.close()


def test_landing_equals_the_resident_view_entry_for_entry(registry_kind):
    """The same readback landed through ``name_of`` and through the old
    row → name dict: hot set, snapshot, timeline and metric nodes agree."""
    s = _make()
    _check_kind(s, registry_kind)
    s.telemetry.writer = _Written()
    traffic = [("web", 7, 1), ("rpc", 5, 2), ("db", 5, 3), ("plain", 2, 0)]
    _drive_second(s, traffic)
    assert s.telemetry.tick()
    readback = s.telemetry._pending.pop()
    real = s.resources
    landed = []
    for registry in (real, _ResidentView(real)):
        s.resources = registry
        s.telemetry._pending.append(readback)     # the same one, twice
        assert s.telemetry.drain() == 1
        snap = s.telemetry.snapshot()
        landed.append((s.telemetry.hot_entries(), snap["hot"],
                       snap["timeline"][-1], s.telemetry.flight_hot(),
                       s.telemetry.writer.calls.pop()))
    s.resources = real
    by_name_of, by_view = landed
    assert by_name_of == by_view
    hot, snap_hot, _timeline, _flight, (ts, nodes) = by_name_of
    assert hot == snap_hot
    # equal loads keep row order (rpc interned before db)
    assert [h["resource"] for h in hot] == ["web", "rpc", "db", "plain"]
    assert ts == (T0 // 1000) * 1000
    assert [(n.resource, n.pass_qps, n.classification) for n in nodes] \
        == sorted((name, n, rtype) for name, n, rtype in traffic)
    assert dict(s.resource_types) == {"web": 1, "rpc": 2, "db": 3}
    s.close()


def test_row_evicted_between_tick_and_drain_is_skipped(monkeypatch):
    """Only the Python registry frees a row without handing it on
    (``evict_name``, the tiering ticker's demotion)."""
    monkeypatch.setenv("SENTINEL_TPU_NATIVE", "0")
    s = _make()
    _drive_second(s, [("stays", 4, 0), ("goes", 6, 0)])
    assert s.telemetry.tick()
    assert s.resources.evict_name("goes")
    assert s.telemetry.drain() == 1
    assert [h["resource"] for h in s.telemetry.hot_entries()] == ["stays"]
    loads, _rows = s.telemetry.last_topk      # the raw readback keeps it
    assert list(loads[:2]) == [6, 4]
    assert _spans(s, "telemetry.land")[-1]["n"] == 2   # asked for both
    s.close()


def test_row_reinterned_between_tick_and_drain_lands_under_the_new_name(
        registry_kind):
    """The name is looked up at land time, as the snapshot was: a row the
    LRU handed to another name since the tick reads that name."""
    s = _make()
    _check_kind(s, registry_kind)
    _drive_second(s, [("victim", 6, 0)])
    row = s.resources.lookup("victim")
    assert s.telemetry.tick()
    heir = None
    for i in range(2 * s.resources.capacity):
        if s.resources.name_of(row) != "victim":
            break
        heir = f"filler-{i}"
        s.resources.get_or_create(heir)
    assert s.resources.name_of(row) == heir
    assert s.telemetry.drain() == 1
    top = s.telemetry.hot_entries()[0]
    assert (top["resource"], top["row"], top["load"]) == (heir, row, 6)
    s.close()


def test_name_of_agrees_with_the_items_view_on_every_row(registry_kind):
    """Both registries answer ``name_of`` as their ``items()`` snapshot
    does, for live, recycled, never-used and out-of-range rows."""
    from sentinel_tpu.core.registry import make_resource_registry
    sparse, recycled = make_resource_registry(16), make_resource_registry(16)
    for name in ("x", "y"):
        sparse.get_or_create(name)
    for i in range(40):                       # overflows: rows are recycled
        recycled.get_or_create(f"n-{i}")
    assert sparse.name_of(15) is None and sparse.name_of(-1) is None
    assert len(recycled) == 16
    for reg in (sparse, recycled):
        view = _ResidentView(reg)
        assert [reg.name_of(r) for r in range(-2, 18)] \
            == [view.name_of(r) for r in range(-2, 18)]
