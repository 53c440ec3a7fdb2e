"""Round 9: the row-sharded engine as the SERVING hot path.

Bit-parity of the meshed (8-virtual-device) engine against the
single-device engine through the full serving stack — DispatchPipeline,
decide-then-exit steps, split/prio/occupy routing, occupy-booking
carry across rule reloads, and the AdaptiveBatcher fan-out — plus the
layout helpers (parallel/local_shard.py batch placement + topology) and
the mesh-attribution counters. tests/test_sharded_local.py pins the
entry-API tier; this file pins the raw/pipelined serving tiers the
front end actually drives.
"""

import asyncio

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.engine.pipeline import EntryBatch
from sentinel_tpu.frontend.batcher import AdaptiveBatcher
from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.parallel.local_shard import (
    MESH_AXIS, batch_sharding, local_mesh, mesh_topology, place_batch,
)
from sentinel_tpu.rules.flow import FlowRule
from sentinel_tpu.serving import DispatchPipeline

pytestmark = pytest.mark.quick

T0 = 1_785_000_000_000
N_DEV = 8


def _cfg(**over):
    return stpu.load_config(max_resources=64, max_origins=32,
                            max_flow_rules=32, max_degrade_rules=16,
                            max_authority_rules=16, host_fast_path=False,
                            **over)


def _rules(api_count=3.0):
    return [FlowRule(resource="api", count=api_count),
            FlowRule(resource="api", count=2.0, limit_app="app-a"),
            FlowRule(resource="bulk", count=1e6)]


def _pair(**over):
    """(single-device, meshed) twins with identical clocks + rules."""
    ref = stpu.Sentinel(_cfg(**over), clock=ManualClock(start_ms=T0))
    sh = stpu.Sentinel(_cfg(**over), clock=ManualClock(start_ms=T0),
                       mesh=local_mesh(N_DEV))
    for s in (ref, sh):
        s.load_flow_rules(_rules())
    return ref, sh


def _raw_columns(ref, sh, n=8192, prio_frac=0.01, seed=29):
    """Mixed raw batch above the 4096 split threshold: ~90% scalar bulk,
    10% origin-carrying (general side), prio_frac prioritized — the
    composition that exercises split + fast-occupy routing."""
    rng = np.random.default_rng(seed)
    row_api = ref.resources.get_or_create("api")
    row_bulk = ref.resources.get_or_create("bulk")
    assert sh.resources.get_or_create("api") == row_api
    assert sh.resources.get_or_create("bulk") == row_bulk
    oid = ref.origins.pin("app-a")
    sh.origins.pin("app-a")
    pad_a = ref.spec.alt_rows
    rows = np.where(rng.random(n) < 0.5, row_api,
                    row_bulk).astype(np.int32)
    has_o = rng.random(n) < 0.1
    alt = {r: ref._alt_row(r, 0, int(oid)) for r in (row_api, row_bulk)}
    for r in (row_api, row_bulk):
        assert sh._alt_row(r, 0, int(oid)) == alt[r]
    return dict(
        rows=rows,
        oids=np.where(has_o, oid, 0).astype(np.int32),
        orow=np.where(has_o, np.where(rows == row_api, alt[row_api],
                                      alt[row_bulk]),
                      pad_a).astype(np.int32),
        ctx0=np.zeros(n, np.int32),
        chain=np.full(n, pad_a, np.int32),
        ones=np.ones(n, np.int32),
        is_in=np.ones(n, np.bool_),
        prio=rng.random(n) < prio_frac,
        rt=np.full(n, 5, np.int32),
        err=np.zeros(n, np.bool_))


def _assert_verdicts_equal(a, b, ctx=""):
    np.testing.assert_array_equal(np.asarray(a.allow), np.asarray(b.allow),
                                  err_msg=f"allow diverged {ctx}")
    np.testing.assert_array_equal(np.asarray(a.reason),
                                  np.asarray(b.reason),
                                  err_msg=f"reason diverged {ctx}")
    np.testing.assert_array_equal(np.asarray(a.wait_ms),
                                  np.asarray(b.wait_ms),
                                  err_msg=f"wait_ms diverged {ctx}")


# ---------------------------------------------------------------------------
# layout helpers
# ---------------------------------------------------------------------------

def test_batch_sharding_divisibility_rule():
    mesh = local_mesh(N_DEV)
    even = np.zeros(8192, np.int32)
    odd = np.zeros(8191, np.int32)
    assert batch_sharding(mesh, even).spec == P(MESH_AXIS)
    assert batch_sharding(mesh, odd).spec == P()
    # trailing (param-lane) dims stay unpartitioned
    assert batch_sharding(mesh, np.zeros((8192, 3), np.int32)).spec \
        == P(MESH_AXIS)


def test_place_batch_places_every_column_and_keeps_values():
    mesh = local_mesh(N_DEV)
    n = 1024
    batch = EntryBatch(
        rows=np.arange(n, dtype=np.int32),
        origin_ids=np.zeros(n, np.int32),
        origin_rows=np.full(n, 7, np.int32),
        context_ids=np.zeros(n, np.int32),
        chain_rows=np.full(n, 7, np.int32),
        acquire=np.ones(n, np.int32),
        is_in=np.ones(n, np.bool_),
        prioritized=np.zeros(n, np.bool_),
        valid=np.ones(n, np.bool_))
    placed = place_batch(batch, mesh)
    assert placed.param_rules is None          # absent leaves stay absent
    for name in ("rows", "acquire", "valid"):
        leaf = getattr(placed, name)
        assert leaf.sharding.spec == P(MESH_AXIS), name
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(getattr(batch, name)))


def test_local_mesh_errors_when_short_of_devices():
    with pytest.raises(ValueError, match="host_platform_device_count"):
        local_mesh(len(jax.devices()) + 1)


def test_mesh_topology_artifact_block():
    ref, sh = _pair()
    assert mesh_topology(ref.spec, None) == {
        "n_devices": 1, "axis": None, "rows_per_device": ref.spec.rows,
        "sharded": False}
    topo = mesh_topology(sh.spec, sh.mesh, sh._mesh_shardings[0])
    assert topo["n_devices"] == N_DEV and topo["axis"] == MESH_AXIS
    assert topo["rows_per_device"] == sh.spec.rows // N_DEV
    assert topo["sharded"] and not topo["multihost"]
    assert topo["state_leaves_sharded"] > 0
    assert topo["state_leaves_replicated"] > 0
    ref.close()
    sh.close()


# ---------------------------------------------------------------------------
# serving-tier parity
# ---------------------------------------------------------------------------

def test_pipeline_parity_and_mesh_counters():
    """Depth-2 pipelined raw dispatch: meshed verdicts bit-identical to
    single-device, with ROUTE_MESHED / PIPE_MESHED attributing every
    meshed dispatch (and staying silent on the single-device engine)."""
    ref, sh = _pair()
    cols = _raw_columns(ref, sh, n=4096 + 512)
    pipes = {"ref": DispatchPipeline(ref, depth=2),
             "sh": DispatchPipeline(sh, depth=2)}
    got = {}
    for key, pipe in pipes.items():
        tickets = [pipe.submit_raw(
            cols["rows"], cols["oids"], cols["orow"], cols["ctx0"],
            cols["chain"], cols["ones"], cols["is_in"], cols["prio"],
            at_ms=T0 + i * 250) for i in range(5)]
        got[key] = [t.result() for t in tickets]
    for i, (a, b) in enumerate(zip(got["ref"], got["sh"])):
        _assert_verdicts_equal(a, b, ctx=f"at step {i}")
    assert sh.obs.counters.get(obs_keys.ROUTE_MESHED) == 5
    assert sh.obs.counters.get(obs_keys.PIPE_MESHED) == 5
    assert ref.obs.counters.get(obs_keys.ROUTE_MESHED) == 0
    assert ref.obs.counters.get(obs_keys.PIPE_MESHED) == 0
    # batch columns actually landed row-sharded on the mesh
    assert sh._state.second.counters.sharding.spec == P(MESH_AXIS)
    ref.close()
    sh.close()


def test_decide_then_exit_parity():
    """Steps WITH exits (RT, errors, thread gauges): decide, then the
    completions, on both engines — verdicts and every state leaf stay
    bit-identical, and each meshed decide is attributed."""
    ref, sh = _pair()
    cols = _raw_columns(ref, sh, n=2048, seed=5)
    for i in range(4):
        hs = [s.decide_raw_nowait(
            cols["rows"], cols["oids"], cols["orow"], cols["ctx0"],
            cols["chain"], cols["ones"], cols["is_in"], cols["prio"],
            at_ms=T0 + i * 250) for s in (ref, sh)]
        for s in (ref, sh):
            s.exit_batch(
                rows=cols["rows"], origin_rows=cols["orow"],
                chain_rows=cols["chain"], acquire=cols["ones"],
                rt_ms=cols["rt"], error=cols["err"], is_in=cols["is_in"],
                at_ms=T0 + i * 250)
        _assert_verdicts_equal(hs[0].result(), hs[1].result(),
                               ctx=f"decide+exit step {i}")
    for a, b in zip(jax.tree.leaves(ref._state), jax.tree.leaves(sh._state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sh.obs.counters.get(obs_keys.ROUTE_MESHED) == 4
    ref.close()
    sh.close()


def test_split_routing_fires_identically_on_mesh(monkeypatch):
    """The meshed engine must take the SAME split decision (scalar bulk +
    prio/origin general slice) as the single-device engine — and the
    verdicts through that split must stay bit-identical."""
    ref, sh = _pair()
    cols = _raw_columns(ref, sh, n=8192)
    calls = {"ref": 0, "sh": 0}
    for key, s in (("ref", ref), ("sh", sh)):
        orig = s._decide_split_nowait

        def probe(*a, _orig=orig, _key=key, **k):
            calls[_key] += 1
            return _orig(*a, **k)

        monkeypatch.setattr(s, "_decide_split_nowait", probe)
    for i in range(3):
        hs = [s.decide_raw_nowait(
            cols["rows"], cols["oids"], cols["orow"], cols["ctx0"],
            cols["chain"], cols["ones"], cols["is_in"], cols["prio"],
            at_ms=T0 + i * 250) for s in (ref, sh)]
        _assert_verdicts_equal(hs[0].result(), hs[1].result(),
                               ctx=f"split step {i}")
    assert calls["ref"] == calls["sh"] > 0
    ref.close()
    sh.close()


def test_occupy_bookings_carry_across_reload_on_mesh():
    """Prioritized denials book future-window occupancy; a rule reload
    mid-stream must CARRY the same number of live bookings on both
    engines and keep post-reload verdicts bit-identical."""
    ref, sh = _pair()
    cols = _raw_columns(ref, sh, n=8192, prio_frac=0.05, seed=11)
    args = (cols["rows"], cols["oids"], cols["orow"], cols["ctx0"],
            cols["chain"], cols["ones"], cols["is_in"], cols["prio"])
    for i in range(3):
        hs = [s.decide_raw_nowait(*args, at_ms=T0 + i * 250)
              for s in (ref, sh)]
        _assert_verdicts_equal(hs[0].result(), hs[1].result(),
                               ctx=f"pre-reload step {i}")
    granted = [s.obs.counters.get(obs_keys.OCCUPY_GRANTED)
               for s in (ref, sh)]
    assert granted[0] == granted[1] > 0, granted
    # clock catches up to the traffic timeline so the bookings are
    # PENDING (target window == clock's next) at reload — the carry path
    for s in (ref, sh):
        s.clock.advance_ms(500)
        s.load_flow_rules(_rules(api_count=4.0))
    carried = [s.obs.counters.get(obs_keys.OCCUPY_CARRIED)
               for s in (ref, sh)]
    assert carried[0] == carried[1] > 0, carried
    for i in range(3, 6):
        hs = [s.decide_raw_nowait(*args, at_ms=T0 + i * 250)
              for s in (ref, sh)]
        _assert_verdicts_equal(hs[0].result(), hs[1].result(),
                               ctx=f"post-reload step {i}")
    assert sh._state.second.counters.sharding.spec == P(MESH_AXIS)
    ref.close()
    sh.close()


def test_frontend_fanout_parity_on_mesh():
    """AdaptiveBatcher on the MESHED engine: per-request verdicts must
    equal a sequential replay of its recorded flush cuts on a
    single-device twin — the round-7 parity pin, now with the mesh
    underneath the pipeline."""
    fe_s, seq_s = None, None
    try:
        seq_s = stpu.Sentinel(_cfg(), clock=ManualClock(start_ms=T0))
        fe_s = stpu.Sentinel(_cfg(), clock=ManualClock(start_ms=T0),
                             mesh=local_mesh(N_DEV))
        for s in (fe_s, seq_s):
            s.load_flow_rules(_rules())
        rng = np.random.default_rng(31)
        stream = [("api" if rng.random() < 0.7 else "bulk",
                   bool(rng.random() < 0.3),
                   "app-a" if rng.random() < 0.4 else "")
                  for _ in range(42)]

        async def run():
            b = AdaptiveBatcher(fe_s, batch_max=8, deadline_ms=60_000,
                                idle_ms=10_000.0, depth=2,
                                record_flushes=True)
            verdicts = await asyncio.gather(
                *(b.submit(r, prioritized=p, origin=o)
                  for r, p, o in stream))
            await b.drain()
            return verdicts, b.flush_log

        verdicts, flush_log = asyncio.run(run())
        assert [r for f in flush_log for r in f["resources"]] == \
            [r for r, _p, _o in stream]
        seq = []
        for f in flush_log:
            v = seq_s.entry_batch_nowait(
                f["resources"],
                acquire=np.asarray(f["counts"], np.int32),
                prioritized=np.asarray(f["prioritized"], np.bool_),
                origins=(f["origins"] if any(f["origins"]) else None),
            ).result()
            seq.extend(zip(np.asarray(v.allow), np.asarray(v.reason),
                           np.asarray(v.wait_ms)))
        assert len(seq) == len(verdicts)
        for i, (got, want) in enumerate(zip(verdicts, seq)):
            assert (got.allow, got.reason, got.wait_ms) == \
                (bool(want[0]), int(want[1]), int(want[2])), f"request {i}"
        assert fe_s.obs.counters.get(obs_keys.PIPE_MESHED) > 0
    finally:
        for s in (fe_s, seq_s):
            if s is not None:
                s.close()
