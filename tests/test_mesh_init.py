"""A meshed engine initialises under its shardings (PR 29): the sharding
pytree comes from the state's shapes, the state is created already laid
out — by the one transfer or by the fill program with ``out_shardings`` —
and nothing is re-placed afterwards. Without a mesh the init call is the
one it was. Runs on the suite's virtual CPU devices."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

import sentinel_tpu as stpu
import sentinel_tpu.runtime as runtime
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.engine import pipeline
from sentinel_tpu.parallel.local_shard import (
    local_mesh, pin_state, state_shardings,
)

pytestmark = pytest.mark.quick

T0 = 1_785_000_000_000
MODES = ["transfer", "program"]


def _cfg(**over):
    kw = dict(max_resources=256, max_origins=32, max_flow_rules=16,
              max_degrade_rules=16, max_authority_rules=16)
    kw.update(over)
    return stpu.load_config(**kw)


def _engine(mesh=None, **over):
    return stpu.Sentinel(_cfg(**over), clock=ManualClock(start_ms=T0),
                         mesh=mesh)


def _buffers(state):
    """Every device buffer of every leaf, by address."""
    return [s.data.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(state)
            for s in leaf.addressable_shards]


@pytest.mark.parametrize("mode", MODES)
def test_a_meshed_engine_comes_out_of_init_on_its_canonical_shardings(
        monkeypatch, mode):
    monkeypatch.setenv("SENTINEL_INIT_MODE", mode)
    mesh = local_mesh(4)
    sph = _engine(mesh)
    cfg = sph.cfg
    want_sh = state_shardings(sph.spec, mesh, sph._state)
    assert sph._mesh_shardings[0] == want_sh
    flat_sh = jax.tree.leaves(want_sh)
    leaves = jax.tree.leaves(sph._state)
    assert len(leaves) == len(flat_sh)
    for leaf, sh in zip(leaves, flat_sh):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        assert len(leaf.sharding.device_set) == 4
    # bit-equal to the host-side mirror, leaf for leaf
    want = pipeline._init_state_np(sph.spec, cfg.max_flow_rules,
                                   cfg.max_degrade_rules)
    for got, ref in zip(leaves, jax.tree.leaves(want)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), ref)
    # a row-sharded leaf holds a quarter of its rows on each device
    ring = sph._state.minute.counters
    assert {s.data.shape[0] for s in ring.addressable_shards} \
        == {sph.spec.rows // 4}
    sph.close()


@pytest.mark.parametrize("mode", MODES)
def test_the_pin_that_follows_init_replaces_no_buffer(monkeypatch, mode):
    monkeypatch.setenv("SENTINEL_INIT_MODE", mode)
    sph = _engine(local_mesh(4))
    before = _buffers(sph._state)
    sph._pin_state_locked()
    assert _buffers(sph._state) == before
    pinned = pin_state(sph._state, sph._mesh_shardings[0])
    assert _buffers(pinned) == before
    sph.close()


def test_init_places_no_leaf_whole_on_one_device(monkeypatch):
    """The fault this PR repairs: the fill program ran with no
    ``out_shardings`` and put the whole state on the default device.
    Every array ``init_state`` hands back is already spread over the mesh,
    on both branches, and ``__init__`` calls it with the shardings."""
    seen = []
    inner = pipeline.init_state

    def spy(spec, nf, nd, shardings=None):
        state = inner(spec, nf, nd, shardings=shardings)
        seen.append((shardings, [len(leaf.sharding.device_set)
                                 for leaf in jax.tree.leaves(state)]))
        return state
    monkeypatch.setattr(runtime, "init_state", spy)
    for mode in MODES:
        monkeypatch.setenv("SENTINEL_INIT_MODE", mode)
        _engine(local_mesh(4)).close()
    assert len(seen) == 2
    for shardings, spread in seen:
        assert shardings is not None and set(spread) == {4}


def test_shardings_from_shapes_equal_shardings_from_a_state():
    mesh = local_mesh(4)
    sph = _engine(mesh)
    cfg = sph.cfg
    shapes = pipeline.init_state_shapes(sph.spec, cfg.max_flow_rules,
                                        cfg.max_degrade_rules)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(shapes))
    from_shapes = state_shardings(sph.spec, mesh, shapes)
    from_state = state_shardings(sph.spec, mesh, sph._state)
    assert from_shapes == from_state
    assert jax.tree.structure(from_shapes) == jax.tree.structure(sph._state)
    # and without the minute ring, whose stub is replicated
    spec = dataclasses.replace(sph.spec, minute=None)
    shapes = pipeline.init_state_shapes(spec, 4, 4)
    state = pipeline.init_state(spec, 4, 4)
    assert state_shardings(spec, mesh, shapes) \
        == state_shardings(spec, mesh, state)
    sph.close()


def test_the_program_branch_runs_with_the_shardings_as_out_shardings(
        monkeypatch):
    """The fill program's own output layout, read off the compiled
    program: what ``state_shardings`` gives, leaf for leaf."""
    monkeypatch.setenv("SENTINEL_INIT_MODE", "program")
    mesh = local_mesh(4)
    spec = _engine().spec
    shapes = pipeline.init_state_shapes(spec, 16, 16)
    want = state_shardings(spec, mesh, shapes)
    made = []
    real_jit = jax.jit

    def spy(fun, **kw):
        jitted = real_jit(fun, **kw)
        made.append((kw, jitted))
        return jitted
    monkeypatch.setattr(pipeline.jax, "jit", spy)
    state = pipeline.init_state(spec, 16, 16, shardings=want)
    (kw, jitted), = made
    assert kw["out_shardings"] is want
    out = jitted.lower().compile().output_shardings
    for got, sh, leaf in zip(jax.tree.leaves(out), jax.tree.leaves(want),
                             jax.tree.leaves(state)):
        assert got.is_equivalent_to(sh, leaf.ndim)


@pytest.mark.parametrize("mode", MODES)
def test_without_a_mesh_the_init_call_is_the_parents(monkeypatch, mode):
    """Same call, same cached program under the same key: the unmeshed
    engine passes no shardings, the program branch is the ``lru_cache``d
    jit keyed ``(spec, nf, nd)`` with no ``out_shardings``, and its
    lowering is byte for byte that of the parent's form."""
    monkeypatch.setenv("SENTINEL_INIT_MODE", mode)
    calls = []
    inner = runtime.init_state
    monkeypatch.setattr(
        runtime, "init_state",
        lambda *a, **kw: calls.append((a, kw)) or inner(*a, **kw))
    sph = _engine()
    (args, kw), = calls
    cfg = sph.cfg
    assert args == (sph.spec, cfg.max_flow_rules, cfg.max_degrade_rules)
    assert kw == {"shardings": None} and sph._mesh_shardings is None
    assert all(len(leaf.sharding.device_set) == 1
               for leaf in jax.tree.leaves(sph._state))
    if mode == "program":
        key = (sph.spec, cfg.max_flow_rules, cfg.max_degrade_rules)
        cached = pipeline._init_state_jit(*key)
        assert pipeline._init_state_jit(*key) is cached        # one entry
        parents = jax.jit(functools.partial(
            pipeline._init_state_traced, *key))
        assert cached.lower().as_text() == parents.lower().as_text()
        info = pipeline._init_state_jit.cache_info()
        pipeline.init_state(*key)
        after = pipeline._init_state_jit.cache_info()
        assert after.hits == info.hits + 1 and after.misses == info.misses
    sph.close()


def test_the_two_new_phases_name_their_parent_and_their_n():
    """``state.init`` is a root phase of ``__init__`` (``n`` = rows, the
    note says over how many devices); ``batch.place`` is a child of the
    dispatch phase open around it (``n`` = events), once per placed
    batch, and only a meshed engine records it."""
    sph = _engine(local_mesh(4))
    (init,) = [s for s in sph.obs.spans.snapshot()
               if s["name"] == "state.init"]
    assert init["n"] == sph.spec.rows == 256 and init["parent"] == 0
    assert init["note"] == "devices=4"
    rows = np.asarray(sph.intern_resources(["api"] * 8), np.int32)
    zeros = np.zeros(8, np.int32)
    pad = np.full(8, sph.spec.alt_rows, np.int32)
    sph.decide_raw_nowait(rows, zeros, pad, zeros, pad, np.ones(8, np.int32),
                          np.ones(8, bool), np.zeros(8, bool)).result()
    sph.exit_batch(rows=rows[:5], origin_rows=pad[:5], chain_rows=pad[:5],
                   acquire=np.ones(5, np.int32), rt_ms=np.ones(5, np.int32),
                   error=np.zeros(5, bool), is_in=np.ones(5, bool))
    spans = sph.obs.spans.snapshot()
    ids = {s["id"]: s for s in spans}
    places = [s for s in spans if s["name"] == "batch.place"]
    assert [(s["n"], ids[s["parent"]]["name"]) for s in places] \
        == [(8, "decide.dispatch"), (5, "exit.dispatch")]
    for s in places:
        parent = ids[s["parent"]]
        assert parent["start_ns"] <= s["start_ns"] \
            and s["end_ns"] <= parent["end_ns"]
    sph.close()

    plain = _engine()
    (init,) = [s for s in plain.obs.spans.snapshot()
               if s["name"] == "state.init"]
    assert init["note"] == "devices=1" and init["n"] == 256
    plain.decide_raw_nowait(
        rows, zeros, pad, zeros, pad, np.ones(8, np.int32),
        np.ones(8, bool), np.zeros(8, bool)).result()
    assert not [s for s in plain.obs.spans.snapshot()
                if s["name"] == "batch.place"]
    plain.close()


def test_the_new_phases_are_in_the_profilers_trace(tmp_path):
    """One call site, two sinks: ``sentinel_tpu.state.init`` and
    ``sentinel_tpu.batch.place`` are annotations of the profiler's trace."""
    from chipbench import trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sph = _engine(local_mesh(4))
        sph.entry_batch(["api"] * 4)
        sph.close()
    finally:
        jax.profiler.stop_trace()
    names = {ev[0] for plane in trace.load_xplane(trace.find_xplane(
        str(tmp_path))) for line in plane["lines"] for ev in line["events"]}
    assert {"sentinel_tpu.state.init", "sentinel_tpu.batch.place"} <= names
