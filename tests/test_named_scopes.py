"""Every stage of the jitted steps carries a ``jax.named_scope``: the token
step and the decide / exit / record-blocks steps are lowered at a tiny
size on the CPU and each scope is found in the ``op_name`` metadata of the
compiled text — what a device trace groups the operations by."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.engine.pipeline import ExitBatch
from sentinel_tpu.parallel.cluster import (
    ClusterEngine, ClusterSpec, TokenBatch,
)

N = 8


def scopes_of(compiled, prefix):
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        found.update(p for p in op_name.split("/") if p.startswith(prefix))
    return found


def test_the_token_step_names_each_stage():
    eng = ClusterEngine(ClusterSpec(n_shards=1, flows_per_shard=16,
                                    namespaces=2, param_keys_per_shard=8))
    pv, pk = eng.spec.max_params, eng.spec.param_keys_per_shard
    batch = TokenBatch(
        local_rows=np.zeros(N, np.int32), acquire=np.ones(N, np.int32),
        prioritized=np.zeros(N, bool), valid=np.ones(N, bool),
        is_param=np.zeros(N, bool),
        param_rows=np.full((N, pv), pk, np.int32),
        param_count=np.zeros((N, pv), np.float32))
    compiled = eng._step.lower(
        eng._table, eng.state, batch, jnp.asarray(eng._connected),
        jnp.asarray(eng._ns_limit), jnp.int32(5), jnp.int32(3)).compile()
    assert scopes_of(compiled, "token.") == {
        "token.ns", "token.decide", "token.param", "token.refresh",
        "token.add.pass", "token.add.block", "token.add.wait"}


@pytest.fixture(scope="module")
def engine():
    sph = stpu.Sentinel(
        config=stpu.load_config(max_resources=64, max_flow_rules=16,
                                max_degrade_rules=16, minute_enabled=True),
        clock=ManualClock(start_ms=1_785_000_000_000))
    sph.load_flow_rules([stpu.FlowRule(resource="api", count=3.0)])
    rows = np.asarray(sph.intern_resources(["api"] * N), np.int32)
    pad = np.full(N, sph.spec.alt_rows, np.int32)
    yield sph, rows, pad, sph._time_scalars(sph.clock.now_ms())
    sph.close()


STAGES = {"refresh.second", "refresh.minute", "record.second",
          "record.minute"}
ALT = {"refresh.alt_second", "record.alt_second"}


@pytest.mark.parametrize("scalar", [True, False],
                         ids=["scalar-noalt", "general-alt"])
def test_the_decide_step_names_each_stage(engine, scalar):
    sph, rows, pad, times = engine
    zeros = np.zeros(N, np.int32)
    batch = sph._build_entry_batch(
        rows, zeros, pad, zeros, pad, np.ones(N, np.int32),
        np.ones(N, bool), np.zeros(N, bool), np.ones(N, bool),
        None, None, None, None, None)
    flags = {"skip_auth": sph._skip_auth, "skip_sys": sph._skip_sys,
             "skip_threads": sph._skip_threads}
    if sph._sortfree:
        flags["sortfree"] = True
    if scalar:
        flags.update(scalar_flow=True, scalar_has_rl=sph._scalar_has_rl)
    step = sph._jit_decide_noalt if scalar else sph._jit_decide
    compiled = step.lower(
        sph._ruleset, sph._state, batch, times,
        jnp.asarray(np.zeros(2, np.float32)), **flags).compile()
    want = {"decide." + s for s in STAGES | (set() if scalar else ALT)}
    # the gate reads the breakers before the flow slot ranks (PR 35); the
    # probe election is a branch of the scalar entry check alone
    want |= {"decide.flow", "decide.degrade", "decide.degrade.gate"}
    assert scopes_of(compiled, "decide.") == want | (
        {"decide.degrade.probe"} if scalar else set())


def test_the_exit_and_record_blocks_steps_name_each_stage(engine):
    sph, rows, pad, times = engine
    xbatch = ExitBatch(
        rows=rows, origin_rows=pad, chain_rows=pad,
        acquire=np.ones(N, np.int32), rt_ms=np.ones(N, np.int32),
        error=np.zeros(N, bool), is_in=np.ones(N, bool),
        valid=np.ones(N, bool), param_rules=None, param_keys=None,
        count_thread=None)
    compiled = sph._jit_exit.lower(
        sph._ruleset, sph._state, xbatch, times,
        skip_threads=sph._skip_threads).compile()
    assert scopes_of(compiled, "exit.") == {
        "exit." + s for s in STAGES | ALT} | {"exit.degrade.feed"}
    compiled = sph._jit_record_blocks.lower(
        sph._state, rows, pad, pad, np.ones(N, np.int32), np.ones(N, bool),
        np.ones(N, bool), times).compile()
    assert scopes_of(compiled, "blocks.") == {"blocks." + s
                                              for s in STAGES | ALT}
