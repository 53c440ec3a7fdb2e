"""Batch x geometry scaling study for the headline decision step.

Sweeps B (events/step) x R (resource rows) on the current device with the
same honest measurement discipline as bench.py (chained+donated steps, one
readback before and after the timed region), and prints one JSON line per
cell plus a final recommendation for bench.py's default batch size.

Usage (from /root/repo): python benchmarks/scaling_study.py
Knobs: SCALE_BS / SCALE_RS (comma lists), SCALE_STEPS.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def one_cell(R: int, B: int, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.registry import (
        OriginRegistry, Registry, ResourceRegistry,
    )
    from sentinel_tpu.engine.pipeline import (
        EngineSpec, EntryBatch, RuleSet, decide_entries, init_state,
    )
    from sentinel_tpu.rules import authority as auth_mod
    from sentinel_tpu.rules import degrade as deg_mod
    from sentinel_tpu.rules import flow as flow_mod
    from sentinel_tpu.rules import param_flow as pf_mod
    from sentinel_tpu.rules import system as sys_mod
    from sentinel_tpu.stats.window import WindowSpec

    NRULES = min(4096, R // 4)
    spec = EngineSpec(rows=R, alt_rows=1024,
                      second=WindowSpec(buckets=2, win_ms=500),
                      minute=None, statistic_max_rt=5000)
    res = ResourceRegistry(R)
    org = OriginRegistry(64)
    ctx = Registry(64, reserved=("sentinel_default_context",))
    rules = [flow_mod.FlowRule(resource=f"r{i}", count=50.0)
             for i in range(NRULES)]
    flow = flow_mod.compile_flow_rules(
        rules, resource_registry=res, context_registry=ctx,
        capacity=NRULES, k_per_resource=2, num_rows=R,
        origin_registry=org)
    deg = deg_mod.compile_degrade_rules(
        [deg_mod.DegradeRule(resource=f"r{i}",
                             grade=deg_mod.GRADE_EXCEPTION_RATIO,
                             count=0.5, time_window=10)
         for i in range(min(NRULES, 1024))],
        resource_registry=res, capacity=min(NRULES, 1024),
        k_per_resource=2, num_rows=R)
    auth = auth_mod.compile_authority_rules(
        [], resource_registry=res, origin_registry=org, capacity=16,
        k_per_resource=2, num_rows=R)
    param = pf_mod.compile_param_rules([], resource_registry=res,
                                       capacity=1, k_per_resource=2)
    ruleset = RuleSet(
        flow_table=flow.table, flow_idx=flow.rule_idx[:, :1],
        deg_table=deg.table, deg_idx=deg.rule_idx[:, :1],
        auth_table=auth.table, auth_idx=auth.rule_idx,
        sys_thresholds=sys_mod.compile_system_rules([]),
        param_table=param.table)
    state = init_state(spec, NRULES, min(NRULES, 1024))
    rng = np.random.default_rng(42)
    hot = rng.integers(1, NRULES, B // 4)
    cold = rng.integers(1, R, B - B // 4)
    rows = np.concatenate([hot, cold]).astype(np.int32)
    rng.shuffle(rows)
    batch = EntryBatch(
        rows=jnp.asarray(rows),
        origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_),
        valid=jnp.ones(B, jnp.bool_))
    step = jax.jit(functools.partial(
        decide_entries, spec, enable_occupy=False, record_alt=False,
        scalar_flow=True, scalar_has_rl=False, skip_auth=True,
        skip_sys=True), donate_argnums=(1,))
    t0_ms = 1_000_000_000
    sysv = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def scalars(i):
        now = t0_ms + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms, now % 500],
            np.int32))

    for i in range(3):
        state, v = step(ruleset, state, batch, scalars(i), sysv)
    _ = np.asarray(v.allow[:1])          # honest gate
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(steps):
        state, v = step(ruleset, state, batch, scalars(3 + i), sysv)
    jax.block_until_ready((state, v))
    dt = time.perf_counter() - t0
    return {"R": R, "B": B, "steps": steps,
            "step_ms": round(dt / steps * 1000, 2),
            "decisions_per_sec": round(B * steps / dt, 0)}


def main() -> None:
    import jax
    bs = [int(x) for x in os.environ.get(
        "SCALE_BS", "131072,262144,524288,1048576,2097152").split(",")]
    rs = [int(x) for x in os.environ.get(
        "SCALE_RS", "65536,262144,1048576").split(",")]
    steps = int(os.environ.get("SCALE_STEPS", "30"))
    print(f"scaling study on {jax.devices()[0]}", file=sys.stderr)
    best = None
    for R in rs:
        for B in bs:
            cell = one_cell(R, B, steps)
            print(json.dumps(cell), flush=True)
            if R == max(rs) and (best is None
                                 or cell["decisions_per_sec"]
                                 > best["decisions_per_sec"]):
                best = cell
    print(json.dumps({"recommended_batch_at_Rmax": best["B"],
                      "rate": best["decisions_per_sec"]}))


if __name__ == "__main__":
    main()
