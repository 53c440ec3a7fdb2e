"""Subtractive ablation of the fused decision step.

The isolation profile (profile_step.py) under-reports composition costs:
components measured alone sum to far less than the fused step, because XLA
schedules/fuses them differently in context. This harness measures each
component's MARGINAL cost instead: jit the REAL step with exactly one
component stubbed out, time it chained+donated exactly like bench.py, and
read the delta vs the unmodified step. Deltas are additive up to scheduling
effects; the all-stubbed floor bounds the elementwise + dispatch residue.

Usage (from /root/repo): python benchmarks/ablate_step.py
Knobs: BENCH_RESOURCES, BENCH_BATCH, BENCH_RULES, PROF_STEPS.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax

    import jax.numpy as jnp

    import sentinel_tpu.engine.pipeline as pl
    from sentinel_tpu.core.registry import (
        OriginRegistry, Registry, ResourceRegistry,
    )
    from sentinel_tpu.engine.pipeline import (
        EngineSpec, EntryBatch, RuleSet, init_state,
    )
    from sentinel_tpu.rules import authority as auth_mod
    from sentinel_tpu.rules import degrade as deg_mod
    from sentinel_tpu.rules import flow as flow_mod
    from sentinel_tpu.rules import param_flow as pf_mod
    from sentinel_tpu.rules import system as sys_mod
    from sentinel_tpu.stats.window import WindowSpec

    R = int(os.environ.get("BENCH_RESOURCES", str(1 << 20)))
    B = int(os.environ.get("BENCH_BATCH", str(1 << 19)))
    NRULES = int(os.environ.get("BENCH_RULES", "4096"))
    STEPS = int(os.environ.get("PROF_STEPS", "20"))

    spec = EngineSpec(rows=R, alt_rows=1024,
                      second=WindowSpec(buckets=2, win_ms=500),
                      minute=None, statistic_max_rt=5000)
    resources = ResourceRegistry(R)
    origins = OriginRegistry(64)
    contexts = Registry(64, reserved=("sentinel_default_context",))
    rules = [flow_mod.FlowRule(resource=f"r{i}", count=50.0)
             for i in range(NRULES)]
    compiled = flow_mod.compile_flow_rules(
        rules, resource_registry=resources, context_registry=contexts,
        capacity=NRULES, k_per_resource=2, num_rows=R,
        origin_registry=origins)
    deg_rules = [deg_mod.DegradeRule(resource=f"r{i}",
                                     grade=deg_mod.GRADE_EXCEPTION_RATIO,
                                     count=0.5, time_window=10)
                 for i in range(min(NRULES, 1024))]
    deg = deg_mod.compile_degrade_rules(
        deg_rules, resource_registry=resources,
        capacity=max(len(deg_rules), 1), k_per_resource=2, num_rows=R)
    auth = auth_mod.compile_authority_rules(
        [], resource_registry=resources, origin_registry=origins,
        capacity=16, k_per_resource=2, num_rows=R)
    param = pf_mod.compile_param_rules(
        [], resource_registry=resources, capacity=1, k_per_resource=2)
    ruleset = RuleSet(
        flow_table=compiled.table, flow_idx=compiled.rule_idx,
        deg_table=deg.table, deg_idx=deg.rule_idx,
        auth_table=auth.table, auth_idx=auth.rule_idx,
        sys_thresholds=sys_mod.compile_system_rules([]),
        param_table=param.table)
    if os.environ.get("SCALAR_DETAIL"):
        # match the runtime's used-slot slicing AND joint rule gather —
        # the exact ruleset shape bench.py/runtime ship
        ruleset = ruleset._replace(
            flow_idx=compiled.rule_idx[:, :compiled.k_used],
            deg_idx=deg.rule_idx[:, :deg.k_used]).with_joint()

    rng = np.random.default_rng(42)
    hot = rng.integers(1, NRULES, B // 4)
    cold = rng.integers(1, R, B - B // 4)
    rows_np = np.concatenate([hot, cold]).astype(np.int32)
    rng.shuffle(rows_np)
    batch = EntryBatch(
        rows=jnp.asarray(rows_np),
        origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_),
        valid=jnp.ones(B, jnp.bool_))
    t0_ms = 1_000_000_000
    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def times_for(i):
        now = t0_ms + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms,
             now % spec.second.win_ms], np.int32))

    # ---- stubs ----
    def stub_flow_check(table, dyn, rule_idx, wspec, main_second,
                        alt_second, main_threads, alt_threads, bview,
                        now_idx_s, rel_now_ms, **kw):
        shape = bview.rows.shape
        return (dyn, jnp.ones(shape, jnp.bool_),
                jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.bool_))

    def stub_degrade_entry(table, st, rule_idx, rows, valid, rel_now_ms, **kw):
        return st, jnp.ones(rows.shape, jnp.bool_)

    def stub_auth(table, rule_idx, rows, origin_ids, valid):
        return jnp.ones(rows.shape, jnp.bool_)

    def stub_sys(thr, wspec, second, threads, is_in, acquire, valid,
                 now_idx_s, load1, cpu, max_rt):
        return jnp.ones(valid.shape, jnp.bool_)

    def stub_record_window(step, name, wspec, wstate, now_idx, touched,
                           adds):
        return wstate

    # ---- flow-internal stubs (FLOW_DETAIL=1) ----
    from sentinel_tpu.ops import segments as seg_mod

    fixed_perm = jnp.asarray(
        rng.permutation(B * compiled.rule_idx.shape[1]).astype(np.int32))

    def stub_sort_by_keys(primary, secondary=None):
        # fixed permutation: kills the argsorts but keeps every downstream
        # permutation gather/scatter real (an iota order would let XLA
        # simplify those away and overstate the sort's cost)
        return fixed_perm[:primary.shape[0]]

    def stub_unsort(order, values_sorted):
        return values_sorted

    def stub_winsum(wspec, state, rows, event, now_idx):
        return jnp.zeros(rows.shape, jnp.int32)

    def stub_warmup(table, dyn, wspec, main_second, now_idx_s, rel_now_ms,
                    minute_spec, main_minute, now_idx_m):
        return dyn, table.count

    def stub_prefix(values_sorted, starts, leader):
        z = jnp.zeros_like(values_sorted)
        return z, z

    def stub_admit(base, amounts, limit, starts, leader, iterations=3):
        return jnp.ones(base.shape, jnp.bool_)

    @contextlib.contextmanager
    def patched(**subs):
        saved = {}
        targets = {
            "flow": (pl.flow_mod, "flow_check", stub_flow_check),
            "degrade": (pl.deg_mod, "degrade_entry_check",
                        stub_degrade_entry),
            "auth": (pl.auth_mod, "authority_check", stub_auth),
            "system": (pl.sys_mod, "system_check", stub_sys),
            "recording": (pl, "_record_window", stub_record_window),
            "sort": (seg_mod, "sort_by_keys", stub_sort_by_keys),
            "unsort": (seg_mod, "unsort", stub_unsort),
            "ranks": (seg_mod, "ranks_by_key", stub_ranks),
            "flowscalar": (pl.flow_mod, "flow_check_scalar",
                           stub_flow_scalar),
            "degscalar": (pl.deg_mod, "degrade_entry_check_scalar",
                          stub_degrade_scalar),
            "winsum": (pl.flow_mod, "window_sum_rows", stub_winsum),
            "warmup": (pl.flow_mod, "_warmup_sync_and_limits",
                       stub_warmup),
            "prefix": (seg_mod, "segment_prefix_sum", stub_prefix),
            "admit": (seg_mod, "greedy_admit", stub_admit),
        }
        for name in subs:
            mod, attr, stub = targets[name]
            saved[name] = getattr(mod, attr)
            setattr(mod, attr, stub)
        try:
            yield
        finally:
            for name, orig in saved.items():
                mod, attr, _ = targets[name]
                setattr(mod, attr, orig)

    # ---- scalar-path stubs (SCALAR_DETAIL=1) ----
    def stub_ranks(key):
        return jnp.zeros_like(key)

    def stub_flow_scalar(table, dyn, rule_idx, wspec, main_second,
                         main_threads, rows, acquire, valid, now_idx_s,
                         rel_now_ms, **kw):
        return (dyn, jnp.ones(rows.shape, jnp.bool_),
                jnp.zeros(rows.shape, jnp.int32))

    def stub_degrade_scalar(table, st, rule_idx, rows, valid, rel_now_ms, **kw):
        return st, jnp.ones(rows.shape, jnp.bool_)

    results = {}

    def run(name, *stub_names, n=STEPS):
        state = init_state(spec, NRULES, max(len(deg_rules), 1))
        scalar = bool(os.environ.get("SCALAR_DETAIL"))
        kw = (dict(scalar_flow=True, scalar_has_rl=False, skip_auth=True,
                   skip_sys=True) if scalar else {})
        with patched(**{s: True for s in stub_names}):
            step = jax.jit(functools.partial(
                pl.decide_entries, spec, enable_occupy=False,
                record_alt=False, **kw), donate_argnums=(1,))
            state, v = step(ruleset, state, batch, times_for(0),
                            sys_scalars)   # trace+compile inside the patch
        _ = np.asarray(v.allow[:1])        # honest gate (idempotent)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for i in range(n):
            state, v = step(ruleset, state, batch, times_for(1 + i),
                            sys_scalars)
        jax.block_until_ready((state, v))
        dt = (time.perf_counter() - t0) / n * 1000
        results[name] = dt
        print(f"  {name:<46s} {dt:9.2f} ms", flush=True)

    print(f"ablate: R={R} B={B} NF={NRULES} steps={STEPS} "
          f"on {jax.devices()[0]}")
    if os.environ.get("SCALAR_DETAIL"):
        run("FULL")
        run("-ranks", "ranks")
        run("-flowscalar", "flowscalar")
        run("-degscalar", "degscalar")
        run("-recording", "recording")
        run("-all (floor)", "flowscalar", "degscalar", "recording")
    elif os.environ.get("FLOW_DETAIL"):
        run("FULL")
        run("-sorts", "sort")
        run("-unsorts", "unsort")
        run("-winsum", "winsum")
        run("-warmup", "warmup")
        run("-prefixsums", "prefix")
        run("-admit+prefix", "admit", "prefix")
        run("-sort-unsort-prefix", "sort", "unsort", "prefix")
    else:
        run("FULL")
        run("-flow", "flow")
        run("-degrade", "degrade")
        run("-auth-system", "auth", "system")
        run("-recording", "recording")
        run("-all (floor)", "flow", "degrade", "auth", "system",
            "recording")
    full = results["FULL"]
    print("marginal costs:")
    for k, v in results.items():
        if k.startswith("-") and k != "-all (floor)":
            print(f"  {k[1:]:<46s} {full - v:9.2f} ms")
    if "-all (floor)" in results:
        print(f"  {'floor':<46s} {results['-all (floor)']:9.2f} ms")


if __name__ == "__main__":
    main()
