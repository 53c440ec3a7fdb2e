"""Per-component profile of the fused decision step on the real chip.

Times each pipeline component in isolation (same shapes as the headline
bench) plus the relevant XLA primitives, so optimization targets the real
cost centers instead of guesses.

Measurement discipline: every measurement here is a CHAINED loop (each
iteration's output feeds the next iteration's input, so the device must
actually execute N steps back-to-back) followed by ONE tiny device→host
readback; per-step cost = elapsed / N. One forced readback runs before
any timing.

Usage (from /root/repo): python benchmarks/profile_step.py
(the backend is JAX's own choice; ``JAX_PLATFORMS=cpu`` for a CPU run).
Knobs: BENCH_RESOURCES, BENCH_BATCH, BENCH_RULES, PROF_STEPS.
"""

from __future__ import annotations

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
    from sentinel_tpu.stats.window import (
        WindowSpec, bucket_add_events, bucket_add_row, close_bucket,
        open_bucket, window_sum_rows,
    )
    from sentinel_tpu.stats import events as ev_mod

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
    state = init_state(spec, NRULES, max(len(deg_rules), 1))

    rng = np.random.default_rng(42)
    hot = rng.integers(1, NRULES, B // 4)
    cold = rng.integers(1, R, B - B // 4)
    rows_np = np.concatenate([hot, cold]).astype(np.int32)
    rng.shuffle(rows_np)
    rows = jnp.asarray(rows_np)
    batch = EntryBatch(
        rows=rows,
        origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_),
        valid=jnp.ones(B, jnp.bool_))
    t0_ms = 1_000_000_000
    times_arr = jnp.asarray(np.array(
        [spec.second.index_of(t0_ms), 0, 0, t0_ms % spec.second.win_ms],
        np.int32))
    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    # warm state + honest-mode gate (process-wide)
    warm = jax.jit(functools.partial(decide_entries, spec,
                                     enable_occupy=False, record_alt=False))
    state, v = warm(ruleset, state, batch, times_arr, sys_scalars)
    _ = np.asarray(v.allow[:1])
    jax.block_until_ready(state)

    results = {}

    def readback_leaf(x):
        leaves = jax.tree_util.tree_leaves(x)
        a = leaves[0]
        return np.asarray(a.reshape(-1)[:1])

    def bench(name, step_fn, carry, n=STEPS):
        """step_fn: carry -> carry (chained). One readback at the end."""
        c = step_fn(carry)
        c = step_fn(c)
        _ = readback_leaf(c)
        t0 = time.perf_counter()
        for _ in range(n):
            c = step_fn(c)
        _ = readback_leaf(c)
        jax.block_until_ready(c)
        dt = (time.perf_counter() - t0) / n * 1000
        results[name] = dt
        print(f"  {name:<44s} {dt:9.2f} ms", flush=True)
        return c

    print(f"profile: R={R} B={B} NF={NRULES} on {jax.devices()[0]}")

    # ---- dispatch floor: chained trivial op ----
    bench("chained_tiny_add (dispatch floor)",
          jax.jit(lambda x: x + 1), jnp.zeros((8,), jnp.int32))

    # ---- primitives (chained through their own outputs) ----
    keys1m = jnp.asarray(rng.integers(0, NRULES, 2 * B).astype(np.int32))
    bench("argsort_1M_int32", jax.jit(
        lambda k: jnp.argsort(k, stable=True) % NRULES), keys1m)
    keys512k = jnp.asarray(rng.integers(0, NRULES, B).astype(np.int32))
    bench("argsort_512k_int32", jax.jit(
        lambda k: jnp.argsort(k, stable=True) % NRULES), keys512k)
    rows512k = jnp.asarray(rng.integers(0, R, B).astype(np.int32))
    bench("argsort_512k_rowkeys (0..1M)", jax.jit(
        lambda k: jnp.argsort(k, stable=True) % R), rows512k)

    pairs_rows = jnp.asarray(rng.integers(0, R, 2 * B).astype(np.int32))
    bench("window_sum_rows_1Mpairs", jax.jit(
        lambda pr: window_sum_rows(
            spec.second, state.second, pr, ev_mod.PASS,
            times_arr[0]) % R), pairs_rows)
    bench("gather_1M_from_1Mvec", jax.jit(
        lambda i: state.threads[i] % R + i % 7), pairs_rows)
    bench("unsort_scatter_1M", jax.jit(
        lambda x: jnp.zeros_like(x).at[keys1m].set(x) % R), pairs_rows)
    bench("cumsum_1M_f32", jax.jit(
        lambda x: jnp.cumsum(x) % 1000.0),
        jnp.ones((2 * B,), jnp.float32))

    def scat_chain(c):
        return c.at[rows, 0, 0].add(1, mode="drop")

    bench("scatter_add_512k_to_1Mtable",
          jax.jit(scat_chain), state.second.counters)

    # ---- components (chained through their state) ----
    cl_fb = jnp.zeros(B, jnp.int32)
    fview = flow_mod.FlowBatchView(
        rows=batch.rows, origin_ids=batch.origin_ids,
        origin_rows=batch.origin_rows, context_ids=batch.context_ids,
        chain_rows=batch.chain_rows, acquire=batch.acquire,
        valid=batch.valid, prioritized=batch.prioritized,
        cluster_fallback=cl_fb)

    def flow_step(carry):
        dyn, _ = carry
        dyn2, allow, wait, occ = flow_mod.flow_check(
            ruleset.flow_table, dyn, ruleset.flow_idx, spec.second,
            state.second, state.alt_second, state.threads,
            state.alt_threads, fview, times_arr[0], times_arr[2],
            in_win_ms=times_arr[3],
            occupy_timeout_ms=spec.occupy_timeout_ms, enable_occupy=False)
        return dyn2, allow

    bench("flow_check", jax.jit(flow_step), (state.flow_dyn, None))

    def deg_step(carry):
        br, _ = carry
        br2, allow = deg_mod.degrade_entry_check(
            ruleset.deg_table, br, ruleset.deg_idx, batch.rows,
            batch.valid, times_arr[2])
        return br2, allow

    bench("degrade_entry_check", jax.jit(deg_step), (state.breakers, None))

    def auth_sys_step(carry):
        a = auth_mod.authority_check(
            ruleset.auth_table, ruleset.auth_idx, batch.rows,
            batch.origin_ids, carry)
        s = sys_mod.system_check(
            ruleset.sys_thresholds, spec.second, state.second,
            state.threads, batch.is_in, batch.acquire, a, times_arr[0],
            sys_scalars[0], sys_scalars[1], spec.statistic_max_rt)
        return a & s

    bench("authority+system", jax.jit(auth_sys_step), batch.valid)

    def record_step(carry):
        second, threads = carry
        ev_ids = jnp.where(batch.valid, jnp.int32(ev_mod.PASS),
                           jnp.int32(ev_mod.BLOCK))
        amt = jnp.where(batch.valid, batch.acquire, 0)
        tgt = jnp.where(batch.valid, batch.rows, jnp.int32(R))
        n_ev = second.counters.shape[2]
        entry_vec = jnp.zeros((n_ev,), jnp.int32).at[ev_mod.PASS].set(
            jnp.sum(amt))
        bucket = open_bucket(spec.second, second, times_arr[0])
        bucket = bucket_add_events(bucket, tgt, ev_ids, amt)
        bucket = bucket_add_row(bucket, 0, entry_vec)
        sec = close_bucket(spec.second, second, bucket, times_arr[0])
        thr = threads.at[tgt].add(jnp.where(batch.valid, 1, 0),
                                  mode="drop")
        return sec, thr

    bench("recording(second+threads)",
          jax.jit(record_step, donate_argnums=(0,)),
          (state.second, state.threads))

    def full_step(carry):
        st, _ = carry
        st2, verd = decide_entries(
            spec, ruleset, st, batch, times_arr, sys_scalars,
            enable_occupy=False, record_alt=False)
        return st2, verd

    bench("FULL decide_entries",
          jax.jit(full_step, donate_argnums=(0,)), (state, None))

    # round 16 — the single-dispatch serving program: the count-min
    # observe scatter fused behind decide_entries in the SAME program
    # (runtime._build_sd_steps). The delta vs FULL decide_entries is the
    # marginal cost of the fused observe; the saved standalone dispatch
    # is the chained_tiny_add floor above.
    from sentinel_tpu.tiering import sketch as sk_mod

    def fused_sd_step(carry):
        st, counts, _ = carry
        st2, verd = decide_entries(
            spec, ruleset, st, batch, times_arr, sys_scalars,
            enable_occupy=False, record_alt=False)
        counts2, _est = sk_mod.update_sketch(counts, batch.rows,
                                             batch.valid)
        return st2, counts2, verd

    # fresh state: the FULL bench above donated (consumed) its carry
    sd_state = init_state(spec, NRULES, max(len(deg_rules), 1))
    bench("FULL decide+sketch_observe (fused sd)",
          jax.jit(fused_sd_step, donate_argnums=(0,)),
          (sd_state, sk_mod.init_sketch(), None))

    comp = (results.get("flow_check", 0)
            + results.get("degrade_entry_check", 0)
            + results.get("authority+system", 0)
            + results.get("recording(second+threads)", 0))
    print(f"  {'sum of components':<44s} {comp:9.2f} ms")


if __name__ == "__main__":
    main()
