"""The five BASELINE.json benchmark configs (SURVEY §6 — establish, don't
reproduce: the reference publishes no numbers).

Run: ``python benchmarks/run_all.py`` → one JSON line per config.
Sizes shrink via ``BENCH_SMALL=1`` for smoke runs. ``bench.py`` at the repo
root stays the driver's single headline metric; this harness is the wider
JMH-equivalent matrix.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _env(name, default):
    return int(os.environ.get(name, str(default)))


SMALL = os.environ.get("BENCH_SMALL") == "1"
# N timed regions per config: every throughput figure below is a median
# over REPEATS regions with a band, so a regression is a shifted band
REPEATS = int(os.environ.get("BENCH_REPEATS", "1" if SMALL else "3"))


def _band(rates):
    """(median, band_min, band_max, runs) for a list of per-region rates."""
    s = sorted(rates)
    return (round(s[len(s) // 2], 0), round(s[0], 0), round(s[-1], 0),
            len(s))


def _run_pipelined(dispatch, steps: int, depth: int):
    """Depth-N double-buffered driver: ``dispatch(s)`` returns a handle
    with ``.result()``. → ``(dt, t_dispatch, t_read, lat)`` with the drain
    included in ``dt`` (all work completes inside the timed region), the
    per-step timers split into dispatch vs readback-stall, and ``lat[s]`` =
    dispatch→verdict-materialized latency of step s — pipelining trades this
    per-grant latency for throughput (a verdict sits in flight while up to
    ``depth-1`` younger steps dispatch), so it is reported, not hidden."""
    from collections import deque

    t_dispatch = 0.0
    t_read = 0.0
    inflight = deque()               # (step, t_dispatched, handle)
    lat = np.empty(steps)
    t0 = time.perf_counter()
    for s in range(steps):
        td = time.perf_counter()
        inflight.append((s, td, dispatch(s)))
        t_dispatch += time.perf_counter() - td
        if len(inflight) >= depth:
            tr = time.perf_counter()
            i, ts, h = inflight.popleft()
            h.result()
            now = time.perf_counter()
            t_read += now - tr
            lat[i] = now - ts
    while inflight:
        tr = time.perf_counter()
        i, ts, h = inflight.popleft()
        h.result()
        now = time.perf_counter()
        t_read += now - tr
        lat[i] = now - ts
    return time.perf_counter() - t0, t_dispatch, t_read, lat


def _pcts(lat):
    """p50/p99 of per-step latencies in ms (a caller's grant waits the whole
    batch round-trip, so batch latency IS the per-grant latency)."""
    return (round(float(np.percentile(lat, 50)) * 1000, 3),
            round(float(np.percentile(lat, 99)) * 1000, 3))


def bench_entry_latency():
    """Config 1 — FlowQpsDemo semantics on the single-entry tier: the
    per-call decide round-trip (the p99 grant-latency budget)."""
    import sentinel_tpu as stpu

    sph = stpu.Sentinel(stpu.load_config(
        max_resources=1024, max_flow_rules=64, max_degrade_rules=64,
        max_authority_rules=16))
    sph.load_flow_rules([stpu.FlowRule(resource="HelloWorld", count=1e9)])
    n = 50 if SMALL else 500
    for _ in range(20):                     # warm the trace + caches
        with sph.entry("HelloWorld"):
            pass
    lat = np.empty(n)
    for i in range(n):
        t0 = time.perf_counter()
        with sph.entry("HelloWorld"):
            pass
        lat[i] = time.perf_counter() - t0
    return {
        "config": "1-entry-latency",
        "p50_ms": round(float(np.percentile(lat, 50)) * 1000, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1000, 3),
        "target_p99_ms": 2.0,
    }


def _mixed_engine(R, NRULES):
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

    spec = EngineSpec(rows=R, alt_rows=1024,
                      second=WindowSpec(buckets=2, win_ms=500),
                      minute=None, statistic_max_rt=5000)
    res = ResourceRegistry(R)
    org = OriginRegistry(64)
    ctxr = Registry(64, reserved=("c",))
    return spec, res, org, ctxr, flow_mod, deg_mod, auth_mod, sys_mod, pf_mod


def bench_all_controllers():
    """Config 2 — Default/WarmUp/RateLimiter mix over 10k resources."""
    import jax
    import jax.numpy as jnp
    from sentinel_tpu.engine.pipeline import (
        EntryBatch, RuleSet, decide_entries, init_state,
    )

    R = 1 << 11 if SMALL else 1 << 14
    NR = 256 if SMALL else 8192
    # B sits at the same 512k knee as the headline bench: at 32k-event
    # steps the band was dispatch-weather-bound (non-overlapping 5.14M vs
    # 8.60M on unchanged code); at 512k the device dominates and the band
    # tightens. STEPS scales down to keep total work comparable.
    B = 1 << 10 if SMALL else 1 << 19
    STEPS = 10 if SMALL else 15
    (spec, res, org, ctxr, flow_mod, deg_mod, auth_mod, sys_mod,
     pf_mod) = _mixed_engine(R, NR)
    behaviors = [flow_mod.BEHAVIOR_DEFAULT, flow_mod.BEHAVIOR_WARM_UP,
                 flow_mod.BEHAVIOR_RATE_LIMITER]
    rules = [flow_mod.FlowRule(resource=f"r{i}", count=50.0,
                               control_behavior=behaviors[i % 3])
             for i in range(NR)]
    flow = flow_mod.compile_flow_rules(
        rules, resource_registry=res, context_registry=ctxr, capacity=NR,
        k_per_resource=4, num_rows=R, origin_registry=org)
    deg = deg_mod.compile_degrade_rules([], resource_registry=res,
                                        capacity=16, k_per_resource=4,
                                        num_rows=R)
    auth = auth_mod.compile_authority_rules(
        [], resource_registry=res, origin_registry=org, capacity=16,
        k_per_resource=4, num_rows=R)
    param = pf_mod.compile_param_rules([], resource_registry=res,
                                       capacity=16, k_per_resource=4)
    ruleset = RuleSet(flow_table=flow.table,
                      flow_idx=flow.rule_idx[:, :1],  # 1 rule/resource:
                      # the runtime's used-slot slicing (_build_ruleset)
                      deg_table=deg.table, deg_idx=deg.rule_idx[:, :1],
                      auth_table=auth.table, auth_idx=auth.rule_idx,
                      sys_thresholds=sys_mod.compile_system_rules([]),
                      param_table=param.table).with_joint()
    state = init_state(spec, NR, 16)
    rng = np.random.default_rng(0)
    batch = EntryBatch(
        rows=jnp.asarray(rng.integers(1, NR, B).astype(np.int32)),
        origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32), is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    # same static variant the runtime selects for this batch shape:
    # alt-free + uniform acquire + no origins → scalar path (with RL
    # rules present), empty auth/system slots skipped, thread gauges
    # elided (no THREAD/system rules)
    step = jax.jit(functools.partial(decide_entries, spec,
                                     enable_occupy=False, record_alt=False,
                                     scalar_flow=True, scalar_has_rl=True,
                                     skip_auth=True, skip_sys=True,
                                     skip_threads=True),
                   donate_argnums=(1,))
    sysv = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def times(i):
        now = 10_000_000 + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now, now % 500], np.int32))

    for i in range(3):
        state, v = step(ruleset, state, batch, times(i), sysv)
    # one forced readback before the timed regions
    np.asarray(v.allow[:1])
    jax.block_until_ready(state)
    rates, disp_ms, dev_ms = [], [], []
    tick = 3
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        t_disp = 0.0
        for i in range(STEPS):
            td = time.perf_counter()
            state, v = step(ruleset, state, batch, times(tick), sysv)
            tick += 1
            t_disp += time.perf_counter() - td
        jax.block_until_ready((state, v))
        dt = time.perf_counter() - t0
        rates.append(B * STEPS / dt)
        disp_ms.append(t_disp / STEPS * 1000)
        dev_ms.append((dt - t_disp) / STEPS * 1000)
    med, lo, hi, n = _band(rates)
    # dispatch returns async: total >> dispatch ⇒ the run is device-bound
    return {"config": "2-all-controllers-10k-resources",
            "decisions_per_sec": med, "band_min": lo, "band_max": hi,
            "runs": n,
            "host_dispatch_ms_per_step": round(
                sorted(disp_ms)[n // 2], 3),
            "device_bound_ms_per_step": round(
                sorted(dev_ms)[n // 2], 3)}


def bench_breakers():
    """Config 3 — circuit breaking (slow-ratio + error-ratio) with exits."""
    import jax
    import jax.numpy as jnp
    from sentinel_tpu.engine.pipeline import (
        EntryBatch, ExitBatch, RuleSet, decide_entries, init_state,
        record_exits,
    )
    from sentinel_tpu.rules import degrade as deg_mod

    R = 1 << 11 if SMALL else 1 << 17
    ND = 256 if SMALL else 4096
    B = 1 << 10 if SMALL else 1 << 14
    STEPS = 10 if SMALL else 100
    (spec, res, org, ctxr, flow_mod, deg_mod, auth_mod, sys_mod,
     pf_mod) = _mixed_engine(R, ND)
    dr = []
    for i in range(ND):
        if i % 2:
            dr.append(deg_mod.DegradeRule(
                resource=f"r{i}", grade=deg_mod.GRADE_RT, count=50,
                time_window=10))
        else:
            dr.append(deg_mod.DegradeRule(
                resource=f"r{i}", grade=deg_mod.GRADE_EXCEPTION_RATIO,
                count=0.5, time_window=10))
    flow = flow_mod.compile_flow_rules(
        [], resource_registry=res, context_registry=ctxr, capacity=16,
        k_per_resource=4, num_rows=R, origin_registry=org)
    deg = deg_mod.compile_degrade_rules(dr, resource_registry=res,
                                        capacity=ND, k_per_resource=4,
                                        num_rows=R)
    auth = auth_mod.compile_authority_rules(
        [], resource_registry=res, origin_registry=org, capacity=16,
        k_per_resource=4, num_rows=R)
    param = pf_mod.compile_param_rules([], resource_registry=res,
                                       capacity=16, k_per_resource=4)
    ruleset = RuleSet(flow_table=flow.table, flow_idx=flow.rule_idx[:, :1],
                      deg_table=deg.table, deg_idx=deg.rule_idx[:, :1],
                      auth_table=auth.table, auth_idx=auth.rule_idx,
                      sys_thresholds=sys_mod.compile_system_rules([]),
                      param_table=param.table).with_joint()
    state = init_state(spec, 16, ND)
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(1, ND, B).astype(np.int32))
    ebatch = EntryBatch(
        rows=rows, origin_ids=jnp.zeros(B, jnp.int32),
        origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        context_ids=jnp.zeros(B, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32), is_in=jnp.ones(B, jnp.bool_),
        prioritized=jnp.zeros(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    xbatch = ExitBatch(
        rows=rows, origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
        acquire=jnp.ones(B, jnp.int32),
        rt_ms=jnp.asarray(rng.integers(1, 200, B).astype(np.int32)),
        error=jnp.asarray(rng.random(B) < 0.3),
        is_in=jnp.ones(B, jnp.bool_), valid=jnp.ones(B, jnp.bool_))
    # same static variants the runtime selects for alt-free traffic
    # (thread gauges elided: degrade-only ruleset has no gauge readers)
    kw = dict(enable_occupy=False, record_alt=False, scalar_flow=True,
              scalar_has_rl=False, skip_auth=True, skip_sys=True,
              skip_threads=True)
    step = jax.jit(functools.partial(decide_entries, spec, **kw))
    exit_step = jax.jit(functools.partial(record_exits, spec,
                                          record_alt=False,
                                          skip_threads=True))
    sysv = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def times(i):
        now = 10_000_000 + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now, now % 500], np.int32))

    # decide, then exit: the two dispatches a serving step makes
    state, v0 = step(ruleset, state, ebatch, times(0), sysv)
    state = exit_step(ruleset, state, xbatch, times(0))
    np.asarray(v0.allow[:1])     # honest-mode gate (see bench.py)
    jax.block_until_ready(state)
    tick = 1
    rates, disp_ms, dev_ms = [], [], []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        t_disp = 0.0
        for i in range(STEPS):
            td = time.perf_counter()
            state, v = step(ruleset, state, ebatch, times(tick), sysv)
            state = exit_step(ruleset, state, xbatch, times(tick))
            tick += 1
            t_disp += time.perf_counter() - td
        jax.block_until_ready((state, v))
        dt = time.perf_counter() - t0
        rates.append(B * STEPS / dt)
        disp_ms.append(t_disp / STEPS * 1000)
        dev_ms.append((dt - t_disp) / STEPS * 1000)
    med, lo, hi, n = _band(rates)
    return {"config": "3-circuit-breakers-entry+exit",
            "entry_exit_pairs_per_sec": med,
            "band_min": lo, "band_max": hi, "runs": n,
            "host_dispatch_ms_per_step": round(
                sorted(disp_ms)[n // 2], 3),
            "device_bound_ms_per_step": round(
                sorted(dev_ms)[n // 2], 3)}


def bench_hot_param_zipf(B_override=None):
    """Config 4 — hot-param throttling over Zipf-skewed keys.

    Double-buffered: ``entry_batch_nowait`` dispatches step s+1..s+DEPTH
    while step s's verdicts are still in flight, hiding the device→host
    readback behind the next step's host prep. The decomposition fields
    show what remains on the critical path (host prep+dispatch vs
    readback stalls).

    Serving batch default 65536; where throughput and grant latency
    trade off on a host-attached chip is not measured — rerun the curve
    (BENCH_SERVE_CURVE=1) there. Override: BENCH_SERVE_B."""
    import sentinel_tpu as stpu

    K = 1 << 12 if SMALL else 1 << 16
    B = B_override or (512 if SMALL else _env("BENCH_SERVE_B", 1 << 16))
    STEPS = 5 if SMALL else 50
    DEPTH = _env("BENCH_PIPE_DEPTH", 8)
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=256, max_flow_rules=16, max_degrade_rules=16,
        max_authority_rules=16, max_param_rules=16,
        param_table_slots=K))
    sph.load_param_flow_rules([stpu.ParamFlowRule(
        resource="hot", param_idx=0, count=1000)])
    rng = np.random.default_rng(0)
    sync_steps = min(STEPS, 10)
    total = 2 + (sync_steps + STEPS) * REPEATS
    # 2D int array form: the fastest args_list shape (vectorized key
    # resolution; distinct keys intern through the native
    # i64_get_or_create_batch table in one FFI call)
    keys = (rng.zipf(1.2, size=B * total) % (K // 2)).reshape(total, B, 1)
    # pre-staged rows: intern the (constant) resource set once; per-step
    # host prep no longer encodes B strings (the config-4 hotspot — host
    # prep was ~10x the device time at 256k before this)
    resources = sph.intern_resources(["hot"] * B)
    for s in range(2):
        sph.entry_batch(resources, args_list=keys[s])
    tick = 2
    # sync reference point (per-step verdict readback on the critical path);
    # per-call latency here IS the per-grant latency a sync caller sees
    sync_rates, sync_lats = [], []
    for _ in range(REPEATS):
        sync_lat = np.empty(sync_steps)
        t0 = time.perf_counter()
        for s in range(sync_steps):
            ts = time.perf_counter()
            sph.entry_batch(resources, args_list=keys[tick])
            tick += 1
            sync_lat[s] = time.perf_counter() - ts
        sync_rates.append(B * sync_steps / (time.perf_counter() - t0))
        sync_lats.append(sync_lat)

    pipe_rates, pipe_lats, disp_ms, read_ms = [], [], [], []
    for _ in range(REPEATS):
        base = tick

        def dispatch(s):
            return sph.entry_batch_nowait(resources,
                                          args_list=keys[base + s])

        dt, t_dispatch, t_read, lat = _run_pipelined(dispatch, STEPS,
                                                     DEPTH)
        tick += STEPS
        pipe_rates.append(B * STEPS / dt)
        pipe_lats.append(lat)
        disp_ms.append(t_dispatch / STEPS * 1000)
        read_ms.append(t_read / STEPS * 1000)
    sp50, sp99 = _pcts(np.concatenate(sync_lats))
    pp50, pp99 = _pcts(np.concatenate(pipe_lats))
    med, lo, hi, n = _band(pipe_rates)
    smed, slo, shi, _ = _band(sync_rates)
    return {"config": "4-hot-param-zipf", "batch": B,
            "param_checks_per_sec": med,
            "band_min": lo, "band_max": hi, "runs": n,
            "sync_checks_per_sec": smed, "sync_band": [slo, shi],
            "pipeline_depth": DEPTH,
            "sync_grant_p50_ms": sp50, "sync_grant_p99_ms": sp99,
            "pipelined_grant_p50_ms": pp50, "pipelined_grant_p99_ms": pp99,
            "budget_ms": 20.0,          # ClusterConstants DEFAULT_REQUEST_TIMEOUT
            # medians over the same regions as the rate band, so the
            # decomposition explains the number beside it
            "host_prep_dispatch_ms_per_step": round(
                sorted(disp_ms)[n // 2], 3),
            "readback_stall_ms_per_step": round(
                sorted(read_ms)[n // 2], 3)}


def bench_cluster_tokens(B_override=None):
    """Config 5 — cluster token grants on the sharded engine.

    Serving batch default 65536 (same rationale as config 4;
    BENCH_SERVE_B overrides)."""
    from sentinel_tpu.parallel.cluster import (
        THRESHOLD_GLOBAL, ClusterEngine, ClusterFlowRule, ClusterSpec,
    )
    import jax

    n_shards = min(8, len(jax.devices()))
    FL = 64 if SMALL else 512
    B = B_override or (256 if SMALL else _env("BENCH_SERVE_B", 1 << 16))
    STEPS = 5 if SMALL else 50
    eng = ClusterEngine(ClusterSpec(n_shards=n_shards,
                                    flows_per_shard=max(FL // n_shards, 16),
                                    namespaces=4))
    eng.load_rules("ns", [ClusterFlowRule(flow_id=i, count=1e9,
                                          threshold_type=THRESHOLD_GLOBAL)
                          for i in range(FL)])
    rng = np.random.default_rng(0)
    # numpy id/acquire form: vectorized request grouping (argsort+scatter,
    # no per-event dict loops)
    ids = rng.integers(0, FL, B)
    ones = np.ones(B, np.int64)
    now = 10_000_000
    eng.request_tokens(ids, ones, now_ms=now)
    tick = 1
    sync_steps = min(STEPS, 10)
    sync_rates, sync_lats = [], []
    for _ in range(REPEATS):
        sync_lat = np.empty(sync_steps)
        t0 = time.perf_counter()
        for s in range(sync_steps):
            ts = time.perf_counter()
            eng.request_tokens(ids, ones, now_ms=now + tick)
            tick += 1
            sync_lat[s] = time.perf_counter() - ts
        sync_rates.append(B * sync_steps / (time.perf_counter() - t0))
        sync_lats.append(sync_lat)
    # double-buffered grants: dispatch N+1..N+DEPTH while N reads back
    DEPTH = _env("BENCH_PIPE_DEPTH", 8)
    pipe_rates, pipe_lats, disp_ms, read_ms = [], [], [], []
    for _ in range(REPEATS):
        base = tick
        dt, t_dispatch, t_read, lat = _run_pipelined(
            lambda s: eng.request_tokens_nowait(
                ids, ones, now_ms=now + base + s),
            STEPS, DEPTH)
        tick += STEPS
        pipe_rates.append(B * STEPS / dt)
        pipe_lats.append(lat)
        disp_ms.append(t_dispatch / STEPS * 1000)
        read_ms.append(t_read / STEPS * 1000)
    sp50, sp99 = _pcts(np.concatenate(sync_lats))
    pp50, pp99 = _pcts(np.concatenate(pipe_lats))
    med, lo, hi, n = _band(pipe_rates)
    smed, slo, shi, _ = _band(sync_rates)
    return {"config": "5-cluster-token-grants",
            "shards": n_shards, "batch": B,
            "grants_per_sec": med,
            "band_min": lo, "band_max": hi, "runs": n,
            "sync_grants_per_sec": smed, "sync_band": [slo, shi],
            "pipeline_depth": DEPTH,
            "sync_grant_p50_ms": sp50, "sync_grant_p99_ms": sp99,
            "pipelined_grant_p50_ms": pp50, "pipelined_grant_p99_ms": pp99,
            "budget_ms": 20.0,          # ClusterConstants DEFAULT_REQUEST_TIMEOUT
            # medians over the same regions as the rate band
            "host_prep_dispatch_ms_per_step": round(
                sorted(disp_ms)[n // 2], 3),
            "readback_stall_ms_per_step": round(
                sorted(read_ms)[n // 2], 3)}


def serve_curve() -> None:
    """BENCH_SERVE_CURVE=1: configs 4/5 across serving batch sizes — one
    JSON line per (config, B), to be read against the reference's 20 ms
    request budget (ClusterConstants.DEFAULT_REQUEST_TIMEOUT)."""
    for B in (1 << 12, 1 << 14, 1 << 16, 1 << 18):
        for fn in (bench_hot_param_zipf, bench_cluster_tokens):
            print(json.dumps(fn(B_override=B)), flush=True)


def main() -> None:
    if os.environ.get("BENCH_SERVE_CURVE") == "1":
        serve_curve()
        return
    for fn in (bench_entry_latency, bench_all_controllers, bench_breakers,
               bench_hot_param_zipf, bench_cluster_tokens):
        print(json.dumps(fn()), flush=True)


if __name__ == "__main__":
    main()
