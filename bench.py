"""Headline benchmark: pass/block decisions/sec @ 1M resources, one chip.

BASELINE.json primary metric. Measures the fused decision pipeline (the full
slot chain: authority → system → flow → degrade → statistics recording) as a
jitted device step over a 1M-row counter tensor, with pre-staged event batches
so the number is device throughput, not host marshalling.

North star (BASELINE.json): ≥50M decisions/sec across 1M resources on a
v5e-8 ⇒ 6.25M/sec/chip. ``vs_baseline`` = measured / 6.25e6.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Knobs via env: BENCH_RESOURCES, BENCH_BATCH, BENCH_STEPS, BENCH_RULES,
BENCH_SHARDS (>1 row-shards the counter tensors over that many devices via
parallel/local_shard.py — the product multi-chip mode; requires that many
visible devices, e.g. the 8-virtual-device CPU harness or a real pod).

The artifact always carries a ``mesh`` block (device count, rows per
device, sharded-vs-replicated state leaf counts, donation/staging knob
state) so the 1-chip run is a self-describing comparison row, and — on
sharded runs or under BENCH_WEAK_SCALING=1 — a ``weak_scaling`` block:
the 1/2/4/8-device fixed-rows-per-device curve through the runtime with
its normalized flatness ratios (benchmarks/weak_scaling.py).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np


def scatter_ab() -> None:
    """BENCH_SCATTER={xla,pallas}: the counter-table scatter-add microbench
    (SURVEY §7 phase 1 'Pallas streaming scatter kernel' — A/B'd against
    XLA's native scatter). Knobs: BENCH_SCATTER_K (table rows),
    BENCH_SCATTER_N (event-stream length), BENCH_SCATTER_E (event lanes).
    Prints the standard one-JSON-line; see benchmarks/scatter_ab.py for the
    full shape sweep."""
    import time

    import jax
    import jax.numpy as jnp

    from sentinel_tpu.ops.pallas_kernels import (
        scatter_add_pallas, scatter_add_xla,
    )

    backend = os.environ["BENCH_SCATTER"]
    K = int(os.environ.get("BENCH_SCATTER_K", str(1 << 12)))
    N = int(os.environ.get("BENCH_SCATTER_N", str(1 << 16)))
    E = int(os.environ.get("BENCH_SCATTER_E", "8"))
    STEPS = int(os.environ.get("BENCH_STEPS", "50"))

    rng = np.random.default_rng(0)
    counters = jnp.zeros((K, E), jnp.int32)
    keys = jnp.asarray(rng.integers(0, K, N).astype(np.int32))
    events = jnp.asarray(rng.integers(0, E, N).astype(np.int32))
    amounts = jnp.asarray(rng.integers(1, 3, N).astype(np.int32))

    if backend == "pallas":
        interp = jax.devices()[0].platform != "tpu"
        fn = jax.jit(functools.partial(scatter_add_pallas, interpret=interp))
    elif backend == "xla":
        fn = jax.jit(scatter_add_xla)
    else:
        raise SystemExit(f"BENCH_SCATTER must be xla|pallas, got {backend}")

    for _ in range(3):
        counters = fn(counters, keys, events, amounts)
    # one forced readback before the timed region
    _ = np.asarray(counters[:1, :1])
    jax.block_until_ready(counters)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        counters = fn(counters, keys, events, amounts)
    jax.block_until_ready(counters)
    dt = time.perf_counter() - t0
    rate = N * STEPS / dt
    print(json.dumps({
        "metric": f"scatter_add_events_per_sec_{backend}_K{K}_N{N}",
        "value": round(rate, 1),
        "unit": "events/s",
        "vs_baseline": 0.0,      # microbench: no north-star share
    }))


def measure_serving(jax) -> dict:
    """Through-the-runtime serving-loop decomposition for the artifact:
    the same traffic dispatched synchronously
    (``entry_batch_nowait(...).result()`` per step) vs through a
    :class:`~sentinel_tpu.serving.DispatchPipeline` at depths 1/2/4,
    plus per-stage span attribution of the pipelined run (mean µs per
    span name, sample=1.0). The sync-vs-depth-2 delta is the per-step
    host readback/idle cost the pipeline hides; the CI gate
    (benchmarks/ci_gate.py ``dispatch_pipeline``) holds the ratio."""
    import collections
    import statistics

    import sentinel_tpu as stpu

    B = int(os.environ.get("BENCH_SERVING_BATCH", "4096"))
    STEPS = int(os.environ.get("BENCH_SERVING_STEPS", "30"))
    REPEATS = int(os.environ.get("BENCH_SERVING_REPEATS", "3"))
    DEPTHS = (1, 2, 4)

    sph = stpu.Sentinel(config=stpu.load_config(
        max_resources=4096, max_flow_rules=256, max_degrade_rules=16,
        max_authority_rules=16, minute_enabled=False))
    sph.load_flow_rules([stpu.FlowRule(resource=f"s{i}", count=1e9)
                         for i in range(256)])
    rng = np.random.default_rng(6)
    rows = sph.intern_resources(
        [f"s{int(i)}" for i in rng.integers(0, 1024, B)])

    def run_sync() -> float:
        t0 = time.perf_counter()
        for _ in range(STEPS):
            sph.entry_batch_nowait(rows).result()
        return (time.perf_counter() - t0) / STEPS * 1000

    def run_pipelined(depth: int) -> float:
        pipe = stpu.DispatchPipeline(sph, depth=depth)
        tickets = collections.deque()
        t0 = time.perf_counter()
        for _ in range(STEPS):
            tickets.append(pipe.submit(rows))
            if len(tickets) > depth:
                tickets.popleft().result()
        while tickets:
            tickets.popleft().result()
        return (time.perf_counter() - t0) / STEPS * 1000

    run_sync()                                   # warm every variant once
    run_pipelined(2)
    out = {"batch": B, "steps": STEPS,
           "sync_step_ms": round(min(run_sync() for _ in range(REPEATS)), 3)}
    out["pipelined_step_ms"] = {
        str(d): round(min(run_pipelined(d) for _ in range(REPEATS)), 3)
        for d in DEPTHS}

    # per-stage attribution of one pipelined pass, every dispatch sampled
    sph.obs.spans.clear()
    sph.obs.spans._stride = 1
    run_pipelined(2)
    stages: dict = {}
    for s in sph.obs.spans.snapshot():
        agg = stages.setdefault(s["name"], [])
        agg.append(s["dur_ns"])
    out["stage_us"] = {
        name: {"n": len(v),
               "mean": round(statistics.fmean(v) / 1000, 1)}
        for name, v in sorted(stages.items())}

    # round 12 — telemetry overhead for the artifact trail: the cost of
    # ONE hot-resource telemetry tick + readback (obs/telemetry.py)
    # against the serving step it rides beside at 1 Hz; the enforced
    # on/off step-time ratio lives in ci_gate gate (k)
    telem = getattr(sph, "telemetry", None)
    if telem is not None and telem.enabled:
        telem.poll()                             # compile the tick once
        t0 = time.perf_counter()
        for _ in range(10):
            telem.poll()
        tick_ms = (time.perf_counter() - t0) / 10 * 1000
        out["telemetry"] = {
            "k": telem.k,
            "tick_ms": round(tick_ms, 3),
            "tick_vs_sync_step": round(
                tick_ms / out["sync_step_ms"], 4) if out["sync_step_ms"]
                else None,
        }

    # round 16 — single-dispatch ablation for the artifact trail: the
    # SAME traffic through this engine (count-min observe fused into
    # the decide program, SENTINEL_SINGLE_DISPATCH default-on) vs an
    # engine built with the knob off (decide + a standalone observe
    # dispatch per step). ``dispatches_per_batch`` is counted from
    # ``pipeline.dispatches`` over the measured region; bit-parity and
    # the steady ==1 invariant are gated by ci_gate gate (m).
    from sentinel_tpu.obs import counters as obs_keys
    c0 = sph.obs.counters.get(obs_keys.PIPE_DISPATCH)
    fused_ms = min(run_sync() for _ in range(REPEATS))
    n_disp = sph.obs.counters.get(obs_keys.PIPE_DISPATCH) - c0
    out["dispatches_per_batch"] = round(n_disp / (STEPS * REPEATS), 4)
    prev_sd = os.environ.get("SENTINEL_SINGLE_DISPATCH")
    os.environ["SENTINEL_SINGLE_DISPATCH"] = "0"
    try:
        two = stpu.Sentinel(config=stpu.load_config(
            max_resources=4096, max_flow_rules=256, max_degrade_rules=16,
            max_authority_rules=16, minute_enabled=False))
    finally:
        if prev_sd is None:
            os.environ.pop("SENTINEL_SINGLE_DISPATCH", None)
        else:
            os.environ["SENTINEL_SINGLE_DISPATCH"] = prev_sd
    two.load_flow_rules([stpu.FlowRule(resource=f"s{i}", count=1e9)
                         for i in range(256)])
    rows_two = two.intern_resources(
        [f"s{int(i)}" for i in rng.integers(0, 1024, B)])

    def run_sync_two() -> float:
        t0 = time.perf_counter()
        for _ in range(STEPS):
            two.entry_batch_nowait(rows_two).result()
        return (time.perf_counter() - t0) / STEPS * 1000

    run_sync_two()                               # warm
    d0 = two.obs.counters.get(obs_keys.PIPE_DISPATCH)
    two_ms = min(run_sync_two() for _ in range(REPEATS))
    d1 = two.obs.counters.get(obs_keys.PIPE_DISPATCH)
    out["single_dispatch"] = {
        "enabled": bool(sph._single_dispatch),
        "fused_step_ms": round(fused_ms, 3),
        "two_dispatch_step_ms": round(two_ms, 3),
        "two_dispatch_per_batch": round(
            (d1 - d0) / (STEPS * REPEATS), 4),
        "step_ratio": (round(fused_ms / two_ms, 4) if two_ms else None),
    }
    two.close()
    sph.close()
    return out


def main() -> None:
    import jax

    # before the headline jit: it runs ahead of any Sentinel construction,
    # so nothing else would have enabled the persistent cache for it
    from sentinel_tpu.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    if os.environ.get("BENCH_SCATTER"):
        scatter_ab()
        return
    import jax.numpy as jnp

    from sentinel_tpu.core.registry import OriginRegistry, Registry, ResourceRegistry
    from sentinel_tpu.runtime import (
        donation_enabled as _donation_enabled,
        host_staging_enabled as _staging_enabled,
        pipeline_depth as _pipeline_depth,
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
    from sentinel_tpu.tune import provenance as tuned_provenance

    R = int(os.environ.get("BENCH_RESOURCES", str(1 << 20)))        # 1M rows
    # Default batch: 512k, the knee benchmarks/scaling_study.py looks for
    # (where on a host-attached chip: not measured). BENCH_BATCH overrides.
    B = int(os.environ.get("BENCH_BATCH", str(1 << 19)))
    STEPS = int(os.environ.get("BENCH_STEPS", "60"))
    NRULES = int(os.environ.get("BENCH_RULES", "4096"))
    WARMUP = 3

    spec = EngineSpec(
        rows=R, alt_rows=1024,
        second=WindowSpec(buckets=2, win_ms=500),
        minute=None,                      # minute ring off: 1M×60 won't fit
        statistic_max_rt=5000)

    resources = ResourceRegistry(R)
    origins = OriginRegistry(64)
    contexts = Registry(64, reserved=("sentinel_default_context",))

    # QPS rules on the first NRULES resources; the rest decide rule-free
    # (still full statistics recording) — a realistic mixed population.
    rules = [flow_mod.FlowRule(resource=f"r{i}", count=50.0)
             for i in range(NRULES)]
    compiled = flow_mod.compile_flow_rules(
        rules, resource_registry=resources, context_registry=contexts,
        capacity=NRULES, k_per_resource=2, num_rows=R, origin_registry=origins)
    deg_rules = [deg_mod.DegradeRule(resource=f"r{i}",
                                     grade=deg_mod.GRADE_EXCEPTION_RATIO,
                                     count=0.5, time_window=10)
                 for i in range(min(NRULES, 1024))]
    deg = deg_mod.compile_degrade_rules(
        deg_rules, resource_registry=resources, capacity=max(len(deg_rules), 1),
        k_per_resource=2, num_rows=R)
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

    # One layout authority (parallel/local_shard.py) for mesh construction,
    # shardings, and placement — the runtime, this bench, and the gates all
    # build the serving layout through the same helpers.
    from sentinel_tpu.parallel.local_shard import (
        local_mesh, mesh_topology, pin_state, place_batch, shardings_for,
    )

    SHARDS = int(os.environ.get("BENCH_SHARDS", "1"))
    mesh = mesh_sh = None
    if SHARDS > 1:
        try:
            mesh = local_mesh(SHARDS)
        except ValueError as exc:
            raise SystemExit(str(exc))
        mesh_sh = shardings_for(spec, mesh, state)
        state = pin_state(state, mesh_sh[0])

    rng = np.random.default_rng(42)
    n_batches = 4
    batches = []
    for _ in range(n_batches):
        # 1/4 of traffic on ruled rows (hot), rest uniform over all 1M
        hot = rng.integers(1, NRULES, B // 4)
        cold = rng.integers(1, R, B - B // 4)
        rows = np.concatenate([hot, cold]).astype(np.int32)
        rng.shuffle(rows)
        batches.append(EntryBatch(
            rows=jax.device_put(jnp.asarray(rows)),
            origin_ids=jnp.zeros(B, jnp.int32),
            origin_rows=jnp.full(B, spec.alt_rows, jnp.int32),
            context_ids=jnp.zeros(B, jnp.int32),
            chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
            acquire=jnp.ones(B, jnp.int32),
            is_in=jnp.ones(B, jnp.bool_),
            prioritized=jnp.zeros(B, jnp.bool_),
            valid=jnp.ones(B, jnp.bool_)))
    if mesh is not None:
        # batch columns partitioned on the event axis, exactly as the
        # runtime's dispatch tier places them (layout only — values and
        # verdicts are unchanged; the parity tests pin that)
        batches = [place_batch(b, mesh) for b in batches]

    # record_alt=False + scalar_flow: the bench batch carries no origin/
    # chain rows, uniform acquire=1, no priorities — the runtime selects
    # these same static variants for such batches (scalar admission path,
    # empty-slot skips, used-rule-slot slicing; see runtime.decide_raw)
    ruleset = ruleset._replace(
        flow_idx=compiled.rule_idx[:, :compiled.k_used],
        deg_idx=deg.rule_idx[:, :deg.k_used]).with_joint()
    # skip_threads: the bench ruleset has no THREAD-grade/system rules, so
    # the runtime would elide the gauge scatters for it too
    step = jax.jit(functools.partial(decide_entries, spec,
                                     enable_occupy=False, record_alt=False,
                                     scalar_flow=True, scalar_has_rl=False,
                                     skip_auth=True, skip_sys=True,
                                     skip_threads=True),
                   donate_argnums=(1,),
                   **({"out_shardings": mesh_sh} if mesh_sh else {}))

    t0_ms = 1_000_000_000
    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def scalars(i):
        now = t0_ms + i * 2  # 2 ms per step → windows rotate during the run
        # packed: ONE transfer per step
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms,
             now % spec.second.win_ms], np.int32))

    print(f"bench: R={R} B={B} steps={STEPS} on {jax.devices()[0]}",
          file=sys.stderr)
    for i in range(WARMUP):
        state, verdicts = step(ruleset, state, batches[i % n_batches],
                               scalars(i), sys_scalars)
    # one forced readback after warmup, before the timed regions
    _ = np.asarray(verdicts.allow[:1])
    jax.block_until_ready(state)

    # N repeated timed regions: the artifact carries the min/max band — a
    # regression is a shifted BAND, not a shifted point.
    REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
    rates = []
    tick = WARMUP
    for _ in range(REPEATS):
        start = time.perf_counter()
        for i in range(STEPS):
            state, verdicts = step(ruleset, state, batches[i % n_batches],
                                   scalars(tick), sys_scalars)
            tick += 1
        jax.block_until_ready((state, verdicts))
        elapsed = time.perf_counter() - start
        rates.append(B * STEPS / elapsed)
        print(f"bench: {B * STEPS} decisions in {elapsed:.3f}s "
              f"({rates[-1]:.0f}/s)", file=sys.stderr)
    rate = sorted(rates)[len(rates) // 2]      # median of the regions

    # decomposition: dispatch floor (chained trivial op) vs full step
    tiny = jax.jit(lambda x: x + 1)
    c = tiny(jnp.zeros((8,), jnp.int32))
    _ = np.asarray(c[:1])
    t0 = time.perf_counter()
    for _ in range(50):
        c = tiny(c)
    jax.block_until_ready(c)
    floor_ms = (time.perf_counter() - t0) / 50 * 1000
    # the same floor with a per-dispatch READBACK (the sync serving
    # loop's real cost on a remote-attached device) vs a depth-2 window
    # that defers each readback one step — the pair the runtime's
    # DispatchPipeline trades between (serving section below measures it
    # through the full runtime)
    import collections as _coll
    x0 = jnp.zeros((8,), jnp.int32)
    t0 = time.perf_counter()
    for _ in range(50):
        _ = np.asarray(tiny(x0)[:1])
    floor_sync_ms = (time.perf_counter() - t0) / 50 * 1000
    window: "_coll.deque" = _coll.deque()
    t0 = time.perf_counter()
    for _ in range(50):
        window.append(tiny(x0))
        if len(window) > 2:
            _ = np.asarray(window.popleft()[:1])
    while window:
        _ = np.asarray(window.popleft()[:1])
    floor_pipe_ms = (time.perf_counter() - t0) / 50 * 1000

    metric = ("decisions_per_sec_1chip_1M_resources" if SHARDS <= 1 else
              f"decisions_per_sec_{SHARDS}shard_1M_resources")
    # north star is per-chip: a sharded run is held to SHARDS× the target
    dev = jax.devices()[0]
    out = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "metric": metric,
        "value": round(rate, 1),
        "unit": "decisions/s",
        "vs_baseline": round(rate / (6.25e6 * max(SHARDS, 1)), 4),
        "band_min": round(min(rates), 1),
        "band_max": round(max(rates), 1),
        "runs": len(rates),
        "step_ms": round(B * STEPS / rate / STEPS * 1000, 2),
        "dispatch_floor_ms": round(floor_ms, 2),
        "dispatch_floor_sync_ms": round(floor_sync_ms, 2),
        "dispatch_floor_pipelined_ms": round(floor_pipe_ms, 2),
        "pipeline_depth": _pipeline_depth(),
        "batch": B,
        "resources": R,
        # serving-mode knob state at measurement time, so the artifact
        # is self-describing (absent key = knob at default)
        "env_knobs": {k: os.environ[k] for k in (
            "SENTINEL_PIPELINE_DEPTH", "SENTINEL_DONATE",
            "SENTINEL_HOST_STAGING", "SENTINEL_FRONTEND_BATCH",
            "SENTINEL_FRONTEND_DEADLINE_MS", "SENTINEL_FRONTEND_BUDGET_MS",
            "SENTINEL_FRONTEND_IDLE_MS", "SENTINEL_FRONTEND_QUEUE",
            "SENTINEL_SORTFREE", "SENTINEL_SORTFREE_BITS",
            "SENTINEL_SORTFREE_CHUNK", "SENTINEL_TUNED_CONFIG",
            "SENTINEL_TELEMETRY_K", "SENTINEL_TELEMETRY_DISABLE",
        ) if k in os.environ},
        # round 11 — tuned-config provenance: whether a
        # SENTINEL_TUNED_CONFIG artifact applied to this run (fingerprint
        # checked against THIS spec/mesh), and its per-knob values
        "tuned_config": tuned_provenance(spec, mesh),
        # serving layout that produced the headline (n_devices=1 on the
        # single-chip run — the comparison row the weak-scaling curve and
        # sharded artifacts are read against), plus the transfer knobs
        # whose defaults depend on the mesh (donation on, host staging
        # bypassed when batch placement is active)
        "mesh": {**mesh_topology(spec, mesh,
                                 mesh_sh[0] if mesh_sh else None),
                 "donation": _donation_enabled(),
                 "host_staging": mesh is None and _staging_enabled(),
                 "batch_placement": mesh is not None},
    }
    # General-path + mixed-batch numbers ride the same artifact (the
    # non-happy path must not regress silently). Skippable via
    # BENCH_GENERAL=0; a failure ends the run.
    if os.environ.get("BENCH_GENERAL", "1") != "0" and SHARDS <= 1:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.general_bench import measure
        del state, batches        # free HBM before the second fixture
        g_steps = int(os.environ.get("BENCH_GENERAL_STEPS", "20"))
        # the sorted/sortfree pair carries the r10 claim: same mode,
        # same fixture, aggregation stage swapped — with the
        # per-stage aggregation_ms marginal in both rows
        out["general"] = measure(jax, "fast", R, B, g_steps, NRULES, 3,
                                 aggregation=True)
        out["general_sortfree"] = measure(
            jax, "fast", R, B, g_steps, NRULES, 3, sortfree=True,
            aggregation=True)
        out["mixed"] = measure(jax, "mixed", R, B, g_steps, NRULES, 3)
        # prioritized-traffic numbers (r6: the priority/occupy cliff —
        # a reintroduced whole-batch demotion can never hide)
        out["prio"] = measure(jax, "prio", R, B, g_steps, NRULES, 3)
        out["prio_mixed"] = measure(jax, "prio_mixed", R, B, g_steps,
                                    NRULES, 3)
    # Through-the-runtime serving decomposition (r6: pipelined dispatch).
    # Skippable via BENCH_SERVING=0.
    if os.environ.get("BENCH_SERVING", "1") != "0" and SHARDS <= 1:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        out["serving"] = measure_serving(jax)
    # 1/2/4/8-device weak-scaling curve through the runtime (r9: fixed
    # rows per device, DispatchPipeline depth swept). Runs by default only
    # on a sharded invocation (the single-chip TPU artifact would see one
    # device and produce a degenerate curve); BENCH_WEAK_SCALING=1 forces
    # it (the CPU virtual-device harness), =0 skips.
    ws_knob = os.environ.get("BENCH_WEAK_SCALING", "")
    if ws_knob != "0" and (ws_knob == "1" or SHARDS > 1):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.weak_scaling import flatness, measure as ws_measure
        counts = tuple(n for n in (1, 2, 4, 8)
                       if n <= max(SHARDS, len(jax.devices())))
        points = ws_measure(
            jax,
            rows_per_dev=int(os.environ.get("WEAK_ROWS_PER_DEV",
                                            str(1 << 14))),
            batch=int(os.environ.get("WEAK_BATCH", str(1 << 13))),
            steps=int(os.environ.get("WEAK_STEPS", "6")),
            device_counts=counts,
            depths=tuple(int(d) for d in os.environ.get(
                "WEAK_DEPTHS", "1,2,4").split(",")))
        out["weak_scaling"] = {"curve": points,
                               "flatness_norm": flatness(points)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
