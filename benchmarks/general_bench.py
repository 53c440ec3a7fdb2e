"""General-path (non-happy-path) benchmark: origin-bearing traffic.

The headline bench measures the scalar admission path (origin-free uniform
traffic). THIS harness measures the general sorted path — the one any batch
takes when a tenant uses ``limitApp``/RELATE/CHAIN origin-scoped rules
(reference ``FlowRuleChecker.selectNodeByRequesterAndStrategy``,
``FlowRuleChecker.java:129-161``) — so the non-happy-path number is tracked
every round instead of silently regressing.

Shape: the headline 1M-resource population, plus an origin-scoped rule and a
RELATE rule family on the hot rows; every event carries an origin id and a
real hashed origin row (record_alt=True — the alt-table scatters are live).

Modes (env GENERAL_MODE):
  fast      all events origin-bearing, fast general path (DEFAULT — what
            the runtime selects for such batches)
  general   all events origin-bearing, SORTED general path (the pre-r5
            fallback; kept measurable so the fallback number is tracked)
  mixed     10% origin-bearing: the per-event split (scalar step on the
            origin-free 90% + fast general step on the rest — the exact
            two-dispatch shape runtime._decide_split_nowait issues)
  prio      all events PRIORITIZED (origin-free): the occupy-capable fast
            variant (rules/flow.flow_check_fast_occupy) — what the
            runtime now selects for whole-prio batches; pre-r6 this
            demoted to the sorted path (a whole-batch cliff)
  prio_mixed  1% prioritized, 99% origin-free scalar: the occupy-aware
            per-event split (occupy-base scalar step on the bulk + fast
            occupy step on the prioritized slice)
Knobs: BENCH_RESOURCES, BENCH_BATCH, BENCH_STEPS, BENCH_RULES,
BENCH_REPEATS. The backend is JAX's own choice (``JAX_PLATFORMS``).

Prints one JSON line like bench.py.
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


def build_general_fixture(jax, R: int, B: int, NRULES: int,
                          origin_share: float = 1.0,
                          prio_share: float = 0.0):
    """→ (spec, ruleset, state, batches, t0_ms). origin_share = fraction of
    events carrying an origin id (1.0 = pure general, 0.1 = mixed);
    prio_share = fraction of PRIORITIZED events (occupy modes)."""
    import jax.numpy as jnp

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
    from sentinel_tpu.runtime import _alt_hash
    from sentinel_tpu.stats.window import WindowSpec

    spec = EngineSpec(rows=R, alt_rows=1024,
                      second=WindowSpec(buckets=2, win_ms=500),
                      minute=None, statistic_max_rt=5000)
    resources = ResourceRegistry(R)
    origins = OriginRegistry(64)
    contexts = Registry(64, reserved=("sentinel_default_context",))

    N_ORIGINS = 8
    origin_names = [f"app-{i}" for i in range(1, N_ORIGINS + 1)]

    # default QPS rules on the hot rows — same population as the headline —
    # PLUS the origin-scoped families that force the general path:
    #   * an origin-specific rule (limitApp="app-1") on the first 256
    #   * a RELATE rule (strategy=RELATE) on the next 256
    rules = [flow_mod.FlowRule(resource=f"r{i}", count=50.0)
             for i in range(NRULES)]
    rules += [flow_mod.FlowRule(resource=f"r{i}", count=30.0,
                                limit_app="app-1")
              for i in range(256)]
    rules += [flow_mod.FlowRule(resource=f"r{i}", count=40.0,
                                strategy=flow_mod.STRATEGY_RELATE,
                                ref_resource=f"r{(i + 1) % NRULES}")
              for i in range(256, 512)]
    compiled = flow_mod.compile_flow_rules(
        rules, resource_registry=resources, context_registry=contexts,
        capacity=len(rules), k_per_resource=2, num_rows=R,
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
        flow_table=compiled.table,
        flow_idx=compiled.rule_idx[:, :compiled.k_used],
        deg_table=deg.table, deg_idx=deg.rule_idx[:, :deg.k_used],
        auth_table=auth.table, auth_idx=auth.rule_idx,
        sys_thresholds=sys_mod.compile_system_rules([]),
        param_table=param.table).with_joint()

    state = init_state(spec, len(rules), max(len(deg_rules), 1))

    origin_ids_all = np.array(
        [origins.get_or_create(nm) for nm in origin_names], np.int32)

    rng = np.random.default_rng(43)
    n_batches = 4
    batches = []
    for _ in range(n_batches):
        hot = rng.integers(1, NRULES, B // 4)
        cold = rng.integers(1, R, B - B // 4)
        rows = np.concatenate([hot, cold]).astype(np.int32)
        rng.shuffle(rows)
        has_origin = rng.random(B) < origin_share
        oid = np.where(has_origin,
                       origin_ids_all[rng.integers(0, N_ORIGINS, B)],
                       0).astype(np.int32)
        # vectorized form of runtime._alt_hash (same constants, uint64
        # intermediate so the numpy product can't overflow-signed)
        orow = np.full(B, spec.alt_rows, np.int32)
        sel = np.nonzero(has_origin)[0]
        h = ((rows[sel].astype(np.uint64) * 0x9E3779B1)
             ^ (oid[sel].astype(np.uint64) * 2 * 0x85EBCA6B)) & 0xFFFFFFFF
        orow[sel] = (h % spec.alt_rows).astype(np.int32)
        chk = _alt_hash(int(rows[sel[0]]), 0, int(oid[sel[0]]),
                        spec.alt_rows) if len(sel) else 0
        assert not len(sel) or int(orow[sel[0]]) == chk
        batches.append(EntryBatch(
            rows=jax.device_put(jnp.asarray(rows)),
            origin_ids=jax.device_put(jnp.asarray(oid)),
            origin_rows=jax.device_put(jnp.asarray(orow)),
            context_ids=jnp.zeros(B, jnp.int32),
            chain_rows=jnp.full(B, spec.alt_rows, jnp.int32),
            acquire=jnp.ones(B, jnp.int32),
            is_in=jnp.ones(B, jnp.bool_),
            prioritized=jax.device_put(jnp.asarray(
                rng.random(B) < prio_share)),
            valid=jnp.ones(B, jnp.bool_)))
    return spec, ruleset, state, batches, 1_000_000_000


def ablate(jax, spec, ruleset, state0, batches, t0_ms, STEPS,
           mode: str = "general") -> None:
    """GENERAL_ABLATE=1: marginal cost of each general-path component
    (same subtractive method as benchmarks/ablate_step.py, but with the
    origin-bearing fixture and record_alt=True). ``mode="fast"`` ablates
    the round-5 fast path (flow_check_fast) instead of the legacy sorted
    path — different stub targets, same discipline."""
    import contextlib

    import jax.numpy as jnp

    import sentinel_tpu.engine.pipeline as pl
    from sentinel_tpu.ops import segments as seg_mod

    rng = np.random.default_rng(7)
    B = batches[0].rows.shape[0]
    K = ruleset.flow_idx.shape[1]
    fixed_perm = jnp.asarray(rng.permutation(B * K).astype(np.int32))

    def stub_sort_by_keys(primary, secondary=None):
        return fixed_perm[:primary.shape[0]]

    def stub_unsort(order, values_sorted):
        return values_sorted

    def stub_winsum(wspec, wstate, rows, event, now_idx):
        return jnp.zeros(rows.shape, jnp.int32)

    def stub_winsum_all(wspec, wstate, event, now_idx):
        return jnp.zeros((wstate.counters.shape[0],), jnp.int32)

    def stub_warmup(table, dyn, wspec, main_second, now_idx_s, rel_now_ms,
                    minute_spec, main_minute, now_idx_m):
        return dyn, table.count

    def stub_prefix(values_sorted, starts, leader):
        z = jnp.zeros_like(values_sorted)
        return z, z

    def stub_admit(base, amounts, limit, starts, leader, iterations=3):
        return jnp.ones(base.shape, jnp.bool_)

    def stub_degrade_entry(table, st, rule_idx, rows, valid, rel_now_ms,
                           **kw):
        return st, jnp.ones(rows.shape, jnp.bool_)

    def stub_record_window(step, name, wspec, wstate, now_idx, touched,
                           adds):
        return wstate

    def stub_ranks(key):
        return jnp.zeros(key.shape, jnp.int32)

    def stub_joint_gather(idx_table, rows, sentinel):
        # CAVEAT: zeros collapse every pair onto rule 0, which perturbs
        # the downstream sort/scatter distributions — this stub's marginal
        # can come out negative; read the whole-flow-slot number instead
        return jnp.zeros((rows.shape[0], idx_table.shape[1]), jnp.int32)

    def stub_flow_fast(table, dyn, rule_idx, wspec, main_second, alt_second,
                       main_threads, alt_threads, batch, now_idx_s,
                       rel_now_ms, **kw):
        return (dyn, jnp.ones(batch.rows.shape, jnp.bool_),
                jnp.zeros(batch.rows.shape, jnp.int32))

    def stub_degrade_scalar(table, st, rule_idx, rows, valid, rel_now_ms,
                            **kw):
        return st, jnp.ones(rows.shape, jnp.bool_)

    targets = {
        "sort": (seg_mod, "sort_by_keys", stub_sort_by_keys),
        "unsort": (seg_mod, "unsort", stub_unsort),
        "winsum": (pl.flow_mod, "window_sum_rows", stub_winsum),
        # the fast path's alt reads go through the DENSE sum since the
        # round-5 continuation — stub both for a complete -winsum
        "winsumall": (pl.flow_mod, "window_sum_all", stub_winsum_all),
        "warmup": (pl.flow_mod, "_warmup_sync_and_limits", stub_warmup),
        "prefix": (seg_mod, "segment_prefix_sum", stub_prefix),
        "admit": (seg_mod, "greedy_admit", stub_admit),
        "degrade": (pl.deg_mod, "degrade_entry_check", stub_degrade_entry),
        "recording": (pl, "_record_window", stub_record_window),
        # fast-path targets (mode="fast")
        "ranks": (seg_mod, "ranks_by_key", stub_ranks),
        "joint": (seg_mod, "padded_table_gather", stub_joint_gather),
        "flowfast": (pl.flow_mod, "flow_check_fast", stub_flow_fast),
        "degscalar": (pl.deg_mod, "degrade_entry_check_scalar",
                      stub_degrade_scalar),
    }

    @contextlib.contextmanager
    def patched(*names):
        saved = {}
        for name in names:
            mod, attr, stub = targets[name]
            saved[name] = getattr(mod, attr)
            setattr(mod, attr, stub)
        try:
            yield
        finally:
            for name, orig in saved.items():
                mod, attr, _ = targets[name]
                setattr(mod, attr, orig)

    import functools as ft
    import time as tm

    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def times_for(i):
        now = t0_ms + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms,
             now % spec.second.win_ms], np.int32))

    results = {}

    fast_kw = (dict(fast_flow=True, skip_threads=True, scalar_has_rl=False)
               if mode == "fast" else {})

    def run(name, *stub_names):
        state = jax.tree.map(jnp.copy, state0)
        with patched(*stub_names):
            step = jax.jit(ft.partial(
                pl.decide_entries, spec, enable_occupy=False,
                record_alt=True, skip_auth=True, skip_sys=True, **fast_kw),
                donate_argnums=(1,))
            state, v = step(ruleset, state, batches[0], times_for(0),
                            sys_scalars)
        _ = np.asarray(v.allow[:1])
        jax.block_until_ready(state)
        t0 = tm.perf_counter()
        for i in range(STEPS):
            state, v = step(ruleset, state, batches[(1 + i) % len(batches)],
                            times_for(1 + i), sys_scalars)
        jax.block_until_ready((state, v))
        dt = (tm.perf_counter() - t0) / STEPS * 1000
        results[name] = dt
        print(f"  {name:<40s} {dt:9.2f} ms", flush=True)

    if mode == "fast":
        floor_stubs = ("flowfast", "degscalar", "joint", "recording")
        run("FULL")
        run("-joint-gather", "joint")
        run("-ranksort", "ranks")
        run("-winsum", "winsum", "winsumall")
        run("-warmup", "warmup")
        run("-flow(whole)", "flowfast")
        run("-degrade", "degscalar")
        run("-recording", "recording")
        run("-all (floor)", *floor_stubs)
    else:
        run("FULL")
        run("-sorts", "sort")
        run("-unsorts", "unsort")
        run("-winsum", "winsum")
        run("-warmup", "warmup")
        run("-prefixsums", "prefix")
        run("-admit", "admit")
        run("-degrade", "degrade")
        run("-recording", "recording")
        run("-all (floor)", "sort", "unsort", "winsum", "warmup", "prefix",
            "admit", "degrade", "recording")
    full = results["FULL"]
    print("marginal costs:")
    for k, v in results.items():
        if k.startswith("-") and k != "-all (floor)":
            print(f"  {k[1:]:<40s} {full - v:9.2f} ms")
    print(f"  {'floor':<40s} {results['-all (floor)']:9.2f} ms")


def _aggregation_ms(jax, spec, ruleset, state0, batches, t0_ms, steps,
                    flow_kw, sortfree: bool) -> float:
    """Marginal cost of the SEGMENT-AGGREGATION stage (the r10 per-stage
    artifact key): full step minus a step with the grouping stubbed out —
    fixed permutation / zero ranks in place of the composite-key sort
    (sorted path) or the claim cascade + counting order (sort-free path).
    Same subtractive discipline as :func:`ablate`."""
    import contextlib
    import functools as ft
    import time as tm

    import jax.numpy as jnp

    import sentinel_tpu.engine.pipeline as pl
    from sentinel_tpu.ops import segments as seg_mod
    from sentinel_tpu.ops import sortfree as sfo_mod

    rng = np.random.default_rng(11)
    B = batches[0].rows.shape[0]
    K = ruleset.flow_idx.shape[1]
    fixed_perm = jnp.asarray(rng.permutation(B * K).astype(np.int32))

    def stub_sort(primary, secondary=None):
        return fixed_perm[:primary.shape[0]]

    def stub_ranks_slot(key):
        return jnp.zeros(key.shape, jnp.int32)

    def stub_pair_plan(k1, k2, sentinel_mask, bits):
        return sfo_mod.BucketPlan(
            bucket=jnp.zeros(k1.shape, jnp.int32),
            overflow=jnp.asarray(False),
            overflow_count=jnp.int32(0),
            num_buckets=sfo_mod.ROUNDS * (1 << bits) + 1)

    def stub_counting(bucket, num_buckets, ranks=None):
        return fixed_perm[:bucket.shape[0]]

    def stub_ranks2d(key2d, sentinel_value, bits):
        return jnp.zeros(key2d.shape, jnp.int32), jnp.int32(0)

    patches = ([(sfo_mod, "build_pair_plan", stub_pair_plan),
                (sfo_mod, "counting_order", stub_counting),
                (sfo_mod, "ranks2d_hashed", stub_ranks2d)]
               if sortfree else
               [(seg_mod, "sort_by_keys", stub_sort),
                (seg_mod, "ranks_per_slot", stub_ranks_slot)])

    @contextlib.contextmanager
    def patched(on: bool):
        saved = [(m, a, getattr(m, a)) for m, a, _ in patches] if on else []
        if on:
            for m, a, stub in patches:
                setattr(m, a, stub)
        try:
            yield
        finally:
            for m, a, orig in saved:
                setattr(m, a, orig)

    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def times_for(i):
        now = t0_ms + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms,
             now % spec.second.win_ms], np.int32))

    def run(stubbed: bool) -> float:
        state = jax.tree.map(jnp.copy, state0)
        with patched(stubbed):
            step = jax.jit(ft.partial(
                pl.decide_entries, spec, enable_occupy=False,
                record_alt=True, skip_auth=True, skip_sys=True,
                skip_threads=True, sortfree=sortfree, **flow_kw),
                donate_argnums=(1,))
            state, v = step(ruleset, state, batches[0], times_for(0),
                            sys_scalars)
        _ = np.asarray(v.allow[:1])
        jax.block_until_ready(state)
        t0 = tm.perf_counter()
        for i in range(steps):
            state, v = step(ruleset, state, batches[(1 + i) % len(batches)],
                            times_for(1 + i), sys_scalars)
        jax.block_until_ready((state, v))
        return (tm.perf_counter() - t0) / steps * 1000

    return run(False) - run(True)


def measure(jax, mode: str, R: int, B: int, STEPS: int, NRULES: int,
            REPEATS: int, sortfree: bool = False,
            aggregation: bool = False) -> dict:
    """Measure one GENERAL_MODE → result dict (the JSON payload). Callable
    from bench.py so the driver artifact carries the general/mixed numbers
    beside the headline (VERDICT r4 #10). ``sortfree`` measures the same
    mode through the r10 hash-bucketed aggregation (the runtime default);
    ``aggregation`` adds the per-stage ``aggregation_ms`` key (marginal
    cost of the segment-grouping stage, subtractive)."""
    import jax.numpy as jnp

    from sentinel_tpu.engine.pipeline import decide_entries

    share = (0.1 if mode == "mixed"
             else 0.0 if mode in ("prio", "prio_mixed") else 1.0)
    prio_share = (1.0 if mode == "prio"
                  else 0.01 if mode == "prio_mixed" else 0.0)
    spec, ruleset, state, batches, t0_ms = build_general_fixture(
        jax, R, B, NRULES, origin_share=share, prio_share=prio_share)

    if os.environ.get("GENERAL_ABLATE"):
        ablate(jax, spec, ruleset, state, batches, t0_ms,
               int(os.environ.get("PROF_STEPS", "15")), mode=mode)
        return {}

    if mode in ("mixed", "prio_mixed"):
        # pre-stage the split's two sub-batches per batch (the runtime
        # partitions on host; the bench measures the device cost of the
        # resulting two dispatches, matching how the headline bench
        # pre-stages its single batch). For prio_mixed the partition key
        # is the prioritized flag (runtime routes prio events to the
        # general side so only that side may commit occupy bookings).
        from sentinel_tpu.engine.pipeline import EntryBatch
        split_batches = []
        for b in batches:
            oid = np.asarray(b.origin_ids)
            scalar_m = ((oid == 0) & ~np.asarray(b.prioritized)
                        if mode == "prio_mixed" else oid == 0)
            idx_s = np.nonzero(scalar_m)[0]
            idx_g = np.nonzero(~scalar_m)[0]

            def pad_pow2(n):
                p = 1024
                while p < n:
                    p *= 2
                return p

            def sub(idx, pad):
                k = idx.shape[0]
                sl = {f: np.asarray(getattr(b, f)) for f in
                      ("rows", "origin_ids", "origin_rows", "context_ids",
                       "chain_rows", "acquire", "is_in", "prioritized",
                       "valid")}
                out = {}
                for f, a in sl.items():
                    fill = (spec.rows if f == "rows" else
                            spec.alt_rows if f in ("origin_rows",
                                                   "chain_rows") else 0)
                    pa = np.full(pad, fill, a.dtype)
                    pa[:k] = a[idx]
                    if f == "valid":
                        pa[k:] = False
                    out[f] = jax.device_put(jnp.asarray(pa))
                return EntryBatch(**out)

            split_batches.append((sub(idx_s, pad_pow2(idx_s.shape[0])),
                                  sub(idx_g, pad_pow2(idx_g.shape[0]))))

    # skip_threads mirrors the runtime's elision for this ruleset (all
    # QPS-grade, no system rules — VERDICT r4 #2)
    # scalar_has_rl=False mirrors the runtime's auto-derived flag for
    # this fixture (no rate-limiter rules loaded) — the RL columns and
    # closed forms compile away
    flow_kw = ({"fast_flow": True, "scalar_has_rl": False}
               if mode in ("fast",) else {})
    if mode == "prio":
        # whole-batch prioritized: the occupy-capable fast variant, the
        # exact static combo the runtime dispatches (record_alt=False —
        # origin-free population takes the *_noalt prio step)
        step = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=True, record_alt=False,
            skip_auth=True, skip_sys=True, skip_threads=True,
            fast_flow=True, scalar_has_rl=False, sortfree=sortfree),
            donate_argnums=(1,))
    else:
        step = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=False, record_alt=True,
            skip_auth=True, skip_sys=True, skip_threads=True,
            sortfree=sortfree, **flow_kw), donate_argnums=(1,))
    if mode == "mixed":
        step_s = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=False, record_alt=False,
            skip_auth=True, skip_sys=True, scalar_flow=True,
            scalar_has_rl=False, skip_threads=True, sortfree=sortfree),
            donate_argnums=(1,))
        step_g = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=False, record_alt=True,
            skip_auth=True, skip_sys=True, fast_flow=True,
            scalar_has_rl=False, skip_threads=True, sortfree=sortfree),
            donate_argnums=(1,))
    elif mode == "prio_mixed":
        # the occupy-aware split: scalar step with the occupy-base fold
        # on the 99% bulk + fast occupy step on the prioritized slice —
        # the exact two-dispatch shape runtime._decide_split_nowait
        # issues while bookings are live
        step_s = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=True, record_alt=False,
            skip_auth=True, skip_sys=True, scalar_flow=True,
            scalar_has_rl=False, skip_threads=True, sortfree=sortfree),
            donate_argnums=(1,))
        step_g = jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=True, record_alt=False,
            skip_auth=True, skip_sys=True, fast_flow=True,
            scalar_has_rl=False, skip_threads=True, sortfree=sortfree),
            donate_argnums=(1,))
    sys_scalars = jnp.asarray(np.array([0.5, 0.1], np.float32))

    def scalars(i):
        now = t0_ms + i * 2
        return jnp.asarray(np.array(
            [spec.second.index_of(now), 0, now - t0_ms,
             now % spec.second.win_ms], np.int32))

    def run_step(i, state):
        if mode in ("mixed", "prio_mixed"):
            bs, bg = split_batches[i % 4]
            state, v = step_s(ruleset, state, bs, scalars(i), sys_scalars)
            state, v = step_g(ruleset, state, bg, scalars(i), sys_scalars)
            return state, v
        return step(ruleset, state, batches[i % 4], scalars(i),
                    sys_scalars)

    print(f"general_bench[{mode}]: R={R} B={B} steps={STEPS} "
          f"on {jax.devices()[0]}", file=sys.stderr)
    for i in range(3):
        state, verdicts = run_step(i, state)
    _ = np.asarray(verdicts.allow[:1])      # honest-mode gate
    jax.block_until_ready(state)

    rates = []
    tick = 3
    for _ in range(REPEATS):
        start = time.perf_counter()
        for i in range(STEPS):
            state, verdicts = run_step(tick, state)
            tick += 1
        jax.block_until_ready((state, verdicts))
        elapsed = time.perf_counter() - start
        rates.append(B * STEPS / elapsed)
        print(f"general_bench: {B * STEPS} decisions in {elapsed:.3f}s "
              f"({rates[-1]:.0f}/s)", file=sys.stderr)
    rate = sorted(rates)[len(rates) // 2]
    suffix = "_sortfree" if sortfree else ""
    out = {
        "metric": f"decisions_per_sec_general_{mode}{suffix}_1chip",
        "value": round(rate, 1),
        "unit": "decisions/s",
        "vs_baseline": round(rate / 6.25e6, 4),
        "band_min": round(min(rates), 1),
        "band_max": round(max(rates), 1),
        "runs": len(rates),
        "step_ms": round(B / rate * 1000, 2),
        "batch": B,
        "resources": R,
    }
    if aggregation and mode not in ("mixed", "prio_mixed"):
        # per-stage key (r10): marginal cost of the segment-grouping
        # stage in THIS variant's step — the sorted-vs-sortfree pair of
        # these is the ablation the round-10 claim rides on
        out["aggregation_ms"] = round(_aggregation_ms(
            jax, spec, ruleset, state, batches, t0_ms,
            min(STEPS, 10), flow_kw, sortfree), 3)
    return out


def main() -> None:
    import jax

    R = int(os.environ.get("BENCH_RESOURCES", str(1 << 20)))
    B = int(os.environ.get("BENCH_BATCH", str(1 << 19)))
    STEPS = int(os.environ.get("BENCH_STEPS", "30"))
    NRULES = int(os.environ.get("BENCH_RULES", "4096"))
    REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
    mode = os.environ.get("GENERAL_MODE", "fast")
    out = measure(jax, mode, R, B, STEPS, NRULES, REPEATS,
                  sortfree=os.environ.get("GENERAL_SORTFREE", "0") == "1",
                  aggregation=os.environ.get("GENERAL_AGGREGATION",
                                             "0") == "1")
    if out:
        print(json.dumps(out))


if __name__ == "__main__":
    main()
