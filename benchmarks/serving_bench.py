"""End-to-end serving bench: the workload zoo through the real front end.

Every round before this one benched the ENGINE (pre-formed uniform
batches, decisions/sec); this bench measures what a service owner sees —
request→verdict latency through the full ingest tier: asyncio submit →
deadline-driven coalescing (frontend/batcher.py) → depth-k pipelined
device dispatch → per-request future fan-out. Each workload from
frontend/workloads.py replays OPEN-LOOP (arrivals fire at their
generated timestamps whether or not earlier requests finished — the
honest way to measure a latency SLO; closed-loop replay would let a slow
server throttle its own offered load) and reports p50/p95/p99 from an
obs/hist.py :class:`LogHistogram` plus the frontend's own counters.

Output: one JSON line per workload on stdout and a single artifact
(``SERVING_BENCH_OUT``, default ``serving_bench.json`` in the CWD) with
the per-workload metrics and the serving-knob environment, so BENCH_rN
records are self-describing. Each workload also carries a
``worst_request`` entry — the slowest request's causal chain exported as
a Chrome-trace-event document (obs/traceexport.py), loadable directly in
``ui.perfetto.dev``.

Knobs: ``SERVING_DURATION_MS`` (default 600), ``SERVING_RATE`` (offered
req/s, default 1000), ``SERVING_SEED`` (default 42), plus the
``SENTINEL_FRONTEND_*`` batcher knobs (frontend/batcher.py). CPU-CI
sized by default; the TPU runs raise rate/duration via env.

benchmarks/ci_gate.py gates the ``steady`` p99 band and the
``flash_crowd`` no-collapse probe through :func:`run_workload` directly.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

DEFAULT_DURATION_MS = float(os.environ.get("SERVING_DURATION_MS", 600))
DEFAULT_RATE = float(os.environ.get("SERVING_RATE", 1000))
DEFAULT_SEED = int(os.environ.get("SERVING_SEED", 42))

#: Env knobs copied into the artifact so BENCH_rN files are
#: self-describing (mirrors bench.py's env_knobs key).
KNOB_ENVS = (
    "SENTINEL_PIPELINE_DEPTH", "SENTINEL_DONATE", "SENTINEL_HOST_STAGING",
    "SENTINEL_FRONTEND_BATCH", "SENTINEL_FRONTEND_DEADLINE_MS",
    "SENTINEL_FRONTEND_BUDGET_MS", "SENTINEL_FRONTEND_IDLE_MS",
    "SENTINEL_FRONTEND_QUEUE",
    "SENTINEL_SORTFREE", "SENTINEL_SORTFREE_BITS", "SENTINEL_SORTFREE_CHUNK",
    "SENTINEL_TUNED_CONFIG",
    "SENTINEL_TELEMETRY_K", "SENTINEL_TELEMETRY_DISABLE",
    "SENTINEL_HOT_ROWS", "SENTINEL_SKETCH_BITS", "SENTINEL_SKETCH_ROWS",
    "SENTINEL_TIER_TICK_MS", "SENTINEL_TIERING_DISABLE",
    "SENTINEL_TIER_COLD_MAX",
    "SENTINEL_SINGLE_DISPATCH",
    "SENTINEL_CONTROL_DISABLE", "SENTINEL_CONTROL_INTERVAL_MS",
    "SENTINEL_CONTROL_P99_HI_MS", "SENTINEL_CONTROL_P99_LO_MS",
    "SENTINEL_CONTROL_MIN_ADMIT", "SENTINEL_CONTROL_COOLDOWN_MS",
    "SENTINEL_CONTROL_DEGRADE_RT_MS",
    "SENTINEL_RESOURCE_HIST_DISABLE", "SENTINEL_RESOURCE_HIST_BUCKETS",
    "SERVING_DURATION_MS", "SERVING_RATE", "SERVING_SEED",
)


def env_knobs() -> Dict[str, str]:
    return {k: os.environ[k] for k in KNOB_ENVS if k in os.environ}


def _rules_for(stpu, name: str):
    """Per-workload rule sets: mostly-generous so steady traffic passes,
    with a deliberately tight rule on the flash hot key (the spike must
    exercise the BLOCK path, not just the queue)."""
    generous = [stpu.FlowRule(resource=f"{name.split('_')[0]}/{i}",
                              count=1e9) for i in range(16)]
    if name == "flash_crowd":
        generous = [stpu.FlowRule(resource=f"flash/{i}", count=1e9)
                    for i in range(16)]
        generous.append(stpu.FlowRule(resource="flash/hot", count=300.0))
    elif name == "overload_episode":
        # the composite carries three prefixes; the flash hot key keeps
        # its tight rule so the spike exercises BLOCK, not just queueing
        generous = [stpu.FlowRule(resource=f"{p}/{i}", count=1e9)
                    for p in ("steady", "flash", "slow")
                    for i in range(16)]
        generous.append(stpu.FlowRule(resource="flash/hot", count=300.0))
    elif name == "priority_mix":
        generous = [stpu.FlowRule(resource=f"prio/{i}", count=400.0)
                    for i in range(8)]
    return generous


def _warm(sph, batch_max: int, resource: str = "warm/0") -> None:
    """Compile every program the replay can hit: the engine pads batches
    to power-of-two geometries, and the batcher always dispatches with
    acquire+prioritized arrays (origins list present or absent), so warm
    each pow2 size in the no-prio and mixed-prio variants, with and
    without origins — an unwarmed variant costs a multi-second XLA
    compile stall mid-replay, which is compile time, not serving
    latency. Programs are shared across Sentinel instances of the same
    geometry, so later workloads in the sweep warm from cache."""
    import numpy as np
    rows = sph.intern_resources([resource])
    n = 1
    while n <= batch_max:
        r = np.full(n, rows[0], np.int32)
        ones = np.ones(n, np.int32)
        noprio = np.zeros(n, np.bool_)
        mixed = np.zeros(n, np.bool_)
        mixed[0] = True
        for prio in (noprio, mixed):
            sph.entry_batch_nowait(r, acquire=ones,
                                   prioritized=prio).result()
            sph.entry_batch_nowait(r, acquire=ones, prioritized=prio,
                                   origins=["warm-app"] * n).result()
        n *= 2


def run_workload(name: str, *, seed: int = DEFAULT_SEED,
                 duration_ms: float = DEFAULT_DURATION_MS,
                 rate_rps: float = DEFAULT_RATE,
                 batch_max: int = 256, deadline_ms: int = 25,
                 budget_ms: int = 3, idle_ms: float = 1.0,
                 depth: int = 2, queue_max: Optional[int] = None,
                 wl_kwargs: Optional[dict] = None,
                 trace_dir: Optional[str] = None,
                 control: bool = False,
                 control_kwargs: Optional[dict] = None) -> Dict:
    """Replay one zoo workload open-loop through a fresh Sentinel +
    AdaptiveBatcher; returns the per-workload metrics dict.

    ``trace_dir`` attaches the SLO flight recorder's rolling
    ``<workload>-trace`` log there (obs/flight.py) — what ci_gate's
    trace-capture probe reads back with ``load_pinned``.

    ``control=True`` attaches a round-17 overload ControlLoop
    (``control_kwargs`` → its constructor: interval_ms, config, seed);
    it rides the CadenceScheduler daemon and its snapshot lands under
    the ``control`` key of the result."""
    import sentinel_tpu as stpu
    from sentinel_tpu.frontend import AdaptiveBatcher, IngestOverload
    from sentinel_tpu.frontend.workloads import make as make_workload
    from sentinel_tpu.obs import counters as obs_keys
    from sentinel_tpu.obs.hist import LogHistogram

    reqs = make_workload(name, seed, duration_ms=duration_ms,
                         rate_rps=rate_rps, **(wl_kwargs or {}))
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=4096, max_origins=64, max_flow_rules=64,
        max_degrade_rules=16, max_authority_rules=16))
    sph.load_flow_rules(_rules_for(stpu, name))
    if trace_dir is not None:
        sph.obs.flight.configure(trace_dir, name)
    _warm(sph, batch_max, reqs[0].resource if reqs else "warm/0")
    sph.obs.counters.clear()
    sph.obs.hist_request.clear()
    # ONE CadenceScheduler is the clock of the telemetry (1 Hz) and
    # tiering (SENTINEL_TIER_TICK_MS) ticks. Health + hot view land in
    # the artifact below; the overhead ratio is gated by ci_gate gate
    # (k).
    telem = getattr(sph, "telemetry", None)
    from sentinel_tpu.serving import CadenceScheduler
    ctl = None
    if control:
        from sentinel_tpu.control import ControlLoop
        ctl = ControlLoop(sph, **(control_kwargs or {}))
    CadenceScheduler(sph, telemetry_interval_sec=1.0).start()

    lat = LogHistogram()
    stats = {"shed": 0, "allowed": 0, "blocked": 0, "deadline_miss": 0}
    worst = {"ns": -1, "trace": 0}      # worst-latency request + trace id
    # per-prefix (tenant) breakdown: the controller gate scores the
    # steady tenant's latency separately from the abusive streams
    by_prefix: Dict[str, Dict] = {}
    deadline_ns = deadline_ms * 1e6

    def _prefix_slot(resource: str) -> Dict:
        p = resource.split("/", 1)[0]
        slot = by_prefix.get(p)
        if slot is None:
            slot = by_prefix[p] = {"offered": 0, "shed": 0,
                                   "completed": 0, "deadline_miss": 0,
                                   "hist": LogHistogram()}
        return slot

    async def replay() -> None:
        batcher = AdaptiveBatcher(
            sph, batch_max=batch_max, deadline_ms=deadline_ms,
            budget_ms=budget_ms, idle_ms=idle_ms, depth=depth,
            queue_max=queue_max)
        if ctl is not None:
            ctl.bind_batcher(batcher)
        loop = asyncio.get_running_loop()
        t_start = loop.time()

        async def fire(r) -> None:
            delay = t_start + r.t_ms / 1000.0 - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            slot = _prefix_slot(r.resource)
            slot["offered"] += 1
            t0 = time.perf_counter_ns()
            try:
                v = await batcher.submit(r.resource, count=r.count,
                                         prioritized=r.prioritized,
                                         origin=r.origin)
            except IngestOverload:
                stats["shed"] += 1
                slot["shed"] += 1
                return
            dt = time.perf_counter_ns() - t0
            lat.record(dt)
            slot["completed"] += 1
            slot["hist"].record(dt)
            if dt > deadline_ns:
                stats["deadline_miss"] += 1
                slot["deadline_miss"] += 1
            if dt > worst["ns"]:
                worst["ns"], worst["trace"] = dt, v.trace_id
            stats["allowed" if v.allow else "blocked"] += 1

        await asyncio.gather(*(fire(r) for r in reqs))
        await batcher.drain()
        batcher.close()

    asyncio.run(replay())
    c = sph.obs.counters
    completed = stats["allowed"] + stats["blocked"]
    out = {
        "workload": name, "seed": seed, "duration_ms": duration_ms,
        "rate_rps": rate_rps, "offered": len(reqs),
        "completed": completed, "shed": stats["shed"],
        "allowed": stats["allowed"], "blocked": stats["blocked"],
        "deadline_miss": stats["deadline_miss"],
        "deadline_miss_frac": (stats["deadline_miss"] / completed
                               if completed else 0.0),
        "p50_ms": lat.percentile_ms(0.50),
        "p95_ms": lat.percentile_ms(0.95),
        "p99_ms": lat.percentile_ms(0.99),
        "max_ms": lat.snapshot()["max_ns"] / 1e6,
        "flush_full": c.get(obs_keys.FE_FLUSH_FULL),
        "flush_deadline": c.get(obs_keys.FE_FLUSH_DEADLINE),
        "flush_idle": c.get(obs_keys.FE_FLUSH_IDLE),
        "enqueued": c.get(obs_keys.FE_ENQUEUE),
        "queue_depth_sum": c.get(obs_keys.FE_QUEUE_DEPTH),
        "shed_counter": c.get(obs_keys.FE_SHED),
        "batcher": {"batch_max": batch_max, "deadline_ms": deadline_ms,
                    "budget_ms": budget_ms, "idle_ms": idle_ms,
                    "depth": depth, "queue_max": queue_max},
        # obs-sourced scoring surface (round 11 — what the autotuner
        # trials read: the engine's OWN request histogram + pipeline
        # counters, not the replay's wall clocks above)
        "p99_obs_ms": sph.obs.hist_request.percentile_ms(0.99),
        "settled_obs": sph.obs.hist_request.count,
        "pipe_stall": c.get(obs_keys.PIPE_STALL),
        "pipe_depth_sum": c.get(obs_keys.PIPE_DEPTH),
        # device dispatches per flushed batch (exits and ticks included;
        # a decide is 1 only when the sketch observe rides the decide
        # program — that invariant is gated by ci_gate gate (m))
        "dispatches": c.get(obs_keys.PIPE_DISPATCH),
        "route_single_dispatch": c.get(obs_keys.ROUTE_SINGLE_DISPATCH),
        "dispatches_per_batch": (
            round(c.get(obs_keys.PIPE_DISPATCH)
                  / (c.get(obs_keys.FE_FLUSH_FULL)
                     + c.get(obs_keys.FE_FLUSH_DEADLINE)
                     + c.get(obs_keys.FE_FLUSH_IDLE)), 4)
            if (c.get(obs_keys.FE_FLUSH_FULL)
                + c.get(obs_keys.FE_FLUSH_DEADLINE)
                + c.get(obs_keys.FE_FLUSH_IDLE)) else None),
        "decisions_per_s": (sph.obs.hist_request.count
                            / (duration_ms / 1e3) if duration_ms else 0.0),
        "by_prefix": {
            p: {"offered": s["offered"], "shed": s["shed"],
                "completed": s["completed"],
                "deadline_miss": s["deadline_miss"],
                "p50_ms": s["hist"].percentile_ms(0.50),
                "p95_ms": s["hist"].percentile_ms(0.95),
                "p99_ms": s["hist"].percentile_ms(0.99)}
            for p, s in sorted(by_prefix.items())},
    }
    if ctl is not None:
        out["control"] = ctl.snapshot(limit=64)
        out["control_dropped"] = c.get(obs_keys.CONTROL_DROPPED)
    if telem is not None and telem.enabled:
        telem.poll()                     # land anything still in flight
        tsnap = telem.snapshot()
        out["telemetry"] = {
            "k": tsnap["k"], "ticks": tsnap["ticks"],
            "drops": tsnap["drops"],
            "hot": [h["resource"] for h in tsnap["hot"][:8]],
        }
    # round 15 — tiered-state health rides every artifact: hit/miss
    # classification, migration counts + latency, cold-tier occupancy
    tiering = getattr(sph, "tiering", None)
    if tiering is not None and tiering.enabled:
        out["tiering"] = tiering.snapshot()
    # worst-request trace dump: the slowest request's causal chain as a
    # Chrome-trace document (load serving_bench.json, pull
    # workloads.<name>.worst_request.trace into ui.perfetto.dev) — must
    # happen before close() drops the span rings
    if worst["trace"] and sph.obs.enabled:
        from sentinel_tpu.obs import traceexport
        out["worst_request"] = {
            "latency_ms": worst["ns"] / 1e6,
            "trace_id": worst["trace"],
            "trace": traceexport.export_chain(sph.obs.spans,
                                              worst["trace"]),
        }
    sph.close()
    return out


#: The default zoo sweep (CPU-CI sized): per-workload overrides on top of
#: the shared duration/rate/seed.
ZOO: Dict[str, dict] = {
    "steady": {},
    "diurnal": {},
    "flash_crowd": {"wl_kwargs": {"spike_mult": 6.0}},
    "zipf_hot": {},
    "priority_mix": {},
    # deliberately small queue bound: the backpressure probe must SHED
    "slow_consumer": {"queue_max": 512,
                      "wl_kwargs": {"burst_mult": 16.0}},
    # round 17 — the controller episode: steady tenant + flash crowd +
    # slow-consumer bursts with the ControlLoop attached (its actions
    # and the per-tenant breakdown land in the artifact)
    "overload_episode": {"control": True, "queue_max": 1024,
                         "control_kwargs": {"interval_ms": 100}},
}


def main() -> int:
    results = {}
    for name, over in ZOO.items():
        res = run_workload(name, **over)
        results[name] = res
        print(json.dumps(res))
    from sentinel_tpu.tune import provenance as tuned_provenance
    artifact = {
        "schema": "serving_bench/1",
        "env_knobs": env_knobs(),
        # round 11: did a SENTINEL_TUNED_CONFIG artifact apply, from
        # where, under which fingerprint, with which per-knob values —
        # so a result is reproducible off-machine
        "tuned_config": tuned_provenance(),
        "defaults": {"duration_ms": DEFAULT_DURATION_MS,
                     "rate_rps": DEFAULT_RATE, "seed": DEFAULT_SEED},
        "workloads": results,
    }
    out_path = Path(os.environ.get("SERVING_BENCH_OUT",
                                   "serving_bench.json"))
    out_path.write_text(json.dumps(artifact, indent=1))
    print(f"artifact: {out_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
