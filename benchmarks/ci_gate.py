"""CI perf-regression gate: (a) the headline bench at CI-sized shapes on
the CPU backend, gated on decisions/sec; (b) the serving-path HOST-PREP
gate, portable across machines.

Usage:
    python benchmarks/ci_gate.py            # gate (exit 1 on regression)
    python benchmarks/ci_gate.py --update   # re-baseline after intentional
                                            # perf-relevant changes

Gate (a): the committed baseline is machine-relative, so it is only
*enforced* on a machine with the same fingerprint (cpu count + node name)
that produced it — there the gate uses a 2× margin over the best of three
runs. On any other machine (e.g. a shared CI runner of a different hardware
class) the gate falls back to an absolute sanity floor instead: the failure
mode that matters — an accidental per-event host loop, lost fusion, or an
accidental device sync per event — costs 3-5 orders of magnitude, which the
sanity floor catches on any hardware.

Gate (b) — the portable one: serving-path host prep (entry_batch /
request_tokens dispatch cost per step) is host code, whatever device
sits behind it, but raw ms/step still scales with machine class — so the gate measures a fixed pure-Python+numpy
CALIBRATION workload on the same machine and enforces the RATIO
host_prep/calibration. Machine speed cancels to first order; what's left is
the code: re-introducing a per-event Python loop moves the ratio by the
same factor on a laptop, this VM, or a shared CI runner, and fails the gate
everywhere. Margin 2.5× over the committed ratio. Run ``--update`` after
intentional host-prep changes.

Gate (c) — the prio-cliff gate (also portable): before r6, one prioritized
event demoted a whole batch to the sorted general path (a 16× cliff on the
TPU headline). Two checks pin it shut: (i) a BANDED ratio of the
general_bench ``prio_mixed`` metric (the occupy-aware split: scalar bulk +
fast-occupy prio slice) over the ``general`` metric (the sorted whole-batch
path a demotion collapses into) — machine speed cancels, and a reintroduced
demotion drags the ratio to ~1.0; (ii) a binary routing probe through the
runtime itself: a mixed 1%-prio batch must still take
``_decide_split_nowait`` (general_bench pre-stages its sub-batches, so only
this probe sees the runtime's routing decision).

Gate (d) — the observability-overhead gate (portable): the obs/ telemetry
layer rides the batch hot path behind ``if obs.enabled`` checks; this gate
times the SAME split-firing workload through two runtimes — obs enabled vs
``SENTINEL_OBS_DISABLE=1`` — interleaved best-of-N, and bands the
instrumented/uninstrumented step-time ratio at ``OBS_OVERHEAD_MAX`` (1.02,
the ISSUE's ≤2% budget). Machine speed cancels in the ratio.

Gate (e) — the dispatch-pipeline gate (r6, portable): the depth-2
``DispatchPipeline`` overlay must cost nothing material over the bare
sync loop
(≤ ``PIPELINE_OVERHEAD_MAX``), and the ``pipeline.depth`` counter must
prove batches genuinely overlapped in flight. The comment block above
``measure_dispatch_pipeline`` explains why the overlay's latency WIN is
carried by the BENCH artifacts rather than gated on the CPU backend.

Gate (f) — the serving SLO gate (r7): request→verdict latency through
the real ingest front end (frontend/batcher.py, replayed open-loop by
benchmarks/serving_bench.py). The steady workload's p99 must sit in
``STEADY_P99_BAND_MS`` at a pinned offered rate, with exact request
accounting; the flash-crowd run must shed/queue gracefully (no lost
futures, no deadline-miss collapse) while actually cutting full
batches. See the comment block above ``measure_serving``.

Gate (g) — the trace-capture mechanism probe (r8): an induced
flash-crowd deadline miss must leave a persisted ``<app>-trace`` chain
behind (obs/flight.py) that spans the request AND batch tiers and
survives the Chrome-trace export round trip. See the comment block
above ``TRACE_REQUIRED_REQUEST_SPAN``.

Gate (h) — the meshed-serving gate (r9): on an 8-virtual-device CPU
mesh (a ``--meshed`` subprocess, so XLA_FLAGS lands before jax
initializes), the row-sharded engine's verdicts through the FULL
serving path — DispatchPipeline, split/prio/occupy routing, a rule reload with live occupy bookings, and the
AdaptiveBatcher fan-out — must be bit-identical to the single-device
engine, and the weak-scaling curve's normalized per-partition cost must
stay flat (≤ ``WEAK_SCALING_FLAT_MAX``). ``CI_GATE_MESHED=0`` skips.
See the comment block above ``MESHED_ENV_FLAG``.

Gate (i) — the sort-free general-path gate (r10): the hash-bucketed
claim-cascade aggregation (ops/sortfree.py) is the DEFAULT general
aggregation; two engines built under SENTINEL_SORTFREE=1 vs =0 must
produce BIT-IDENTICAL verdicts through the real dispatch (pair-key
general route, split route with a prioritized occupy slice, booking
carry across a mid-stream rule reload), the ``split_route.sortfree``
attribution must tick on the sortfree engine only, the DEFAULT-sized
claim table must not overflow, and the sortfree/sorted general
throughput ratio must stay ≥ ``SORTFREE_MIN_RATIO`` on the CPU backend
— a band that pins the cascade's KNOWN below-parity CPU cost from
degenerating (XLA:CPU's sort is the fast case; the win this round
claims is the accelerator's, carried informationally by the bench
artifacts ``general`` vs ``general_sortfree`` and their
``aggregation_ms`` keys). ``CI_GATE_SORTFREE=0`` skips. See the
comment block above ``SORTFREE_ENV_FLAG``.

Gate (j) — the autotune gate (r11): a tiny CPU sweep (2 knobs × small
grids, short rungs) through ``sentinel_tpu.tune.run_sweep`` must
CONVERGE with every trial passing the verdict bit-parity spot-check
and pin a ``TUNED.json``; the pinned config, loaded back through the
real ``SENTINEL_TUNED_CONFIG`` startup path, must then produce
bit-identical verdicts below the batcher and ≥ ``TUNE_MIN_RATIO`` of
the default config's throughput through the full serving replay.
``CI_GATE_TUNE=0`` skips. See the comment block above
``TUNE_ENV_FLAG``.

Gate (k) — the hot-resource telemetry gate (r12): a planted-hot-key
Zipf mix through the FULL serving path (engine + ``start_transport`` +
dashboard server) must surface the planted keys in ``/obs/topk.json``
(hottest planted key ranked FIRST — the sharded top-K is exact, not
approximate) AND in the ``<app>-metric`` log read back through
``MetricSearcher``, with a non-empty per-second timeline; and the obs
overhead probe re-run with the telemetry ticker ON (5 Hz, harsher than
the production 1 Hz) must stay inside the same fixed
``OBS_OVERHEAD_MAX`` band — telemetry must not cost what obs/ saved.
``CI_GATE_TELEMETRY=0`` skips. See the comment block above
``TELEMETRY_ENV_FLAG``.

Gate (l) — the tiered-state gate (r15): a 16M-key Zipf(s=1.1) stream
through the FULL serving path (AdaptiveBatcher replay, the tiering
ticker running at a small ``SENTINEL_HOT_ROWS`` target) must sustain a
hot-tier hit rate ≥ ``TIER_HIT_RATE_MIN`` while actually migrating
rows (nonzero ``tier.promoted`` AND ``tier.demoted``) and recording
the migration-latency histogram; and a resident-key parity probe —
identical seeded traffic with live flow rules and a mid-run rule
reload, through a hot tier an order of magnitude smaller than the key
set vs an all-resident engine — must produce BIT-IDENTICAL verdicts
(the cold tier's demote→promote round trip may never change an
answer). The obs-overhead band (gate d, ≤ ``OBS_OVERHEAD_MAX``) now
runs with tiering ON on both engines, so the sketch-update dispatch
cost is already inside that band. ``CI_GATE_TIER=0`` skips. See the
comment block above ``TIER_ENV_FLAG``.

Gate (m) — the single-dispatch gate (r16): a decide batch on a
tiering engine must cost exactly ONE device dispatch
(``pipeline.dispatches`` rises by one per batch; the sketch observe
rides the jitted decide program), and verdicts AND the count-min table
must be bit-identical between ``SENTINEL_SINGLE_DISPATCH=1`` and ``=0``
through tiered churn with a mid-run rule reload.
``CI_GATE_SINGLE_DISPATCH=0`` skips. See the comment block above
``SINGLE_DISPATCH_ENV_FLAG``.
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BASELINE_FILE = HERE / "ci_baseline.json"

# any machine that can run the suite at all clears this unless the fused
# step degenerates into per-event Python/host work (that failure mode
# costs ~1000x; honest CPU throughput at gate shapes is ~0.3-1M/s)
SANITY_FLOOR_DECISIONS_PER_SEC = 2e5

ENV = {
    **os.environ,
    # the gates are CPU-only by design: the child never takes a chip
    "JAX_PLATFORMS": "cpu",
    "BENCH_RESOURCES": str(1 << 14),
    "BENCH_BATCH": str(1 << 13),
    "BENCH_STEPS": "20",
    "BENCH_RULES": "256",
    # the gate times the scalar headline; the general/mixed add-ons
    # (bench.py BENCH_GENERAL) would triple gate wall time for a number
    # gated separately by the parity tests
    "BENCH_GENERAL": "0",
}


def fingerprint() -> str:
    return f"{platform.node()}/{os.cpu_count()}cpu"


def measure_once() -> float:
    out = subprocess.run(
        [sys.executable, str(HERE.parent / "bench.py")], env=ENV,
        capture_output=True, text=True, timeout=600, check=True)
    line = out.stdout.strip().splitlines()[-1]
    return float(json.loads(line)["value"])


HOST_PREP_MARGIN = 2.5


def calibrate() -> float:
    """Fixed CPU reference workload (numpy vector ops + dict/string churn,
    the same primitive mix the host-prep paths use) → seconds. Used to
    normalize host-prep timings into a machine-independent ratio."""
    import time as _time

    import numpy as np
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 5000, 200_000)
    t0 = _time.perf_counter()
    for _ in range(10):
        u, inv = np.unique(keys, return_inverse=True)
        _ = u[inv][:1000].tolist()
        d = {}
        for i in range(20_000):
            d[f"k{i & 1023}"] = i
        _ = np.argsort(keys[:50_000], kind="stable")
    return _time.perf_counter() - t0


def measure_host_prep() -> dict:
    """Serving-path host-prep seconds/step on the CPU backend: the dispatch
    side of entry_batch_nowait (param keys) and request_tokens_nowait
    (cluster grouping) — the two vectorized prep paths this gate holds."""
    import time as _time

    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu
    from sentinel_tpu.parallel.cluster import (
        THRESHOLD_GLOBAL, ClusterEngine, ClusterFlowRule, ClusterSpec,
    )

    B, STEPS = 4096, 12
    # donation off for THIS runtime: the CPU PJRT client acquires donated
    # buffers synchronously, which folds device step time into the
    # dispatch call — this gate pins the HOST marshalling code, so it
    # must time an undonated dispatch (the donated fast path is covered
    # by gate (e) and the parity tests)
    prev_donate = os.environ.get("SENTINEL_DONATE")
    os.environ["SENTINEL_DONATE"] = "0"
    try:
        sph = stpu.Sentinel(stpu.load_config(
            max_resources=256, max_flow_rules=16, max_degrade_rules=16,
            max_authority_rules=16, max_param_rules=16,
            param_table_slots=1 << 12))
    finally:
        if prev_donate is None:
            os.environ.pop("SENTINEL_DONATE", None)
        else:
            os.environ["SENTINEL_DONATE"] = prev_donate
    sph.load_param_flow_rules([stpu.ParamFlowRule(
        resource="hot", param_idx=0, count=1e9)])
    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.2, size=B * STEPS) % 2048).reshape(STEPS, B, 1)
    resources = ["hot"] * B
    handles = [sph.entry_batch_nowait(resources, args_list=keys[0])
               for _ in range(2)]          # warm compile + caches
    for h in handles:
        h.result()
    entry_times = []
    for s in range(STEPS):
        t0 = _time.perf_counter()
        h = sph.entry_batch_nowait(resources, args_list=keys[s])
        entry_times.append(_time.perf_counter() - t0)
        h.result()

    eng = ClusterEngine(ClusterSpec(n_shards=1, flows_per_shard=64,
                                    namespaces=4))
    eng.load_rules("ns", [ClusterFlowRule(flow_id=i, count=1e9,
                                          threshold_type=THRESHOLD_GLOBAL)
                          for i in range(64)])
    ids = rng.integers(0, 64, B)
    ones = np.ones(B, np.int64)
    eng.request_tokens(ids, ones, now_ms=10_000_000)
    cluster_times = []
    for s in range(STEPS):
        t0 = _time.perf_counter()
        h = eng.request_tokens_nowait(ids, ones, now_ms=10_000_100 + s)
        cluster_times.append(_time.perf_counter() - t0)
        h.result()
    return {"entry_prep_s_per_step": min(entry_times),
            "cluster_prep_s_per_step": min(cluster_times)}


# prio_mixed / general throughput band at gate shapes. Honest CPU value is
# ~1.5 (both prio_mixed dispatches skip alt recording; general pays the
# composite-key sort + alt scatter). A reintroduced whole-batch demotion
# makes the prio-mixed workload RUN the general path, so the ratio falls to
# ~1.0 — well below the low edge. The high edge catches a degenerated
# denominator (the general measurement itself collapsing) rather than a
# legitimate speedup: both sides share the same fixture and backend, so a
# >8x gap means the gate is no longer measuring what it claims.
PRIO_RATIO_BAND = (1.15, 8.0)


def measure_prio_cliff() -> dict:
    """Kernel-level prio gate: general_bench's ``prio_mixed`` (the exact
    two-dispatch split shape the runtime issues for a 1%-prioritized batch
    with live bookings) vs ``general`` (the sorted whole-batch path the
    pre-r6 demotion forced everything onto), both in-process at small CPU
    shapes. The RATIO is the gated number — machine speed cancels."""
    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import general_bench

    R, B, STEPS, NRULES, REPEATS = 1 << 12, 1 << 12, 8, 128, 3
    pm = general_bench.measure(jax, "prio_mixed", R, B, STEPS, NRULES,
                               REPEATS)["value"]
    gen = general_bench.measure(jax, "general", R, B, STEPS, NRULES,
                                REPEATS)["value"]
    return {"prio_mixed_per_sec": pm, "general_per_sec": gen,
            "prio_vs_general_ratio": pm / gen}


def check_prio_split_routing():
    """Runtime-level prio gate → error string or None. general_bench
    pre-stages the split's sub-batches, so a demotion reintroduced in
    ``runtime._decide_split_nowait`` would not move the metric above —
    this probe feeds a mixed 1%-prio batch through the runtime and
    asserts the split dispatch actually fires."""
    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu

    sph = stpu.Sentinel(stpu.load_config(
        max_resources=64, max_origins=32, max_flow_rules=32,
        max_degrade_rules=16, max_authority_rules=16,
        host_fast_path=False))
    sph.load_flow_rules([
        stpu.FlowRule(resource="api", count=500.0),
        stpu.FlowRule(resource="api", count=3.0, limit_app="app-a"),
    ])
    oid = sph.origins.pin("app-a")
    row = sph.resources.get_or_create("api")
    rng = np.random.default_rng(7)
    n = 8192                      # scalar side > the 4096 split threshold
    pad_a = sph.spec.alt_rows
    has_o = rng.random(n) < 0.1
    oids = np.where(has_o, oid, 0).astype(np.int32)
    orow = np.where(has_o, sph._alt_row(row, 0, int(oid)),
                    pad_a).astype(np.int32)
    calls = []
    orig = sph._decide_split_nowait
    sph._decide_split_nowait = lambda *a, **k: (calls.append(1),
                                                orig(*a, **k))[1]
    sph.decide_raw(np.full(n, row, np.int32), oids, orow,
                   np.zeros(n, np.int32), np.full(n, pad_a, np.int32),
                   np.ones(n, np.int32), np.ones(n, bool),
                   rng.random(n) < 0.01)          # 1% prioritized
    if not calls:
        return ("mixed 1%-prio batch did not take the split dispatch — "
                "whole-batch prioritized demotion is back (pre-r6 cliff)")
    return None


# instrumented/uninstrumented wall-time band for the observability layer
# (obs/): the spans + counters + histograms riding the batch hot path must
# stay within 2% of SENTINEL_OBS_DISABLE=1. Measured best-of-N interleaved
# THROUGH the runtime (entry_batch_nowait with a split-firing mixed batch)
# — general_bench.measure() pre-stages sub-batches and drives the jitted
# step directly, so it never executes a single instrumented line.
OBS_OVERHEAD_MAX = 1.02


def measure_obs_overhead() -> dict:
    """Ratio of best entry-batch step time with obs enabled over obs
    disabled (two otherwise-identical runtimes, the disabled one built
    under SENTINEL_OBS_DISABLE=1). Mixed 10%-origin batches above the
    4096-row threshold so the split path — the most-instrumented route —
    is the one being timed. Both runtimes build under the default env,
    so from round 20 BOTH carry the per-resource RT histogram scatter
    in record_exits — the band therefore re-verifies with histograms
    enabled, and the scatter itself is exercised on the timed path."""
    import time as _time

    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu
    from sentinel_tpu.obs import OBS_DISABLE_ENV

    def build(disable: bool):
        prev = os.environ.get(OBS_DISABLE_ENV)
        if disable:
            os.environ[OBS_DISABLE_ENV] = "1"
        else:
            os.environ.pop(OBS_DISABLE_ENV, None)
        try:
            sph = stpu.Sentinel(stpu.load_config(
                max_resources=64, max_origins=32, max_flow_rules=32,
                max_degrade_rules=16, max_authority_rules=16,
                host_fast_path=False))
        finally:
            if prev is None:
                os.environ.pop(OBS_DISABLE_ENV, None)
            else:
                os.environ[OBS_DISABLE_ENV] = prev
        sph.load_flow_rules([
            stpu.FlowRule(resource="api", count=1e9),
            stpu.FlowRule(resource="api", count=1e9, limit_app="app-a"),
        ])
        return sph

    B, STEPS, REPEATS = 8192, 6, 8
    rng = np.random.default_rng(11)
    resources = ["api"] * B
    origins = ["app-a" if x else "" for x in (rng.random(B) < 0.1)]
    pair = [("on", build(False)), ("off", build(True))]
    assert pair[0][1].obs.enabled and not pair[1][1].obs.enabled
    best = {}
    for _key, sph in pair:                  # warm compiles + caches
        for _ in range(2):
            sph.entry_batch_nowait(resources, origins=origins).result()
    for rep in range(REPEATS):
        # interleaved AND order-alternated: slow drift and the
        # first-measured-runs-warmer bias both cancel in the ratio
        for key, sph in (pair if rep % 2 == 0 else pair[::-1]):
            t0 = _time.perf_counter()
            for _ in range(STEPS):
                sph.entry_batch_nowait(resources,
                                       origins=origins).result()
            dt = (_time.perf_counter() - t0) / STEPS
            best[key] = min(best.get(key, dt), dt)
    for _key, sph in pair:
        sph.close()
    return {"obs_on_s_per_step": best["on"],
            "obs_off_s_per_step": best["off"],
            "obs_overhead_ratio": best["on"] / best["off"]}


# Gate (e) — the dispatch-pipeline gate (r6, portable). Ratios, so machine
# speed cancels:
#   overlay:  DispatchPipeline(depth=2) vs the sync loop through
#             entry_batch_nowait. On THIS backend the window is ~
#             breakeven — the CPU PJRT client acquires donated buffers
#             synchronously at dispatch and chained steps serialize on
#             device anyway — so the CPU pin is "adds no material
#             overhead" (≤ PIPELINE_OVERHEAD_MAX), while the depth/stall
#             counters prove batches genuinely overlapped in flight. The
#             latency WIN of the window is an accelerator-backend effect,
#             carried by the BENCH artifacts (bench.py "serving" +
#             dispatch_floor_*_ms keys), not gateable on CPU.
#   floor:    tiny-op per-dispatch readback vs a depth-2 deferred-readback
#             window — recorded for the artifact trail but NOT gated: the
#             CPU round trip is ~35 µs, so the window's deque overhead is
#             the same order as the savings and the ratio is noise there.
PIPELINE_OVERHEAD_MAX = 1.10


def measure_dispatch_pipeline() -> dict:
    import time as _time

    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import sentinel_tpu as stpu
    from sentinel_tpu.obs import counters as obs_keys

    # --- floor pin: per-dispatch readback vs depth-2 deferred window ---
    import collections
    tiny = jax.jit(lambda x: x + 1)
    x0 = jnp.zeros((8,), jnp.int32)
    _ = np.asarray(tiny(x0)[:1])
    N = 200

    def floor_sync() -> float:
        t0 = _time.perf_counter()
        for _ in range(N):
            _ = np.asarray(tiny(x0)[:1])
        return (_time.perf_counter() - t0) / N

    def floor_pipe() -> float:
        window: "collections.deque" = collections.deque()
        t0 = _time.perf_counter()
        for _ in range(N):
            window.append(tiny(x0))
            if len(window) > 2:
                _ = np.asarray(window.popleft()[:1])
        while window:
            _ = np.asarray(window.popleft()[:1])
        return (_time.perf_counter() - t0) / N

    fbest = {}
    for rep in range(8):
        for key, fn in ([("s", floor_sync), ("p", floor_pipe)]
                        if rep % 2 == 0 else
                        [("p", floor_pipe), ("s", floor_sync)]):
            dt = fn()
            fbest[key] = min(fbest.get(key, dt), dt)

    # --- runtime fixture of the overlay pin ---
    B, STEPS, REPEATS = 8192, 6, 8
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=1024, max_flow_rules=64, max_degrade_rules=16,
        max_authority_rules=16))
    sph.load_flow_rules([stpu.FlowRule(resource=f"s{i}", count=1e9)
                         for i in range(64)])
    rng = np.random.default_rng(13)
    rows = sph.intern_resources(
        [f"s{int(i)}" for i in rng.integers(0, 512, B)])

    def run_sync() -> float:
        t0 = _time.perf_counter()
        for _ in range(STEPS):
            sph.entry_batch_nowait(rows).result()
        return (_time.perf_counter() - t0) / STEPS

    def run_pipelined() -> float:
        pipe = stpu.DispatchPipeline(sph, depth=2)
        tickets: "collections.deque" = collections.deque()
        t0 = _time.perf_counter()
        for _ in range(STEPS):
            tickets.append(pipe.submit(rows))
            if len(tickets) > pipe.depth:
                tickets.popleft().result()
        while tickets:
            tickets.popleft().result()
        return (_time.perf_counter() - t0) / STEPS

    best = {}
    pairs = [("sync", run_sync), ("pipelined", run_pipelined)]
    for _key, fn in pairs:                       # warm compiles + caches
        fn()
    for rep in range(REPEATS):
        for key, fn in (pairs if rep % 2 == 0 else pairs[::-1]):
            dt = fn()
            best[key] = min(best.get(key, dt), dt)

    # mechanism probe: the overlay numbers only mean something if batches
    # actually were in flight together
    depth_sum = sph.obs.counters.get(obs_keys.PIPE_DEPTH)
    stalls = sph.obs.counters.get(obs_keys.PIPE_STALL)
    # run_pipelined executed once to warm + once per repeat; average
    # in-flight depth > 1 ⟺ depth_sum > enqueues
    enqueues = (REPEATS + 1) * STEPS
    sph.close()
    return {
        "floor_sync_s": fbest["s"], "floor_pipelined_s": fbest["p"],
        "floor_ratio": fbest["p"] / fbest["s"],
        "sync_s_per_step": best["sync"],
        "pipelined_s_per_step": best["pipelined"],
        "pipeline_overhead_ratio": best["pipelined"] / best["sync"],
        "pipelined_depth_reached": depth_sum > enqueues,
        "pipeline_stalls": stalls,
    }


# Gate (f) — the serving SLO gate (r7): end-to-end request→verdict
# latency through the real front end (frontend/batcher.py open-loop
# replay, benchmarks/serving_bench.py). Two probes:
#   steady:  at a pinned offered rate on the CPU backend, the p99 must
#            sit inside a BAND — the high edge is the SLO (generous vs
#            the ~16 ms measured here: CPU CI machine classes vary, but
#            an event-loop stall, a lost wakeup, or a blocking call on
#            the loop thread costs 10-100×, which any hardware catches);
#            the low edge catches a degenerated measurement (a p99 of
#            ~0 means requests never crossed the device). Zero shed and
#            exact accounting (completed == offered) are part of the pin.
#   flash:   an 8× arrival spike against a small batch bound must DEGRADE
#            GRACEFULLY: every request accounted (completed + shed ==
#            offered — no lost futures), no deadline-miss collapse
#            (< FLASH_MISS_COLLAPSE of completed missing their budget),
#            and the mechanism probe — the spike must actually cut
#            batch_max-full batches (flush_full > 0), or the run never
#            stressed the coalescing path it claims to.
STEADY_P99_BAND_MS = (0.2, 150.0)
FLASH_MISS_COLLAPSE = 0.9

# Gate (g) — the trace-capture mechanism probe (r8): an induced
# flash-crowd deadline miss must leave a PERSISTED causal chain behind.
# A fresh flash replay with a 2 ms request deadline (every settled
# request misses) runs with the flight recorder's <app>-trace log
# attached to a temp dir; the probe then reads the rotation back with
# ``load_pinned`` and requires (i) ≥1 pinned record including a
# ``deadline_miss`` kind, (ii) the chain to span the TIERS — the
# request-side terminal span (frontend.settle) AND a batch-side span
# (frontend.flush / pipeline.enqueue) reached through a fan-in link —
# and (iii) the record to survive the Chrome-trace export + json.loads
# round trip. Each leg pins a different failure: trace-id threading
# severed (chain collapses to one tier), trigger plumbing dead (no
# record at all), writer/searcher codec drift (parse failure).
TRACE_REQUIRED_REQUEST_SPAN = "frontend.settle"
TRACE_REQUIRED_BATCH_SPANS = ("frontend.flush", "pipeline.enqueue")


def measure_serving() -> dict:
    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import serving_bench

    steady = serving_bench.run_workload(
        "steady", seed=42, duration_ms=600.0, rate_rps=1000.0)
    flash = serving_bench.run_workload(
        "flash_crowd", seed=43, duration_ms=600.0, rate_rps=1000.0,
        batch_max=64, wl_kwargs={"spike_mult": 8.0})
    return {
        "steady_p99_ms": steady["p99_ms"],
        "steady_worst_traced": bool(
            steady.get("worst_request", {}).get("trace")),
        "steady_p50_ms": steady["p50_ms"],
        "steady_offered": steady["offered"],
        "steady_completed": steady["completed"],
        "steady_shed": steady["shed"],
        "flash_offered": flash["offered"],
        "flash_completed": flash["completed"],
        "flash_shed": flash["shed"],
        "flash_miss_frac": flash["deadline_miss_frac"],
        "flash_flush_full": flash["flush_full"],
        "flash_p50_ms": flash["p50_ms"],
    }


def measure_trace_capture() -> dict:
    """Gate (g): induced deadline misses must pin a persisted, parseable,
    tier-spanning causal chain (see the comment block above
    TRACE_REQUIRED_REQUEST_SPAN)."""
    import shutil
    import tempfile

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import serving_bench
    from sentinel_tpu.obs import flight as flight_mod
    from sentinel_tpu.obs import traceexport

    tmp = tempfile.mkdtemp(prefix="sentinel-trace-gate-")
    try:
        res = serving_bench.run_workload(
            "flash_crowd", seed=44, duration_ms=300.0, rate_rps=1000.0,
            batch_max=64, deadline_ms=2, wl_kwargs={"spike_mult": 8.0},
            trace_dir=tmp)
        pinned = flight_mod.load_pinned(tmp, "flash_crowd")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    kinds, names = set(), set()
    for rec in pinned:
        kinds.add(rec.get("kind"))
        for s in rec.get("spans", ()):
            names.add(s.get("name"))
    chain_ok = False
    export_ok = False
    for rec in pinned:
        rec_names = {s.get("name") for s in rec.get("spans", ())}
        if (TRACE_REQUIRED_REQUEST_SPAN in rec_names
                and rec_names.intersection(TRACE_REQUIRED_BATCH_SPANS)
                and rec.get("links")):
            chain_ok = True
            doc = json.loads(traceexport.dumps(traceexport.chrome_trace(rec)))
            export_ok = bool(doc.get("traceEvents"))
            break
    return {
        "induced_misses": res["deadline_miss"],
        "pinned_records": len(pinned),
        "kinds": sorted(k for k in kinds if k),
        "chain_spans_tiers_ok": chain_ok,
        "chrome_trace_ok": export_ok,
    }


# Gate (h) — the meshed-serving gate (r9): the row-sharded engine IS the
# serving hot path, so its promotion is pinned by two probes run in a
# dedicated subprocess on an 8-virtual-device CPU mesh (XLA_FLAGS must be
# set before the jax backend initializes — hence the ``--meshed``
# re-exec, the same isolation trick measure_once uses for bench.py):
#   parity:   a single-device engine and an 8-device meshed engine are
#             driven through the FULL serving stack with identical
#             traffic — DispatchPipeline over decide_raw_nowait (a mixed
#             batch above the split threshold with 10% origins and 1%
#             prioritized, so the split + fast-occupy routes fire), a
#             mid-stream rule reload with live occupy bookings (the
#             carry path), and the
#             AdaptiveBatcher fan-out (meshed verdicts replayed
#             flush-by-flush on the single-device twin) — and every
#             verdict must be BIT-IDENTICAL. Placement is layout, not
#             math; any divergence means the mesh path computes
#             something different from what the tests promise.
#             Mechanism probes ride along: the split dispatch must
#             actually fire, ROUTE_MESHED/PIPE_MESHED must tick, and
#             both engines must CARRY the same number of live occupy
#             bookings across the reload (a zero means the probe never
#             exercised the carry path it claims to pin).
#   flatness: the weak-scaling curve (benchmarks/weak_scaling.py) at
#             small shapes — fixed rows per device, 1/2/4/8 devices,
#             depth-swept through the pipeline. On this host the
#             virtual devices SERIALIZE, so the gated number is the
#             normalized per-partition cost step_ms(n)/(n·step_ms(1)):
#             ~1.0 benign (measured 0.71-1.02 here), and climbing past
#             WEAK_SCALING_FLAT_MAX only on super-linear pathology
#             (all-to-all blowup, per-shard recompiles, a host loop
#             over shards) — the portable signal that survives the move
#             to real parallel silicon.
# CI_GATE_MESHED=0 skips the whole gate (e.g. a tier that already ran
# it, or a debug loop on the other gates).
MESHED_ENV_FLAG = "CI_GATE_MESHED"
WEAK_SCALING_FLAT_MAX = 1.6
MESHED_N_DEV = 8


def _meshed_parity(jax) -> dict:
    import numpy as np

    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.obs import counters as obs_keys
    from sentinel_tpu.parallel.local_shard import local_mesh
    from sentinel_tpu.serving import DispatchPipeline

    T0 = 1_785_000_000_000

    def cfg():
        return stpu.load_config(
            max_resources=64, max_origins=32, max_flow_rules=32,
            max_degrade_rules=16, max_authority_rules=16,
            host_fast_path=False)

    def build(mesh):
        s = stpu.Sentinel(cfg(), clock=ManualClock(start_ms=T0), mesh=mesh)
        s.load_flow_rules([
            stpu.FlowRule(resource="api", count=3.0),
            stpu.FlowRule(resource="api", count=2.0, limit_app="app-a"),
            stpu.FlowRule(resource="bulk", count=1e6),
        ])
        return s

    ref, meshed = build(None), build(local_mesh(MESHED_N_DEV))

    def vequal(a, b) -> bool:
        return (np.array_equal(np.asarray(a.allow), np.asarray(b.allow))
                and np.array_equal(np.asarray(a.reason),
                                   np.asarray(b.reason))
                and np.array_equal(np.asarray(a.wait_ms),
                                   np.asarray(b.wait_ms)))

    # mixed raw traffic above the 4096 split threshold: 90% scalar bulk,
    # 10% origin-carrying (the general side), 1% prioritized (the
    # fast-occupy side, denied often enough under count=3.0 to book)
    rng = np.random.default_rng(29)
    n = 8192
    row_api = ref.resources.get_or_create("api")
    row_bulk = ref.resources.get_or_create("bulk")
    assert meshed.resources.get_or_create("api") == row_api
    assert meshed.resources.get_or_create("bulk") == row_bulk
    oid = ref.origins.pin("app-a")
    meshed.origins.pin("app-a")
    pad_a = ref.spec.alt_rows
    rows = np.where(rng.random(n) < 0.5, row_api, row_bulk).astype(np.int32)
    has_o = rng.random(n) < 0.1
    oids = np.where(has_o, oid, 0).astype(np.int32)
    # alt rows are scalar-hashed per (resource row, origin); record the
    # edge on BOTH engines so eviction hygiene stays in lockstep
    alt = {r: ref._alt_row(r, 0, int(oid)) for r in (row_api, row_bulk)}
    for r in (row_api, row_bulk):
        assert meshed._alt_row(r, 0, int(oid)) == alt[r]
    orow = np.where(has_o,
                    np.where(rows == row_api, alt[row_api], alt[row_bulk]),
                    pad_a).astype(np.int32)
    ctx0 = np.zeros(n, np.int32)
    chain = np.full(n, pad_a, np.int32)
    ones = np.ones(n, np.int32)
    is_in = np.ones(n, np.bool_)
    prio = rng.random(n) < 0.01

    split_calls = []
    orig_split = meshed._decide_split_nowait
    meshed._decide_split_nowait = lambda *a, **k: (
        split_calls.append(1), orig_split(*a, **k))[1]

    out = {"parity": {}}
    pipes = {"ref": DispatchPipeline(ref, depth=2),
             "meshed": DispatchPipeline(meshed, depth=2)}

    def drive_raw(steps: int, tick0: int) -> bool:
        got = {}
        for key, pipe in pipes.items():
            tickets = [pipe.submit_raw(
                rows, oids, orow, ctx0, chain, ones, is_in, prio,
                at_ms=T0 + (tick0 + i) * 250) for i in range(steps)]
            got[key] = [t.result() for t in tickets]
        return all(vequal(a, b) for a, b in zip(got["ref"], got["meshed"]))

    # depth-2 pipelined dispatch, windows rotating, split + occupy live
    out["parity"]["pipeline_raw"] = drive_raw(4, 0)
    granted = {k: s.obs.counters.get(obs_keys.OCCUPY_GRANTED)
               for k, s in (("ref", ref), ("meshed", meshed))}
    # rule reload with those bookings still PENDING: the engine clock
    # must first catch up to the traffic timeline — settle_occupied
    # carries only bookings whose target window is the clock's next one
    for s in (ref, meshed):
        s.clock.advance_ms(750)
        s.load_flow_rules([
            stpu.FlowRule(resource="api", count=4.0),
            stpu.FlowRule(resource="api", count=2.0, limit_app="app-a"),
            stpu.FlowRule(resource="bulk", count=1e6),
        ])
    out["parity"]["post_reload"] = drive_raw(4, 4)
    out["split_fired"] = len(split_calls)
    out["occupy_granted_ref"] = granted["ref"]
    out["occupy_granted_meshed"] = granted["meshed"]
    out["occupy_carried_ref"] = ref.obs.counters.get(
        obs_keys.OCCUPY_CARRIED)
    out["occupy_carried_meshed"] = meshed.obs.counters.get(
        obs_keys.OCCUPY_CARRIED)
    out["route_meshed"] = meshed.obs.counters.get(obs_keys.ROUTE_MESHED)
    out["pipe_meshed"] = meshed.obs.counters.get(obs_keys.PIPE_MESHED)
    ref.close()
    meshed.close()

    # front-end fan-out: the batcher on the MESHED engine, its recorded
    # flush cuts replayed sequentially on a fresh single-device twin
    import asyncio

    from sentinel_tpu.frontend.batcher import AdaptiveBatcher

    fe_m, seq_r = build(local_mesh(MESHED_N_DEV)), build(None)
    frng = np.random.default_rng(31)
    stream = [("api" if frng.random() < 0.7 else "bulk",
               bool(frng.random() < 0.3),
               "app-a" if frng.random() < 0.4 else "")
              for _ in range(42)]

    async def run():
        b = AdaptiveBatcher(fe_m, batch_max=8, deadline_ms=60_000,
                            idle_ms=10_000.0, depth=2, record_flushes=True)
        verdicts = await asyncio.gather(
            *(b.submit(r, prioritized=p, origin=o) for r, p, o in stream))
        await b.drain()
        return verdicts, b.flush_log

    verdicts, flush_log = asyncio.run(run())
    seq = []
    for f in flush_log:
        v = seq_r.entry_batch_nowait(
            f["resources"],
            acquire=np.asarray(f["counts"], np.int32),
            prioritized=np.asarray(f["prioritized"], np.bool_),
            origins=(f["origins"] if any(f["origins"]) else None),
        ).result()
        seq.extend(zip(np.asarray(v.allow), np.asarray(v.reason),
                       np.asarray(v.wait_ms)))
    out["parity"]["frontend"] = (
        len(seq) == len(verdicts)
        and all((g.allow, g.reason, g.wait_ms)
                == (bool(w[0]), int(w[1]), int(w[2]))
                for g, w in zip(verdicts, seq)))
    fe_m.close()
    seq_r.close()
    return out


def meshed_main() -> int:
    """The ``--meshed`` re-exec body: 8 virtual CPU devices (flag set
    before jax initializes), parity + flatness, ONE JSON line out."""
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={MESHED_N_DEV}")
    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import weak_scaling

    out = _meshed_parity(jax)
    points = weak_scaling.measure(
        jax, rows_per_dev=2048, batch=4096, steps=4,
        device_counts=(1, 2, 4, MESHED_N_DEV), depths=(1, 2), rules=64)
    out["curve_devices"] = [p["devices"] for p in points if "step_ms" in p]
    out["flatness_norm"] = weak_scaling.flatness(points)
    print(json.dumps(out))
    return 0


def measure_meshed() -> dict:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={MESHED_N_DEV}",
    }
    out = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--meshed"],
        env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        return {"error": (out.stderr or out.stdout)[-2000:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


# Gate (i) — the sort-free general-path gate (r10): ops/sortfree.py's
# hash-bucketed claim cascade replaced the n·log n composite-key sort as
# the DEFAULT general/mixed aggregation, with the sorted path kept as a
# bit-parity reference behind SENTINEL_SORTFREE=0. Two probes pin the
# promotion:
#   parity:   two engines built under SENTINEL_SORTFREE=1 vs =0 are
#             driven through the REAL dispatch with identical traffic
#             in two phases — first a rate-limiter ruleset (the
#             per-rule segment collapse) under a non-uniform-acquire
#             mixed batch (defeats the fast-path uniform-acquire
#             precondition, so the whole batch takes the pair-key
#             GENERAL route the cascade owns) plus a split-firing
#             8192-row mixed batch; then a reload to an occupy-capable
#             ruleset whose 1% prioritized slice is denied often
#             enough under count=3.0 to book PriorityWait, with a
#             second reload while those bookings are live (the carry
#             fold) — and every verdict must be BIT-IDENTICAL.
#             Mechanism probes ride along:
#             split_route.sortfree must tick on the sortfree engine and
#             stay dead on the sorted one, ROUTE_GENERAL and the split
#             dispatch must prove the cascade routes actually ran, the
#             carried booking counts must match, and the DEFAULT-sized
#             claim table must not overflow (an overflow here means
#             table sizing regressed — the lax.cond sorted fallback
#             would hide the perf loss while parity stays green).
#   ratio:    general_bench mode="general" sortfree/sorted decisions
#             per sec at small CPU shapes — machine speed cancels. The
#             honest CPU story: XLA:CPU's sort
#             is excellent and the claim cascade's chunked scatter scan
#             is serial there, so sortfree runs BELOW parity on this
#             backend (~0.78× at the gate's B=4096, degrading with B —
#             the win this round claims is the accelerator's, where the
#             composite-key sort is the bottleneck the paper names).
#             The band therefore pins the CPU cost from DEGENERATING,
#             not from existing: a per-element host loop, lost fusion,
#             or an accidental sync costs 10-1000×, which ≥
#             SORTFREE_MIN_RATIO catches on any hardware, while the
#             accelerator-side win is carried informationally by the
#             bench artifacts.
# CI_GATE_SORTFREE=0 skips the whole gate.
SORTFREE_ENV_FLAG = "CI_GATE_SORTFREE"
SORTFREE_MIN_RATIO = 0.5


def _sortfree_parity() -> dict:
    import numpy as np

    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.obs import counters as obs_keys

    T0 = 1_785_000_000_000
    # phase 1 carries the RATE-LIMITER rule (the per-rule segment
    # collapse the cascade must reproduce); phase 2 swaps it for the
    # always-pass bulk rule because an RL rule in the ruleset suppresses
    # PriorityWait grants — the occupy booking/carry probe needs them
    RULES_RL = [
        stpu.FlowRule(resource="api", count=3.0),
        stpu.FlowRule(resource="api", count=2.0, limit_app="app-a"),
        stpu.FlowRule(resource="paced", count=10.0,
                      control_behavior=stpu.BEHAVIOR_RATE_LIMITER,
                      max_queueing_time_ms=400),
    ]
    RULES_OCC = [
        stpu.FlowRule(resource="api", count=3.0),
        stpu.FlowRule(resource="api", count=2.0, limit_app="app-a"),
        stpu.FlowRule(resource="bulk", count=1e6),
    ]

    def build(env):
        # the flag is read at ruleset build, so it must be set before
        # construction (and again before every reload)
        os.environ["SENTINEL_SORTFREE"] = env
        s = stpu.Sentinel(stpu.load_config(
            max_resources=64, max_origins=32, max_flow_rules=32,
            max_degrade_rules=16, max_authority_rules=16,
            host_fast_path=False), clock=ManualClock(start_ms=T0))
        s.load_flow_rules(RULES_RL)
        return s

    saved = os.environ.get("SENTINEL_SORTFREE")
    engines = []
    try:
        srt, sf = build("0"), build("1")
        engines = [srt, sf]
        assert not srt._sortfree and sf._sortfree

        def reload(rules):
            # the env flag is re-read at every reload: restore each
            # engine's setting or both would flip to the last value set
            for s, env in ((srt, "0"), (sf, "1")):
                os.environ["SENTINEL_SORTFREE"] = env
                s.load_flow_rules(rules)
            assert not srt._sortfree and sf._sortfree

        rng = np.random.default_rng(29)
        rows_by_name = {}
        for name in ("api", "paced", "bulk"):
            rows_by_name[name] = srt.resources.get_or_create(name)
            assert sf.resources.get_or_create(name) == rows_by_name[name]
        oid = srt.origins.pin("app-a")
        sf.origins.pin("app-a")
        pad_a = srt.spec.alt_rows
        alt = {r: srt._alt_row(r, 0, int(oid))
               for r in rows_by_name.values()}
        for r in rows_by_name.values():
            assert sf._alt_row(r, 0, int(oid)) == alt[r]

        def mixed(n, other, origin_frac, prio_frac, acquire_hi):
            row_api, row_o = rows_by_name["api"], rows_by_name[other]
            rows = np.where(rng.random(n) < 0.5, row_api,
                            row_o).astype(np.int32)
            has_o = rng.random(n) < origin_frac
            oids = np.where(has_o, oid, 0).astype(np.int32)
            orow = np.where(has_o,
                            np.where(rows == row_api, alt[row_api],
                                     alt[row_o]),
                            pad_a).astype(np.int32)
            acq = rng.integers(1, acquire_hi + 1, n).astype(np.int32)
            return (rows, oids, orow, np.zeros(n, np.int32),
                    np.full(n, pad_a, np.int32), acq,
                    np.ones(n, np.bool_),
                    np.asarray(rng.random(n) < prio_frac))

        split_calls = []
        orig_split = sf._decide_split_nowait
        sf._decide_split_nowait = lambda *a, **k: (
            split_calls.append(1), orig_split(*a, **k))[1]

        def vequal(a, b):
            return (np.array_equal(np.asarray(a.allow), np.asarray(b.allow))
                    and np.array_equal(np.asarray(a.reason),
                                       np.asarray(b.reason))
                    and np.array_equal(np.asarray(a.wait_ms),
                                       np.asarray(b.wait_ms)))

        parity = True

        def both(batch):
            nonlocal parity
            parity = parity and vequal(srt.decide_raw(*batch),
                                       sf.decide_raw(*batch))

        def tick(ms=250):
            for s in engines:
                s.clock.advance_ms(ms)

        # batches are built ONCE so both engines see byte-identical
        # traffic: non-uniform acquire → whole-batch pair-key general
        # route; 8192 rows + origins → split dispatch
        gen = mixed(1024, "paced", 0.25, 0.0, 2)
        spl = mixed(8192, "paced", 0.25, 0.01, 1)
        occ = mixed(8192, "bulk", 0.1, 0.01, 1)

        # phase 1 — RL ruleset: general-route + split parity with the
        # per-rule segment collapse live
        for _ in range(2):
            both(gen)
            tick()
            both(spl)
            tick()
        reload(RULES_OCC)
        # phase 2 — occupy: windows rotate under the 250ms ticks until
        # the api quota fills from a PRIOR bucket, then the denied prio
        # slice books into the next window (PriorityWait); reloading
        # BEFORE the next tick finds those bookings pending → carried
        for i in range(4):
            both(occ)
            if i < 3:
                tick()
        reload(RULES_OCC)
        tick()
        both(gen)          # general route with the carried ring live
        both(occ)
        return {
            "parity": bool(parity),
            "split_fired": len(split_calls),
            "route_general": sf.obs.counters.get(obs_keys.ROUTE_GENERAL),
            "route_sortfree": sf.obs.counters.get(obs_keys.ROUTE_SORTFREE),
            "route_sortfree_sorted_engine":
                srt.obs.counters.get(obs_keys.ROUTE_SORTFREE),
            "overflow_default_table":
                sf.obs.counters.get(obs_keys.SORTFREE_OVERFLOW),
            "occupy_granted_sorted":
                srt.obs.counters.get(obs_keys.OCCUPY_GRANTED),
            "occupy_granted_sortfree":
                sf.obs.counters.get(obs_keys.OCCUPY_GRANTED),
            "occupy_carried_sorted":
                srt.obs.counters.get(obs_keys.OCCUPY_CARRIED),
            "occupy_carried_sortfree":
                sf.obs.counters.get(obs_keys.OCCUPY_CARRIED),
        }
    finally:
        if saved is None:
            os.environ.pop("SENTINEL_SORTFREE", None)
        else:
            os.environ["SENTINEL_SORTFREE"] = saved
        for s in engines:
            s.close()


def measure_sortfree() -> dict:
    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import general_bench

    out = _sortfree_parity()
    R, B, STEPS, NRULES, REPEATS = 1 << 12, 1 << 12, 8, 128, 3
    srt = general_bench.measure(jax, "general", R, B, STEPS, NRULES,
                                REPEATS)["value"]
    sf = general_bench.measure(jax, "general", R, B, STEPS, NRULES,
                               REPEATS, sortfree=True)["value"]
    out["sorted_per_sec"] = srt
    out["sortfree_per_sec"] = sf
    out["sortfree_vs_sorted_ratio"] = sf / srt
    return out


# Gate (j) — the autotune gate (r11): sentinel_tpu/tune/ promoted the
# scattered env knobs into a typed registry plus a measurement-driven
# sweep (coordinate descent + successive halving over REAL serving
# episodes), so the gate pins the whole loop end to end:
#   sweep:    run_sweep over 2 knobs × tiny grids at short rungs on the
#             CPU backend. It must CONVERGE (every trial ran; no parity
#             failure) and write the TUNED.json artifact. Every trial's
#             verdict bit-parity spot-check vs the default config must
#             pass (tune.parity_fail == 0) — the tuner is a PERF tool
#             and must never pin a config that changes a verdict.
#   pin:      the artifact is then loaded back the way production
#             would: SENTINEL_TUNED_CONFIG set for a fresh serving
#             replay, with the provenance probe asserting the startup
#             path genuinely resolved it (fingerprint matched, knobs
#             applied) rather than silently falling back to defaults.
#   parity:   the pinned config's trace-knob slice must produce a
#             byte-identical verdict stream below the batcher
#             (_verdict_signature, the same comparable every trial
#             used).
#   ratio:    tuned/default settled-request throughput through the full
#             serving replay, best-of-N interleaved so machine drift
#             cancels, must stay ≥ TUNE_MIN_RATIO — the tuner's whole
#             contract is "never worse than defaults"; a winner that
#             loses to the baseline it beat during search means the
#             scoring plumbing (obs-sourced decisions_per_s / p99) or
#             the artifact application path regressed.
# CI_GATE_TUNE=0 skips the whole gate.
TUNE_ENV_FLAG = "CI_GATE_TUNE"
TUNE_MIN_RATIO = 0.95


def measure_tune() -> dict:
    import shutil
    import tempfile

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import serving_bench
    from sentinel_tpu.obs import counters as obs_keys
    from sentinel_tpu.tune import artifact as tune_artifact
    from sentinel_tpu.tune import knobs as tune_knobs
    from sentinel_tpu.tune import run_sweep
    from sentinel_tpu.tune.runner import _verdict_signature

    tmp = tempfile.mkdtemp(prefix="sentinel-tune-gate-")
    out_path = os.path.join(tmp, "TUNED.json")
    try:
        sweep = run_sweep(
            envs=("SENTINEL_PIPELINE_DEPTH", "SENTINEL_FRONTEND_BUDGET_MS"),
            grids={"SENTINEL_PIPELINE_DEPTH": (1, 2),
                   "SENTINEL_FRONTEND_BUDGET_MS": (1, 3)},
            workload="steady", seed=11, rate_rps=800.0, slo_p99_ms=150.0,
            rung_ms=(150, 300), out_path=out_path)
        res = sweep["result"]
        out = {
            "converged": bool(res.converged),
            "trials": sweep["trials"],
            "parity_checks": sweep["parity_checks"],
            "parity_fail": sweep["counters"].get(
                obs_keys.TUNE_PARITY_FAIL, 0),
            "best_config": dict(res.best_config),
            "artifact_written": sweep["artifact"] is not None,
        }
        if sweep["artifact"] is None:
            return out

        # pinned-config bit-parity below the batcher: same comparable
        # every trial used, over the winner's trace-knob slice
        trace_cfg = tune_knobs.trace_knobs(sweep["artifact"]["knobs"])
        out["pinned_bit_parity"] = (
            _verdict_signature(trace_cfg, seed=5, steps=3, events=64)
            == _verdict_signature({}, seed=5, steps=3, events=64))

        # pinned vs default through the full serving replay — the pinned
        # run loads the artifact via the REAL startup path (env pin), so
        # this also covers resolve_startup + the frontend kwarg fill
        prev = os.environ.get(tune_artifact.TUNED_CONFIG_ENV)

        def episode(pin: bool) -> float:
            if pin:
                os.environ[tune_artifact.TUNED_CONFIG_ENV] = out_path
            else:
                os.environ.pop(tune_artifact.TUNED_CONFIG_ENV, None)
            try:
                if pin and "artifact_loaded" not in out:
                    prov = tune_artifact.provenance()
                    out["artifact_loaded"] = bool(prov.get("tuned"))
                m = serving_bench.run_workload(
                    "steady", seed=11, duration_ms=300.0, rate_rps=800.0)
            finally:
                if prev is None:
                    os.environ.pop(tune_artifact.TUNED_CONFIG_ENV, None)
                else:
                    os.environ[tune_artifact.TUNED_CONFIG_ENV] = prev
            return float(m.get("decisions_per_s") or 0.0)

        best = {}
        for rep in range(3):
            order = [("tuned", True), ("default", False)]
            for key, pin in (order if rep % 2 == 0 else order[::-1]):
                best[key] = max(best.get(key, 0.0), episode(pin))
        out["tuned_decisions_per_s"] = best["tuned"]
        out["default_decisions_per_s"] = best["default"]
        out["tuned_vs_default_ratio"] = (
            best["tuned"] / best["default"] if best["default"] else 0.0)
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# Gate (k) — the hot-resource telemetry gate (r12). Two halves:
#   surface:  a planted-hot-key Zipf mix through the FULL serving path
#             (real Sentinel + start_transport + the dashboard server)
#             must surface the planted keys in the dashboard's
#             /obs/topk.json proxy of the agent's ``topk`` command AND
#             in the <app>-metric log the telemetry writer rides
#             (metrics/searcher.py read-back). Binary: the whole
#             device-tick → async-readback → transport → dashboard
#             chain either works or the gate fails.
#   overhead: the obs-overhead probe re-run with the telemetry TICKER
#             running on the instrumented engine (device tick + async
#             readback overlapped with the dispatch loop) — the
#             instrumented/uninstrumented step-time ratio must stay
#             inside the SAME fixed band (OBS_OVERHEAD_MAX, 1.02):
#             telemetry must not cost what obs/ saved. Machine speed
#             cancels in the ratio.
# CI_GATE_TELEMETRY=0 skips the whole gate.
TELEMETRY_ENV_FLAG = "CI_GATE_TELEMETRY"


def measure_telemetry() -> dict:
    import tempfile
    import time as _time
    import urllib.request

    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.dashboard import Dashboard
    from sentinel_tpu.dashboard.server import DashboardServer
    from sentinel_tpu.metrics.searcher import MetricSearcher
    from sentinel_tpu.obs import OBS_DISABLE_ENV
    from sentinel_tpu.transport import start_transport

    T0 = 1_785_000_000_000
    out: dict = {}

    # ---- surface half: planted hot keys end to end -------------------
    tmp = tempfile.mkdtemp(prefix="sentinel-telemetry-gate-")
    clk = ManualClock(start_ms=T0)
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=64, max_flow_rules=16, max_degrade_rules=16,
        max_authority_rules=16, host_fast_path=False,
        metric_log_dir=tmp), clock=clk)
    rt = start_transport(sph, host="127.0.0.1", port=0)
    dash = DashboardServer(Dashboard(password="", clock=clk,
                                     agent_timeout_s=30.0),
                           host="127.0.0.1", port=0)
    dport = dash.start(fetch=False)
    try:
        # drive LATE in the wall second so the traffic is still inside
        # the rolling window when the completed second lands
        clk.advance_ms(600)
        rng = np.random.default_rng(12)
        for z in rng.zipf(1.4, size=200):       # Zipf background
            try:
                sph.entry(f"bg-{min(int(z) - 1, 24)}").exit()
            except stpu.BlockException:
                pass
        for name, n in (("planted-hot-a", 120), ("planted-hot-b", 60)):
            for _ in range(n):
                sph.entry(name).exit()
        clk.advance_ms(500)                     # completes second T0/1000
        with urllib.request.urlopen(
                f"http://127.0.0.1:{dport}/obs/topk.json"
                f"?ip=127.0.0.1&port={rt.port}&tick=1",
                timeout=30) as r:
            body = json.loads(r.read().decode("utf-8"))
        data = body.get("data") or {}
        hot_names = [h["resource"] for h in data.get("hot", [])]
        out["topk_top3"] = hot_names[:3]
        out["planted_in_topk"] = (
            body.get("success", False)
            and "planted-hot-a" in hot_names
            and "planted-hot-b" in hot_names)
        out["planted_rank1"] = bool(hot_names
                                    and hot_names[0] == "planted-hot-a")
        out["timeline_len"] = len(data.get("timeline", []))
        out["drops"] = data.get("drops", -1)
        out["knobs"] = {"k": data.get("k"),
                        "n_shards": data.get("n_shards")}
        seen = {n.resource for n in MetricSearcher(
            tmp, sph.telemetry.base_name).find(T0 - 1000, T0 + 10_000)}
        out["metric_log_resources"] = len(seen)
        out["planted_in_metric_log"] = "planted-hot-a" in seen
    finally:
        dash.stop()
        rt.stop()
        sph.close()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)

    # ---- overhead half: obs-overhead probe, telemetry ticker ON ------
    def build(disable_obs: bool):
        prev = os.environ.get(OBS_DISABLE_ENV)
        if disable_obs:
            os.environ[OBS_DISABLE_ENV] = "1"
        else:
            os.environ.pop(OBS_DISABLE_ENV, None)
        try:
            s = stpu.Sentinel(stpu.load_config(
                max_resources=64, max_origins=32, max_flow_rules=32,
                max_degrade_rules=16, max_authority_rules=16,
                host_fast_path=False))
        finally:
            if prev is None:
                os.environ.pop(OBS_DISABLE_ENV, None)
            else:
                os.environ[OBS_DISABLE_ENV] = prev
        s.load_flow_rules([
            stpu.FlowRule(resource="api", count=1e9),
            stpu.FlowRule(resource="api", count=1e9, limit_app="app-a"),
        ])
        return s

    B, STEPS, REPEATS = 8192, 6, 8
    rng = np.random.default_rng(11)
    resources = ["api"] * B
    origins = ["app-a" if x else "" for x in (rng.random(B) < 0.1)]
    pair = [("on", build(False)), ("off", build(True))]
    assert pair[0][1].telemetry.enabled
    assert not pair[1][1].obs.enabled
    # 5 Hz — HARSHER than the production 1 Hz cadence, so the band holds
    # margin: the tick's brief engine-lock hold and the async readback
    # both overlap the timed dispatch loop several times per region
    pair[0][1].telemetry.start(interval_sec=0.2)
    best: dict = {}
    for _key, s in pair:                    # warm compiles + caches
        for _ in range(2):
            s.entry_batch_nowait(resources, origins=origins).result()
    for rep in range(REPEATS):
        for key, s in (pair if rep % 2 == 0 else pair[::-1]):
            t0 = _time.perf_counter()
            for _ in range(STEPS):
                s.entry_batch_nowait(resources, origins=origins).result()
            dt = (_time.perf_counter() - t0) / STEPS
            best[key] = min(best.get(key, dt), dt)
    out["telemetry_ticks"] = pair[0][1].telemetry.snapshot()["ticks"]
    for _key, s in pair:
        s.close()
    out["telemetry_on_s_per_step"] = best["on"]
    out["telemetry_off_s_per_step"] = best["off"]
    out["telemetry_overhead_ratio"] = best["on"] / best["off"]
    return out


# Gate (l) — the tiered-state gate (r15). Two halves:
#   serving:  zipf_hot over a 16M-rank universe (no materialized key
#             list — workloads._zipf_ranks) through the real
#             AdaptiveBatcher replay with the tiering ticker running
#             against a deliberately small SENTINEL_HOT_ROWS target.
#             Gated: hit rate ≥ TIER_HIT_RATE_MIN (hot_hit/(hot_hit+
#             cold_miss); FIRST-SIGHT keys tick neither — a brand-new
#             key never had state to miss, so the rate measures
#             hot-tier sizing, not keyspace size), nonzero promoted
#             AND demoted (the migration machinery actually ran), and
#             a recorded migration-latency histogram.
#   parity:   seeded churn traffic with live flow rules and a mid-run
#             rule reload through a 24-row hot tier vs a 4096-row
#             all-resident engine — verdict triples (allow, reason,
#             wait_ms) must be bit-identical, and the probe must
#             actually block somewhere (a parity of all-PASS proves
#             nothing about restored window state).
# The serving half pins SENTINEL_TPU_NATIVE=0: proactive (sketch-
# driven) demotion needs Registry.evict_name, which the native C++
# table does not expose this round — under the native registry only
# LRU-overflow demotion applies (documented in OPERATIONS.md).
# CI_GATE_TIER=0 skips the whole gate.
TIER_ENV_FLAG = "CI_GATE_TIER"
TIER_HIT_RATE_MIN = 0.95


def measure_tiering() -> dict:
    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock

    from benchmarks import serving_bench

    out: dict = {}

    # ---- serving half: 16M-key Zipf through the full front end -------
    overrides = {"SENTINEL_TPU_NATIVE": "0", "SENTINEL_HOT_ROWS": "512",
                 "SENTINEL_TIER_TICK_MS": "100"}
    prev = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        m = serving_bench.run_workload(
            "zipf_hot", seed=15, duration_ms=800.0, rate_rps=2500.0,
            wl_kwargs={"universe": 16_000_000})
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    t = m.get("tiering") or {}
    hits, misses = t.get("hot_hit", 0), t.get("cold_miss", 0)
    out["hit_rate"] = (hits / (hits + misses)
                       if (hits + misses) else None)
    out["hot_hit"] = hits
    out["cold_miss"] = misses
    out["promoted"] = t.get("promoted", 0)
    out["demoted"] = t.get("demoted", 0)
    out["sketch_overflow"] = t.get("sketch_overflow", 0)
    out["resident"] = t.get("resident", 0)
    out["cold"] = t.get("cold", 0)
    out["ticks"] = t.get("ticks", 0)
    out["migrate_p50_ms"] = t.get("migrate_p50_ms")
    out["migrate_p99_ms"] = t.get("migrate_p99_ms")
    out["serving_completed"] = m.get("completed", 0)
    out["serving_p99_ms"] = m.get("p99_ms")

    # ---- parity half: tiered vs all-resident, bit-identical ----------
    T0 = 1_785_000_000_000
    RULED = [f"zk{i}" for i in range(8)]
    KEYS = [f"zk{i}" for i in range(48)]

    def drive(capacity: int):
        clk = ManualClock(start_ms=T0)
        sph = stpu.Sentinel(stpu.load_config(
            max_resources=capacity, max_flow_rules=16,
            max_degrade_rules=16, max_authority_rules=16,
            host_fast_path=False), clock=clk)
        sph.load_flow_rules([stpu.FlowRule(resource=r, count=3.0)
                             for r in RULED])
        rng = np.random.default_rng(1501)
        verdicts = []
        for step in range(40):
            if step == 20:      # mid-run reload: pins move, state carries
                sph.load_flow_rules(
                    [stpu.FlowRule(resource=r, count=3.0)
                     for r in RULED[:4]]
                    + [stpu.FlowRule(resource=f"zk{i}", count=2.0)
                       for i in range(8, 12)])
            names = list(rng.choice(KEYS, size=12, replace=False))
            prio = rng.random(12) < 0.25
            v = sph.entry_batch(names, acquire=[1] * 12,
                                prioritized=list(prio))
            verdicts.append((np.asarray(v.allow).copy(),
                             np.asarray(v.reason).copy(),
                             np.asarray(v.wait_ms).copy()))
            clk.advance_ms(25)
        snap = sph.tiering.snapshot()
        sph.close()
        return verdicts, snap

    small_v, small_snap = drive(24)
    big_v, big_snap = drive(4096)
    out["parity"] = all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        and np.array_equal(a[2], b[2])
        for a, b in zip(small_v, big_v))
    out["parity_blocked"] = int(sum(
        int((~a).sum()) for a, _r, _w in small_v))
    out["parity_promoted"] = small_snap.get("promoted", 0)
    out["parity_demoted"] = small_snap.get("demoted", 0)
    out["parity_big_demoted"] = big_snap.get("demoted", 0)
    return out


# Gate (m) — the single-dispatch gate (r16). One probe, two readings:
#   mechanism: with the knob on, pipeline.dispatches must rise by
#             exactly ONE per batch (the sketch observe rides the
#             decide program, no standalone observe dispatch) and
#             split_route.single_dispatch must attribute every batch.
#   parity:   seeded churn traffic (tiered 24-row engine, mid-run rule
#             reload, ~25% prioritized) with SENTINEL_SINGLE_DISPATCH=1
#             vs =0 — verdict triples AND the final count-min table
#             must be bit-identical, the probe must block somewhere
#             (an all-PASS parity is vacuous), and the route counter
#             must prove the two runs really took different routes.
# CI_GATE_SINGLE_DISPATCH=0 skips the whole gate.
SINGLE_DISPATCH_ENV_FLAG = "CI_GATE_SINGLE_DISPATCH"


def measure_single_dispatch() -> dict:
    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import sentinel_tpu as stpu
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.obs import counters as obs_keys

    T0 = 1_785_000_000_000
    out: dict = {}

    RULED = [f"sd{i}" for i in range(8)]
    SKEYS = [f"sd{i}" for i in range(48)]

    def churn(sd_env: str):
        # staging stays ON: slot reuse is settlement-tied since round 17
        # (ROADMAP issue 5 fixed), so the bit-parity probe now also
        # exercises the ring under tiering churn
        overrides = {"SENTINEL_TPU_NATIVE": "0",
                     "SENTINEL_SINGLE_DISPATCH": sd_env}
        prev = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            cclk = ManualClock(start_ms=T0)
            s = stpu.Sentinel(stpu.load_config(
                max_resources=24, max_flow_rules=16, max_degrade_rules=16,
                max_authority_rules=16, host_fast_path=False), clock=cclk)
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        try:
            s.load_flow_rules([stpu.FlowRule(resource=r, count=3.0)
                               for r in RULED])
            rng = np.random.default_rng(1604)
            verdicts = []
            for step in range(32):
                if step == 16:  # mid-run reload: pins move, state carries
                    s.load_flow_rules(
                        [stpu.FlowRule(resource=r, count=3.0)
                         for r in RULED[:4]]
                        + [stpu.FlowRule(resource=f"sd{i}", count=2.0)
                           for i in range(8, 12)])
                names = list(rng.choice(SKEYS, size=12, replace=False))
                prio = list(rng.random(12) < 0.25)
                v = s.entry_batch(names, acquire=[1] * 12,
                                  prioritized=prio)
                verdicts.append((np.asarray(v.allow).copy(),
                                 np.asarray(v.reason).copy(),
                                 np.asarray(v.wait_ms).copy()))
                cclk.advance_ms(25)
            sketch = np.asarray(s.tiering._sketch).copy()
            route = s.obs.counters.get(obs_keys.ROUTE_SINGLE_DISPATCH)
            disp = s.obs.counters.get(obs_keys.PIPE_DISPATCH)
            return verdicts, sketch, route, disp
        finally:
            s.close()

    on_v, on_sk, on_route, on_disp = churn("1")
    off_v, off_sk, off_route, _off_disp = churn("0")
    # mechanism: every churn batch is one whole-batch decide, and with
    # the observe fused that is one dispatch (cold-path programs —
    # invalidation drains, promotions, reloads — are not counted)
    out["mech_batches"] = len(on_v)
    out["dispatches_per_batch"] = on_disp / len(on_v)
    out["route_single_dispatch"] = on_route
    out["parity"] = all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        and np.array_equal(a[2], b[2])
        for a, b in zip(on_v, off_v))
    out["sketch_parity"] = bool(np.array_equal(on_sk, off_sk))
    out["parity_blocked"] = int(sum(
        int((~a).sum()) for a, _r, _w in on_v))
    out["parity_route_on"] = on_route
    out["parity_route_off"] = off_route
    return out


# Gate (n) — the overload-controller gate (r17): the closed loop from
# device telemetry to the frontend admission valve must actually hold
# service through a composite overload episode. The probe replays the
# ``overload_episode`` workload (steady tenant + flash crowd + bursty
# slow consumer, benchmarks can't fake this: the arrival schedule is
# 2-3× the CPU backend's service rate at batch_max=8) four ways:
#   controlled: ControlLoop attached (100 ms cadence, 300 ms cooldown)
#             with a bounded queue — the steady TENANT's p95 (the
#             by_prefix breakdown, not the blended number the abusive
#             streams pollute; p95 because the extreme tail belongs to
#             the backend's own 1 Hz cadence programs, measured
#             identical in an unloaded run — see measure_control) must
#             sit inside the same STEADY_P99_BAND_MS gate (f) pins for
#             healthy serving, and goodput (completed within deadline)
#             must reach CONTROL_MIN_RATIO of the best STATIC config
#             below — self-driving protection may not cost more than
#             that vs the best hand-tuned fixed setting.
#   static grid: the same episode through three fixed configs (deep
#             queue, shallow queue, bigger batches) with NO controller
#             — the honest competitors a careful operator could have
#             picked in advance.
#   off-probe: the deep-queue static run doubles as the control: with
#             nobody shedding, queueing delay must push the steady
#             tenant's p95 OUTSIDE the band — if it doesn't, the
#             episode never overloaded the backend and the controlled
#             numbers above are vacuous.
# Mechanism probes ride along: the controller must APPLY at least one
# action (an idle controller holding the band proves nothing), the
# admission valve must actually drop requests (control.admission_dropped
# > 0), and EVERY applied action must land a pinned ``controller_action``
# flight record in the <app>-trace log — interventions are evidence,
# not just counters (the force=True trigger path bypasses the per-kind
# rate limiter precisely so no action goes unpinned).
# Round 20 adds the deterministic tail probe (measure_control_tail):
# a ManualClock slow-consumer episode whose per-tick mean sits under
# SENTINEL_CONTROL_DEGRADE_RT_MS while its interval p99 sits over it —
# the tail-aware degrade path must open the victim's breaker, the
# mean fallback (SENTINEL_RESOURCE_HIST_DISABLE=1) must NOT, and a
# histograms-on/off parity leg pins verdicts + dispatch count equal.
# CI_GATE_CONTROL=0 skips the whole gate.
CONTROL_ENV_FLAG = "CI_GATE_CONTROL"
CONTROL_MIN_RATIO = 0.5


def measure_control() -> dict:
    import shutil
    import tempfile

    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from benchmarks import serving_bench
    from sentinel_tpu.control import PolicyConfig
    from sentinel_tpu.obs import flight as flight_mod

    # The backend is made slow on purpose (batch_max=16 at an 8 ms
    # coalescing budget ≈ 1-2k req/s service) so a modest arrival rate
    # overloads IT rather than the replay interpreter — past ~4k req
    # total the asyncio loop itself becomes the bottleneck and every
    # config collapses identically, proving nothing about the
    # controller. burst_mult is tamed from the workload default so the
    # slow-consumer share doesn't push the NON-spike average over
    # service: outside the spike window ([0.3, 0.6] of the episode)
    # the offered rate sits comfortably under service; inside it the
    # 8× flash share pushes well over.
    EP = dict(seed=17, duration_ms=2000.0, rate_rps=1000.0,
              batch_max=16, budget_ms=8, deadline_ms=25,
              wl_kwargs={"burst_mult": 4.0})

    def goodput(m: dict) -> int:
        return m["completed"] - m["deadline_miss"]

    def steady_of(m: dict) -> dict:
        return (m.get("by_prefix") or {}).get("steady") or {}

    # Warmup: a long, LIGHT episode at both batch geometries so every
    # padded dispatch width AND the tick programs compile before
    # anything is timed — a first-occurrence XLA compile mid-replay
    # stalls serving for hundreds of ms and would be charged to
    # whichever config drew it. The 1.6 s duration is what lets the
    # telemetry/tiering ticks actually fire during warmup.
    for bm in (16, 32):
        serving_bench.run_workload(
            "overload_episode", seed=3, duration_ms=1600.0,
            rate_rps=400.0, batch_max=bm, budget_ms=8,
            wl_kwargs={"burst_mult": 4.0})

    # The scored statistic is the steady tenant's p95, not p99: the
    # residual extreme tail (~1% at ~0.3-0.5 s) is the backend's own
    # 1 Hz cadence programs executing on the CPU "device", which
    # serialize with serving dispatches — it shows up identically in
    # an UNLOADED steady run and no admission policy can shed around
    # it. p95 isolates the queueing delay the controller actually
    # owns; the off-probe violation below clears the band by >10× so
    # nothing rides on the choice.
    out: dict = {}

    # ---- static grid: the hand-tuned competitors, no controller ------
    grid = {
        "deep_queue": dict(queue_max=1024),
        "shallow_queue": dict(queue_max=64),
        "big_batch": dict(queue_max=1024, batch_max=32),
    }
    best_static, static_out = None, {}
    for gname, cfg in grid.items():
        m = serving_bench.run_workload(
            "overload_episode", **{**EP, **cfg})
        g = goodput(m)
        st = steady_of(m)
        static_out[gname] = {
            "goodput": g, "steady_p95_ms": st.get("p95_ms"),
            "steady_p99_ms": st.get("p99_ms"),
            "shed": m["shed"], "deadline_miss": m["deadline_miss"]}
        if best_static is None or g > best_static:
            best_static = g
        if gname == "deep_queue":   # doubles as the controller-off probe
            out["off_steady_p95_ms"] = st.get("p95_ms")
    out["static"] = static_out
    out["best_static_goodput"] = best_static

    # ---- controlled episode, flight recorder attached ----------------
    # Policy tuned to the probe's timescale: 100 ms cadence, 300 ms
    # cooldown; the p99 trip wire sits above the request deadline so
    # the QUEUE signal (0.75 × queue_max) does the fast work and the
    # shed floor is 0.3 — the valve may never throttle below 30%, which
    # bounds the goodput a misestimated p99 can throw away. The
    # overload retune HALVES the coalescing budget (shorter batches →
    # lower admitted-request latency) instead of the big-batch default,
    # and recovery is snappier so the post-spike tail contributes
    # goodput. Best-of-2: an open-loop real-time replay on a shared CI
    # box draws scheduler noise the controller cannot shed around, so
    # the run with the better steady p95 is scored (same min-of-N
    # discipline as every timing probe in this file).
    ctl, pinned = None, []
    for _attempt in range(2):
        tmp = tempfile.mkdtemp(prefix="sentinel-control-gate-")
        try:
            m = serving_bench.run_workload(
                "overload_episode", control=True, queue_max=48,
                control_kwargs={
                    "interval_ms": 100,
                    "config": PolicyConfig(
                        p99_hi_ms=35.0, p99_lo_ms=15.0, min_admit=0.3,
                        cooldown_ms=300, retune_budget_ms=4,
                        retune_cap_frac=1.0, shed_recover=0.25)},
                trace_dir=tmp, **EP)
            pins = flight_mod.load_pinned(tmp, "overload_episode")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        if (ctl is None or (steady_of(m).get("p95_ms") or 1e9)
                < (steady_of(ctl).get("p95_ms") or 1e9)):
            ctl, pinned = m, pins
    snap = ctl.get("control") or {}
    steady = steady_of(ctl)
    out["steady_p95_ms"] = steady.get("p95_ms")
    out["steady_p99_ms"] = steady.get("p99_ms")
    out["steady_completed"] = steady.get("completed", 0)
    out["goodput"] = goodput(ctl)
    out["actions_applied"] = snap.get("total_actions", 0)
    out["action_kinds"] = sorted(
        {a.get("kind") for a in snap.get("actions", ())})
    out["admission_dropped"] = ctl.get("control_dropped", 0)
    out["actions_pinned"] = sum(
        1 for rec in pinned if rec.get("kind") == "controller_action")
    out["min_admit_frac"] = min(
        [a["action"].get("frac", 1.0)
         for a in snap.get("actions", ())
         if a.get("kind") == "shed_rate"] or [1.0])
    out["goodput_ratio"] = (out["goodput"] / best_static
                            if best_static else None)
    return out


def measure_control_tail() -> dict:
    """Gate (n) round-20 extension: the slow-consumer episode the MEAN
    degrade signal provably cannot catch. Deterministic ManualClock
    probe (no replay, no wall clock): a victim resource serves a
    bimodal mix — 40 × 1 ms + 2 × 200 ms per controller tick, mean
    ≈ 10 ms, interval p99 ≈ 230 ms — against a 100 ms degrade bound,
    next to an all-fast steady resource. Four legs:

      tail:   histograms ON — the tail-aware controller must force-open
              the VICTIM's breaker (and only the victim's) while every
              per-tick mean stays under the bound;
      mean:   ``SENTINEL_RESOURCE_HIST_DISABLE=1`` — the same episode
              through the pre-r20 mean fallback must decide NOTHING
              (if it trips, the scenario doesn't discriminate and the
              tail leg proves nothing);
      parity: a controller-free mixed pass/block stream, histograms on
              vs off — verdict-for-verdict identical AND the SAME
              ``pipeline.dispatches`` count (the table may not cost a
              dispatch: ``dispatches_per_batch`` is pinned unchanged
              from round 16 by gate (m); this is the same invariant
              from the feature side).
    """
    sys.path.insert(0, str(HERE.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import sentinel_tpu as stpu
    from sentinel_tpu.control import ControlLoop
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.core.errors import BlockException
    from sentinel_tpu.obs import counters as obs_keys
    from sentinel_tpu.tune.knobs import env_overrides

    BOUND_MS = 100.0

    def _cfg():
        return stpu.load_config(
            max_resources=64, max_flow_rules=16, max_degrade_rules=16,
            max_authority_rules=16, host_fast_path=False)

    def _timed(s, name, rt_ms):
        e = s.entry(name)
        if rt_ms:
            s.clock.advance_ms(rt_ms)
        e.exit()

    def _episode() -> dict:
        """One slow-consumer episode under the current env; returns the
        per-leg evidence."""
        s = stpu.Sentinel(_cfg(),
                          clock=ManualClock(start_ms=1_785_000_000_000))
        try:
            s.load_degrade_rules([
                stpu.DegradeRule(resource=r,
                                 grade=stpu.GRADE_EXCEPTION_COUNT,
                                 count=10_000, time_window=5)
                for r in ("victim", "steady")])
            ctl = ControlLoop(s, interval_ms=50)
            mean_max, p99_min = 0.0, float("inf")
            for _ in range(ctl.policy.cfg.degrade_bad_ticks):
                for _i in range(40):
                    _timed(s, "victim", 1)
                    _timed(s, "steady", 1)
                for _i in range(2):
                    _timed(s, "victim", 200)
                s.telemetry.poll()
                hot = {h["resource"]: h
                       for h in s.telemetry.hot_entries()}
                v = hot.get("victim", {})
                mean_max = max(mean_max, float(v.get("rt_ms", 0.0)))
                p99_min = min(p99_min,
                              float(v.get("rt_p99_ms", float("inf"))))
                ctl.tick()
                ctl.drain()
            deg = ctl.policy.snapshot().get("degrade", {})
            victim_open = deg.get("victim") == "open"
            steady_open = "steady" in deg
            victim_blocked = False
            try:
                s.entry("victim")
            except stpu.DegradeException:
                victim_blocked = True
            steady_serves = True
            try:
                with s.entry("steady"):
                    pass
            except Exception:
                steady_serves = False
            return {
                "victim_open": victim_open and victim_blocked,
                "steady_open": steady_open or not steady_serves,
                "victim_mean_ms_max": mean_max,
                "victim_p99_ms_min": (None if p99_min == float("inf")
                                      else p99_min),
                "tail_signal_ticks":
                    s.obs.counters.get(obs_keys.CONTROL_TAIL_SIGNAL),
            }
        finally:
            s.close()

    def _verdicts() -> tuple:
        """Controller-free mixed stream against a tight flow rule;
        returns (verdict bits, dispatch count) for the parity leg."""
        s = stpu.Sentinel(_cfg(),
                          clock=ManualClock(start_ms=1_785_000_000_000))
        try:
            s.load_flow_rules([stpu.FlowRule(resource="lim", count=3)])
            bits = []
            for i in range(150):
                name = "lim" if i % 3 else "free"
                try:
                    e = s.entry(name)
                    s.clock.advance_ms(1 + (i % 7))
                    e.exit()
                    bits.append(True)
                except BlockException:
                    bits.append(False)
            return bits, int(s.obs.counters.get(obs_keys.PIPE_DISPATCH))
        finally:
            s.close()

    out: dict = {}
    with env_overrides({"SENTINEL_CONTROL_DEGRADE_RT_MS": BOUND_MS}):
        tail = _episode()
        with env_overrides({"SENTINEL_RESOURCE_HIST_DISABLE": True}):
            mean = _episode()
    out["tail_degrade_opened"] = tail["victim_open"]
    out["tail_steady_open"] = tail["steady_open"]
    out["victim_mean_ms_max"] = tail["victim_mean_ms_max"]
    out["victim_p99_ms_min"] = tail["victim_p99_ms_min"]
    out["tail_signal_ticks"] = tail["tail_signal_ticks"]
    out["mean_under_bound"] = tail["victim_mean_ms_max"] < BOUND_MS
    out["mean_fallback_opened"] = mean["victim_open"]
    v_on, d_on = _verdicts()
    with env_overrides({"SENTINEL_RESOURCE_HIST_DISABLE": True}):
        v_off, d_off = _verdicts()
    out["verdict_parity"] = bool(np.array_equal(v_on, v_off))
    out["dispatches_on"] = d_on
    out["dispatches_off"] = d_off
    return out


def main() -> int:
    best = max(measure_once() for _ in range(3))
    cal = calibrate()
    prep = measure_host_prep()
    prio = measure_prio_cliff()
    routing_err = check_prio_split_routing()
    obs = measure_obs_overhead()
    disp = measure_dispatch_pipeline()
    serving = measure_serving()
    trace = measure_trace_capture()
    meshed = (measure_meshed()
              if os.environ.get(MESHED_ENV_FLAG, "1") != "0" else None)
    sortfree = (measure_sortfree()
                if os.environ.get(SORTFREE_ENV_FLAG, "1") != "0" else None)
    tune = (measure_tune()
            if os.environ.get(TUNE_ENV_FLAG, "1") != "0" else None)
    telemetry = (measure_telemetry()
                 if os.environ.get(TELEMETRY_ENV_FLAG, "1") != "0"
                 else None)
    tiering = (measure_tiering()
               if os.environ.get(TIER_ENV_FLAG, "1") != "0" else None)
    single = (measure_single_dispatch()
              if os.environ.get(SINGLE_DISPATCH_ENV_FLAG, "1") != "0"
              else None)
    control = (measure_control()
               if os.environ.get(CONTROL_ENV_FLAG, "1") != "0" else None)
    if control is not None:
        # round 20: the deterministic slow-consumer tail probe rides the
        # same gate flag — binary mechanism legs, nothing re-baselined
        control["tail"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in measure_control_tail().items()}
    ratios = {k.replace("_s_per_step", "_ratio"): v / cal
              for k, v in prep.items()}
    if "--update" in sys.argv:
        BASELINE_FILE.write_text(json.dumps(
            {"cpu_decisions_per_sec_floor": best / 2,
             "measured_at_update": best,
             "machine": fingerprint(),
             "host_prep_ratios": ratios,
             # informational: the prio band and the obs-overhead band are
             # fixed (PRIO_RATIO_BAND / OBS_OVERHEAD_MAX), not
             # re-baselined per machine
             "prio_cliff": {k: round(v, 4) for k, v in prio.items()},
             "obs_overhead": {k: round(v, 4) for k, v in obs.items()},
             "dispatch_pipeline": {
                 k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in disp.items()},
             # informational: the serving SLO band is fixed
             # (STEADY_P99_BAND_MS / FLASH_MISS_COLLAPSE), not
             # re-baselined per machine
             "serving": {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in serving.items()},
             # informational: gate (g) is binary (mechanism), nothing
             # machine-relative to pin
             "trace_capture": trace,
             # informational: gate (h) is parity (binary) plus the fixed
             # WEAK_SCALING_FLAT_MAX band, not re-baselined per machine
             "meshed_serving": meshed,
             # informational: gate (i) is parity (binary) plus the fixed
             # SORTFREE_MIN_RATIO band, not re-baselined per machine
             "sortfree": ({k: (round(v, 4) if isinstance(v, float) else v)
                           for k, v in sortfree.items()}
                          if sortfree is not None else None),
             # informational: gate (j) is convergence + parity (binary)
             # plus the fixed TUNE_MIN_RATIO band, not re-baselined
             "tune": ({k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in tune.items()}
                      if tune is not None else None),
             # informational: gate (k) is binary (surface) plus the
             # fixed OBS_OVERHEAD_MAX band, not re-baselined per machine
             "telemetry": ({k: (round(v, 6) if isinstance(v, float)
                                else v)
                            for k, v in telemetry.items()}
                           if telemetry is not None else None),
             # informational: gate (l) is parity (binary) plus the fixed
             # TIER_HIT_RATE_MIN band, not re-baselined per machine
             "tiering": ({k: (round(v, 4) if isinstance(v, float)
                              else v)
                          for k, v in tiering.items()}
                         if tiering is not None else None),
             # informational: gate (m) is parity + mechanism (binary)
             # plus the fixed OBS_OVERHEAD_MAX band, not re-baselined
             # per machine
             "single_dispatch": ({k: (round(v, 6) if isinstance(v, float)
                                      else v)
                                  for k, v in single.items()}
                                 if single is not None else None),
             # informational: gate (n) is band + mechanism (binary) plus
             # the fixed STEADY_P99_BAND_MS / CONTROL_MIN_RATIO bands,
             # not re-baselined per machine
             "control": ({k: (round(v, 4) if isinstance(v, float)
                              else v)
                          for k, v in control.items()}
                         if control is not None else None),
             "calibration_s": cal}, indent=1))
        print(f"baseline updated: floor={best / 2:.0f} (measured {best:.0f}) "
              f"on {fingerprint()}; host-prep ratios "
              f"{ {k: round(v, 4) for k, v in ratios.items()} }")
        return 0
    baseline = json.loads(BASELINE_FILE.read_text())
    same_machine = baseline.get("machine") == fingerprint()
    floor = (baseline["cpu_decisions_per_sec_floor"] if same_machine
             else SANITY_FLOOR_DECISIONS_PER_SEC)
    out = {
        "measured": best, "floor": floor,
        "mode": "baseline-machine" if same_machine else "sanity-floor",
        "ratio_vs_floor": round(best / floor, 2),
        "calibration_s": round(cal, 4),
        "host_prep": {k: round(v, 4) for k, v in prep.items()},
        "host_prep_ratios": {k: round(v, 4) for k, v in ratios.items()},
        "prio_cliff": {k: round(v, 4) for k, v in prio.items()},
        "prio_split_routing": "ok" if routing_err is None else "DEMOTED",
        "obs_overhead": {k: round(v, 4) for k, v in obs.items()},
        "dispatch_pipeline": {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in disp.items()},
        "serving": {k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in serving.items()},
        "trace_capture": trace,
        "meshed_serving": meshed if meshed is not None else "skipped",
        "sortfree": ({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in sortfree.items()}
                     if sortfree is not None else "skipped"),
        "tune": ({k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in tune.items()}
                 if tune is not None else "skipped"),
        "telemetry": ({k: (round(v, 6) if isinstance(v, float) else v)
                       for k, v in telemetry.items()}
                      if telemetry is not None else "skipped"),
        "tiering": ({k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in tiering.items()}
                    if tiering is not None else "skipped"),
        "single_dispatch": ({k: (round(v, 6) if isinstance(v, float)
                                 else v)
                             for k, v in single.items()}
                            if single is not None else "skipped"),
        "control": ({k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in control.items()}
                    if control is not None else "skipped"),
    }
    print(json.dumps(out))
    rc = 0
    if meshed is not None:
        if "error" in meshed:
            print(f"MESHED-GATE REGRESSION: the --meshed probe subprocess "
                  f"failed to run: {meshed['error']}", file=sys.stderr)
            rc = 1
        else:
            for probe, ok in meshed["parity"].items():
                if not ok:
                    print(f"MESHED-PARITY REGRESSION ({probe}): verdicts "
                          f"through the meshed serving path diverged from "
                          f"the single-device engine — placement must be "
                          f"layout, not math; the row-sharded hot path is "
                          f"computing something different", file=sys.stderr)
                    rc = 1
            if meshed["split_fired"] == 0:
                print("MESHED-MECHANISM REGRESSION: the mixed probe batch "
                      "never took the split dispatch on the meshed engine "
                      "— the parity above did not cover the prio/occupy "
                      "routing it claims to", file=sys.stderr)
                rc = 1
            if meshed["route_meshed"] == 0 or meshed["pipe_meshed"] == 0:
                print(f"MESHED-MECHANISM REGRESSION: mesh attribution "
                      f"counters dead (split_route.meshed="
                      f"{meshed['route_meshed']}, pipeline.meshed_dispatch="
                      f"{meshed['pipe_meshed']}) — the scrape can no "
                      f"longer tell meshed traffic from single-device",
                      file=sys.stderr)
                rc = 1
            carried = (meshed["occupy_carried_ref"],
                       meshed["occupy_carried_meshed"])
            if carried[0] != carried[1] or carried[0] == 0:
                print(f"MESHED-OCCUPY REGRESSION: occupy bookings carried "
                      f"across the rule reload diverged or never happened "
                      f"(ref={carried[0]}, meshed={carried[1]}) — the "
                      f"booking carry path is broken or unexercised on "
                      f"the mesh", file=sys.stderr)
                rc = 1
            flat = meshed.get("flatness_norm") or {}
            worst = max((v for k, v in flat.items() if k != "1"),
                        default=None)
            if (worst is None or worst > WEAK_SCALING_FLAT_MAX
                    or MESHED_N_DEV not in meshed.get("curve_devices", [])):
                print(f"WEAK-SCALING REGRESSION: normalized per-partition "
                      f"cost {flat} (curve over "
                      f"{meshed.get('curve_devices')}) — worst ratio "
                      f"{worst} vs max {WEAK_SCALING_FLAT_MAX}; per-step "
                      f"cost is growing super-linearly with device count "
                      f"(all-to-all blowup, per-shard recompiles, or a "
                      f"host loop over shards)", file=sys.stderr)
                rc = 1
    if sortfree is not None:
        if not sortfree["parity"]:
            print("SORTFREE-PARITY REGRESSION: verdicts through the "
                  "hash-bucketed general path diverged from the sorted "
                  "reference through the real dispatch — the claim "
                  "cascade (or its lax.cond sorted fallback) is "
                  "computing something different; SENTINEL_SORTFREE=0 "
                  "is the operator escape hatch while this is debugged",
                  file=sys.stderr)
            rc = 1
        if (sortfree["route_sortfree"] == 0
                or sortfree["route_sortfree_sorted_engine"] != 0):
            print(f"SORTFREE-MECHANISM REGRESSION: split_route.sortfree "
                  f"attribution is wrong (sortfree engine="
                  f"{sortfree['route_sortfree']}, sorted engine="
                  f"{sortfree['route_sortfree_sorted_engine']}) — either "
                  f"the default flipped or the scrape can no longer tell "
                  f"the aggregation variants apart", file=sys.stderr)
            rc = 1
        if sortfree["route_general"] == 0 or sortfree["split_fired"] == 0:
            print(f"SORTFREE-MECHANISM REGRESSION: the probe batches no "
                  f"longer exercise the routes the parity claims to pin "
                  f"(general route={sortfree['route_general']}, "
                  f"split_fired={sortfree['split_fired']})",
                  file=sys.stderr)
            rc = 1
        if sortfree["overflow_default_table"] != 0:
            print(f"SORTFREE-TABLE REGRESSION: the DEFAULT-sized claim "
                  f"table overflowed "
                  f"{sortfree['overflow_default_table']} times on the "
                  f"probe traffic — table sizing regressed; the sorted "
                  f"fallback hides the perf loss while parity stays "
                  f"green", file=sys.stderr)
            rc = 1
        carried = (sortfree["occupy_carried_sorted"],
                   sortfree["occupy_carried_sortfree"])
        if carried[0] != carried[1] or carried[0] == 0:
            print(f"SORTFREE-OCCUPY REGRESSION: occupy bookings carried "
                  f"across the rule reload diverged or never happened "
                  f"(sorted={carried[0]}, sortfree={carried[1]}) — the "
                  f"booking-fold parity is broken or unexercised",
                  file=sys.stderr)
            rc = 1
        sr = sortfree["sortfree_vs_sorted_ratio"]
        if sr < SORTFREE_MIN_RATIO:
            print(f"SORTFREE-PERF REGRESSION: sortfree/sorted general "
                  f"throughput ratio {sr:.3f} < {SORTFREE_MIN_RATIO} on "
                  f"the CPU backend — the cascade's known below-parity "
                  f"CPU cost (~0.78× at gate shapes; the accelerator "
                  f"owns the win) has DEGENERATED: look for a "
                  f"per-element host loop, lost fusion, or an "
                  f"accidental device sync in ops/sortfree.py",
                  file=sys.stderr)
            rc = 1
    if tune is not None:
        if not tune["converged"] or not tune["artifact_written"]:
            print(f"TUNE-GATE REGRESSION: the tiny CPU sweep failed to "
                  f"converge or pin its TUNED.json (converged="
                  f"{tune['converged']}, artifact_written="
                  f"{tune['artifact_written']}, trials={tune['trials']}) "
                  f"— the search/runner/artifact loop is broken",
                  file=sys.stderr)
            rc = 1
        if tune["parity_fail"] != 0 or not tune.get("pinned_bit_parity",
                                                    True):
            print(f"TUNE-PARITY REGRESSION: verdict bit-parity broke "
                  f"(tune.parity_fail={tune['parity_fail']}, pinned "
                  f"config parity={tune.get('pinned_bit_parity')}) — a "
                  f"tuned config changed a VERDICT; the tuner must only "
                  f"ever move perf knobs", file=sys.stderr)
            rc = 1
        if tune["artifact_written"] and not tune.get("artifact_loaded"):
            print("TUNE-MECHANISM REGRESSION: SENTINEL_TUNED_CONFIG "
                  "pointed at the freshly pinned artifact but the "
                  "startup path did not resolve it (provenance says "
                  "tuned=false) — the load/fingerprint plumbing is dead "
                  "and every 'tuned' run silently uses defaults",
                  file=sys.stderr)
            rc = 1
        tr = tune.get("tuned_vs_default_ratio")
        if tune["artifact_written"] and (tr is None
                                         or tr < TUNE_MIN_RATIO):
            print(f"TUNE-PERF REGRESSION: tuned/default throughput ratio "
                  f"{tr if tr is None else round(tr, 3)} < "
                  f"{TUNE_MIN_RATIO} through the serving replay — the "
                  f"pinned winner loses to the defaults it beat during "
                  f"search; the obs-sourced scoring or the artifact "
                  f"application path regressed", file=sys.stderr)
            rc = 1
    if telemetry is not None:
        if not telemetry["planted_in_topk"]:
            print(f"TELEMETRY-GATE REGRESSION: planted hot keys missing "
                  f"from /obs/topk.json (top3={telemetry['topk_top3']}) "
                  f"— the device top-K → async readback → topk command "
                  f"→ dashboard chain is broken somewhere",
                  file=sys.stderr)
            rc = 1
        elif not telemetry["planted_rank1"]:
            print(f"TELEMETRY-GATE REGRESSION: the hottest planted key "
                  f"is not ranked first (top3={telemetry['topk_top3']}) "
                  f"— the sharded top-K merge ordering regressed",
                  file=sys.stderr)
            rc = 1
        if not telemetry["planted_in_metric_log"]:
            print(f"TELEMETRY-GATE REGRESSION: planted hot keys never "
                  f"reached the <app>-metric log "
                  f"({telemetry['metric_log_resources']} resources read "
                  f"back) — the per-second persistence ride on the "
                  f"metric writer/searcher is dead", file=sys.stderr)
            rc = 1
        if telemetry["timeline_len"] == 0:
            print("TELEMETRY-GATE REGRESSION: the per-second timeline "
                  "ring surfaced zero completed seconds through the "
                  "dashboard probe — the device ring append or its "
                  "readback is dead", file=sys.stderr)
            rc = 1
        tratio = telemetry["telemetry_overhead_ratio"]
        if tratio > OBS_OVERHEAD_MAX:
            print(f"TELEMETRY-OVERHEAD REGRESSION: instrumented/"
                  f"uninstrumented step-time ratio {tratio:.4f} > "
                  f"{OBS_OVERHEAD_MAX} with the telemetry ticker ON "
                  f"(5 Hz probe cadence) — the telemetry tick is "
                  f"leaking cost into the dispatch path (lock hold too "
                  f"long, a sync readback, or per-tick recompiles)",
                  file=sys.stderr)
            rc = 1
    if tiering is not None:
        if not tiering["parity"]:
            print("TIER-PARITY REGRESSION: verdicts through the small "
                  "hot tier diverged from the all-resident engine — the "
                  "demote→promote round trip (window slices, occupy "
                  "bookings, or the settle replay for missed reloads) "
                  "changed an answer; SENTINEL_TIERING_DISABLE=1 is the "
                  "operator escape hatch while this is debugged",
                  file=sys.stderr)
            rc = 1
        if tiering["parity_blocked"] == 0:
            print("TIER-PARITY REGRESSION: the parity probe never "
                  "produced a BLOCK verdict — an all-PASS parity proves "
                  "nothing about restored window state; the probe's rule "
                  "pressure degenerated", file=sys.stderr)
            rc = 1
        if tiering["parity_promoted"] == 0 or tiering["parity_demoted"] == 0:
            print(f"TIER-MECHANISM REGRESSION: the parity probe's small "
                  f"engine migrated nothing (promoted="
                  f"{tiering['parity_promoted']}, demoted="
                  f"{tiering['parity_demoted']}) — the parity above "
                  f"never exercised the cold tier", file=sys.stderr)
            rc = 1
        hr = tiering["hit_rate"]
        if hr is None or hr < TIER_HIT_RATE_MIN:
            print(f"TIER-HIT-RATE REGRESSION: hot-tier hit rate "
                  f"{hr if hr is None else round(hr, 4)} < "
                  f"{TIER_HIT_RATE_MIN} on the 16M-key Zipf serving run "
                  f"(hot_hit={tiering['hot_hit']}, cold_miss="
                  f"{tiering['cold_miss']}) — the sketch-driven demotion "
                  f"is evicting keys the workload still needs (hash "
                  f"quality, decay cadence, or victim selection "
                  f"regressed)", file=sys.stderr)
            rc = 1
        if tiering["promoted"] == 0 or tiering["demoted"] == 0:
            print(f"TIER-MECHANISM REGRESSION: the 16M-key serving run "
                  f"migrated nothing (promoted={tiering['promoted']}, "
                  f"demoted={tiering['demoted']}, ticks="
                  f"{tiering['ticks']}) — the ticker, the hot-rows "
                  f"target, or the evict_name path is dead and the hit "
                  f"rate above is vacuous", file=sys.stderr)
            rc = 1
        if tiering["promoted"] and tiering["migrate_p50_ms"] is None:
            print("TIER-MECHANISM REGRESSION: promotions happened but "
                  "the migration-latency histogram recorded nothing — "
                  "the cold-miss slow path lost its instrumentation",
                  file=sys.stderr)
            rc = 1
    if single is not None:
        if single["dispatches_per_batch"] != 1.0:
            print(f"SINGLE-DISPATCH REGRESSION: a decide batch cost "
                  f"{single['dispatches_per_batch']} device dispatches "
                  f"(batches={single['mech_batches']}) — the sketch "
                  f"observe fell back to a standalone program",
                  file=sys.stderr)
            rc = 1
        if single["route_single_dispatch"] < single["mech_batches"]:
            print(f"SINGLE-DISPATCH MECHANISM REGRESSION: only "
                  f"{single['route_single_dispatch']} of "
                  f"{single['mech_batches']} batches earned "
                  f"split_route.single_dispatch — the scrape can no "
                  f"longer tell the sketch-fused decide from the legacy "
                  f"composition", file=sys.stderr)
            rc = 1
        if not single["parity"] or not single["sketch_parity"]:
            print(f"SINGLE-DISPATCH PARITY REGRESSION: verdict parity="
                  f"{single['parity']}, sketch parity="
                  f"{single['sketch_parity']} between "
                  f"SENTINEL_SINGLE_DISPATCH=1 and =0 — the fused "
                  f"observe changed an answer; "
                  f"SENTINEL_SINGLE_DISPATCH=0 is the operator escape "
                  f"hatch while this is debugged", file=sys.stderr)
            rc = 1
        if single["parity_blocked"] == 0:
            print("SINGLE-DISPATCH PARITY REGRESSION: the parity probe "
                  "never produced a BLOCK verdict — an all-PASS parity "
                  "proves nothing; the probe's rule pressure degenerated",
                  file=sys.stderr)
            rc = 1
        if (single["parity_route_on"] == 0
                or single["parity_route_off"] != 0):
            print(f"SINGLE-DISPATCH MECHANISM REGRESSION: route "
                  f"attribution (split_route.single_dispatch on="
                  f"{single['parity_route_on']}, off="
                  f"{single['parity_route_off']}) says the two parity "
                  f"runs did not actually take different routes",
                  file=sys.stderr)
            rc = 1
    if control is not None:
        c_lo, c_hi = STEADY_P99_BAND_MS
        sp95 = control["steady_p95_ms"]
        if sp95 is None or not c_lo <= sp95 <= c_hi:
            print(f"CONTROL-GATE REGRESSION: steady-tenant p95 "
                  f"{sp95 if sp95 is None else round(sp95, 2)} ms "
                  f"outside band [{c_lo}, {c_hi}] WITH the controller "
                  f"attached — the closed loop is not protecting the "
                  f"well-behaved tenant through the overload episode "
                  f"(SENTINEL_CONTROL_DISABLE=1 is the operator escape "
                  f"hatch while this is debugged)", file=sys.stderr)
            rc = 1
        off95 = control["off_steady_p95_ms"]
        if off95 is not None and off95 <= c_hi:
            print(f"CONTROL-GATE REGRESSION: the controller-OFF "
                  f"deep-queue run kept the steady tenant's p95 at "
                  f"{round(off95, 2)} ms (≤ {c_hi}) — the episode never "
                  f"overloaded the backend, so the controlled band "
                  f"above is vacuous; the probe's rate/batch pressure "
                  f"degenerated", file=sys.stderr)
            rc = 1
        gr = control["goodput_ratio"]
        if gr is None or gr < CONTROL_MIN_RATIO:
            print(f"CONTROL-GOODPUT REGRESSION: controlled goodput "
                  f"{control['goodput']} is "
                  f"{gr if gr is None else round(gr, 3)} of the best "
                  f"static config "
                  f"({control['best_static_goodput']}) < "
                  f"{CONTROL_MIN_RATIO} — self-driving protection is "
                  f"throwing away more work than the best hand-tuned "
                  f"fixed setting would", file=sys.stderr)
            rc = 1
        if (control["actions_applied"] == 0
                or control["admission_dropped"] == 0):
            print(f"CONTROL-MECHANISM REGRESSION: the controller applied "
                  f"{control['actions_applied']} actions and the "
                  f"admission valve dropped "
                  f"{control['admission_dropped']} requests over the "
                  f"overload episode — an idle controller holding the "
                  f"band proves nothing; the observe/decide/actuate "
                  f"chain is dead", file=sys.stderr)
            rc = 1
        if control["actions_pinned"] < control["actions_applied"]:
            print(f"CONTROL-EVIDENCE REGRESSION: "
                  f"{control['actions_applied']} applied actions pinned "
                  f"only {control['actions_pinned']} controller_action "
                  f"flight records — interventions must leave evidence; "
                  f"the force-pin path (flight.trigger force=True) or "
                  f"the <app>-trace persistence is dropping them",
                  file=sys.stderr)
            rc = 1
        tail = control.get("tail") or {}
        if not tail.get("mean_under_bound", False):
            print(f"CONTROL-TAIL REGRESSION: the slow-consumer probe's "
                  f"per-tick victim MEAN peaked at "
                  f"{tail.get('victim_mean_ms_max')} ms (>= the 100 ms "
                  f"bound) — the bimodal mix degenerated and the tail "
                  f"leg below discriminates nothing", file=sys.stderr)
            rc = 1
        if not tail.get("tail_degrade_opened", False) \
                or tail.get("tail_steady_open", True):
            print(f"CONTROL-TAIL REGRESSION: tail-aware degrade did not "
                  f"isolate the slow consumer (victim opened: "
                  f"{tail.get('tail_degrade_opened')}, steady touched: "
                  f"{tail.get('tail_steady_open')}; victim interval p99 "
                  f"{tail.get('victim_p99_ms_min')} ms, mean "
                  f"{tail.get('victim_mean_ms_max')} ms, tail-signal "
                  f"ticks {tail.get('tail_signal_ticks')}) — the device "
                  f"histogram → ResourceTailTracker → degrade tracker → "
                  f"force_breaker chain is broken", file=sys.stderr)
            rc = 1
        if tail.get("mean_fallback_opened", True):
            print("CONTROL-TAIL REGRESSION: the mean-RT fallback "
                  "(SENTINEL_RESOURCE_HIST_DISABLE=1) ALSO opened the "
                  "victim on the bimodal episode — the scenario no "
                  "longer separates tail from mean, so the tail leg "
                  "proves nothing; re-tune the probe's mix",
                  file=sys.stderr)
            rc = 1
        if not tail.get("verdict_parity", False) \
                or tail.get("dispatches_on") != tail.get("dispatches_off"):
            print(f"CONTROL-TAIL PARITY REGRESSION: histograms on vs "
                  f"off diverged (verdict parity "
                  f"{tail.get('verdict_parity')}, dispatches "
                  f"{tail.get('dispatches_on')} vs "
                  f"{tail.get('dispatches_off')}) — the table must be "
                  f"verdict-free and dispatch-free", file=sys.stderr)
            rc = 1
    if trace["pinned_records"] == 0 or "deadline_miss" not in trace["kinds"]:
        print(f"TRACE-CAPTURE REGRESSION: {trace['induced_misses']} induced "
              f"deadline misses pinned {trace['pinned_records']} chains "
              f"(kinds {trace['kinds']}) — the flight recorder's "
              f"deadline_miss trigger or its <app>-trace persistence is "
              f"dead", file=sys.stderr)
        rc = 1
    elif not trace["chain_spans_tiers_ok"]:
        print("TRACE-CAPTURE REGRESSION: no pinned chain spans both the "
              f"request tier ({TRACE_REQUIRED_REQUEST_SPAN}) and a batch "
              f"tier span {TRACE_REQUIRED_BATCH_SPANS} with a causal "
              "link — the trace-id threading between the front end and "
              "the dispatch path is severed", file=sys.stderr)
        rc = 1
    elif not trace["chrome_trace_ok"]:
        print("TRACE-CAPTURE REGRESSION: the pinned chain did not survive "
              "the Chrome-trace export + json.loads round trip",
              file=sys.stderr)
        rc = 1
    p99 = serving["steady_p99_ms"]
    slo_lo, slo_hi = STEADY_P99_BAND_MS
    if p99 is None or not slo_lo <= p99 <= slo_hi:
        print(f"SERVING-SLO REGRESSION: steady p99 request→verdict "
              f"{p99 if p99 is None else round(p99, 2)} ms outside band "
              f"[{slo_lo}, {slo_hi}] — "
              f"{'the measurement degenerated (requests never crossed the device)' if p99 is not None and p99 < slo_lo else 'the ingest tier is stalling (blocking call on the loop thread, lost wakeup, or deadline logic broken)'}",
              file=sys.stderr)
        rc = 1
    if (serving["steady_shed"] != 0
            or serving["steady_completed"] != serving["steady_offered"]):
        print(f"SERVING-SLO REGRESSION: steady workload shed "
              f"{serving['steady_shed']} / completed "
              f"{serving['steady_completed']} of "
              f"{serving['steady_offered']} offered — a sustainable rate "
              f"must neither shed nor lose requests", file=sys.stderr)
        rc = 1
    if (serving["flash_completed"] + serving["flash_shed"]
            != serving["flash_offered"]):
        print(f"SERVING-FLASH REGRESSION: "
              f"{serving['flash_completed']} completed + "
              f"{serving['flash_shed']} shed != "
              f"{serving['flash_offered']} offered — requests were LOST "
              f"(leaked futures) under the spike", file=sys.stderr)
        rc = 1
    if serving["flash_miss_frac"] >= FLASH_MISS_COLLAPSE:
        print(f"SERVING-FLASH REGRESSION: deadline-miss fraction "
              f"{serving['flash_miss_frac']:.3f} ≥ {FLASH_MISS_COLLAPSE} "
              f"under the flash crowd — the front end collapsed instead "
              f"of shedding/queueing through the spike", file=sys.stderr)
        rc = 1
    if serving["flash_flush_full"] == 0:
        print("SERVING-FLASH REGRESSION: the spike never cut a "
              "batch_max-full batch (flush_reason.full == 0) — the flash "
              "probe is not stressing the coalescing path",
              file=sys.stderr)
        rc = 1
    po = disp["pipeline_overhead_ratio"]
    if po > PIPELINE_OVERHEAD_MAX:
        print(f"PIPELINE-OVERHEAD REGRESSION: pipelined/sync step-time "
              f"ratio {po:.4f} > {PIPELINE_OVERHEAD_MAX} — the "
              f"DispatchPipeline layer costs material time over the bare "
              f"nowait loop (lock contention, per-submit device syncs, or "
              f"settle-order bookkeeping growth)", file=sys.stderr)
        rc = 1
    if not disp["pipelined_depth_reached"]:
        print("PIPELINE-MECHANISM REGRESSION: pipeline.depth counter shows "
              "batches never overlapped in flight (depth window collapsed "
              "to 1) — the overlay timing above proved nothing",
              file=sys.stderr)
        rc = 1
    oratio = obs["obs_overhead_ratio"]
    if oratio > OBS_OVERHEAD_MAX:
        print(f"OBS-OVERHEAD REGRESSION: instrumented/uninstrumented "
              f"step-time ratio {oratio:.4f} > {OBS_OVERHEAD_MAX} — the "
              f"observability layer (obs/) is no longer ~free on the hot "
              f"path; look for per-event work, device syncs, or lock "
              f"contention added under `if obs.enabled`", file=sys.stderr)
        rc = 1
    lo, hi = PRIO_RATIO_BAND
    pr = prio["prio_vs_general_ratio"]
    if not lo <= pr <= hi:
        print(f"PRIO-CLIFF REGRESSION: prio_mixed/general ratio {pr:.3f} "
              f"outside band [{lo}, {hi}] — "
              f"{'the occupy-aware split has collapsed to sorted-general speed (demotion cliff)' if pr < lo else 'the general denominator degenerated; the gate is not measuring what it claims'}",
              file=sys.stderr)
        rc = 1
    if routing_err is not None:
        print(f"PRIO-ROUTING REGRESSION: {routing_err}", file=sys.stderr)
        rc = 1
    if best < floor:
        print(f"PERF REGRESSION: {best:.0f} decisions/s < floor {floor:.0f} "
              f"({'>2x below the rate at baseline time' if same_machine else 'below the absolute sanity floor — the fused step has degenerated'})",
              file=sys.stderr)
        rc = 1
    committed = baseline.get("host_prep_ratios")
    if committed:
        for k, limit in committed.items():
            got = ratios.get(k)
            if got is not None and got > limit * HOST_PREP_MARGIN:
                print(f"HOST-PREP REGRESSION ({k}): measured ratio "
                      f"{got:.4f} > committed {limit:.4f} × "
                      f"{HOST_PREP_MARGIN} — serving-path host prep grew "
                      f"relative to this machine's CPU calibration "
                      f"(machine-independent signal)", file=sys.stderr)
                rc = 1
    return rc


if __name__ == "__main__":
    if "--meshed" in sys.argv:
        raise SystemExit(meshed_main())
    raise SystemExit(main())
