#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip, at the product size.

The quickest proof that the system still starts on a TPU. Through the
entry points a user calls — ``Sentinel(load_config(...))`` →
``sph.frontend()`` (``AdaptiveBatcher``) → ``DispatchPipeline`` → the
jitted tick → verdict fan-out, behind ``frontend.server.start_server``
answering real HTTP, with the ``CadenceScheduler`` running — it drives one
deployment of 1M resident rows (``PRODUCT`` below) through every program
family the engine serves with, then the other front door
(``ClusterTokenServer`` over a ``ClusterEngine``, real TCP frames), and on
a host with four chips the same deployment row-sharded over a mesh.

Every verdict is checked against counts this script works out on the host
from the same rules, using integer thresholds only (a burst of 50 against
``count=20`` is 20 allowed and 30 ``FlowException``). Time is virtual
(``ManualClock``): the window a burst lands in is part of the input, like
the seed, so a cold compile in the middle of a phase cannot move a count.

``main()`` refuses any platform other than ``tpu``. The phases are plain
functions; ``tests/test_chip_smoke.py`` runs them on the CPU at ``TINY``.
Stdout is two JSON lines: the summary (every phase, ending ``"claim":
null``), then — last, and nothing but — ``{"ok": ..., "device": {...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

T0_MS = 1_785_000_000_000       # virtual epoch, on a window edge
HELLO_COUNT = 20                # BASELINE.json config 1
HELLO_BURST = 50
HOT_COUNT = 5                   # the hot-param rule's per-value budget
#: Served phases stop being STARTED past this many seconds (cold compiles
#: at 1M rows decide how far the list gets); the token door and the mesh
#: phase always run. The driver's limit is 1200 s.
SERVED_BUDGET_S = 780.0


@dataclasses.dataclass(frozen=True)
class Geometry:
    rows: int            # Sentinel max_resources: rows resident on device
    flow_rules: int      # FlowRule(count=rule_count) on r0..r{n-1}
    degrade_rules: int   # exception-ratio DegradeRule on r0..r{n-1}
    rule_count: int      # threshold of every r* flow rule
    tick: int            # the serving batch (frontend batch_max)
    burst_rows: int      # r* rows offered 4x their count in a serving tick
    origin_rows: int     # r* rows carrying origins in the mixed tick
    cluster_flows: int   # ClusterSpec.flows_per_shard


#: BASELINE.json's north-star size: 1M rows resident, bench.py's rule
#: population, run_all.py's config-4/5 serving batch.
PRODUCT = Geometry(rows=1 << 20, flow_rules=4096, degrade_rules=1024,
                   rule_count=50, tick=1 << 16, burst_rows=256,
                   origin_rows=10, cluster_flows=4096)
#: The CPU rehearsal (tests/test_chip_smoke.py).
TINY = Geometry(rows=256, flow_rules=16, degrade_rules=8, rule_count=8,
                tick=64, burst_rows=1, origin_rows=1, cluster_flows=64)


class SmokeFailure(Exception):
    """A verdict did not match the host-side count, or teardown was not
    clean."""


def check(cond: bool, what: str, **detail) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {detail}" if detail else what)


def wait_for(pred, what: str, poll, timeout_s: float = 120.0) -> None:
    """Readbacks land on whichever thread drains first — the cadence
    daemon or ``poll`` here; wait until the host view shows it."""
    deadline = time.monotonic() + timeout_s
    while not pred():
        check(time.monotonic() < deadline, f"timed out: {what}")
        poll()
        time.sleep(0.02)


# ----------------------------------------------------------------------
# Host-side reference: DefaultController QPS admission, integers only
# ----------------------------------------------------------------------

def flow_reference(events: Sequence[Tuple[str, int]],
                   limits: Dict[str, int]) -> List[bool]:
    """Greedy in arrival order, all inside one statistics window: an event
    acquiring ``a`` on a ruled resource passes iff the passes already
    counted plus ``a`` stay within the rule's count; unruled resources
    always pass."""
    used: Dict[str, int] = {}
    out = []
    for name, acquire in events:
        limit = limits.get(name)
        ok = limit is None or used.get(name, 0) + acquire <= limit
        if ok and limit is not None:
            used[name] = used.get(name, 0) + acquire
        out.append(ok)
    return out


def check_verdicts(what: str, events, limits, verdicts, reason: str) -> dict:
    """Compare (allow, reason_name) pairs against :func:`flow_reference`."""
    want = flow_reference(events, limits)
    got = [bool(v[0]) for v in verdicts]
    bad = [i for i, (w, g) in enumerate(zip(want, got)) if w != g]
    check(not bad, f"{what}: allow mismatch", first=bad[:5], n=len(bad))
    wrong = [v[1] for v in verdicts if not v[0] and v[1] != reason]
    check(not wrong, f"{what}: block reason", got=sorted(set(wrong)),
          want=reason)
    return {"events": len(events), "allowed": sum(got),
            "blocked": len(got) - sum(got)}


# ----------------------------------------------------------------------
# Measurement helpers
# ----------------------------------------------------------------------

class CompileMeter:
    """Counts what JAX compiles or loads, from ``jax.monitoring``: every
    program's trace, lowering and backend compile-or-cache-load seconds,
    and which of them the persistent cache served."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event in self._EVENTS:
            self.seconds += secs
            if event == self._EVENTS[2]:
                self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, float, int]:
        return self.programs, self.seconds, self.cache_hits


def tree_bytes(tree) -> int:
    """Bytes the leaves need from their shapes alone (no layout padding)."""
    import jax
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree) if hasattr(leaf, "dtype"))


def device_memory(device) -> Optional[dict]:
    stats = device.memory_stats()
    if not stats:
        return None
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def cache_entries(path: Optional[str]) -> Optional[int]:
    if not path:
        return None
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def looks_like_oom(exc: BaseException) -> bool:
    text = str(exc)
    return any(s in text for s in ("RESOURCE_EXHAUSTED", "Out of memory",
                                   "out of memory", "exceeds the available",
                                   "Ran out of memory"))


# ----------------------------------------------------------------------
# The deployment
# ----------------------------------------------------------------------

class Deployment:
    """One engine with its rules loaded and every row occupied, plus the
    serving objects a phase drives it through."""

    def __init__(self, geom: Geometry, seed: int, *, minute: bool,
                 mesh=None) -> None:
        import numpy as np
        import sentinel_tpu as stpu
        from sentinel_tpu.rules.degrade import GRADE_EXCEPTION_RATIO

        self.geom = geom
        self.rng = np.random.default_rng(seed)
        self.clock = stpu.ManualClock(start_ms=T0_MS)
        cfg = stpu.load_config(max_resources=geom.rows,
                               max_flow_rules=2 * geom.flow_rules,
                               max_degrade_rules=2 * geom.degrade_rules,
                               minute_enabled=minute)
        self.sph = stpu.Sentinel(cfg, clock=self.clock, mesh=mesh)
        sph = self.sph
        self.limits = {f"r{i}": geom.rule_count
                       for i in range(geom.flow_rules)}
        self.limits["HelloWorld"] = HELLO_COUNT
        sph.load_flow_rules([stpu.FlowRule(resource=name, count=float(c))
                             for name, c in self.limits.items()])
        sph.load_degrade_rules([
            stpu.DegradeRule(resource=f"r{i}", grade=GRADE_EXCEPTION_RATIO,
                             count=0.5, time_window=10)
            for i in range(geom.degrade_rules)])
        sph.load_param_flow_rules([stpu.ParamFlowRule(
            resource="hot", param_idx=0, count=HOT_COUNT)])
        # occupy every remaining row: a table of empty rows is not a
        # deployment. The registry is then full, so the next NEW name
        # evicts the least recently used one (tier_migration relies on it)
        free = geom.rows - len(sph.resources)
        self.fill = [f"k{i}" for i in range(free)]
        sph.intern_resources(self.fill)
        check(len(sph.resources) == geom.rows, "registry not full",
              have=len(sph.resources), rows=geom.rows)
        self.fe = sph.frontend(batch_max=geom.tick)
        self.pipe = stpu.DispatchPipeline(sph)
        # started as serving_bench/start_transport start it
        self.sched = stpu.CadenceScheduler(sph, telemetry_interval_sec=1.0)
        self.sched.start()

    # ruled rows, one disjoint group per phase so no phase reads another's
    # window: r0/r1 exits (they also carry degrade rules), then the burst
    # rows, the mixed tick's origin rows, the origin phase's two rows
    def ruled(self, start: int, n: int) -> List[str]:
        check(start + n <= self.geom.flow_rules, "ruled rows exhausted")
        return [f"r{i}" for i in range(start, start + n)]

    @property
    def burst_names(self) -> List[str]:
        return self.ruled(2, self.geom.burst_rows)

    @property
    def mixed_origin_names(self) -> List[str]:
        return self.ruled(2 + self.geom.burst_rows, self.geom.origin_rows)

    @property
    def origin_phase_names(self) -> List[str]:
        return self.ruled(
            2 + self.geom.burst_rows + self.geom.origin_rows, 2)

    @property
    def rank_key_fits(self) -> bool:
        """The runtime's own ``key_fits`` test (``decide_raw_nowait``):
        the fast general, fast-occupy and per-event split routes rank by
        a composite (flow-table slot, alt row) key that must fit int32;
        where it does not, the same batches take the pair-key general
        route."""
        sph = self.sph
        return (sph._ruleset.flow_table.active.shape[0]
                * (sph.spec.alt_rows + 1)) < 2 ** 31

    def bytes_from_shapes(self) -> dict:
        sph = self.sph
        return {"state": tree_bytes(sph._state),
                "ruleset": tree_bytes(sph._ruleset),
                "sketch": tree_bytes(sph.tiering.sketch_for_fuse_locked())}

    def close(self) -> None:
        """Stop everything this deployment started; anything left running
        or any service that failed to stop is a :class:`SmokeFailure`."""
        from sentinel_tpu.obs import counters as ck
        self.sph.close()
        check(not self.sph.close_errors, "engine teardown raised",
              errors=[repr(e) for e in self.sph.close_errors])
        leaked = self.sph.obs.counters.get(ck.PIPE_LEAKED)
        check(leaked == 0, "verdict handles left to the GC finalizer",
              leaked=leaked)
        check(self.sched.errors == 0, "cadence daemon raised",
              errors=self.sched.errors)
        check(self.sched._thread is None, "cadence daemon still running")


def build_deployment(geom: Geometry, seed: int, reduced: List[str],
                     mesh=None) -> Deployment:
    """The default ``minute_enabled=True`` first; where the device cannot
    hold it, without the minute ring — named under ``reduced`` with the
    allocator's own words."""
    try:
        return Deployment(geom, seed, minute=True, mesh=mesh)
    except Exception as exc:
        if not looks_like_oom(exc):
            raise
        reduced.append("minute ring off (minute_enabled=False): "
                       + " ".join(str(exc).split())[:400])
    gc.collect()
    return Deployment(geom, seed, minute=False, mesh=mesh)


# ----------------------------------------------------------------------
# Served phases (the order is the order families are dropped from the end)
# ----------------------------------------------------------------------

def serving_tick_events(d: Deployment) -> List[Tuple[str, int]]:
    """One serving batch of origin-free scalar traffic: every burst row
    offered 4x its count, the rest spread over unruled rows, shuffled."""
    g = d.geom
    names = [n for n in d.burst_names for _ in range(4 * g.rule_count)]
    rest = g.tick - len(names)
    check(rest > 0, "tick smaller than the burst")
    picks = d.rng.integers(0, len(d.fill), rest)
    names += [d.fill[int(i)] for i in picks]
    d.rng.shuffle(names)
    return [(n, 1) for n in names]


async def submit_all(d: Deployment, events, *, origin: str = "",
                     prioritized: Sequence[bool] = ()) -> list:
    """Gathered in-process submits → [(allow, reason_name, wait_ms)]."""
    prio = list(prioritized) or [False] * len(events)
    vs = await asyncio.gather(*(
        d.fe.submit(name, count=acq, origin=origin, prioritized=p)
        for (name, acq), p in zip(events, prio)))
    return [(v.allow, v.reason_name, v.wait_ms) for v in vs]


async def post_batch(http, entries: List[dict]) -> list:
    async with http["session"].post(http["base"] + "/v1/entry_batch",
                                    json={"entries": entries}) as resp:
        check(resp.status == 200, "entry_batch status", status=resp.status)
        body = await resp.json()
    return [(v["allow"], v["reason_name"], v["wait_ms"])
            for v in body["verdicts"]]


async def phase_scalar(d: Deployment, http) -> dict:
    """Origin-free scalar traffic: config 1's burst over HTTP, one
    request alone, then a full serving tick in process."""
    from sentinel_tpu.obs import counters as ck
    events = [("HelloWorld", 1)] * HELLO_BURST
    out = {"hello": check_verdicts(
        "HelloWorld burst", events, d.limits,
        await post_batch(http, [{"resource": n} for n, _ in events]),
        "FlowException")}
    check(out["hello"]["allowed"] == HELLO_COUNT, "HelloWorld allowed",
          got=out["hello"]["allowed"])
    async with http["session"].post(http["base"] + "/v1/entry",
                                    json={"resource": d.fill[0]}) as resp:
        body = await resp.json()
        check(resp.status == 200 and body["allow"] is True,
              "single /v1/entry", status=resp.status, body=body)
    async with http["session"].get(http["base"] + "/healthz") as resp:
        check((await resp.json())["ok"] is True, "/healthz")
    async with http["session"].get(http["base"] + "/stats") as resp:
        stats = await resp.json()
        check(stats["counters"].get(ck.FE_ENQUEUE, 0) == HELLO_BURST + 1,
              "/stats enqueue count", counters=stats["counters"])

    d.clock.advance_ms(2000)
    events = serving_tick_events(d)
    out["tick"] = check_verdicts("serving tick", events, d.limits,
                                 await submit_all(d, events),
                                 "FlowException")
    check(out["tick"]["allowed"] == d.geom.tick
          - 3 * d.geom.rule_count * d.geom.burst_rows,
          "serving tick allowed", got=out["tick"]["allowed"])
    c = d.sph.obs.counters
    check(c.get(ck.ROUTE_SCALAR) >= 3 and c.get(ck.ROUTE_SORTFREE) >= 3,
          "scalar sort-free route", counters=c.snapshot())
    return out


async def phase_origin(d: Deployment, http) -> dict:
    """Origin-bearing traffic over HTTP: uniform acquire takes the fast
    general route (hashed claim cascade) where the rank key fits, mixed
    acquire always the pair-key general route."""
    from sentinel_tpu.obs import counters as ck
    d.clock.advance_ms(2000)
    c = d.sph.obs.counters
    fast0, gen0 = c.get(ck.ROUTE_FAST), c.get(ck.ROUTE_GENERAL)
    ra, rb = d.origin_phase_names
    n = d.geom.rule_count + 10
    events = [(ra, 1)] * n
    out = {"uniform_acquire": check_verdicts(
        "origin, uniform acquire", events, d.limits,
        await post_batch(http, [{"resource": r, "origin": "app-a"}
                                for r, _ in events]), "FlowException")}
    events = [(rb, 1 + i % 2) for i in range(n)]
    out["mixed_acquire"] = check_verdicts(
        "origin, mixed acquire", events, d.limits,
        await post_batch(http, [{"resource": r, "count": a,
                                 "origin": "app-a"} for r, a in events]),
        "FlowException")
    n_fast = 1 if d.rank_key_fits else 0
    check(c.get(ck.ROUTE_FAST) == fast0 + n_fast
          and c.get(ck.ROUTE_GENERAL) == gen0 + 2 - n_fast,
          "origin routes", counters=c.snapshot())
    check(c.get(ck.SORTFREE_OVERFLOW) == 0, "sort-free claim overflow",
          overflow=c.get(ck.SORTFREE_OVERFLOW))
    return out


async def phase_mixed_prio(d: Deployment, http) -> dict:
    """A serving tick fills the burst rows; 600 ms later, in the next
    bucket of the same rolling window, a mixed tick brings ~1%
    prioritized events on those full rows (each books the next window:
    allowed, with the wait to its edge), ~1% origin-bearing events on
    fresh ruled rows, and scalar traffic for the rest — the per-event
    split with an occupy-capable general half where the rank key fits,
    the whole-batch general route with occupy where it does not."""
    from sentinel_tpu.obs import counters as ck
    g = d.geom
    d.clock.advance_ms(2000)
    fill = serving_tick_events(d)
    check_verdicts("fill tick", fill, d.limits, await submit_all(d, fill),
                   "FlowException")
    d.clock.advance_ms(600)
    win_ms = d.sph.spec.second.win_ms
    want_wait = win_ms - d.clock.now_ms() % win_ms

    prio = [(n, 1) for n in d.burst_names for _ in range(2)]
    normal = [(n, 1) for n in d.burst_names]
    with_origin = [(n, 1) for n in d.mixed_origin_names
                   for _ in range(g.rule_count + 10)]
    n_rest = g.tick - len(prio) - len(normal) - len(with_origin)
    check(n_rest > 0, "mixed tick overfull")
    rest = [(d.fill[int(i)], 1)
            for i in d.rng.integers(0, len(d.fill), n_rest)]
    c = d.sph.obs.counters
    split0, gen0 = c.get(ck.ROUTE_SPLIT), c.get(ck.ROUTE_GENERAL)
    v_prio, v_norm, v_org, v_rest = await asyncio.gather(
        submit_all(d, prio, prioritized=[True] * len(prio)),
        submit_all(d, normal),
        submit_all(d, with_origin, origin="app-b"),
        submit_all(d, rest))
    bad = [v for v in v_prio if not (v[0] and v[2] == want_wait)]
    check(not bad, "prioritized events must book the next window",
          want_wait=want_wait, first=bad[:3], n=len(bad))
    check(not any(v[0] for v in v_norm),
          "plain events on full rows must block",
          allowed=sum(v[0] for v in v_norm))
    out = {"prioritized": {"events": len(prio), "booked": len(v_prio),
                           "wait_ms": want_wait},
           "full_rows_blocked": len(v_norm),
           "origin": check_verdicts("mixed tick origins", with_origin,
                                    d.limits, v_org, "FlowException"),
           "scalar_rest": check_verdicts("mixed tick rest", rest, d.limits,
                                         v_rest, "FlowException")}
    if not d.rank_key_fits:
        check(c.get(ck.ROUTE_GENERAL) == gen0 + 1, "general route",
              counters=c.snapshot())
    elif n_rest + len(normal) >= 4096:      # the runtime's split floor
        check(c.get(ck.ROUTE_SPLIT) == split0 + 1, "split route",
              counters=c.snapshot())
    check(c.get(ck.OCCUPY_GRANTED) >= len(prio), "occupy.granted",
          got=c.get(ck.OCCUPY_GRANTED))
    return out


def phase_exits(d: Deployment) -> dict:
    """Entries through ``DispatchPipeline.submit_raw``, then their exits
    through ``exit_batch``: the exit program records RT + errors
    (``rt_hist``) and a telemetry tick reads them back. Every exit of r0
    fails, so its exception-ratio breaker opens and the next tick's
    entries on it are ``DegradeException``; r1 fails 2 of 20 and stays
    closed."""
    import numpy as np
    from sentinel_tpu.core.errors import exception_name_for
    sph, clock = d.sph, d.clock
    clock.advance_ms(2000)
    sph.telemetry.poll()        # a tick now: the daemon's next is 1500 ms off
    ticks0 = sph.telemetry.snapshot()["ticks"]
    clock.advance_ms(1000)      # a fresh window, not yet due for the daemon
    rx, ry = sph.intern_resources(["r0", "r1"])
    free = sph.intern_resources(d.fill[:16])
    pad = sph.spec.alt_rows

    def cols(rows):
        n = len(rows)
        zeros = np.zeros(n, np.int32)
        alt = np.full(n, pad, np.int32)
        return (np.asarray(rows, np.int32), zeros, alt, zeros, alt,
                np.ones(n, np.int32), np.ones(n, np.bool_),
                np.zeros(n, np.bool_))

    # entries stay inside the rows' flow budget over both ticks, so the
    # only thing that can block one is the breaker
    n_x = min(24, d.geom.rule_count // 2)
    n_y = n_x - 1
    x_exits, y_exits = 24, 20
    rows = [rx] * n_x + [ry] * n_y + list(free)
    xrows = np.asarray([rx] * x_exits + [ry] * y_exits, np.int32)
    rt = d.rng.integers(1, 200, len(xrows)).astype(np.int32)
    err = np.zeros(len(xrows), np.bool_)
    err[:x_exits + 2] = True            # all of r0's, two of r1's
    v1 = d.pipe.submit_raw(*cols(rows)).result()
    check(bool(np.all(v1.allow)), "tick 1 must admit everything",
          blocked=int(np.sum(~v1.allow)))
    alt = np.full(len(xrows), pad, np.int32)
    sph.exit_batch(rows=xrows, origin_rows=alt, chain_rows=alt,
                   acquire=np.ones(len(xrows), np.int32), rt_ms=rt,
                   error=err, is_in=np.ones(len(xrows), np.bool_))
    t_tick = clock.now_ms()
    check(sph.telemetry.tick(), "telemetry tick refused")
    clock.advance_ms(10)
    rows2 = [rx] * n_x + [ry] * n_x
    v2 = d.pipe.submit_raw(*cols(rows2)).result()
    check(not v2.allow[:n_x].any() and bool(v2.allow[n_x:].all()),
          "breaker: r0 open, r1 closed", allow=v2.allow.tolist())
    names = {exception_name_for(int(r)) for r in v2.reason[:n_x]}
    check(names == {"DegradeException"}, "breaker block reason", got=names)

    wait_for(lambda: sph.telemetry.snapshot()["ts_ms"] == t_tick,
             "telemetry tick to land", d.sched.poll)
    check(sph.telemetry.snapshot()["ticks"] == ticks0 + 1,
          "one telemetry tick since the poll",
          ticks=sph.telemetry.snapshot()["ticks"], before=ticks0)
    loads, top_rows = sph.telemetry.last_topk
    check(int(top_rows[0]) == int(rx) and int(loads[0]) == n_x,
          "top-K: hottest row", row=int(top_rows[0]), load=int(loads[0]),
          want=(int(rx), n_x))
    hot = sph.telemetry.hot_entries(1)[0]
    check(hot["resource"] == "r0" and hot["exception"] == x_exits,
          "top-K: hot entry", hot={k: hot[k] for k in
                                   ("resource", "pass", "exception")})
    if "rt_hist" in hot:
        check(sum(hot["rt_hist"]) == x_exits, "rt_hist row count",
              got=sum(hot["rt_hist"]), want=x_exits)
    return {"tick1": {"entries": len(rows), "exits": len(xrows),
                      "errors": int(err.sum())},
            "tick2": {"degrade_blocked": n_x, "allowed": n_x},
            "topk_row": int(top_rows[0]), "topk_load": int(loads[0]),
            "rt_hist_count": sum(hot.get("rt_hist", ())) or None}


def phase_hot_param(d: Deployment) -> dict:
    """A hot-parameter batch through the pipeline: four values, ten
    events each, against a per-value budget of five."""
    import numpy as np
    from sentinel_tpu.core.errors import exception_name_for
    d.clock.advance_ms(2000)
    values = [v for v in range(4) for _ in range(10)]
    args = np.asarray(values, np.int64)[:, None]
    v = d.pipe.submit(["hot"] * len(values), args_list=args).result()
    for val in range(4):
        got = int(np.sum(v.allow[np.asarray(values) == val]))
        check(got == HOT_COUNT, "hot-param value budget", value=val, got=got)
    names = {exception_name_for(int(r)) for r in v.reason[~v.allow]}
    check(names == {"ParamFlowException"}, "hot-param block reason",
          got=names)
    return {"events": len(values), "allowed": int(np.sum(v.allow)),
            "blocked": int(np.sum(~v.allow))}


def phase_per_call(d: Deployment) -> dict:
    """Per-call ``entry()``/``exit()`` on the host fast path: HelloWorld
    serves from pre-charged leases until a chunk is denied, an unruled
    name admits free; the buffered statistics land on the device when a
    reader asks."""
    import sentinel_tpu as stpu
    sph = d.sph
    d.clock.advance_ms(2000)
    passed = blocked = 0
    for _ in range(HELLO_COUNT + 5):
        try:
            with sph.entry("HelloWorld"):
                passed += 1
        except stpu.FlowException:
            blocked += 1
    check((passed, blocked) == (HELLO_COUNT, 5), "per-call HelloWorld",
          passed=passed, blocked=blocked)
    free_name = d.fill[1]
    for _ in range(5):
        with sph.entry(free_name):
            pass
    check(sph._fast.fast_admits > 0 and sph._fast.lease_renewals > 0,
          "host fast path unused", admits=sph._fast.fast_admits,
          renewals=sph._fast.lease_renewals)
    totals = sph.node_totals("HelloWorld")      # forces the flush
    check(totals["pass"] == HELLO_COUNT and totals["block"] == 5,
          "HelloWorld device totals", totals=totals)
    free_totals = sph.node_totals(free_name)
    check(free_totals["pass"] == 5 and free_totals["success"] == 5,
          "free-row device totals", totals=free_totals)
    return {"hello_passed": passed, "hello_blocked": blocked,
            "lease_renewals": sph._fast.lease_renewals,
            "fast_admits": sph._fast.fast_admits}


async def phase_tier_migration(d: Deployment, http) -> dict:
    """Every row is occupied, so a new name evicts the least recently
    used one: its state is extracted to the host cold tier, and asking
    for it again restores it."""
    sph = d.sph
    d.clock.advance_ms(2000)
    before = sph.tiering.snapshot()
    v = await submit_all(d, [("smoke-fresh-key", 1)])
    check(v[0][0], "fresh key must pass")
    wait_for(lambda: len(sph.tiering.cold) > 0,
             "demoted key to land in the cold tier", sph.tiering.poll)
    victims = sph.tiering.cold.names()
    check(len(victims) == 1, "one demoted key expected", cold=victims)
    v = await submit_all(d, [(victims[0], 1)])
    check(v[0][0], "promoted key must pass")
    after = sph.tiering.snapshot()
    check(after["demoted"] - before["demoted"] == 2
          and after["promoted"] - before["promoted"] == 1
          and victims[0] not in sph.tiering.cold,
          "tier migration counts", before=before, after=after)
    return {"victim": victims[0], "demoted": 2, "promoted": 1,
            "migrate_p50_ms": after["migrate_p50_ms"]}


#: (name, function, needs the event loop) — ISSUE 21's order; a cold run
#: that runs out of budget drops from the end.
SERVED_PHASES = (
    ("scalar", phase_scalar, True),
    ("origin", phase_origin, True),
    ("mixed_prio", phase_mixed_prio, True),
    ("exits", phase_exits, False),
    ("hot_param", phase_hot_param, False),
    ("per_call", phase_per_call, False),
    ("tier_migration", phase_tier_migration, True),
)


class PhaseLog:
    """Times each phase and records what it compiled and what the device
    holds after it."""

    def __init__(self, device, meter: CompileMeter, t_start: float) -> None:
        self.device = device
        self.meter = meter
        self.phases: Dict[str, dict] = {}
        self.t_start = t_start

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_start

    def begin(self, sph=None):
        from sentinel_tpu.obs import counters as ck
        return (time.perf_counter(), self.meter.snapshot(),
                sph.obs.counters.get(ck.CACHE_MISS) if sph else 0)

    def end(self, name: str, token, detail: dict, sph=None) -> None:
        from sentinel_tpu.obs import counters as ck
        t0, (p0, s0, h0), miss0 = token
        p1, s1, h1 = self.meter.snapshot()
        rec = {"seconds": round(time.perf_counter() - t0, 3),
               # JAX's own trace + lower + compile-or-load seconds (they
               # overlap where two threads compile at once)
               "compile_seconds": round(s1 - s0, 3),
               "xla_programs": p1 - p0, "loaded_from_cache": h1 - h0,
               "check": "ok"}
        if sph is not None:
            rec["programs_compiled"] = (
                sph.obs.counters.get(ck.CACHE_MISS) - miss0)
        mem = device_memory(self.device)
        if mem:
            rec["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
            rec["bytes_in_use"] = mem.get("bytes_in_use")
        rec.update(detail)
        self.phases[name] = rec
        print(f"chip_smoke: {name} ok in {rec['seconds']} s "
              f"({rec['xla_programs']} programs, "
              f"{rec['compile_seconds']} s compiling)", file=sys.stderr)


async def run_served(d: Deployment, log: PhaseLog, reduced: List[str],
                     phases=SERVED_PHASES, label: str = "",
                     budget_s: float = SERVED_BUDGET_S) -> None:
    """Start the HTTP front door and run the served phases through it."""
    import aiohttp
    from sentinel_tpu.frontend.server import start_server
    runner = await start_server(d.fe, host="127.0.0.1", port=0)
    port = runner.addresses[0][1]
    try:
        async with aiohttp.ClientSession() as session:
            http = {"session": session, "base": f"http://127.0.0.1:{port}"}
            for i, (name, fn, on_loop) in enumerate(phases):
                if log.elapsed() > budget_s:
                    reduced.extend(
                        f"dropped {label}{n}: past the {budget_s:.0f} s "
                        f"served budget" for n, _f, _l in phases[i:])
                    break
                token = log.begin(d.sph)
                detail = (await fn(d, http) if on_loop
                          else await asyncio.to_thread(fn, d))
                log.end(label + name, token, detail, d.sph)
    finally:
        await runner.cleanup()


# ----------------------------------------------------------------------
# The other front door: cluster token server over real TCP
# ----------------------------------------------------------------------

def phase_token_door(geom: Geometry, n_shards: int = 1) -> dict:
    """``ClusterTokenServer`` on an ephemeral port over a
    ``ClusterEngine``; a ``ClusterTokenClient`` sends a few hundred FLOW
    requests against GLOBAL and AVG_LOCAL rules over real frames."""
    import sentinel_tpu as stpu
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.cluster.server import ClusterTokenServer
    from sentinel_tpu.parallel.cluster import (
        STATUS_BLOCKED, STATUS_OK, THRESHOLD_AVG_LOCAL, THRESHOLD_GLOBAL,
        ClusterEngine, ClusterFlowRule, ClusterSpec)

    n_global, n_avg, per_flow = 32, 16, 6
    engine = ClusterEngine(ClusterSpec(
        n_shards=n_shards, flows_per_shard=geom.cluster_flows, namespaces=4))
    server = ClusterTokenServer(engine, host="127.0.0.1", port=0,
                                clock=stpu.ManualClock(start_ms=T0_MS))
    server.load_flow_rules("smoke-ns", [
        ClusterFlowRule(flow_id=i, count=4.0,
                        threshold_type=THRESHOLD_GLOBAL)
        for i in range(n_global)] + [
        ClusterFlowRule(flow_id=1000 + i, count=2.0,
                        threshold_type=THRESHOLD_AVG_LOCAL)
        for i in range(n_avg)])
    server.start()
    # the first frames wait for the step's compile, not for the 20 ms
    # the reference client allows a warm server
    client = ClusterTokenClient("127.0.0.1", server.port,
                                namespace="smoke-ns",
                                request_timeout_ms=600_000,
                                auto_reconnect=False)
    try:
        client.start()
        check(server.connection_count("smoke-ns") == 1,
              "one client connected (AVG_LOCAL thresholds scale with it)")
        flows = (list(range(n_global))
                 + [1000 + i for i in range(n_avg)]) * per_flow
        res = client.request_tokens_batch([(f, 1, False)
                                           for f in flows[:-2]])
        res += [client.request_token(f, 1) for f in flows[-2:]]
        ok = sum(r.status == STATUS_OK for r in res)
        blocked = sum(r.status == STATUS_BLOCKED for r in res)
        want_ok = 4 * n_global + 2 * n_avg
        check((ok, blocked) == (want_ok, len(flows) - want_ok),
              "token grants", ok=ok, blocked=blocked,
              statuses=sorted({r.status for r in res}))
    finally:
        client.stop()
        server.stop()
    check(server._thread is None, "token server thread still running")
    return {"requests": len(flows), "ok": ok, "blocked": blocked,
            "shards": n_shards}


# ----------------------------------------------------------------------
# Four chips: the same deployment, row-sharded
# ----------------------------------------------------------------------

def phase_mesh(geom: Geometry, seed: int, log: PhaseLog, reduced: List[str],
               single: Dict[str, dict], n: int = 4) -> dict:
    """``Sentinel(cfg, mesh=local_mesh(n))`` through the same front end,
    same seed: the served phases' counts must equal the single-device
    run's, the window tensor must span ``n`` devices with ``rows/n`` per
    shard, and no device may hold the bulk of the state."""
    import jax
    from sentinel_tpu.parallel.local_shard import local_mesh
    d = build_deployment(geom, seed, reduced, mesh=local_mesh(n))
    try:
        counters = d.sph._state.second.counters
        shards = counters.addressable_shards
        check(len(counters.sharding.device_set) == n
              and all(s.data.shape[0] == geom.rows // n for s in shards),
              "window tensor layout", devices=len(counters.sharding.device_set),
              shard_rows=[s.data.shape[0] for s in shards])
        asyncio.run(run_served(d, log, reduced, SERVED_PHASES[:3],
                               label="mesh_", budget_s=float("inf")))
        for name, _fn, _l in SERVED_PHASES[:3]:
            got = {k: v for k, v in log.phases["mesh_" + name].items()
                   if isinstance(v, dict)}
            want = {k: v for k, v in single[name].items()
                    if isinstance(v, dict)}
            check(got == want, f"mesh {name} differs from single device",
                  mesh=got, single=want)
        in_use = [device_memory(dev) for dev in jax.devices()[:n]]
        if all(in_use):
            used = [m["bytes_in_use"] for m in in_use]
            check(max(used) <= 2 * min(used),
                  "per-device bytes in use must be within 2x", used=used)
        else:
            used = None
    finally:
        d.close()
    del d, counters, shards
    gc.collect()        # engine ↔ service cycles hold the state until now
    return {"devices": n, "rows_per_shard": geom.rows // n,
            "bytes_in_use_per_device": used,
            "phases": ["mesh_" + name for name, _f, _l in SERVED_PHASES[:3]]}


# ----------------------------------------------------------------------
# Entry point
# ----------------------------------------------------------------------

def run(geom: Geometry, seed: int, t_start: Optional[float] = None) -> dict:
    """Everything, on whatever platform JAX has (``main`` is the one that
    insists on a TPU) → the summary dict. ``t_start`` is when the process
    began its imports, so set-up covers them."""
    if t_start is None:
        t_start = time.perf_counter()
    import jax
    import jaxlib
    import sentinel_tpu  # noqa: F401 — import time is part of set-up
    from sentinel_tpu import native
    from sentinel_tpu.core.compile_cache import (
        active_cache_dir, enable_persistent_cache)

    devices = jax.devices()
    dev = devices[0]
    enable_persistent_cache()
    cache_dir = active_cache_dir()
    cache_before = cache_entries(cache_dir)
    meter = CompileMeter()
    log = PhaseLog(dev, meter, t_start)
    reduced: List[str] = []

    registry = "native" if native.native_available() else "python"
    check(registry == "native"
          or os.environ.get("SENTINEL_TPU_NATIVE") == "0",
          "the native registry failed to build and SENTINEL_TPU_NATIVE "
          "is unset: the Python table is a different host path")

    token = log.begin()
    d = build_deployment(geom, seed, reduced)
    if not d.rank_key_fits:
        reduced.append(
            "fast general, fast-occupy and split routes unreachable at "
            f"this geometry: rank key "
            f"{d.sph._ruleset.flow_table.active.shape[0]} flow slots x "
            f"{d.sph.spec.alt_rows + 1} alt rows >= 2^31; origin-bearing, "
            "mixed and prioritized batches ran on the pair-key general "
            "route")
    shapes = d.bytes_from_shapes()
    log.end("set_up", token, {
        "import_and_backend_seconds": round(token[0] - t_start, 3),
        "rows": geom.rows, "resident_names": len(d.sph.resources),
        "minute_ring": d.sph.spec.minute is not None,
        "bytes_from_shapes": shapes,
        "bytes_from_shapes_total": sum(shapes.values())}, d.sph)
    try:
        asyncio.run(run_served(d, log, reduced))
    finally:
        d.close()
    single = dict(log.phases)
    del d
    gc.collect()

    token = log.begin()
    log.end("token_door", token, phase_token_door(geom))

    if len(devices) >= 4:
        token = log.begin()
        log.end("mesh", token, phase_mesh(geom, seed, log, reduced, single))
        token = log.begin()
        log.end("mesh_token_door", token, phase_token_door(geom, 4))
        mesh = {"devices": 4}
    else:
        mesh = {"skipped": f"{len(devices)} device visible"}

    stray = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and not t.daemon]
    check(not stray, "non-daemon threads left running", threads=stray)
    cache_after = cache_entries(cache_dir)
    total_s = log.elapsed()
    first = log.phases["set_up"]
    set_up_s = (first["import_and_backend_seconds"] + first["seconds"]
                + meter.seconds - first["compile_seconds"])
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None       # a CPU-only installation
    return {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(devices),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "seed": seed, "rows": geom.rows, "tick": geom.tick,
        "registry": registry,
        "cache_dir": cache_dir,
        "cache_entries": {"before": cache_before, "after": cache_after},
        "reduced": reduced,
        "total_seconds": round(total_s, 3),
        # imports, backend init, engine construction, interning every
        # name, and every second JAX spent tracing, lowering and
        # compiling or loading a program in any phase — apart from the
        # ticks, which are the rest
        "set_up_seconds": round(set_up_s, 3),
        "tick_seconds": round(total_s - set_up_s, 3),
        "xla_programs": meter.programs,
        "loaded_from_cache": meter.cache_hits,
        "phases": log.phases,
        "mesh": mesh,
        "claim": None,
    }


def result_line(summary: dict) -> str:
    """The last line of stdout, which is what the driver parses: ``ok``
    and the device as JAX reports it, and no other key."""
    return json.dumps({"ok": summary["ok"], "device": summary["device"]})


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the traffic (default 0)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: refusing platform {platform!r}: this is the "
              f"chip check, and a CPU run proves nothing about the chip "
              f"(rehearse with tests/test_chip_smoke.py)", file=sys.stderr)
        return 1
    # block/record logs default to ~/logs/csp; keep the run inside a
    # directory that goes away with it
    log_dir = tempfile.mkdtemp(prefix="chip_smoke_logs_")
    os.environ.setdefault("SENTINEL_TPU_LOG_DIR", log_dir)
    try:
        summary = run(PRODUCT, args.seed, t_start)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps(summary))
    print(result_line(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
