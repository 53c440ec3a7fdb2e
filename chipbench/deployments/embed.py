"""The engine embedded in a service: ``Sentinel`` at the deployment's size,
driven through ``DispatchPipeline`` (batches) or ``sph.frontend()``
(requests), on the real clock, with the cadence scheduler armed."""

from __future__ import annotations

import asyncio
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import registry, stats
from chipbench.cell import Checks, Context, Measured, Span, Tracer
from chipbench.generators import arrivals
from chipbench.reference.engine import BreakerRule, EngineReference

_EXITS = 4        # rng stream of the completions (RT, errors)
SHED, UNANSWERED = -2, -1


class _EngineTap:
    """Every call into the engine, in the engine's order, with the clock
    reading the engine stamped it with — taken at the three methods the
    serving tiers go through. The tap's lock makes the recorded order the
    order of the state updates when decides and exits come from different
    threads."""

    def __init__(self, sph) -> None:
        import jax
        self.calls: List[tuple] = []
        self.lock = threading.Lock()
        self._note = jax.profiler.TraceAnnotation
        self._now = threading.local()
        self._scalars, self._entry, self._exit = (
            sph._time_scalars, sph.entry_batch_nowait, sph.exit_batch)
        sph._time_scalars = self._time_scalars
        sph.entry_batch_nowait = self._entry_batch_nowait
        sph.exit_batch = self._exit_batch

    def _time_scalars(self, now_ms):
        self._now.ms = int(now_ms)
        return self._scalars(now_ms)

    def _entry_batch_nowait(self, resources, **kw):
        # the annotation carries the batch size into the trace, where the
        # device's work is counted per whole cycle of this call
        with self.lock, self._note("bench.entry", n=len(resources)):
            t0 = time.monotonic()
            handle = self._entry(resources, **kw)
            what = (np.array(resources, copy=True)
                    if isinstance(resources, np.ndarray) else list(resources))
            self.calls.append(("entry", t0, time.monotonic(), self._now.ms,
                               what, handle))
        return handle

    def _exit_batch(self, **kw):
        with self.lock:
            t0 = time.monotonic()
            self._exit(**kw)
            self.calls.append(("exit", t0, time.monotonic(), self._now.ms,
                               np.array(kw["rows"], copy=True),
                               np.array(kw["error"], copy=True)))


class EmbeddedEngineCell:
    def __init__(self, ctx: Context) -> None:
        self.ctx, self.cfg, self.mix = ctx, ctx.cell.config, ctx.cell.traffic
        self.sph = self.sched = self.tap = self.fe = self.pipe = None

    # -- the deployment ---------------------------------------------------
    def set_up(self) -> None:
        import sentinel_tpu as stpu
        from sentinel_tpu.rules.degrade import GRADE_EXCEPTION_RATIO
        cfg, seed = self.cfg, self.ctx.seed
        self.sph = sph = stpu.Sentinel(stpu.load_config(
            max_resources=cfg["rows"], max_flow_rules=2 * cfg["flow_rules"],
            max_degrade_rules=2 * cfg["degrade_rules"]))
        ruled = [f"r{i}" for i in range(cfg["flow_rules"])]
        self.flow = {name: cfg["flow_count"] for name in ruled}
        self.breaker = {f"r{i}": BreakerRule(
            cfg["degrade_ratio"], cfg["degrade_window_s"] * 1000)
            for i in range(cfg["degrade_rules"])}
        sph.load_flow_rules(
            [stpu.FlowRule(resource=n, count=float(c))
             for n, c in self.flow.items()]
            + [stpu.FlowRule(resource="HelloWorld", count=20.0)])
        sph.load_degrade_rules([stpu.DegradeRule(
            resource=n, grade=GRADE_EXCEPTION_RATIO, count=r.ratio,
            time_window=cfg["degrade_window_s"])
            for n, r in self.breaker.items()])
        sph.load_param_flow_rules([stpu.ParamFlowRule(
            resource="hot", param_idx=0, count=5)])
        fill = [f"k{i}" for i in range(cfg["rows"] - len(sph.resources))]
        # popularity rank -> name: the ruled names first, then the fill,
        # each in an order from the seed; interning them occupies every row
        rng = arrivals.rng_for(seed, arrivals._PERM)
        names = np.array(ruled, object)[rng.permutation(len(ruled))].tolist() \
            + np.array(fill, object)[rng.permutation(len(fill))].tolist()
        self.names = np.array(names, object)
        self.rows = np.asarray(sph.intern_resources(names))
        if len(sph.resources) != cfg["rows"]:
            raise RuntimeError(f"registry holds {len(sph.resources)} names, "
                               f"not {cfg['rows']}")
        self.name_of_row = np.empty(cfg["rows"] + 1, object)  # + the pad row
        self.name_of_row[self.rows] = self.names
        self.tap = _EngineTap(sph)
        self.sched = stpu.CadenceScheduler(sph, telemetry_interval_sec=1.0)
        self.sched.start()
        gen = registry.find("generators", self.mix["generator"])
        self.schedule = gen(self.mix, seed, self.ctx.seconds, len(names))
        erng = arrivals.rng_for(seed, _EXITS)
        n = self.schedule.rank.size
        self.rt_ms = np.maximum(1, erng.lognormal(
            np.log(self.mix["rt_median_ms"]), self.mix["rt_sigma"], n)
        ).astype(np.int32)
        self.error = erng.random(n) < self.mix["error_rate"]
        self.pad_row = sph.spec.rows
        self.alt_pad = sph.spec.alt_rows
        for size in self.mix.get("warm_exit_sizes", []):
            self._exit(np.full(size, self.pad_row, np.int32),
                       np.ones(size, np.int32), np.zeros(size, bool))
        if self.mix["drives"] == "frontend":
            for size in self.mix["warm_entry_sizes"]:
                cold = self.rows[-size:]            # unruled tail names
                sph.entry_batch_nowait(np.ascontiguousarray(cold)).result()

    def _exit(self, rows, rt_ms, error) -> None:
        n = rows.shape[0]
        self.sph.exit_batch(
            rows=rows, origin_rows=np.full(n, self.alt_pad, np.int32),
            chain_rows=np.full(n, self.alt_pad, np.int32),
            acquire=np.ones(n, np.int32), rt_ms=rt_ms, error=error,
            is_in=np.ones(n, bool))

    # -- the window -------------------------------------------------------
    def run_window(self, tracer: Tracer) -> Measured:
        if self.mix["drives"] == "pipeline":
            return self._closed_batches(tracer)
        return asyncio.run(self._open_requests(tracer))

    def _closed_batches(self, tracer: Tracer) -> Measured:
        """One caller, closed loop: submit batch k, take batch k-1's
        verdicts, exit what of k-1 passed. The warm phase is the same loop
        before the window opens. The window opens and closes on a verdict
        in the caller's hands: it opens with the first one that comes
        ``warm_seconds`` or more after the loop's first (which may have
        compiled), and closes with the first one ``--seconds`` or more
        later, so it holds whole batches and the rate does not move in
        steps of one."""
        import sentinel_tpu as stpu
        self.pipe = pipe = stpu.DispatchPipeline(self.sph)
        size, rank = self.mix["batch"], self.schedule.rank
        seconds, warm = self.ctx.seconds, self.mix["warm_seconds"]
        self.caller_got: List[np.ndarray] = []      # per submit, in order
        done: List[float] = []                      # verdicts in hand at
        prev = t0 = None
        k = 0
        while t0 is None or done[-1] < t0 + seconds:
            lo = (k % (rank.size // size)) * size
            idx = rank[lo: lo + size]
            ticket = pipe.submit(self.names[idx].tolist())
            if prev is not None:
                done.append(self._settle(prev))
                if t0 is None and done[-1] - done[0] >= warm:
                    t0 = done[-1]
                    tracer.arm(t0)
            prev = (ticket, idx, lo)
            k += 1
        end = done[-1]
        self._settle(prev)                  # past the window: not counted
        batches = sum(t0 < t <= end for t in done)
        return Measured(
            t0=t0, window_s=end - t0, attempted=batches * size, failed=0,
            end_to_end={"decisions_per_s": batches * size / (end - t0)},
            spans=self._spans(t0, end - t0),
            counters={"batches": batches})

    def _settle(self, prev) -> float:
        """The verdicts as the pipeline hands them to the caller, kept for
        ``caller_wrong``; → the instant they were in hand."""
        ticket, idx, lo = prev
        verdicts = ticket.result()
        at = time.monotonic()
        allow = np.asarray(verdicts.allow)
        self.caller_got.append(
            np.where(allow, 0, np.asarray(verdicts.reason)).astype(np.int64))
        passed = np.nonzero(allow)[0]
        self._exit(self.rows[idx[passed]], self.rt_ms[lo + passed],
                   self.error[lo + passed])
        return at

    async def _open_requests(self, tracer: Tracer) -> Measured:
        """Open loop through the front end with no knob set: one
        ``submit`` per request at its due time, completions handed back
        in bulk every ``exit_every_ms``."""
        from sentinel_tpu.frontend import IngestOverload
        self.fe = fe = self.sph.frontend(record_flushes=True)
        mix, sched = self.mix, self.schedule
        seconds = self.ctx.seconds
        due, rank = sched.due_s.tolist(), sched.rank
        names = self.names[rank].tolist()
        n = len(due)
        recv = np.full(n, np.nan)
        sent = np.full(n, np.nan)
        reason = np.full(n, UNANSWERED, np.int64)
        to_exit: List[int] = []
        loop = asyncio.get_running_loop()
        t0 = time.monotonic() + mix["warm_seconds"] + 0.1
        tracer.arm(t0)

        async def one(i: int) -> None:
            try:
                v = await fe.submit(names[i])
            except IngestOverload:
                reason[i] = SHED                    # refused at the door:
                return                              # a failure, answered
            recv[i] = time.monotonic() - t0
            reason[i] = v.reason if not v.allow else 0
            if v.allow:
                to_exit.append(i)

        async def exit_pump() -> None:
            while True:
                await asyncio.sleep(mix["exit_every_ms"] / 1e3)
                if to_exit:
                    take = np.array(to_exit)
                    to_exit.clear()
                    await loop.run_in_executor(
                        None, self._exit, self.rows[rank[take]],
                        self.rt_ms[take], self.error[take])

        pump = loop.create_task(exit_pump())
        tasks = []
        i = 0
        while i < n:
            now = time.monotonic() - t0
            while i < n and due[i] <= now:
                tasks.append(loop.create_task(one(i)))
                sent[i] = time.monotonic() - t0
                i += 1
            if i < n:
                # a real sleep, never a spin: the generator shares the
                # process, and a thread that spins holds the GIL against
                # the batcher's worker threads for 5 ms at a time
                wait = due[i] - (time.monotonic() - t0)
                await asyncio.sleep(wait if wait > 0 else 0)
        await asyncio.wait(tasks, timeout=mix["timeout_ms"] / 1e3
                           + mix["grace_s"])
        pump.cancel()
        await fe.drain()
        self.flush_log = list(fe.flush_log)
        self.request_names, self.request_reason = names, reason
        timed = sched.due_s >= 0.0
        lat = stats.due_latency_ms(sched.due_s[timed], recv[timed],
                                   mix["timeout_ms"])
        late = (sent[timed] - sched.due_s[timed]) * 1e3
        # queue wait, from the benchmark's clock: submit() to the start of
        # the engine call of the batch that carried the request
        starts = [c[1] - t0 for c in self.tap.calls if c[0] == "entry"][
            -len(self.flush_log):]
        where: Dict[str, List[float]] = {}
        for start, entry in zip(starts, self.flush_log):
            for name in entry["resources"]:
                where.setdefault(name, []).append(start)
        nth: Dict[str, int] = {}
        wait = np.full(n, np.nan)
        for i, name in enumerate(names):
            if reason[i] == SHED:
                continue
            j = nth.get(name, 0)
            nth[name] = j + 1
            if j < len(where.get(name, ())):
                wait[i] = (where[name][j] - sent[i]) * 1e3
        wait = wait[timed]
        print(f"embed cell: generator late p99 {np.percentile(late, 99):.3f} "
              f"max {late.max():.3f} ms", file=sys.stderr)
        return Measured(
            t0=t0, window_s=seconds, attempted=int(timed.sum()),
            failed=stats.failed(lat, mix["timeout_ms"]),
            end_to_end={"grant_p50_ms": stats.percentile_exact(lat, 50),
                        "grant_p99_ms": stats.percentile_exact(lat, 99)},
            spans=self._spans(t0, seconds),
            samples={"grant_ms": lat, "late_ms": late,
                     "due_s": sched.due_s[timed],
                     "queue_wait_ms": wait[~np.isnan(wait)]})

    def _spans(self, t0: float, seconds: float) -> Dict[str, List[Span]]:
        """The tap's clock around each engine call, plus the program's own
        spans (``obs.spans``) by name, inside the window."""
        out: Dict[str, List[Span]] = {"bench.entry": [], "bench.exit": []}
        for kind, a, b, _, what, *_ in self.tap.calls:
            if 0.0 <= a - t0 and b - t0 <= seconds:
                out[f"bench.{kind}"].append(Span(a - t0, b - t0, len(what)))
        obs = self.sph.obs
        base = time.monotonic() - obs.spans.now_ns() / 1e9 - t0
        for rec in obs.spans.snapshot():
            a, b = rec["start_ns"] / 1e9 + base, rec["end_ns"] / 1e9 + base
            if 0.0 <= a and b <= seconds:
                out.setdefault(rec["name"], []).append(
                    Span(a, b, rec["n"] or 1))
        return out

    def release(self) -> None:
        self.epoch_ms = self.sph.epoch_ms   # the breakers' windows count from it
        if self.fe is not None:
            self.fe.close()
        self.sph.close()
        self.sph._state = self.sph._ruleset = None

    # -- correct ----------------------------------------------------------
    def reference(self, **window) -> EngineReference:
        window = {"buckets": self.cfg["window_buckets"],
                  "win_ms": self.cfg["window_ms"], **window}
        return EngineReference(self.flow, self.breaker,
                               self.epoch_ms, **window)

    def _replay(self, ref: EngineReference) -> List[List[int]]:
        """The tap's calls through a reference → reasons per entry call."""
        out = []
        for kind, _, _, now_ms, what, extra in self.tap.calls:
            if kind == "entry":
                names = what if isinstance(what, list) \
                    else self.name_of_row[what].tolist()
                out.append(ref.entries(names, now_ms))
            else:
                keep = what != self.pad_row
                ref.exits(self.name_of_row[what[keep]].tolist(),
                          extra[keep].tolist(), now_ms)
        return out

    def check(self) -> Checks:
        got = []
        for call in self.tap.calls:
            if call[0] == "entry":
                v = call[5].result()
                got.append(np.where(np.asarray(v.allow), 0,
                                    np.asarray(v.reason)).astype(np.int64))
        return self._judge(got, served=True)

    def control(self) -> Checks:
        """The reference in the program's place with the statistics window
        the configuration states coarsened to one bucket of 1000 ms."""
        ctl = self._replay(self.reference(buckets=1, win_ms=1000))
        checks = self._judge([np.asarray(r, np.int64) for r in ctl],
                             served=False)
        if self.mix["drives"] == "pipeline":
            # in the program's place the control hands its caller what it
            # decides, so the caller's reading is the engine's
            checks["caller_wrong"] = checks["engine_wrong"]
        return checks

    @staticmethod
    def _differ(want, got) -> int:
        """Answers that differ, batch by batch, a missing one counted."""
        return abs(len(want) - len(got)) + sum(
            int((np.asarray(w, np.int64) != g[: len(w)]).sum())
            + abs(len(w) - len(g)) for w, g in zip(want, got))

    def _judge(self, got, served: bool) -> Checks:
        """``got``: what the engine's handles said, per entry call.
        ``served`` adds what the serving tier handed its caller: the
        pipeline's tickets, or the front end's per-request verdicts."""
        ref = self.reference()
        want = self._replay(ref)
        checks = {"engine_wrong": (self._differ(want, got), 0)}
        if served and self.mix["drives"] == "frontend":
            checks.update(self._frontend_fanout(want))
        elif served:
            # the j-th submit is the j-th entry call: one caller thread,
            # and set-up makes no entry call in this mix
            checks["caller_wrong"] = (self._differ(want, self.caller_got), 0)
        print(f"embed cell: reference saw {ref.trips} breaker trips",
              file=sys.stderr)
        return checks

    def _frontend_fanout(self, want: List[List[int]]) -> Checks:
        """Each request's own verdict against the reference's for its
        place in the engine's order: the j-th request for a name is the
        j-th occurrence of that name in the flushed batches."""
        offset = sum(c[0] == "entry" for c in self.tap.calls) \
            - len(self.flush_log)         # the warm-up's direct calls
        per_name: Dict[str, List[int]] = {}
        for k, entry in enumerate(self.flush_log):
            for name, r in zip(entry["resources"], want[offset + k]):
                per_name.setdefault(name, []).append(r)
        seen: Dict[str, int] = {}
        wrong = unanswered = 0
        for name, r in zip(self.request_names, self.request_reason):
            if r == SHED:                   # never reached the engine
                continue
            j = seen.get(name, 0)
            seen[name] = j + 1
            if r == UNANSWERED:
                unanswered += 1
            elif j >= len(per_name.get(name, ())) or per_name[name][j] != r:
                wrong += 1
        return {"client_wrong": (wrong, 0), "unanswered": (unanswered, 0)}

    def close(self) -> None:
        if self.sched is not None:
            self.sched.stop()
        if self.sph is not None and self.sph._state is not None:
            self.sph.close()


BUILDERS = {"embedded_engine": EmbeddedEngineCell}
