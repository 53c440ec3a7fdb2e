"""The embedded engine under the project's own configs 2 + 3: ``embed.py``'s
cell with 10,000 flow rules over all four traffic-shaping controllers and
100,000 slow-ratio and exception-ratio circuit breakers on its 1M-row
table, and a tenth of the dependencies unhealthy. What differs from the
resident cell, and only that, is here:

* the rule population (``set_up``), from the configuration's fields;
* a completion's RT and error depend on the health of the dependency
  behind the name — a name of popularity rank ``r`` is sick during submit
  ``k`` iff ``(r + k // moves_every) % one_in == 0`` — and every admitted
  entry, waited or not, is exited on the row its TICKET returned;
* every verdict's ``wait_ms`` is kept, from the engine's handle and from
  the pipeline's ticket, and the RT of every completion for the replay;
* ``check()`` compares reasons AND waits with the plain sequential
  reference (``chipbench/reference/shaping.py``), every limit 0, and adds
  ``unexercised``: 1 for each thing the run has to contain, by the
  reference's own count, and did not;
* ``control()`` is the reference in the program's place with the fault the
  program had planted: events a breaker refuses charged to the count-based
  flow budget. It must not be correct.
"""

from __future__ import annotations

import sys
import time
from typing import List, Tuple

import numpy as np

from chipbench import registry
from chipbench.cell import Checks, Measured, Tracer
from chipbench.deployments.embed import _EXITS, EmbeddedEngineCell, _EngineTap
from chipbench.generators import arrivals
from chipbench.reference import shaping

#: configuration's controller names -> the reference's codes, in the order
#: of ``FlowRule.control_behavior`` (RuleConstant.CONTROL_BEHAVIOR_*)
BEHAVIORS = {"DefaultController": shaping.DEFAULT,
             "WarmUpController": shaping.WARM_UP,
             "RateLimiterController": shaping.RATE_LIMITER,
             "WarmUpRateLimiterController": shaping.WARM_UP_RATE_LIMITER}
#: the program's counters whose window deltas the per-layer metrics read
COUNTERS = ("verdict.paced", "verdict.passed_now", "breaker.seen_open",
            "breaker.seen_closed", "breaker.opened", "breaker.half_opened",
            "breaker.closed", "block_reason.FlowException",
            "block_reason.DegradeException")


class _AtOpen:
    """The loop arms its tracer at the instant the window opens: the
    program's counters are read there too."""

    def __init__(self, cell: "RulesEngineCell", tracer: Tracer) -> None:
        self.cell, self.tracer = cell, tracer

    def arm(self, t0: float) -> None:
        self.cell.at_open = self.cell._counters()
        self.tracer.arm(t0)


def rule_population(cfg: dict) -> Tuple[dict, dict]:
    """The configuration's rules as the reference takes them:
    ``r0 … r<flow_rules-1>`` carry a flow rule, controller by ``i % 4``;
    ``r0 … r<degrade_rules-1>`` a breaker, odd ``i`` slow-ratio, even ``i``
    exception-ratio."""
    kinds = [BEHAVIORS[b] for b in cfg["flow_behaviors"]]
    flow = {f"r{i}": shaping.FlowShape(
        count=cfg["flow_count"], behavior=kinds[i % len(kinds)],
        warm_up_period_s=cfg["warm_up_period_sec"],
        max_queue_ms=cfg["max_queueing_time_ms"],
        cold_factor=cfg["cold_factor"]) for i in range(cfg["flow_rules"])}
    retry_ms = cfg["degrade_window_s"] * 1000
    common = dict(retry_ms=retry_ms, min_requests=cfg["degrade_min_requests"],
                  interval_ms=cfg["degrade_stat_interval_ms"])
    breakers = {f"r{i}": (
        shaping.Breaker(shaping.SLOW_RATIO, cfg["slow_ratio_threshold"],
                        max_rt_ms=cfg["slow_rt_ms"], **common) if i % 2
        else shaping.Breaker(shaping.ERROR_RATIO, cfg["error_ratio"],
                             **common)) for i in range(cfg["degrade_rules"])}
    return flow, breakers


class RulesEngineCell(EmbeddedEngineCell):
    # -- the deployment ---------------------------------------------------
    def set_up(self) -> None:
        import sentinel_tpu as stpu
        from sentinel_tpu.rules.degrade import (
            GRADE_EXCEPTION_RATIO, GRADE_RT,
        )
        cfg, mix, seed = self.cfg, self.mix, self.ctx.seed
        self.exit_log: List[np.ndarray] = []        # per exit call: rt_ms
        self.flow, self.breaker = rule_population(cfg)
        self.sph = sph = stpu.Sentinel(stpu.load_config(
            max_resources=cfg["rows"], max_flow_rules=cfg["max_flow_rules"],
            max_degrade_rules=cfg["max_degrade_rules"]))
        sph.load_flow_rules([stpu.FlowRule(
            resource=n, count=float(s.count), control_behavior=s.behavior,
            warm_up_period_sec=s.warm_up_period_s,
            max_queueing_time_ms=s.max_queue_ms)
            for n, s in self.flow.items()])
        sph.load_degrade_rules([stpu.DegradeRule(
            resource=n,
            grade=GRADE_RT if b.grade == shaping.SLOW_RATIO
            else GRADE_EXCEPTION_RATIO,
            count=b.max_rt_ms if b.grade == shaping.SLOW_RATIO
            else b.threshold,
            time_window=cfg["degrade_window_s"],
            min_request_amount=b.min_requests,
            stat_interval_ms=b.interval_ms,
            slow_ratio_threshold=b.threshold
            if b.grade == shaping.SLOW_RATIO else 1.0)
            for n, b in self.breaker.items()])
        both = [f"r{i}" for i in range(cfg["flow_rules"])]
        only = [f"r{i}" for i in range(cfg["flow_rules"],
                                       cfg["degrade_rules"])]
        fill = [f"k{i}" for i in range(cfg["rows"] - len(sph.resources))]
        # popularity rank -> name: the flow-ruled names first, then the
        # breaker-only names, then the fill, each in an order from the
        # seed; interning them occupies every row
        rng = arrivals.rng_for(seed, arrivals._PERM)
        names = [n for group in (both, only, fill) for n in
                 np.array(group, object)[rng.permutation(len(group))]]
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
        gen = registry.find("generators", mix["generator"])
        self.schedule = gen(mix, seed, self.ctx.seconds, len(names))
        erng = arrivals.rng_for(seed, _EXITS)
        n = self.schedule.rank.size

        def completions(median: str, sigma: str, rate: str):
            rt = np.maximum(1, erng.lognormal(
                np.log(mix[median]), mix[sigma], n)).astype(np.int32)
            return rt, erng.random(n) < mix[rate]
        # the healthy draws first, as embed.py makes them
        self.rt_ms, self.error = completions(
            "rt_median_ms", "rt_sigma", "error_rate")
        self.sick_rt_ms, self.sick_error = completions(
            "sick_rt_median_ms", "sick_rt_sigma", "sick_error_rate")
        self.pad_row = sph.spec.rows
        self.alt_pad = sph.spec.alt_rows
        for size in mix.get("warm_exit_sizes", []):
            self._exit(np.full(size, self.pad_row, np.int32),
                       np.ones(size, np.int32), np.zeros(size, bool))

    def _exit(self, rows, rt_ms, error) -> None:
        self.exit_log.append(np.array(rt_ms, copy=True))
        super()._exit(rows, rt_ms, error)

    # -- the window -------------------------------------------------------
    def run_window(self, tracer: Tracer) -> Measured:
        self.at_open = None
        self.caller_wait: List[np.ndarray] = []     # per submit, in order
        m = super().run_window(_AtOpen(self, tracer))
        at_close = self._counters()
        m.counters.update({k: at_close[k] - self.at_open[k]
                           for k in COUNTERS})
        print(f"rules cell: the window's counters {m.counters}",
              file=sys.stderr)
        return m

    def _counters(self) -> dict:
        return {k: self.sph.obs.counters.get(k) for k in COUNTERS}

    def _settle(self, prev) -> float:
        """As the resident cell's, but the waits are kept, a completion is
        the sick kind where its dependency is sick during this submit, and
        the exits go to the rows the ticket returned."""
        ticket, idx, lo = prev
        verdicts = ticket.result()
        at = time.monotonic()
        k = len(self.caller_got)                    # this submit's number
        allow = np.asarray(verdicts.allow)
        self.caller_got.append(
            np.where(allow, 0, np.asarray(verdicts.reason)).astype(np.int64))
        self.caller_wait.append(np.asarray(verdicts.wait_ms).astype(np.int64))
        passed = np.nonzero(allow)[0]
        mix = self.mix
        sick = (idx[passed] + k // mix["sick_moves_every_submits"]) \
            % mix["sick_one_in"] == 0
        at_ = lo + passed
        rows = getattr(ticket, "rows", None)
        rows = self.rows[idx[passed]] if rows is None else rows[passed]
        self._exit(rows,
                   np.where(sick, self.sick_rt_ms[at_], self.rt_ms[at_]),
                   np.where(sick, self.sick_error[at_], self.error[at_]))
        return at

    # -- correct ----------------------------------------------------------
    def reference(self, **kw) -> shaping.ShapingReference:
        return shaping.ShapingReference(
            self.flow, self.breaker, self.epoch_ms,
            buckets=self.cfg["window_buckets"], win_ms=self.cfg["window_ms"],
            **kw)

    def _replay(self, ref: shaping.ShapingReference):
        """The tap's calls through a reference → reasons and waits per
        entry call."""
        reasons, waits = [], []
        rts = iter(self.exit_log)
        for kind, _, _, now_ms, what, extra in self.tap.calls:
            if kind == "entry":
                names = what if isinstance(what, list) \
                    else self.name_of_row[what].tolist()
                r, w = ref.entries(names, now_ms)
                reasons.append(r)
                waits.append(w)
            else:
                rt_ms = next(rts)
                keep = what != self.pad_row
                ref.exits(self.name_of_row[what[keep]].tolist(),
                          rt_ms[keep].tolist(), extra[keep].tolist(), now_ms)
        return reasons, waits

    def check(self) -> Checks:
        got, got_wait = [], []
        for call in self.tap.calls:
            if call[0] == "entry":
                v = call[5].result()
                got.append(np.where(np.asarray(v.allow), 0,
                                    np.asarray(v.reason)).astype(np.int64))
                got_wait.append(np.asarray(v.wait_ms).astype(np.int64))
        t = time.monotonic()
        ref = self.reference()
        self.sound = want, want_wait = self._replay(ref)
        print(f"rules cell: the reference replayed "
              f"{sum(len(w) for w in want)} events in "
              f"{time.monotonic() - t:.1f} s and saw {ref.seen}",
              file=sys.stderr)
        self._show_first_mismatches(want, want_wait, got, got_wait)
        return {
            "engine_wrong": (self._differ(want, got), 0),
            # the j-th submit is the j-th entry call: one caller thread,
            # and set-up makes no entry call in this mix
            "caller_wrong": (self._differ(want, self.caller_got), 0),
            "wait_wrong": (self._differ(want_wait, got_wait)
                           + self._differ(want_wait, self.caller_wait), 0),
            # a run that did not contain what the configuration is for
            # proves nothing about it
            "unexercised": (sum(ref.seen[e] == 0
                                for e in shaping.EXERCISES), 0),
        }

    def _show_first_mismatches(self, want, want_wait, got, got_wait,
                               limit: int = 8) -> None:
        """Where the engine and the reference part, for whoever has to find
        out why: per entry call (at most ``limit``) the first name that
        differs, its rules, and the (reason, wait) pairs of its first
        events in that call, wanted and got."""
        entries = [c for c in self.tap.calls if c[0] == "entry"]
        for j, call in enumerate(entries[: len(got)]):
            w, ww = np.asarray(want[j]), np.asarray(want_wait[j])
            g, gw = got[j][: w.size], got_wait[j][: w.size]
            bad = np.nonzero((w != g) | (ww != gw))[0]
            if not bad.size:
                continue
            names = call[4] if isinstance(call[4], list) \
                else self.name_of_row[call[4]].tolist()
            name = names[bad[0]]
            at = [k for k, n in enumerate(names) if n == name][:12]
            print(f"rules cell: entry call {j} at "
                  f"{call[3] - self.epoch_ms} ms differs in {bad.size} "
                  f"events, first on {name} ({self.flow.get(name)}, "
                  f"{self.breaker.get(name)}): wanted "
                  f"{[(int(w[k]), int(ww[k])) for k in at]} got "
                  f"{[(int(g[k]), int(gw[k])) for k in at]}",
                  file=sys.stderr)
            limit -= 1
            if not limit:
                return

    def control(self) -> Checks:
        """The reference in the program's place with the program's old
        fault planted: an event a breaker refuses is charged to the
        count-based flow budget. In the program's place the control hands
        its caller what it decides, so the caller's reading is the
        engine's."""
        sound, sound_wait = self.sound      # check() has replayed it
        ctl, ctl_wait = self._replay(self.reference(charge_refused=True))
        as_arrays = [np.asarray(r, np.int64) for r in ctl]
        wrong = self._differ(sound, as_arrays)
        wait_wrong = self._differ(
            sound_wait, [np.asarray(w, np.int64) for w in ctl_wait])
        return {"engine_wrong": (wrong, 0), "caller_wrong": (wrong, 0),
                "wait_wrong": (2 * wait_wrong, 0)}


BUILDERS = {"embedded_engine_rules": RulesEngineCell}
