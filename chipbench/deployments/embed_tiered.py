"""The embedded engine asked about more names than it has rows: ``embed.py``'s
cell over a universe of ``names`` names, 16 x its table. The table starts
full of the most popular names and the cold tier empty; from the first
batch on every batch evicts rows to the host and brings names back. What
differs from the resident cell, and only that, is here:

* the generator draws from the universe, not from the resident names;
* a passed entry is exited on the row its TICKET returned — a row means
  different names at different times, so the row table ``embed.py`` makes
  at set-up cannot say — and the names of every exit are kept for the
  replay;
* ``check()`` adds ``state_wrong``: among ALL names with a completion in
  the run, those whose cumulative RT histogram, read by name from the
  program whichever tier holds it, differs from the plain reference's
  (``chipbench/reference/tiered.py``, which never forgets a name);
* ``control()`` is the same run with ``SENTINEL_TIERING_DISABLE=1`` —
  lossy eviction — which must read ``state_wrong`` > 0.
"""

from __future__ import annotations

import gc
import os
import resource
import sys
import time
from typing import List

import numpy as np

from chipbench import registry
from chipbench.cell import Checks, Measured, Tracer
from chipbench.deployments.embed import EmbeddedEngineCell
from chipbench.readers.tiering import program_seconds
from chipbench.reference.tiered import HIST_BUCKETS, TieredReference

DISABLE = "SENTINEL_TIERING_DISABLE"
COUNTERS = ("tier.hot_hit", "tier.cold_miss", "tier.promoted", "tier.demoted",
            "tier.first_sight", "tier.land_inline",
            "intern.names", "intern.distinct")


class _AtOpen:
    """The loop arms its tracer at the instant the window opens: the
    program's counters are read there too."""

    def __init__(self, cell: "TieredEngineCell", tracer: Tracer) -> None:
        self.cell, self.tracer = cell, tracer

    def arm(self, t0: float) -> None:
        self.cell.at_open = self.cell._counters()
        self.cell.gc_pauses.clear()
        self.tracer.arm(t0)


class _GcPauses:
    """Seconds the collector held the process, by generation, since the
    last ``clear`` — printed beside the window's counters: the cold tier
    is millions of host objects, and a slow run should say whether the
    collector made it so."""

    def __init__(self) -> None:
        self.seconds = [0.0, 0.0, 0.0]
        self.runs = [0, 0, 0]
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.seconds[info["generation"]] += time.monotonic() - self._t
            self.runs[info["generation"]] += 1

    def clear(self) -> None:
        self.seconds, self.runs = [0.0, 0.0, 0.0], [0, 0, 0]


class TieredEngineCell(EmbeddedEngineCell):
    # -- the deployment ---------------------------------------------------
    def set_up(self) -> None:
        from sentinel_tpu.serving import PipelinedVerdicts
        if not hasattr(PipelinedVerdicts, "rows"):
            # a program whose tickets do not say which rows a batch was
            # admitted on cannot exit what it entered once rows churn:
            # said at once, before anything is built
            raise RuntimeError(
                "this program's DispatchPipeline tickets carry no rows: "
                "the cell cannot run on it")
        cfg, seed = self.cfg, self.ctx.seed
        self.exit_log: List[tuple] = []     # per exit call: (ranks, rt_ms)
        self.demoted_names: set = set()
        super().set_up()                    # the table, full; rules; warm-ups
        sph = self.sph
        sph.tiering.add_demote_listener(self.demoted_names.update)
        resident = self.names
        # rank -> name over the universe: the resident names, then names
        # nobody has interned, made for the ranks the schedule draws
        gen = registry.find("generators", self.mix["generator"])
        self.schedule = gen(self.mix, seed, self.ctx.seconds, cfg["names"])
        self.names = np.empty(cfg["names"], object)
        self.names[: resident.size] = resident
        drawn = np.unique(self.schedule.rank)
        drawn = drawn[drawn >= resident.size]
        # (the resident fill is k0..k<fill-1>: a rank past it is no one's)
        self.names[drawn] = [f"k{r}" for r in drawn.tolist()]
        # the row each name was last seen on (set-up's for the resident
        # ones), and who has changed rows since: evicted and brought back
        self.last_row = np.full(cfg["names"], -1, np.int32)
        self.last_row[: resident.size] = self.rows
        self.moved = np.zeros(cfg["names"], bool)
        sph.tiering.warm_migration(cfg["warm_migrate_rows"])

    def _exit(self, rows, rt_ms, error, ranks=None) -> None:
        self.exit_log.append((ranks, rt_ms))
        super()._exit(rows, rt_ms, error)

    # -- the window -------------------------------------------------------
    def run_window(self, tracer: Tracer) -> Measured:
        self.at_open = None
        self.gc_pauses = _GcPauses()
        gc.callbacks.append(self.gc_pauses)
        try:
            self.measured = m = super().run_window(_AtOpen(self, tracer))
        finally:
            gc.callbacks.remove(self.gc_pauses)
        at_close = self._counters()
        m.counters.update(
            {k: at_close[k] - self.at_open[k] for k in COUNTERS})
        return m

    def _counters(self) -> dict:
        return {k: self.sph.obs.counters.get(k) for k in COUNTERS}

    def _settle(self, prev) -> float:
        """As the resident cell's, but the exits go to the rows the ticket
        returned, and the names exited are kept."""
        ticket, idx, lo = prev
        verdicts = ticket.result()
        at = time.monotonic()
        allow = np.asarray(verdicts.allow)
        self.caller_got.append(
            np.where(allow, 0, np.asarray(verdicts.reason)).astype(np.int64))
        rows = ticket.rows
        was = self.last_row[idx]
        self.moved[idx[(was >= 0) & (was != rows)]] = True
        self.last_row[idx] = rows
        passed = np.nonzero(allow)[0]
        self._exit(rows[passed], self.rt_ms[lo + passed],
                   self.error[lo + passed], ranks=idx[passed])
        return at

    def release(self) -> None:
        """Before the state goes: what every name with a completion owns,
        read by name; and, of a traced run, the migration programs' device
        time while the trace is still there."""
        ranks = [r for r, _ in self.exit_log if r is not None]
        self.state_ranks = np.unique(np.concatenate(ranks)) if ranks \
            else np.zeros(0, np.int64)
        self.state_names = self.names[self.state_ranks].tolist()
        t = time.monotonic()
        self.state_hist = self.sph.rt_hist_by_name(self.state_names)
        snap = self.sph.tiering.snapshot()
        print(f"tiered cell: read {len(self.state_names)} names' histograms "
              f"in {time.monotonic() - t:.1f} s; resident {snap['resident']}, "
              f"cold {snap['cold']}, pending_land {snap['pending_land']}; "
              f"the window's counters {self.measured.counters}; collector "
              f"runs {self.gc_pauses.runs} seconds "
              f"{[round(x, 3) for x in self.gc_pauses.seconds]}; the "
              f"process's peak RSS "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB",
              file=sys.stderr)
        if self.ctx.trace:
            programs = next(m["programs"] for m in self.ctx.cell.per_layer
                            if "programs" in m)
            seconds = program_seconds(str(self.ctx.workdir / "trace"),
                                      programs)
            if seconds is not None:
                self.measured.samples["program_device_s"] = seconds
                print(f"tiered cell: device seconds by program {seconds}",
                      file=sys.stderr)
        super().release()

    # -- correct ----------------------------------------------------------
    def reference(self, **window) -> TieredReference:
        window = {"buckets": self.cfg["window_buckets"],
                  "win_ms": self.cfg["window_ms"], **window}
        self.ref = TieredReference(self.flow, self.breaker, self.epoch_ms,
                                   **window)
        return self.ref

    def _replay(self, ref: TieredReference) -> List[List[int]]:
        """The tap's calls through the reference: entries by the names
        they were submitted under, exits by the names the cell kept."""
        out = []
        exits = iter(self.exit_log)
        for kind, _, _, now_ms, what, extra in self.tap.calls:
            if kind == "entry":
                out.append(ref.entries(what, now_ms))
                continue
            ranks, rt_ms = next(exits)
            if ranks is not None:           # not a warm-up of pad rows
                ref.completions(self.names[ranks].tolist(), rt_ms.tolist(),
                                extra.tolist(), now_ms)
        return out

    def check(self) -> Checks:
        checks = super().check()            # replays: self.ref holds the run
        ref = self.ref
        # the reference's histograms by place; the last row stands for a
        # name it never saw a completion of, and equals nothing
        want = np.zeros((len(ref.completed) + 1, HIST_BUCKETS), np.int64)
        want[-1] = -1
        cells = np.fromiter(ref.cells.keys(), np.int64, len(ref.cells))
        want[cells // HIST_BUCKETS, cells % HIST_BUCKETS] = np.fromiter(
            ref.cells.values(), np.int64, len(ref.cells))
        place = np.fromiter((ref.completed.get(n, -1)
                             for n in self.state_names), np.int64,
                            len(self.state_names))
        wrong = int((self.state_hist != want[place]).any(axis=1).sum()) \
            + len(ref.completed) - int((place >= 0).sum())   # + never read
        demoted = sum(n in self.demoted_names for n in self.state_names)
        promoted = int(self.moved[self.state_ranks].sum())
        print(f"tiered cell: {len(self.state_names)} names compared, "
              f"{demoted} of them demoted at least once, {promoted} "
              f"admitted on more than one row (promoted)", file=sys.stderr)
        checks["state_wrong"] = (wrong, 0)
        # a run that migrated nothing it compared proves nothing
        checks["unmigrated"] = (int(demoted == 0) + int(promoted == 0), 0)
        return checks

    def control(self) -> Checks:
        """The same run with tiering off: eviction forgets what a name
        owned, as before PR 15. Verdicts hold (no ruled name is ever
        evicted); ``state_wrong`` must not."""
        os.environ[DISABLE] = "1"
        twin = type(self)(self.ctx)
        try:
            twin.set_up()
            twin.run_window(Tracer(None, 0.0, 0.0))
            twin.release()
            return twin.check()
        finally:
            del os.environ[DISABLE]
            twin.close()


BUILDERS = {"embedded_engine_tiered": TieredEngineCell}
