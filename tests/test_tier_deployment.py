"""A service whose names outnumber its rows, end to end on the normal
path: 256 rows over 4,096 names, Zipf batches of 64 through
``DispatchPipeline``, every passed entry exited on the rows its ticket
returned. Verdicts and every name's cumulative RT histogram — read by
name, whichever tier holds it — equal the plain reference's, which never
forgets a name; with ``SENTINEL_TIERING_DISABLE=1`` (lossy eviction) the
histograms do not, so the comparison is live."""

import numpy as np
import pytest

import sentinel_tpu as stpu
from chipbench.generators.arrivals import rng_for, zipf_ranks
from chipbench.reference.engine import BreakerRule
from chipbench.reference.tiered import HIST_BUCKETS, TieredReference, rt_bucket
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.rules.degrade import GRADE_EXCEPTION_RATIO

ROWS, NAMES, BATCH, STEPS = 256, 4096, 64, 90
FLOW = {f"r{i}": 6 for i in range(8)}
# a flow rule and an open breaker never meet on one name at this size
# (tests/chipbench/conftest.py says why)
BREAKERS = {f"b{i}": BreakerRule(0.5, 2000) for i in range(4)}
UNIVERSE = np.array(list(FLOW) + list(BREAKERS)
                    + [f"k{i}" for i in range(NAMES - 12)], object)


def _engine(monkeypatch, registry, disable=False):
    monkeypatch.setenv("SENTINEL_TPU_NATIVE",
                       "1" if registry == "native" else "0")
    if disable:
        monkeypatch.setenv("SENTINEL_TIERING_DISABLE", "1")
    clk = ManualClock(start_ms=1_785_000_000_000)
    sph = stpu.Sentinel(stpu.load_config(
        max_resources=ROWS, max_flow_rules=16, max_degrade_rules=16,
        max_authority_rules=16, host_fast_path=False), clock=clk)
    if registry == "native":
        assert type(sph.resources).__name__ == "NativeRegistry"
    sph.load_flow_rules([stpu.FlowRule(resource=n, count=float(c))
                         for n, c in FLOW.items()])
    sph.load_degrade_rules([stpu.DegradeRule(
        resource=n, grade=GRADE_EXCEPTION_RATIO, count=r.ratio,
        time_window=r.retry_ms // 1000) for n, r in BREAKERS.items()])
    return sph, clk


def _drive(sph, clk, seed=7):
    """The closed loop of a batch-tier caller: submit batch k, take batch
    k-1's verdicts, exit what of k-1 passed on the rows ITS ticket
    returned. → (verdict mismatches, the reference, the tickets' rows by
    batch, the names by batch)."""
    ref = TieredReference(FLOW, BREAKERS, sph.epoch_ms)
    pipe = stpu.DispatchPipeline(sph)
    rng = rng_for(seed, 9)
    ranks = zipf_ranks(rng_for(seed, 2), STEPS * BATCH, 1.1, NAMES)
    pad = np.full(BATCH, sph.spec.alt_rows, np.int32)
    wrong, rows_by_batch, names_by_batch = 0, [], []
    prev = None

    def settle(ticket, names, want):
        nonlocal wrong
        v = ticket.result()
        allow = np.asarray(v.allow)
        got = np.where(allow, 0, np.asarray(v.reason))
        wrong += int((got != np.asarray(want)).sum())
        passed = np.nonzero(allow)[0]
        n = passed.size
        rt = np.maximum(1, rng.lognormal(np.log(5.0), 1.0, n)).astype(np.int32)
        err = rng.random(n) < 0.4
        sph.exit_batch(rows=ticket.rows[passed], origin_rows=pad[:n],
                       chain_rows=pad[:n], acquire=np.ones(n, np.int32),
                       rt_ms=rt, error=err, is_in=np.ones(n, bool))
        ref.completions([names[i] for i in passed], rt.tolist(),
                        err.tolist(), clk.now_ms())

    for k in range(STEPS):
        names = UNIVERSE[ranks[k * BATCH:(k + 1) * BATCH]].tolist()
        ticket = pipe.submit(names)
        want = ref.entries(names, clk.now_ms())
        assert ticket.rows.shape == (BATCH,) and ticket.rows.dtype == np.int32
        rows_by_batch.append(np.array(ticket.rows))
        names_by_batch.append(names)
        if prev is not None:
            settle(*prev)
        prev = (ticket, names, want)
        clk.advance_ms(70)
    settle(*prev)
    return wrong, ref, rows_by_batch, names_by_batch


def _state_wrong(sph, ref):
    names = list(ref.completed)
    got = sph.rt_hist_by_name(names)
    want = np.array([ref.histogram(n) for n in names])
    return int((got != want).any(axis=1).sum()), len(names)


@pytest.mark.parametrize("registry", ["native", "python"])
def test_verdicts_and_every_names_histogram_survive_the_churn(
        monkeypatch, registry):
    sph, clk = _engine(monkeypatch, registry)
    try:
        donated = []
        restore = sph._jit_restore

        def spy(state, *rest):
            out = restore(state, *rest)
            donated.append(state.second.counters.is_deleted())
            return out
        sph._jit_restore = spy
        wrong, ref, rows_by, names_by = _drive(sph, clk)
        assert wrong == 0
        assert ref.trips > 0                # the breakers did trip
        snap = sph.tiering.snapshot()
        assert snap["demoted"] > 0 and snap["promoted"] > 0
        # the restore rewrites the state in place: what it was given is gone
        assert donated and all(donated)
        state_wrong, compared = _state_wrong(sph, ref)
        assert compared > ROWS              # more names than the table holds
        assert state_wrong == 0
        # a name's row does change under the caller: only the ticket knows
        seen, moved = {}, 0
        for rows, names in zip(rows_by, names_by):
            for name, row in zip(names, rows.tolist()):
                moved += seen.get(name, row) != row
                seen[name] = row
        assert moved > 0
        c = sph.obs.counters
        assert c.get(ck.TIER_FIRST_SIGHT) >= len(seen) - ROWS
        assert (c.get(ck.TIER_HOT_HIT) + c.get(ck.TIER_COLD_MISS)
                <= c.get(ck.INTERN_NAMES))
    finally:
        sph.close()


@pytest.mark.parametrize("registry", ["native", "python"])
def test_without_tiering_the_state_comparison_fails(monkeypatch, registry):
    """The control: lossy eviction forgets what an evicted name owned."""
    sph, clk = _engine(monkeypatch, registry, disable=True)
    try:
        assert not sph.tiering.enabled
        wrong, ref, *_ = _drive(sph, clk)
        assert wrong == 0       # no ruled name is ever evicted: verdicts hold
        state_wrong, _ = _state_wrong(sph, ref)
        assert state_wrong > 0
    finally:
        sph.close()


def test_a_cold_names_by_name_reads_answer(monkeypatch):
    sph, clk = _engine(monkeypatch, "python")
    try:
        pad = np.full(3, sph.spec.alt_rows, np.int32)
        h = sph.entry_batch_nowait(["gone", "gone", "gone"])
        assert np.asarray(h.result().allow).all()
        sph.exit_batch(rows=h.rows, origin_rows=pad, chain_rows=pad,
                       acquire=np.ones(3, np.int32),
                       rt_ms=np.array([1, 3, 900], np.int32),
                       error=np.array([False, True, False]),
                       is_in=np.ones(3, bool))
        want = np.zeros(HIST_BUCKETS, np.int32)
        for rt in (1, 3, 900):
            want[rt_bucket(rt)] += 1
        assert want[0] == 1 and want[2] == 1 and want[10] == 1
        hot = sph.node_totals("gone")
        assert hot["pass"] == 3 and hot["success"] == 3 \
            and hot["exception"] == 1
        assert (sph.rt_hist_by_name(["gone"])[0] == want).all()
        assert sph.resources.evict_name("gone")
        sph.entry_batch(["other"])              # the drain demotes it
        assert sph.resources.lookup("gone") is None
        # in flight or landed, the cold tier answers for it
        assert (sph.rt_hist_by_name(["gone", "never-seen"])
                == np.stack([want, np.zeros_like(want)])).all()
        assert "gone" in sph.tiering.cold
        cold = sph.node_totals("gone")
        assert cold == hot
        clk.advance_ms(5000)                    # its second window runs out
        assert sph.node_totals("gone")["pass"] == 0
        assert sph.node_totals("never-seen") == {}
        # and reading moved nothing: it promotes with all it owned
        sph.entry_batch(["gone"])
        assert sph.resources.lookup("gone") is not None
        assert (sph.rt_hist_by_name(["gone"])[0] == want).all()
    finally:
        sph.close()


def test_the_migration_programs_compile_before_traffic(monkeypatch):
    """``warm_migration`` leaves nothing for a drain of those sizes to
    compile, and changes nothing."""
    from chipbench.compile_meter import CompileMeter
    sph, clk = _engine(monkeypatch, "python")
    try:
        names = [f"w{i}" for i in range(ROWS)]
        sph.entry_batch(names[:12])             # the decide at 16 lanes
        sph.entry_batch(names[:5])              # and at 8
        before = sph.rt_hist_by_name(names[:2]).copy()
        totals = sph.node_totals("w0")
        sph.tiering.warm_migration([8, 9])      # 8 and 16 rows
        assert sph.node_totals("w0") == totals and totals["pass"] == 2
        assert (sph.rt_hist_by_name(names[:2]) == before).all()
        free = ROWS - len(sph.resources)
        sph.intern_resources(names[12:12 + free])       # the table is full
        assert len(sph.resources) == ROWS
        meter = CompileMeter()
        sph.entry_batch([f"new{i}" for i in range(12)])  # 12 evictions -> 16
        sph.entry_batch(names[:5])       # 5 come back -> 8, and 5 more leave
        snap = sph.tiering.snapshot()
        assert snap["demoted"] == 17 and snap["promoted"] == 5
        assert meter.programs == 0
    finally:
        sph.close()
