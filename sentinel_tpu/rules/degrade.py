"""Circuit breakers: vectorized DegradeSlot.

Reference (``sentinel-core/.../slots/block/degrade/``):

* ``DegradeSlot`` — entry: every breaker for the resource must ``tryPass``;
  exit: if the entry wasn't blocked, ``onRequestComplete`` feeds each breaker.
* ``AbstractCircuitBreaker`` — CLOSED/OPEN/HALF_OPEN CAS state machine; OPEN
  → HALF_OPEN probe after ``timeWindow`` s (one winner passes); probe failure
  re-opens, success closes.
* ``ResponseTimeCircuitBreaker`` — slow-ratio over a single-bucket LeapArray
  of ``statIntervalMs`` (``new LeapArray<SlowRequestCounter>(1, intervalMs)``);
  trips when ``slow/total > slowRatioThreshold`` and ``total >=
  minRequestAmount``. ``count`` is the max allowed RT.
* ``ExceptionCircuitBreaker`` — ERROR_RATIO / ERROR_COUNT over the same
  single-bucket window shape.

TPU-native shape: one struct-of-arrays breaker state; the per-rule
"single-bucket LeapArray" is a (stamp, slow, total) triple with per-rule
window length — lazy reset by window-index comparison, wraparound-safe int32
rel-ms. Probe admission in a batch picks the segment-first event (the CAS
winner analog).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sentinel_tpu.ops import segments as seg

# Grades (reference RuleConstant.DEGRADE_GRADE_*)
GRADE_RT = 0
GRADE_EXCEPTION_RATIO = 1
GRADE_EXCEPTION_COUNT = 2

STATE_CLOSED = 0
STATE_OPEN = 1
STATE_HALF_OPEN = 2


@dataclasses.dataclass
class DegradeRule:
    """Host-facing rule (reference ``DegradeRule.java`` field parity)."""

    resource: str
    grade: int
    count: float                 # RT: max allowed rt ms; RATIO: [0,1]; COUNT: n
    time_window: int             # seconds to stay OPEN
    min_request_amount: int = 5
    stat_interval_ms: int = 1000
    slow_ratio_threshold: float = 1.0

    def is_valid(self) -> bool:
        if not self.resource or self.count < 0 or self.time_window <= 0:
            return False
        if self.grade not in (GRADE_RT, GRADE_EXCEPTION_RATIO, GRADE_EXCEPTION_COUNT):
            return False
        if self.grade == GRADE_EXCEPTION_RATIO and self.count > 1.0:
            return False
        if self.min_request_amount <= 0 or self.stat_interval_ms <= 0:
            return False
        if self.grade == GRADE_RT and not (0.0 <= self.slow_ratio_threshold <= 1.0):
            return False
        return True


class DegradeRuleTable(NamedTuple):
    """Static device arrays, ND+1 rows (sentinel last)."""

    active: jnp.ndarray              # bool
    grade: jnp.ndarray               # int32
    count: jnp.ndarray               # float32
    retry_timeout_ms: jnp.ndarray    # int32 (time_window * 1000)
    min_request: jnp.ndarray         # int32
    interval_ms: jnp.ndarray         # int32
    ratio_threshold: jnp.ndarray     # float32 (slow ratio or error ratio or count)
    # the ratio threshold as num/den where it IS a fraction of small whole
    # numbers (0.6 = 3/5, 0.5 = 1/2: what rules are written with), else
    # den 0. ``bad/total > threshold`` is then ``bad*den > num*total`` in
    # integers: the original compares doubles, float32 rounds 3/5 and 0.6
    # apart on a device whose division is not correctly rounded, and a
    # sick dependency sits exactly on its threshold often
    ratio_num: jnp.ndarray           # int32
    ratio_den: jnp.ndarray           # int32


class BreakerState(NamedTuple):
    """Mutable device state."""

    state: jnp.ndarray               # int32[ND+1] STATE_*
    next_retry_ms: jnp.ndarray       # int32[ND+1] rel-ms
    win_stamp: jnp.ndarray           # int32[ND+1] window index of the bucket
    bad: jnp.ndarray                 # int32[ND+1] slow or error count
    total: jnp.ndarray               # int32[ND+1] completed count


class CompiledDegradeRules(NamedTuple):
    table: DegradeRuleTable
    rule_idx: jnp.ndarray            # int32[R, Kd]
    rules: Tuple[DegradeRule, ...]
    num_active: int
    k_used: int = 1                  # max rules on any one resource
    # the numpy original of rule_idx, kept so the runtime's ruleset
    # assembly (used-slot slicing + joint-gather concat) runs host-side
    # — two fewer programs to compile or load per process
    rule_idx_np: Optional["np.ndarray"] = None


#: largest denominator a threshold is taken as a fraction with, and the
#: counts up to which the integer products then stay inside int32
_FRACTION_DEN_MAX = 1000
_FRACTION_COUNT_MAX = (2 ** 31 - 1) // _FRACTION_DEN_MAX


def _threshold_of(r: DegradeRule) -> float:
    return r.slow_ratio_threshold if r.grade == GRADE_RT else r.count


@functools.lru_cache(maxsize=None)
def _as_small_fraction(x: float) -> Tuple[int, int]:
    """(num, den) with ``num / den == x`` as doubles and ``den`` at most
    ``_FRACTION_DEN_MAX``, or (0, 0) where ``x`` is no such fraction."""
    from fractions import Fraction
    f = Fraction(x).limit_denominator(_FRACTION_DEN_MAX)
    if 0.0 <= x <= 1.0 and f.numerator / f.denominator == x:
        return f.numerator, f.denominator
    return 0, 0


def init_breaker_state(nd: int) -> BreakerState:
    return BreakerState(
        state=jnp.zeros((nd + 1,), jnp.int32),
        next_retry_ms=jnp.full((nd + 1,), -(2 ** 30), jnp.int32),
        win_stamp=jnp.full((nd + 1,), -(2 ** 30), jnp.int32),
        bad=jnp.zeros((nd + 1,), jnp.int32),
        total=jnp.zeros((nd + 1,), jnp.int32),
    )


def compile_degrade_rules(rules: Sequence[DegradeRule], *, resource_registry,
                          capacity: int, k_per_resource: int,
                          num_rows: int) -> CompiledDegradeRules:
    valid = [r for r in rules if r.is_valid()]
    if len(valid) > capacity:
        raise ValueError(f"too many degrade rules: {len(valid)} > {capacity}")
    nd = capacity
    active = np.zeros(nd + 1, np.bool_)
    grade = np.zeros(nd + 1, np.int32)
    count = np.zeros(nd + 1, np.float32)
    retry = np.full(nd + 1, 1, np.int32)
    minreq = np.full(nd + 1, 1, np.int32)
    interval = np.full(nd + 1, 1000, np.int32)
    ratio = np.zeros(nd + 1, np.float32)
    ratio_num = np.zeros(nd + 1, np.int32)
    ratio_den = np.zeros(nd + 1, np.int32)
    rule_idx = np.full((num_rows, k_per_resource), nd, np.int32)
    slots_used = {}
    for j, r in enumerate(valid):
        row = resource_registry.pin(r.resource)
        k = slots_used.get(row, 0)
        if k >= k_per_resource:
            raise ValueError(
                f"more than {k_per_resource} degrade rules for {r.resource!r}")
        slots_used[row] = k + 1
        rule_idx[row, k] = j
        active[j] = True
        grade[j] = r.grade
        count[j] = r.count
        retry[j] = r.time_window * 1000
        minreq[j] = r.min_request_amount
        interval[j] = r.stat_interval_ms
        if r.grade == GRADE_RT:
            ratio[j] = r.slow_ratio_threshold
        elif r.grade == GRADE_EXCEPTION_RATIO:
            ratio[j] = r.count
        else:
            ratio[j] = r.count  # absolute error count
        if r.grade != GRADE_EXCEPTION_COUNT:
            ratio_num[j], ratio_den[j] = _as_small_fraction(_threshold_of(r))
    table = DegradeRuleTable(
        active=jnp.asarray(active), grade=jnp.asarray(grade),
        count=jnp.asarray(count), retry_timeout_ms=jnp.asarray(retry),
        min_request=jnp.asarray(minreq), interval_ms=jnp.asarray(interval),
        ratio_threshold=jnp.asarray(ratio),
        ratio_num=jnp.asarray(ratio_num), ratio_den=jnp.asarray(ratio_den),
    )
    return CompiledDegradeRules(table=table, rule_idx=jnp.asarray(rule_idx),
                                rules=tuple(valid), num_active=len(valid),
                                k_used=max(1, max(slots_used.values(),
                                                  default=0)),
                                rule_idx_np=rule_idx)


def degrade_entry_check(
    table: DegradeRuleTable, st: BreakerState, rule_idx: jnp.ndarray,
    rows: jnp.ndarray, valid: jnp.ndarray, rel_now_ms: jnp.ndarray,
) -> Tuple[BreakerState, jnp.ndarray]:
    """→ (state', allow bool[B]).

    CLOSED passes; OPEN passes one probe per rule once the retry window
    elapsed (transitioning to HALF_OPEN); HALF_OPEN blocks (the in-flight
    probe owns it). Mirrors ``AbstractCircuitBreaker.tryPass`` +
    ``fromOpenToHalfOpen`` with segment-first as the CAS winner.
    """
    B = rows.shape[0]
    Kd = rule_idx.shape[1]
    ND = table.active.shape[0] - 1
    R = rule_idx.shape[0]

    safe_rows = jnp.minimum(rows, R - 1)
    rules_bk = jnp.where((rows < R)[:, None], rule_idx[safe_rows], ND)
    rj = rules_bk.reshape(-1)
    valid_bk = jnp.repeat(valid, Kd) & table.active[rj]
    rj_seg = jnp.where(valid_bk, rj, ND)

    order = seg.sort_by_keys(rj_seg)
    rj_s = rj_seg[order]
    starts = seg.segment_starts(rj_s, jnp.zeros_like(rj_s))

    # one packed gather for both breaker-state columns (separate
    # 1M-element gathers each cost about what the packed one does)
    gs = jnp.stack([st.state, st.next_retry_ms], axis=1)[rj_s]
    state_s = gs[:, 0]
    retry_due = (rel_now_ms - gs[:, 1]) >= 0
    open_probe = (state_s == STATE_OPEN) & retry_due & starts
    pass_s = (state_s == STATE_CLOSED) | open_probe | (rj_s == ND)

    pair_pass = seg.unsort(order, pass_s.astype(jnp.int32)).astype(jnp.bool_)
    allow = jnp.all(pair_pass.reshape(B, Kd), axis=1)

    # OPEN→HALF_OPEN only for rules whose probe event is actually admitted by
    # ALL breakers of its resource. Transitioning unconditionally would strand
    # a rule in HALF_OPEN with no in-flight probe to resolve it when a sibling
    # breaker blocks the event (reference parity: fromOpenToHalfOpen reverts
    # via entry.whenTerminate when the entry is blocked downstream).
    event_of_s = order // Kd  # sorted position → originating event index
    probe_event_ok = allow[event_of_s]
    probe_rules = jnp.where(open_probe & probe_event_ok, rj_s, ND)
    new_state = st.state.at[probe_rules].set(STATE_HALF_OPEN, mode="drop")
    new_state = new_state.at[ND].set(STATE_CLOSED)  # keep sentinel inert
    st = st._replace(state=new_state)

    return st, allow | ~valid


def _open_due(table: DegradeRuleTable, st: BreakerState,
              rel_now_ms: jnp.ndarray) -> jnp.ndarray:
    """bool[ND+1]: the rules that are OPEN with their retry due."""
    return ((st.state == STATE_OPEN)
            & ((rel_now_ms - st.next_retry_ms) >= 0)
            & table.active)


def degrade_gate(
    table: DegradeRuleTable, st: BreakerState, rules_bk: jnp.ndarray,
    rel_now_ms: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """What the degrade slot WILL say to the events of each resource, read
    before the flow slot decides → (code int32[B, Kd], closed bool[B],
    probe bool[B]).

    In sequence (``FlowSlot`` → ``DegradeSlot`` → ``StatisticSlot``) an
    event the breaker refuses is never counted as a pass, so it spends
    nothing of a count-based flow budget. The flow check needs to know
    that while it ranks a resource's events, and it can: a breaker belongs
    to one resource, so every event of a resource meets the same
    breakers. ``closed``: all of them let everything through — the flow
    rank is the arrival rank. ``probe``: each is CLOSED or OPEN with its
    retry due, at least one the latter — the first event the flow slot
    admits is the probe and passes, nothing after it does. Neither:
    nothing passes. ``code`` is the ONE per-pair gather the entry check
    needs too (bit 0 the rule passes everything, bit 1 its probe is due);
    hand it to :func:`degrade_entry_check_scalar`."""
    ND = table.active.shape[0] - 1
    open_due = _open_due(table, st, rel_now_ms)
    # an INACTIVE rule is structurally CLOSED (trip and probe both require
    # active); the sentinel never blocks
    pass_rule = ((st.state == STATE_CLOSED) | ~table.active).at[ND].set(True)
    code = (pass_rule.astype(jnp.int32)
            | (open_due.astype(jnp.int32) << 1))[rules_bk]
    closed = jnp.all((code & 1) != 0, axis=1)
    probe = jnp.all(code != 0, axis=1) & ~closed
    return code, closed, probe


def degrade_entry_check_scalar(
    table: DegradeRuleTable, st: BreakerState, rule_idx: jnp.ndarray,
    rows: jnp.ndarray, valid: jnp.ndarray, rel_now_ms: jnp.ndarray,
    rules_bk: Optional[jnp.ndarray] = None,   # pre-gathered [B, Kd] rule
    # ids (the pipeline's joint flow+degrade gather); None = gather here
    gate_code: Optional[jnp.ndarray] = None,  # degrade_gate's code for
    # the same rules_bk and state; None = gather here
) -> Tuple[BreakerState, jnp.ndarray]:
    """Sort-free :func:`degrade_entry_check` → (state', allow bool[B]).

    Breaker state is per-RULE, so the only cross-event computation is the
    probe election (one winner per OPEN rule whose retry window elapsed —
    the CAS-winner analog). The common all-CLOSED case is one packed
    per-rule lookup gathered per pair; probe election runs under a
    ``lax.cond`` (a batch only pays the scatter-min when some rule is
    actually OPEN with its retry due). Bit-exact with the sorted path:
    the scatter-min winner is the first valid pair in batch order, which
    is what sort stability picked. Reference:
    ``AbstractCircuitBreaker.tryPass`` + ``fromOpenToHalfOpen``.
    """
    B = rows.shape[0]
    Kd = rule_idx.shape[1]
    ND = table.active.shape[0] - 1
    BK = B * Kd

    if rules_bk is None:
        rules_bk = seg.padded_table_gather(rule_idx, rows, ND)
    if gate_code is None:
        gate_code, _, _ = degrade_gate(table, st, rules_bk, rel_now_ms)
    rj = rules_bk.reshape(-1)
    code = gate_code.reshape(-1)
    # only event VALIDITY must exclude pairs from probe election: an
    # invalid event's pairs pass and share the sentinel's key
    valid_bk = jnp.repeat(valid, Kd)
    key = jnp.where(valid_bk, rj, ND)

    open_due = _open_due(table, st, rel_now_ms)
    # the base verdict is needed by BOTH cond branches: hoisting it keeps
    # the common no-probe branch a pure pass-through. (Measured: running
    # the election UNCONDITIONALLY costs ~6 ms/step more than this cond —
    # the [B]→[ND] scatter-min is the expensive part, not the branch.)
    pair_base = ((code & 1) != 0) | ~valid_bk

    def _no_probe(_):
        return st.state, jnp.all(pair_base.reshape(B, Kd), axis=1)

    def _probe(_):
        with jax.named_scope("decide.degrade.probe"):
            idx = jnp.arange(BK, dtype=jnp.int32)
            win = seg.first_index_by_key(key, ND + 1)
            winner_pair = (idx == win[key]) & ((code & 2) != 0) & valid_bk
            pair_pass = pair_base | winner_pair
            allow_ev = jnp.all(pair_pass.reshape(B, Kd), axis=1)
            # OPEN→HALF_OPEN only when the probe's event is admitted by
            # ALL breakers of its resource (general-path comment at
            # degrade_entry_check for why)
            winner_ev = jnp.minimum(win // Kd, B - 1)
            ok = open_due & (win < BK) & allow_ev[winner_ev]
            new_state = jnp.where(ok, STATE_HALF_OPEN, st.state)
            return new_state, allow_ev

    new_state, allow_ev = jax.lax.cond(
        jnp.any(open_due), _probe, _no_probe, None)
    st = st._replace(state=new_state.at[ND].set(STATE_CLOSED))
    return st, allow_ev | ~valid


def degrade_exit_feed(
    table: DegradeRuleTable, st: BreakerState, rule_idx: jnp.ndarray,
    rows: jnp.ndarray, rt_ms: jnp.ndarray, error: jnp.ndarray,
    valid: jnp.ndarray, rel_now_ms: jnp.ndarray,
) -> BreakerState:
    """Completion feed (``DegradeSlot.exit`` → ``onRequestComplete``).

    Records (total, slow-or-error) into each rule's single bucket with lazy
    per-rule window reset, resolves HALF_OPEN probes, and trips CLOSED
    breakers whose window crossed the threshold.
    """
    Kd = rule_idx.shape[1]
    ND = table.active.shape[0] - 1
    R = rule_idx.shape[0]

    safe_rows = jnp.minimum(rows, R - 1)
    rules_bk = jnp.where((rows < R)[:, None], rule_idx[safe_rows], ND)
    rj = rules_bk.reshape(-1)
    valid_bk = jnp.repeat(valid, Kd) & table.active[rj] & (rj != ND)
    rj_safe = jnp.where(valid_bk, rj, ND)

    rt_bk = jnp.repeat(rt_ms, Kd)
    err_bk = jnp.repeat(error, Kd)
    is_rt = table.grade[rj_safe] == GRADE_RT
    bad_bk = jnp.where(is_rt, rt_bk.astype(jnp.float32) > table.count[rj_safe],
                       err_bk).astype(jnp.int32)

    # --- HALF_OPEN probe resolution (before window bookkeeping) ---
    # Sort-free: the probe outcome is per-RULE (the first valid completion
    # in batch order resolves it), so a scatter-min elects the winner pair
    # and everything else is [ND]-sized — and the whole election runs under
    # a lax.cond so batches with no in-flight probe (the common case) pay
    # nothing. Winner order parity: flattened [B, Kd] index order is batch
    # order, exactly what the old stable sort's segment-first picked.
    BK = rj_safe.shape[0]

    def _no_resolve(_):
        return st.state, st.next_retry_ms, st.win_stamp

    def _resolve(_):
        win = seg.first_index_by_key(rj_safe, ND + 1)
        half = (st.state == STATE_HALF_OPEN) & (win < BK)
        winner_bad = bad_bk[jnp.minimum(win, BK - 1)]
        ok_r = half & (winner_bad == 0)
        fail_r = half & (winner_bad != 0)
        state = jnp.where(ok_r, STATE_CLOSED,
                          jnp.where(fail_r, STATE_OPEN, st.state))
        next_retry = jnp.where(fail_r, rel_now_ms + table.retry_timeout_ms,
                               st.next_retry_ms)
        # closing resets the stat window (reference resetStat on close)
        win_stamp = jnp.where(ok_r, -(2 ** 30), st.win_stamp)
        return state, next_retry, win_stamp

    state, next_retry, win_stamp = jax.lax.cond(
        jnp.any(st.state == STATE_HALF_OPEN), _resolve, _no_resolve, None)
    state = state.at[ND].set(STATE_CLOSED)
    st = st._replace(state=state, next_retry_ms=next_retry.astype(jnp.int32),
                     win_stamp=win_stamp)

    # --- single-bucket lazy reset + scatter-add ---
    widx = rel_now_ms // jnp.maximum(table.interval_ms[rj_safe], 1)   # [BK]
    keep = (st.win_stamp[rj_safe] == widx).astype(jnp.int32)
    bad0 = st.bad.at[rj_safe].multiply(keep, mode="drop")
    total0 = st.total.at[rj_safe].multiply(keep, mode="drop")
    stamp = st.win_stamp.at[rj_safe].set(widx, mode="drop")
    ones = valid_bk.astype(jnp.int32)
    bad1 = bad0.at[rj_safe].add(bad_bk * ones, mode="drop")
    total1 = total0.at[rj_safe].add(ones, mode="drop")
    st = st._replace(bad=bad1, total=total1, win_stamp=stamp)

    # --- trip CLOSED breakers (vector over rules) ---
    grade = table.grade
    totals = st.total.astype(jnp.float32)
    bads = st.bad.astype(jnp.float32)
    enough = st.total >= table.min_request
    ratio = bads / jnp.maximum(totals, 1.0)
    exact = (table.ratio_den > 0) & (st.total <= _FRACTION_COUNT_MAX)
    over = jnp.where(exact,
                     st.bad * table.ratio_den > table.ratio_num * st.total,
                     ratio > table.ratio_threshold)
    trip_ratio = enough & over
    # RT grade: reference also trips when ratio threshold >= 1 means never
    trip_count = bads >= table.ratio_threshold
    trip = jnp.where(grade == GRADE_EXCEPTION_COUNT, enough & trip_count, trip_ratio)
    trip = trip & (st.state == STATE_CLOSED) & table.active
    state = jnp.where(trip, STATE_OPEN, st.state)
    next_retry = jnp.where(trip, rel_now_ms + table.retry_timeout_ms, st.next_retry_ms)
    return st._replace(state=state, next_retry_ms=next_retry.astype(jnp.int32))
