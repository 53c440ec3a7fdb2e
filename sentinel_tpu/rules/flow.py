"""Flow rules: vectorized FlowSlot / FlowRuleChecker / traffic-shaping controllers.

Reference semantics being reproduced (all paths under
``sentinel-core/.../slots/block/flow/``):

* ``FlowRuleChecker.checkFlow:44-80`` — every rule configured for the resource
  must pass; rules not applicable to the event's origin pass trivially (null
  node selection).
* ``FlowRuleChecker.selectNodeByRequesterAndStrategy:129-161`` — the *stat row*
  a rule reads is a function of (limitApp, strategy): global resource row,
  per-origin row, related resource's row, or per-context (CHAIN) row.
* ``DefaultController.canPass:50-76`` — reject when
  ``current + prefix + acquire > count`` (QPS grade reads rolling-second pass;
  THREAD grade reads live concurrency).
* ``RateLimiterController:30-90`` — leaky-bucket pacing on a per-rule
  ``latestPassedTime``; wait ≤ maxQueueingTimeMs else block.
* ``WarmUpController:66-190`` — Guava-style token ramp: warningToken /
  maxToken / slope; above the warning line the admitted QPS shrinks to
  ``1/(aboveToken·slope + 1/count)``; tokens refill once per second using the
  previous second's pass count.

TPU-native shape: rules compile (host-side numpy, at rule-load time — the
analog of ``FlowRuleUtil.buildFlowRuleMap``) into a struct-of-arrays
``FlowRuleTable`` plus a per-resource gather table ``rule_idx[R, K]``; the
check is one jitted function over (batch × K) rule applications using the
segment machinery in ``ops/segments.py`` for exact greedy FIFO admission
within the batch. Divergence from the reference is *bounded batching skew*
only, licensed by the reference's own tolerated check-then-act races
(``FlowRuleChecker.java:89``, ``DefaultController.java:87``).

Blocking behaviors return ``wait_ms`` verdicts instead of sleeping the caller
(the reference's cluster protocol already works this way — ``TokenResult
.waitInMs`` — generalized here to local mode; the host SDK sleeps).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from sentinel_tpu.ops import segments as seg
from sentinel_tpu.ops import sortfree as sfo
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    WindowSpec, WindowState, prev_window_sum_rows, window_sum_all,
    window_sum_rows,
)

# Grades (reference RuleConstant.FLOW_GRADE_*)
GRADE_THREAD = 0
GRADE_QPS = 1
# Strategies (RuleConstant.STRATEGY_*)
STRATEGY_DIRECT = 0
STRATEGY_RELATE = 1
STRATEGY_CHAIN = 2
# Control behaviors (RuleConstant.CONTROL_BEHAVIOR_*)
BEHAVIOR_DEFAULT = 0
BEHAVIOR_WARM_UP = 1
BEHAVIOR_RATE_LIMITER = 2
BEHAVIOR_WARM_UP_RATE_LIMITER = 3

# limit_origin sentinel codes (limitApp strings "default"/"other")
LIMIT_DEFAULT = -1
LIMIT_OTHER = -2

# Stat-row selection kinds (compiled from limitApp × strategy)
SEL_MAIN = 0    # resource's global row            (default + DIRECT)
SEL_ORIGIN = 1  # event's per-origin row           (specific origin / other)
SEL_REF = 2     # related resource's global row    (RELATE)
SEL_CHAIN = 3   # event's per-context row          (CHAIN, context == refResource)


@dataclasses.dataclass
class FlowRule:
    """Host-facing rule object (reference ``FlowRule.java`` field parity)."""

    resource: str
    count: float
    grade: int = GRADE_QPS
    limit_app: str = "default"
    strategy: int = STRATEGY_DIRECT
    ref_resource: str = ""
    control_behavior: int = BEHAVIOR_DEFAULT
    warm_up_period_sec: int = 10
    max_queueing_time_ms: int = 500
    cluster_mode: bool = False
    cluster_flow_id: int = 0
    cluster_threshold_type: int = 0      # 0 AVG_LOCAL, 1 GLOBAL
    cluster_fallback_to_local: bool = True

    def is_valid(self) -> bool:
        if not self.resource or self.count < 0:
            return False
        if self.grade not in (GRADE_THREAD, GRADE_QPS):
            return False
        if self.strategy in (STRATEGY_RELATE, STRATEGY_CHAIN) and not self.ref_resource:
            return False
        if self.control_behavior == BEHAVIOR_WARM_UP and self.warm_up_period_sec <= 0:
            return False
        return True


class FlowRuleTable(NamedTuple):
    """Static (per rule-load) device arrays, NF+1 rows; last row = inactive
    sentinel so padded gathers are harmless."""

    active: jnp.ndarray          # bool[NF+1]
    grade: jnp.ndarray           # int32
    count: jnp.ndarray           # float32
    behavior: jnp.ndarray        # int32
    sel_kind: jnp.ndarray        # int32 (SEL_*)
    ref_row: jnp.ndarray         # int32 — main-table row for SEL_REF
    ref_context: jnp.ndarray     # int32 — required context id for SEL_CHAIN
    limit_origin: jnp.ndarray    # int32 — LIMIT_DEFAULT/LIMIT_OTHER/origin id
    max_queue_ms: jnp.ndarray    # int32
    # warm-up precomputed constants (WarmUpController ctor math)
    warning_token: jnp.ndarray   # float32
    max_token: jnp.ndarray       # float32
    slope: jnp.ndarray           # float32
    sync_row: jnp.ndarray        # int32 — main-table row used for token sync
    cluster_mode: jnp.ndarray    # bool
    # What the original computes in doubles, computed in float64 at rule
    # load (the device has float32): the warm-up refill threshold
    # ``(int)count / coldFactor`` (an INTEGER division there), the pacing
    # cost of one token ``Math.round(1.0 / count * 1000)``, and per
    # warm-up rule one row of ``wu_tab`` for each value ``aboveToken`` can
    # take (``storedTokens`` is a long, so 0..maxToken-warningToken):
    # column 0 ``floor(nextUp(1 / (aboveToken*slope + 1/count)))`` — the
    # most ``passQps + acquire`` may be — and column 1 the pacing cost of
    # one token at that rate. Rules with the same (count, period) share
    # their rows; ``wu_tab`` is one dummy row while no warm-up rule is
    # loaded, and the lookups compile away.
    refill_below: jnp.ndarray    # float32
    rl_cost1: jnp.ndarray        # int32
    wu_off: jnp.ndarray          # int32 — first row of the rule's levels
    wu_levels: jnp.ndarray       # int32 — maxToken - warningToken
    wu_tab: jnp.ndarray          # int32[T, 2]


class FlowDynState(NamedTuple):
    """Per-rule mutable shaping state (device)."""

    latest_passed_ms: jnp.ndarray   # int32[NF+1] — rel-ms pacing clock
    stored_tokens: jnp.ndarray      # float32[NF+1]
    last_filled_sec: jnp.ndarray    # int32[NF+1] — rel seconds
    # occupy ("borrow-from-future", OccupiableBucketLeapArray rebuilt as
    # virtual bookings keyed by RESOURCE ROW — shared by every rule on the
    # node like the reference's future buckets): slot s holds tokens booked
    # for window occupied_window[r, s]; a booking keeps counting toward the
    # rolling admission sum for B windows after it lands. A booking made at
    # W targets W+1 and stays live through W+B, so B+1 consecutive windows
    # can hold live bookings — the slot ring has B+1 slots (window mod B+1)
    # so a new booking never clobbers a live one.
    occupied_count: jnp.ndarray     # float32[R, B+1]
    occupied_window: jnp.ndarray    # int32[R, B+1]


class CompiledFlowRules(NamedTuple):
    """Host-side compile output."""

    table: FlowRuleTable
    rule_idx: jnp.ndarray           # int32[R, K] → table row, NF = none
    rules: Tuple[FlowRule, ...]     # original objects, index-aligned with table
    num_active: int
    k_used: int = 1                 # max rules on any ONE resource (the
    # rule-gather width the device steps actually need — rule_idx slots
    # are front-packed, so slicing [:, :k_used] loses nothing)
    # numpy original of rule_idx: the runtime's ruleset assembly (slice +
    # joint concat) runs host-side — fewer programs to compile or load per
    # process (cold-start story)
    rule_idx_np: Optional[np.ndarray] = None


def init_flow_dyn(nf: int, buckets: int = 2, rows: int = 1) -> FlowDynState:
    return FlowDynState(
        latest_passed_ms=jnp.full((nf + 1,), -(2 ** 30), jnp.int32),
        stored_tokens=jnp.zeros((nf + 1,), jnp.float32),
        last_filled_sec=jnp.full((nf + 1,), -(2 ** 30), jnp.int32),
        occupied_count=jnp.zeros((rows, buckets + 1), jnp.float32),
        occupied_window=jnp.full((rows, buckets + 1), -(2 ** 30),
                                 jnp.int32),
    )


#: rows ``wu_tab`` may have (8 bytes each): the sum over the DISTINCT
#: (count, period) pairs of warm-up rules of maxToken - warningToken + 1;
#: past it the load fails loudly, as past ``capacity``
_WU_TAB_MAX = 1 << 24
_I32_MAX = 2 ** 31 - 1


def _java_round(x: float) -> int:
    """``Math.round(double)`` — floor(x + 0.5) — held to int32."""
    return int(min(max(np.floor(x + 0.5), 0.0), _I32_MAX))


def _warmup_levels(count: float, slope: float, levels: int) -> np.ndarray:
    """int32[levels + 1, 2]: for aboveToken = 0..levels, what
    ``WarmUpController.canPass`` / ``WarmUpRateLimiterController.canPass``
    compute from it in doubles — ``warningQps = Math.nextUp(1.0 /
    (aboveToken * slope + 1.0 / count))`` as the most ``passQps +
    acquireCount`` may be, and ``Math.round(1.0 / warningQps * 1000)``."""
    above = np.arange(levels + 1, dtype=np.float64)
    count = np.float64(count)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        qps = np.nextafter(1.0 / (above * slope + 1.0 / count), np.inf)
        cost = np.floor(1.0 / qps * 1000.0 + 0.5)
    out = np.empty((levels + 1, 2), np.int32)
    out[:, 0] = np.clip(np.nan_to_num(np.floor(qps), nan=0.0), 0, _I32_MAX)
    out[:, 1] = np.clip(np.nan_to_num(cost, nan=_I32_MAX), 0, _I32_MAX)
    return out


def compile_flow_rules(rules: Sequence[FlowRule], *, resource_registry,
                       context_registry, capacity: int, k_per_resource: int,
                       num_rows: int, cold_factor: float = 3.0,
                       origin_registry=None) -> CompiledFlowRules:
    """Validate + vectorize rules (the ``FlowRuleUtil`` analog).

    Origin-specific ``limit_app`` strings are interned through
    ``origin_registry`` (pinned so ids stay stable while referenced).
    Resources named by rules are pinned in the resource registry.
    Invalid rules are skipped (reference logs and skips); rules beyond
    ``capacity`` or more than ``k_per_resource`` per resource raise — unlike
    the reference's silent 6000-chain cap, overflow here is loud.
    """
    valid = [r for r in rules if r.is_valid()]
    if len(valid) > capacity:
        raise ValueError(f"too many flow rules: {len(valid)} > capacity {capacity}")

    nf = capacity
    active = np.zeros(nf + 1, np.bool_)
    grade = np.zeros(nf + 1, np.int32)
    count = np.zeros(nf + 1, np.float32)
    behavior = np.zeros(nf + 1, np.int32)
    sel_kind = np.zeros(nf + 1, np.int32)
    ref_row = np.zeros(nf + 1, np.int32)
    ref_context = np.full(nf + 1, -1, np.int32)
    limit_origin = np.full(nf + 1, LIMIT_DEFAULT, np.int32)
    max_queue_ms = np.zeros(nf + 1, np.int32)
    warning_token = np.zeros(nf + 1, np.float32)
    max_token = np.zeros(nf + 1, np.float32)
    slope = np.zeros(nf + 1, np.float32)
    sync_row = np.full(nf + 1, num_rows, np.int32)
    cluster_mode = np.zeros(nf + 1, np.bool_)
    refill_below = np.zeros(nf + 1, np.float32)
    rl_cost1 = np.zeros(nf + 1, np.int32)
    wu_off = np.zeros(nf + 1, np.int32)
    wu_levels = np.zeros(nf + 1, np.int32)
    wu_rows: List[np.ndarray] = [np.zeros((1, 2), np.int32)]
    wu_seen = {}
    wu_total = 1
    cold_i = max(int(cold_factor), 2)    # SentinelConfig: an int above 1

    rule_idx = np.full((num_rows, k_per_resource), nf, np.int32)
    slots_used = {}

    for j, r in enumerate(valid):
        row = resource_registry.pin(r.resource)
        k = slots_used.get(row, 0)
        if k >= k_per_resource:
            raise ValueError(
                f"more than {k_per_resource} flow rules for resource {r.resource!r}; "
                f"raise max_rules_per_resource")
        slots_used[row] = k + 1
        rule_idx[row, k] = j

        active[j] = True
        grade[j] = r.grade
        count[j] = r.count
        behavior[j] = r.control_behavior
        max_queue_ms[j] = r.max_queueing_time_ms
        cluster_mode[j] = r.cluster_mode
        sync_row[j] = row

        la = r.limit_app or "default"
        if la == "default":
            limit_origin[j] = LIMIT_DEFAULT
        elif la == "other":
            limit_origin[j] = LIMIT_OTHER
        else:
            if origin_registry is None:
                raise ValueError("origin-specific rule needs an origin registry")
            limit_origin[j] = origin_registry.pin(la)

        if r.strategy == STRATEGY_RELATE:
            sel_kind[j] = SEL_REF
            ref_row[j] = resource_registry.pin(r.ref_resource)
            sync_row[j] = ref_row[j]
        elif r.strategy == STRATEGY_CHAIN:
            sel_kind[j] = SEL_CHAIN
            ref_context[j] = context_registry.pin(r.ref_resource)
        elif la in ("default",):
            sel_kind[j] = SEL_MAIN
        else:
            # specific origin or "other" + DIRECT → the event's origin row
            # (FlowRuleChecker.java:137-141,154-158)
            sel_kind[j] = SEL_ORIGIN

        if r.count > 0:
            rl_cost1[j] = _java_round(1.0 / r.count * 1000.0)
        if r.control_behavior in (BEHAVIOR_WARM_UP, BEHAVIOR_WARM_UP_RATE_LIMITER):
            # WarmUpController.java:66-90 constructor math: warningToken
            # and maxToken are ints, coldFactor is an int, and the first
            # division is an integer division
            wt = int(r.warm_up_period_sec * r.count) // (cold_i - 1)
            mt = wt + int(2.0 * r.warm_up_period_sec * r.count
                          / (1.0 + cold_i))
            warning_token[j] = wt
            max_token[j] = mt
            slope64 = (cold_i - 1.0) / max(r.count, 1e-300) / max(mt - wt, 1e-9)
            slope[j] = slope64
            refill_below[j] = int(r.count) // cold_i
            wu_levels[j] = mt - wt
            shape = (float(r.count), mt - wt)
            if shape not in wu_seen:
                wu_seen[shape] = wu_total
                wu_rows.append(_warmup_levels(float(r.count), slope64,
                                              mt - wt))
                wu_total += mt - wt + 1
                if wu_total > _WU_TAB_MAX:
                    raise ValueError(
                        f"warm-up rules need {wu_total} token levels in "
                        f"all, more than {_WU_TAB_MAX}: count x "
                        f"warm_up_period_sec of {r.resource!r} is too "
                        f"large")
            wu_off[j] = wu_seen[shape]

    table = FlowRuleTable(
        active=jnp.asarray(active), grade=jnp.asarray(grade),
        count=jnp.asarray(count), behavior=jnp.asarray(behavior),
        sel_kind=jnp.asarray(sel_kind), ref_row=jnp.asarray(ref_row),
        ref_context=jnp.asarray(ref_context),
        limit_origin=jnp.asarray(limit_origin),
        max_queue_ms=jnp.asarray(max_queue_ms),
        warning_token=jnp.asarray(warning_token),
        max_token=jnp.asarray(max_token), slope=jnp.asarray(slope),
        sync_row=jnp.asarray(sync_row),
        cluster_mode=jnp.asarray(cluster_mode),
        refill_below=jnp.asarray(refill_below),
        rl_cost1=jnp.asarray(rl_cost1), wu_off=jnp.asarray(wu_off),
        wu_levels=jnp.asarray(wu_levels),
        wu_tab=jnp.asarray(np.concatenate(wu_rows)),
    )
    return CompiledFlowRules(table=table, rule_idx=jnp.asarray(rule_idx),
                             rules=tuple(valid), num_active=len(valid),
                             k_used=max(1, max(slots_used.values(),
                                               default=0)),
                             rule_idx_np=rule_idx)


# ---------------------------------------------------------------------------
# Device-side check
# ---------------------------------------------------------------------------

class FlowBatchView(NamedTuple):
    """Pre-gathered per-event inputs the flow check needs (built by the
    engine so gathers are shared across slots)."""

    rows: jnp.ndarray          # int32[B] main row, >= R padding
    origin_ids: jnp.ndarray    # int32[B]
    origin_rows: jnp.ndarray   # int32[B] alt-table row, >= RA when absent
    context_ids: jnp.ndarray   # int32[B]
    chain_rows: jnp.ndarray    # int32[B] alt-table row, >= RA when absent
    acquire: jnp.ndarray       # int32[B]
    valid: jnp.ndarray         # bool[B]
    prioritized: jnp.ndarray   # bool[B] — entryWithPriority (occupy eligible)
    cluster_fallback: jnp.ndarray  # int32[B] — bit k: check slot-k cluster rule locally


def flow_check(
    table: FlowRuleTable,
    dyn: FlowDynState,
    rule_idx: jnp.ndarray,
    spec: WindowSpec,
    main_second: WindowState,
    alt_second: WindowState,
    main_threads: jnp.ndarray,
    alt_threads: jnp.ndarray,
    batch: FlowBatchView,
    now_idx_s: jnp.ndarray,      # int32 scalar, second-window index
    rel_now_ms: jnp.ndarray,     # int32 scalar, ms since process epoch
    minute_spec: Optional[WindowSpec] = None,
    main_minute: Optional[WindowState] = None,
    now_idx_m: Optional[jnp.ndarray] = None,
    in_win_ms: Optional[jnp.ndarray] = None,   # int32 scalar, now % win_ms
    occupy_timeout_ms: int = 500,
    enable_occupy: bool = True,                # STATIC: trade a second jit
    # variant for zero occupy cost on batches with no prioritized events
    has_thread_rules: bool = True,             # STATIC: False = no loaded
    # rule reads live concurrency → the [BK] thread-gauge gathers compile
    # away (the gauges themselves may be unmaintained then; see
    # pipeline.decide_entries skip_threads)
    sortfree: bool = False,                    # STATIC: group segments via
    # the hash-bucketed claim cascade + counting-sort permutation
    # (ops/sortfree.py) instead of the n·log n composite-key sort; on
    # claim overflow a lax.cond takes the sorted reference branch, so
    # results are bit-identical either way (the runtime's
    # SENTINEL_SORTFREE routing flips this; flow_check_sortfree also
    # surfaces the overflow count)
    gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (closed,
    # probe) bool[B] of rules/degrade.degrade_gate: what DegradeSlot will
    # say to each event's resource, so that an event it refuses spends
    # nothing of a count-based budget (see _spent_rank)
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """→ (dyn', allow bool[B], wait_ms int32[B], occupied bool[B]).

    ``allow[i]`` False means blocked by some flow rule. ``wait_ms`` > 0 with
    ``allow`` True = rate-limiter pass-after-wait (host SDK sleeps).
    ``occupied[i]`` True = prioritized event admitted by borrowing from the
    NEXT window (``tryOccupyNext`` → ``PriorityWaitException``): the caller
    sleeps ``wait_ms`` and the pass is accounted to the future window — the
    recorder must log OCCUPIED_PASS, not PASS, for these events.
    """
    dyn, allow, wait_ms, occupied, _ = _flow_check_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, in_win_ms, occupy_timeout_ms, enable_occupy,
        has_thread_rules, sortfree, gate)
    return dyn, allow, wait_ms, occupied


def flow_check_sortfree(
    table: FlowRuleTable,
    dyn: FlowDynState,
    rule_idx: jnp.ndarray,
    spec: WindowSpec,
    main_second: WindowState,
    alt_second: WindowState,
    main_threads: jnp.ndarray,
    alt_threads: jnp.ndarray,
    batch: FlowBatchView,
    now_idx_s: jnp.ndarray,
    rel_now_ms: jnp.ndarray,
    minute_spec: Optional[WindowSpec] = None,
    main_minute: Optional[WindowState] = None,
    now_idx_m: Optional[jnp.ndarray] = None,
    in_win_ms: Optional[jnp.ndarray] = None,
    occupy_timeout_ms: int = 500,
    enable_occupy: bool = True,
    has_thread_rules: bool = True,
    gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`flow_check` with ``sortfree=True``, additionally returning
    the claim-cascade overflow count (int32 scalar — elements that fell
    back to the sorted branch this step; feeds the
    ``sortfree.bucket_overflow`` counter)."""
    return _flow_check_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, in_win_ms, occupy_timeout_ms, enable_occupy,
        has_thread_rules, True, gate)


def _flow_check_impl(
    table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
    alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
    now_idx_m, in_win_ms, occupy_timeout_ms, enable_occupy,
    has_thread_rules, sortfree, gate=None,
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B = batch.rows.shape[0]
    K = rule_idx.shape[1]
    NF = table.active.shape[0] - 1
    R = rule_idx.shape[0]
    RA = alt_threads.shape[0]

    safe_rows = jnp.minimum(batch.rows, R - 1)
    rules_bk = jnp.where((batch.rows < R)[:, None], rule_idx[safe_rows], NF)  # [B,K]
    rj = rules_bk.reshape(-1)                                                # [BK]

    # ONE packed [NF+1, 9] gather per index set instead of a 1M-element
    # gather per column — eight separate gathers cost about eight packed
    # ones; the stack itself is a trivial
    # [NF, 9] op re-done per step
    pk = jnp.stack([
        table.active.astype(jnp.int32),        # 0
        table.limit_origin,                    # 1
        table.cluster_mode.astype(jnp.int32),  # 2
        table.sel_kind,                        # 3
        table.ref_context,                     # 4
        table.ref_row,                         # 5
        table.behavior,                        # 6
        table.grade,                           # 7
        table.max_queue_ms,                    # 8
    ], axis=1)
    g = pk[rj]                                 # [BK, 9]
    act = g[:, 0] != 0

    # --- applicability: limitApp × origin (FlowRuleChecker.checkFlow null-node) ---
    lim = g[:, 1]
    origin_bk = jnp.repeat(batch.origin_ids, K)
    ctx_bk = jnp.repeat(batch.context_ids, K)
    # "other": origin matches no specific-origin rule of this resource
    specific_hit = jnp.any(
        (lim.reshape(B, K) == batch.origin_ids[:, None])
        & act.reshape(B, K), axis=1)                                         # [B]
    specific_hit_bk = jnp.repeat(specific_hit, K)
    app_default = lim == LIMIT_DEFAULT
    app_specific = lim == origin_bk
    app_other = (lim == LIMIT_OTHER) & (~specific_hit_bk) & (origin_bk != 0)
    applicable = act & (app_default | app_specific | app_other)
    # cluster-mode rules are enforced by the token server, not locally —
    # EXCEPT the specific rules whose token request failed with
    # fallbackToLocal: bit k of the per-event mask re-enables slot k
    # (per-rule FlowRuleChecker.passClusterCheck / fallbackToLocalOrPass)
    slot_bk = jnp.tile(jnp.arange(K, dtype=jnp.int32), B)
    fb_bk = (jnp.repeat(batch.cluster_fallback, K) >> slot_bk) & 1
    applicable = applicable & ((g[:, 2] == 0) | (fb_bk == 1))
    # CHAIN additionally requires the event's context to match refResource
    kind = g[:, 3]
    applicable = applicable & jnp.where(
        kind == SEL_CHAIN, ctx_bk == g[:, 4], True)

    # --- stat-row selection ---
    rows_bk = jnp.repeat(batch.rows, K)
    orow_bk = jnp.repeat(batch.origin_rows, K)
    crow_bk = jnp.repeat(batch.chain_rows, K)
    use_alt = (kind == SEL_ORIGIN) | (kind == SEL_CHAIN)
    sel_main_row = jnp.where(kind == SEL_REF, g[:, 5], rows_bk)
    sel_alt_row = jnp.where(kind == SEL_CHAIN, crow_bk, orow_bk)
    # events whose alt row is absent (no origin / no chain stats): rule passes
    applicable = applicable & jnp.where(use_alt, sel_alt_row < RA, True)

    # --- current counts for the selected rows ---
    main_pass = window_sum_rows(spec, main_second, jnp.minimum(sel_main_row, R - 1),
                                ev.PASS, now_idx_s).astype(jnp.float32)
    alt_pass = window_sum_rows(spec, alt_second, jnp.minimum(sel_alt_row, RA - 1),
                               ev.PASS, now_idx_s).astype(jnp.float32)
    cur_pass = jnp.where(use_alt, alt_pass, main_pass)
    if has_thread_rules:
        main_thr = main_threads[jnp.minimum(sel_main_row, R - 1)].astype(
            jnp.float32)
        alt_thr = alt_threads[jnp.minimum(sel_alt_row, RA - 1)].astype(
            jnp.float32)
        cur_thr = jnp.where(use_alt, alt_thr, main_thr)
    else:
        cur_thr = jnp.zeros_like(cur_pass)   # no THREAD-grade rule reads it

    # --- greedy segment admission ---
    acq_bk = jnp.repeat(batch.acquire, K).astype(jnp.float32)
    valid_bk = jnp.repeat(batch.valid, K) & applicable

    # --- warm-up token sync (vector over rules; a rule syncs when a pair
    # it applies to arrives) ---
    dyn, shaping = _warmup_sync_and_limits(
        table, dyn, spec, main_second, now_idx_s, rel_now_ms,
        minute_spec, main_minute, now_idx_m,
        lambda: _rules_reached(rj, valid_bk, NF))
    eff_limit = shaping.limit[rj]                                            # [BK]
    # inapplicable pairs get the sentinel rule NF so they share one segment
    # that never blocks; their acquire contributes nothing.
    rj_seg = jnp.where(valid_bk, rj, NF)
    # Pacing state is PER RULE (one latestPassedTime per RateLimiterController
    # instance), so rate-limiter pairs collapse to one segment per rule; other
    # behaviors segment by (rule, selected stat row). behavior/grade come
    # from the rj packed gather: invalid pairs (rj_seg == NF) may read a
    # real rule's values here, but their row_seg is overridden to 0 below
    # either way, so segmentation is unaffected.
    behavior_bk = g[:, 6]
    is_rl_bk = ((behavior_bk == BEHAVIOR_RATE_LIMITER)
                | (behavior_bk == BEHAVIOR_WARM_UP_RATE_LIMITER)) & (
        g[:, 7] == GRADE_QPS)
    row_seg = jnp.where(use_alt, sel_alt_row + R, sel_main_row)  # disjoint key space
    row_seg = jnp.where(is_rl_bk, 0, row_seg)
    row_seg = jnp.where(valid_bk, row_seg, 0)
    if sortfree:
        # Sort-free grouping: the claim cascade + counting sort yields a
        # STABLE key-grouping permutation; everything downstream (starts,
        # prefix sums, greedy admission, RL fixed point, occupy fold,
        # unsorts) is permutation-invariant across segments and
        # stability-preserving within them, so the admitted bits match
        # the sorted branch exactly (parity argument: ops/sortfree.py).
        # Claim overflow takes the sorted branch via lax.cond — graceful
        # fallback, never a wrong answer.
        plan = sfo.build_pair_plan(rj_seg, row_seg, rj_seg == NF,
                                   sfo.table_bits(B * K))
        order = lax.cond(
            plan.overflow,
            lambda _: seg.sort_by_keys(rj_seg, row_seg),
            lambda _: sfo.counting_order(plan.bucket, plan.num_buckets),
            None)
        sf_overflow = plan.overflow_count
    else:
        order = seg.sort_by_keys(rj_seg, row_seg)
        sf_overflow = jnp.int32(0)
    rj_s = rj_seg[order]
    row_s = row_seg[order]
    acq_s = jnp.where(valid_bk, acq_bk, 0.0)[order]
    starts = seg.segment_starts(rj_s, row_s)
    leader = seg.segment_leader_index(starts)

    # --- occupy bookings (virtual OccupiableBucketLeapArray) ---
    # bookings are keyed by resource ROW (shared by all rules on the node,
    # like the reference's future buckets). Landed bookings (window already
    # reached) count toward the rolling admission sum for B windows,
    # exactly as seeded borrowed PASS would. STATIC skip: the host tracks
    # whether any booking can still be live and compiles this away
    # otherwise (the gathers + extra scatter cost ~40% of the hot step).
    occ_cnt = dyn.occupied_count             # [R, S]
    occ_win = dyn.occupied_window            # [R, S]
    g_s = pk[rj_s]                           # [BK, 9] one sorted-side gather
    grade_s = g_s[:, 7]
    if enable_occupy:
        safe_main_occ = jnp.minimum(sel_main_row, R - 1)
        occ_age_bk = now_idx_s - occ_win[safe_main_occ]      # [BK, S]
        occ_cnt_bk = occ_cnt[safe_main_occ]                  # [BK, S]
        landed_bk = jnp.sum(
            jnp.where((occ_age_bk >= 0) & (occ_age_bk < spec.buckets),
                      occ_cnt_bk, 0.0), axis=1)
        # bookings still live in the NEXT window (pending or recently
        # landed) — budget already spoken for when occupying more
        nextw_bk = jnp.sum(
            jnp.where((occ_age_bk >= -1) & (occ_age_bk < spec.buckets - 1),
                      occ_cnt_bk, 0.0), axis=1)
        # only main-row selections see bookings (occupy is main-row-only)
        no_book = use_alt | (sel_main_row >= R)
        landed_bk = jnp.where(no_book, 0.0, landed_bk)
        nextw_bk = jnp.where(no_book, 0.0, nextw_bk)
        base_s = jnp.where(grade_s == GRADE_QPS,
                           cur_pass[order] + landed_bk[order],
                           cur_thr[order])
    else:
        base_s = jnp.where(grade_s == GRADE_QPS, cur_pass[order],
                           cur_thr[order])
    limit_s = eff_limit[order]
    behavior_s = g_s[:, 6]

    pass_default_s = seg.greedy_admit(base_s, acq_s, limit_s, starts, leader)
    if gate is not None:
        # an event DegradeSlot refuses is never counted as a pass
        # (_spent_rank, for amounts that differ): where nothing will pass
        # each pair is checked against the window alone; where a probe is
        # due the first pair the window admits passes, and only its
        # amount stands against the pairs after it
        closed, probe = gate
        closed_s = jnp.repeat(closed, K)[order]
        probe_s = jnp.repeat(probe, K)[order]
        alone_s = base_s + acq_s <= limit_s
        earlier, _ = seg.segment_prefix_sum(
            alone_s.astype(jnp.int32), starts, leader)
        spent, _ = seg.segment_prefix_sum(
            jnp.where(alone_s & (earlier == 0), acq_s, 0.0), starts, leader)
        pass_default_s = jnp.where(
            closed_s, pass_default_s,
            jnp.where(probe_s, base_s + spent + acq_s <= limit_s, alone_s))

    # --- rate limiter (paced queue) ---
    # Shaped behaviors apply only to QPS-grade rules (FlowRuleUtil
    # .generateRater falls back to DefaultController for THREAD grade).
    # cost per element in ms: Math.round(acquire / qps * 1000)
    raw_count_s = table.count[rj_s]
    cost_s = _pacing_cost(acq_s.astype(jnp.int32), shaping.cost1[rj_s],
                          shaping.rate[rj_s])
    c_first = seg.segment_broadcast_first(cost_s, leader)
    L0 = dyn.latest_passed_ms[rj_s]
    due = (L0 + c_first - rel_now_ms) <= 0
    base_time = jnp.where(due, rel_now_ms - c_first, L0)
    is_rl = ((behavior_s == BEHAVIOR_RATE_LIMITER)
             | (behavior_s == BEHAVIOR_WARM_UP_RATE_LIMITER)) & (grade_s == GRADE_QPS)
    # a rejected request never advances the pacing clock (its CAS fails in
    # the reference), so its cost must not delay later in-batch requests:
    # fixed-point — exclusive prefix over admitted costs + own cost always
    pass_rl_s = jnp.ones_like(starts)
    maxq_s = g_s[:, 8]
    for _ in range(3):
        excl_cost, _ = seg.segment_prefix_sum(
            jnp.where(pass_rl_s, cost_s, 0), starts, leader)
        latest_s = base_time + excl_cost + cost_s
        wait_s = jnp.maximum(latest_s - rel_now_ms, 0)
        pass_rl_s = wait_s <= maxq_s
        # zero-count rate limiter blocks everything (count<=0 → block)
        pass_rl_s = pass_rl_s & (raw_count_s > 0)

    # --- occupy attempt (tryOccupyNext, DefaultController prioritized path) ---
    # A denied prioritized request may pre-book the NEXT window when the pass
    # count surviving into it (current bucket + live bookings) leaves room
    # under the threshold, and the wait fits OccupyTimeout (default 500 ms).
    inapplicable_s = rj_s == NF
    if enable_occupy and in_win_ms is not None and occupy_timeout_ms > 0:
        wait_next = (jnp.int32(spec.win_ms) - in_win_ms).astype(jnp.int32)

        def _occupy_attempt(_):
            can_time = wait_next <= occupy_timeout_ms
            # passes that SURVIVE into window now+1: every bucket whose
            # stamp is within the last B-1 windows (0 <= now-stamp <= B-2)
            # — the oldest live bucket expires at the edge, the rest carry
            safe_main = jnp.minimum(sel_main_row, R - 1)
            srow_stamps = main_second.stamps[safe_main]        # [BK, B]
            sdelta = now_idx_s - srow_stamps
            survive_mask = (sdelta >= 0) & (sdelta <= spec.buckets - 2)
            surviving_bk = jnp.sum(
                jnp.where(survive_mask,
                          main_second.counters[safe_main, :, ev.PASS], 0),
                axis=1).astype(jnp.float32)
            prio_s = jnp.repeat(batch.prioritized, K)[order]
            eligible_s = (prio_s & (grade_s == GRADE_QPS)
                          & (behavior_s == BEHAVIOR_DEFAULT)
                          & ~pass_default_s & ~inapplicable_s
                          & ~use_alt[order] & can_time)
            occ_base_s = surviving_bk[order] + nextw_bk[order]
            occ_amt_s = jnp.where(eligible_s, acq_s, 0.0)
            occ_adm = seg.greedy_admit(occ_base_s, occ_amt_s, limit_s,
                                       starts, leader) & eligible_s

            # event-level gate BEFORE committing bookings: a booking is
            # only real if the whole event is admitted by the flow slot —
            # every failing pair of the event must itself be
            # occupy-admitted (PriorityWaitException is the admission)
            pair_ok_tmp = jnp.where(is_rl, pass_rl_s,
                                    pass_default_s | occ_adm) | inapplicable_s
            occ_adm_pairs = seg.unsort(
                order, occ_adm.astype(jnp.int32)).astype(jnp.bool_)
            pair_ok_pairs = seg.unsort(
                order, pair_ok_tmp.astype(jnp.int32)).astype(jnp.bool_)
            event_ok = jnp.all(pair_ok_pairs.reshape(B, K), axis=1)  # [B]
            event_occ = (jnp.any(occ_adm_pairs.reshape(B, K), axis=1)
                         & event_ok & batch.valid)                   # [B]

            # book ONE grant per admitted event on its resource row (the
            # reference's first denying rule throws PriorityWait and books
            # on the node once), slot ring keyed by window now+1
            slots_n = occ_cnt.shape[1]
            slot = (now_idx_s + 1) % slots_n
            grants = jnp.zeros(occ_cnt.shape[0], jnp.float32).at[
                jnp.where(event_occ, batch.rows, occ_cnt.shape[0])].add(
                jnp.where(event_occ, batch.acquire, 0).astype(jnp.float32),
                mode="drop")
            granted_row = grants > 0
            slot_keep = occ_win[:, slot] == now_idx_s + 1
            new_cnt = jnp.where(granted_row,
                                jnp.where(slot_keep, occ_cnt[:, slot], 0.0)
                                + grants,
                                occ_cnt[:, slot])
            new_win = jnp.where(granted_row, now_idx_s + 1,
                                occ_win[:, slot])
            return (occ_cnt.at[:, slot].set(new_cnt),
                    occ_win.at[:, slot].set(new_win),
                    occ_adm & jnp.repeat(event_occ, K)[order])

        def _no_occupy(_):
            return (occ_cnt, occ_win,
                    jnp.zeros_like(pass_default_s).astype(jnp.bool_))

        # real control flow: batches with no prioritized events (the common
        # case, and the whole benchmark) skip the occupy math entirely
        new_occ_cnt, new_occ_win, occ_admit_s = jax.lax.cond(
            jnp.any(batch.prioritized), _occupy_attempt, _no_occupy, None)
        dyn = dyn._replace(occupied_count=new_occ_cnt,
                           occupied_window=new_occ_win)
    else:
        occ_admit_s = jnp.zeros_like(pass_default_s).astype(jnp.bool_)
        wait_next = jnp.int32(0)

    pair_pass_s = jnp.where(is_rl, pass_rl_s, pass_default_s | occ_admit_s)
    pair_pass_s = pair_pass_s | inapplicable_s
    pair_wait_s = jnp.where(is_rl & pair_pass_s & ~inapplicable_s, wait_s, 0)
    pair_wait_s = jnp.maximum(pair_wait_s,
                              jnp.where(occ_admit_s, wait_next, 0))

    # update pacing clocks: last passing element's latest per rule segment
    new_latest = jnp.where(is_rl & pair_pass_s & ~inapplicable_s,
                           latest_s, -(2 ** 30))
    dyn = dyn._replace(latest_passed_ms=dyn.latest_passed_ms.at[
        jnp.where(is_rl & ~inapplicable_s, rj_s, NF)].max(new_latest, mode="drop"))

    # --- combine back to events ---
    pair_pass = seg.unsort(order, pair_pass_s.astype(jnp.int32)).astype(jnp.bool_)
    pair_wait = seg.unsort(order, pair_wait_s.astype(jnp.int32))
    pair_occ = seg.unsort(order, occ_admit_s.astype(jnp.int32)).astype(jnp.bool_)
    allow = jnp.all(pair_pass.reshape(B, K), axis=1)
    wait_ms = jnp.max(pair_wait.reshape(B, K), axis=1)
    occupied = jnp.any(pair_occ.reshape(B, K), axis=1) & allow & batch.valid
    allow = allow | ~batch.valid
    return dyn, allow, wait_ms.astype(jnp.int32), occupied, sf_overflow


def flow_check_scalar(
    table: FlowRuleTable,
    dyn: FlowDynState,
    rule_idx: jnp.ndarray,
    spec: WindowSpec,
    main_second: WindowState,
    main_threads: jnp.ndarray,
    rows: jnp.ndarray,           # int32[B] (>= R padding)
    acquire: jnp.ndarray,        # int32[B] — HOST-VERIFIED uniform (>= 1)
    valid: jnp.ndarray,          # bool[B]
    now_idx_s: jnp.ndarray,
    rel_now_ms: jnp.ndarray,
    minute_spec: Optional[WindowSpec] = None,
    main_minute: Optional[WindowState] = None,
    now_idx_m: Optional[jnp.ndarray] = None,
    has_rate_limiter: bool = True,    # STATIC: ruleset has RL/WU-RL rules
    # — False elides the RL columns, closed forms, and pair math entirely
    # (NOT just the pacing update): only pass False when the loaded
    # ruleset truly has no RL/WU-RL rules, or they admit as DEFAULT.
    # Safe default True matches flow_check_fast: forgetting the flag
    # costs performance, never correctness.
    rules_bk: Optional[jnp.ndarray] = None,   # pre-gathered [B, K] rule
    # ids (the pipeline's joint flow+degrade gather); None = gather here
    occupy_base: bool = False,        # STATIC: live occupy bookings may
    # exist → fold LANDED bookings into the per-rule QPS admission base
    # (one [NF+1, S] gather — negligible). The batch itself must still
    # carry no prioritized events (this path never books); it only has
    # to SEE bookings committed by prioritized traffic dispatched around
    # it (runtime._decide_split_nowait's scalar side).
    sortfree: bool = False,           # STATIC: compute per-slot arrival
    # ranks by identity-bucketed scatter (ops/sortfree.ranks2d_ident —
    # keys are already dense rule ids, so no hashing and no overflow)
    # instead of the batched stable sort; exact, not probabilistic
    gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (closed,
    # probe) bool[B] of rules/degrade.degrade_gate — see _spent_rank
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray]:
    """Scalar-path flow check → (dyn', allow bool[B], wait_ms int32[B]).

    Bit-exact with :func:`flow_check` under the preconditions the HOST
    must verify before selecting this variant (``runtime.decide_raw``):

    * the batch carries no origin/chain rows and no origins (every
      ``use_alt`` selection in the general path resolves to padding →
      SEL_ORIGIN/SEL_CHAIN rules pass trivially);
    * no prioritized events (live bookings are fine with
      ``occupy_base=True`` — this path reads them, never writes them);
    * no per-event ``cluster_fallback`` bits (cluster rules are simply
      inapplicable locally);
    * ``acquire`` is uniform across valid events with value >= 1.

    Under those conditions every quantity the general path gathers PER
    PAIR — window base, live threads, effective limit, pacing clock, cost,
    behavior, grade — is a function of the RULE alone, so this path
    computes [NF+1]-sized per-rule admission budgets and touches the
    B*K pair axis only for: the rule gather, the arrival-rank computation
    (one stable argsort — :func:`ops.segments.ranks_by_key`), one budget
    gather, and elementwise compares. The general path's greedy fixed
    point collapses to ``rank`` compares (exact for uniform acquire: the
    admitted prefix of a segment is its first ``budget`` elements), and
    the rate limiter collapses to its closed form
    (``latest_k = base_time + k*cost`` is monotone in k, so the passing
    set is a rank prefix — RateLimiterController.java:30-90 semantics).

    Reference parity: DefaultController.canPass:50-76 (QPS + THREAD),
    WarmUpController.java:66-190 (via ``_warmup_sync_and_limits``),
    RateLimiterController.java:30-90, FlowRuleChecker rule-set semantics.
    """
    B = rows.shape[0]
    K = rule_idx.shape[1]
    NF = table.active.shape[0] - 1
    R = rule_idx.shape[0]

    # ---- per-rule admission state ([NF+1]-sized, negligible) ----
    if rules_bk is None:
        rules_bk = seg.padded_table_gather(rule_idx, rows, NF)
    dyn, shaping = _warmup_sync_and_limits(
        table, dyn, spec, main_second, now_idx_s, rel_now_ms,
        minute_spec, main_minute, now_idx_m,
        lambda: _rules_reached(rules_bk, valid[:, None], NF))
    eff_limit = shaping.limit
    sel_row = jnp.minimum(table.sync_row, R - 1)
    base_pass = window_sum_rows(spec, main_second, sel_row, ev.PASS,
                                now_idx_s).astype(jnp.float32)
    if occupy_base:
        # landed bookings count toward the rolling QPS sum exactly as in
        # flow_check; a valid pair's selected row IS its rule's sync_row,
        # so the per-pair landed sum is a per-rule column here (same
        # float operands + association → bit-exact)
        base_pass = base_pass + _landed_per_rule(
            dyn, sel_row, spec, now_idx_s)
    base_thr = main_threads[sel_row].astype(jnp.float32)
    base = jnp.where(table.grade == GRADE_QPS, base_pass, base_thr)

    # rules that can apply to an origin-less, fallback-free batch:
    # default-limitApp, local-mode, MAIN/REF row selection
    applies = (table.active
               & (table.limit_origin == LIMIT_DEFAULT)
               & (~table.cluster_mode)
               & ((table.sel_kind == SEL_MAIN)
                  | (table.sel_kind == SEL_REF)))
    # DEFAULT/WARM_UP: pair with rank r passes iff
    #   (base + r*a) + a <= eff_limit   — same operand association as the
    # general path's `base + excl + amounts <= limit` so the float32
    # rounding is identical (bit-exact while r*a < 2^24, where the general
    # path's cumsum is itself exact)
    acq_of_rule = jnp.float32(0) + jnp.max(
        jnp.where(valid, acquire, 0)).astype(jnp.float32)    # the uniform a
    if has_rate_limiter:
        is_rl = (((table.behavior == BEHAVIOR_RATE_LIMITER)
                  | (table.behavior == BEHAVIOR_WARM_UP_RATE_LIMITER))
                 & (table.grade == GRADE_QPS))
        base_time, cost, max_k = _rl_closed_form(
            table, dyn, shaping, acq_of_rule, rel_now_ms)

    # ---- per-pair work ----
    rj = rules_bk.reshape(-1)                                # [BK]
    valid_bk = jnp.repeat(valid, K)
    # INVALID pairs share the sentinel segment (they must not consume
    # ranks in real groups). INAPPLICABLE RULES need no key remap at all:
    # applicability is per-rule in this path, so an inapplicable rule's
    # group holds only inapplicable pairs — encoding "always passes" in
    # its table row (limit=+inf, is_rl off) is equivalent and saves the
    # applies[rj] gather.
    key = jnp.where(valid_bk, rj, NF)
    # per-slot ranks: slot columns carry disjoint rule sets (see
    # seg.ranks_per_slot; the NF sentinel group's per-slot ranks only
    # feed the npairs lane of the inactive rule)
    if sortfree:
        rank = sfo.ranks2d_ident(key.reshape(B, K), NF + 2).reshape(-1)
    else:
        rank = seg.ranks_per_slot(key.reshape(B, K)).reshape(-1)  # int32[BK]

    a_bk = jnp.repeat(acquire, K).astype(jnp.float32)
    limit_eff = jnp.where(applies, eff_limit, jnp.float32(3e38))
    # ONE packed per-rule verdict gather: int columns plus the float
    # columns bitcast to int32 (exact round-trip). RL math stays int32 —
    # float32 ms arithmetic drifts after ~4.6 h of uptime. The 4 RL
    # columns + their pair math only exist when a rate-limiter rule is
    # loaded (static elision, mirrors flow_check_fast).
    cols = [
        lax.bitcast_convert_type(base, jnp.int32),           # 0
        lax.bitcast_convert_type(limit_eff, jnp.int32),      # 1
    ]
    if has_rate_limiter:
        cols += [(is_rl & applies).astype(jnp.int32),        # 2
                 base_time, cost, max_k]                     # 3, 4, 5
    vt = jnp.stack(cols, axis=1)
    g = vt[key]                                              # [BK, C]
    base_pair = lax.bitcast_convert_type(g[:, 0], jnp.float32)
    limit_pair = lax.bitcast_convert_type(g[:, 1], jnp.float32)
    rankf = _spent_rank(rank.reshape(B, K), gate).reshape(-1).astype(
        jnp.float32)

    pass_default = (base_pair + rankf * a_bk) + a_bk <= limit_pair
    if has_rate_limiter:
        # RL: pass iff rank < max_k (the rank-prefix form of
        # `base_time + (rank+1)*cost - now <= maxQueueing`, exactly the
        # general path's fixed point for uniform cost — overflow-free).
        # wait for PASSING pairs only: (rank+1)*cost is bounded there.
        pass_rl = rank < g[:, 5]
        safe_rank = jnp.minimum(rank, g[:, 5])   # blocked lanes: clamp
        # the product so dead-lane arithmetic can't overflow int32
        wait_pair = jnp.maximum(
            g[:, 3] + (safe_rank + 1) * g[:, 4] - rel_now_ms, 0)
        pair_is_rl = g[:, 2] != 0
        pair_pass = jnp.where(pair_is_rl, pass_rl, pass_default)
        pair_pass = pair_pass | (key == NF)
        pair_wait = jnp.where(pair_is_rl & pair_pass & (key != NF),
                              wait_pair, 0)
        wait_ms = jnp.max(pair_wait.reshape(B, K), axis=1)
    else:
        pair_pass = pass_default | (key == NF)
        wait_ms = jnp.zeros((B,), jnp.int32)

    allow = jnp.all(pair_pass.reshape(B, K), axis=1)

    # ---- pacing-clock update (only when the ruleset has RL rules) ----
    if has_rate_limiter:
        # per-rule pass count = min(#valid pairs, rank budget); the rank
        # array already encodes group sizes (max rank + 1)
        npairs = jnp.zeros((NF + 2,), jnp.int32).at[key].max(
            rank + 1, mode="drop")[:NF + 1]
        passed = jnp.minimum(npairs, max_k)
        passed = jnp.where(is_rl & applies & (table.count > 0), passed, 0)
        new_latest = jnp.where(
            passed > 0,
            (base_time + passed * cost).astype(jnp.int32),
            dyn.latest_passed_ms)
        dyn = dyn._replace(
            latest_passed_ms=jnp.maximum(dyn.latest_passed_ms, new_latest))

    allow = allow | ~valid
    return dyn, allow, wait_ms


def _rules_reached(rules: jnp.ndarray, reached: jnp.ndarray,
                   nf: int) -> jnp.ndarray:
    """bool[NF+1]: the rules some pair of ``rules`` (any shape) names where
    ``reached`` (same shape) holds — for ``_warmup_sync_and_limits``."""
    key = jnp.where(reached, rules, nf).reshape(-1)
    return jnp.zeros((nf + 1,), jnp.bool_).at[key].set(True, mode="drop")


def _spent_rank(rank: jnp.ndarray,
                gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]]
                ) -> jnp.ndarray:
    """How many earlier events of a pair's segment a COUNT-based
    controller (Default, WarmUp, THREAD grade) has counted as passes when
    the pair is checked → int32[B, K], from the arrival rank [B, K].

    ``StatisticSlot`` counts a pass once the whole chain has passed, so
    an event ``DegradeSlot`` refuses spends nothing of the budget. With
    ``gate = (closed, probe)`` from ``rules/degrade.degrade_gate``: where
    every breaker of the event's resource is CLOSED each admitted earlier
    event passed, the rank itself; where a probe is due exactly one event
    passes — the first the flow slot admits — so at most 1; otherwise
    none did, 0. (The first event of a probe resource is checked at 0; if
    it fails so does every later one, which is why ``min(rank, 1)`` needs
    no second pass.) The pacing controllers do not ask: they move
    ``latestPassedTime`` in ``canPass``, before ``DegradeSlot`` is
    reached, and keep the arrival rank."""
    if gate is None:
        return rank
    closed, probe = gate
    return jnp.where(closed[:, None], rank,
                     jnp.where(probe[:, None], jnp.minimum(rank, 1), 0))


def _landed_per_rule(dyn: FlowDynState, sel_row: jnp.ndarray,
                     spec: WindowSpec, now_idx_s: jnp.ndarray) -> jnp.ndarray:
    """LANDED occupy bookings per rule → float32[NF+1]: sum of bookings on
    the rule's selected main row whose target window has been reached and
    is still inside the rolling interval (age in [0, B)). The per-rule
    form of ``flow_check``'s ``landed_bk`` — identical numeric values for
    every valid main-row pair, since such a pair's ``sel_main_row`` equals
    its rule's ``sync_row``."""
    occ_age = now_idx_s - dyn.occupied_window[sel_row]      # [NF+1, S]
    return jnp.sum(
        jnp.where((occ_age >= 0) & (occ_age < spec.buckets),
                  dyn.occupied_count[sel_row], 0.0), axis=1)


def flow_check_fast(
    table: FlowRuleTable,
    dyn: FlowDynState,
    rule_idx: jnp.ndarray,
    spec: WindowSpec,
    main_second: WindowState,
    alt_second: WindowState,
    main_threads: jnp.ndarray,
    alt_threads: jnp.ndarray,
    batch: FlowBatchView,
    now_idx_s: jnp.ndarray,
    rel_now_ms: jnp.ndarray,
    minute_spec: Optional[WindowSpec] = None,
    main_minute: Optional[WindowState] = None,
    now_idx_m: Optional[jnp.ndarray] = None,
    has_rate_limiter: bool = True,    # STATIC: ruleset has RL/WU-RL rules
    has_thread_rules: bool = True,    # STATIC: see flow_check
    rules_bk: Optional[jnp.ndarray] = None,   # [B, K] pre-gathered rule ids
    sortfree: bool = False,           # STATIC: per-slot ranks via the
    # hashed claim cascade (ops/sortfree.ranks2d_hashed) with a lax.cond
    # sorted fallback on claim overflow — bit-exact either way
    gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # see
    # _spent_rank
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray]:
    """Fast GENERAL-path flow check → (dyn', allow bool[B], wait_ms int32[B]).

    The scalar path's rank-prefix admission (:func:`flow_check_scalar`)
    generalized to origin-bearing traffic: per-pair applicability and
    stat-row selection (``FlowRuleChecker.selectNodeByRequesterAndStrategy``,
    FlowRuleChecker.java:129-161) stay fully live, but the sorted
    greedy/fixed-point machinery of :func:`flow_check` collapses to ONE
    composite-key rank sort plus closed forms. Host-verified preconditions
    (``runtime.decide_raw``):

    * ``acquire`` uniform across valid events, value >= 1;
    * no prioritized events and no live occupy bookings (occupy off).

    Origins, alt rows, CHAIN contexts, and per-event cluster-fallback bits
    are all allowed — that is the point.

    Why it is bit-exact with :func:`flow_check` under those preconditions:

    * every admission segment of the general path is keyed by
      (rule, selected stat row); a rule's selected MAIN/REF row is a
      function of the rule alone (a flow rule names one resource), so the
      row sub-key matters only for SEL_ORIGIN/SEL_CHAIN pairs, whose alt
      row is < RA — the composite int32 key
      ``rule * (RA + 1) + (use_alt ? alt_row + 1 : 0)`` reproduces the
      exact segmentation (RL pairs pace per RULE — sub-key 0 — matching
      the general path's ``row_seg = 0`` for rate limiters);
    * within a segment, base and limit are constant and amounts are the
      uniform ``a``, so the greedy fixed point's admitted set is the rank
      prefix ``base + rank*a + a <= limit`` (same operand association as
      the general path's cumsum form — bit-identical while counts stay
      under 2^24, where the cumsum itself is exact);
    * the rate limiter collapses to the same bounded per-rule rank budget
      ``max_k`` as the scalar path (RateLimiterController.java:30-90).
    """
    dyn, allow, wait_ms, _, _ = _flow_check_fast_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, has_rate_limiter, has_thread_rules, rules_bk,
        enable_occupy=False, in_win_ms=None, occupy_timeout_ms=0,
        sortfree=sortfree, gate=gate)
    return dyn, allow, wait_ms


def flow_check_fast_sortfree(
    table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
    alt_threads, batch, now_idx_s, rel_now_ms, minute_spec=None,
    main_minute=None, now_idx_m=None, has_rate_limiter=True,
    has_thread_rules=True, rules_bk=None, gate=None,
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`flow_check_fast` with ``sortfree=True``, additionally
    returning the claim-cascade overflow count (int32 scalar) →
    (dyn', allow, wait_ms, sf_overflow)."""
    dyn, allow, wait_ms, _, sf_overflow = _flow_check_fast_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, has_rate_limiter, has_thread_rules, rules_bk,
        enable_occupy=False, in_win_ms=None, occupy_timeout_ms=0,
        sortfree=True, gate=gate)
    return dyn, allow, wait_ms, sf_overflow


def flow_check_fast_occupy(
    table: FlowRuleTable,
    dyn: FlowDynState,
    rule_idx: jnp.ndarray,
    spec: WindowSpec,
    main_second: WindowState,
    alt_second: WindowState,
    main_threads: jnp.ndarray,
    alt_threads: jnp.ndarray,
    batch: FlowBatchView,
    now_idx_s: jnp.ndarray,
    rel_now_ms: jnp.ndarray,
    minute_spec: Optional[WindowSpec] = None,
    main_minute: Optional[WindowState] = None,
    now_idx_m: Optional[jnp.ndarray] = None,
    in_win_ms: Optional[jnp.ndarray] = None,
    occupy_timeout_ms: int = 500,
    has_rate_limiter: bool = True,    # STATIC: see flow_check_fast
    has_thread_rules: bool = True,    # STATIC: see flow_check
    rules_bk: Optional[jnp.ndarray] = None,   # [B, K] pre-gathered rule ids
    sortfree: bool = False,           # STATIC: see flow_check_fast
    gate: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # see
    # _spent_rank
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Occupy-capable fast general path → (dyn', allow, wait_ms, occupied).

    :func:`flow_check_fast` plus the PRIORITIZED admission path
    (``DefaultController.canPass`` prioritized=true → ``tryOccupyNext``,
    DefaultController.java:77-97) — no composite-key sort, no greedy fixed
    point. Same host-verified preconditions as the plain fast path
    (uniform acquire >= 1, key fits int32); prioritized events and live
    bookings are allowed — that is the point.

    Why it stays bit-exact with :func:`flow_check` (enable_occupy=True):

    * LANDED bookings fold into the admission base per RULE: occupy is
      main-row-only and a valid main-row pair's ``sel_main_row`` is its
      rule's ``sync_row``, so ``landed_bk`` is a [NF+1] column riding the
      packed verdict gather (alt-row pairs never see bookings in either
      path);
    * the occupy attempt's ``greedy_admit`` runs over the same segments
      with amounts only on ELIGIBLE pairs — with uniform acquire its
      fixed point is the rank prefix AMONG ELIGIBLE PAIRS, so one extra
      per-slot rank pass over an eligibility-masked key reproduces it:
      admitted iff ``(surviving + next_window + rank_elig*a) + a <=
      limit`` (same operand association as the cumsum form);
    * the event-level gate (every failing pair must itself be
      occupy-admitted) and the one-booking-per-event scatter commit are
      the general path's own event-indexed code, verbatim — they never
      needed the sort.

    The attempt (ranks + booking scatter) runs under
    ``lax.cond(any(prioritized))``: a batch routed here only because
    bookings were still live pays one [NF+1, S] fold and nothing else.
    """
    assert in_win_ms is not None, \
        "flow_check_fast_occupy needs in_win_ms (occupy wait math)"
    dyn, allow, wait_ms, occupied, _ = _flow_check_fast_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, has_rate_limiter, has_thread_rules, rules_bk,
        enable_occupy=True, in_win_ms=in_win_ms,
        occupy_timeout_ms=occupy_timeout_ms, sortfree=sortfree, gate=gate)
    return dyn, allow, wait_ms, occupied


def flow_check_fast_occupy_sortfree(
    table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
    alt_threads, batch, now_idx_s, rel_now_ms, minute_spec=None,
    main_minute=None, now_idx_m=None, in_win_ms=None, occupy_timeout_ms=500,
    has_rate_limiter=True, has_thread_rules=True, rules_bk=None, gate=None,
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`flow_check_fast_occupy` with ``sortfree=True``, additionally
    returning the claim-cascade overflow count (int32 scalar) →
    (dyn', allow, wait_ms, occupied, sf_overflow)."""
    assert in_win_ms is not None, \
        "flow_check_fast_occupy_sortfree needs in_win_ms (occupy wait math)"
    return _flow_check_fast_impl(
        table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
        alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
        now_idx_m, has_rate_limiter, has_thread_rules, rules_bk,
        enable_occupy=True, in_win_ms=in_win_ms,
        occupy_timeout_ms=occupy_timeout_ms, sortfree=True, gate=gate)


def _flow_check_fast_impl(
    table, dyn, rule_idx, spec, main_second, alt_second, main_threads,
    alt_threads, batch, now_idx_s, rel_now_ms, minute_spec, main_minute,
    now_idx_m, has_rate_limiter, has_thread_rules, rules_bk,
    enable_occupy, in_win_ms, occupy_timeout_ms, sortfree=False, gate=None,
) -> Tuple[FlowDynState, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B = batch.rows.shape[0]
    K = rule_idx.shape[1]
    NF = table.active.shape[0] - 1
    R = rule_idx.shape[0]
    RA = alt_threads.shape[0]
    # composite key must fit int32 (static shapes → checked at trace time;
    # the runtime host gate checks the same product before selecting this
    # variant and falls back to flow_check otherwise)
    assert (NF + 1) * (RA + 1) < 2 ** 31, \
        "rule-capacity x alt-rows too large for the fast general path"

    if rules_bk is None:
        rules_bk = seg.padded_table_gather(rule_idx, batch.rows, NF)  # [B,K]

    # ---- per-rule step state ----
    # (a warm-up rule syncs when a live event of its RESOURCE arrives:
    # whether the rule applies to the event's origin is read from the
    # gather below, which needs this step's limits — an origin-specific
    # warm-up rule may sync a call earlier than the original's would)
    dyn, shaping = _warmup_sync_and_limits(
        table, dyn, spec, main_second, now_idx_s, rel_now_ms,
        minute_spec, main_minute, now_idx_m,
        lambda: _rules_reached(rules_bk, batch.valid[:, None], NF))
    eff_limit = shaping.limit
    acq_of_rule = jnp.float32(0) + jnp.max(
        jnp.where(batch.valid, batch.acquire, 0)).astype(jnp.float32)
    if has_rate_limiter:
        is_rl_rule = (((table.behavior == BEHAVIOR_RATE_LIMITER)
                       | (table.behavior == BEHAVIOR_WARM_UP_RATE_LIMITER))
                      & (table.grade == GRADE_QPS))
        base_time, cost, max_k = _rl_closed_form(
            table, dyn, shaping, acq_of_rule, rel_now_ms)

    # ---- stat reads. MAIN/REF rows are PER-RULE quantities: a valid
    # (event, rule) pair always has rule.sync_row == the event's row (the
    # rule was gathered FROM that row; sync_row = own row, or ref_row for
    # RELATE), so the main-table window/thread reads are [NF+1]-sized and
    # ride the packed gather below — no [B]-sized gather over the 1M-row
    # window table at all. Only the ORIGIN/CHAIN reads are per-event, and
    # those hit the small [RA]-row alt table. ----
    # the alt table is tiny ([RA] rows): sum it DENSELY once (cheap) and
    # gather [B] values from the result — one gather per read instead of
    # per-bucket counter+stamp gathers; padding rows index the appended 0
    alt_pass_dense = jnp.concatenate([
        window_sum_all(spec, alt_second, ev.PASS,
                       now_idx_s).astype(jnp.float32),
        jnp.zeros((1,), jnp.float32)])
    safe_orow = jnp.minimum(batch.origin_rows, RA)
    safe_crow = jnp.minimum(batch.chain_rows, RA)
    or_pass = alt_pass_dense[safe_orow]
    cr_pass = alt_pass_dense[safe_crow]
    if has_thread_rules:
        alt_thr_dense = jnp.concatenate([
            alt_threads.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
        or_thr = alt_thr_dense[safe_orow]
        cr_thr = alt_thr_dense[safe_crow]

    # per-rule selected-row reads ([NF+1]-sized; sync_row covers both the
    # MAIN row — the rule's own resource — and the REF row for RELATE)
    srow_sel = jnp.minimum(table.sync_row, R - 1)
    row_pass = window_sum_rows(spec, main_second, srow_sel, ev.PASS,
                               now_idx_s).astype(jnp.float32)
    if enable_occupy:
        # fold LANDED bookings into the per-rule QPS base (flow_check's
        # `cur_pass + landed_bk`, same operands + association); alt-row
        # pairs read the alt columns and stay booking-free, matching the
        # general path's `no_book` mask
        row_pass = row_pass + _landed_per_rule(dyn, srow_sel, spec,
                                               now_idx_s)

    # ---- ONE packed per-rule gather [NF+1, C] → [B, K, C]. Column count
    # is STATIC per ruleset: the RL block (4 columns + closed forms) only
    # exists when a rate-limiter rule is loaded, the thread block (2
    # columns) only when something reads the gauges — the same static
    # elision as skip_auth/skip_sys/skip_threads ----
    cols = [
        table.active.astype(jnp.int32),                      # 0
        table.limit_origin,                                  # 1
        table.cluster_mode.astype(jnp.int32),                # 2
        table.sel_kind,                                      # 3
        table.ref_context,                                   # 4
        lax.bitcast_convert_type(eff_limit, jnp.int32),      # 5
        lax.bitcast_convert_type(row_pass, jnp.int32),       # 6
    ]
    ncol = 7
    if has_rate_limiter:
        i_rl, i_bt, i_cost, i_mk = ncol, ncol + 1, ncol + 2, ncol + 3
        cols += [is_rl_rule.astype(jnp.int32), base_time, cost, max_k]
        ncol += 4
    if has_thread_rules:
        i_thr, i_grade = ncol, ncol + 1
        row_thr = main_threads[srow_sel].astype(jnp.float32)
        cols += [lax.bitcast_convert_type(row_thr, jnp.int32), table.grade]
        ncol += 2
    if enable_occupy:
        # per-rule occupy eligibility: only DefaultController-grade rules
        # (QPS + DEFAULT behavior) have a prioritized path
        i_occ = ncol
        cols += [((table.grade == GRADE_QPS)
                  & (table.behavior == BEHAVIOR_DEFAULT)).astype(jnp.int32)]
        ncol += 1
    vt = jnp.stack(cols, axis=1)
    g = vt[rules_bk]                                         # [B, K, C]

    # ---- applicability (FlowRuleChecker.checkFlow null-node selection) ----
    act = g[..., 0] != 0
    lim = g[..., 1]
    oid = batch.origin_ids[:, None]
    specific_hit = jnp.any((lim == oid) & act, axis=1)[:, None]
    app = act & ((lim == LIMIT_DEFAULT) | (lim == oid)
                 | ((lim == LIMIT_OTHER) & ~specific_hit & (oid != 0)))
    slot_k = jnp.arange(K, dtype=jnp.int32)[None, :]
    fb = (batch.cluster_fallback[:, None] >> slot_k) & 1
    app = app & ((g[..., 2] == 0) | (fb == 1))
    kind = g[..., 3]
    app = app & jnp.where(kind == SEL_CHAIN,
                          batch.context_ids[:, None] == g[..., 4], True)
    use_alt = (kind == SEL_ORIGIN) | (kind == SEL_CHAIN)
    alt_row = jnp.where(kind == SEL_CHAIN, batch.chain_rows[:, None],
                        batch.origin_rows[:, None])
    app = app & jnp.where(use_alt, alt_row < RA, True)
    valid_pair = batch.valid[:, None] & app

    # ---- per-pair base (selected stat row's count; MAIN/REF both come
    # from the per-rule sync_row column) ----
    main_pass_p = lax.bitcast_convert_type(g[..., 6], jnp.float32)
    alt_pass_p = jnp.where(kind == SEL_CHAIN, cr_pass[:, None],
                           or_pass[:, None])
    cur_pass = jnp.where(use_alt, alt_pass_p, main_pass_p)
    if has_thread_rules:
        main_thr_p = lax.bitcast_convert_type(g[..., i_thr], jnp.float32)
        alt_thr_p = jnp.where(kind == SEL_CHAIN, cr_thr[:, None],
                              or_thr[:, None])
        cur_thr = jnp.where(use_alt, alt_thr_p, main_thr_p)
        base = jnp.where(g[..., i_grade] == GRADE_QPS, cur_pass, cur_thr)
    else:
        base = cur_pass              # no THREAD-grade rule reads the gauge

    # ---- composite-key arrival ranks (the only cross-event pass) ----
    if has_rate_limiter:
        rl_p = g[..., i_rl] != 0
        subrow = jnp.where(use_alt & ~rl_p, alt_row + 1, 0)
    else:
        subrow = jnp.where(use_alt, alt_row + 1, 0)
    key = rules_bk * (RA + 1) + subrow
    key = jnp.where(valid_pair, key, NF * (RA + 1))
    # per-slot ranks: slot columns carry disjoint rule sets (see
    # seg.ranks_per_slot; sentinel ranks are never consumed)
    if sortfree:
        # hashed claim cascade per slot column; any column's claim
        # overflow flips the whole rank table to the sorted reference
        # via lax.cond — graceful fallback, never a wrong answer
        rank_h, sf_ovf = sfo.ranks2d_hashed(key, NF * (RA + 1),
                                            sfo.table_bits(B))
        rank = lax.cond(sf_ovf > 0,
                        lambda _: seg.ranks_per_slot(key),
                        lambda _: rank_h, None)
    else:
        rank = seg.ranks_per_slot(key)
        sf_ovf = jnp.int32(0)

    # ---- admission (closed forms) ----
    a_f = acq_of_rule                       # the uniform acquire, float32
    rankf = _spent_rank(rank, gate).astype(jnp.float32)
    limit_pair = lax.bitcast_convert_type(g[..., 5], jnp.float32)
    pass_default = (base + rankf * a_f) + a_f <= limit_pair
    if has_rate_limiter:
        pass_rl = rank < g[..., i_mk]
        safe_rank = jnp.minimum(rank, g[..., i_mk])
        wait_pair = jnp.maximum(
            g[..., i_bt] + (safe_rank + 1) * g[..., i_cost] - rel_now_ms,
            0)

    # ---- occupy attempt (tryOccupyNext; see flow_check_fast_occupy) ----
    if enable_occupy and in_win_ms is not None and occupy_timeout_ms > 0:
        wait_next = (jnp.int32(spec.win_ms) - in_win_ms).astype(jnp.int32)
        occ_cnt = dyn.occupied_count             # [R, S]
        occ_win = dyn.occupied_window            # [R, S]

        def _occupy_attempt(_):
            can_time = wait_next <= occupy_timeout_ms
            # per-rule: passes SURVIVING into window now+1 (flow_check's
            # survive_mask, over the rule's selected row) + bookings
            # still live in the next window — eligible pairs are always
            # main-row, where sel_main_row == sync_row
            srow_stamps = main_second.stamps[srow_sel]       # [NF+1, B]
            sdelta = now_idx_s - srow_stamps
            survive_mask = (sdelta >= 0) & (sdelta <= spec.buckets - 2)
            surviving = jnp.sum(
                jnp.where(survive_mask,
                          main_second.counters[srow_sel, :, ev.PASS], 0),
                axis=1).astype(jnp.float32)
            occ_age = now_idx_s - occ_win[srow_sel]          # [NF+1, S]
            nextw = jnp.sum(
                jnp.where((occ_age >= -1) & (occ_age < spec.buckets - 1),
                          occ_cnt[srow_sel], 0.0), axis=1)
            occ_base_p = (surviving + nextw)[rules_bk]       # [B, K]
            eligible = (batch.prioritized[:, None] & (g[..., i_occ] != 0)
                        & ~pass_default & valid_pair & ~use_alt & can_time)
            # ranks among ELIGIBLE pairs only: the general path's greedy
            # fixed point gives ineligible pairs zero amounts, so its
            # admitted set is exactly the eligible-rank prefix under the
            # uniform acquire — one extra per-slot rank pass, no sort
            key_occ = jnp.where(eligible, key, NF * (RA + 1))
            if sortfree:
                r_occ_h, ovf_occ = sfo.ranks2d_hashed(
                    key_occ, NF * (RA + 1), sfo.table_bits(B))
                rank_occ = lax.cond(
                    ovf_occ > 0,
                    lambda _: seg.ranks_per_slot(key_occ),
                    lambda _: r_occ_h, None).astype(jnp.float32)
            else:
                rank_occ = seg.ranks_per_slot(key_occ).astype(jnp.float32)
                ovf_occ = jnp.int32(0)
            occ_adm = (((occ_base_p + rank_occ * a_f) + a_f <= limit_pair)
                       & eligible)

            # event-level gate BEFORE committing bookings: every failing
            # pair of the event must itself be occupy-admitted
            if has_rate_limiter:
                pair_ok = (jnp.where(rl_p, pass_rl, pass_default | occ_adm)
                           | ~valid_pair)
            else:
                pair_ok = (pass_default | occ_adm) | ~valid_pair
            event_ok = jnp.all(pair_ok, axis=1)
            event_occ = (jnp.any(occ_adm, axis=1) & event_ok
                         & batch.valid)                      # [B]

            # one booking per admitted event on its resource row, slot
            # ring keyed by window now+1 (flow_check's commit, verbatim)
            slots_n = occ_cnt.shape[1]
            slot = (now_idx_s + 1) % slots_n
            grants = jnp.zeros(occ_cnt.shape[0], jnp.float32).at[
                jnp.where(event_occ, batch.rows, occ_cnt.shape[0])].add(
                jnp.where(event_occ, batch.acquire, 0).astype(jnp.float32),
                mode="drop")
            granted_row = grants > 0
            slot_keep = occ_win[:, slot] == now_idx_s + 1
            new_cnt = jnp.where(granted_row,
                                jnp.where(slot_keep, occ_cnt[:, slot], 0.0)
                                + grants,
                                occ_cnt[:, slot])
            new_win = jnp.where(granted_row, now_idx_s + 1,
                                occ_win[:, slot])
            return (occ_cnt.at[:, slot].set(new_cnt),
                    occ_win.at[:, slot].set(new_win),
                    occ_adm & event_occ[:, None],
                    ovf_occ)

        def _no_occupy(_):
            return (occ_cnt, occ_win, jnp.zeros_like(pass_default),
                    jnp.int32(0))

        # real control flow, like flow_check: a batch routed here only
        # because bookings were live (no prioritized events) skips the
        # whole attempt — it pays the landed fold and nothing else
        new_occ_cnt, new_occ_win, occ_adm_p, sf_ovf_occ = jax.lax.cond(
            jnp.any(batch.prioritized), _occupy_attempt, _no_occupy, None)
        dyn = dyn._replace(occupied_count=new_occ_cnt,
                           occupied_window=new_occ_win)
        sf_ovf = sf_ovf + sf_ovf_occ
    else:
        occ_adm_p = jnp.zeros_like(pass_default)
        wait_next = jnp.int32(0)

    if has_rate_limiter:
        pair_pass = (jnp.where(rl_p, pass_rl, pass_default | occ_adm_p)
                     | ~valid_pair)
        pair_wait = jnp.where(rl_p & pair_pass & valid_pair, wait_pair, 0)
        if enable_occupy:
            pair_wait = jnp.maximum(pair_wait,
                                    jnp.where(occ_adm_p, wait_next, 0))
        wait_ms = jnp.max(pair_wait, axis=1)
    else:
        pair_pass = (pass_default | occ_adm_p) | ~valid_pair
        if enable_occupy:
            wait_ms = jnp.max(jnp.where(occ_adm_p, wait_next, 0), axis=1)
        else:
            wait_ms = jnp.zeros((B,), jnp.int32)

    allow = jnp.all(pair_pass, axis=1)
    occupied = jnp.any(occ_adm_p, axis=1) & allow & batch.valid

    # ---- pacing-clock update (per rule; RL segments are per-rule) ----
    if has_rate_limiter:
        rl_valid = rl_p & valid_pair
        npairs = jnp.zeros((NF + 2,), jnp.int32).at[
            jnp.where(rl_valid, rules_bk, NF + 1)].max(
            rank + 1, mode="drop")[:NF + 1]
        passed = jnp.minimum(npairs, max_k)
        passed = jnp.where(is_rl_rule & (table.count > 0), passed, 0)
        new_latest = jnp.where(
            passed > 0,
            (base_time + passed * cost).astype(jnp.int32),
            dyn.latest_passed_ms)
        dyn = dyn._replace(
            latest_passed_ms=jnp.maximum(dyn.latest_passed_ms, new_latest))

    allow = allow | ~batch.valid
    return dyn, allow, wait_ms.astype(jnp.int32), occupied, sf_ovf


class _Shaping(NamedTuple):
    """Per-rule [NF+1] columns of one step, from
    :func:`_warmup_sync_and_limits`."""

    limit: jnp.ndarray      # float32 — the most passQps + acquire may be
    cost1: jnp.ndarray      # int32 — pacing cost of ONE token, ms
    rate: jnp.ndarray       # float32 — the QPS a pacing rule paces at
    # (for an acquire other than 1, whose cost is computed in float32)


def _pacing_cost(acquire: jnp.ndarray, shaping_cost1: jnp.ndarray,
                 shaping_rate: jnp.ndarray) -> jnp.ndarray:
    """``Math.round(1.0 * acquire / qps * 1000)`` → int32. Exact for
    ``acquire == 1`` (the load-time float64 column); in float32, rounded
    half up as the original rounds, for any other."""
    other = jnp.floor(acquire.astype(jnp.float32)
                      / jnp.maximum(shaping_rate, 1e-9) * 1000.0 + 0.5)
    return jnp.where(acquire == 1, shaping_cost1,
                     jnp.minimum(other, 2.0 ** 30).astype(jnp.int32))


def _rl_closed_form(table: FlowRuleTable, dyn: FlowDynState,
                    shaping: _Shaping, acq_of_rule: jnp.ndarray,
                    rel_now_ms: jnp.ndarray):
    """Per-rule RATE_LIMITER closed form → (base_time, cost, max_k),
    shared bit-exactly by the scalar and fast paths (cost is per-rule
    for uniform acquire — RateLimiterController.java:30-90; a
    WarmUpRateLimiter's cost is the warm-up rate's while its tokens are
    above the warning line).

    All arithmetic stays per-RULE and BOUNDED: the admitted-rank budget
    ``max_k = (now + maxq - base_time) // cost`` has numerator in
    ``[0, cost + maxq]`` (due ⇒ base_time = now - cost; else
    now - L0 < cost), so no rank*cost product over the unbounded arrival
    rank can overflow int32 — a pair passes iff ``rank < max_k``.
    ``cost == 0`` (huge count): every rank shares one wait =
    ``max(base - now, 0)``, matching the general path's uniform-latest
    case. ``count <= 0`` RL blocks everything."""
    with jax.named_scope("decide.flow.rl"):
        cost = _pacing_cost(acq_of_rule.astype(jnp.int32), shaping.cost1,
                            shaping.rate)
        L0 = dyn.latest_passed_ms
        due = (L0 + cost - rel_now_ms) <= 0
        base_time = jnp.where(due, rel_now_ms - cost, L0)
        maxq_eff = jnp.where(table.count > 0, table.max_queue_ms,
                             jnp.int32(-1))
        rl_numer = rel_now_ms + maxq_eff - base_time
        max_k = jnp.maximum(rl_numer // jnp.maximum(cost, 1), 0)
        wait0_ok = jnp.maximum(base_time - rel_now_ms, 0) <= maxq_eff
        max_k = jnp.where(cost > 0, max_k,
                          jnp.where(wait0_ok, jnp.int32(2 ** 30), 0))
        max_k = jnp.where(table.count > 0, max_k, 0)
    return base_time, cost, max_k


def _warmup_sync_and_limits(
    table: FlowRuleTable, dyn: FlowDynState, spec: WindowSpec,
    main_second: WindowState, now_idx_s: jnp.ndarray, rel_now_ms: jnp.ndarray,
    minute_spec: Optional[WindowSpec], main_minute: Optional[WindowState],
    now_idx_m: Optional[jnp.ndarray],
    touched: Callable[[], jnp.ndarray],
) -> Tuple[FlowDynState, _Shaping]:
    """Warm-up token refill (WarmUpController.syncToken), once a second for
    each rule an event of this step reaches, and each rule's limit and
    pacing cost for this step.

    ``touched()`` → bool[NF+1]: the rules ``canPass`` is called on in this
    step (asked for only while a warm-up rule is loaded). The original
    syncs inside ``canPass``, so a rule nobody asks keeps its tokens and
    its ``lastFilledTime``: after three idle seconds ONE sync refills three
    seconds' worth and takes off the one previous second's passes. A sync
    of every rule at every step would take off each idle second's
    predecessor too and end elsewhere.

    Non-warm-up rules get their plain ``count``. Token state syncs against the
    rule's ``sync_row``, using the previous *second's* pass count — the
    reference reads ``previousPassQps`` from the MINUTE array's previous 1 s
    bucket (``StatisticNode.previousPassQps`` → ``rollingCounterInMinute``),
    so the minute window is the canonical source; without it we fall back to
    the second window's previous (sub-second) bucket, which under-counts and
    makes the ramp slower (conservative).

    ``stored_tokens`` holds whole numbers, as the original's long does (a
    refill is truncated), so ``aboveToken`` indexes the rule's rows of
    ``wu_tab``: the limit and the cost there are the original's doubles,
    not float32's. Exact while a rule's maxToken stays under 2**24.
    """
    if table.wu_tab.shape[0] == 1:      # STATIC: no warm-up rule loaded
        return dyn, _Shaping(table.count, table.rl_cost1, table.count)
    with jax.named_scope("decide.flow.warmup"):
        is_wu = ((table.behavior == BEHAVIOR_WARM_UP)
                 | (table.behavior == BEHAVIOR_WARM_UP_RATE_LIMITER)) & (
            table.grade == GRADE_QPS)
        R = main_second.stamps.shape[0]
        srow = jnp.minimum(table.sync_row, R - 1)
        if minute_spec is not None and main_minute is not None:
            pass_prev = prev_window_sum_rows(
                minute_spec, main_minute, srow, ev.PASS,
                now_idx_m).astype(jnp.float32)
        else:
            pass_prev = prev_window_sum_rows(
                spec, main_second, srow, ev.PASS,
                now_idx_s).astype(jnp.float32)

        now_sec = rel_now_ms // 1000
        should_sync = is_wu & (now_sec > dyn.last_filled_sec) & touched()
        old = dyn.stored_tokens
        elapsed_s = (now_sec - dyn.last_filled_sec).astype(jnp.float32)
        # coolDownTokens: below the warning line always refill; above it
        # only while the previous second passed fewer than
        # (int)count / coldFactor
        refill_ok = (old < table.warning_token) | (
            (old > table.warning_token) & (pass_prev < table.refill_below))
        refilled = jnp.minimum(jnp.floor(old + elapsed_s * table.count),
                               table.max_token)
        new_tokens = jnp.where(refill_ok, refilled, old)
        new_tokens = jnp.maximum(new_tokens - pass_prev, 0.0)
        stored = jnp.where(should_sync, new_tokens, old)
        last_filled = jnp.where(should_sync, now_sec, dyn.last_filled_sec)
        dyn = dyn._replace(stored_tokens=stored, last_filled_sec=last_filled)

        warm = is_wu & (stored >= table.warning_token)
        above = jnp.clip((stored - table.warning_token).astype(jnp.int32),
                         0, table.wu_levels)
        level = table.wu_tab[jnp.where(warm, table.wu_off + above, 0)]
        limit = jnp.where(warm, level[:, 0].astype(jnp.float32), table.count)
        cost1 = jnp.where(warm, level[:, 1], table.rl_cost1)
        rate = jnp.where(
            warm, 1.0 / (above.astype(jnp.float32) * table.slope
                         + 1.0 / jnp.maximum(table.count, 1e-9)),
            table.count)
    return dyn, _Shaping(limit, cost1, rate)
