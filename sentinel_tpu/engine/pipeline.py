"""The decision pipeline: slot chain as one fused jitted step.

Reference architecture (``sentinel-core``, SURVEY §3.1): every entry walks
``NodeSelectorSlot → ClusterBuilderSlot → LogSlot → StatisticSlot →
AuthoritySlot → SystemSlot → [ParamFlowSlot] → FlowSlot → DegradeSlot``, where
``StatisticSlot`` fires the rule slots FIRST and records pass/block *after*
the decision returns (``StatisticSlot.java:54-131``) — statistics are
post-decision, and that ordering is preserved here.

TPU-native shape: the whole chain is two pure functions over dense state —

* :func:`decide_entries` — batch of entry events → verdicts + updated state;
* :func:`record_exits`  — batch of completions → updated state (RT/success/
  exception recording + circuit-breaker feed, ``StatisticSlot.exit`` +
  ``DegradeSlot.exit``).

Node-tree equivalents are *views* over rows (SURVEY §7 phase 1): the global
per-resource row is the ClusterNode, hashed (resource × origin) and
(resource × context) rows in the ``alt`` table are origin-/chain-DefaultNodes,
and row 0 aggregates all inbound traffic (ENTRY_NODE). Gating masks cascade
through the slots so an event blocked upstream never consumes downstream
quota (a blocked-by-authority request can't eat flow tokens or a breaker
probe), and blocked events don't record pass counts — decision-before-
statistics, like the reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core.errors import BlockReason
from sentinel_tpu.core.registry import ENTRY_NODE_ROW
from sentinel_tpu.rules import authority as auth_mod
from sentinel_tpu.rules import degrade as deg_mod
from sentinel_tpu.obs import resource_hist
from sentinel_tpu.ops.segments import padded_table_gather
from sentinel_tpu.rules import flow as flow_mod
from sentinel_tpu.rules import param_flow as pf_mod
from sentinel_tpu.rules import system as sys_mod
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    WindowSpec, WindowState, bucket_add_events, bucket_add_hist,
    bucket_add_row, bucket_add_vecs, close_bucket, extract_rows,
    hist_add_fits, init_window, invalidate_rows, open_bucket, refresh_rows,
    restore_rows,
)


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static engine geometry (hashable; closed over by the jitted steps)."""

    rows: int                 # R — main resource rows (row 0 = ENTRY_NODE)
    alt_rows: int             # RA — hashed (resource×origin/context) rows
    second: WindowSpec
    minute: Optional[WindowSpec]
    statistic_max_rt: int
    param_keys: int = 0       # PK — hot-key rows (0 = param flow disabled)
    param_pairs: int = 0      # PV — (rule, value) checks per event
    occupy_timeout_ms: int = 500   # OccupyTimeoutProperty default (0 = off)
    # HB — per-resource RT histogram buckets (obs/resource_hist.py);
    # 0 = table disabled: state.rt_hist is None and every consumer
    # compiles the feature away (round-20 bit-parity switch)
    hist_buckets: int = 0
    # the row axis of the window tensors is split over a device mesh
    # (parallel/local_shard.py): single-row updates take the form the SPMD
    # partitioner keeps on the owning shard (stats/window.bucket_add_row)
    rows_sharded: bool = False


class SentinelState(NamedTuple):
    """All mutable device state, one pytree."""

    second: WindowState           # [R]
    minute: WindowState           # [R] (rows=1 when minute disabled)
    alt_second: WindowState       # [RA]
    threads: jnp.ndarray          # int32[R]
    alt_threads: jnp.ndarray      # int32[RA]
    flow_dyn: flow_mod.FlowDynState
    breakers: deg_mod.BreakerState
    param_dyn: pf_mod.ParamDynState
    # per-registered-DeviceSlot pytree state slices (engine/slots.py),
    # positionally aligned with the custom_slots tuple the steps were
    # compiled with; () when no custom slots are registered
    custom: Tuple = ()
    # int32[R, HB] cumulative per-resource RT histogram (round 20) —
    # counts only grow (they ride tier demote/promote and geometry
    # changes) and reset on row invalidation. None ⇔ spec.hist_buckets
    # == 0, so the leaf's absence keeps old programs byte-identical.
    rt_hist: Optional[jnp.ndarray] = None


class RuleSet(NamedTuple):
    """All compiled rule tables; swapped atomically on rule reload."""

    flow_table: flow_mod.FlowRuleTable
    flow_idx: jnp.ndarray
    deg_table: deg_mod.DegradeRuleTable
    deg_idx: jnp.ndarray
    auth_table: auth_mod.AuthorityRuleTable
    auth_idx: jnp.ndarray
    sys_thresholds: sys_mod.SystemThresholds
    param_table: pf_mod.ParamRuleTable
    # concat(flow_idx, deg_idx) [R, Kf+Kd] — the scalar path gathers BOTH
    # slots' rule ids in ONE pass over the big row table (a 512k random
    # gather from a [1M]-row table costs ~6 ms on the v5 chip; two of
    # them were ~25% of the scalar step). None = gather separately.
    # ALWAYS build via with_joint() (or build_joint_np on the SAME numpy
    # arrays being shipped as flow_idx/deg_idx — the runtime's host-side
    # assembly) — the consumer splits at flow_idx.shape[1], so any other
    # hand-concatenated copy can silently desync.
    joint_idx: Optional[jnp.ndarray] = None

    def with_joint(self) -> "RuleSet":
        """→ self with ``joint_idx`` derived from the flow_idx/deg_idx
        THIS ruleset actually carries (desync-proof by construction)."""
        return self._replace(joint_idx=jnp.concatenate(
            [self.flow_idx, self.deg_idx], axis=1))

    @staticmethod
    def build_joint_np(flow_idx_np, deg_idx_np):
        """Host-side form of :meth:`with_joint` for callers that assemble
        the ruleset in numpy and device_put once (cold-start path): pass
        the EXACT arrays that become flow_idx/deg_idx."""
        import numpy as np
        return np.concatenate([flow_idx_np, deg_idx_np], axis=1)


class EntryBatch(NamedTuple):
    """Device-side entry events (padded to static size; padding: rows >= R,
    valid False)."""

    rows: jnp.ndarray           # int32[B]
    origin_ids: jnp.ndarray     # int32[B] (0 = none)
    origin_rows: jnp.ndarray    # int32[B] (>= RA = none)
    context_ids: jnp.ndarray    # int32[B]
    chain_rows: jnp.ndarray     # int32[B] (>= RA = none)
    acquire: jnp.ndarray        # int32[B]
    is_in: jnp.ndarray          # bool[B]
    prioritized: jnp.ndarray    # bool[B]
    valid: jnp.ndarray          # bool[B]
    param_rules: Optional[jnp.ndarray] = None   # int32[B, PV] (param slot off: None)
    param_keys: Optional[jnp.ndarray] = None    # int32[B, PV]
    # per-event bitmask over per-resource rule slots: bit k set = the
    # cluster-mode rule in slot k had its token request fail with
    # fallbackToLocalWhenFail, so exactly that rule checks LOCALLY
    # (per-rule FlowRuleChecker.fallbackToLocalOrPass); None = no fallback
    cluster_fallback: Optional[jnp.ndarray] = None   # int32[B]
    # False = don't count this event in the thread (concurrency) gauges:
    # host-leased admissions are never thread-counted (the lease pre-charge
    # batch and each leased exit both carry False, keeping the gauge
    # consistent). None = all True.
    count_thread: Optional[jnp.ndarray] = None       # bool[B]
    # False = a DENIAL of this event records no BLOCK stat: lease renewal
    # probes are speculative acquire=C requests — a denied probe isn't C
    # denied callers (the triggering caller re-decides per-event and
    # records its own block). None = all True.
    record_block: Optional[jnp.ndarray] = None       # bool[B]


class ExitBatch(NamedTuple):
    rows: jnp.ndarray           # int32[B]
    origin_rows: jnp.ndarray    # int32[B]
    chain_rows: jnp.ndarray     # int32[B]
    acquire: jnp.ndarray        # int32[B]
    rt_ms: jnp.ndarray          # int32[B]
    error: jnp.ndarray          # bool[B]
    is_in: jnp.ndarray          # bool[B]
    valid: jnp.ndarray          # bool[B]
    param_rules: Optional[jnp.ndarray] = None   # int32[B, PV]
    param_keys: Optional[jnp.ndarray] = None    # int32[B, PV]
    count_thread: Optional[jnp.ndarray] = None  # bool[B] (see EntryBatch)


class Verdicts(NamedTuple):
    allow: jnp.ndarray          # bool[B]
    reason: jnp.ndarray         # int8[B] (BlockReason codes)
    wait_ms: jnp.ndarray        # int32[B]
    sf_overflow: Optional[jnp.ndarray] = None   # int32 scalar — sort-free
    # claim-cascade overflow count this step (elements that took the
    # sorted fallback; feeds obs counter sortfree.bucket_overflow). None
    # when the step was built without the sortfree static.


def _init_state_traced(spec: EngineSpec, nf: int, nd: int) -> SentinelState:
    minute_rows = spec.rows if spec.minute else 1
    minute_spec = spec.minute or WindowSpec(1, 1000, track_rt=False)
    return SentinelState(
        second=init_window(spec.second, spec.rows),
        minute=init_window(minute_spec, minute_rows),
        alt_second=init_window(spec.second, spec.alt_rows),
        threads=jnp.zeros((spec.rows,), jnp.int32),
        alt_threads=jnp.zeros((spec.alt_rows,), jnp.int32),
        flow_dyn=flow_mod.init_flow_dyn(nf, spec.second.buckets, spec.rows),
        breakers=deg_mod.init_breaker_state(nd),
        param_dyn=pf_mod.init_param_dyn(spec.param_keys),
        rt_hist=(jnp.zeros((spec.rows, spec.hist_buckets), jnp.int32)
                 if spec.hist_buckets else None),
    )


@functools.lru_cache(maxsize=None)
def _init_state_jit(spec: EngineSpec, nf: int, nd: int):
    return jax.jit(functools.partial(_init_state_traced, spec, nf, nd))


def _init_state_np(spec: EngineSpec, nf: int, nd: int) -> SentinelState:
    """Numpy mirror of :func:`_init_state_traced` (bit-identical leaves —
    pinned by ``tests/test_pipeline.py::test_init_state_np_parity``)."""
    import numpy as np

    # python literals, NOT the module's device scalars (int(NEVER) would
    # be a blocking device readback — the RPC this function exists to
    # avoid); parity with the traced constants pinned by the test
    never = -(2 ** 30)
    i32max = np.iinfo(np.int32).max

    def win(wspec, rows):
        b_rt = wspec.buckets if wspec.track_rt else 0
        return WindowState(
            counters=np.zeros((rows, wspec.buckets, ev.NUM_EVENTS),
                              np.int32),
            stamps=np.full((rows, wspec.buckets), never, np.int32),
            rt_sum=np.zeros((rows, b_rt), np.float32),
            min_rt=np.full((rows, b_rt), i32max, np.int32))

    minute_rows = spec.rows if spec.minute else 1
    minute_spec = spec.minute or WindowSpec(1, 1000, track_rt=False)
    pk = spec.param_keys
    return SentinelState(
        second=win(spec.second, spec.rows),
        minute=win(minute_spec, minute_rows),
        alt_second=win(spec.second, spec.alt_rows),
        threads=np.zeros((spec.rows,), np.int32),
        alt_threads=np.zeros((spec.alt_rows,), np.int32),
        flow_dyn=flow_mod.FlowDynState(
            latest_passed_ms=np.full((nf + 1,), never, np.int32),
            stored_tokens=np.zeros((nf + 1,), np.float32),
            last_filled_sec=np.full((nf + 1,), never, np.int32),
            occupied_count=np.zeros(
                (spec.rows, spec.second.buckets + 1), np.float32),
            occupied_window=np.full(
                (spec.rows, spec.second.buckets + 1), never, np.int32)),
        breakers=deg_mod.BreakerState(
            state=np.zeros((nd + 1,), np.int32),
            next_retry_ms=np.full((nd + 1,), never, np.int32),
            win_stamp=np.full((nd + 1,), never, np.int32),
            bad=np.zeros((nd + 1,), np.int32),
            total=np.zeros((nd + 1,), np.int32)),
        param_dyn=pf_mod.ParamDynState(
            tokens=np.zeros((pk + 1,), np.float32),
            last_fill_ms=np.full((pk + 1,), never, np.int32),
            latest_passed_ms=np.full((pk + 1,), never, np.int32),
            threads=np.zeros((pk + 1,), np.int32),
            override=np.full((pk + 1,), -1.0, np.float32)),
        rt_hist=(np.zeros((spec.rows, spec.hist_buckets), np.int32)
                 if spec.hist_buckets else None),
    )


# up to this size the state is built on the host and shipped as one
# transfer (no XLA program to compile or load at start-up); above it one
# fused fill program runs instead, so a 1M-row state is never materialized
# in host memory first. Where the crossover sits on a host-attached chip:
# not measured.
_TRANSFER_STATE_LIMIT_BYTES = 48 * 1024 * 1024


def init_state_shapes(spec: EngineSpec, nf: int, nd: int) -> SentinelState:
    """The state's structure and shapes with nothing allocated: what a
    meshed engine derives its sharding pytree from before a byte of the
    state exists (parallel/local_shard.state_shardings)."""
    return jax.eval_shape(
        functools.partial(_init_state_traced, spec, nf, nd))


def init_state(spec: EngineSpec, nf: int, nd: int,
               shardings: Optional[SentinelState] = None) -> SentinelState:
    """Initial device state — WITHOUT paying per-process program loads
    where possible.

    Eager construction dispatched ~17 tiny fill programs, each a
    compile or cache load of its own at every process start (the
    cold-start story in docs/OPERATIONS.md). Serving-sized states
    (≤ ~48 MB) are instead built host-side and device_put as ONE
    transfer (no XLA program at all); bigger states (the 1M-row scale)
    fall back to one fused fill program, jit-cached per geometry.

    ``shardings`` (a meshed engine's ``state_shardings`` pytree) creates
    the state already laid out: the transfer places each leaf with its
    sharding, the fill program runs with them as ``out_shardings`` so
    every device fills its own rows and no leaf ever exists whole on one
    device — at 4M rows the state is 12.9 GB, most of a 16 GB chip."""
    import math
    import os
    mode = os.environ.get("SENTINEL_INIT_MODE", "")
    # size from shapes alone — don't allocate ~90 MB of numpy zeros just
    # to discard them on the program path
    nbytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(init_state_shapes(spec, nf, nd)))
    if mode != "program" and (mode == "transfer"
                              or nbytes <= _TRANSFER_STATE_LIMIT_BYTES):
        return jax.device_put(_init_state_np(spec, nf, nd), shardings)
    if shardings is None:
        return _init_state_jit(spec, nf, nd)()
    # not cached: the pytree holds the mesh, and an engine initialises once
    return jax.jit(functools.partial(_init_state_traced, spec, nf, nd),
                   out_shardings=shardings)()


def _stat_targets(spec: EngineSpec, rows, origin_rows, chain_rows, valid,
                  is_in):
    """Recording target rows shared by entry/block recorders: the event row
    + the global ENTRY row (IN only) in the main table, the origin + chain
    rows in the alt table; padding = one-past-the-end (dropped scatters)."""
    pad_r = jnp.int32(spec.rows)
    pad_a = jnp.int32(spec.alt_rows)
    main_rows = jnp.where(valid, rows, pad_r)
    entry_rows = jnp.where(valid & is_in, jnp.int32(ENTRY_NODE_ROW), pad_r)
    alt_o = jnp.where(valid, origin_rows, pad_a)
    alt_c = jnp.where(valid, chain_rows, pad_a)
    return (jnp.concatenate([main_rows, entry_rows]),
            jnp.concatenate([alt_o, alt_c]))


def _scoped(name: str, fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` under ``jax.named_scope(name)``: the stage's
    name lands in every HLO operation's ``op_name`` (metadata only, no
    run-time cost), so a device trace or the compiled text says which
    stage owns an operation."""
    with jax.named_scope(name):
        return fn(*args, **kwargs)


def _record_window(step: str, name: str, wspec: WindowSpec,
                   wstate: WindowState, now_idx, touched, adds) -> WindowState:
    """One window's record stage: slice the current bucket's plane out of
    the ring with every row lazily reset (scope ``<step>.refresh.<name>``),
    apply ``adds`` (Bucket → Bucket) to the plane and write it back once
    (``<step>.record.<name>``) — no scatter ever has the ring as its
    operand (stats.window.open_bucket says what that costs on the chip).
    ``touched`` are the rows ``adds`` lands on, read only when B == 1."""
    if wspec.buckets >= 2:
        bucket = _scoped(f"{step}.refresh.{name}", open_bucket, wspec, wstate,
                         now_idx)
    else:   # B=1: full restamp would erase untouched rows' prev window
        wstate = _scoped(f"{step}.refresh.{name}", refresh_rows, wspec,
                         wstate, touched, now_idx)
        bucket = open_bucket(wspec, wstate, now_idx, reset=False)
    with jax.named_scope(f"{step}.record.{name}"):
        return close_bucket(wspec, wstate, adds(bucket), now_idx)


def decide_entries(
    spec: EngineSpec,
    rules: RuleSet,
    state: SentinelState,
    batch: EntryBatch,
    times: jnp.ndarray,          # int32[4]: idx_s, idx_m, rel_ms, in_win_ms
    sys_scalars: jnp.ndarray,    # float32[2]: load1, cpu_usage
    enable_occupy: bool = True,  # STATIC (see flow_check)
    custom_slots: Tuple = (),    # STATIC: registered DeviceSlots (slots.py)
    record_alt: bool = True,     # STATIC: False = batch carries no origin/
    # chain rows (host-verified all-padding) → the alt-table scatters and
    # the alt thread gauge compile away entirely; origin-less traffic is
    # the common case and those scatters are pure padding work there
    scalar_flow: bool = False,   # STATIC: HOST-VERIFIED preconditions
    # (no alt rows, uniform acquire >= 1, no prioritized events, no
    # cluster_fallback bits) → flow + degrade take the scalar admission
    # path: per-rule budgets, one rank sort, sort-free breaker probes
    # (see rules/flow.flow_check_scalar). Implies record_alt=False.
    # With enable_occupy=True the scalar checker folds LANDED occupy
    # bookings into the QPS base (occupy_base) — the batch still carries
    # no prioritized events, it only dispatches AROUND live bookings.
    fast_flow: bool = False,     # STATIC: HOST-VERIFIED preconditions
    # (uniform acquire >= 1, composite key fits int32) → the fast
    # GENERAL path: origins/alt rows/CHAIN/fallback bits all live,
    # admission via rank closed forms (rules/flow.flow_check_fast).
    # Mutually exclusive with scalar_flow. With enable_occupy=True the
    # occupy-capable variant runs (rules/flow.flow_check_fast_occupy):
    # prioritized events take the vectorized tryOccupyNext path.
    skip_auth: bool = False,     # STATIC: no authority rules loaded —
    # the whole slot (incl. its [B, Ka] gathers) compiles away
    skip_sys: bool = False,      # STATIC: no system thresholds set
    scalar_has_rl: bool = True,  # STATIC: ruleset contains rate-limiter
    # rules (scalar path only — gates the pacing-clock histogram scatter)
    skip_threads: bool = False,  # STATIC: nothing loaded READS the live-
    # concurrency gauges (no THREAD-grade flow rules, no system rules, no
    # THREAD-grade param rules — the only reference readers:
    # DefaultController.java:50-76 THREAD branch, SystemRuleManager
    # .checkSystem, ParamFlowChecker THREAD mode), so their maintenance
    # scatters are elided entirely. The gauges then read 0 (observability
    # trade documented in docs/OPERATIONS.md); loading a gauge-reading
    # rule flips the flag (retrace) and the gauge warms as pre-flip
    # entries exit (decrements clamp at 0).
    sortfree: bool = False,      # STATIC: every flow path groups segments
    # via the sort-free hash-bucketed scatter machinery (ops/sortfree.py)
    # instead of stable sorts — bit-exact by construction (claim overflow
    # falls back to the sorted branch under lax.cond). The verdicts then
    # carry sf_overflow (int32 scalar) for the runtime's
    # sortfree.bucket_overflow counter.
) -> Tuple[SentinelState, Verdicts]:
    """One device step: decide a batch, then record post-decision statistics.

    Time/system inputs arrive PACKED (one int32[4] + one float32[2]) so a
    step costs two host→device transfers, not six — each per-call
    transfer is dispatch latency on the hot path."""
    R = spec.rows
    RA = spec.alt_rows
    now_idx_s = times[0]
    now_idx_m = times[1]
    rel_now_ms = times[2]
    in_win_ms = times[3]
    load1 = sys_scalars[0]
    cpu_usage = sys_scalars[1]

    if scalar_flow:
        assert not record_alt, "scalar_flow implies record_alt=False"
    if fast_flow:
        assert not scalar_flow, "fast_flow is exclusive with scalar_flow"

    # ---- slot cascade (each gate only sees events still alive) ----
    live = batch.valid

    if skip_auth:
        auth_ok = jnp.ones_like(live)
    else:
        auth_ok = auth_mod.authority_check(
            rules.auth_table, rules.auth_idx, batch.rows, batch.origin_ids,
            live)
    live1 = live & auth_ok

    # unset thresholds fold to a huge sentinel, so the check is a no-op pass
    # when no system rules are loaded (no branch: avoids retracing); a host
    # that KNOWS no system rules exist passes skip_sys and the whole check
    # (its ENTRY-row window reads included) compiles away
    if skip_sys:
        sys_ok = jnp.ones_like(live1)
    else:
        sys_ok = sys_mod.system_check(
            rules.sys_thresholds, spec.second, state.second, state.threads,
            batch.is_in, batch.acquire, live1, now_idx_s, load1, cpu_usage,
            spec.statistic_max_rt)
    live2 = live1 & sys_ok

    # ParamFlowSlot sits between SystemSlot and FlowSlot (extension SPI slot
    # order, SURVEY §1). Static skip when the engine has no param geometry.
    param_dyn = state.param_dyn
    if spec.param_keys and batch.param_rules is not None:
        # scalar_flow/fast_flow imply host-verified uniform acquire — the
        # precondition for the rank-prefix param variant (VERDICT r4 #9)
        pcheck = (pf_mod.param_check_scalar
                  if (scalar_flow or fast_flow) else pf_mod.param_check)
        param_dyn, param_ok, param_wait = pcheck(
            rules.param_table, param_dyn, batch.param_rules, batch.param_keys,
            batch.acquire, live2, rel_now_ms)
        live2 = live2 & param_ok
    else:
        param_ok = jnp.ones_like(live2)
        param_wait = jnp.zeros(live2.shape, jnp.int32)

    flow_bk = deg_bk = None
    if (scalar_flow or fast_flow) and rules.joint_idx is not None:
        # ONE random gather over the [R, Kf+Kd] joint table feeds both
        # slots (see RuleSet.joint_idx)
        Kf = rules.flow_idx.shape[1]
        NFs = rules.flow_table.active.shape[0] - 1
        NDs = rules.deg_table.active.shape[0] - 1
        joint = padded_table_gather(rules.joint_idx, batch.rows, 0)
        in_r = (batch.rows < R)[:, None]
        flow_bk = jnp.where(in_r, joint[:, :Kf], NFs)
        deg_bk = jnp.where(in_r, joint[:, Kf:], NDs)
    sf_ovf = jnp.int32(0)
    # what DegradeSlot will say to each event's resource, read BEFORE the
    # flow slot ranks the resource's events: an event a breaker refuses is
    # never counted as a pass, so it spends nothing of a count-based
    # budget (rules/flow._spent_rank). One [B, Kd] gather, which the
    # scalar entry check below reuses
    if deg_bk is None:
        deg_bk = padded_table_gather(
            rules.deg_idx, batch.rows, rules.deg_table.active.shape[0] - 1)
    gate_code, gate_closed, gate_probe = _scoped(
        "decide.degrade.gate", deg_mod.degrade_gate, rules.deg_table,
        state.breakers, deg_bk, rel_now_ms)
    gate = (gate_closed, gate_probe)
    if scalar_flow:
        flow_dyn, flow_ok, wait_ms = _scoped(
            "decide.flow", flow_mod.flow_check_scalar, rules.flow_table,
            state.flow_dyn, rules.flow_idx, spec.second, state.second,
            state.threads, batch.rows, batch.acquire, live2, now_idx_s,
            rel_now_ms, minute_spec=spec.minute,
            main_minute=state.minute if spec.minute else None,
            now_idx_m=now_idx_m, has_rate_limiter=scalar_has_rl,
            rules_bk=flow_bk, occupy_base=enable_occupy, sortfree=sortfree,
            gate=gate)
        occupied = jnp.zeros_like(flow_ok)
        live3 = live2 & flow_ok
        breakers, deg_ok = _scoped(
            "decide.degrade", deg_mod.degrade_entry_check_scalar,
            rules.deg_table, state.breakers, rules.deg_idx, batch.rows, live3,
            rel_now_ms, rules_bk=deg_bk, gate_code=gate_code)
    elif fast_flow:
        # fast general path: per-pair origin/row selection stays live, the
        # admission machinery collapses to rank closed forms; the degrade
        # slot is origin-independent, so the scalar variant applies as-is
        cl_fb = (batch.cluster_fallback if batch.cluster_fallback is not None
                 else jnp.zeros(batch.valid.shape, jnp.int32))
        fview = flow_mod.FlowBatchView(
            rows=batch.rows, origin_ids=batch.origin_ids,
            origin_rows=batch.origin_rows, context_ids=batch.context_ids,
            chain_rows=batch.chain_rows, acquire=batch.acquire, valid=live2,
            prioritized=batch.prioritized, cluster_fallback=cl_fb)
        if enable_occupy:
            fn_occ = (flow_mod.flow_check_fast_occupy_sortfree if sortfree
                      else flow_mod.flow_check_fast_occupy)
            out = _scoped(
                "decide.flow", fn_occ, rules.flow_table, state.flow_dyn,
                rules.flow_idx, spec.second, state.second, state.alt_second,
                state.threads, state.alt_threads, fview, now_idx_s, rel_now_ms,
                minute_spec=spec.minute,
                main_minute=state.minute if spec.minute else None,
                now_idx_m=now_idx_m, in_win_ms=in_win_ms,
                occupy_timeout_ms=spec.occupy_timeout_ms,
                has_rate_limiter=scalar_has_rl,
                has_thread_rules=not skip_threads, rules_bk=flow_bk,
                gate=gate)
            if sortfree:
                flow_dyn, flow_ok, wait_ms, occupied, sf_ovf = out
            else:
                flow_dyn, flow_ok, wait_ms, occupied = out
        else:
            fn_plain = (flow_mod.flow_check_fast_sortfree if sortfree
                        else flow_mod.flow_check_fast)
            out = _scoped(
                "decide.flow", fn_plain, rules.flow_table, state.flow_dyn,
                rules.flow_idx, spec.second, state.second, state.alt_second,
                state.threads, state.alt_threads, fview, now_idx_s, rel_now_ms,
                minute_spec=spec.minute,
                main_minute=state.minute if spec.minute else None,
                now_idx_m=now_idx_m, has_rate_limiter=scalar_has_rl,
                has_thread_rules=not skip_threads, rules_bk=flow_bk,
                gate=gate)
            if sortfree:
                flow_dyn, flow_ok, wait_ms, sf_ovf = out
            else:
                flow_dyn, flow_ok, wait_ms = out
            occupied = jnp.zeros_like(flow_ok)
        live3 = live2 & flow_ok
        # occupied (PriorityWait) events bypass the degrade slot — see the
        # general branch below
        breakers, deg_ok = _scoped(
            "decide.degrade", deg_mod.degrade_entry_check_scalar,
            rules.deg_table, state.breakers, rules.deg_idx, batch.rows,
            live3 & ~occupied, rel_now_ms, rules_bk=deg_bk,
            gate_code=gate_code)
        deg_ok = deg_ok | occupied
    else:
        cl_fb = (batch.cluster_fallback if batch.cluster_fallback is not None
                 else jnp.zeros(batch.valid.shape, jnp.int32))
        fview = flow_mod.FlowBatchView(
            rows=batch.rows, origin_ids=batch.origin_ids,
            origin_rows=batch.origin_rows, context_ids=batch.context_ids,
            chain_rows=batch.chain_rows, acquire=batch.acquire, valid=live2,
            prioritized=batch.prioritized, cluster_fallback=cl_fb)
        fcheck = (flow_mod.flow_check_sortfree if sortfree
                  else flow_mod.flow_check)
        out = _scoped(
            "decide.flow", fcheck, rules.flow_table, state.flow_dyn,
            rules.flow_idx, spec.second, state.second, state.alt_second,
            state.threads, state.alt_threads, fview, now_idx_s, rel_now_ms,
            minute_spec=spec.minute,
            main_minute=state.minute if spec.minute else None,
            now_idx_m=now_idx_m, in_win_ms=in_win_ms,
            occupy_timeout_ms=spec.occupy_timeout_ms,
            enable_occupy=enable_occupy, has_thread_rules=not skip_threads,
            gate=gate)
        if sortfree:
            flow_dyn, flow_ok, wait_ms, occupied, sf_ovf = out
        else:
            flow_dyn, flow_ok, wait_ms, occupied = out
        live3 = live2 & flow_ok

        # occupied (PriorityWait) events bypass the degrade slot entirely —
        # in the reference the PriorityWaitException aborts the slot chain
        # before DegradeSlot.entry runs, and the booking is already committed
        breakers, deg_ok = _scoped(
            "decide.degrade", deg_mod.degrade_entry_check, rules.deg_table,
            state.breakers, rules.deg_idx, batch.rows, live3 & ~occupied,
            rel_now_ms)
        deg_ok = deg_ok | occupied

    # ---- user DeviceSlots (slot-chain SPI analog; STATIC: compiles to
    # nothing when none are registered) ----
    custom_states = state.custom
    if custom_slots:
        from sentinel_tpu.engine.slots import DeviceSlotView, run_device_slots
        from sentinel_tpu.stats.window import window_sum_rows
        safe_rows = jnp.minimum(batch.rows, R - 1)
        pass_counts = window_sum_rows(
            spec.second, state.second, safe_rows, ev.PASS,
            now_idx_s).astype(jnp.float32)
        cview = DeviceSlotView(
            rows=batch.rows, origin_ids=batch.origin_ids,
            acquire=batch.acquire, is_in=batch.is_in,
            prioritized=batch.prioritized, live=live3 & deg_ok,
            now_idx_s=now_idx_s, rel_now_ms=rel_now_ms,
            pass_counts=pass_counts)
        custom_states, custom_ok, custom_reason = run_device_slots(
            custom_slots, state.custom, cview)
    else:
        custom_ok = jnp.ones_like(live)
        custom_reason = jnp.zeros(batch.rows.shape, jnp.int8)

    allow = live & auth_ok & sys_ok & param_ok & flow_ok & deg_ok & custom_ok
    reason = jnp.zeros(batch.rows.shape, jnp.int8)
    reason = jnp.where(~custom_ok, custom_reason, reason)
    reason = jnp.where(~deg_ok, jnp.int8(BlockReason.DEGRADE), reason)
    reason = jnp.where(~flow_ok, jnp.int8(BlockReason.FLOW), reason)
    reason = jnp.where(~param_ok, jnp.int8(BlockReason.PARAM_FLOW), reason)
    reason = jnp.where(~sys_ok, jnp.int8(BlockReason.SYSTEM), reason)
    reason = jnp.where(~auth_ok, jnp.int8(BlockReason.AUTHORITY), reason)
    reason = jnp.where(~batch.valid, jnp.int8(BlockReason.NONE), reason)
    wait_ms = jnp.where(allow, jnp.maximum(wait_ms, param_wait), 0)

    # ---- StatisticSlot.entry (post-decision recording) ----
    passed = allow & batch.valid
    blocked = ~allow & batch.valid
    # occupied (PriorityWait) entries don't count PASS now — their pass
    # belongs to the next window (virtual booking in flow dyn state); they
    # still hold a thread and show up as OCCUPIED_PASS in this second's
    # metrics (half-a-window earlier than the reference's landing-time
    # accounting; admission math is unaffected)
    pass_now = passed & ~occupied
    occupied = occupied & passed      # occupied implies admitted; belt-and-
    # braces so a blocked event can never record OCCUPIED_PASS
    pad_r = jnp.int32(R)
    pad_a = jnp.int32(RA)

    _, alt_targets = _stat_targets(
        spec, batch.rows, batch.origin_rows, batch.chain_rows, batch.valid,
        batch.is_in)
    blocked_rec = (blocked & batch.record_block
                   if batch.record_block is not None else blocked)
    occ1 = occupied if enable_occupy else jnp.zeros_like(pass_now)

    # Recording strategy (this block was ~70% of the step's device time as
    # per-event add_rows passes): (1) the current bucket's plane of every
    # row, lazily reset, not the ring (_record_window); (2) each event
    # lands in exactly ONE lane (pass_now / occupied / blocked are mutually
    # exclusive), so the per-row record is one fused scatter of B indices
    # (bucket_add_events); (3) the global ENTRY row — formerly a second
    # B-index scatter half — is a reduction + one single-row update
    # (bucket_add_row).
    rec1 = pass_now | occ1 | blocked_rec            # all already ∧ valid
    ev_ids1 = jnp.where(pass_now, jnp.int32(ev.PASS),
                        jnp.where(occ1, jnp.int32(ev.OCCUPIED_PASS),
                                  jnp.int32(ev.BLOCK)))
    acq = batch.acquire
    rec_amt1 = jnp.where(rec1, acq, 0)
    main_rec1 = jnp.where(rec1, batch.rows, pad_r)

    ein = batch.is_in
    n_ev = state.second.counters.shape[2]
    entry_vec = jnp.zeros((n_ev,), jnp.int32)
    entry_vec = entry_vec.at[ev.PASS].set(
        jnp.sum(jnp.where(pass_now & ein, acq, 0)))
    if enable_occupy:
        entry_vec = entry_vec.at[ev.OCCUPIED_PASS].set(
            jnp.sum(jnp.where(occ1 & ein, acq, 0)))
    entry_vec = entry_vec.at[ev.BLOCK].set(
        jnp.sum(jnp.where(blocked_rec & ein, acq, 0)))

    def record_main(bucket):
        bucket = bucket_add_events(bucket, main_rec1, ev_ids1, rec_amt1)
        return bucket_add_row(bucket, ENTRY_NODE_ROW, entry_vec,
                              sharded=spec.rows_sharded)

    # B=1: ENTRY joins the refresh list only when this batch actually lands
    # something on it — an idle/all-outbound batch restamping ENTRY would
    # erase its previous-window bucket (previousPassQps for warm-up rules
    # reading the entry node). bucket_add_row with an all-zero vector on
    # the unrefreshed bucket is a no-op.
    entry_refresh = jnp.where(jnp.any(entry_vec != 0),
                              jnp.int32(ENTRY_NODE_ROW), pad_r)
    touched = jnp.concatenate([main_rec1, entry_refresh[None]])
    second = _record_window(
        "decide", "second", spec.second, state.second, now_idx_s, touched,
        record_main)

    # alt rows (origin + chain hashes): no OCCUPIED lane on alt (as before)
    if record_alt:
        alt_mask1 = pass_now | blocked_rec
        alt_mask2 = jnp.concatenate([alt_mask1, alt_mask1])
        ev_ids2 = jnp.concatenate([ev_ids1, ev_ids1])
        alt_rec = jnp.where(alt_mask2, alt_targets, pad_a)
        if fast_flow and RA <= 4096 and hist_add_fits(2 * batch.rows.shape[0]):
            # the [2B]-index scatter collides massively on the small alt
            # table; the histogram matmul is ~3x cheaper on the MXU, and
            # fast_flow's host-verified uniform acquire makes its int32
            # post-scaling bit-exact (see stats.window.bucket_add_hist)
            a_uni = jnp.max(jnp.where(batch.valid, acq, 0))

            def record_alt_rows(bucket):
                return bucket_add_hist(bucket, alt_rec, ev_ids2, a_uni)
        else:
            acq2 = jnp.concatenate([acq, acq])
            alt_amt = jnp.where(alt_mask2, acq2, 0)

            def record_alt_rows(bucket):
                return bucket_add_events(bucket, alt_rec, ev_ids2, alt_amt)
        alt_second = _record_window(
            "decide", "alt_second", spec.second, state.alt_second, now_idx_s,
            alt_targets, record_alt_rows)
    else:
        alt_second = state.alt_second

    minute = state.minute
    if spec.minute:
        minute = _record_window(
            "decide", "minute", spec.minute, state.minute, now_idx_m,
            touched, record_main)

    if skip_threads:
        # nothing loaded reads the gauges: the scatters (+ the alt half)
        # compile away — ~1/3 of the scalar step's floor
        threads = state.threads
        alt_threads = state.alt_threads
    else:
        ct1 = batch.count_thread
        thr_mask1 = passed if ct1 is None else passed & ct1
        thr_amt1 = jnp.where(thr_mask1, 1, 0)
        # +1 per entry (reference curThreadNum); leased admissions opt out
        threads = state.threads.at[
            jnp.where(passed, batch.rows, pad_r)].add(thr_amt1, mode="drop")
        threads = threads.at[ENTRY_NODE_ROW].add(
            jnp.sum(jnp.where(thr_mask1 & ein, 1, 0)))
        if record_alt:
            pass2 = jnp.concatenate([passed, passed])
            thr_amt2 = jnp.concatenate([thr_amt1, thr_amt1])
            alt_threads = state.alt_threads.at[
                jnp.where(pass2, alt_targets, pad_a)].add(thr_amt2,
                                                          mode="drop")
        else:
            alt_threads = state.alt_threads

    if spec.param_keys and batch.param_rules is not None and \
            not skip_threads:
        param_dyn = pf_mod.param_thread_update(
            rules.param_table, param_dyn, batch.param_rules, batch.param_keys,
            passed, +1)

    new_state = SentinelState(
        second=second, minute=minute, alt_second=alt_second,
        threads=threads, alt_threads=alt_threads,
        flow_dyn=flow_dyn, breakers=breakers, param_dyn=param_dyn,
        custom=custom_states, rt_hist=state.rt_hist)
    return new_state, Verdicts(allow=allow, reason=reason, wait_ms=wait_ms,
                               sf_overflow=sf_ovf if sortfree else None)


def record_exits(
    spec: EngineSpec,
    rules: RuleSet,
    state: SentinelState,
    batch: ExitBatch,
    times: jnp.ndarray,          # int32[4] (same packing as decide_entries)
    record_alt: bool = True,     # STATIC (see decide_entries)
    skip_threads: bool = False,  # STATIC (see decide_entries)
) -> SentinelState:
    """Completion step: ``StatisticSlot.exit`` (rt/success/exception, thread
    decrement, for node + origin + chain + ENTRY) then ``DegradeSlot.exit``
    (breaker feed)."""
    R = spec.rows
    RA = spec.alt_rows
    now_idx_s = times[0]
    now_idx_m = times[1]
    rel_now_ms = times[2]
    pad_r = jnp.int32(R)
    pad_a = jnp.int32(RA)

    main_rows = jnp.where(batch.valid, batch.rows, pad_r)
    alt_o = jnp.where(batch.valid, batch.origin_rows, pad_a)
    alt_c = jnp.where(batch.valid, batch.chain_rows, pad_a)
    alt_targets = jnp.concatenate([alt_o, alt_c])

    acq1 = jnp.where(batch.valid, batch.acquire, 0)
    err1 = jnp.where(batch.error, acq1, 0)
    rt1 = batch.rt_ms
    ein = batch.valid & batch.is_in

    # An exit can record BOTH SUCCESS and EXCEPTION, so the fused per-row
    # form is a full event-lane payload (one scatter instead of one per
    # event type); rt rides the same pass. The ENTRY row is a reduction +
    # one single-row update, not a second scatter half (see decide).
    n_ev = state.second.counters.shape[2]
    payload = jnp.zeros((batch.rows.shape[0], n_ev), jnp.int32)
    payload = payload.at[:, ev.SUCCESS].set(acq1)
    payload = payload.at[:, ev.EXCEPTION].set(err1)
    payload2 = jnp.concatenate([payload, payload])

    entry_vec = jnp.zeros((n_ev,), jnp.int32)
    entry_vec = entry_vec.at[ev.SUCCESS].set(jnp.sum(jnp.where(ein, acq1, 0)))
    entry_vec = entry_vec.at[ev.EXCEPTION].set(
        jnp.sum(jnp.where(ein, err1, 0)))
    # float32 BEFORE the sum: the ENTRY aggregate overflows int32 within a
    # single large batch (rt_sum is float32 for exactly this reason)
    entry_rt_add = jnp.sum(jnp.where(ein, rt1, 0).astype(jnp.float32))
    entry_rt_min = jnp.min(jnp.where(ein, rt1, jnp.iinfo(jnp.int32).max))

    def record_main(bucket):
        bucket = bucket_add_vecs(bucket, main_rows, payload, rt_ms=rt1,
                                 rt_valid=batch.valid)
        return bucket_add_row(bucket, ENTRY_NODE_ROW, entry_vec,
                              rt_add=entry_rt_add, rt_min=entry_rt_min,
                              sharded=spec.rows_sharded)

    # B=1: same ENTRY gating as decide_entries — only refresh the entry
    # row when an IN event actually lands on it this batch
    entry_refresh = jnp.where(jnp.any(ein), jnp.int32(ENTRY_NODE_ROW), pad_r)
    touched = jnp.concatenate([main_rows, entry_refresh[None]])
    second = _record_window(
        "exit", "second", spec.second, state.second, now_idx_s, touched,
        record_main)
    if record_alt:
        rt2 = jnp.concatenate([rt1, rt1])
        valid2 = jnp.concatenate([batch.valid, batch.valid])
        alt_second = _record_window(
            "exit", "alt_second", spec.second, state.alt_second, now_idx_s,
            alt_targets,
            lambda bucket: bucket_add_vecs(bucket, alt_targets, payload2,
                                           rt_ms=rt2, rt_valid=valid2))
    else:
        alt_second = state.alt_second

    minute = state.minute
    if spec.minute:
        minute = _record_window(
            "exit", "minute", spec.minute, state.minute, now_idx_m, touched,
            record_main)

    if skip_threads:
        threads = state.threads
        alt_threads = state.alt_threads
    else:
        ct1 = batch.count_thread
        dec1 = jnp.where(batch.valid if ct1 is None
                         else batch.valid & ct1, 1, 0)
        threads = state.threads.at[main_rows].add(-dec1, mode="drop")
        threads = threads.at[ENTRY_NODE_ROW].add(
            -jnp.sum(jnp.where(ein if ct1 is None else ein & ct1, 1, 0)))
        threads = jnp.maximum(threads, 0)
        if record_alt:
            dec2 = jnp.concatenate([dec1, dec1])
            alt_threads = state.alt_threads.at[alt_targets].add(-dec2,
                                                               mode="drop")
            alt_threads = jnp.maximum(alt_threads, 0)
        else:
            alt_threads = state.alt_threads

    breakers = _scoped(
        "exit.degrade.feed", deg_mod.degrade_exit_feed,
        rules.deg_table, state.breakers, rules.deg_idx, batch.rows,
        batch.rt_ms, batch.error, batch.valid, rel_now_ms)

    param_dyn = state.param_dyn
    if spec.param_keys and batch.param_rules is not None and \
            not skip_threads:
        param_dyn = pf_mod.param_thread_update(
            rules.param_table, param_dyn, batch.param_rules, batch.param_keys,
            batch.valid, -1)

    rt_hist = state.rt_hist
    if spec.hist_buckets:
        # round 20: cumulative per-resource RT histogram — one +1 per
        # valid exit at [row, log2 ms bucket]; invalid lanes ride the
        # pad row and drop. Not acquire-scaled: the table counts
        # completions (the tail shape), one sample per exit like the
        # entry-node rt aggregate, not acquire-weighted like rt_sum.
        bidx = resource_hist.bucket_index(rt1, spec.hist_buckets)
        rt_hist = rt_hist.at[main_rows, bidx].add(
            jnp.where(batch.valid, 1, 0), mode="drop")

    return SentinelState(
        second=second, minute=minute, alt_second=alt_second,
        threads=threads, alt_threads=alt_threads,
        flow_dyn=state.flow_dyn, breakers=breakers, param_dyn=param_dyn,
        custom=state.custom, rt_hist=rt_hist)


def record_blocks(
    spec: EngineSpec,
    state: SentinelState,
    rows: jnp.ndarray,
    origin_rows: jnp.ndarray,
    chain_rows: jnp.ndarray,
    acquire: jnp.ndarray,
    is_in: jnp.ndarray,
    valid: jnp.ndarray,
    times: jnp.ndarray,          # int32[4]
) -> SentinelState:
    """Record BLOCK events decided OUTSIDE the local pipeline (cluster token
    denials: the reference's StatisticSlot counts a cluster BLOCKED like any
    other BlockException)."""
    now_idx_s = times[0]
    now_idx_m = times[1]
    main_targets, alt_targets = _stat_targets(
        spec, rows, origin_rows, chain_rows, valid, is_in)
    amt = jnp.where(valid, acquire, 0)
    amt2 = jnp.concatenate([amt, amt])
    def record_main(bucket):
        return bucket_add_events(bucket, main_targets, ev.BLOCK, amt2)

    second = _record_window(
        "blocks", "second", spec.second, state.second, now_idx_s,
        main_targets, record_main)
    alt_second = _record_window(
        "blocks", "alt_second", spec.second, state.alt_second, now_idx_s,
        alt_targets,
        lambda bucket: bucket_add_events(bucket, alt_targets, ev.BLOCK, amt2))
    minute = state.minute
    if spec.minute:
        minute = _record_window(
            "blocks", "minute", spec.minute, state.minute, now_idx_m,
            main_targets, record_main)
    return state._replace(second=second, alt_second=alt_second, minute=minute)


def uncount_reserved(spec: EngineSpec, state: SentinelState,
                     rows: jnp.ndarray, sec_idx: jnp.ndarray,
                     min_idx: jnp.ndarray,
                     amounts: jnp.ndarray) -> SentinelState:
    """Return unused host-lease tokens to their window buckets: a lease
    pre-charge recorded PASS for the whole chunk up front (the admission
    ledger must see reserved tokens), so the remainder of an expired lease
    is subtracted back — pass metrics then count actual admissions, not
    reservations. Only live buckets are touched (see
    :func:`stats.window.uncount_rows`)."""
    from sentinel_tpu.stats.window import uncount_rows

    second = uncount_rows(spec.second, state.second, rows, sec_idx,
                          ev.PASS, amounts)
    minute = state.minute
    if spec.minute:
        minute = uncount_rows(spec.minute, state.minute, rows, min_idx,
                              ev.PASS, amounts)
    return state._replace(second=second, minute=minute)


def invalidate_resource_rows(spec: EngineSpec, state: SentinelState,
                             rows: jnp.ndarray,
                             alt_rows: jnp.ndarray) -> SentinelState:
    """Forget recycled rows' stats (registry eviction hygiene).

    ``alt_rows`` are the hashed (resource × origin/context) rows the evicted
    resources ever touched — without clearing them, a recycled main row whose
    (new resource, origin) pair hashes to the same alt slot would inherit the
    evicted resource's live origin counters. A hash-collided alt row shared
    with a live pair loses that pair's short-window stats too — bounded, the
    same merging the hash already implies.
    """
    second = invalidate_rows(spec.second, state.second, rows)
    minute = state.minute
    if spec.minute:
        minute = invalidate_rows(spec.minute, state.minute, rows)
    threads = state.threads.at[rows].set(0, mode="drop")
    alt_second = invalidate_rows(spec.second, state.alt_second, alt_rows)
    alt_threads = state.alt_threads.at[alt_rows].set(0, mode="drop")
    rt_hist = state.rt_hist
    if rt_hist is not None:
        # the ONLY reset path for the cumulative RT histogram (round 20)
        rt_hist = rt_hist.at[rows].set(0, mode="drop")
    # occupy bookings are keyed by resource ROW — a recycled row must not
    # inherit the evicted resource's pre-booked next-window budget
    flow_dyn = state.flow_dyn._replace(
        occupied_count=state.flow_dyn.occupied_count.at[rows].set(
            0.0, mode="drop"),
        occupied_window=state.flow_dyn.occupied_window.at[rows].set(
            -(2 ** 30), mode="drop"))
    return state._replace(second=second, minute=minute, threads=threads,
                          alt_second=alt_second, alt_threads=alt_threads,
                          flow_dyn=flow_dyn, rt_hist=rt_hist)


class ResourceRowSlice(NamedTuple):
    """One batch of demoted rows' complete per-row state — everything
    :func:`invalidate_resource_rows` destroys, gathered FIRST so the cold
    tier (sentinel_tpu/tiering/) can hold it host-side and a later
    promotion restores the row bit-identically. Window stamps and occupy
    target windows are absolute indices, so the payload needs no
    rebasing at restore time. ``alt_*`` leaves carry the hashed
    (resource × origin/context) slots the demoted resources touched —
    keyed by (kind, key id) host-side so promotion can re-hash them to
    the NEW row's slots."""

    second: WindowState            # [K, ...] per-row second-window slice
    minute: WindowState            # [K, ...] ([K, 0...] when disabled)
    threads: jnp.ndarray           # int32[K]
    occ_cnt: jnp.ndarray           # float32[K, B+1] occupy booking ring
    occ_win: jnp.ndarray           # int32[K, B+1]
    alt_second: WindowState        # [KA, ...] alt-window slices
    alt_threads: jnp.ndarray       # int32[KA]
    rt_hist: Optional[jnp.ndarray] = None   # int32[K, HB] (round 20;
    # None when the engine has no histogram table — see EngineSpec)


def extract_resource_rows(spec: EngineSpec, state: SentinelState,
                          rows: jnp.ndarray,
                          alt_rows: jnp.ndarray) -> ResourceRowSlice:
    """Gather the demotion payload for ``rows`` (+ their ``alt_rows``)
    out of the live state. Pure gathers into FRESH output buffers — safe
    to dispatch under the engine lock and read back asynchronously while
    later steps donate the state (the telemetry-tick discipline)."""
    r = rows.clip(0, spec.rows - 1)
    ra = alt_rows.clip(0, spec.alt_rows - 1)
    if spec.minute:
        minute = extract_rows(spec.minute, state.minute, rows)
    else:   # minute ring disabled: placeholder slice (ignored at restore)
        minute = extract_rows(spec.second, state.minute,
                              jnp.zeros_like(rows))
    return ResourceRowSlice(
        second=extract_rows(spec.second, state.second, rows),
        minute=minute,
        threads=state.threads[r],
        occ_cnt=state.flow_dyn.occupied_count[r],
        occ_win=state.flow_dyn.occupied_window[r],
        alt_second=extract_rows(spec.second, state.alt_second, alt_rows),
        alt_threads=state.alt_threads[ra],
        rt_hist=state.rt_hist[r] if state.rt_hist is not None else None)


def restore_resource_rows(spec: EngineSpec, state: SentinelState,
                          rows: jnp.ndarray, payload: ResourceRowSlice,
                          alt_rows: jnp.ndarray) -> SentinelState:
    """Scatter a promotion payload into freshly (re)allocated ``rows``.

    The inverse of :func:`extract_resource_rows` modulo two documented
    asymmetries: (a) ``alt_rows`` here are the NEW rows' hashed slots
    (host-side re-hash of the payload's (kind, key id) identities — a
    collision with a live pair overwrites that pair's short-window alt
    stats, the same bounded merging the hash table already implies); and
    (b) occupy bookings that straddled a rule reload while cold must be
    settled HOST-side first (tiering/coldtier.py replays the reload's
    ``settle_occupied`` with the reload's own ``now_idx``, so the
    restored ring is bit-identical to the ring the row would hold had it
    stayed resident). Padding rows >= R / alt >= RA drop."""
    second = restore_rows(spec.second, state.second, rows, payload.second)
    minute = state.minute
    if spec.minute:
        minute = restore_rows(spec.minute, state.minute, rows,
                              payload.minute)
    flow_dyn = state.flow_dyn._replace(
        occupied_count=state.flow_dyn.occupied_count.at[rows].set(
            payload.occ_cnt, mode="drop"),
        occupied_window=state.flow_dyn.occupied_window.at[rows].set(
            payload.occ_win, mode="drop"))
    rt_hist = state.rt_hist
    if rt_hist is not None and payload.rt_hist is not None:
        rt_hist = rt_hist.at[rows].set(payload.rt_hist, mode="drop")
    return state._replace(
        second=second, minute=minute,
        threads=state.threads.at[rows].set(payload.threads, mode="drop"),
        alt_second=restore_rows(spec.second, state.alt_second, alt_rows,
                                payload.alt_second),
        alt_threads=state.alt_threads.at[alt_rows].set(
            payload.alt_threads, mode="drop"),
        flow_dyn=flow_dyn, rt_hist=rt_hist)
