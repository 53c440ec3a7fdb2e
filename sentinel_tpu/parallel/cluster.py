"""Sharded cluster token engine: the TPU-native ClusterFlowChecker.

Reference semantics being reproduced (``sentinel-cluster-server-default``):

* ``ClusterFlowChecker.acquireClusterToken`` (``flow/ClusterFlowChecker.java:55-112``):
  threshold = ``calcGlobalThreshold(rule) × exceedCount`` where the global
  threshold is ``count`` (GLOBAL) or ``count × connectedCount`` (AVG_LOCAL);
  pass ⇒ add PASS/PASS_REQUEST (+OCCUPIED_PASS when prioritized); prioritized
  deficit ⇒ ``tryOccupyNext`` → SHOULD_WAIT(waitInMs) bounded by
  ``maxOccupyRatio``; else BLOCK/BLOCK_REQUEST.
* ``GlobalRequestLimiter`` (``server/connection/../GlobalRequestLimiter.java``):
  per-namespace inbound token-request QPS self-protection (default 30,000/s,
  ``ServerFlowConfig.java:26-31``) → TOO_MANY_REQUEST.
* ``ClusterMetric`` (``statistic/metric/ClusterMetric.java``): 10×100 ms
  LeapArray of ClusterFlowEvent counters — here the same
  :mod:`sentinel_tpu.stats.window` dense tensors used by the local engine.

TPU-native shape (SURVEY §2.8 north star): flow counters live in ONE window
tensor of rows = ``n_shards × flows_per_shard``, sharded over the mesh axis
``"shard"`` on the row dimension — each device owns its flows' counters, so
per-flow admission is an entirely local greedy segment scan (no collective on
the critical path). The *namespace* request-limiter counters are
shard-local tensors whose pod-global totals are combined with ``lax.psum``
over ICI inside ``shard_map`` — the reference's single-JVM global view,
rebuilt as a collective.

The host routes each token request to its flow's owner shard by batch
position (``ClusterEngine.request_tokens``); cross-shard prefix interaction in
the namespace limiter is ignored within one batch step, a bounded
over-admission of the same class the reference tolerates
(``FlowRuleChecker.java:89`` comment).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sentinel_tpu.core.pending import PendingResult, start_host_copy
from sentinel_tpu.obs import RuntimeObs
from sentinel_tpu.ops import segments as seg
from sentinel_tpu.parallel import shard_math
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    WindowSpec, WindowState, init_window, valid_mask, window_sum_all,
)

# TokenResultStatus parity (CORE/cluster/TokenResultStatus.java)
STATUS_BAD_REQUEST = -4
STATUS_TOO_MANY_REQUEST = -2
STATUS_FAIL = -1
STATUS_OK = 0
STATUS_BLOCKED = 1
STATUS_SHOULD_WAIT = 2
STATUS_NO_RULE_EXISTS = 3
STATUS_NO_REF_RULE_EXISTS = 4
STATUS_NOT_AVAILABLE = 5
STATUS_RELEASE_OK = 6
STATUS_ALREADY_RELEASE = 7

# thresholdType (ClusterRuleConstant)
THRESHOLD_AVG_LOCAL = 0
THRESHOLD_GLOBAL = 1

# ClusterMetric geometry: sampleCount 10 × interval 1000 ms
CLUSTER_WINDOW = WindowSpec(buckets=10, win_ms=100, track_rt=False)


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """Static sharded-engine geometry (hashable, closed over by jit)."""

    n_shards: int
    flows_per_shard: int          # L — flow rows owned per shard
    namespaces: int               # NS — namespace slots
    window: WindowSpec = CLUSTER_WINDOW
    param_keys_per_shard: int = 0  # PK — hot-key rows per shard (0 = off)
    max_params: int = 4            # PV — values per param request

    @property
    def total_rows(self) -> int:
        return self.n_shards * self.flows_per_shard

    @property
    def total_param_rows(self) -> int:
        return self.n_shards * max(1, self.param_keys_per_shard)


class ClusterRuleTable(NamedTuple):
    """Device rule arrays, row-sharded like the counters ([S·L])."""

    active: jnp.ndarray        # bool[S·L]
    count: jnp.ndarray         # float32 — rule threshold
    is_global: jnp.ndarray     # bool — GLOBAL vs AVG_LOCAL
    exceed: jnp.ndarray        # float32 — exceedCount factor
    max_occupy: jnp.ndarray    # float32 — maxOccupyRatio
    ns_id: jnp.ndarray         # int32 — owning namespace


class ClusterState(NamedTuple):
    flows: WindowState             # rows = S·L (sharded on rows)
    ns: WindowState                # rows = S·NS (sharded: NS local rows/shard)
    params: WindowState            # rows = S·PK — hot-key counters


class TokenBatch(NamedTuple):
    """Routed request batch, arrays [S·Bl] sharded on axis 0."""

    local_rows: jnp.ndarray    # int32 — row within the owner shard [0, L)
    acquire: jnp.ndarray       # int32
    prioritized: jnp.ndarray   # bool
    valid: jnp.ndarray         # bool
    is_param: jnp.ndarray      # bool — PARAM_FLOW request (param path)
    param_rows: jnp.ndarray    # int32[S·Bl, PV] — local key row; PK = none
    param_count: jnp.ndarray   # float32[S·Bl, PV] — raw per-value threshold


class TokenVerdicts(NamedTuple):
    status: jnp.ndarray        # int32[S·Bl] — TokenResultStatus codes
    wait_ms: jnp.ndarray       # int32[S·Bl]
    remaining: jnp.ndarray     # int32[S·Bl]


def init_cluster_state(spec: ClusterSpec) -> ClusterState:
    return ClusterState(
        flows=init_window(spec.window, spec.total_rows),
        ns=init_window(spec.window, spec.n_shards * spec.namespaces),
        params=init_window(spec.window, spec.total_param_rows),
    )


def _shard_step(
    spec: ClusterSpec,
    table: ClusterRuleTable,
    state: ClusterState,
    batch: TokenBatch,
    connected: jnp.ndarray,     # float32[NS] replicated
    ns_limit: jnp.ndarray,      # float32[NS] replicated
    now_idx: jnp.ndarray,       # int32 scalar
    in_win_ms: jnp.ndarray,     # int32 scalar — ms elapsed inside current window
) -> Tuple[ClusterState, TokenVerdicts]:
    """Per-shard body (runs under shard_map; local views)."""
    w = spec.window
    L = table.active.shape[0]       # local flow rows
    NS = spec.namespaces
    Bl = batch.local_rows.shape[0]  # local batch

    rows = jnp.where(batch.valid, batch.local_rows, 0)
    active = table.active[rows] & batch.valid
    ns_req = jnp.where(active, table.ns_id[rows], NS)  # NS = inapplicable seg

    # named scopes: HLO metadata only (op_name), so a device trace or the
    # compiled text says which stage owns an operation
    with jax.named_scope("token.ns"):
        # ---- GlobalRequestLimiter: pod-global per-namespace request QPS (psum) ----
        ns_local = window_sum_all(w, state.ns, ev.PASS, now_idx).astype(jnp.float32)
        ns_global = lax.psum(ns_local, "shard")                       # [NS]
        ns_base = jnp.concatenate([ns_global, jnp.zeros((1,), jnp.float32)])
        ns_lim = jnp.concatenate([ns_limit, jnp.full((1,), jnp.inf, jnp.float32)])

        order_ns = seg.sort_by_keys(ns_req)
        ns_s = ns_req[order_ns]
        starts_ns = seg.segment_starts(ns_s, jnp.zeros_like(ns_s))
        leader_ns = seg.segment_leader_index(starts_ns)
        ones = jnp.where(active, 1.0, 0.0)[order_ns]
        limiter_ok_s = seg.greedy_admit(ns_base[ns_s], ones, ns_lim[ns_s],
                                        starts_ns, leader_ns)
        limiter_ok = seg.unsort(order_ns, limiter_ok_s.astype(jnp.int32)).astype(jnp.bool_)
        proceed = active & limiter_ok

    with jax.named_scope("token.decide"):
        # ---- per-flow admission (ClusterFlowChecker.acquireClusterToken) ----
        flow_req = proceed & ~batch.is_param
        latest = window_sum_all(w, state.flows, ev.PASS, now_idx).astype(jnp.float32)  # [L]
        conn = connected[jnp.minimum(table.ns_id, NS - 1)]
        thr_rule = table.count * jnp.where(table.is_global, 1.0, conn) * table.exceed  # [L]

        seg_rows = jnp.where(flow_req, rows, L)  # L = never-blocking sentinel segment
        order = seg.sort_by_keys(seg_rows)
        rows_s = seg_rows[order]
        starts = seg.segment_starts(rows_s, jnp.zeros_like(rows_s))
        leader = seg.segment_leader_index(starts)
        acq_s = jnp.where(flow_req, batch.acquire, 0).astype(jnp.float32)[order]
        safe_rows_s = jnp.minimum(rows_s, L - 1)
        base_s = latest[safe_rows_s]
        lim_s = jnp.where(rows_s < L, thr_rule[safe_rows_s], jnp.inf)
        admit_s = seg.greedy_admit(base_s, acq_s, lim_s, starts, leader)
        excl_s, _ = seg.segment_prefix_sum(jnp.where(admit_s, acq_s, 0.0), starts, leader)
        remaining_s = lim_s - base_s - excl_s - acq_s
        admitted = seg.unsort(order, admit_s.astype(jnp.int32)).astype(jnp.bool_) & flow_req
        remaining = jnp.where(jnp.isfinite(remaining_s), remaining_s, 0.0)
        remaining = seg.unsort(order, remaining.astype(jnp.int32))

        # ---- occupy: prioritized deficit pre-books future windows ----
        denied = flow_req & ~admitted
        waiting_sum = window_sum_all(w, state.flows, ev.WAITING, now_idx).astype(jnp.float32)
        occupy_open = waiting_sum[rows] <= table.max_occupy[rows] * thr_rule[rows]
        # expiry scan: waiting until bucket k (stamp s_k) rotates out frees its
        # PASS count at wait = (s_k - now_idx + B)·win - in_win_ms
        stamps_req = state.flows.stamps[rows]                       # [Bl, B]
        pass_req = state.flows.counters[rows, :, ev.PASS]           # [Bl, B]
        live = valid_mask(w, stamps_req, now_idx)
        delta = jnp.where(live, stamps_req - now_idx, jnp.int32(0))  # [-B+1, 0]
        # freed(k) = sum of pass in buckets expiring no later than bucket k
        freed = jnp.sum(
            jnp.where(live[:, None, :] & (delta[:, None, :] <= delta[:, :, None]),
                      pass_req[:, None, :], 0), axis=2).astype(jnp.float32)  # [Bl, B]
        total_pass = latest[rows][:, None]
        fits = (total_pass - freed + batch.acquire[:, None].astype(jnp.float32)
                <= thr_rule[rows][:, None]) & live
        wait_k = (delta + w.buckets) * w.win_ms - in_win_ms          # [Bl, B]
        wait_k = jnp.where(fits & (wait_k > 0), wait_k, jnp.int32(2 ** 30))
        best_wait = jnp.min(wait_k, axis=1)
        should_wait = (denied & batch.prioritized & occupy_open
                       & (best_wait < 2 ** 30))
        wait_ms = jnp.where(should_wait, best_wait, 0)

        blocked = denied & ~should_wait

    with jax.named_scope("token.param"):
        # ---- hot-param admission (ClusterParamFlowChecker.acquireClusterToken) ----
        # Per-value avg vs calcGlobalThreshold; a request passes iff EVERY carried
        # value fits, and only then are all its values counted (reference
        # semantics; the host resolves per-item threshold overrides into
        # ``param_count``). Values are hashed onto PK local key rows; within one
        # batch step concurrent requests on a shared key over-admit — the same
        # check-then-act class the reference tolerates across threads.
        PK = spec.param_keys_per_shard
        is_p = proceed & batch.is_param
        pstate = state.params
        if PK:
            latest_p = window_sum_all(w, pstate, ev.PASS, now_idx).astype(jnp.float32)
            prow = batch.param_rows                               # [Bl, PV]
            live = (prow >= 0) & (prow < PK) & is_p[:, None]
            thr_p = batch.param_count * jnp.where(
                table.is_global[rows], 1.0, conn[rows])[:, None]  # [Bl, PV]
            acq_f = batch.acquire.astype(jnp.float32)[:, None]

            # within-batch exact admission: greedy segment admit over flattened
            # (request × value) rows sharing a key, like the flow path. A value
            # row admitted for a request that ultimately fails on ANOTHER value
            # still reserves quota within this batch (bounded under-admission) —
            # but its count is never recorded, so nothing leaks across steps.
            flat_keys = jnp.where(live, prow, PK).reshape(-1)     # [Bl·PV]
            order_p = seg.sort_by_keys(flat_keys)
            keys_s = flat_keys[order_p]
            starts_p = seg.segment_starts(keys_s, jnp.zeros_like(keys_s))
            leader_p = seg.segment_leader_index(starts_p)
            acq_flat_s = jnp.where(live, acq_f, 0.0).reshape(-1)[order_p]
            safe_keys_s = jnp.minimum(keys_s, PK - 1)
            base_s = latest_p[safe_keys_s]
            lim_s = jnp.where(keys_s < PK, thr_p.reshape(-1)[order_p], jnp.inf)
            ok_s = seg.greedy_admit(base_s, acq_flat_s, lim_s, starts_p, leader_p)
            excl_p, _ = seg.segment_prefix_sum(
                jnp.where(ok_s, acq_flat_s, 0.0), starts_p, leader_p)
            rem_flat_s = lim_s - base_s - excl_p - acq_flat_s
            row_ok = seg.unsort(order_p, ok_s.astype(jnp.int32)).reshape(
                (Bl, -1)).astype(jnp.bool_)
            rem_flat = seg.unsort(
                order_p, jnp.where(jnp.isfinite(rem_flat_s), rem_flat_s, 0.0)
            ).reshape((Bl, -1))

            any_live = jnp.any(live, axis=1)
            all_ok = jnp.all(row_ok | ~live, axis=1)
            param_pass = is_p & (all_ok | ~any_live)
            param_block = is_p & any_live & ~all_ok
            # remaining meaningful only for single-value requests (host packs
            # values densely from column 0); multi-value → -1 like the reference
            nlive = jnp.sum(live.astype(jnp.int32), axis=1)
            rem1 = jnp.maximum(rem_flat[:, 0], 0.0)
            rem_p = jnp.where(nlive == 1, rem1, -1.0).astype(jnp.int32)

            from sentinel_tpu.stats.window import add_rows as _add, refresh_rows as _refresh
            flat = jnp.where(live & param_pass[:, None], prow, PK).reshape(-1)
            pstate = _refresh(w, pstate, flat, now_idx)
            pstate = _add(w, pstate, flat, ev.PASS,
                          jnp.where(live & param_pass[:, None],
                                    batch.acquire[:, None], 0).reshape(-1), now_idx)
        else:
            param_pass = is_p          # param slot disabled: empty-values → OK
            param_block = jnp.zeros_like(is_p)
            rem_p = jnp.full((Bl,), -1, jnp.int32)

    # ---- record (post-decision, like StatisticSlot ordering) ----
    pad = jnp.int32(L)
    def tgt(mask):
        return jnp.where(mask, rows, pad)

    flows = state.flows
    from sentinel_tpu.stats.window import add_rows, refresh_rows
    acq = batch.acquire
    with jax.named_scope("token.refresh"):
        flows = refresh_rows(w, flows, tgt(proceed), now_idx)
    with jax.named_scope("token.add.pass"):
        flows = add_rows(w, flows, tgt(admitted), ev.PASS,
                         jnp.where(admitted, acq, 0), now_idx)
        flows = add_rows(w, flows, tgt(admitted), ev.PASS_REQUEST,
                         jnp.where(admitted, 1, 0), now_idx)
        flows = add_rows(w, flows, tgt(admitted & batch.prioritized),
                         ev.OCCUPIED_PASS,
                         jnp.where(admitted & batch.prioritized, acq, 0),
                         now_idx)
    with jax.named_scope("token.add.block"):
        flows = add_rows(w, flows, tgt(blocked), ev.BLOCK,
                         jnp.where(blocked, acq, 0), now_idx)
        flows = add_rows(w, flows, tgt(blocked), ev.BLOCK_REQUEST,
                         jnp.where(blocked, 1, 0), now_idx)
    with jax.named_scope("token.add.wait"):
        flows = add_rows(w, flows, tgt(should_wait), ev.WAITING,
                         jnp.where(should_wait, acq, 0), now_idx)

    with jax.named_scope("token.ns"):
        ns_state = state.ns
        ns_state = refresh_rows(w, ns_state, ns_req, now_idx)
        ns_state = add_rows(w, ns_state,
                            jnp.where(proceed, ns_req, jnp.int32(NS)),
                            ev.PASS, jnp.where(proceed, 1, 0), now_idx)
        ns_state = add_rows(w, ns_state,
                            jnp.where(active & ~limiter_ok, ns_req,
                                      jnp.int32(NS)),
                            ev.BLOCK, jnp.where(active & ~limiter_ok, 1, 0),
                            now_idx)

    status = jnp.full((Bl,), STATUS_FAIL, jnp.int32)
    status = jnp.where(batch.valid & ~table.active[rows], STATUS_NO_RULE_EXISTS, status)
    status = jnp.where(active & ~limiter_ok, STATUS_TOO_MANY_REQUEST, status)
    status = jnp.where(blocked, STATUS_BLOCKED, status)
    status = jnp.where(should_wait, STATUS_SHOULD_WAIT, status)
    status = jnp.where(admitted, STATUS_OK, status)
    status = jnp.where(param_block, STATUS_BLOCKED, status)
    status = jnp.where(param_pass, STATUS_OK, status)

    remaining = jnp.where(admitted, jnp.maximum(remaining, 0), 0)
    remaining = jnp.where(param_pass | param_block,
                          jnp.where(param_pass, rem_p, 0), remaining)
    verdicts = TokenVerdicts(
        status=status,
        wait_ms=wait_ms.astype(jnp.int32),
        remaining=remaining.astype(jnp.int32))
    return ClusterState(flows=flows, ns=ns_state, params=pstate), verdicts


@dataclasses.dataclass
class ClusterParamFlowRule:
    """Cluster hot-param rule (reference ``ParamFlowRule`` cluster fields:
    flowId, thresholdType, count, plus exclusive per-item thresholds —
    ``parsedHotItems``)."""

    flow_id: int
    count: float
    threshold_type: int = THRESHOLD_AVG_LOCAL
    items: Optional[Dict[object, float]] = None

    def value_threshold(self, value: object) -> float:
        if self.items is not None:
            override = self.items.get(value)
            if override is not None:
                return float(override)
        return float(self.count)


@dataclasses.dataclass
class ClusterFlowRule:
    """Host-facing cluster rule (reference ``FlowRule`` cluster fields +
    ``ClusterFlowConfig``: flowId, thresholdType, count; exceedCount and
    maxOccupyRatio come from ``ClusterServerConfigManager`` server-wide but are
    kept per-rule here, defaulting to the reference's 1.0/1.0)."""

    flow_id: int
    count: float
    threshold_type: int = THRESHOLD_AVG_LOCAL
    exceed_count: float = 1.0
    max_occupy_ratio: float = 1.0


class PendingTokenResults(PendingResult):
    """Handle for an in-flight token batch: the device step is already
    dispatched (and the verdict transfer started async); :meth:`result`
    materializes the aligned ``(status, wait_ms, remaining)`` list. Lets
    callers double-buffer — dispatch batch N+1 while batch N's verdicts are
    still in flight over the host link."""

    __slots__ = ()


def _start_host_copy(verdicts: "TokenVerdicts") -> None:
    start_host_copy((verdicts.status, verdicts.wait_ms, verdicts.remaining))


class ClusterEngine:
    """Host facade: flow routing, namespace management, the sharded step.

    The reference's ``ClusterFlowRuleManager`` (flowId→rule, namespace→flowIds,
    per-namespace property suppliers) + ``DefaultTokenService`` dispatch,
    collapsed onto dense sharded tensors.
    """

    def __init__(self, spec: ClusterSpec, mesh: Optional[Mesh] = None,
                 default_ns_qps: float = 30_000.0,
                 obs: Optional[RuntimeObs] = None):
        self.spec = spec
        # phases of the engine call (token.route / put / dispatch /
        # readback / gather; docs/OBSERVABILITY.md) and the token
        # server's cycle record here: ClusterTokenServer uses engine.obs
        self.obs = obs if obs is not None else RuntimeObs()
        if mesh is None:
            devs = jax.devices()[:spec.n_shards]
            if len(devs) < spec.n_shards:
                raise ValueError(
                    f"need {spec.n_shards} devices, have {len(devs)}")
            mesh = Mesh(np.array(devs), ("shard",))
        self.mesh = mesh
        # Multi-process mesh (multihost/): state + batches shard across
        # processes; readbacks then go through a cross-process allgather
        # instead of np.asarray (a host can only address its own shards).
        # Rule loads / connected counts MUST be replayed identically on
        # every participating process — the mesh is SPMD, every process
        # executes every step (multihost/ingest.py drives this).
        self._multiprocess = len(
            {d.process_index for d in np.ravel(mesh.devices)}) > 1
        self._sh_rows = NamedSharding(mesh, P("shard"))
        self._sh_rep = NamedSharding(mesh, P())

        self._flow_to_row: Dict[int, int] = {}
        self._row_to_flow: Dict[int, int] = {}
        self._ns_ids: Dict[str, int] = {}
        self._flow_ns: Dict[int, str] = {}
        self._rules: Dict[int, ClusterFlowRule] = {}
        self._param_rules: Dict[int, ClusterParamFlowRule] = {}
        self._fid_lookup = None       # dense fid→row (vectorized prep)
        # host-side hot-value sightings per param flow for metricList's
        # topParams (ClusterParamMetric.getTopValues analog): fid →
        # {value: count} over the current window, previous window kept so
        # a read right after rotation isn't empty
        self._param_hits: Dict[int, Dict[object, int]] = {}
        self._param_hits_prev: Dict[int, Dict[object, int]] = {}
        self._param_hits_win = -1
        self._param_hits_cap = 64     # values tracked per flow (LRU-ish)
        self._connected = np.ones(spec.namespaces, np.float32)
        self._default_ns_qps = float(default_ns_qps)
        self._ns_limit = np.full(spec.namespaces, default_ns_qps, np.float32)
        self._next_row_per_shard = [0] * spec.n_shards
        self._free_rows: List[List[int]] = [[] for _ in range(spec.n_shards)]
        self._rr = 0  # round-robin shard cursor for row allocation
        self._lock = threading.RLock()  # guards state swap (donated buffers),
        # routing tables, and rule reloads against concurrent server threads

        self.state = jax.device_put(init_cluster_state(spec), self._sh_rows)
        self._table = self._empty_table()
        self._step = self._build_step()
        self._row_gather = None  # lazy jitted row snapshot (multiprocess)

    # ------------------------------------------------------------------
    def _empty_table(self) -> ClusterRuleTable:
        n = self.spec.total_rows
        z = np.zeros(n, np.float32)
        return jax.device_put(ClusterRuleTable(
            active=jnp.asarray(np.zeros(n, np.bool_)),
            count=jnp.asarray(z), is_global=jnp.asarray(np.zeros(n, np.bool_)),
            exceed=jnp.asarray(np.ones(n, np.float32)),
            max_occupy=jnp.asarray(np.ones(n, np.float32)),
            ns_id=jnp.asarray(np.zeros(n, np.int32))), self._sh_rows)

    def _build_step(self):
        spec = self.spec
        mesh = self.mesh
        body = functools.partial(_shard_step, spec)
        row_spec = P("shard")
        state_specs = ClusterState(
            flows=WindowState(*([row_spec] * 4)), ns=WindowState(*([row_spec] * 4)),
            params=WindowState(*([row_spec] * 4)))
        table_specs = ClusterRuleTable(*([row_spec] * 6))
        batch_specs = TokenBatch(*([row_spec] * 7))
        sm = shard_map(
            body, mesh=mesh,
            in_specs=(table_specs, state_specs, batch_specs, P(), P(), P(), P()),
            out_specs=(state_specs, TokenVerdicts(row_spec, row_spec, row_spec)),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    # ------------------------------------------------------------------
    # Namespace / rule management
    # ------------------------------------------------------------------

    def namespace_id(self, namespace: str) -> int:
        nid = self._ns_ids.get(namespace)
        if nid is None:
            if len(self._ns_ids) >= self.spec.namespaces:
                raise ValueError("namespace capacity exceeded")
            nid = len(self._ns_ids)
            self._ns_ids[namespace] = nid
        return nid

    def set_connected_count(self, namespace: str, count: int) -> None:
        """ConnectionManager.getConnectedCount feed for AVG_LOCAL thresholds."""
        with self._lock:
            self._connected[self.namespace_id(namespace)] = max(1, count)
        # connected counts are replicated scalars; no table rebuild needed

    def set_namespace_qps_limit(self, namespace: str, limit: float) -> None:
        """ServerFlowConfig.maxAllowedQps per namespace (hot-tunable)."""
        with self._lock:
            self._ns_limit[self.namespace_id(namespace)] = limit

    def namespace_qps_limit(self, namespace: str, *,
                            create: bool = True) -> float:
        """Per-namespace maxAllowedQps. ``create=False`` is a pure read: an
        unregistered namespace returns the default limit without consuming
        one of the ``spec.namespaces`` slots (read-only command-plane
        fetches must not allocate capacity)."""
        with self._lock:
            nid = self._ns_ids.get(namespace)
            if nid is None:
                if not create:
                    return float(self._default_ns_qps)
                nid = self.namespace_id(namespace)
            return float(self._ns_limit[nid])

    def namespace_flow_ids(self, namespace: str) -> List[int]:
        """Flow ids registered under a namespace (flow + param rules)."""
        with self._lock:
            return sorted(fid for fid, ns in self._flow_ns.items()
                          if ns == namespace)

    def namespace_rules(self, namespace: str, *, param: bool = False
                        ) -> Dict[int, object]:
        """Read-only snapshot {flow_id: rule} of what this engine ENFORCES
        for a namespace — ``param=False`` → :class:`ClusterFlowRule` entries
        (excluding param-rule proxy rows), ``param=True`` →
        :class:`ClusterParamFlowRule` entries. The supported surface for
        command-plane fetch/metricList (don't reach into ``_rules``)."""
        with self._lock:
            store = self._param_rules if param else self._rules
            return {fid: store[fid]
                    for fid, ns in sorted(self._flow_ns.items())
                    if ns == namespace and fid in store
                    and (param or fid not in self._param_rules)}

    def load_rules(self, namespace: str, rules: Sequence[ClusterFlowRule]) -> None:
        """Replace the namespace's rules (ClusterFlowRuleManager property path).

        Rows of removed flows go to a free list for reuse; their window state
        is invalidated immediately so a reused row can't inherit the dead
        flow's live counters.
        """
        with self._lock:
            self.namespace_id(namespace)
            freed: List[int] = []
            for fid, ns in list(self._flow_ns.items()):
                if (ns == namespace and fid not in {r.flow_id for r in rules}
                        and fid not in self._param_rules):
                    row = self._flow_to_row.pop(fid)
                    self._row_to_flow.pop(row, None)
                    self._flow_ns.pop(fid)
                    self._rules.pop(fid, None)
                    self._free_rows[row // self.spec.flows_per_shard].append(row)
                    freed.append(row)
            for r in rules:
                if r.flow_id not in self._flow_to_row:
                    self._flow_to_row[r.flow_id] = self._alloc_row()
                    self._row_to_flow[self._flow_to_row[r.flow_id]] = r.flow_id
                self._flow_ns[r.flow_id] = namespace
                self._rules[r.flow_id] = r
            if freed:
                from sentinel_tpu.stats.window import invalidate_rows
                self.state = self.state._replace(flows=invalidate_rows(
                    self.spec.window, self.state.flows,
                    jnp.asarray(np.asarray(freed, np.int32))))
            self._rebuild_table()

    def load_param_rules(self, namespace: str,
                         rules: Sequence["ClusterParamFlowRule"]) -> None:
        """Replace the namespace's hot-param rules
        (ClusterParamFlowRuleManager property path). Requires
        ``spec.param_keys_per_shard > 0``."""
        if self.spec.param_keys_per_shard <= 0 and rules:
            raise ValueError("engine built without param key capacity")
        with self._lock:
            self.namespace_id(namespace)
            new_ids = {r.flow_id for r in rules}
            freed: List[int] = []
            for fid, ns in list(self._flow_ns.items()):
                if (ns == namespace and fid in self._param_rules
                        and fid not in new_ids):
                    row = self._flow_to_row.pop(fid)
                    self._row_to_flow.pop(row, None)
                    self._flow_ns.pop(fid)
                    self._rules.pop(fid, None)
                    self._param_rules.pop(fid, None)
                    self._free_rows[row // self.spec.flows_per_shard].append(row)
                    freed.append(row)
            if freed:
                from sentinel_tpu.stats.window import invalidate_rows
                self.state = self.state._replace(flows=invalidate_rows(
                    self.spec.window, self.state.flows,
                    jnp.asarray(np.asarray(freed, np.int32))))
            for r in rules:
                if r.flow_id not in self._flow_to_row:
                    self._flow_to_row[r.flow_id] = self._alloc_row()
                    self._row_to_flow[self._flow_to_row[r.flow_id]] = r.flow_id
                self._flow_ns[r.flow_id] = namespace
                self._param_rules[r.flow_id] = r
                # proxy row in the rule table: ns routing + GLOBAL/AVG flag
                self._rules[r.flow_id] = ClusterFlowRule(
                    flow_id=r.flow_id, count=r.count,
                    threshold_type=r.threshold_type)
            self._rebuild_table()

    def _param_key(self, flow_id: int, value: object) -> int:
        """Stable (process-independent) hash of a param value onto the owner
        shard's PK key rows. Type-tagged so ``1`` and ``"1"`` stay distinct."""
        import hashlib

        tag = f"{flow_id}|{type(value).__name__}|{value!r}".encode()
        h = hashlib.blake2s(tag, digest_size=8).digest()
        return int.from_bytes(h, "little") % self.spec.param_keys_per_shard

    def request_param_tokens(self, flow_ids: Sequence[int],
                             acquire: Sequence[int],
                             params: Sequence[Sequence[object]],
                             *, now_ms: int) -> List[Tuple[int, int, int]]:
        """Batched ``TokenService.requestParamToken`` → ``(status, wait_ms,
        remaining)`` per request. Values beyond ``spec.max_params`` per
        request are dropped (cap documented on :class:`ClusterSpec`)."""
        return self.request_param_tokens_nowait(
            flow_ids, acquire, params, now_ms=now_ms).result()

    def request_param_tokens_nowait(
            self, flow_ids: Sequence[int], acquire: Sequence[int],
            params: Sequence[Sequence[object]],
            *, now_ms: int) -> PendingTokenResults:
        """Dispatch-only variant: the sharded step is enqueued and the
        verdict readback deferred to ``.result()`` so callers can overlap
        batch N's readback with batch N+1's host prep + dispatch."""
        from sentinel_tpu.core.batching import pad_pow2

        n = len(flow_ids)
        S = self.spec.n_shards
        L = self.spec.flows_per_shard
        PV = self.spec.max_params
        PK = self.spec.param_keys_per_shard

        with self._lock:
            per_shard: List[List[int]] = [[] for _ in range(S)]
            results: List[Optional[Tuple[int, int, int]]] = [None] * n
            for i, fid in enumerate(flow_ids):
                rule = self._param_rules.get(int(fid))
                if acquire[i] <= 0:
                    results[i] = (STATUS_BAD_REQUEST, 0, 0)
                elif rule is None:
                    results[i] = (STATUS_NO_RULE_EXISTS, 0, 0)
                elif not params[i]:
                    results[i] = (STATUS_OK, 0, 0)   # empty values pass
                else:
                    per_shard[self._flow_to_row[int(fid)] // L].append(i)

            bl = max((len(p) for p in per_shard), default=0)
            if bl == 0:
                out = [r or (STATUS_FAIL, 0, 0) for r in results]
                return PendingTokenResults(lambda: out)
            blp = pad_pow2(bl)

            rows = np.zeros((S, blp), np.int32)
            acq = np.zeros((S, blp), np.int32)
            valid = np.zeros((S, blp), np.bool_)
            is_param = np.zeros((S, blp), np.bool_)
            prow = np.full((S, blp, PV), PK, np.int32)
            pcnt = np.zeros((S, blp, PV), np.float32)
            win = now_ms // (self.spec.window.win_ms
                             * self.spec.window.buckets)
            if win != self._param_hits_win:
                self._param_hits_prev = self._param_hits
                self._param_hits = {}
                self._param_hits_win = win
            for s in range(S):
                for k, i in enumerate(per_shard[s]):
                    fid = int(flow_ids[i])
                    rule = self._param_rules[fid]
                    rows[s, k] = self._flow_to_row[fid] % L
                    acq[s, k] = acquire[i]
                    valid[s, k] = True
                    is_param[s, k] = True
                    hits = self._param_hits.setdefault(fid, {})
                    for j, v in enumerate(list(params[i])[:PV]):
                        prow[s, k, j] = self._param_key(fid, v)
                        pcnt[s, k, j] = rule.value_threshold(v)
                        if v in hits or len(hits) < self._param_hits_cap:
                            hits[v] = hits.get(v, 0) + int(acquire[i])

            batch = jax.device_put(TokenBatch(
                local_rows=jnp.asarray(rows.reshape(-1)),
                acquire=jnp.asarray(acq.reshape(-1)),
                prioritized=jnp.asarray(np.zeros((S * blp,), np.bool_)),
                valid=jnp.asarray(valid.reshape(-1)),
                is_param=jnp.asarray(is_param.reshape(-1)),
                param_rows=jnp.asarray(prow.reshape(S * blp, PV)),
                param_count=jnp.asarray(pcnt.reshape(S * blp, PV))),
                self._sh_rows)

            w = self.spec.window
            now_idx = jnp.int32(w.index_of(now_ms))
            in_win = jnp.int32(now_ms % w.win_ms)
            self.state, verdicts = self._step(
                self._table, self.state, batch,
                jax.device_put(jnp.asarray(self._connected), self._sh_rep),
                jax.device_put(jnp.asarray(self._ns_limit), self._sh_rep),
                now_idx, in_win)
        self._maybe_start_host_copy(verdicts)
        return PendingTokenResults(functools.partial(
            self._gather_results, verdicts, per_shard, results, S, blp))

    def _gather_results(self, verdicts, per_shard, results, S, blp):
        """Deferred readback: materialize the verdict arrays and scatter
        them back into request order (shared by flow + param paths)."""
        n = len(results)
        with self.obs.phase("token.readback", n=n):
            st = self._to_host(verdicts.status).reshape(S, blp)
            wt = self._to_host(verdicts.wait_ms).reshape(S, blp)
            rm = self._to_host(verdicts.remaining).reshape(S, blp)
        with self.obs.phase("token.gather", n=n):
            for s in range(S):
                for k, i in enumerate(per_shard[s]):
                    results[i] = (int(st[s, k]), int(wt[s, k]),
                                  int(rm[s, k]))
            return [r or (STATUS_FAIL, 0, 0) for r in results]

    def _alloc_row(self) -> int:
        L = self.spec.flows_per_shard
        for _ in range(self.spec.n_shards):
            s = self._rr
            self._rr = (self._rr + 1) % self.spec.n_shards
            if self._free_rows[s]:
                return self._free_rows[s].pop()
            if self._next_row_per_shard[s] < L:
                local = self._next_row_per_shard[s]
                self._next_row_per_shard[s] += 1
                return s * L + local
        raise ValueError("cluster flow capacity exceeded")

    def _rebuild_fid_lookup(self) -> None:
        """Dense flow-id → global-row array for the vectorized request
        prep; None when ids are sparse enough that the array would waste
        memory (the loop path then resolves through the dict)."""
        self._fid_lookup = None
        if not self._flow_to_row:
            return
        if min(self._flow_to_row) < 0:
            return        # negative fids route via the dict; array can't
        max_fid = max(self._flow_to_row)
        if max_fid < max(1 << 20, 4 * len(self._flow_to_row)):
            lut = np.full(max_fid + 1, -1, np.int64)
            for fid, row in self._flow_to_row.items():
                lut[fid] = row
            self._fid_lookup = lut

    def _rebuild_table(self) -> None:
        self._rebuild_fid_lookup()
        n = self.spec.total_rows
        active = np.zeros(n, np.bool_)
        count = np.zeros(n, np.float32)
        is_global = np.zeros(n, np.bool_)
        exceed = np.ones(n, np.float32)
        max_occ = np.ones(n, np.float32)
        ns_id = np.zeros(n, np.int32)
        for fid, row in self._flow_to_row.items():
            r = self._rules[fid]
            active[row] = True
            count[row] = r.count
            is_global[row] = r.threshold_type == THRESHOLD_GLOBAL
            exceed[row] = r.exceed_count
            max_occ[row] = r.max_occupy_ratio
            ns_id[row] = self._ns_ids[self._flow_ns[fid]]
        self._table = jax.device_put(ClusterRuleTable(
            active=jnp.asarray(active), count=jnp.asarray(count),
            is_global=jnp.asarray(is_global), exceed=jnp.asarray(exceed),
            max_occupy=jnp.asarray(max_occ), ns_id=jnp.asarray(ns_id)),
            self._sh_rows)

    # ------------------------------------------------------------------
    # Token requests
    # ------------------------------------------------------------------

    def request_tokens(self, flow_ids: Sequence[int], acquire: Sequence[int],
                       prioritized: Optional[Sequence[bool]] = None,
                       *, now_ms: int) -> List[Tuple[int, int, int]]:
        """Batched ``TokenService.requestToken`` → list of
        ``(status, wait_ms, remaining)`` aligned with the inputs."""
        return self.request_tokens_nowait(
            flow_ids, acquire, prioritized, now_ms=now_ms).result()

    def request_tokens_nowait(self, flow_ids: Sequence[int],
                              acquire: Sequence[int],
                              prioritized: Optional[Sequence[bool]] = None,
                              *, now_ms: int) -> PendingTokenResults:
        """Dispatch-only ``requestToken``: enqueue the sharded step, start
        the async device→host verdict copy, and defer materialization to
        ``.result()`` — the double-buffered front-end the serving path uses
        to hide readback latency (state updates still apply in dispatch
        order under the engine lock)."""
        from sentinel_tpu.core.batching import pad_pow2

        n = len(flow_ids)
        S = self.spec.n_shards
        L = self.spec.flows_per_shard

        with self._lock:
            with self.obs.phase("token.route", n=n):
                vec = self._vector_prep(flow_ids, acquire, prioritized, n, S, L)
                if vec is not None:
                    prep, gather = vec
                    if prep is None:        # nothing routable: results are final
                        return PendingTokenResults(lambda: gather)
                    rows, acq, prio, valid, blp = prep
                else:
                    if prioritized is None:     # numpy arrays: no truthiness
                        prioritized = [False] * n
                    per_shard: List[List[int]] = [[] for _ in range(S)]
                    results: List[Optional[Tuple[int, int, int]]] = [None] * n
                    for i, fid in enumerate(flow_ids):
                        row = self._flow_to_row.get(int(fid))
                        if acquire[i] <= 0:
                            # DefaultTokenService.requestToken count validation
                            results[i] = (STATUS_BAD_REQUEST, 0, 0)
                        elif row is None:
                            results[i] = (STATUS_NO_RULE_EXISTS, 0, 0)
                        else:
                            per_shard[row // L].append(i)

                    bl = max((len(p) for p in per_shard), default=0)
                    if bl == 0:
                        out = [r or (STATUS_FAIL, 0, 0) for r in results]
                        return PendingTokenResults(lambda: out)
                    blp = pad_pow2(bl)

                    rows = np.zeros((S, blp), np.int32)
                    acq = np.zeros((S, blp), np.int32)
                    prio = np.zeros((S, blp), np.bool_)
                    valid = np.zeros((S, blp), np.bool_)
                    for s in range(S):
                        for k, i in enumerate(per_shard[s]):
                            rows[s, k] = self._flow_to_row[int(flow_ids[i])] % L
                            acq[s, k] = acquire[i]
                            prio[s, k] = bool(prioritized[i])
                            valid[s, k] = True

            verdicts = self.step_routed(rows, acq, prio, valid, blp,
                                        now_ms=now_ms)
        if vec is not None:
            return PendingTokenResults(functools.partial(
                self._gather_results_vec, verdicts, gather, blp))
        return PendingTokenResults(functools.partial(
            self._gather_results, verdicts, per_shard, results, S, blp))

    def step_routed(self, rows, acq, prio, valid, blp: int, *,
                    now_ms: int) -> TokenVerdicts:
        """Run the sharded device step on pre-routed ``[S, Bl]`` lanes
        (``shard_math.route_requests`` layout) and return the raw sharded
        verdicts; scatter back with ``shard_math.scatter_verdicts``.

        This is the SPMD choke point shared by the single-process request
        paths and :mod:`sentinel_tpu.multihost.ingest`. In a multi-process
        mesh every participating process must call it with the SAME
        geometry (``blp``), ``now_ms`` and routing plan — only the lanes
        of shards this host owns need real payload data (``device_put``
        materializes local shards only); read verdicts back via
        :meth:`_gather_results_vec` / ``_to_host``.
        """
        S = self.spec.n_shards
        PV = self.spec.max_params
        PK = self.spec.param_keys_per_shard
        obs = self.obs
        lanes = S * blp
        with self._lock:
            with obs.phase("token.put", n=lanes):
                batch = self._put_rows(TokenBatch(
                    local_rows=rows.reshape(-1).astype(np.int32),
                    acquire=acq.reshape(-1).astype(np.int32),
                    prioritized=prio.reshape(-1).astype(np.bool_),
                    valid=valid.reshape(-1).astype(np.bool_),
                    is_param=np.zeros((lanes,), np.bool_),
                    param_rows=np.full((lanes, PV), PK, np.int32),
                    param_count=np.zeros((lanes, PV), np.float32)))

                w = self.spec.window
                if self._multiprocess:
                    # scalars must be placed on every process's local
                    # devices (an uncommitted single-device array is not
                    # addressable by the other hosts of the global mesh)
                    now_idx = jax.device_put(
                        np.int32(w.index_of(now_ms)), self._sh_rep)
                    in_win = jax.device_put(
                        np.int32(now_ms % w.win_ms), self._sh_rep)
                else:
                    now_idx = jnp.int32(w.index_of(now_ms))
                    in_win = jnp.int32(now_ms % w.win_ms)
                connected = jax.device_put(
                    jnp.asarray(self._connected), self._sh_rep)
                ns_limit = jax.device_put(
                    jnp.asarray(self._ns_limit), self._sh_rep)
            # the verdicts' host copy starts under the lock now: the one
            # single-process caller holds this reentrant lock around the
            # call already, and multi-process readback starts no copy
            with obs.phase("token.dispatch", n=lanes):
                self.state, verdicts = self._step(
                    self._table, self.state, batch, connected, ns_limit,
                    now_idx, in_win)
                self._maybe_start_host_copy(verdicts)
        return verdicts

    def _put_rows(self, tree):
        """Place a host pytree on the row sharding. Multi-process meshes
        need ``make_array_from_callback`` — each process materializes its
        OWN shards from its own host arrays (``device_put`` would instead
        assert the value is identical on every process, defeating
        host-local ingestion where non-local lanes hold garbage/zeros)."""
        if not self._multiprocess:
            return jax.device_put(tree, self._sh_rows)
        return jax.tree.map(
            lambda x: jax.make_array_from_callback(
                x.shape, self._sh_rows, lambda idx, x=x: x[idx]), tree)

    def _maybe_start_host_copy(self, verdicts: TokenVerdicts) -> None:
        # The async device→host prefetch only works on fully-addressable
        # arrays; multi-process readback goes through _to_host's
        # allgather instead.
        if not self._multiprocess:
            _start_host_copy(verdicts)

    def _to_host(self, x) -> np.ndarray:
        """Materialize a possibly cross-process row-sharded array."""
        if self._multiprocess:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(
                x, tiled=True))
        return np.asarray(x)

    def rows_for_flows(self, flow_ids) -> Optional[np.ndarray]:
        """Global row per flow id (``-1`` = unregistered), vectorized via
        the dense lookup when possible. → None for sparse/non-int ids
        (callers fall back to the dict path). The row→shard math on the
        result is :mod:`~sentinel_tpu.parallel.shard_math`'s."""
        lut = self._fid_lookup
        if lut is None:
            return None
        ids = np.asarray(flow_ids)
        if ids.dtype.kind not in "iu" or ids.ndim != 1:
            return None
        in_rng = (ids >= 0) & (ids < lut.shape[0])
        return np.where(in_rng, lut[np.clip(ids, 0, lut.shape[0] - 1)], -1)

    def _vector_prep(self, flow_ids, acquire, prioritized, n: int, S: int,
                     L: int):
        """Vectorized request grouping (shard_math.route_requests): one
        argsort + scatter instead of per-event dict/append loops. → None
        to fall back to the loop path (sparse ids, non-int input), or
        ``(prep_arrays_or_None, gather_ctx_or_final_results)``."""
        if n == 0:
            return None
        rowg = self.rows_for_flows(flow_ids)
        if rowg is None:
            return None
        lanes, plan = shard_math.route_requests(
            rowg, acquire, prioritized, S, L,
            status_fail=STATUS_FAIL, status_bad=STATUS_BAD_REQUEST,
            status_no_rule=STATUS_NO_RULE_EXISTS)
        if lanes is None:
            return (None, [(int(s), 0, 0) for s in plan.status0])
        return ((lanes.rows, lanes.acquire, lanes.prioritized, lanes.valid,
                 lanes.lanes), plan)

    def _gather_results_vec(self, verdicts, plan, blp):
        """Vectorized inverse of :meth:`_vector_prep`'s grouping."""
        n = plan.status0.shape[0]
        with self.obs.phase("token.readback", n=n):
            status = self._to_host(verdicts.status)
            wait_ms = self._to_host(verdicts.wait_ms)
            remaining = self._to_host(verdicts.remaining)
        with self.obs.phase("token.gather", n=n):
            return shard_math.scatter_verdicts(
                plan, blp, status, wait_ms, remaining, self.spec.n_shards)

    def top_params(self, flow_id: int, *, now_ms: int,
                   top_n: int = 10) -> Dict[object, int]:
        """Most-requested param values of a flow over the current (or,
        right after a rotation, the previous) window — feeds metricList's
        ``topParams`` (``ClusterParamMetric.getTopValues``). Counts are
        REQUESTED acquire sums, host-observed; grant/deny split stays in
        the device counters."""
        with self._lock:
            win = now_ms // (self.spec.window.win_ms
                             * self.spec.window.buckets)
            if win - self._param_hits_win > 1:
                return {}            # tracker is stale by more than a window
            hits = (self._param_hits.get(flow_id)
                    or self._param_hits_prev.get(flow_id) or {})
            return dict(sorted(hits.items(), key=lambda kv: -kv[1])[:top_n])

    def _row_snapshot(self, row: int):
        """``(counters[row], stamps[row])`` of the flow window state. In a
        multi-process mesh a host can't index shards it doesn't own, so
        the row is gathered on-device to a replicated output — which also
        means every process must call this collectively (SPMD), same as
        the step itself."""
        if not self._multiprocess:
            return (np.asarray(self.state.flows.counters[row]),
                    np.asarray(self.state.flows.stamps[row]))
        if self._row_gather is None:
            self._row_gather = jax.jit(
                lambda c, s, r: (c[r], s[r]), out_shardings=self._sh_rep)
        c, s = self._row_gather(self.state.flows.counters,
                                self.state.flows.stamps, row)
        return np.asarray(c), np.asarray(s)

    def flow_metrics(self, flow_id: int, *, now_ms: int) -> dict:
        """Per-flow current-window snapshot (ClusterMetricNodeGenerator)."""
        with self._lock:
            row = self._flow_to_row.get(flow_id)
            if row is None:
                return {}
            w = self.spec.window
            now_idx = jnp.int32(w.index_of(now_ms))
            counters, stamps = self._row_snapshot(row)  # [B, E], [B]
        delta = (int(now_idx) - stamps.astype(np.int64)).astype(np.int32)
        live = (delta >= 0) & (delta < w.buckets)
        tot = np.where(live[:, None], counters, 0).sum(axis=0)
        return {name: int(tot[i]) for i, name in enumerate(ev.NAMES)}
