"""Host runtime: the public facade (SphU/SphO/Tracer analog) around the
jitted decision pipeline.

The reference's hot path is an in-process method call
(``SphU.entry → CtSph.entryWithPriority``, SURVEY §3.1); here a guarded call
becomes one device step. Two API tiers:

* :meth:`Sentinel.entry` — per-call context-manager parity with
  ``try (Entry e = SphU.entry(name)) { ... }``: pads the event into a small
  fixed batch, runs the decide step, raises a
  :class:`~sentinel_tpu.core.errors.BlockException` subclass on deny, sleeps
  on pass-with-wait (RateLimiter verdicts). Convenient, correct, ~one device
  round-trip of latency.
* :meth:`Sentinel.entry_batch` / :meth:`Sentinel.exit_batch` — the throughput
  tier: numpy arrays in, verdict arrays out; this is what adapters, the
  cluster token server, and the benchmark drive.

State lives on device; the runtime owns the registries, rule compilation
(property-cell driven, ``XxxRuleManager.loadRules`` analog), the process
epoch for wraparound-safe relative time, and the 1 s system-status sampler
(``SystemStatusListener`` analog).
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import threading
import weakref
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sentinel_tpu.core.batching import (
    pad_into as _pad_into, pad_pow2, pad_to as _pad_to,
)
from sentinel_tpu.core.clock import Clock, global_clock
from sentinel_tpu.core.compile_cache import (
    enable_persistent_cache, program_key,
)
from sentinel_tpu.core.pending import PendingResult, start_host_copy
from sentinel_tpu.core.config import SentinelConfig, load_config
from sentinel_tpu.core.context import current_context
from sentinel_tpu.core.errors import (
    BlockException, BlockReason, ErrorEntryFreeError, block_exception_for,
    is_block_exception,
)
from sentinel_tpu.core import errors as err_mod
from sentinel_tpu.core.property import SentinelProperty
from sentinel_tpu.core.registry import (
    ENTRY_NODE_ROW, InternedBatch, OriginRegistry, Registry,
    ResourceRegistry, make_origin_registry, make_registry,
    make_resource_registry,
)
from sentinel_tpu.engine.pipeline import (
    EngineSpec, EntryBatch, ExitBatch, RuleSet, SentinelState, Verdicts,
    decide_entries, init_state, init_state_shapes,
    invalidate_resource_rows, record_blocks, record_exits,
    restore_resource_rows,
)
from sentinel_tpu.engine import fastpath as fp_mod
from sentinel_tpu.rules import authority as auth_mod
from sentinel_tpu.rules import degrade as deg_mod
from sentinel_tpu.rules import flow as flow_mod
from sentinel_tpu.rules import param_flow as pf_mod
from sentinel_tpu.rules import system as sys_mod
from sentinel_tpu.core.callbacks import StatisticCallbackRegistry
from sentinel_tpu.core.logs import BlockStatLogger, record_log
from sentinel_tpu.obs import RuntimeObs
from sentinel_tpu.obs import counters as obs_keys
from sentinel_tpu.stats import events as ev
from sentinel_tpu.stats.window import (
    MINUTE_SPEC, SECOND_SPEC, WindowSpec, bucket_snapshot, init_window,
    rolling_totals, rt_totals,
)

ENTRY_TYPE_OUT = 0
ENTRY_TYPE_IN = 1

_log = logging.getLogger("sentinel_tpu.runtime")

#: Depth of the serving dispatch pipeline (sentinel_tpu/serving.py) — how
#: many batches may be in flight before a submit settles the oldest.
PIPELINE_DEPTH_ENV = "SENTINEL_PIPELINE_DEPTH"


def _env_on(name: str, default: bool = True) -> bool:
    v = os.environ.get(name, "")
    if not v:
        return default
    return v.lower() not in ("0", "off", "false", "disable", "disabled")


def donation_enabled() -> bool:
    """Buffer donation on the jitted steps: the engine-state argument's
    device buffers are reused for the output state, halving the step's
    peak state footprint and letting XLA update the window tensors in
    place. Every runtime call site threads ``state_in → state_out``
    under the dispatch lock, so the consumed input is never read again;
    ``SENTINEL_DONATE=0`` is the escape hatch (e.g. for external code
    that calls the ``_jit_*`` steps directly and re-reads its input)."""
    return _env_on("SENTINEL_DONATE")


def host_staging_enabled() -> bool:
    """Reuse preallocated host staging buffers for the per-step batch
    columns instead of fresh numpy allocations (``_StagingRing``);
    ``SENTINEL_HOST_STAGING=0`` disables."""
    return _env_on("SENTINEL_HOST_STAGING")


def sortfree_enabled() -> bool:
    """Sort-free general path: the flow slots group admission segments
    via the hash-bucketed claim cascade + scatter ranks (ops/sortfree.py)
    instead of n·log n stable sorts — the default. Bit-exact with the
    sorted reference by construction (claim overflow falls back to the
    sorted branch under ``lax.cond``; the ``sortfree.bucket_overflow``
    counter tracks how often). ``SENTINEL_SORTFREE=0`` is the escape
    hatch — it reverts every path to the sorted reference machinery and
    restores the pre-round-10 program cache keys (see
    docs/OPERATIONS.md "Sort-free general path")."""
    return _env_on("SENTINEL_SORTFREE")


def single_dispatch_enabled() -> bool:
    """Sketch-fused decide: fold the tiering sketch's conservative-update
    scatter into the jitted decide programs (the sketch table becomes
    another donated operand), so a decide batch on a tiering engine is
    one device dispatch, not a decide plus a standalone observe.
    Bit-exact with the two-dispatch composition by construction (the
    fused program traces the same ``sketch.update_sketch``).
    ``SENTINEL_SINGLE_DISPATCH=0`` is the operator escape hatch — it
    restores the pre-round-16 dispatch sequence AND its program cache
    keys byte-for-byte (see docs/OPERATIONS.md "Sketch-fused decide and
    the tick schedule")."""
    return _env_on("SENTINEL_SINGLE_DISPATCH")


def pipeline_depth(default: int = 2) -> int:
    """The ``SENTINEL_PIPELINE_DEPTH`` knob, clamped to [1, 64]."""
    raw = os.environ.get(PIPELINE_DEPTH_ENV, "")
    try:
        d = int(raw) if raw else default
    except ValueError:
        return default
    return max(1, min(d, 64))


#: Static flag names shared by every decide program (must match the
#: ``decide_entries`` keyword surface).
_STEP_STATICS = ("scalar_flow", "fast_flow", "skip_auth", "skip_sys",
                 "scalar_has_rl", "skip_threads", "sortfree")


def named_partial(name: str, fn, *args, **kwargs):
    """``functools.partial`` with a name: jitted, its program is the
    module ``jit_<name>`` — what a device trace shows of it (a bare
    partial compiles as ``jit__unknown``). The tier migration programs
    carry theirs (``tier_extract``, ``tier_invalidate``, ``tier_restore``)
    so that their device time can be read by name."""
    bound = functools.partial(fn, *args, **kwargs)
    bound.__name__ = name
    return bound


def _build_steps(spec: EngineSpec, custom_slots: tuple, shardings=None,
                 donate: bool = True):
    """``shardings`` = (state_shardings, verdict_shardings) pins every
    step's state output to the mesh layout (parallel/local_shard.py) so
    sharded state can never silently decay to replicated across steps.

    ``donate`` donates each step's engine-state argument (the output
    state reuses its buffers — see :func:`donation_enabled`)."""
    if shardings is None:
        st_out = vd_out = None
        kw_sv = kw_s = {}
    else:
        st_out, vd_out = shardings
        kw_sv = {"out_shardings": (st_out, vd_out)}
        kw_s = {"out_shardings": st_out}
    # state is positional arg 1 of the partials below (rules, state, ...)
    # except invalidate/record_blocks where it leads
    kw_d1 = {"donate_argnums": (1,)} if donate else {}
    kw_d0 = {"donate_argnums": (0,)} if donate else {}
    def dec(occ, alt):
        return jax.jit(functools.partial(
            decide_entries, spec, enable_occupy=occ,
            custom_slots=custom_slots, record_alt=alt),
            static_argnames=_STEP_STATICS, **kw_sv, **kw_d1)

    # jit objects are lazy (tracing happens on first call), so building all
    # variants is free; the *_noalt ones compile away the origin/chain
    # scatters for batches the host verified carry no alt rows (the common
    # origin-less case — two fewer million-index scatters per step)
    return (dec(False, True), dec(True, True),
            dec(False, False), dec(True, False),
            jax.jit(functools.partial(record_exits, spec),
                    static_argnames=("skip_threads",), **kw_s, **kw_d1),
            jax.jit(functools.partial(record_exits, spec,
                                      record_alt=False),
                    static_argnames=("skip_threads",), **kw_s, **kw_d1),
            jax.jit(named_partial("tier_invalidate",
                                  invalidate_resource_rows, spec),
                    **kw_s, **kw_d0),
            jax.jit(functools.partial(record_blocks, spec),
                    **kw_s, **kw_d0),
            # tier promotion (tiering/manager.py): the state is rewritten
            # in place and, on a mesh, lands under its own shardings
            jax.jit(named_partial("tier_restore",
                                  restore_resource_rows, spec),
                    **kw_s, **kw_d0))


@functools.lru_cache(maxsize=None)
def _jitted_steps_cached(spec: EngineSpec, donate: bool = True):
    return _build_steps(spec, (), donate=donate)


def _jitted_steps(spec: EngineSpec, custom_slots: tuple = (), shardings=None,
                  donate: Optional[bool] = None):
    """Compiled steps shared across Sentinel instances with the same geometry
    (EngineSpec is a frozen, hashable dataclass). Variants WITH custom
    DeviceSlots or mesh shardings are deliberately NOT cached globally: the
    owning Sentinel holds the only reference, so stale compilations (and the
    slot objects / mesh) are garbage-collected on every register/unregister
    instead of pinned forever by an unbounded cache key."""
    if donate is None:
        donate = donation_enabled()
    if custom_slots or shardings is not None:
        return _build_steps(spec, custom_slots, shardings, donate)
    return _jitted_steps_cached(spec, donate)

def _build_sd_steps(spec: EngineSpec, custom_slots: tuple, shardings=None,
                    donate: bool = True, mesh=None):
    """Sketch-fused decide programs (``SENTINEL_SINGLE_DISPATCH``):
    ``decide_entries`` + :func:`sketch.update_sketch` over the batch's
    rows in one program, ``(rules, state, sketch, batch, times,
    sys_scalars) → (state, verdicts, sketch)``. Four variants in
    :func:`_build_steps`'s layout (index ``(2 if no_alt else 0) +
    (1 if use_occ else 0)``).

    Bit-parity with the legacy composition (decide, then the standalone
    observe) is by construction: the sketch update reads only
    ``batch.rows``/``valid`` (never the decide outputs) and the decide
    never reads the sketch.

    The sketch output is replicated on meshed engines
    (``NamedSharding(mesh, P())`` — the table is a few KB; only the
    row-sharded state carries a layout)."""
    from sentinel_tpu.tiering import sketch as sk_mod

    if shardings is None or mesh is None:
        kw3: dict = {}
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        st_out, vd_out = shardings
        rep = NamedSharding(mesh, PartitionSpec())
        kw3 = {"out_shardings": (st_out, vd_out, rep)}
    kw_d12 = {"donate_argnums": (1, 2)} if donate else {}

    def dec_sd(occ, alt):
        base = functools.partial(decide_entries, spec, enable_occupy=occ,
                                 custom_slots=custom_slots, record_alt=alt)

        def step(rules, state, sketch, batch, times, sys_scalars,
                 scalar_flow=False, fast_flow=False, skip_auth=False,
                 skip_sys=False, scalar_has_rl=True, skip_threads=False,
                 sortfree=False):
            state, verdicts = base(
                rules, state, batch, times, sys_scalars,
                scalar_flow=scalar_flow, fast_flow=fast_flow,
                skip_auth=skip_auth, skip_sys=skip_sys,
                scalar_has_rl=scalar_has_rl, skip_threads=skip_threads,
                sortfree=sortfree)
            # the overflow flag is dropped exactly like observe_locked's
            # (self-clamping halve happens inside update_sketch; the
            # COUNTER is ticked from the ticker's largest-counter readback)
            sketch, _overflow = sk_mod.update_sketch(
                sketch, batch.rows, batch.valid)
            return state, verdicts, sketch

        return jax.jit(step, static_argnames=_STEP_STATICS,
                       **kw3, **kw_d12)

    return (dec_sd(False, True), dec_sd(True, True),
            dec_sd(False, False), dec_sd(True, False))


@functools.lru_cache(maxsize=None)
def _sd_steps_cached(spec: EngineSpec, donate: bool):
    """Sketch-fused programs shared across Sentinel instances with the same
    geometry — same caching policy as :func:`_jitted_steps_cached`
    (variants with custom DeviceSlots or mesh shardings stay per-instance
    so their compilations are collectable)."""
    return _build_sd_steps(spec, (), donate=donate)


# jitted once at import; shapes are padded to powers of two so the trace
# cache stays small (calling jax.jit(...) per drain would re-trace every time)
_jit_invalidate_param_keys = jax.jit(pf_mod.invalidate_param_keys)
_jit_apply_overrides = jax.jit(pf_mod.apply_overrides)
# small device-side copy used to hand breaker observers a column that
# survives the next step's donation of the state it was read from
_jit_copy_column = jax.jit(jnp.copy)


@functools.lru_cache(maxsize=None)
def _jit_uncount_reserved(spec: EngineSpec):
    from sentinel_tpu.engine.pipeline import uncount_reserved
    return jax.jit(functools.partial(uncount_reserved, spec))


@functools.lru_cache(maxsize=None)
def _jit_bucket_snapshot(spec: WindowSpec):
    return jax.jit(functools.partial(bucket_snapshot, spec))


@functools.lru_cache(maxsize=None)
def _jit_settle_occupied(spec: WindowSpec):
    from sentinel_tpu.stats.window import settle_occupied
    return jax.jit(functools.partial(settle_occupied, spec,
                                     event=ev.PASS))

_H1 = 0x9E3779B1
_H2 = 0x85EBCA6B
_MASK = 0xFFFFFFFF


def _alt_hash(row: int, kind: int, key_id: int, ra: int) -> int:
    """Stable (resource, origin/context) → alt-table row."""
    h = ((row * _H1) ^ ((key_id * 2 + kind) * _H2)) & _MASK
    return h % ra


class _CpuSampler:
    """CPU usage from /proc/stat deltas, sampled at most once per second."""

    def __init__(self, clock: Clock):
        self._clock = clock
        self._last_ms = -10_000
        self._last_total = 0
        self._last_idle = 0
        self._value = -1.0

    def sample(self) -> Tuple[float, float]:
        now = self._clock.now_ms()
        if now - self._last_ms >= 1000:
            self._last_ms = now
            try:
                import os
                load1 = os.getloadavg()[0]
            except OSError:  # pragma: no cover
                load1 = -1.0
            self._load1 = load1
            try:
                with open("/proc/stat") as fh:
                    parts = fh.readline().split()[1:]
                vals = [int(x) for x in parts[:8]]
                total = sum(vals)
                idle = vals[3] + (vals[4] if len(vals) > 4 else 0)
                dt = total - self._last_total
                di = idle - self._last_idle
                if self._last_total and dt > 0:
                    self._value = max(0.0, min(1.0, 1.0 - di / dt))
                self._last_total, self._last_idle = total, idle
            except (OSError, ValueError, IndexError):  # pragma: no cover
                self._value = -1.0
        return getattr(self, "_load1", -1.0), self._value


class Entry:
    """A granted (or in-flight) guarded call. Context-manager; reference
    ``Entry``/``CtEntry`` with try-with-resources semantics."""

    __slots__ = ("_rt", "resource", "row", "origin_row", "chain_row",
                 "acquire", "is_in", "create_ms", "error", "_exited",
                 "param_pairs", "wait_ms", "_terminate_handlers", "fast")

    def __init__(self, rt: "Sentinel", resource: str, row: int, origin_row: int,
                 chain_row: int, acquire: int, is_in: bool, create_ms: int,
                 param_pairs=None):
        self._rt = rt
        self.resource = resource
        self.row = row
        self.origin_row = origin_row
        self.chain_row = chain_row
        self.acquire = acquire
        self.is_in = is_in
        self.create_ms = create_ms
        self.param_pairs = param_pairs   # (rules [PV], keys [PV]) or None
        self.error: Optional[BaseException] = None
        self._exited = False
        self.wait_ms = 0   # pacing verdict; >0 only with entry(sleep=False)
        self._terminate_handlers = None   # CtEntry.whenTerminate callbacks
        self.fast = None   # "free"/"leased" when host-fast-path admitted

    def trace(self, exc: BaseException) -> None:
        """Reference ``Tracer.trace`` — mark a business exception so it feeds
        exception-ratio/count circuit breakers and exception QPS."""
        if exc is not None and not is_block_exception(exc):
            self.error = exc

    def when_terminate(self, fn) -> None:
        """Register ``fn(entry)`` to run after exit (reference
        ``CtEntry.whenTerminate`` — the hook HALF_OPEN probes and the api
        facade's entry stack use)."""
        if self._terminate_handlers is None:
            self._terminate_handlers = []
        self._terminate_handlers.append(fn)

    def exit(self) -> None:
        if self._exited:
            raise ErrorEntryFreeError(f"entry for {self.resource!r} exited twice")
        self._exited = True
        self._rt._exit_one(self)
        if self._terminate_handlers:
            for fn in self._terminate_handlers:
                fn(self)

    def __enter__(self) -> "Entry":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.trace(exc)
        self.exit()
        return False


def _settle_leaked(cell, on_leak) -> None:
    """GC finalizer for a :class:`PendingVerdicts` dropped without
    ``.result()``: the deferred host bookkeeping (blocked-pin release,
    block log, breaker diffs) must not be lost with the handle. Runs the
    leak callback (counter + warning) first so a settle failure — e.g.
    the owning Sentinel was closed — still leaves the leak visible."""
    if cell.done:
        return
    try:
        on_leak()
    except Exception:   # telemetry must never mask the settle
        pass
    try:
        cell.settle()
    except Exception:
        _log.debug("leaked PendingVerdicts settle failed", exc_info=True)


class PendingVerdicts(PendingResult):
    """Handle for an in-flight batch decide: ``result()`` materializes the
    :class:`Verdicts` and performs the deferred host-side bookkeeping
    (blocked-pin release, block log) — it MUST be called for every handle.

    A handle the caller drops anyway is settled by a GC finalizer (see
    :func:`_settle_leaked`) and counted in ``pipeline.leaked_handles`` —
    correctness is preserved, but the settle then runs at an arbitrary
    point on the GC's thread, so a leak is still a caller bug.

    ``rows`` (names batches: ``entry_batch_nowait``) is the int32 row each
    event was admitted on, known at dispatch: what ``exit_batch`` takes
    for the entries that pass. Under tiering churn a name's row changes
    from one batch to the next, so this is the only way to exit what was
    entered without interning the names a second time."""

    __slots__ = ("_leak_finalizer", "rows")

    def attach_leak_guard(self, on_leak) -> None:
        f = weakref.finalize(self, _settle_leaked, self._cell, on_leak)
        # never settle during interpreter shutdown: the backend may
        # already be torn down, and the process exiting is not a leak
        f.atexit = False
        self._leak_finalizer = f

    def result(self):
        fin = getattr(self, "_leak_finalizer", None)
        if fin is not None:
            fin.detach()
        return self._cell.settle()


class _StagingRing:
    """Preallocated host staging for the always-present entry-batch columns
    of one padded size: ``_build_entry_batch`` fills a free slot in place
    (``pad_into``) instead of allocating ~9 fresh numpy arrays per step —
    the ``entry.prep`` cost a serving loop re-pays every dispatch.

    A slot must not be rewritten while a dispatch built from it could
    still read it. The round-7 ring assumed a jit call copies host
    operands synchronously; on this backend that does not always hold
    under tiering churn (ROADMAP known-issue 5), so slot reuse is now
    tied to dispatch SETTLEMENT: ``acquire()`` hands out a slot from the
    free list, and the dispatch path releases it from its deferred-read
    closure only after the verdict readback has materialized — by which
    point the device has consumed the staged operands. Under churn
    (pipeline deeper than the free list, or a slot held across a stall)
    ``acquire()`` grows the pool with a fresh slot instead of ever
    rewriting an in-flight one; ``grown`` counts those allocations. A
    slot leaked on an exception path simply shrinks the pool — the next
    acquire re-grows it — so correctness never depends on release."""

    __slots__ = ("b", "_free", "_lock", "grown")

    _INT_COLS = ("rows", "origin_ids", "origin_rows", "context_ids",
                 "chain_rows", "acquire")
    _BOOL_COLS = ("is_in", "prioritized", "valid")

    def __init__(self, b: int, depth: int):
        self.b = b
        self.grown = 0
        self._lock = threading.Lock()
        self._free = [self._new_slot() for _ in range(depth)]

    def _new_slot(self) -> dict:
        return {**{c: np.empty(self.b, np.int32) for c in self._INT_COLS},
                **{c: np.empty(self.b, np.bool_) for c in self._BOOL_COLS}}

    def acquire(self) -> dict:
        with self._lock:
            if self._free:
                return self._free.pop()
            self.grown += 1
        return self._new_slot()

    def release(self, slot: dict) -> None:
        with self._lock:
            self._free.append(slot)


class Sentinel:
    """The framework instance (Env/CtSph + rule managers, in one object)."""

    def __init__(self, config: Optional[SentinelConfig] = None,
                 clock: Optional[Clock] = None, mesh=None):
        """``mesh`` (a ``jax.sharding.Mesh`` with a ``"rows"`` axis) turns
        on the row-sharded multi-chip mode: the ``[R, B, E]`` window tensors
        and thread gauges shard on the resource axis across the mesh
        (parallel/local_shard.py), the product form of the north-star
        "single sharded counter tensor". Semantics are identical to the
        single-device engine (parity is pinned by tests); max_resources
        must be a multiple of the mesh size.

        The mesh may be externally built and span PROCESSES (a
        ``sentinel_tpu.multihost.mesh.global_mesh(axis="rows")`` over a
        bootstrapped multi-process runtime): state then shards across
        hosts and ``is_multihost`` is True. That mode is SPMD — every
        process must construct the engine identically and replay the
        same rule loads and entry batches in the same order (see
        docs/OPERATIONS.md "Multi-host pod deployment")."""
        self.cfg = config or load_config()
        self.clock = clock or global_clock()
        self.mesh = mesh
        self._mesh_shardings = None      # (state_sh, verdict_sh) when meshed
        cfg = self.cfg

        # Cold-start: persistent XLA compilation cache — the first process
        # against a cache directory pays the step compiles, every later
        # one starts warm (core/compile_cache.py; OPERATIONS.md "Cold start")
        enable_persistent_cache()

        # factories pick the native C++ interning table when buildable
        self.resources = make_resource_registry(cfg.max_resources)
        self.origins = make_origin_registry(cfg.max_origins)
        self.contexts = make_registry(2048,
                                      reserved=("sentinel_default_context",))

        from sentinel_tpu.obs.resource_hist import engine_hist_buckets
        self.spec = EngineSpec(
            rows=cfg.max_resources,
            alt_rows=max(2 * cfg.max_resources, 1024),
            second=WindowSpec(cfg.second_sample_count,
                              cfg.second_interval_ms // max(cfg.second_sample_count, 1)),
            minute=MINUTE_SPEC if cfg.minute_enabled else None,
            statistic_max_rt=cfg.statistic_max_rt,
            param_keys=cfg.param_table_slots,
            param_pairs=cfg.param_pairs_per_event,
            occupy_timeout_ms=cfg.occupy_timeout_ms,
            # round 20 — per-resource RT histograms (0 = disabled; a
            # trace-time knob: the value is baked into the state pytree
            # and every jitted step program's cache key)
            hist_buckets=engine_hist_buckets(),
            rows_sharded=mesh is not None,
        )
        self.param_key_registry = pf_mod.make_param_key_registry(cfg.param_table_slots)
        self._user_param_rules: List[pf_mod.ParamFlowRule] = []
        self._gateway_param_rules: List[pf_mod.ParamFlowRule] = []
        # bumped on every param-rule reload: pairs resolved against a stale
        # (table, registry) pair carry their generation and are dropped by
        # decide_raw/exit if a reload happened in between — a stale rule slot
        # must never be applied against the new table
        self._param_gen = 0
        # process epoch: wraparound-safe int32 relative time base
        self.epoch_ms = self.clock.now_ms()

        # Round 11 — tuned-config startup resolution + knob-registry
        # validation (sentinel_tpu/tune). ``SENTINEL_TUNED_CONFIG``
        # names a sweep-produced TUNED.json; a fingerprint-matching
        # artifact fills in every knob whose env var the operator left
        # UNSET (explicit env wins per knob — the override path), a
        # mismatch resolves to {} and serving proceeds on defaults.
        # Events (artifact load/fallback + unknown/out-of-clamp
        # SENTINEL_* env keys) are routed to RecordLog and the tune.*
        # counters once self.obs exists below.
        from sentinel_tpu import tune as tune_mod
        self._tuned, self._tune_events = tune_mod.resolve_startup(
            spec=self.spec, mesh=mesh)
        # SORTFREE_BITS/CHUNK are read from env inside the traced flow
        # programs (ops/sortfree.py) — the one knob pair with no
        # injection path — so a tuned value pins the (still-unset) env
        # var for this process; first engine wins, and the pin is logged
        for _env in ("SENTINEL_SORTFREE_BITS", "SENTINEL_SORTFREE_CHUNK"):
            if _env in self._tuned and _env not in os.environ:
                os.environ[_env] = str(self._tuned[_env])
                self._tune_events.append((
                    None,   # log-only: the artifact load already ticked
                    f"pinned {_env}={self._tuned[_env]} from tuned "
                    f"config (trace-time knob, applied via env)"))

        self._lock = threading.RLock()
        # main row → alt rows it ever hashed to; consulted on row eviction so
        # the recycled row's origin/context stats are cleared too
        self._alt_rows_by_row: dict = {}
        # self-telemetry bundle (obs/): spans + decision counters +
        # latency histograms + sampled block-event log. Every hot-path
        # instrumentation site below guards on the single `obs.enabled`
        # flag (SENTINEL_OBS_DISABLE); sampling via SENTINEL_TRACE_SAMPLE.
        # Built before the state so that the state's initialisation is a
        # phase like any other.
        self.obs = RuntimeObs(clock=self.clock)
        # Meshed: the sharding pytree comes from the state's SHAPES, so
        # the state is created already laid out — each device fills its
        # own rows and no leaf ever exists whole on the default device
        # (at 4M rows the state is most of a chip's memory).
        state_sh, n_devices = None, 1
        if mesh is not None:
            from sentinel_tpu.parallel.local_shard import shardings_for
            self._mesh_shardings = shardings_for(
                self.spec, mesh, init_state_shapes(
                    self.spec, cfg.max_flow_rules, cfg.max_degrade_rules))
            state_sh, n_devices = self._mesh_shardings[0], mesh.devices.size
        # init_state picks transfer-based init (one device_put, no XLA
        # program) for serving-sized geometries and one fused fill
        # program at the 1M-row scale — see OPERATIONS.md "Cold start".
        with self.obs.phase("state.init", n=self.spec.rows,
                            note=f"devices={n_devices}"):
            self._state = init_state(
                self.spec, cfg.max_flow_rules, cfg.max_degrade_rules,
                shardings=state_sh)
        # Multi-process "rows" mesh (multihost/): replicated leaves
        # (rules, verdicts) stay host-readable everywhere; row-sharded
        # leaves are only partially addressable per host.
        self.is_multihost = mesh is not None and len(
            {d.process_index for d in np.ravel(np.asarray(mesh.devices))}) > 1
        # meshed serving places batch columns on batch-axis NamedShardings
        # before dispatch (parallel/local_shard.place_batch) — single-
        # process meshes only: a multihost batch column is per-process
        # host data and stays with the SPMD replication contract
        self._place_batches = mesh is not None and not self.is_multihost
        self._compile_empty_rules()

        self.flow_property: SentinelProperty = SentinelProperty()
        self.degrade_property: SentinelProperty = SentinelProperty()
        self.system_property: SentinelProperty = SentinelProperty()
        self.authority_property: SentinelProperty = SentinelProperty()
        self.flow_property.add_listener(lambda rs: self.load_flow_rules(rs))
        self.degrade_property.add_listener(lambda rs: self.load_degrade_rules(rs))
        self.system_property.add_listener(lambda rs: self.load_system_rules(rs))
        self.authority_property.add_listener(lambda rs: self.load_authority_rules(rs))
        self.param_flow_property: SentinelProperty = SentinelProperty()
        self.param_flow_property.add_listener(lambda rs: self.load_param_flow_rules(rs))
        # SampleCountProperty / IntervalProperty analogs: live second-window
        # geometry (update_window_geometry rebuilds state + re-jits)
        self.sample_count_property: SentinelProperty = SentinelProperty()
        self.sample_count_property.add_listener(
            lambda sc: self.update_window_geometry(sample_count=int(sc)))
        self.interval_property: SentinelProperty = SentinelProperty()
        self.interval_property.add_listener(
            lambda ms: self.update_window_geometry(interval_ms=int(ms)))

        self._sys_rules: List[sys_mod.SystemRule] = []
        self._cpu = _CpuSampler(self.clock)
        self._global_on = True  # reference Constants.ON / setSwitch command
        # resource → ResourceTypeConstants classification (first writer wins)
        self.resource_types: dict = {}
        # per-second rolled-up block log (LogSlot → EagleEyeLogUtil analog)
        self.block_log = BlockStatLogger(self.clock)
        # surface the startup tune events (artifact load / fingerprint
        # fallback / rejected env knobs) now that telemetry exists:
        # RecordLog line + one counter tick each (key None = log-only)
        if self._tune_events:
            rl = record_log()
            for _key, _msg in self._tune_events:
                (rl.info if _key == obs_keys.TUNE_LOADED
                 else rl.warning)("tune: %s", _msg)
                if _key is not None:
                    self.obs.counters.add(_key)
        # services registered for Sentinel.close() (metric timer,
        # exporter, ...): stopped once, LIFO, idempotently
        self._shutdown_hooks: List = []
        self._closed = False
        # what close() had to swallow to finish the teardown — empty after
        # a clean one (chip_smoke.py fails on anything here)
        self.close_errors: List[BaseException] = []
        # Round 12 — device-resident hot-resource telemetry (obs/
        # telemetry.py): a jitted tick over the live sharded window state
        # (per-shard top-K merged device-side + the ENTRY-row per-second
        # timeline ring) with asynchronous host readback on its own
        # thread. Constructed here (after the shutdown registry — it
        # self-registers) but the ticker only starts when the transport
        # bootstrap (or an operator) calls telemetry.start().
        from sentinel_tpu.obs.telemetry import HotTelemetry
        self.telemetry = HotTelemetry(self)
        # Round 15 — tiered resource state (tiering/): the device table
        # becomes the HOT tier; recycled rows' window counters, thread
        # gauges and occupy bookings spill to a host cold tier and are
        # restored bit-identically when the key is interned again.
        # Constructed after the shutdown registry (it self-registers);
        # the sketch ticker starts with the transport bootstrap or an
        # operator tiering.start(). SENTINEL_TIERING_DISABLE reverts to
        # the pre-round-15 lossy eviction.
        from sentinel_tpu.tiering import TierManager
        self.tiering = TierManager(self)
        # per-rule-family pinned-name ledger (flow/degrade/param/auth):
        # reloads release pins no other family still needs, so formerly
        # ruled keys become demotable (see _update_rule_pins_locked)
        self._rule_pins: dict = {}
        self.callbacks = StatisticCallbackRegistry()
        # circuit-breaker transition observers (EventObserverRegistry).
        # Event-driven: every decide/exit step that can move breaker state
        # carries the [ND] state vector out with its existing readback and
        # diffs it against ONE shared baseline on the thread that lands the
        # batch; the metric-timer poll shares the same baseline, so it is a
        # pure fallback (unread pending verdicts) and never double-fires.
        self._breaker_observers: list = []
        # (seq, rules-tuple identity, states list) of the last landed diff
        self._breaker_live: Optional[Tuple[int, tuple, List[int]]] = None
        self._breaker_seq = 0            # dispatch order, under self._lock
        # serializes diffs: concurrent diffs against one baseline would
        # double-fire observers and lose interleaved transitions
        self._breaker_event_lock = threading.Lock()
        # delivery stays seq-ordered WITHOUT holding the event lock in
        # user code: transitions are enqueued under the event lock (queue
        # order == seq order) and drained by a single active drainer;
        # re-entrant or concurrent callers enqueue and return
        self._breaker_fire_q: "collections.deque" = collections.deque()
        self._breaker_firing = False

        # dispatch-cost knobs (read once at construction): buffer donation
        # on the jitted steps and host staging reuse for batch columns.
        # self._tuned only carries knobs whose env var is UNSET, so the
        # get() fallback to the env helper preserves env precedence
        self._donate = bool(self._tuned.get("SENTINEL_DONATE",
                                            donation_enabled()))
        self._staging_on = bool(self._tuned.get("SENTINEL_HOST_STAGING",
                                                host_staging_enabled()))
        # padded batch size → _StagingRing; ring depth covers the deepest
        # supported dispatch pipeline plus the split path's two builds
        self._staging: dict = {}
        self._staging_depth = max(4, 2 * int(self._tuned.get(
            PIPELINE_DEPTH_ENV, pipeline_depth())) + 2)

        # device slots compile into the steps at registration
        # (register_slot); none yet
        self._device_slots: tuple = ()
        # sketch-fused decide programs are built lazily (_sd_steps_locked)
        # and reset with the step tuple. The knob off leaves every legacy
        # path — and its program cache keys — byte-identical to pre-r16.
        self._single_dispatch = bool(self._tuned.get(
            "SENTINEL_SINGLE_DISPATCH", single_dispatch_enabled()))
        self._bind_steps_locked()
        # (variant, geometry, statics) combos already dispatched once —
        # see _note_program_locked
        self._fetched_programs: set = set()
        self._token_service = None          # cluster TokenService (client or
        # embedded server facade); set via set_token_service
        self._cluster_rules_by_row: dict = {}
        self._cluster_param_rules_by_row: dict = {}
        self._occupy_live_until_ms = -1     # last ms a booking can be live
        # highest second-window index any dispatch has stamped; late fast-
        # path flush groups older than a full ring vs this are re-stamped
        # to now (safe-late) instead of resurrecting a recycled bucket
        self._seen_idx = -(2 ** 62)

        # pluggable processor slots (SlotChainBuilder SPI analog,
        # engine/slots.py): host gates veto before dispatch, device slots
        # (above) compile into the fused decide at registration
        self._host_gates: tuple = ()

        # host-side fast path (SURVEY §7 hard-part 1): rule-free rows admit
        # on host with batched stat recording; single-simple-QPS rows serve
        # from a device-pre-charged token lease
        self._fast = fp_mod.HostFastPath(
            flush_events=cfg.fast_path_flush_events,
            flush_ms=cfg.fast_path_flush_ms,
            lease_fraction=cfg.fast_path_lease_fraction,
            win_ms=self.spec.second.win_ms)
        self._fast_enabled = bool(cfg.host_fast_path)
        # serializes drain→dispatch in _flush_fast: without it a concurrent
        # flush could land a buffered EXIT before the flush carrying its
        # matching pass, leaving the thread gauge permanently skewed (the
        # exit decrement clamps at 0, the late pass increment doesn't)
        self._flush_lock = threading.Lock()

        # SPI-discovered slots (SlotChainProvider.newSlotChain analog:
        # every new "chain" is built from the registered ProcessorSlot
        # providers). Fresh instances per Sentinel — slot state must not
        # leak across engines.
        from sentinel_tpu.core.spi import SERVICE_PROCESSOR_SLOT, SpiLoader
        for slot in SpiLoader.of(
                SERVICE_PROCESSOR_SLOT).load_new_instance_list_sorted():
            self.register_slot(slot)

    # ------------------------------------------------------------------
    # Rule management (XxxRuleManager.loadRules analog)
    # ------------------------------------------------------------------

    def _compile_empty_rules(self) -> None:
        cfg = self.cfg
        self._flow = flow_mod.compile_flow_rules(
            [], resource_registry=self.resources, context_registry=self.contexts,
            capacity=cfg.max_flow_rules, k_per_resource=cfg.max_rules_per_resource,
            num_rows=cfg.max_resources, cold_factor=float(cfg.cold_factor),
            origin_registry=self.origins)
        self._deg = deg_mod.compile_degrade_rules(
            [], resource_registry=self.resources, capacity=cfg.max_degrade_rules,
            k_per_resource=cfg.max_rules_per_resource, num_rows=cfg.max_resources)
        self._auth = auth_mod.compile_authority_rules(
            [], resource_registry=self.resources, origin_registry=self.origins,
            capacity=cfg.max_authority_rules, k_per_resource=2,
            num_rows=cfg.max_resources)
        self._sys = sys_mod.compile_system_rules([])
        self._param = pf_mod.compile_param_rules(
            [], resource_registry=self.resources,
            capacity=cfg.max_param_rules,
            k_per_resource=cfg.max_rules_per_resource)
        self._ruleset = self._build_ruleset()

    def _build_ruleset(self) -> RuleSet:
        """Assemble the dispatch RuleSet from the compiled tables.

        Callers hold ``self._lock`` (every rule-swap API rebuilds under
        it); the ``__init__`` call runs before any thread exists.
        """
        # Used-slot slicing: the device steps iterate a [B, K] pair axis
        # where K is the rule-gather width — slicing it to the MAX RULES ON
        # ANY ONE RESOURCE (not the configured capacity) halves the hot
        # path's per-pair work for the dominant one-rule-per-resource
        # population. A reload that widens K retraces the step (rare, and
        # amortized by the persistent compilation cache).
        kf = self._flow.k_used
        kd = self._deg.k_used
        # Static step flags (jit static args — variants recompile when they
        # flip, steady-state rulesets keep one trace):
        self._scalar_has_rl = any(
            r.control_behavior in (flow_mod.BEHAVIOR_RATE_LIMITER,
                                   flow_mod.BEHAVIOR_WARM_UP_RATE_LIMITER)
            and r.grade == flow_mod.GRADE_QPS for r in self._flow.rules)
        self._skip_auth = self._auth.num_active == 0
        self._skip_sys = not getattr(self, "_sys_rules", [])
        # sort-free segment grouping (env-pinned per process, read at
        # every reload so a test flipping the env var between Sentinels
        # gets the expected variant; the tuned-config override applies
        # only while the env var is unset — see resolve_startup)
        self._sortfree = bool(getattr(self, "_tuned", {}).get(
            "SENTINEL_SORTFREE", sortfree_enabled()))
        # Thread-gauge elision: nothing loaded READS live concurrency →
        # the gauge-maintenance scatters compile away (the only readers:
        # THREAD-grade flow rules — DefaultController.java:50-76, system
        # rules — SystemRuleManager.checkSystem, THREAD-grade param rules
        # — ParamFlowChecker). Gauges read 0 while elided; loading a
        # reader flips the flag (retrace) and the gauge warms as pre-flip
        # entries exit (decrements clamp at 0). See docs/OPERATIONS.md.
        prev_skip = getattr(self, "_skip_threads", None)
        self._skip_threads = (
            not self.cfg.thread_gauge_always
            and self._skip_sys
            and not any(r.grade == flow_mod.GRADE_THREAD
                        for r in self._flow.rules)
            and not any(r.grade == pf_mod.GRADE_THREAD
                        for r in self._param.rules))
        if prev_skip is not None and prev_skip != self._skip_threads \
                and hasattr(self, "_state"):
            # Flag flip invalidates the gauges: entries counted while
            # maintenance was ON would otherwise leak a permanent
            # OVER-count when their elided exits never decrement (e.g.
            # unload the THREAD rule, exits happen elided, reload one).
            # Zeroing restores the documented contract — transient
            # under-count only, gauges warm as live entries exit
            # (decrements clamp at 0). `x * 0` keeps mesh sharding.
            st = self._state
            self._state = st._replace(
                threads=st.threads * 0,
                alt_threads=st.alt_threads * 0,
                param_dyn=st.param_dyn._replace(
                    threads=st.param_dyn.threads * 0))
        # Used-slot slice + joint concat in NUMPY, one device transfer:
        # the jnp forms dispatch dynamic_slice/concatenate programs, each
        # a per-process program load at start-up (the cold-start story,
        # docs/OPERATIONS.md).
        if self._flow.rule_idx_np is not None \
                and self._deg.rule_idx_np is not None:
            fi_np = self._flow.rule_idx_np[:, :kf]
            di_np = self._deg.rule_idx_np[:, :kd]
            joint_np = RuleSet.build_joint_np(fi_np, di_np)
            flow_idx, deg_idx, joint = jax.device_put(
                (fi_np, di_np, joint_np))
            return RuleSet(
                flow_table=self._flow.table,
                flow_idx=flow_idx,
                deg_table=self._deg.table,
                deg_idx=deg_idx,
                auth_table=self._auth.table, auth_idx=self._auth.rule_idx,
                sys_thresholds=self._sys,
                param_table=self._param.table,
                joint_idx=joint)
        flow_idx = self._flow.rule_idx[:, :kf]
        deg_idx = self._deg.rule_idx[:, :kd]
        return RuleSet(
            flow_table=self._flow.table,
            flow_idx=flow_idx,
            deg_table=self._deg.table,
            deg_idx=deg_idx,
            auth_table=self._auth.table, auth_idx=self._auth.rule_idx,
            sys_thresholds=self._sys,
            param_table=self._param.table).with_joint()

    def _rebuild_fastpath(self) -> None:
        """Recompute the host-fast-path classification after any rule load
        (see :mod:`sentinel_tpu.engine.fastpath`). Rows named by any rule
        are pinned in the registry, so classifications can't be stolen by
        LRU row recycling. Callers hold ``self._lock`` (all rule-swap
        paths); the ``__init__`` call runs before any thread exists."""
        if not self._fast_enabled:
            return
        inel: set = set()
        lease: dict = {}
        for r in self._deg.rules:
            inel.add(self.resources.get_or_create(r.resource))
        for r in self._auth.rules:
            inel.add(self.resources.get_or_create(r.resource))
        inel.update(self._param.by_row.keys())
        inel.update(self._cluster_rules_by_row.keys())
        inel.update(self._cluster_param_rules_by_row.keys())
        flow_by_row: dict = {}
        for r in self._flow.rules:
            row = self.resources.get_or_create(r.resource)
            flow_by_row.setdefault(row, []).append(r)
            if r.strategy == flow_mod.STRATEGY_RELATE and r.ref_resource:
                # RELATE reads the ref row's live counts — fast-path lag
                # there would skew this rule's decisions
                inel.add(self.resources.get_or_create(r.ref_resource))
        for row, rs in flow_by_row.items():
            r = rs[0]
            if (len(rs) == 1 and r.grade == flow_mod.GRADE_QPS
                    and r.control_behavior == flow_mod.BEHAVIOR_DEFAULT
                    and r.strategy == flow_mod.STRATEGY_DIRECT
                    and (r.limit_app or "default") == "default"
                    and not r.cluster_mode):
                lease[row] = float(r.count)
            else:
                inel.add(row)
        lease = {row: c for row, c in lease.items() if row not in inel}
        self._fast.set_tables(inel, lease, sys_active=bool(self._sys_rules))

    def load_flow_rules(self, rules: Sequence[flow_mod.FlowRule]) -> None:
        # buffered fast-path passes were admitted under the OLD tables —
        # land them before the swap or the flush would re-decide them
        self._flush_fast()
        cfg = self.cfg
        compiled = flow_mod.compile_flow_rules(
            rules, resource_registry=self.resources, context_registry=self.contexts,
            capacity=cfg.max_flow_rules, k_per_resource=cfg.max_rules_per_resource,
            num_rows=cfg.max_resources, cold_factor=float(cfg.cold_factor),
            origin_registry=self.origins)
        # cluster rules carry their rule-table SLOT position (k within the
        # per-resource rule gather) so a failed token request can re-enable
        # exactly that rule locally via a per-event bitmask — per-rule
        # fallbackToLocalOrPass (FlowRuleChecker.java:184-193), not one
        # all-or-nothing flag. Slot assignment mirrors compile_flow_rules.
        cluster_map: dict = {}
        slots_used: dict = {}
        for r in compiled.rules:
            row = self.resources.get_or_create(r.resource)
            k = slots_used.get(row, 0)
            slots_used[row] = k + 1
            if r.cluster_mode:
                cluster_map.setdefault(row, []).append((k, r))
        with self._lock:
            self._flow = compiled
            self._cluster_rules_by_row = cluster_map
            self._ruleset = self._build_ruleset()
            # fresh shaping state for the new tables (reference rebuilds
            # raters) — but occupy bookings are ROW-keyed promises already
            # granted to callers (the PriorityWait admission happened), so
            # they must survive the reload: LANDED bookings settle into
            # the second window as PASS (every rolling sum then reads the
            # same total it read from the booking ring) and PENDING ones
            # carry into the fresh ring (tests/test_occupy.py pins both)
            old_dyn = self._state.flow_dyn
            now_idx = self.spec.second.index_of(self.clock.now_ms())
            second, pend_cnt, pend_win = _jit_settle_occupied(
                self.spec.second)(
                self._state.second, old_dyn.occupied_count,
                old_dyn.occupied_window, jnp.int32(now_idx))
            if self.obs.enabled:
                # booking lifecycle at reload: pending bookings carry into
                # the fresh ring, landed ones settled as PASS — a cold
                # path, so the two device reads are acceptable here
                prev = int(np.asarray(
                    jax.device_get(old_dyn.occupied_count)).sum())
                carried = int(np.asarray(jax.device_get(pend_cnt)).sum())
                self.obs.counters.add(obs_keys.OCCUPY_CARRIED, carried)
                self.obs.counters.add(obs_keys.OCCUPY_SETTLED,
                                      max(0, prev - carried))
            fresh = flow_mod.init_flow_dyn(cfg.max_flow_rules,
                                           self.spec.second.buckets,
                                           self.spec.rows)
            fresh = fresh._replace(occupied_count=pend_cnt,
                                   occupied_window=pend_win)
            self._state = self._state._replace(second=second,
                                               flow_dyn=fresh)
            self._pin_state_locked()
            self._rebuild_fastpath()
            # release pins the new table no longer needs (mirrors the
            # compile's pin sites: resource, relate-ref resource,
            # chain-ref context, origin-specific limit_app)
            res: set = set()
            org: set = set()
            ctxs: set = set()
            for r in compiled.rules:
                res.add(r.resource)
                la = r.limit_app or "default"
                if la not in ("default", "other"):
                    org.add(la)
                if r.strategy == flow_mod.STRATEGY_RELATE:
                    res.add(r.ref_resource)
                elif r.strategy == flow_mod.STRATEGY_CHAIN:
                    ctxs.add(r.ref_resource)
            self._update_rule_pins_locked("flow", res, org, ctxs)
            # cold entries replay this settle at promote time with this
            # exact now_idx (tiering/coldtier.settle_entry_np)
            self.tiering.on_rules_reloaded_locked(now_idx)

    def set_token_service(self, svc) -> None:
        """Install the cluster token service used for cluster-mode flow rules
        (reference ``TokenClientProvider`` / embedded-server provider): any
        object with ``request_token(flow_id, count, prioritized=False) →
        TokenResult-like`` (``status``, ``wait_ms``). ``None`` uninstalls —
        cluster rules then take the fallback path."""
        self._token_service = svc

    # ------------------------------------------------------------------
    # Pluggable processor slots (SlotChainProvider / SlotChainBuilder SPI
    # analog — engine/slots.py; demo: demos/slot_spi.py)
    # ------------------------------------------------------------------

    def register_slot(self, slot) -> None:
        """Register a user processor slot WITHOUT editing the engine:
        a :class:`~sentinel_tpu.engine.slots.HostGate` runs on host before
        every dispatch (single + batch tiers); a
        :class:`~sentinel_tpu.engine.slots.DeviceSlot` is compiled into
        the fused decide step (re-jit at registration), with its own state
        slice carried in the engine state. Denials surface as
        :class:`CustomSlotException` carrying the slot's name and are
        recorded like every other block."""
        from sentinel_tpu.engine import slots as slots_mod

        # reason codes live in int8 verdict arrays: DeviceSlot i maps to
        # CUSTOM_BASE+i (must stay below CUSTOM_GATE_BASE), HostGate i to
        # CUSTOM_GATE_BASE+i (must stay below 128) — enforce the caps
        # loudly instead of silently wrapping into another slot's code
        max_dev = int(BlockReason.CUSTOM_GATE_BASE) - int(
            BlockReason.CUSTOM_BASE)
        max_gate = 128 - int(BlockReason.CUSTOM_GATE_BASE)
        if isinstance(slot, slots_mod.DeviceSlot):
            if len(self._device_slots) >= max_dev:
                raise ValueError(f"at most {max_dev} device slots")
            self._flush_fast()      # land buffered stats via the old step
            with self._lock:
                self._device_slots = self._device_slots + (slot,)
                # device slots must see EVERY event: the host fast path
                # (which bypasses the device) turns off while any are live
                self._fast_enabled = False
                self._reload_custom_jits_locked()
        elif isinstance(slot, slots_mod.HostGate):
            if len(self._host_gates) >= max_gate:
                raise ValueError(f"at most {max_gate} host gates")
            with self._lock:
                self._host_gates = self._host_gates + (slot,)
        else:
            raise TypeError(
                "slot must subclass HostGate or DeviceSlot (engine/slots.py)")

    def unregister_slot(self, slot) -> None:
        from sentinel_tpu.engine import slots as slots_mod

        if isinstance(slot, slots_mod.DeviceSlot):
            with self._lock:
                self._device_slots = tuple(
                    s for s in self._device_slots if s is not slot)
                self._fast_enabled = (bool(self.cfg.host_fast_path)
                                      and not self._device_slots)
                self._reload_custom_jits_locked()
        else:
            with self._lock:
                self._host_gates = tuple(
                    g for g in self._host_gates if g is not slot)

    def _refresh_shardings_locked(self) -> None:
        """Meshed mode: re-derive the sharding pytree from the CURRENT state
        structure (custom-slot registration / geometry changes alter it) and
        re-place every leaf on its canonical device layout."""
        if self.mesh is None:
            return
        from sentinel_tpu.parallel.local_shard import (
            pin_state, shardings_for,
        )
        self._mesh_shardings = shardings_for(self.spec, self.mesh,
                                             self._state)
        self._state = pin_state(self._state, self._mesh_shardings[0])

    def _uncount_step(self):
        """Lease-uncount step; the meshed variant pins the state output to
        the canonical shardings (the global cache can't — it's keyed on spec
        alone and shardings are per-instance). Cached per (spec, shardings)
        on the instance so flushes don't retrace."""
        if self.mesh is None:
            return _jit_uncount_reserved(self.spec)
        cached = getattr(self, "_uncount_cache", None)
        # identity compare on the live shardings object (a freed tuple's id
        # could be reused; holding the reference makes 'is' sound)
        if (cached is None or cached[0] is not self._mesh_shardings
                or cached[1] != self.spec):
            from sentinel_tpu.engine.pipeline import uncount_reserved
            fn = jax.jit(functools.partial(uncount_reserved, self.spec),
                         out_shardings=self._mesh_shardings[0])
            self._uncount_cache = cached = (self._mesh_shardings, self.spec,
                                            fn)
        return cached[2]

    def _update_rule_pins_locked(self, family: str, res: set, org: set,
                                 ctx: set) -> None:
        """Refcounted rule-pin release (round 15): each rule family
        registers the (resource, origin, context) names its CURRENT
        compiled table pins; names the previous table pinned that no
        family references anymore are unpinned, so formerly ruled keys
        become evictable — and hence demotable to the cold tier.
        Pre-round-15 compile-time pins leaked forever, which would have
        made every rule-bound row a permanent hot-tier resident. Must
        run AFTER the table swap: until then the old table still
        addresses the old rows. Reserved rows (ENTRY node, origin "")
        are pinned at construction outside this ledger and never appear
        in rule sets."""
        old = self._rule_pins.get(family, (set(), set(), set()))
        new = (set(res), set(org), set(ctx))
        self._rule_pins[family] = new
        regs = (self.resources, self.origins, self.contexts)
        for kind in range(3):
            still: set = set()
            for fam, sets in self._rule_pins.items():
                if fam != family:
                    still |= sets[kind]
            for name in old[kind] - new[kind] - still:
                regs[kind].unpin(name)
        # pin-path interns bypass intern_resources: a newly ruled key
        # that sits in the COLD tier just got a fresh (zeroed) row from
        # the pin's alloc — classify it so the next eviction drain
        # promotes its window/booking state before any rule evaluates
        # against the zeroed row. tick=False: rule loads are control
        # plane, not serving traffic — the hit-rate counters stay pure.
        if self.tiering.enabled and res:
            pairs = [(n, r) for n, r in
                     ((n, self.resources.lookup(n)) for n in res)
                     if r is not None]
            if pairs:
                self.tiering.note_interned(
                    [p[0] for p in pairs], [p[1] for p in pairs],
                    tick=False)

    def _pin_state_locked(self) -> None:
        """Re-place state leaves after host code rebuilt some of them
        (rule reloads swap in fresh unsharded arrays); no-op without a
        mesh, and a cheap no-op for leaves already placed correctly."""
        if self.mesh is not None:
            from sentinel_tpu.parallel.local_shard import pin_state
            self._state = pin_state(self._state, self._mesh_shardings[0])

    def _bind_steps_locked(self) -> None:
        """(Re)bind the jitted steps to the live geometry, device slots
        and shardings — at construction and wherever one of them
        changes."""
        (self._jit_decide, self._jit_decide_prio,
         self._jit_decide_noalt, self._jit_decide_prio_noalt,
         self._jit_exit, self._jit_exit_noalt,
         self._jit_invalidate, self._jit_record_blocks,
         self._jit_restore) = \
            _jitted_steps(self.spec, self._device_slots,
                          self._mesh_shardings, donate=self._donate)
        self._sd_steps = None       # sketch-fused variants track the tuple

    def _reload_custom_jits_locked(self) -> None:
        self._state = self._state._replace(custom=tuple(
            s.init_state(self.spec) for s in self._device_slots))
        self._refresh_shardings_locked()    # custom states change structure
        self._bind_steps_locked()

    def _sd_steps_locked(self):
        """Sketch-fused decide programs, built lazily (engine lock held —
        the builder reads live geometry / shardings; plain-geometry
        engines share the process-wide :func:`_sd_steps_cached`
        compilations). Never consulted with ``SENTINEL_SINGLE_DISPATCH``
        off."""
        if self._sd_steps is None:
            if self._device_slots or self._mesh_shardings is not None \
                    or self.mesh is not None:
                self._sd_steps = _build_sd_steps(
                    self.spec, self._device_slots, self._mesh_shardings,
                    donate=self._donate, mesh=self.mesh)
            else:
                self._sd_steps = _sd_steps_cached(self.spec, self._donate)
        return self._sd_steps

    def _slot_code(self, kind: str, index: int) -> int:
        """Reason code for a custom slot denial (disjoint sub-spaces: the
        pipeline emits CUSTOM_BASE+i for DeviceSlot i; host gates use
        CUSTOM_GATE_BASE+i)."""
        return (int(BlockReason.CUSTOM_GATE_BASE) + index if kind == "gate"
                else int(BlockReason.CUSTOM_BASE) + index)

    def slot_name_for_code(self, code: int) -> str:
        """Registered slot name for a CUSTOM_BASE+ reason code."""
        code = int(code)
        if code >= BlockReason.CUSTOM_GATE_BASE:
            i = code - int(BlockReason.CUSTOM_GATE_BASE)
            return (self._host_gates[i].name  # graftlint: disable=LOCK002 -- diagnostic lookup over append-only slot lists; a stale read names the previous slot
                    if i < len(self._host_gates) else "unknown-slot")  # graftlint: disable=LOCK002 -- diagnostic lookup over append-only slot lists; a stale read names the previous slot
        i = code - int(BlockReason.CUSTOM_BASE)
        return (self._device_slots[i].name  # graftlint: disable=LOCK002 -- diagnostic lookup over append-only slot lists; a stale read names the previous slot
                if i < len(self._device_slots) else "unknown-slot")  # graftlint: disable=LOCK002 -- diagnostic lookup over append-only slot lists; a stale read names the previous slot

    def _run_host_gates_one(self, resource: str, origin: str, acquire: int,
                            args: Sequence, row: int, o_row: int, c_row: int,
                            is_in: bool) -> None:
        """Run the registered gates for one entry; raises on denial after
        recording the block (StatisticSlot parity)."""
        for gi, gate in enumerate(self._host_gates):  # graftlint: disable=LOCK002 -- gate list is append-only and published whole; iterating a stale snapshot is the SPI contract
            exc = None
            try:
                ok = gate.check(resource, origin, acquire, args)
            except BlockException as e:
                ok, exc = False, e
            if not ok:
                raise self._record_cluster_block(
                    self._slot_code("gate", gi), resource, origin, row,
                    o_row, c_row, acquire, is_in, exc=exc,
                    slot_name=gate.name)

    def _run_host_gates_batch(self, resources, origins, acq, args_list,
                              is_in, n: int):
        """→ (blocked bool[n], reasons int32[n]); denials are block-logged
        here (the device record happens batched upstream)."""
        blocked = np.zeros(n, np.bool_)
        reasons = np.zeros(n, np.int32)
        for gi, gate in enumerate(self._host_gates):  # graftlint: disable=LOCK002 -- gate list is append-only and published whole; iterating a stale snapshot is the SPI contract
            oks = np.asarray(gate.check_batch(resources, origins, acq,
                                              args_list), np.bool_)
            newly = ~oks & ~blocked
            if newly.any():
                code = self._slot_code("gate", gi)
                reasons[newly] = code
                blocked |= newly
                for i in np.nonzero(newly)[0].tolist():
                    org = (origins[i] if origins is not None
                           and origins[i] else "")
                    self._log_cluster_block(code, resources[i], org,
                                            int(acq[i]))
        return blocked, reasons

    def load_degrade_rules(self, rules: Sequence[deg_mod.DegradeRule]) -> None:
        # buffered fast-path passes were admitted under the OLD tables —
        # land them before the swap or the flush would re-decide them
        self._flush_fast()
        cfg = self.cfg
        compiled = deg_mod.compile_degrade_rules(
            rules, resource_registry=self.resources, capacity=cfg.max_degrade_rules,
            k_per_resource=cfg.max_rules_per_resource, num_rows=cfg.max_resources)
        with self._lock:
            self._deg = compiled
            self._ruleset = self._build_ruleset()
            self._state = self._state._replace(
                breakers=deg_mod.init_breaker_state(cfg.max_degrade_rules))
            self._pin_state_locked()
            self._rebuild_fastpath()
            self._update_rule_pins_locked(
                "degrade", {r.resource for r in compiled.rules}, set(),
                set())

    def load_param_flow_rules(self, rules: Sequence[pf_mod.ParamFlowRule]) -> None:
        self._user_param_rules = list(rules)
        self._reload_param_rules()

    def set_gateway_param_rules(self, rules: Sequence[pf_mod.ParamFlowRule]) -> None:
        """Install gateway-converted param rules (GatewayRuleManager path);
        merged with user param rules into the single param slot."""
        self._gateway_param_rules = list(rules)
        self._reload_param_rules()

    def _reload_param_rules(self) -> None:
        self._flush_fast()      # see load_flow_rules
        cfg = self.cfg
        all_rules = self._user_param_rules + self._gateway_param_rules
        # cluster-mode param rules delegate to the token server
        # (ParamFlowChecker.passClusterCheck → requestParamToken); only the
        # local ones compile into the device table
        rules = [r for r in all_rules if not r.cluster_mode]
        cluster_map: dict = {}
        for r in all_rules:
            if r.cluster_mode:
                row = self.resources.get_or_create(r.resource)
                cluster_map.setdefault(row, []).append(r)
        compiled = pf_mod.compile_param_rules(
            rules, resource_registry=self.resources,
            capacity=cfg.max_param_rules,
            k_per_resource=cfg.max_rules_per_resource)
        with self._lock:
            self._cluster_param_rules_by_row = cluster_map
            self._param = compiled
            self._ruleset = self._build_ruleset()
            # rule slots changed meaning: fresh key interning + cold key state
            # (ParameterMetricStorage re-initializes metrics per rule)
            self.param_key_registry = pf_mod.make_param_key_registry(cfg.param_table_slots)
            self._param_gen += 1
            self._state = self._state._replace(
                param_dyn=pf_mod.init_param_dyn(self.spec.param_keys))
            self._pin_state_locked()
            self._rebuild_fastpath()
            # cluster-mode param rules don't compile into the device
            # table but their rows must stay resident for delegation
            self._update_rule_pins_locked(
                "param", {r.resource for r in compiled.rules}
                | {r.resource for r in all_rules if r.cluster_mode},
                set(), set())

    def load_system_rules(self, rules: Sequence[sys_mod.SystemRule]) -> None:
        # buffered fast-path passes were admitted under the OLD tables —
        # land them before the swap or the flush would re-decide them
        self._flush_fast()
        with self._lock:
            self._sys_rules = list(rules)
            self._sys = sys_mod.compile_system_rules(rules)
            self._ruleset = self._build_ruleset()
            self._rebuild_fastpath()

    def load_authority_rules(self, rules: Sequence[auth_mod.AuthorityRule]) -> None:
        # buffered fast-path passes were admitted under the OLD tables —
        # land them before the swap or the flush would re-decide them
        self._flush_fast()
        cfg = self.cfg
        compiled = auth_mod.compile_authority_rules(
            rules, resource_registry=self.resources, origin_registry=self.origins,
            capacity=cfg.max_authority_rules, k_per_resource=2,
            num_rows=cfg.max_resources)
        with self._lock:
            self._auth = compiled
            self._ruleset = self._build_ruleset()
            self._rebuild_fastpath()
            org: set = set()
            for r in compiled.rules:
                org.update(o.strip() for o in r.limit_app.split(",")
                           if o.strip())
            self._update_rule_pins_locked(
                "authority", {r.resource for r in compiled.rules}, org,
                set())

    def update_window_geometry(self, sample_count: Optional[int] = None,
                               interval_ms: Optional[int] = None) -> None:
        """Live second-window geometry change — the
        ``SampleCountProperty``/``IntervalProperty`` analog
        (``node/SampleCountProperty.java``: the reference swaps fresh
        LeapArrays into every node). Second windows and flow shaping state
        cold-reset (history discard is the reference semantic); the minute
        ring, thread gauges, breakers and hot-param state carry over. The
        engine re-jits for the new geometry and host leases are dropped."""
        import dataclasses as _dc

        sc = int(sample_count if sample_count is not None
                 else self.cfg.second_sample_count)
        iv = int(interval_ms if interval_ms is not None
                 else self.cfg.second_interval_ms)
        if sc <= 0 or iv <= 0 or iv % sc:
            raise ValueError(
                "interval_ms must be a positive multiple of sample_count")
        self._flush_fast()      # land buffered stats on the OLD geometry
        with self._lock:
            if (sc == self.cfg.second_sample_count
                    and iv == self.cfg.second_interval_ms):
                return
            self.cfg = _dc.replace(self.cfg, second_sample_count=sc,
                                   second_interval_ms=iv)
            new_second = WindowSpec(sc, iv // sc)
            self.spec = _dc.replace(self.spec, second=new_second)
            self._state = self._state._replace(
                second=init_window(new_second, self.spec.rows),
                alt_second=init_window(new_second, self.spec.alt_rows),
                flow_dyn=flow_mod.init_flow_dyn(
                    self.cfg.max_flow_rules, new_second.buckets,
                    self.spec.rows))
            self._refresh_shardings_locked()
            self._bind_steps_locked()
            self._occupy_live_until_ms = -1
            self._seen_idx = -(2 ** 62)
            self._fast.win_ms = max(1, new_second.win_ms)
            self._rebuild_fastpath()     # drops leases against old buckets
            # tiering: cold entries + in-flight demote payloads carry
            # OLD-geometry second windows and booking rings; land the
            # in-flight ones, then cold-reset every cold entry to the
            # new bucket count (the same reset resident rows just got)
            # so a later promote can't scatter mismatched shapes
            self.tiering.on_geometry_changed_locked()

    def set_global_switch(self, on: bool) -> None:
        """Reference setSwitch command — off = everything passes unchecked."""
        self._global_on = bool(on)

    @property
    def threads_elided(self) -> bool:
        """True while thread-gauge maintenance is compiled away (no loaded
        rule reads live concurrency): ``curThreadNum``-style gauges read 0
        regardless of traffic. Observability payloads carry this as
        ``threadsElided`` so an operator can't mistake an elided 0 for an
        idle system (docs/OPERATIONS.md "Live-concurrency gauges")."""
        return bool(getattr(self, "_skip_threads", False))

    # ------------------------------------------------------------------
    # Lifecycle (shutdown registry + close)
    # ------------------------------------------------------------------

    def register_shutdown(self, service) -> None:
        """Register a service for :meth:`close` — anything with a
        ``stop()`` or ``close()`` method (``MetricTimerListener`` and
        ``PrometheusExporter`` self-register at construction). Stopped
        LIFO, each at most once; double registration is deduplicated so
        re-wiring a service across restarts can't double-stop it."""
        if not any(service is s for s in self._shutdown_hooks):
            self._shutdown_hooks.append(service)

    def close(self) -> None:
        """Idempotent runtime teardown: flush buffered fast-path stats,
        stop every registered service (daemon threads joined — no thread
        leak across repeated open/close), close self-telemetry and the
        block log. The engine object stays readable (snapshots work) but
        should not dispatch after close."""
        if self._closed:
            return
        self._closed = True
        try:
            self._flush_fast()
        except Exception as exc:    # closing must not depend on device health
            self.close_errors.append(exc)
        hooks, self._shutdown_hooks = self._shutdown_hooks, []
        for svc in reversed(hooks):
            fn = getattr(svc, "stop", None) or getattr(svc, "close", None)
            if fn is None:
                continue
            try:
                fn()
            except Exception as exc:    # one bad service must not leak the rest
                self.close_errors.append(exc)
        self.obs.close()
        try:
            self.block_log.close()
        except Exception:       # pragma: no cover - appender already gone
            pass

    def __enter__(self) -> "Sentinel":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def frontend(self, **kwargs):
        """A new :class:`~sentinel_tpu.frontend.AdaptiveBatcher` ingest
        tier over this runtime (kwargs pass through: batch_max,
        deadline_ms, budget_ms, idle_ms, queue_max, depth, ...). The
        batcher self-registers with :meth:`register_shutdown`, so
        :meth:`close` tears it down. One batcher per event loop.

        Tuned-config application (round 11): any of those kwargs the
        caller leaves unset is filled from the ``SENTINEL_TUNED_CONFIG``
        artifact resolved at construction — but only for knobs whose env
        var is also unset (explicit kwarg > explicit env > artifact >
        the batcher's built-in defaults)."""
        from sentinel_tpu.frontend import AdaptiveBatcher
        from sentinel_tpu.tune import FRONTEND_KWARG_ENVS
        for kw, env in FRONTEND_KWARG_ENVS:
            if kw not in kwargs and env in self._tuned:
                kwargs[kw] = self._tuned[env]
        return AdaptiveBatcher(self, **kwargs)

    # ------------------------------------------------------------------
    # Time helpers
    # ------------------------------------------------------------------

    def _rel_ms(self, now_ms: int) -> int:
        return int((now_ms - self.epoch_ms + 2 ** 31) % 2 ** 32 - 2 ** 31)

    def _time_scalars(self, now_ms: int):
        """Packed int32[4] time vector: ONE host→device transfer per step
        (per-scalar transfers are hot-path dispatch latency)."""
        s = self.spec
        idx_s = s.second.index_of(now_ms)
        idx_m = s.minute.index_of(now_ms) if s.minute else 0
        return jnp.asarray(np.array(
            [idx_s, idx_m, self._rel_ms(now_ms),
             now_ms % s.second.win_ms], np.int32))

    def _restamp_if_stale_locked(self, at_ms: Optional[int], now: int,
                                 times):
        """Safe-late re-stamp for event-time (``at_ms``) dispatches,
        atomic with ``_seen_idx`` — callers hold ``_lock``. A stamp a
        full window ring older than anything already dispatched would
        re-own a physical bucket a newer write holds: the device-side
        refresh zeroes that bucket's LIVE counts, resurrecting spent
        admission budget mid-window (real over-admission, caught by
        test_fastpath's deterministic overadmit harness). The fast-path
        flush pre-checks the same condition, but reads ``_seen_idx``
        outside this lock — a decide landing between its check and this
        dispatch makes the stale stamp dangerous, so the authoritative
        check lives here."""
        if (at_ms is not None
                and self._seen_idx - self.spec.second.index_of(now)
                >= self.spec.second.buckets):
            now = self.clock.now_ms()
            times = self._time_scalars(now)
        return now, times

    # ------------------------------------------------------------------
    # Per-call API
    # ------------------------------------------------------------------

    def entry(self, resource: str, *, origin: Optional[str] = None,
              acquire: int = 1, entry_type: int = ENTRY_TYPE_IN,
              prioritized: bool = False, args: Sequence = (),
              resource_type: int = 0, sleep: bool = True) -> Entry:
        """Guard a call. Raises a BlockException subclass when denied;
        sleeps (via the clock) on pass-with-wait verdicts. ``args`` are the
        call's parameters for hot-param rules (``SphU.entry(name, args)``).
        ``sleep=False`` skips the pacing sleep and instead reports it on
        ``Entry.wait_ms`` so async callers can await it (the cluster
        protocol's ``TokenResult.waitInMs`` pattern generalized locally)."""
        if not self._global_on:
            now = self.clock.now_ms()
            return Entry(self, resource, -1, -1, -1, acquire,
                         entry_type == ENTRY_TYPE_IN, now)
        ctx = current_context()
        use_origin = ctx.origin if origin is None else origin
        # resolve rows ONCE; the same rows feed the verdict and the Entry so
        # an LRU eviction between lookups can't skew exit accounting
        row = self.resources.get_or_create(resource)
        if self.tiering.enabled:
            # classify + queue promotion if this key's state is cold
            self.tiering.note_interned((resource,), (row,))
        if resource_type:   # ResourceTypeConstants classification for metrics
            self.resource_types[resource] = resource_type
        origin_id = self.origins.get_or_create(use_origin) if use_origin else 0
        o_row, c_row = self._alt_rows_for(row, use_origin, ctx.name)
        context_id = (self.contexts.get_or_create(ctx.name)
                      if c_row < self.spec.alt_rows else 0)
        is_in = entry_type == ENTRY_TYPE_IN

        # user host gates veto before anything else (slot-chain SPI tier 1)
        if self._host_gates:  # graftlint: disable=LOCK002 -- hot-path feature gate: a stale read routes one call through the exact device path, never unsafely
            self._run_host_gates_one(resource, use_origin or "", acquire,
                                     args, row, o_row, c_row, is_in)

        # host fast path: rule-free rows admit on host with batched stat
        # recording; single-simple-QPS rows serve from a device
        # pre-charged lease (engine/fastpath.py). Falls through to the
        # exact device path for everything else.
        if self._fast_enabled and not prioritized:  # graftlint: disable=LOCK002 -- hot-path feature gate: a stale read routes one call through the exact device path, never unsafely
            fe = self._fast_entry(resource, row, o_row, c_row, origin_id,
                                  use_origin or "", acquire, is_in, args)
            if fe is not None:
                return fe
        if self._fast_enabled and self._fast.due(self.clock.now_ms()):  # graftlint: disable=LOCK002 -- hot-path feature gate: a stale read routes one call through the exact device path, never unsafely
            self._flush_fast()     # keep buffered stats fresh under mixed
            # fast/slow traffic (the device sees them before this decide)

        # cluster-mode rules: token-server delegation BEFORE the local
        # pipeline (FlowRuleChecker.passClusterCheck); failed requests with
        # fallbackToLocalWhenFail re-enable exactly those rules locally
        # (per-rule slot bitmask)
        cluster_fb = 0
        cluster_wait = 0
        crules = self._cluster_rules_by_row.get(row)
        if crules:
            cluster_fb, cluster_wait = self._cluster_check(
                resource, use_origin or "", row, o_row, c_row, acquire,
                is_in, prioritized, crules, sleep)
        cprules = self._cluster_param_rules_by_row.get(row)
        if cprules and args:
            cluster_wait += self._cluster_param_check(
                resource, use_origin or "", row, o_row, c_row, acquire,
                is_in, args, cprules, sleep)

        pairs = self._resolve_param_pairs_one(row, args)
        pr = pk = None
        if pairs is not None:
            pr = pairs[0][None, :]
            pk = pairs[1][None, :]
        try:
            verdict = self.decide_raw(
                np.array([row], np.int32), np.array([origin_id], np.int32),
                np.array([o_row], np.int32), np.array([context_id], np.int32),
                np.array([c_row], np.int32), np.array([acquire], np.int32),
                np.array([is_in], np.bool_), np.array([prioritized], np.bool_),
                param_rules=pr, param_keys=pk,
                param_gen=pairs[2] if pairs is not None else -1,
                cluster_fallback=(np.array([cluster_fb], np.int32)
                                  if cluster_fb else None))
            if not bool(verdict.allow[0]):
                rcode = int(verdict.reason[0])
                exc = block_exception_for(
                    rcode, resource, origin=use_origin,
                    slot_name=(self.slot_name_for_code(rcode)
                               if rcode >= BlockReason.CUSTOM_BASE else ""))
                # LogSlot: block events roll into sentinel-block.log
                self.block_log.log(resource, type(exc).__name__,
                                   origin=use_origin or "")
                if self.obs.enabled:
                    self._obs_block(resource, rcode, use_origin or "", 1)
                if not self.callbacks.empty:   # StatisticSlot onBlocked
                    self.callbacks.fire_blocked(resource, use_origin or "",
                                                acquire, exc)
                raise exc
        except BaseException:
            if pairs is not None:   # blocked entries never exit → unpin now
                pairs[3].unpin_rows(pairs[4])
            raise
        if not self.callbacks.empty:           # StatisticSlot onPass
            self.callbacks.fire_pass(resource, use_origin or "", acquire,
                                     args)
        wait = int(verdict.wait_ms[0])
        if wait > 0 and sleep:
            self.clock.sleep_ms(wait)
        if not sleep:
            wait += cluster_wait     # cluster SHOULD_WAIT surfaces here too
        now = self.clock.now_ms()
        # sleep=False: project create_ms past the wait the caller will await,
        # so rt excludes pacing delay exactly like the sleep=True path
        e = Entry(self, resource, row, o_row, c_row, acquire, is_in,
                  now if sleep else now + wait, param_pairs=pairs)
        if not sleep:
            e.wait_ms = wait
        return e

    def _record_cluster_block(self, reason: int, resource: str, origin: str,
                              row: int, o_row: int, c_row: int,
                              acquire: int, is_in: bool, exc=None,
                              slot_name: str = "") -> BlockException:
        """Record + log + fire callbacks for a denial decided off-device
        (token server or host gate); returns the exception for the caller
        to raise (StatisticSlot accounting for blocks decided off-device).
        ``exc`` overrides the constructed exception (a gate raising its own
        BlockException subclass propagates it)."""
        times = self._time_scalars(self.clock.now_ms())
        with self._lock:
            self._state = self._jit_record_blocks(
                self._state,
                jnp.asarray(np.array([row], np.int32)),
                jnp.asarray(np.array([o_row], np.int32)),
                jnp.asarray(np.array([c_row], np.int32)),
                jnp.asarray(np.array([acquire], np.int32)),
                jnp.asarray(np.array([is_in], np.bool_)),
                jnp.asarray(np.array([True], np.bool_)),
                times)
        return self._log_cluster_block(reason, resource, origin, acquire,
                                       exc=exc, slot_name=slot_name)

    def _cluster_check(self, resource: str, origin: str, row: int,
                       o_row: int, c_row: int, acquire: int, is_in: bool,
                       prioritized: bool, crules,
                       sleep: bool = True,
                       record: bool = True) -> Tuple[int, int]:
        """``passClusterCheck`` for this resource's cluster-mode rules.
        ``crules`` is a list of ``(slot_k, rule)`` pairs (slot = the rule's
        position in the per-resource rule gather). Returns
        ``(fallback_bits, pending_wait_ms)`` where bit k of ``fallback_bits``
        re-enables exactly slot k's rule in the local pipeline — per-rule
        ``fallbackToLocalOrPass`` (FlowRuleChecker.java:184-193), so mixed
        grant/failure locally enforces only the failed rules. Raises
        FlowException on BLOCKED and records the block like StatisticSlot
        would. TOO_MANY_REQUEST (server overload, status -2) degrades to the
        fallback path like FAIL — it never denies outright
        (FlowRuleChecker.applyTokenResult). With ``sleep=False`` SHOULD_WAIT
        waits are returned instead of slept (async callers await them via
        ``Entry.wait_ms``)."""
        svc = self._token_service
        fallback_bits = 0
        pending_wait = 0
        for slot_k, r in crules:
            status, wait = -1, 0           # FAIL when no service installed
            if svc is not None:
                try:
                    res = svc.request_token(r.cluster_flow_id, acquire,
                                            prioritized)
                    status = int(res.status)
                    wait = int(getattr(res, "wait_ms", 0))
                except Exception as exc:
                    from sentinel_tpu.core.logs import record_log
                    record_log().warning(
                        "cluster token request failed: %r", exc)
            if status == 0:                # OK
                continue
            if status == 2:                # SHOULD_WAIT → sleep, then pass
                if wait > 0:
                    if sleep:
                        self.clock.sleep_ms(wait)
                    else:
                        pending_wait += wait
                continue
            if status == 1:                # BLOCKED
                if record:
                    raise self._record_cluster_block(
                        int(BlockReason.FLOW), resource, origin, row,
                        o_row, c_row, acquire, is_in)
                raise self._log_cluster_block(int(BlockReason.FLOW),
                                              resource, origin, acquire)
            # FAIL / NO_RULE_EXISTS / BAD_REQUEST / TOO_MANY_REQUEST
            # → local check (iff fallbackToLocalWhenFail) or pass
            if r.cluster_fallback_to_local:
                fallback_bits |= 1 << slot_k
        return fallback_bits, pending_wait

    def _cluster_param_check(self, resource: str, origin: str, row: int,
                             o_row: int, c_row: int, acquire: int,
                             is_in: bool, args: Sequence, cprules,
                             sleep: bool = True, record: bool = True) -> int:
        """``ParamFlowChecker.passClusterCheck`` → ``requestParamToken`` for
        cluster-mode hot-param rules. BLOCKED raises ParamFlowException and
        (when ``record``) records the block; ``record=False`` lets the batch
        tier record all cluster blocks in ONE device call instead.
        TOO_MANY_REQUEST (server overload) passes through like FAIL — it
        never denies (ParamFlowChecker.passClusterCheck fallback). The local
        fallback for param rules is a documented pass-through here — the
        flow path carries the exact local fallback."""
        svc = self._token_service
        pending_wait = 0
        for r in cprules:
            idx = r.param_idx if r.param_idx >= 0 else len(args) + r.param_idx
            if idx < 0 or idx >= len(args):
                continue                      # no such arg → rule passes
            value = args[idx]
            status, wait = -1, 0
            if svc is not None:
                try:
                    res = svc.request_param_token(r.cluster_flow_id, acquire,
                                                  [value])
                    status = int(res.status)
                    wait = int(getattr(res, "wait_ms", 0))
                except Exception as exc:
                    from sentinel_tpu.core.logs import record_log
                    record_log().warning(
                        "cluster param token request failed: %r", exc)
            if status == 0:
                continue
            if status == 2:
                if wait > 0:
                    if sleep:
                        self.clock.sleep_ms(wait)
                    else:
                        pending_wait += wait
                continue
            if status == 1:                   # BLOCKED
                if record:
                    raise self._record_cluster_block(
                        int(BlockReason.PARAM_FLOW), resource, origin, row,
                        o_row, c_row, acquire, is_in)
                raise self._log_cluster_block(int(BlockReason.PARAM_FLOW),
                                              resource, origin, acquire)
            # FAIL / NO_RULE / TOO_MANY: pass through (logged when RPC failed)
        return pending_wait

    def _resolve_param_pairs_one(self, row: int, args: Sequence):
        """→ (rules [PV], keys [PV], generation, registry), or None when the
        resource has no param rules / no args (rule-free events skip the
        param slot). Table, registry and generation are snapshotted together
        under the lock so they are mutually consistent. The key rows come
        back PINNED against LRU recycling (so a concurrent intern flood can't
        recycle them between decide and exit); the caller owns the unpin —
        on block, or after the exit-side decrement."""
        with self._lock:
            compiled = self._param
            registry = self.param_key_registry
            gen = self._param_gen
        if not compiled.num_active or not args:
            return None
        if row not in compiled.by_row:
            return None
        pr, pk = pf_mod.resolve_pairs(compiled, registry, row, args,
                                      self.spec.param_pairs)
        pins = pf_mod.thread_key_rows(compiled, pr, pk)
        registry.pin_rows(pins)
        return (pr, pk, gen, registry, pins)

    def _alt_row(self, row: int, kind: int, key_id: int) -> int:
        """Hash + record the (main row → alt row) edge for eviction
        hygiene. The slot's host identity ``(kind, key_id)`` travels
        with the edge so the tiering demote can snapshot the slice under
        a portable key and the promote can re-hash it onto the new row
        (tiering/manager.py)."""
        r = _alt_hash(row, kind, key_id, self.spec.alt_rows)
        self._alt_rows_by_row.setdefault(row, {})[r] = (kind, key_id)
        return r

    def _alt_rows_for(self, row: int, origin: str, context_name: str):
        ra = self.spec.alt_rows
        o_row = ra
        c_row = ra
        if origin:
            o_row = self._alt_row(row, 0, self.origins.get_or_create(origin))
        if context_name and context_name != "sentinel_default_context":
            c_row = self._alt_row(row, 1, self.contexts.get_or_create(context_name))
        return o_row, c_row

    def _fast_entry(self, resource: str, row: int, o_row: int, c_row: int,
                    origin_id: int, origin: str, acquire: int,
                    is_in: bool, args: Sequence = ()) -> Optional[Entry]:
        """Try the host fast path → an admitted :class:`Entry`, or None to
        take the exact device path (never decides a DENIAL on host)."""
        fast = self._fast
        if fast.sys_active and is_in:
            return None          # SystemSlot gates inbound traffic globally
        kind = fast.classify(row)
        if kind == fp_mod.INELIGIBLE:
            return None
        now = self.clock.now_ms()
        if kind == fp_mod.FREE:
            fast.buffer_pass(row, o_row, c_row, acquire, is_in, now)
            mode = "free"
        else:
            # leases pre-charge stats without alt rows, so they only serve
            # origin-less, default-context events; others need per-event
            # recording → device path
            if origin_id != 0 or c_row < self.spec.alt_rows:
                return None
            verdict = fast.lease_state(row, acquire, is_in, now)
            if verdict == fp_mod.DEVICE:
                return None
            if verdict == fp_mod.RENEW:
                if fast.is_hot(row, now):
                    return None    # chunk denied this bucket: exact path
                # single renewal in flight per row: a concurrent pre-charge
                # would double-spend the window budget (under-admission)
                if not fast.begin_renewal(row):
                    return None
                try:
                    # re-check under the claim (another thread may have
                    # installed a lease between lease_state and here)
                    recheck = fast.lease_state(row, acquire, is_in, now)
                    if recheck == fp_mod.DEVICE:
                        # a mismatched-entry-type lease went live meanwhile:
                        # pre-charging a second chunk would double-spend
                        # the window — exactly what DEVICE exists to avoid
                        return None
                    if recheck != fp_mod.ADMIT:
                        chunk = fast.lease_chunk(row, acquire)
                        gen0 = fast.table_gen
                        ra = self.spec.alt_rows
                        # at_ms=now: the chunk's PASS must land in the SAME
                        # bucket the lease is stamped with — a rotation
                        # mid-pre-charge would otherwise make the expiry
                        # uncount target a bucket that never held the chunk
                        v = self.decide_raw(
                            np.array([row], np.int32), np.zeros(1, np.int32),
                            np.array([ra], np.int32), np.zeros(1, np.int32),
                            np.array([ra], np.int32),
                            np.array([chunk], np.int32),
                            np.array([is_in], np.bool_),
                            np.zeros(1, np.bool_),
                            count_thread=np.zeros(1, np.bool_),
                            record_block=np.zeros(1, np.bool_),
                            at_ms=now)
                        if not bool(v.allow[0]):
                            fast.mark_hot(row, now)
                            return None
                        fast.install_lease(row, chunk, acquire, is_in, now,
                                           gen=gen0)
                finally:
                    fast.end_renewal(row)
            mode = "leased"
        if not self.callbacks.empty:   # StatisticSlot onPass
            self.callbacks.fire_pass(resource, origin, acquire, args)
        e = Entry(self, resource, row, o_row, c_row, acquire, is_in, now)
        e.fast = mode
        if fast.due(now):
            self._flush_fast(now)
        return e

    def _flush_fast(self, now_ms: Optional[int] = None) -> None:
        """Land buffered fast-path stats on device with their EVENT-TIME
        window stamps: groups are keyed by second-window index and each
        group dispatches with its own times, so late flushes (idle gaps,
        introspection pulls) still attribute pass/success to the second
        they happened in — reference exit-time recording semantics. Groups
        older than a full window ring relative to anything already
        dispatched are re-stamped to now (safe-late): stamping them old
        could resurrect a physical bucket a newer write already owns.
        Passes go through the normal jitted decide (rule-free events can't
        block → pure StatisticSlot recording), exits through the batched
        exit step."""
        now = self.clock.now_ms() if now_ms is None else now_ms
        with self._flush_lock:
            self._flush_fast_locked(now)

    def _flush_fast_locked(self, now: int) -> None:
        passes, exits, expired = self._fast.drain(now)
        if not passes and not exits and not expired:
            return
        B = self.spec.second.buckets
        idx_of = self.spec.second.index_of

        def grouped(events, ms_pos):
            by: dict = {}
            for e in events:
                by.setdefault(idx_of(e[ms_pos]), []).append(e)
            return sorted(by.items())

        for g_idx, grp in grouped(passes, 5):
            at = grp[0][5] if self._seen_idx - g_idx < B else None
            n = len(grp)
            self.decide_raw_nowait(
                np.fromiter((p[0] for p in grp), np.int32, n),
                np.zeros(n, np.int32),
                np.fromiter((p[1] for p in grp), np.int32, n),
                np.zeros(n, np.int32),
                np.fromiter((p[2] for p in grp), np.int32, n),
                np.fromiter((p[3] for p in grp), np.int32, n),
                np.fromiter((p[4] for p in grp), np.bool_, n),
                np.zeros(n, np.bool_),
                # verdicts unused (all rule-free), but the handle is
                # settled here, not left to the leak finalizer
                at_ms=at).result()
        if expired:
            # return unused lease tokens to their window buckets (pass
            # metrics then reflect actual admissions, not reservations);
            # is_in pre-charges also counted the ENTRY node
            rows: list = []
            secs: list = []
            mins: list = []
            amts: list = []
            min_spec = self.spec.minute
            for row, created, remaining, was_in in expired:
                targets = [row, ENTRY_NODE_ROW] if was_in else [row]
                for r in targets:
                    rows.append(r)
                    secs.append(self.spec.second.index_of(created))
                    mins.append(min_spec.index_of(created) if min_spec else 0)
                    amts.append(remaining)
            m = len(rows)
            bm = self._pad(m)
            with self._lock:
                self._state = self._uncount_step()(
                    self._state,
                    jnp.asarray(_pad_to(np.asarray(rows, np.int32), bm,
                                        self.spec.rows, np.int32)),
                    jnp.asarray(_pad_to(np.asarray(secs, np.int32), bm, 0,
                                        np.int32)),
                    jnp.asarray(_pad_to(np.asarray(mins, np.int32), bm, 0,
                                        np.int32)),
                    jnp.asarray(_pad_to(np.asarray(amts, np.int32), bm, 0,
                                        np.int32)))
        for g_idx, grp in grouped(exits, 8):
            at = grp[0][8] if self._seen_idx - g_idx < B else None
            n = len(grp)
            self.exit_batch(
                rows=np.fromiter((x[0] for x in grp), np.int32, n),
                origin_rows=np.fromiter((x[1] for x in grp), np.int32, n),
                chain_rows=np.fromiter((x[2] for x in grp), np.int32, n),
                acquire=np.fromiter((x[3] for x in grp), np.int32, n),
                rt_ms=np.fromiter((x[4] for x in grp), np.int32, n),
                error=np.fromiter((x[5] for x in grp), np.bool_, n),
                is_in=np.fromiter((x[6] for x in grp), np.bool_, n),
                count_thread=np.fromiter((x[7] for x in grp), np.bool_, n),
                at_ms=at)

    def _exit_one(self, e: Entry) -> None:
        if e.row < 0:  # global switch was off at entry
            return
        now = self.clock.now_ms()
        rt = max(0, now - e.create_ms)
        if e.fast is not None:
            # fast-path entries exit through the host buffer (leased ones
            # opted out of the thread gauge on entry — symmetric here)
            self._fast.buffer_exit(
                e.row, e.origin_row, e.chain_row, e.acquire,
                min(rt, self.cfg.statistic_max_rt), e.error is not None,
                e.is_in, e.fast == "free", now)
            if not self.callbacks.empty:
                self.callbacks.fire_exit(e.resource, rt, e.error is not None,
                                         e.acquire)
            if self._fast.due(now):
                self._flush_fast(now)
            return
        pr = pk = None
        gen = -1
        if e.param_pairs is not None:
            pr = e.param_pairs[0][None, :]
            pk = e.param_pairs[1][None, :]
            gen = e.param_pairs[2]
        self.exit_batch(
            rows=np.array([e.row], np.int32),
            origin_rows=np.array([e.origin_row], np.int32),
            chain_rows=np.array([e.chain_row], np.int32),
            acquire=np.array([e.acquire], np.int32),
            rt_ms=np.array([min(rt, self.cfg.statistic_max_rt)], np.int32),
            error=np.array([e.error is not None], np.bool_),
            is_in=np.array([e.is_in], np.bool_),
            param_rules=pr, param_keys=pk, param_gen=gen)
        if not self.callbacks.empty:           # MetricExitCallback analog
            self.callbacks.fire_exit(e.resource, rt, e.error is not None,
                                     e.acquire)

    # ------------------------------------------------------------------
    # Batch API (throughput tier)
    # ------------------------------------------------------------------

    def _pad(self, n: int) -> int:
        return pad_pow2(n)

    def _intern_batch(self, resources: Sequence[str]) -> InternedBatch:
        """Intern a batch of names for the batch doors: ONE dedup
        (:func:`sentinel_tpu.core.registry.intern_batch`), the registry
        and tiering's classification over the DISTINCT names, and the
        count of how far that helped (``intern.names`` /
        ``intern.distinct``: their ratio is the share of the per-name
        work a batch still pays — 1.0 for traffic that never repeats a
        name)."""
        batch = self.resources.intern_batch(resources)
        # tiering: classify hot hit / cold miss and queue promotions for
        # any re-interned cold keys (restored in the next eviction drain,
        # before that dispatch's decide)
        self.tiering.note_interned(batch.names_u, batch.rows_u, batch.counts)
        if self.obs.enabled:
            self.obs.counters.add(obs_keys.INTERN_NAMES, len(resources))
            self.obs.counters.add(obs_keys.INTERN_DISTINCT,
                                  len(batch.names_u))
        return batch

    def intern_resources(self, resources: Sequence[str]) -> np.ndarray:
        """Pre-stage a batch's resource rows: intern the names and return
        the int32 row array, one row per OCCURRENCE. Serving loops that
        dispatch the same resource set step after step pass the returned
        array straight to :meth:`entry_batch` / :meth:`entry_batch_nowait`
        as ``resources``, moving the string-encode + intern cost out of
        the per-step path (one FFI call here instead of one per step).

        The same dedup as a string batch's own (:meth:`_intern_batch`):
        the registry touches and tiering classifies each distinct NAME
        once where the batch repeats its names, so a Zipf batch over a
        huge keyspace (round 15's 16M–64M-key workloads) interns its few
        hundred distinct names once; ``tier.hot_hit`` / ``tier.cold_miss``
        count occurrences."""
        return self._intern_batch(resources).rows

    def entry_batch(self, resources: Sequence[str], *,
                    origins: Optional[Sequence[str]] = None,
                    contexts: Optional[Sequence[str]] = None,
                    acquire: Optional[Sequence[int]] = None,
                    entry_types: Optional[Sequence[int]] = None,
                    prioritized: Optional[Sequence[bool]] = None,
                    args_list: Optional[Sequence[Sequence]] = None) -> Verdicts:
        return self.entry_batch_nowait(
            resources, origins=origins, contexts=contexts, acquire=acquire,
            entry_types=entry_types, prioritized=prioritized,
            args_list=args_list).result()

    def entry_batch_nowait(
            self, resources: Sequence[str], *,
            origins: Optional[Sequence[str]] = None,
            contexts: Optional[Sequence[str]] = None,
            acquire: Optional[Sequence[int]] = None,
            entry_types: Optional[Sequence[int]] = None,
            prioritized: Optional[Sequence[bool]] = None,
            args_list: Optional[Sequence[Sequence]] = None,
            trace_id: int = 0
    ) -> "PendingVerdicts":
        """Dispatch-only batch tier: host prep + cluster delegation + the
        jitted decide are all issued, but the verdict readback (the ~RTT
        that dominates a remote-attached device) is deferred to
        ``.result()``. Callers double-buffer — dispatch batch N+1 while N's
        verdicts are in flight — to hide the device→host latency entirely.
        ``.result()`` MUST be called for every handle: it also releases
        blocked events' key pins and writes the block log. The handle's
        ``rows`` are the rows the events were admitted on: pass those of
        the entries that passed to :meth:`exit_batch`.

        ``args_list`` may be a 2D numpy integer array (one row per event) —
        the fastest form: single-rule integer-key workloads then resolve
        fully vectorized with one intern per distinct key.

        ``resources`` may be a numpy INTEGER array of pre-interned rows
        (from :meth:`intern_resources`) — serving loops that re-dispatch
        the same resource set every step then skip the per-step string
        intern entirely (the config-4 host-prep hotspot: encoding B
        strings per step dwarfed the device time at large batches).
        Names are recovered lazily (registry reverse lookup) only where a
        denial log or cluster/gate tier actually needs them. Rows evicted
        by registry pressure after interning resolve to row-recycled
        verdicts — same class of skew as any stale name→row cache."""
        n = len(resources)
        # self-telemetry: one flag check when off; when on, the
        # entry→verdict histogram records per batch and a sampled batch
        # (obs.spans stride) carries a trace id through its whole
        # lifecycle — entry prep → host gates → cluster precheck →
        # split decision → compile-cache lookup → device dispatch →
        # settle (docs/OBSERVABILITY.md span schema). A caller-minted
        # trace_id (DispatchPipeline / the serving front end) overrides
        # the stride so the batch stays on its causal chain.
        obs = self.obs
        obs_on = obs.enabled
        tr = (trace_id or obs.spans.maybe_trace()) if obs_on else 0
        t0 = obs.spans.now_ns() if obs_on else 0
        with obs.phase("entry.prep", n=n, trace=tr) as prep:
            if isinstance(resources, np.ndarray) and resources.dtype.kind in "iu":
                rows = np.ascontiguousarray(resources, np.int32)
                resources = None
            else:
                batch = self._intern_batch(resources)
                rows = batch.rows
                prep.note = f"distinct={len(batch.names_u)}"
            if resources is None and (self._host_gates  # graftlint: disable=LOCK002 -- hot-path feature gate: a stale read routes one batch through the exact device path, never unsafely
                                      or self._cluster_rules_by_row
                                      or self._cluster_param_rules_by_row):
                # gates and cluster delegation are name-keyed SPI surfaces;
                # materialize names once for the whole batch (rare combination)
                resources = [self.resources.name_of(int(r)) or "" for r in rows]
            param_rules = param_keys = None
            param_gen = -1
            with self._lock:
                compiled = self._param
                registry = self.param_key_registry
                gen = self._param_gen
            origin_ids = np.zeros(n, np.int32)
            origin_rows = np.full(n, self.spec.alt_rows, np.int32)
            context_ids = np.zeros(n, np.int32)
            chain_rows = np.full(n, self.spec.alt_rows, np.int32)
            if origins is not None:
                for i, o in enumerate(origins):
                    if o:
                        oid = self.origins.get_or_create(o)
                        origin_ids[i] = oid
                        origin_rows[i] = self._alt_row(int(rows[i]), 0, oid)
            if contexts is not None:
                for i, c in enumerate(contexts):
                    if c and c != "sentinel_default_context":
                        cid = self.contexts.get_or_create(c)
                        context_ids[i] = cid
                        chain_rows[i] = self._alt_row(int(rows[i]), 1, cid)
            acq = np.asarray(acquire, np.int32) if acquire is not None else np.ones(n, np.int32)
            is_in = (np.asarray(entry_types, np.int32) == ENTRY_TYPE_IN) \
                if entry_types is not None else np.ones(n, np.bool_)
            prio = np.asarray(prioritized, np.bool_) if prioritized is not None \
                else np.zeros(n, np.bool_)

        # user host gates veto first (slot-chain SPI tier 1); denials are
        # logged in the gate runner and device-recorded batched below.
        # Gates run BEFORE param-key pinning: a gate that raises must not
        # leak pins (a custom check_batch raising propagates to the caller)
        gate_blocked = gate_reasons = None
        if self._host_gates:  # graftlint: disable=LOCK002 -- hot-path feature gate: a stale read routes one batch through the exact device path, never unsafely
            t_g = obs.spans.now_ns() if tr else 0
            gate_blocked, gate_reasons = self._run_host_gates_batch(
                resources, origins, acq, args_list, is_in, n)
            if tr:
                obs.spans.record(tr, "entry.host_gates", t_g,
                                 obs.spans.now_ns(), n=n)
            if not gate_blocked.any():
                gate_blocked = gate_reasons = None

        pin_arr = None
        if args_list is not None and compiled.num_active:
            param_gen = gen
            param_rules, param_keys = pf_mod.resolve_pairs_many(
                compiled, registry, rows, args_list, self.spec.param_pairs)
            # pin THREAD-grade pairs while in flight (released for blocked
            # events below; allowed events stay pinned until exit_batch);
            # computed once and reused for the blocked-event release
            pin_arr = pf_mod.thread_key_rows(
                compiled, param_rules, param_keys).reshape(
                    param_keys.shape)
            registry.pin_rows(pin_arr)

        # cluster-mode rules: token delegation BEFORE the local decide, ONE
        # batched RPC for the whole batch when the service supports it.
        # Cluster-blocked events are excluded from the local decide and
        # surfaced as FLOW/PARAM_FLOW denials in the returned verdicts.
        cl = None
        if self._cluster_rules_by_row or self._cluster_param_rules_by_row:
            t_c = obs.spans.now_ns() if tr else 0
            cl = self._cluster_precheck_batch(
                resources, origins, rows, origin_rows, chain_rows,
                acq, is_in, prio, args_list, n, skip=gate_blocked)
            if tr:
                obs.spans.record(tr, "entry.cluster_precheck", t_c,
                                 obs.spans.now_ns(), n=n)
        cl_blocked = cl_waits = cl_reasons = None
        cluster_fb_arr = valid_mask = None
        if cl is not None:
            cluster_fb_arr, cl_blocked, cl_waits, cl_reasons, valid_mask = cl
        if gate_blocked is not None:
            # merge gate denials into the pre-blocked set (gates ran first,
            # so they take precedence and never overlap a cluster denial)
            if cl_blocked is None:
                cl_blocked = gate_blocked
                cl_reasons = gate_reasons
                cl_waits = np.zeros(n, np.int32)
                valid_mask = ~gate_blocked
            else:
                cl_blocked = cl_blocked | gate_blocked
                cl_reasons = np.where(gate_blocked, gate_reasons, cl_reasons)
                valid_mask = valid_mask & ~gate_blocked
        if cl_blocked is not None:
            # one batched device record for every pre-blocked event
            if cl_blocked.any():
                idxs = np.nonzero(cl_blocked)[0]
                m = len(idxs)
                bm = self._pad(m)
                times = self._time_scalars(self.clock.now_ms())
                with self._lock:
                    self._state = self._jit_record_blocks(
                        self._state,
                        jnp.asarray(_pad_to(rows[idxs], bm, self.spec.rows,
                                            np.int32)),
                        jnp.asarray(_pad_to(origin_rows[idxs], bm,
                                            self.spec.alt_rows, np.int32)),
                        jnp.asarray(_pad_to(chain_rows[idxs], bm,
                                            self.spec.alt_rows, np.int32)),
                        jnp.asarray(_pad_to(acq[idxs], bm, 0, np.int32)),
                        jnp.asarray(_pad_to(is_in[idxs], bm, False,
                                            np.bool_)),
                        jnp.asarray(_pad_to(np.ones(m, np.bool_), bm, False,
                                            np.bool_)),
                        times)

        pending = self.decide_raw_nowait(
            rows, origin_ids, origin_rows, context_ids, chain_rows, acq,
            is_in, prio, param_rules=param_rules, param_keys=param_keys,
            param_gen=param_gen, cluster_fallback=cluster_fb_arr,
            valid=valid_mask, trace_id=tr)

        def _finalize() -> Verdicts:
            t_s = obs.spans.now_ns() if tr else 0
            verdicts = pending.result()
            if cl_blocked is not None and cl_blocked.any():
                allow = np.array(verdicts.allow, copy=True)
                reason = np.array(verdicts.reason, copy=True)
                allow[cl_blocked] = False
                # per-event reason: param-token denials raise
                # ParamFlowException downstream, flow-token denials
                # FlowException (entry() parity)
                reason[cl_blocked] = cl_reasons[cl_blocked]
                verdicts = Verdicts(allow=allow, reason=reason,
                                    wait_ms=np.maximum(verdicts.wait_ms,
                                                       cl_waits))
            elif cl_waits is not None:
                verdicts = verdicts._replace(
                    wait_ms=np.maximum(verdicts.wait_ms, cl_waits))

            if param_keys is not None:
                # blocked events never exit → release their pins immediately
                blocked = ~np.asarray(verdicts.allow)
                if blocked.any():
                    registry.unpin_rows(pin_arr[blocked])
            # LogSlot parity for the batch tier: blocked events roll into
            # sentinel-block.log (same per-second dedup as the single path,
            # grouped here so a mostly-blocked batch is a handful of log
            # calls); cluster blocks were already logged in the pre-check
            denied = np.nonzero(~np.asarray(verdicts.allow))[0]
            if denied.size:
                reasons = np.asarray(verdicts.reason)
                grouped: dict = {}
                for i in denied.tolist():
                    if cl_blocked is not None and cl_blocked[i]:
                        continue
                    res_i = (resources[i] if resources is not None
                             else self.resources.name_of(int(rows[i])) or "")
                    key = (res_i, int(reasons[i]),
                           (origins[i] if origins is not None
                            and origins[i] else ""))
                    grouped[key] = grouped.get(key, 0) + 1
                for (res, rcode, origin), cnt in grouped.items():
                    self.block_log.log(
                        res, err_mod.exception_name_for(rcode),
                        origin=origin, count=cnt)
                    if obs_on:
                        self._obs_block(res, rcode, origin, cnt)
            if obs_on:
                allowed = np.asarray(verdicts.allow)
                paced = int(np.count_nonzero(
                    allowed & (np.asarray(verdicts.wait_ms) > 0)))
                obs.counters.add(obs_keys.VERDICT_PACED, paced)
                obs.counters.add(obs_keys.VERDICT_PASSED_NOW,
                                 int(np.count_nonzero(allowed)) - paced)
                t_end = obs.spans.now_ns()
                obs.hist_entry.record(t_end - t0)
                if tr:
                    obs.spans.record(tr, "entry.settle", t_s, t_end, n=n)
                    obs.spans.record(tr, "entry.total", t0, t_end, n=n)
            return verdicts

        return self._pending_verdicts(_finalize, rows=rows)

    def _log_cluster_block(self, reason: int, resource: str, origin: str,
                           acquire: int, exc=None,
                           slot_name: Optional[str] = None) -> BlockException:
        """Block log + StatisticSlot callbacks for a denial decided
        off-device (token server or host gate; device record happens
        batched upstream); returns the exception for callers that raise
        it. ``exc`` overrides the constructed exception (a gate raising
        its own BlockException subclass propagates it)."""
        if exc is None:
            if slot_name is None:
                slot_name = (self.slot_name_for_code(reason)
                             if reason >= BlockReason.CUSTOM_BASE else "")
            exc = block_exception_for(reason, resource, origin=origin,
                                      slot_name=slot_name)
        self.block_log.log(resource, type(exc).__name__, origin=origin)
        if self.obs.enabled:
            self._obs_block(resource, reason, origin, 1)
        if not self.callbacks.empty:
            self.callbacks.fire_blocked(resource, origin, acquire, exc)
        return exc

    def _obs_block(self, resource: str, rcode: int, origin: str,
                   count: int, now_ms: Optional[int] = None) -> None:
        """Per-reason denial counter + sampled structured block-event
        record (obs/eventlog.py), keyed by the int8 verdict code —
        custom-slot codes resolve through :meth:`slot_name_for_code`."""
        label = (self.slot_name_for_code(rcode)
                 if rcode >= BlockReason.CUSTOM_BASE
                 else err_mod.exception_name_for(rcode))
        obs = self.obs
        obs.counters.add(obs_keys.BLOCK_PREFIX + label, count)
        ms = self.clock.now_ms() if now_ms is None else now_ms
        obs.block_events.log(
            ms, resource, rcode, reason_name=label, origin=origin,
            count=count)
        # block-reason burst SLO trigger (obs/flight.py): one cheap
        # counter roll per grouped denial record, window math inside
        obs.flight.note_blocks(count, ms)

    def _cluster_precheck_batch(self, resources, origins, rows, origin_rows,
                                chain_rows, acq, is_in, prio, args_list,
                                n: int, skip=None):
        """Cluster token delegation for a whole batch → ``(fallback_bits or
        None, cl_blocked, cl_waits, cl_reasons, valid_mask)``.

        When the installed token service exposes the pipelined batch surface
        (``request_tokens_batch`` — the embedded engine and the socket
        client both do), ALL of the batch's token requests go out as ONE
        call instead of a blocking RPC per event
        (``ClusterFlowChecker.java:55-112`` semantics per request, applied
        in rule order per event; a BLOCKED verdict short-circuits the
        event's remaining results exactly like the exception would have).
        Tokens for an event's later rules may be consumed even when an
        earlier rule blocks — bounded over-consumption of the same class as
        the reference's tolerated check-then-act races. Falls back to the
        per-event blocking path for plain per-call services."""
        svc = self._token_service
        fallback = np.zeros(n, np.int32)      # per-rule slot bitmask
        cl_blocked = np.zeros(n, np.bool_)
        cl_waits = np.zeros(n, np.int32)
        cl_reasons = np.full(n, int(BlockReason.FLOW), np.int32)
        valid_mask = np.ones(n, np.bool_)

        use_batch = svc is not None and hasattr(svc, "request_tokens_batch")
        if not use_batch:
            for i in range(n):
                if skip is not None and skip[i]:
                    continue       # already denied by a host gate
                crules = self._cluster_rules_by_row.get(int(rows[i]))
                cprules = self._cluster_param_rules_by_row.get(int(rows[i]))
                if not crules and not cprules:
                    continue
                org = (origins[i] if origins is not None
                       and origins[i] else "")
                try:
                    if crules:
                        fb, w = self._cluster_check(
                            resources[i], org, int(rows[i]),
                            int(origin_rows[i]), int(chain_rows[i]),
                            int(acq[i]), bool(is_in[i]), bool(prio[i]),
                            crules, sleep=False, record=False)
                        fallback[i] = fb
                        cl_waits[i] = w
                    if (cprules and args_list is not None
                            and args_list[i] is not None
                            and len(args_list[i]) > 0):
                        cl_waits[i] += self._cluster_param_check(
                            resources[i], org, int(rows[i]),
                            int(origin_rows[i]), int(chain_rows[i]),
                            int(acq[i]), bool(is_in[i]), args_list[i],
                            cprules, sleep=False, record=False)
                except BlockException as exc:
                    cl_blocked[i] = True
                    if isinstance(exc, err_mod.ParamFlowException):
                        cl_reasons[i] = int(BlockReason.PARAM_FLOW)
                    valid_mask[i] = False   # out of the local decide
            return ((fallback if fallback.any() else None), cl_blocked,
                    cl_waits, cl_reasons, valid_mask)

        # ---- batched path: collect → one RPC per kind → apply in order ----
        flow_req: list = []    # (event_i, slot_k, rule)
        param_req: list = []   # (event_i, rule, value)
        for i in range(n):
            if skip is not None and skip[i]:
                continue           # already denied by a host gate
            crules = self._cluster_rules_by_row.get(int(rows[i]))
            cprules = self._cluster_param_rules_by_row.get(int(rows[i]))
            if crules:
                for slot_k, r in crules:
                    flow_req.append((i, slot_k, r))
            if (cprules and args_list is not None
                    and args_list[i] is not None
                    and len(args_list[i]) > 0):
                a = args_list[i]
                for r in cprules:
                    idx = (r.param_idx if r.param_idx >= 0
                           else len(a) + r.param_idx)
                    if 0 <= idx < len(a):
                        param_req.append((i, r, a[idx]))
        from sentinel_tpu.core.logs import record_log
        flow_res: list = [None] * len(flow_req)
        param_res: list = [None] * len(param_req)
        try:
            if flow_req:
                flow_res = svc.request_tokens_batch(
                    [(r.cluster_flow_id, int(acq[i]), bool(prio[i]))
                     for i, _k, r in flow_req])
        except Exception as exc:
            record_log().warning("batched cluster token request failed: %r",
                                 exc)
        # the param batch surface is gated on ITS OWN method — a service
        # exposing only the flow batch must not silently fail-open for
        # param rules (per-call requestParamToken is the fallback)
        try:
            if param_req and hasattr(svc, "request_param_tokens_batch"):
                param_res = svc.request_param_tokens_batch(
                    [(r.cluster_flow_id, int(acq[i]), [v])
                     for i, r, v in param_req])
            elif param_req:
                param_res = [svc.request_param_token(
                    r.cluster_flow_id, int(acq[i]), [v])
                    for i, r, v in param_req]
        except Exception as exc:
            record_log().warning("batched cluster param request failed: %r",
                                 exc)
        for (i, slot_k, r), res in zip(flow_req, flow_res):
            if cl_blocked[i]:
                continue        # first BLOCK wins (exception short-circuit)
            status = int(res.status) if res is not None else -1
            if status == 0:
                continue
            if status == 2:
                cl_waits[i] += int(getattr(res, "wait_ms", 0))
                continue
            if status == 1:
                cl_blocked[i] = True
                valid_mask[i] = False
                cl_reasons[i] = int(BlockReason.FLOW)
                self._log_cluster_block(
                    int(BlockReason.FLOW), resources[i],
                    (origins[i] if origins is not None and origins[i]
                     else ""), int(acq[i]))
                continue
            # FAIL / NO_RULE / BAD_REQUEST / TOO_MANY → per-rule fallback
            if r.cluster_fallback_to_local:
                fallback[i] |= 1 << slot_k
        for (i, r, _v), res in zip(param_req, param_res):
            if cl_blocked[i]:
                continue
            status = int(res.status) if res is not None else -1
            if status == 0:
                continue
            if status == 2:
                cl_waits[i] += int(getattr(res, "wait_ms", 0))
                continue
            if status == 1:
                cl_blocked[i] = True
                valid_mask[i] = False
                cl_reasons[i] = int(BlockReason.PARAM_FLOW)
                self._log_cluster_block(
                    int(BlockReason.PARAM_FLOW), resources[i],
                    (origins[i] if origins is not None and origins[i]
                     else ""), int(acq[i]))
            # other statuses: pass through (param fallback is pass-through)
        return ((fallback if fallback.any() else None), cl_blocked,
                cl_waits, cl_reasons, valid_mask)

    def _pad_pairs(self, arr: Optional[np.ndarray], b: int, fill: int):
        """Pad an [n, PV] pair array to [b, PV] (or None passthrough)."""
        if arr is None:
            return None
        out = np.full((b, self.spec.param_pairs), fill, np.int32)
        out[:arr.shape[0]] = arr
        return out

    def decide_raw(self, rows, origin_ids, origin_rows, context_ids, chain_rows,
                   acquire, is_in, prioritized, *, param_rules=None,
                   param_keys=None, param_gen: int = -1,
                   cluster_fallback=None, valid=None,
                   count_thread=None, record_block=None,
                   at_ms: Optional[int] = None) -> Verdicts:
        """Lowest-level host entry point: pre-resolved numpy arrays.
        ``param_gen`` is the generation the pair arrays were resolved against;
        stale pairs (a reload raced the resolve) are dropped, not misapplied."""
        return self.decide_raw_nowait(
            rows, origin_ids, origin_rows, context_ids, chain_rows, acquire,
            is_in, prioritized, param_rules=param_rules,
            param_keys=param_keys, param_gen=param_gen,
            cluster_fallback=cluster_fallback, valid=valid,
            count_thread=count_thread, record_block=record_block,
            at_ms=at_ms).result()

    def _batch_has_no_alt(self, origin_rows, chain_rows) -> bool:
        """True when every origin/chain row is padding (>= alt_rows) — the
        single criterion both the entry and exit paths use to pick the
        *_noalt step variants (the alt-table scatters compile away)."""
        pad_a = self.spec.alt_rows
        return bool(np.min(origin_rows, initial=pad_a) >= pad_a
                    and np.min(chain_rows, initial=pad_a) >= pad_a)

    def _on_leaked_handle(self) -> None:
        if self.obs.enabled:
            self.obs.counters.add(obs_keys.PIPE_LEAKED)
        _log.warning("PendingVerdicts dropped without .result(); "
                     "settled by the GC finalizer")

    def _pending_verdicts(self, fn, rows=None) -> "PendingVerdicts":
        """Wrap a deferred settle in a leak-guarded handle (every nowait
        path returns through here so no handle can silently drop its
        bookkeeping)."""
        h = PendingVerdicts(fn)
        h.rows = rows
        h.attach_leak_guard(self._on_leaked_handle)
        return h

    def _breaker_snapshot_locked(self):
        """Donation-safe handle on the current breaker-state column for a
        DEFERRED read: with donation on, the state pytree owning this
        leaf is consumed by the next dispatched step, so observers get a
        small async device-side copy instead of the live leaf."""
        col = self._state.breakers.state
        return _jit_copy_column(col) if self._donate else col

    def _breaker_ticket_locked(self):
        """Breaker observers ride a step's existing readback: →
        ``(seq, degrade rules, state column)`` for the deferred diff, or
        None without observers. The seq is taken under the dispatch lock
        so diffs land in dispatch order."""
        if not self._breaker_observers:
            return None
        self._breaker_seq += 1
        return (self._breaker_seq, self._deg.rules,
                self._breaker_snapshot_locked())

    @staticmethod
    def _lane_eligibility(n, origin_ids, acquire, prioritized, valid,
                          flow_slots, pad_a):
        """Host-side route eligibility of one raw batch (numpy, before
        any padding) → ``(vfull, oid_np, prio_np, acq_uniform,
        no_origin_ids, key_fits, any_prio)``.

        Only lanes the caller marked valid count: arbitrary values on
        invalid lanes are masked device-side and must not disqualify a
        fast path. A shorter ``valid`` is legal (pad_to fills False).
        ``key_fits``: the fast general path's composite rank key
        (``flow_slots`` × (``pad_a`` + 1)) must fit int32. ``prio_np`` is
        the one host copy of the prioritized column, reused by the
        any-prio check, the split mask and the occupy-granted count."""
        vfull = np.ones(n, np.bool_)
        if valid is not None:
            vsrc = np.asarray(valid, bool)
            m = min(n, vsrc.shape[0])
            vfull[:] = False
            vfull[:m] = vsrc[:m]
        acq_np = np.asarray(acquire)
        oid_np = np.asarray(origin_ids)
        acq_v = acq_np if valid is None else acq_np[vfull]
        acq_uniform = (acq_v.size > 0
                       and int(acq_v.min()) == int(acq_v.max()) >= 1)
        oid_v = oid_np if valid is None else oid_np[vfull]
        no_origin_ids = int(np.max(oid_v, initial=0)) == 0
        key_fits = flow_slots * (pad_a + 1) < 2 ** 31
        prio_np = np.asarray(prioritized)
        return (vfull, oid_np, prio_np, acq_uniform, no_origin_ids,
                key_fits, bool(prio_np.any()))

    def _step_scalars(self, now):
        """The ``(times, sys_scalars)`` operands of a decide step stamped
        at ``now``."""
        times = self._time_scalars(now)
        load1, cpu = self._cpu.sample()
        return times, jnp.asarray(np.array([load1, cpu], np.float32))

    def _base_flags(self) -> dict:
        """The static flags every decide program takes; the route adds
        its own (``scalar_flow`` / ``fast_flow`` + ``scalar_has_rl``)."""
        flags = {"skip_auth": self._skip_auth,
                 "skip_sys": self._skip_sys,
                 "skip_threads": self._skip_threads}
        if self._sortfree:
            # conditional key presence: with sortfree disabled the
            # flags dict — hence every cached program key — is
            # byte-identical to pre-round-10 builds
            flags["sortfree"] = True
        return flags

    def _observe_locked(self, *batches):
        """Hot-set sketch observe (tiering) for the batches about to be
        decided → ``(sd_sketch, standalone observes dispatched)``.
        Single-dispatch engines fuse the scatter-max INTO the decide
        program (the sketch rides as a donated operand, returned here);
        otherwise ``sd_sketch`` is None and the legacy standalone observe
        is dispatched per batch. Padding lanes are valid=False no-ops
        either way."""
        sd_sketch = (self.tiering.sketch_for_fuse_locked()
                     if self._single_dispatch else None)
        observed = 0
        if sd_sketch is None:
            for b in batches:
                observed += int(self.tiering.observe_locked(b.rows, b.valid))
        return sd_sketch, observed

    def _occupy_stamp_locked(self, any_prio: bool, now: int) -> bool:
        """Static occupy variant: the occupy-aware pipeline runs only when
        this batch is prioritized OR a previous booking can still be live
        (bookings last ≤ B+1 windows — a concurrent prioritized batch
        since the caller's optimistic host check keeps occupy live);
        everything else compiles to a pipeline with zero occupy code."""
        if any_prio:
            self._occupy_live_until_ms = now + (
                (self.spec.second.buckets + 1) * self.spec.second.win_ms)
        return any_prio or now < self._occupy_live_until_ms

    def _run_decide_locked(self, batch, flags, no_alt, use_occ, sd_sketch,
                           state, times, sys_scalars):
        """THE place a decide program is dispatched (engine lock held) →
        ``(state, verdicts, sketch)``. ``no_alt`` / ``use_occ`` pick the
        variant — the *_noalt ones compile the alt-table scatters away
        (origin ids without rows are fine for the elision: the fast path
        matches them by ID) — and ``sd_sketch`` the family: the
        same-indexed sketch-fused program when not None, else the legacy
        one (``sketch`` is then None)."""
        if sd_sketch is not None:
            step = self._sd_steps_locked()[
                (2 if no_alt else 0) + (1 if use_occ else 0)]
            self._note_program_locked("decide_sd", step, batch, flags)
            return step(self._ruleset, state, sd_sketch, batch, times,
                        sys_scalars, **flags)
        if no_alt:
            step = (self._jit_decide_prio_noalt if use_occ
                    else self._jit_decide_noalt)
        else:
            step = self._jit_decide_prio if use_occ else self._jit_decide
        self._note_program_locked("decide", step, batch, flags)
        state, verdicts = step(self._ruleset, state, batch, times,
                               sys_scalars, **flags)
        return state, verdicts, None

    def _settle_decide(self, staged, parts, granted, brk, obs_on, tr,
                       span, t_disp, n) -> None:
        """Shared tail of a decide handle's deferred read, run once every
        verdict array of ``parts`` is on the host. That proves the device
        consumed the staged host operands: only now may the slots be
        reused (a read that raised instead just leaks its slots — safe).
        ``granted`` = ``(allow, wait_ms, prioritized)`` host columns of
        the lanes that may have booked, or None."""
        while staged:
            ring, slot = staged.pop()
            ring.release(slot)
        if obs_on:
            obs = self.obs
            t_end = obs.spans.now_ns()
            obs.hist_dispatch.record(t_end - t_disp)
            if tr:
                obs.spans.record(tr, span, t_disp, t_end, n=n)
            ovf = 0
            for v in parts:
                if v.sf_overflow is not None:
                    ovf += int(np.asarray(v.sf_overflow))
            if ovf:
                obs.counters.add(obs_keys.SORTFREE_OVERFLOW, ovf)
            if granted is not None:
                allow, wait_ms, prio = granted
                booked = int(np.count_nonzero(allow & (wait_ms > 0) & prio))
                if booked:
                    obs.counters.add(obs_keys.OCCUPY_GRANTED, booked)
        if brk is not None:
            self._diff_and_fire_breakers(
                brk[0], brk[1], np.asarray(brk[2][:-1]).tolist())

    def decide_raw_nowait(self, rows, origin_ids, origin_rows, context_ids,
                          chain_rows, acquire, is_in, prioritized, *,
                          param_rules=None, param_keys=None,
                          param_gen: int = -1, cluster_fallback=None,
                          valid=None, count_thread=None,
                          record_block=None,
                          at_ms: Optional[int] = None,
                          trace_id: int = 0) -> "PendingVerdicts":
        """:meth:`decide_raw` with the verdict readback deferred: the step
        is dispatched (state already advanced in order under the lock) and
        the device→host verdict copy started async; ``.result()``
        materializes. The double-buffering primitive for serving paths.

        Path selection (host-verified; see rules/flow.py for the variants):

        * all events scalar-eligible → scalar admission path (with live
          occupy bookings: the occupy-base scalar variant — bookings are
          read into the QPS base, never written);
        * origin-bearing or PRIORITIZED events present, uniform acquire →
          the fast general path (whole batch; prioritized traffic takes
          the occupy-capable variant), or a PER-EVENT SPLIT when the
          batch mixes kinds — one origin or prioritized event no longer
          demotes the entire batch to the sorted path;
        * otherwise (non-uniform acquire, oversized key) → general path.

        ``trace_id`` threads a sampled batch's span chain through from
        ``entry_batch_nowait``; direct callers get their own sampling
        decision. Every dispatch lands one ``split_route.*`` counter.
        """
        n = rows.shape[0]
        obs = self.obs
        obs_on = obs.enabled
        tr = trace_id if trace_id else (obs.spans.maybe_trace()
                                        if obs_on else 0)
        t_d0 = obs.spans.now_ns() if obs_on else 0
        pad_a = self.spec.alt_rows
        (vfull, oid_np, prio_np, acq_uniform, no_origin_ids, key_fits,
         any_prio) = self._lane_eligibility(
            n, origin_ids, acquire, prioritized, valid,
            self._ruleset.flow_table.active.shape[0],  # graftlint: disable=LOCK002 -- single atomic reference read; rule swaps publish a complete RuleSet under the lock
            pad_a)
        no_alt_rows = self._batch_has_no_alt(origin_rows, chain_rows)
        now = self.clock.now_ms() if at_ms is None else at_ms

        # ---- per-event split (occupy state re-verified under the lock
        # by _decide_split_nowait). The dominant pure-scalar batch
        # short-circuits on the aggregate checks above and never
        # materializes the per-event mask (hot dispatch path). Neither
        # prioritized events nor live bookings disable the split any
        # more: prioritized events ride the general side's occupy-capable
        # fast variant, and the scalar side folds live bookings into its
        # admission base (occupy_base) — the pre-r6 whole-batch demotion
        # to the sorted path was a whole-batch cliff.
        pure_scalar = (no_origin_ids and no_alt_rows
                       and cluster_fallback is None)
        if (not pure_scalar or any_prio) and acq_uniform and key_fits:
            # per-event scalar eligibility: no origin id (origin-limited
            # RELATE rules match on the ID, not the row), no real alt
            # rows, no cluster-fallback bits, not prioritized (only the
            # general side may book); invalid lanes scalar-safe
            ev_scalar = ((oid_np == 0)
                         & (np.asarray(origin_rows) >= pad_a)
                         & (np.asarray(chain_rows) >= pad_a)
                         & ~prio_np)
            if cluster_fallback is not None:
                ev_scalar = ev_scalar & (np.asarray(cluster_fallback) == 0)
            ev_scalar = ev_scalar | ~vfull
            n_general_v = int(np.count_nonzero(~ev_scalar & vfull))
            n_scalar_v = int(np.count_nonzero(ev_scalar & vfull))
            if n_general_v > 0 and n_scalar_v >= 4096:
                if obs_on:
                    obs.counters.add(obs_keys.ROUTE_SPLIT)
                    if self.mesh is not None:
                        obs.counters.add(obs_keys.ROUTE_MESHED)
                    if tr:
                        obs.spans.record(
                            tr, "decide.split_decision", t_d0,
                            obs.spans.now_ns(), n=n,
                            note=f"scalar={n_scalar_v} "
                                 f"general={n_general_v}")
                return self._decide_split_nowait(
                    rows, origin_ids, origin_rows, context_ids, chain_rows,
                    acquire, is_in, ev_scalar, vfull,
                    prioritized=prio_np, any_prio=any_prio,
                    param_rules=param_rules, param_keys=param_keys,
                    param_gen=param_gen, cluster_fallback=cluster_fallback,
                    count_thread=count_thread, record_block=record_block,
                    now=now, trace_id=tr)

        # the whole-batch route: from the batch build to the dispatch
        # returning (the split route above records split.dispatch)
        with obs.phase("decide.dispatch", n=n, trace=tr) as dispatch:
            staged: list = []
            batch = self._build_entry_batch(
                rows, origin_ids, origin_rows, context_ids, chain_rows,
                acquire, is_in, prioritized, vfull, param_rules, param_keys,
                cluster_fallback, count_thread, record_block, staged=staged)
            times, sys_scalars = self._step_scalars(now)
            lock_wait = obs.phase("engine.lock_wait", n=n, trace=tr).start()
            with self._lock:
                lock_wait.stop()
                # gen check must happen under the same lock that guards reloads,
                # or a reload racing here could land stale pairs on the new table
                if batch.param_rules is not None and param_gen != self._param_gen:
                    batch = batch._replace(param_rules=None, param_keys=None)
                now, times = self._restamp_if_stale_locked(at_ms, now, times)
                self._drain_evictions_locked()
                sd_sketch, observed = self._observe_locked(batch)
                self._seen_idx = max(self._seen_idx,
                                     self.spec.second.index_of(now))
                use_occ = self._occupy_stamp_locked(any_prio, now)
                flags = self._base_flags()
                if (no_alt_rows and no_origin_ids and not any_prio
                        and cluster_fallback is None and acq_uniform):
                    # scalar admission path (rules/flow.flow_check_scalar);
                    # requires the row-based no_alt (the step variant must be
                    # record_alt=False for the scalar assertion). Live occupy
                    # bookings are fine: the occupy step variant folds them
                    # into the QPS base (occupy_base) — this path never books
                    flags["scalar_flow"] = True
                    flags["scalar_has_rl"] = self._scalar_has_rl
                elif acq_uniform and key_fits:
                    # fast general path: origins/alt rows/fallback bits live,
                    # rank closed-form admission (rules/flow.flow_check_fast);
                    # with prioritized events or live bookings the occupy-
                    # capable variant runs (flow_check_fast_occupy) — no more
                    # whole-batch demotion to the sorted path
                    flags["fast_flow"] = True
                    flags["scalar_has_rl"] = self._scalar_has_rl
                with obs.annotate("sentinel_tpu.decide"):
                    self._state, verdicts, new_sketch = \
                        self._run_decide_locked(
                            batch, flags, no_alt_rows, use_occ, sd_sketch,
                            self._state, times, sys_scalars)
                if sd_sketch is not None:
                    self.tiering.set_sketch_locked(new_sketch)
                brk = self._breaker_ticket_locked()
            start_host_copy((verdicts.allow, verdicts.reason, verdicts.wait_ms)
                            + ((brk[2],) if brk else ()))
            if obs_on:
                # which path this whole batch took (flags/use_occ were fixed
                # under the dispatch lock)
                if "scalar_flow" in flags:
                    route = obs_keys.ROUTE_SCALAR
                elif "fast_flow" in flags:
                    route = (obs_keys.ROUTE_FAST_OCCUPY if use_occ
                             else obs_keys.ROUTE_FAST)
                else:
                    route = obs_keys.ROUTE_GENERAL
                obs.counters.add(route)
                if "sortfree" in flags:
                    obs.counters.add(obs_keys.ROUTE_SORTFREE)
                if self.mesh is not None:
                    obs.counters.add(obs_keys.ROUTE_MESHED)
                obs.counters.add(obs_keys.PIPE_DISPATCH, 1 + observed)
                if sd_sketch is not None:
                    obs.counters.add(obs_keys.ROUTE_SINGLE_DISPATCH)
                dispatch.note = route.split(".", 1)[1]
        t_disp = obs.spans.now_ns() if obs_on else 0

        def _read() -> Verdicts:
            out = Verdicts(allow=np.asarray(verdicts.allow)[:n],
                           reason=np.asarray(verdicts.reason)[:n],
                           wait_ms=np.asarray(verdicts.wait_ms)[:n])
            self._settle_decide(
                staged, (verdicts,),
                (out.allow, out.wait_ms, prio_np[:n]) if any_prio else None,
                brk, obs_on, tr, "decide.device", t_disp, n)
            return out

        return self._pending_verdicts(_read)

    def _note_program_locked(self, kind: str, step, batch, flags) -> None:
        """First-vs-repeat dispatch accounting per (program, padded batch
        geometry, statics) combo: ``compile_cache.miss`` on the dispatch
        that traces and compiles the program (or loads it from the
        persistent cache), ``compile_cache.hit`` on every later one.
        ``kind`` separates families that share a jit object's statics
        but are different executables (``decide`` / ``decide_sd``)."""
        geometry = (int(batch.rows.shape[0]),)
        columns = tuple(c is not None for c in batch)
        key = program_key(kind, id(step), geometry, flags, columns)
        hit = key in self._fetched_programs
        if not hit:
            self._fetched_programs.add(key)
        if self.obs.enabled:
            self.obs.counters.add(obs_keys.CACHE_HIT if hit
                                  else obs_keys.CACHE_MISS)

    # below this padded size, staging buys nothing: the per-call entry
    # tier pads to b=8..256 and its allocation cost is noise, while the
    # ring would become shared mutable state for every concurrent
    # entry() thread
    _STAGING_MIN_B = 512

    def _build_entry_batch(self, rows, origin_ids, origin_rows, context_ids,
                           chain_rows, acquire, is_in, prioritized, vfull,
                           param_rules, param_keys, cluster_fallback,
                           count_thread, record_block,
                           staged=None) -> EntryBatch:
        """Pad raw numpy event arrays into a device EntryBatch (shared by
        the whole-batch and split dispatch paths).

        Serving-sized batches fill a preallocated staging slot
        (``_StagingRing``) in place of ~9 fresh allocations per step;
        the rare optional columns (param pairs, cluster bits, thread
        counting, block recording) stay freshly allocated. ``staged``
        (a list) is the slot-ownership out-param: a staging slot used
        here is appended as ``(ring, slot)`` and the CALLER must release
        it after its dispatch settles (the deferred-read closures do).
        Callers that pass no list get fresh allocations — a slot nobody
        will release must never be acquired.

        Meshed serving additionally places every column on its batch-axis
        :class:`NamedSharding` (parallel/local_shard.place_batch) so the
        host→device transfer lands partitioned like the step that
        consumes it. Placement BYPASSES the staging ring: ``device_put``
        gives no bound on when it finishes reading the source buffer, so
        a reused slot could be rewritten mid-transfer by a later step in
        the dispatch window — fresh columns make the handoff safe."""
        n = rows.shape[0]
        b = self._pad(n)
        pad_r = self.spec.rows
        pad_a = self.spec.alt_rows
        if (self._staging_on and b >= self._STAGING_MIN_B
                and staged is not None and not self._place_batches):
            ring = self._staging.get(b)
            if ring is None:
                ring = self._staging.setdefault(
                    b, _StagingRing(b, self._staging_depth))
            s = ring.acquire()
            staged.append((ring, s))
            rows_c = _pad_into(s["rows"], rows, pad_r)
            origin_ids_c = _pad_into(s["origin_ids"], origin_ids, 0)
            origin_rows_c = _pad_into(s["origin_rows"], origin_rows, pad_a)
            context_ids_c = _pad_into(s["context_ids"], context_ids, 0)
            chain_rows_c = _pad_into(s["chain_rows"], chain_rows, pad_a)
            acquire_c = _pad_into(s["acquire"], acquire, 0)
            is_in_c = _pad_into(s["is_in"], is_in, False)
            prio_c = _pad_into(s["prioritized"], prioritized, False)
            valid_c = _pad_into(s["valid"], vfull, False)
        else:
            rows_c = _pad_to(rows, b, pad_r, np.int32)
            origin_ids_c = _pad_to(origin_ids, b, 0, np.int32)
            origin_rows_c = _pad_to(origin_rows, b, pad_a, np.int32)
            context_ids_c = _pad_to(context_ids, b, 0, np.int32)
            chain_rows_c = _pad_to(chain_rows, b, pad_a, np.int32)
            acquire_c = _pad_to(acquire, b, 0, np.int32)
            is_in_c = _pad_to(is_in, b, False, np.bool_)
            prio_c = _pad_to(prioritized, b, False, np.bool_)
            valid_c = _pad_to(vfull, b, False, np.bool_)
        batch = EntryBatch(
            rows=rows_c,
            origin_ids=origin_ids_c,
            origin_rows=origin_rows_c,
            context_ids=context_ids_c,
            chain_rows=chain_rows_c,
            acquire=acquire_c,
            is_in=is_in_c,
            prioritized=prio_c,
            valid=valid_c,
            param_rules=self._pad_pairs(param_rules, b,
                                        self.cfg.max_param_rules),
            param_keys=self._pad_pairs(param_keys, b, self.spec.param_keys),
            cluster_fallback=(_pad_to(cluster_fallback, b, 0, np.int32)
                              if cluster_fallback is not None else None),
            count_thread=(_pad_to(count_thread, b, False, np.bool_)
                          if count_thread is not None else None),
            record_block=(_pad_to(record_block, b, False, np.bool_)
                          if record_block is not None else None),
        )
        return self._place_batch(batch, n)

    def _place_batch(self, batch, n: int):
        """Meshed-mode batch-axis placement (no-op otherwise); shared by
        the entry, split and exit dispatch tiers. ``n`` events
        are placed under the phase ``batch.place`` — the host work only
        the mesh path does, apart from the dispatch phase around it."""
        if not self._place_batches:
            return batch
        from sentinel_tpu.parallel.local_shard import place_batch
        with self.obs.phase("batch.place", n=n):
            return place_batch(batch, self.mesh)

    def _decide_split_nowait(self, rows, origin_ids, origin_rows,
                             context_ids, chain_rows, acquire, is_in,
                             ev_scalar, vfull, *, prioritized, any_prio,
                             param_rules, param_keys,
                             param_gen, cluster_fallback, count_thread,
                             record_block, now,
                             trace_id: int = 0) -> "PendingVerdicts":
        """Mixed-batch dispatch: scalar-eligible events take the scalar
        step, origin-bearing AND prioritized ones the fast general step —
        one origin or prioritized event no longer demotes the whole batch
        off the fast paths.

        The two sub-steps run scalar-first under one dispatch-lock hold.
        That is a legitimate serialization of the batch: intra-batch
        ordering is already a batching artifact (the reference's
        concurrent callers race the same way), and each sub-step is
        bit-exact with the general path over its own events
        (tests/test_split_dispatch.py pins split == sequential).
        Prioritized events are routed to the GENERAL side by the caller's
        ``ev_scalar`` mask: only the general sub-step may commit occupy
        bookings (flow_check_fast_occupy); the scalar sub-step runs first
        and — when bookings may be live — folds them into its admission
        base (occupy_base) without ever writing them."""
        n = rows.shape[0]
        obs = self.obs
        obs_on = obs.enabled
        tr = trace_id
        t_d0 = obs.spans.now_ns() if obs_on else 0
        idx_s = np.nonzero(ev_scalar)[0]
        idx_g = np.nonzero(~ev_scalar)[0]

        def take(arr, idx):
            return None if arr is None else np.asarray(arr)[idx]

        zeros_s = np.zeros(idx_s.shape[0], np.bool_)
        zeros_g = np.zeros(idx_g.shape[0], np.bool_)
        staged: list = []
        bs = self._build_entry_batch(
            take(rows, idx_s), take(origin_ids, idx_s),
            take(origin_rows, idx_s), take(context_ids, idx_s),
            take(chain_rows, idx_s), take(acquire, idx_s),
            take(is_in, idx_s), zeros_s, vfull[idx_s],
            take(param_rules, idx_s), take(param_keys, idx_s),
            None, take(count_thread, idx_s), take(record_block, idx_s),
            staged=staged)
        orow_g = take(origin_rows, idx_g)
        crow_g = take(chain_rows, idx_g)
        prio_g = (take(prioritized, idx_g) if any_prio else zeros_g)
        bg = self._build_entry_batch(
            take(rows, idx_g), take(origin_ids, idx_g), orow_g,
            take(context_ids, idx_g), crow_g, take(acquire, idx_g),
            take(is_in, idx_g), prio_g, vfull[idx_g],
            take(param_rules, idx_g), take(param_keys, idx_g),
            take(cluster_fallback, idx_g), take(count_thread, idx_g),
            take(record_block, idx_g), staged=staged)
        no_alt_g = self._batch_has_no_alt(orow_g, crow_g)
        times, sys_scalars = self._step_scalars(now)
        lock_wait = obs.phase("engine.lock_wait", n=n, trace=tr).start()
        with self._lock:
            lock_wait.stop()
            if bs.param_rules is not None and param_gen != self._param_gen:
                bs = bs._replace(param_rules=None, param_keys=None)
                bg = bg._replace(param_rules=None, param_keys=None)
            self._drain_evictions_locked()
            # both split halves carry real traffic rows; with the observe
            # fused, the sketch threads through both sub-steps
            sd_sketch, observed = self._observe_locked(bs, bg)
            self._seen_idx = max(self._seen_idx,
                                 self.spec.second.index_of(now))
            flags = self._base_flags()
            # occupy re-verified under the lock: both sides take their
            # occupy-AWARE fast variants when it is live (scalar reads
            # live bookings via occupy_base, general may book via
            # flow_check_fast_occupy); neither demotes to the sorted path
            use_occ = self._occupy_stamp_locked(any_prio, now)
            fl_s = dict(flags, scalar_flow=True,
                        scalar_has_rl=self._scalar_has_rl)
            fl_g = dict(flags, fast_flow=True,
                        scalar_has_rl=self._scalar_has_rl)
            with obs.annotate("sentinel_tpu.decide_split"):
                # the scalar half is always the noalt variant (origin-free
                # by construction), the general half keys off its own
                # no_alt_g
                state, v1, sketch = self._run_decide_locked(
                    bs, fl_s, True, use_occ, sd_sketch, self._state, times,
                    sys_scalars)
                self._state, v2, sketch = self._run_decide_locked(
                    bg, fl_g, no_alt_g, use_occ, sketch, state, times,
                    sys_scalars)
            if sd_sketch is not None:
                self.tiering.set_sketch_locked(sketch)
            brk = self._breaker_ticket_locked()
        start_host_copy((v1.allow, v1.reason, v1.wait_ms,
                         v2.allow, v2.reason, v2.wait_ms)
                        + ((brk[2],) if brk else ()))
        n_s = idx_s.shape[0]
        n_g = idx_g.shape[0]
        t_disp = 0
        if obs_on:
            if "sortfree" in flags:
                obs.counters.add(obs_keys.ROUTE_SORTFREE)
            # two sub-dispatches plus any legacy standalone observes;
            # split never earns split_route.single_dispatch (it is a
            # two-program route by definition)
            obs.counters.add(obs_keys.PIPE_DISPATCH, 2 + observed)
            t_disp = obs.spans.now_ns()
            if tr:
                obs.spans.record(tr, "split.dispatch", t_d0, t_disp, n=n,
                                 note=f"scalar={n_s} general={n_g} "
                                      f"occ={int(use_occ)}")

        def _read() -> Verdicts:
            allow = np.empty(n, np.bool_)
            reason = np.empty(n, np.int8)
            wait = np.empty(n, np.int32)
            allow[idx_s] = np.asarray(v1.allow)[:n_s]
            reason[idx_s] = np.asarray(v1.reason)[:n_s]
            wait[idx_s] = np.asarray(v1.wait_ms)[:n_s]
            allow[idx_g] = np.asarray(v2.allow)[:n_g]
            reason[idx_g] = np.asarray(v2.reason)[:n_g]
            wait[idx_g] = np.asarray(v2.wait_ms)[:n_g]
            self._settle_decide(
                staged, (v1, v2),
                (allow[idx_g], wait[idx_g], prio_g) if any_prio else None,
                brk, obs_on, tr, "split.device", t_disp, n)
            return Verdicts(allow=allow, reason=reason, wait_ms=wait)

        return self._pending_verdicts(_read)

    def exit_batch(self, *, rows, origin_rows, chain_rows, acquire, rt_ms,
                   error, is_in, param_rules=None, param_keys=None,
                   param_gen: int = -1, count_thread=None,
                   at_ms: Optional[int] = None) -> None:
        n = rows.shape[0]
        obs = self.obs
        tr = obs.spans.maybe_trace() if obs.enabled else 0
        with obs.phase("exit.dispatch", n=n, trace=tr):
            b = self._pad(n)
            batch = ExitBatch(
                rows=_pad_to(rows, b, self.spec.rows, np.int32),
                origin_rows=_pad_to(origin_rows, b, self.spec.alt_rows, np.int32),
                chain_rows=_pad_to(chain_rows, b, self.spec.alt_rows, np.int32),
                acquire=_pad_to(acquire, b, 0, np.int32),
                rt_ms=_pad_to(rt_ms, b, 0, np.int32),
                error=_pad_to(error, b, False, np.bool_),
                is_in=_pad_to(is_in, b, False, np.bool_),
                valid=_pad_to(np.ones(n, np.bool_), b, False, np.bool_),
                param_rules=self._pad_pairs(param_rules, b, self.cfg.max_param_rules),
                param_keys=self._pad_pairs(param_keys, b, self.spec.param_keys),
                count_thread=(_pad_to(count_thread, b, False, np.bool_)
                              if count_thread is not None else None),
            )
            batch = self._place_batch(batch, n)
            now = self.clock.now_ms() if at_ms is None else at_ms
            times = self._time_scalars(now)
            lock_wait = obs.phase("engine.lock_wait", n=n, trace=tr).start()
            with self._lock:
                lock_wait.stop()
                now, times = self._restamp_if_stale_locked(at_ms, now, times)
                if self.tiering.enabled:
                    # tiering only: a key demoted between entry and exit must
                    # promote back before this decrement, or the exit would
                    # land on a recycled (or invalidated) row. Tiering-off
                    # keeps the historical no-drain exit path.
                    self._drain_evictions_locked()
                self._seen_idx = max(self._seen_idx,
                                     self.spec.second.index_of(now))
                unpin = None
                if batch.param_rules is not None:
                    if param_gen != self._param_gen:
                        # state was reset by a reload: neither decrement nor unpin
                        # (the pins live on the discarded registry)
                        batch = batch._replace(param_rules=None, param_keys=None)
                    else:
                        unpin = (self.param_key_registry,
                                 pf_mod.thread_key_rows(self._param, param_rules,
                                                        param_keys))
                exit_step = (self._jit_exit_noalt
                             if self._batch_has_no_alt(origin_rows, chain_rows)
                             else self._jit_exit)
                with self.obs.annotate("sentinel_tpu.exit"):
                    self._state = exit_step(self._ruleset, self._state, batch,
                                            times,
                                            skip_threads=self._skip_threads)
                # exit feeds resolve probes / trip breakers: with observers
                # registered, this call pays one small state read so the
                # observer fires within the exit call that caused the arc
                brk = self._breaker_ticket_locked()
            # unpin only AFTER the device-side decrement is enqueued (entry-side
            # pin discipline: resolve→pin, decide, exit-decrement→unpin)
            if unpin is not None:
                unpin[0].unpin_rows(unpin[1])
            if obs.enabled:
                obs.counters.add(obs_keys.PIPE_DISPATCH)
        if brk is not None:
            self._diff_and_fire_breakers(
                brk[0], brk[1], np.asarray(brk[2][:-1]).tolist())

    def _drain_evictions_locked(self) -> None:
        ev_keys, overrides = self.param_key_registry.drain_updates()
        if ev_keys:
            rows = jnp.asarray(_pad_to(np.asarray(ev_keys, np.int32),
                                       self._pad(len(ev_keys)),
                                       self.spec.param_keys, np.int32))
            self._state = self._state._replace(
                param_dyn=_jit_invalidate_param_keys(
                    self._state.param_dyn, rows))
        if overrides:
            rows = jnp.asarray(_pad_to(
                np.asarray([r for r, _ in overrides], np.int32),
                self._pad(len(overrides)), self.spec.param_keys, np.int32))
            vals = jnp.asarray(_pad_to(
                np.asarray([v for _, v in overrides], np.float32),
                self._pad(len(overrides)), -1.0, np.float32))
            self._state = self._state._replace(
                param_dyn=_jit_apply_overrides(
                    self._state.param_dyn, rows, vals))
        evicted = self.resources.drain_evicted()
        if evicted:
            if self.obs.enabled:
                # rows recycled by registry pressure: their stats AND any
                # live occupy bookings are invalidated below
                self.obs.counters.add(obs_keys.OCCUPY_EVICTED,
                                      len(evicted))
            with self.obs.phase("tier.demote", n=len(evicted)):
                # tiering demote: snapshot the recycled rows' state into
                # the cold tier BEFORE the invalidate destroys it
                # (dispatch-only; stream order keeps the gather reading
                # pre-invalidate values). Must run before the alt-edge pop
                # below — the snapshot needs the slots' host identities.
                self.tiering.pre_invalidate_locked(evicted,
                                                   self.clock.now_ms())
                alt: List[int] = []
                for row in evicted:
                    alt.extend(self._alt_rows_by_row.pop(row, ()))
                rows_arr = _pad_to(np.asarray(evicted, np.int32),
                                   self._pad(len(evicted)), self.spec.rows,
                                   np.int32)
                alt_arr = _pad_to(np.asarray(alt, np.int32),
                                  self._pad(len(alt)), self.spec.alt_rows,
                                  np.int32)
                self._state = self._jit_invalidate(
                    self._state, jnp.asarray(rows_arr), jnp.asarray(alt_arr))
        # tiering promote (the documented slow path): restore re-interned
        # cold keys into their freshly allocated rows — after the
        # invalidate, before the decide that triggered the intern, so
        # that decide reads the row exactly as if it had never left.
        # Unconditional: the promoted row may come from the free list
        # with no eviction in this drain.
        self.tiering.post_invalidate_locked(self.clock.now_ms())

    # ------------------------------------------------------------------
    # Introspection (command-surface backing)
    # ------------------------------------------------------------------

    def metrics_snapshot(self, time_ms: int):
        """Per-resource :class:`MetricNode` list for the completed second
        containing ``time_ms`` (the ``MetricTimerListener`` pull: reference
        aggregates every ClusterNode + ENTRY_NODE per whole second —
        ``node/metric/MetricTimerListener.java:34-40``). Requires the minute
        ring (per-second buckets); returns [] when it is disabled."""
        from sentinel_tpu.metrics.node import MetricNode, TOTAL_IN_RESOURCE_NAME

        if self.spec.minute is None:
            return []
        self._flush_fast()      # buffered fast-path stats land first
        idx = jnp.int32(self.spec.minute.index_of(time_ms))
        with self._lock:
            counters, rt = _jit_bucket_snapshot(self.spec.minute)(
                self._state.minute, idx)
            counters = np.asarray(counters)
            rt = np.asarray(rt)
            threads = np.asarray(self._state.threads)
            items = self.resources.items()
            rtypes = dict(self.resource_types)
        sec_ms = (time_ms // 1000) * 1000
        nodes = []
        for name, row in items:
            c = counters[row]
            if not (c[ev.PASS] or c[ev.BLOCK] or c[ev.SUCCESS]
                    or c[ev.EXCEPTION] or c[ev.OCCUPIED_PASS]):
                continue
            succ = int(c[ev.SUCCESS])
            nodes.append(MetricNode(
                timestamp=sec_ms,
                resource=(TOTAL_IN_RESOURCE_NAME if row == ENTRY_NODE_ROW
                          else name),
                pass_qps=int(c[ev.PASS]), block_qps=int(c[ev.BLOCK]),
                success_qps=succ, exception_qps=int(c[ev.EXCEPTION]),
                rt=int(rt[row] / succ) if succ else 0,
                occupied_pass_qps=int(c[ev.OCCUPIED_PASS]),
                concurrency=int(threads[row]),
                classification=rtypes.get(name, 0)))
        nodes.sort(key=lambda n: n.resource)
        return nodes

    def node_totals(self, resource: str) -> dict:
        """Current rolling-second totals for a resource (ClusterNode view),
        whichever tier holds it: a demoted name reads from its cold entry
        what its row would read had it stayed."""
        row = self.resources.lookup(resource)
        if row is None:
            entry = self.tiering.cold_entry(resource)
            if entry is None:
                return {}
            now_idx = self.spec.second.index_of(self.clock.now_ms())
            tot, rt = entry.rolling_totals(self.spec.second.buckets, now_idx)
            t = self._totals_dict(tot, rt, entry.threads)
        else:
            t = self.node_totals_by_row(row)
        t.pop("avg_rt", None)
        return t

    def rt_hist_by_name(self, resources: Sequence[str]) -> np.ndarray:
        """The cumulative RT histogram (``int32[n, HB]``, the buckets of
        obs/resource_hist.py) of each name, whichever tier holds it: a
        resident name's row of ``state.rt_hist``, a demoted name's row of
        its cold block, zeros for a name neither tier knows (or with the table
        disabled). Pending evictions and promotions are applied first, so
        the rows the registry names hold their owners' state; the engine
        lock is held for that and for the dispatch of a copy of the table
        (the telemetry-tick discipline), not for the read or the look-ups.
        One full-table device read: a dashboard's or a check's call, not
        the serving path's. Exact while no other thread interns names
        during the call."""
        hb = self.spec.hist_buckets
        out = np.zeros((len(resources), hb), np.int32)
        if not hb or not len(resources):
            return out
        with self._lock:
            self._drain_evictions_locked()
            table = _jit_copy_column(self._state.rt_hist)
        rows = np.fromiter(
            (-1 if r is None else r
             for r in map(self.resources.lookup, resources)),
            np.int64, count=len(resources))
        hot = rows >= 0
        out[hot] = np.asarray(table)[rows[hot]]
        cold = np.nonzero(~hot)[0]
        if cold.size:
            out[cold] = self.tiering.cold_rt_hist(
                [resources[i] for i in cold.tolist()], hb)
        return out

    def get_flow_rules(self) -> List[flow_mod.FlowRule]:
        return list(self._flow.rules)

    def get_degrade_rules(self) -> List[deg_mod.DegradeRule]:
        return list(self._deg.rules)

    def get_authority_rules(self) -> List[auth_mod.AuthorityRule]:
        return list(self._auth.rules)

    def get_system_rules(self) -> List[sys_mod.SystemRule]:
        return list(self._sys_rules)

    def get_param_flow_rules(self) -> List[pf_mod.ParamFlowRule]:
        return list(self._user_param_rules)

    def system_status(self) -> dict:
        """Live ``systemStatus`` command payload (SystemStatusListener view)."""
        load, cpu = self._cpu.sample()
        entry = self.node_totals_by_row(ENTRY_NODE_ROW)
        return {
            "rqps": entry.get("pass", 0), "qps": entry.get("pass", 0),
            "thread": entry.get("threads", 0), "rt": entry.get("avg_rt", 0),
            "load": load, "cpuUsage": cpu,
        }

    def _totals_snapshot(self):
        """One full-table device read → (counters[R,E], rt[R], threads[R])."""
        self._flush_fast()      # buffered fast-path stats land first
        now = self.clock.now_ms()
        idx_s = jnp.int32(self.spec.second.index_of(now))
        with self._lock:
            tot = np.asarray(rolling_totals(self.spec.second,
                                            self._state.second, idx_s))
            rt = (np.asarray(rt_totals(self.spec.second, self._state.second,
                                       idx_s))
                  if self.spec.second.track_rt
                  else np.zeros(self.spec.rows, np.float32))
            threads = np.asarray(self._state.threads)
        return tot, rt, threads

    @staticmethod
    def _totals_dict(tot_row, rt_row: float, threads_row: int) -> dict:
        succ = int(tot_row[ev.SUCCESS])
        return {
            "pass": int(tot_row[ev.PASS]), "block": int(tot_row[ev.BLOCK]),
            "success": succ, "exception": int(tot_row[ev.EXCEPTION]),
            "threads": int(threads_row),
            "avg_rt": (float(rt_row) / succ) if succ else 0.0,
        }

    def node_totals_by_row(self, row: int) -> dict:
        tot, rt, threads = self._totals_snapshot()
        return self._totals_dict(tot[row], rt[row], threads[row])

    def all_node_totals(self) -> List[Tuple[str, int, dict]]:
        """(name, row, totals) for every registered resource — ONE device
        snapshot regardless of resource count (clusterNode/tree commands)."""
        items = self.resources.items()
        tot, rt, threads = self._totals_snapshot()
        return [(name, row,
                 self._totals_dict(tot[row], rt[row], threads[row]))
                for name, row in items]

    def origin_totals(self, resource: str) -> List[dict]:
        """Per-origin rolling-second stats of one resource (the ``origin``
        command — reference ClusterNode.getOriginCountMap view). Origins are
        hashed rows in the alt table, so attribution is per (resource×origin)
        hash cell; collisions merge rows (bounded inaccuracy by design)."""
        row = self.resources.lookup(resource)
        if row is None:
            return []
        self._flush_fast()      # buffered fast-path stats land first
        now = self.clock.now_ms()
        idx_s = jnp.int32(self.spec.second.index_of(now))
        with self._lock:
            touched = set(self._alt_rows_by_row.get(row, ()))
            origins = self.origins.items()
            tot = np.asarray(rolling_totals(self.spec.second,
                                            self._state.alt_second, idx_s))
            threads = np.asarray(self._state.alt_threads)
        out = []
        for name, oid in origins:
            if not name:
                continue
            r = _alt_hash(row, 0, oid, self.spec.alt_rows)
            if r not in touched:
                continue
            t = tot[r]
            out.append({
                "origin": name, "passQps": int(t[ev.PASS]),
                "blockQps": int(t[ev.BLOCK]),
                "successQps": int(t[ev.SUCCESS]),
                "exceptionQps": int(t[ev.EXCEPTION]),
                "threadNum": int(threads[r]),
            })
        return out

    def breaker_states(self) -> List[int]:
        with self._lock:
            return np.asarray(self._state.breakers.state[:-1]).tolist()

    def add_breaker_observer(self, fn) -> None:
        """Register ``fn(resource, prev_state, new_state)`` for circuit-
        breaker transitions (reference ``EventObserverRegistry``).

        Event-driven: the observer fires on the thread that lands the
        entry/exit batch that caused the arc (the state vector rides the
        batch's existing device→host readback, so registering observers
        adds no extra round-trips to the decide path; exit batches — which
        otherwise need no readback — pay one small read while observers
        are registered). The metric timer's
        :meth:`check_breaker_transitions` poll remains as a fallback for
        verdicts nobody materializes, sharing the same baseline so no
        transition fires twice."""
        with self._lock:
            self._breaker_observers = self._breaker_observers + [fn]

    def _diff_and_fire_breakers(self, seq: int, rules_snap: tuple,
                                states: List[int]) -> int:
        """Diff ``states`` (host ints, rule-slot order) against the shared
        baseline and notify observers → transitions fired. ``seq`` orders
        snapshots (dispatch order under the engine lock): a stale snapshot
        landing after a newer one is skipped — its transitions were already
        visible to the newer diff."""
        observers = self._breaker_observers
        to_fire = []
        with self._breaker_event_lock:
            prev = self._breaker_live
            if prev is not None and seq <= prev[0]:
                return 0
            self._breaker_live = (seq, rules_snap, states)
            # a rules reload re-pairs slots with new rules: new baseline
            if prev is None or prev[1] is not rules_snap:
                return 0
            if observers:
                for j, r in enumerate(rules_snap):
                    if j < len(prev[2]) and j < len(states) \
                            and prev[2][j] != states[j]:
                        to_fire.append((r.resource, prev[2][j], states[j],
                                        observers))
            fired = len(to_fire)
            # enqueue under the event lock: the seq check above admits
            # snapshots in order, so queue order == transition order
            self._breaker_fire_q.extend(to_fire)
        # every enqueuer drains its own items, so the empty case can skip
        # the drain's lock round-trips entirely (hot-path materialization)
        if to_fire:
            self._drain_breaker_fires()
        return fired

    def _drain_breaker_fires(self) -> None:
        """Deliver queued breaker transitions in seq order. Exactly one
        thread drains at a time (the rest — including an observer that
        re-enters the engine and lands new transitions — enqueue and
        return; the active drainer picks their items up). Observers thus
        run with NO engine lock held: re-entry (entry(),
        decide_raw().result(), check_breaker_transitions()) cannot
        self-deadlock, and a slow observer cannot stall concurrent
        verdict materializations — only delay later deliveries, which
        must wait anyway to preserve per-observer ordering."""
        with self._breaker_event_lock:
            if self._breaker_firing:
                return
            self._breaker_firing = True
        try:
            while True:
                with self._breaker_event_lock:
                    if not self._breaker_fire_q:
                        # reset ATOMICALLY with the empty check: a
                        # non-atomic reset would let a concurrent
                        # enqueuer see firing=True after our check and
                        # strand its items until the next transition
                        self._breaker_firing = False
                        return
                    res, old, new, observers = \
                        self._breaker_fire_q.popleft()
                for fn in observers:
                    try:
                        fn(res, old, new)
                    except Exception as exc:
                        from sentinel_tpu.core.logs import record_log
                        record_log().warning(
                            "breaker observer failed: %r", exc)
        except BaseException:
            # Ctrl-C/SystemExit in an observer: a stuck True flag would
            # silently end all future delivery (queued items, if any,
            # deliver on the next transition)
            with self._breaker_event_lock:
                self._breaker_firing = False
            raise

    def check_breaker_transitions(self) -> int:
        """Poll fallback: snapshot current breaker states and run them
        through the shared diff → number of transitions seen. With the
        event path active this only catches arcs whose batch verdicts
        were never materialized; rule reloads reset the baseline."""
        with self._lock:
            observers = self._breaker_observers
            if not observers:
                return 0
            self._breaker_seq += 1
            seq = self._breaker_seq
            rules_snap = self._deg.rules
            # materialize under the lock: with donation on, the state
            # could be consumed by a concurrent dispatch the moment the
            # lock is released
            states = np.asarray(self._state.breakers.state[:-1]).tolist()
        return self._diff_and_fire_breakers(seq, rules_snap, states)

    def breaker_resources(self) -> List[Tuple[str, int]]:
        """(resource, state) per loaded degrade rule, rule-slot order
        (EventObserverRegistry/observability view). States and rules are
        snapshotted under one lock so a concurrent rule reload can't pair
        new rules with another generation's states."""
        with self._lock:
            states = np.asarray(self._state.breakers.state[:-1]).tolist()
            rules = list(self._deg.rules)
        return [(r.resource, states[j]) for j, r in enumerate(rules)
                if j < len(states)]

    def force_breaker(self, resource: str, state: int) -> bool:
        """Force every degrade-rule slot on ``resource`` into ``state``
        (``STATE_CLOSED``/``STATE_OPEN``/``STATE_HALF_OPEN``) — the
        overload controller's Degrade actuator (round 17). The device
        kernels then evolve the slot normally: a forced-OPEN slot
        half-opens after the rule's own ``time_window`` (its
        ``next_retry_ms`` is stamped exactly as a device trip would),
        a forced-CLOSED/HALF_OPEN slot starts a fresh stat window.
        Observers see the arc through the shared transition diff. → True
        when the resource has at least one loaded degrade rule."""
        state = int(state)
        if state not in (deg_mod.STATE_CLOSED, deg_mod.STATE_OPEN,
                         deg_mod.STATE_HALF_OPEN):
            raise ValueError(f"invalid breaker state {state}")
        # buffered fast-path passes were admitted under the old breaker
        # state — land them first (same discipline as a rules reload)
        self._flush_fast()
        never = -(2 ** 30)
        with self._lock:
            slots = [j for j, r in enumerate(self._deg.rules)
                     if r.resource == resource]
            if not slots:
                return False
            idx = jnp.asarray(slots, jnp.int32)
            st = self._state.breakers
            if state == deg_mod.STATE_OPEN:
                now_rel = self._rel_ms(self.clock.now_ms())
                retry = st.next_retry_ms.at[idx].set(
                    (self._deg.table.retry_timeout_ms[idx]
                     + now_rel).astype(jnp.int32))
            else:
                retry = st.next_retry_ms.at[idx].set(never)
            self._state = self._state._replace(breakers=st._replace(
                state=st.state.at[idx].set(state),
                next_retry_ms=retry,
                win_stamp=st.win_stamp.at[idx].set(never),
                bad=st.bad.at[idx].set(0),
                total=st.total.at[idx].set(0)))
            self._pin_state_locked()
        self.check_breaker_transitions()
        return True
