"""Row-sharding of the LOCAL engine over a device mesh (product mode).

The north-star scale axis (SURVEY §7 phase 1): the local ``[R, B, E]``
window tensors — the dense rebuild of the reference's per-resource
StatisticNode forest — shard on the RESOURCE axis across the mesh, the
distributed analog of the reference's checker running against shared
state (``sentinel-cluster-server-default/.../flow/ClusterFlowChecker.java:38-118``
generalized to the whole slot chain). Rules, batches, and verdicts are
replicated; XLA's SPMD partitioner keeps the scatter-adds local to the
owning shard and inserts the gathers the decision reads need.

Usage::

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("rows",))
    sph = Sentinel(config, mesh=mesh)      # everything else is unchanged

Design notes (why GSPMD annotations, not ``shard_map``): one local entry
event touches up to four DIFFERENT row spaces — its main row, the global
ENTRY row, and two hashed alt rows (origin/chain) — each owned by a
potentially different shard, plus replicated per-rule state (breakers,
pacing clocks). ``shard_map`` with host-side owner routing (the
:mod:`~sentinel_tpu.parallel.cluster` pattern) fits the token engine,
where a request targets exactly one flow row; for the full slot chain the
sharding is expressed as annotations on the state pytree and XLA
partitions the fused step. Parity with the single-device engine is
bit-exact (asserted in tests and the driver dry run).

Field map (state pytree → PartitionSpec), the single source of truth:

==================  ==========================  =====================
state field          shape                       sharding
==================  ==========================  =====================
second/minute        WindowState [R, B, ...]     P("rows") on axis 0
alt_second           WindowState [RA, B, ...]    P("rows") on axis 0
threads              int32[R]                    P("rows")
alt_threads          int32[RA]                   P("rows")
flow_dyn.occupied_*  [R, B+1]                    P("rows") on axis 0
flow_dyn (pacing)    [NF+1]                      replicated
breakers             [ND+1]                      replicated
param_dyn            [PK+1]                      replicated
custom               user DeviceSlot pytrees     replicated
rt_hist              int32[R, HB] (or absent)    P("rows") on axis 0
==================  ==========================  =====================
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sentinel_tpu.engine.pipeline import EngineSpec, SentinelState, Verdicts
from sentinel_tpu.parallel import shard_math

MESH_AXIS = "rows"


def local_mesh(n_devices: Optional[int] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """The one way to build a local row-sharding mesh — runtime callers,
    benches, the driver dry run, and tests all construct through here so
    the axis name and device ordering can never drift apart.

    ``n_devices`` takes the first n visible devices (all of them when
    None); pass ``devices`` to pin an explicit ordering instead."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"local_mesh(n_devices={n_devices}) but only "
                    f"{len(devices)} devices visible — on CPU, set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{n_devices}")
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (MESH_AXIS,))


def validate_mesh(spec: EngineSpec, mesh: Mesh) -> None:
    """Fail fast (with a fix) when the geometry can't shard over the mesh."""
    if MESH_AXIS not in mesh.axis_names:
        raise ValueError(
            f"local-engine mesh needs a {MESH_AXIS!r} axis; got "
            f"{mesh.axis_names} — build it as Mesh(devices, ({MESH_AXIS!r},))")
    n = mesh.shape[MESH_AXIS]
    shard_math.validate_divisible(
        "max_resources", spec.rows, n,
        f"round max_resources up to a multiple of {n}")
    shard_math.validate_divisible(
        "alt_rows", spec.alt_rows, n,
        f"round max_resources up to a multiple of {n} (alt_rows follows it)")


def shard_of_rows(n_rows: int, mesh: Optional[Mesh],
                  rows: np.ndarray) -> np.ndarray:
    """Owner shard per row id under the contiguous leading-axis split
    (``validate_mesh`` guarantees even divisibility). Unmeshed engines
    are a single shard. The tiering ticker uses this to spread
    proactive demotions across shards so no device's hot set thins
    faster than its peers'."""
    rows = np.asarray(rows)
    if mesh is None:
        return np.zeros(rows.shape, np.int32)
    per = n_rows // mesh.shape[MESH_AXIS]
    return (rows // per).astype(np.int32)


def state_shardings(spec: EngineSpec, mesh: Mesh,
                    state: SentinelState) -> SentinelState:
    """A ``SentinelState``-shaped pytree of :class:`NamedSharding` per the
    field map above. ``state`` supplies only the STRUCTURE of the
    variable-shape parts (custom slot states, rt-tracking window leaves):
    a live state or its shapes (``pipeline.init_state_shapes``) give the
    same pytree, so an engine has its shardings before its state exists."""
    row = NamedSharding(mesh, P(MESH_AXIS))
    rep = NamedSharding(mesh, P())

    def rows_first(sub):          # every leaf leads with the row axis
        return jax.tree.map(lambda _: row, sub)

    def replicated(sub):
        return jax.tree.map(lambda _: rep, sub)

    return SentinelState(
        second=rows_first(state.second),
        # minute is [R]-rowed when enabled, a 1-row stub when disabled
        minute=(rows_first(state.minute) if spec.minute
                else replicated(state.minute)),
        alt_second=rows_first(state.alt_second),
        threads=row,
        alt_threads=row,
        flow_dyn=state.flow_dyn._replace(
            latest_passed_ms=rep, stored_tokens=rep, last_filled_sec=rep,
            occupied_count=row, occupied_window=row),
        breakers=replicated(state.breakers),
        param_dyn=replicated(state.param_dyn),
        custom=replicated(state.custom),
        # round 20: [R, HB] RT histogram rows live with their resource
        rt_hist=(row if state.rt_hist is not None else None),
    )


def verdict_shardings(mesh: Mesh) -> Verdicts:
    rep = NamedSharding(mesh, P())
    return Verdicts(allow=rep, reason=rep, wait_ms=rep, sf_overflow=rep)


def pin_state(state: SentinelState,
              shardings: SentinelState) -> SentinelState:
    """Place (or re-place) every state leaf on its canonical sharding —
    used at init and whenever host code rebuilds a leaf (window geometry
    change, snapshot restore), so a freshly created unsharded array can't
    silently drop the engine back to single-device execution."""
    return jax.tree.map(jax.device_put, state, shardings)


def shardings_for(spec: EngineSpec, mesh: Optional[Mesh],
                  state: SentinelState):
    """→ (state_shardings, verdict_shardings) or (None, None) without a
    mesh; the one call sites use."""
    if mesh is None:
        return None, None
    validate_mesh(spec, mesh)
    return state_shardings(spec, mesh, state), verdict_shardings(mesh)


@functools.lru_cache(maxsize=8)
def batch_shardings(mesh: Mesh):
    """→ (batch_axis, replicated) :class:`NamedSharding` pair for event
    columns (cached per mesh — one pair serves every dispatch)."""
    return NamedSharding(mesh, P(MESH_AXIS)), NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, x) -> NamedSharding:
    """Batch-axis sharding for ONE event column: partition the leading
    (event) dimension over the mesh when it divides evenly, else
    replicate — the retrieval brief's naive-sharding utility pattern
    (SNIPPETS [1]/[2] ``get_naive_sharding``). Trailing dimensions (the
    param-pair lanes) stay unpartitioned."""
    sharded, rep = batch_shardings(mesh)
    n = mesh.shape[MESH_AXIS]
    return sharded if (x.ndim >= 1 and x.shape[0] % n == 0) else rep


def place_batch(batch, mesh: Mesh):
    """Place every present column of an ``EntryBatch`` / ``ExitBatch``
    (any NamedTuple of host arrays with optional ``None`` leaves) on its
    batch-axis sharding before dispatch. Explicit placement keeps the
    host→device transfer of the event columns partitioned like the step
    that consumes them — without it the compiled step would re-lay-out
    replicated inputs on every dispatch. Values are unchanged (placement
    is layout, not math); the parity tests pin that."""
    return jax.tree.map(
        lambda x: jax.device_put(x, batch_sharding(mesh, np.asarray(x))),
        batch)


def topk_layout(spec: EngineSpec, mesh: Optional[Mesh]):
    """→ ``(n_shards, rows_per_shard)`` for the telemetry top-K merge
    (obs/telemetry.py). THE row-ownership contract of the sharded merge:
    shard ``i`` owns the contiguous global rows
    ``[i*rows_per_shard, (i+1)*rows_per_shard)`` — exactly how GSPMD
    partitions a ``P("rows")`` axis-0 sharding — so a local top-k index
    maps to its global row as ``local + axis_index * rows_per_shard``.
    Kept here (not in the telemetry module) so the layout can never
    drift from the state sharding it must mirror."""
    if mesh is None:
        return 1, spec.rows
    n = int(mesh.shape[MESH_AXIS])
    return n, spec.rows // n


def mesh_topology(spec: EngineSpec, mesh: Optional[Mesh],
                  state_sh: Optional[SentinelState] = None) -> dict:
    """Artifact-ready description of the serving layout: device count,
    axis name, per-device row span, and — when the sharding pytree is
    supplied — how many state leaves actually shard vs replicate, so a
    BENCH artifact records the layout that produced its numbers."""
    if mesh is None:
        return {"n_devices": 1, "axis": None,
                "rows_per_device": spec.rows, "sharded": False}
    n = mesh.shape[MESH_AXIS]
    out = {"n_devices": int(n), "axis": MESH_AXIS,
           "rows_per_device": spec.rows // int(n), "sharded": True,
           "multihost": len({d.process_index
                             for d in np.ravel(np.asarray(mesh.devices))}) > 1}
    if state_sh is not None:
        leaves = jax.tree.leaves(state_sh)
        n_rows = sum(1 for s in leaves if s.spec == P(MESH_AXIS))
        out["state_leaves_sharded"] = n_rows
        out["state_leaves_replicated"] = len(leaves) - n_rows
    return out
