"""What the TPU's compiler makes of the row-sharded decide and exit steps,
compiled here for a described ``v5e:2x2`` (no chip attached, nothing
runs): no collective may move a whole window tensor.

The fault this pins (PR 29): the ENTRY row's one-row update became a
dynamic slice of the sharded row axis, and the SPMD partitioner answered
by all-gathering the whole second window and the whole minute ring onto
every chip in every step — 8 GB at 4,194,304 rows. The CPU's compiler
does not make that rewrite, so only a compile for the chip shows it.

Since PR 30 the steps record into the current bucket's plane and not into
the ring (``stats.window.open_bucket``); compiled at the cell's own size
the plane form has to stay shard-local too, with no ring-sized temporary
on any chip (the ring form: 2,014 MB a chip for decide's minute part).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library (on-chip-measurement guide, §2).
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import sentinel_tpu as stpu
import sentinel_tpu.runtime as runtime
from sentinel_tpu.engine import pipeline
from sentinel_tpu.parallel.local_shard import (
    MESH_AXIS, state_shardings, verdict_shardings,
)
from sentinel_tpu.tiering import sketch as sk

ROWS, BATCH = 16_384, 1_024
CELL_ROWS, CELL_BATCH = 4 << 20, 65_536     # `mesh-4m.batch-scalar`
COLLECTIVE = re.compile(
    r"= \(?(\w+)\[([\d,]*)\][^=]*? (all-gather|all-reduce|all-to-all|"
    r"collective-permute|reduce-scatter)(?:-start)?\(")
DECIDE_FLAGS = dict(skip_auth=True, skip_sys=True, skip_threads=True,
                    sortfree=True, scalar_flow=True, scalar_has_rl=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    """``compiled(step, rows_sharded, rows, batch)``: one of the cell's two
    step programs — the scalar decide and the exit without alt rows, as
    ``mesh-4m.batch-scalar`` dispatches them — compiled for a state of
    ``rows`` rows split over the four described chips, ``batch`` columns
    on their batch-axis shardings."""
    cfg = dict(max_resources=1024, max_flow_rules=64, max_degrade_rules=16)
    small = stpu.Sentinel(stpu.load_config(**cfg))
    mesh = Mesh(np.array(topo.devices), (MESH_AXIS,))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(MESH_AXIS))
    i32, b = jnp.int32, jnp.bool_

    def build(step: str, rows_sharded: bool, rows: int = ROWS,
              batch: int = BATCH):
        def col(dtype):
            return jax.ShapeDtypeStruct((batch,), dtype, sharding=row)

        spec = dataclasses.replace(small.spec, rows=rows, alt_rows=2 * rows,
                                   rows_sharded=rows_sharded)
        shapes = pipeline.init_state_shapes(spec, 64, 16)
        st_sh = state_shardings(spec, mesh, shapes)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, st_sh)
        rules = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(rows if d == 1024 else d for d in x.shape), x.dtype,
                sharding=rep), small._ruleset)
        times = jax.ShapeDtypeStruct((4,), i32, sharding=rep)
        steps = runtime._build_steps(
            spec, (), (st_sh, verdict_shardings(mesh)), True)
        if step == "decide":
            entries = pipeline.EntryBatch(
                rows=col(i32), origin_ids=col(i32), origin_rows=col(i32),
                context_ids=col(i32), chain_rows=col(i32), acquire=col(i32),
                is_in=col(b), prioritized=col(b), valid=col(b))
            sys_scalars = jax.ShapeDtypeStruct((2,), jnp.float32,
                                               sharding=rep)
            return steps[2].lower(rules, state, entries, times, sys_scalars,
                                  **DECIDE_FLAGS).compile()
        exits = pipeline.ExitBatch(
            rows=col(i32), origin_rows=col(i32), chain_rows=col(i32),
            acquire=col(i32), rt_ms=col(i32), error=col(b), is_in=col(b),
            valid=col(b))
        return steps[5].lower(rules, state, exits, times,
                              skip_threads=True).compile()
    yield build
    small.close()


def _largest_collective(program) -> int:
    """Elements of the largest array any collective of the program moves."""
    most = 0
    for m in COLLECTIVE.finditer(program.as_text()):
        dims = [int(d) for d in m.group(2).split(",") if d]
        most = max(most, int(np.prod(dims)) if dims else 1)
    return most


@pytest.mark.parametrize("step", ["decide", "exit"])
def test_no_collective_moves_a_window_tensor(compiled, step):
    """Every collective of the sharded step is batch-sized: the scatters
    stay on the owning shard and nothing gathers a table (the smallest,
    the second window's counters, has ``ROWS × 2 × 8`` elements)."""
    assert _largest_collective(compiled(step, True)) \
        <= 16 * BATCH < ROWS * 2 * 8


@pytest.mark.parametrize("step", ["decide", "exit"])
def test_at_the_cells_size_no_chip_holds_a_ring_sized_temp(compiled, step):
    """4,194,304 rows, 65,536 lanes: under 256 MB of temp a chip (a shard
    of the minute ring is 2,013 MB), collectives that stay batch-sized
    (the widest is the entry batch's ``s32[65536, 8]`` all-gather) and no
    ``while`` that carries a shard's ring."""
    program = compiled(step, True, CELL_ROWS, CELL_BATCH)
    assert program.memory_analysis().temp_size_in_bytes < 256 << 20
    assert _largest_collective(program) <= 16 * CELL_BATCH
    carried = [int(np.prod([int(d) for d in dims.split(",")]))
               for line in program.as_text().splitlines() if " while(" in line
               for dims in re.findall(r"\[([\d,]+)\]",
                                      line.split(" while(")[0])]
    assert max(carried, default=0) < CELL_ROWS // 4 * 60 * 8   # a shard's ring


def _largest_array(program) -> int:
    """Elements of the largest array the compiled program names: an
    operand, a result or a temporary."""
    return max(int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
               for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\]",
                                      program.as_text()))


def test_the_tiering_tick_holds_nothing_of_the_tables_size(topo):
    """The default tick on the cell's engine — the ``int32[4, 4096]``
    sketch replicated, as the sketch-fused decide hands it back: decay and
    one maximum, the same on every chip, so no collective and no array
    beyond the sketch. Until PR 36 it estimated all 4,194,304 rows on
    every chip (``SR x R`` gathered lanes, 76 % of the cell's device
    time) for a reader no default deployment has; the control is that
    estimate, which proactive demotion still dispatches, and shows here
    as a result of ``CELL_ROWS`` elements."""
    mesh = Mesh(np.array(topo.devices), (MESH_AXIS,))
    sketch = jax.ShapeDtypeStruct(
        (sk.DEFAULT_ROWS, 1 << sk.DEFAULT_BITS), jnp.int32,
        sharding=NamedSharding(mesh, P()))
    tick = sk.jit_tick_read.lower(sketch).compile()
    assert _largest_collective(tick) == 0
    assert _largest_array(tick) == sk.DEFAULT_ROWS << sk.DEFAULT_BITS
    mem = tick.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < CELL_ROWS
    estimate = sk.jit_estimate_all.lower(sketch, n_rows=CELL_ROWS).compile()
    assert _largest_array(estimate) >= CELL_ROWS


def test_a_one_row_update_of_the_sharded_ring_gathers_it_whole(topo):
    """The control, and why ``EngineSpec.rows_sharded`` exists: the ENTRY
    row's update as a ONE-index update of the row-sharded minute ring (the
    unmeshed form before PR 29) becomes a dynamic slice of the sharded
    axis, and the partitioner gathers the ring whole — ``ROWS × 60 × 8``
    elements on every chip. Sliced out to the plane and written back with
    nothing between, the compiler folds the three into that same update."""
    mesh = Mesh(np.array(topo.devices), (MESH_AXIS,))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(MESH_AXIS))
    ring = jax.ShapeDtypeStruct((ROWS, 60, 8), jnp.int32, sharding=row)
    k = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    vec = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rep)

    def on_the_ring(ring, k, vec):
        return ring.at[0, k, :].add(vec)

    def on_the_plane(ring, k, vec):
        plane = jax.lax.dynamic_index_in_dim(ring, k, 1, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            ring, plane.at[0, :].add(vec), k, 1)

    for form in (on_the_ring, on_the_plane):
        program = jax.jit(form, out_shardings=row).lower(
            ring, k, vec).compile()
        assert _largest_collective(program) >= ROWS * 60 * 8, form
