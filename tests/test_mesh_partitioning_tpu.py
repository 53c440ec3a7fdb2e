"""What the TPU's compiler makes of the row-sharded decide and exit steps,
compiled here for a described ``v5e:2x2`` (no chip attached, nothing
runs): no collective may move a whole window tensor.

The fault this pins (PR 29): the ENTRY row's one-row update became a
dynamic slice of the sharded row axis, and the SPMD partitioner answered
by all-gathering the whole second window and the whole minute ring onto
every chip in every step — 8 GB at 4,194,304 rows. The CPU's compiler
does not make that rewrite, so only a compile for the chip shows it.

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

ROWS, BATCH = 16_384, 1_024
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
    """``compiled(step, rows_sharded)``: one of the cell's two step
    programs — the scalar decide and the exit without alt rows, as
    ``mesh-4m.batch-scalar`` dispatches them — compiled for a state of
    ``ROWS`` rows split over the four described chips, batch columns on
    their batch-axis shardings."""
    cfg = dict(max_resources=1024, max_flow_rules=64, max_degrade_rules=16)
    small = stpu.Sentinel(stpu.load_config(**cfg))
    mesh = Mesh(np.array(topo.devices), (MESH_AXIS,))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(MESH_AXIS))
    i32, b = jnp.int32, jnp.bool_

    def col(dtype):
        return jax.ShapeDtypeStruct((BATCH,), dtype, sharding=row)

    def build(step: str, rows_sharded: bool):
        spec = dataclasses.replace(small.spec, rows=ROWS, alt_rows=2 * ROWS,
                                   rows_sharded=rows_sharded)
        shapes = pipeline.init_state_shapes(spec, 64, 16)
        st_sh = state_shardings(spec, mesh, shapes)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, st_sh)
        rules = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(ROWS if d == 1024 else d for d in x.shape), x.dtype,
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


def test_the_one_row_form_gathers_the_whole_ring(compiled):
    """The control: the exit step with the ENTRY row updated as a one-row
    dynamic slice (``rows_sharded=False``, the unmeshed form) gathers the
    minute ring whole — ``ROWS × 60 × 8`` elements on every chip."""
    assert _largest_collective(compiled("exit", False)) >= ROWS * 60 * 8
