"""What the TPU's compiler makes of the record stage at the product's size,
compiled here for a described ``v5e:2x2`` (no chip attached, nothing runs):
the decide, exit and block-record programs over ``[1048576, 60, 8]`` hold
no ring-sized temporary and no ``while`` that carries the ring.

The fault this pins (PR 30): from 1,024 indices up the TPU compiler lowers
a scatter by flattening its WHOLE operand to a row-major 1-D array and
rebuilding the tiled array afterwards in a ``while``. With the 2 GB minute
ring as the operand that was 2,017 MB of temp in the decide and
block-record programs and 5,375 MB in the exit program, four to nine
whole-ring passes a step; at 512 indices it scattered in place. The steps
now record into the current bucket's 32 MB plane (``stats.window.
open_bucket``), so what is relaid out is the plane. The CPU's compiler
makes no such rewrite, so only a compile for the chip shows it.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library (on-chip-measurement guide, §2).
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import sentinel_tpu as stpu
import sentinel_tpu.runtime as runtime
from sentinel_tpu.engine import pipeline

ROWS = 1 << 20
RING = ROWS * 60 * 8                # elements of the minute ring's counters
TEMP_LIMIT = 256 << 20
# the flags `embed-1m.batch-scalar` and `mesh-4m.batch-scalar` dispatch with
DECIDE_FLAGS = dict(skip_auth=True, skip_sys=True, skip_threads=True,
                    sortfree=True, scalar_flow=True, scalar_has_rl=False)
ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


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
    """``compiled(step, lanes)``: the scalar decide, the exit without alt
    rows or the block-record program, donated state of ``ROWS`` rows with
    the minute ring, on ONE described chip."""
    small = stpu.Sentinel(stpu.load_config(
        max_resources=1024, max_flow_rules=64, max_degrade_rules=16))
    chip = SingleDeviceSharding(topo.devices[0])
    i32, b = jnp.int32, jnp.bool_
    spec = dataclasses.replace(small.spec, rows=ROWS, alt_rows=2 * ROWS)
    assert spec.minute is not None and spec.minute.buckets == 60

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    state = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                         pipeline.init_state_shapes(spec, 64, 16))
    rules = jax.tree.map(
        lambda x: on_chip(tuple(ROWS if d == 1024 else d for d in x.shape),
                          x.dtype), small._ruleset)
    times = on_chip((4,), i32)
    steps = runtime._build_steps(spec, (), None, True)

    def build(step: str, lanes: int):
        def col(dtype):
            return on_chip((lanes,), dtype)
        if step == "decide":
            entries = pipeline.EntryBatch(
                rows=col(i32), origin_ids=col(i32), origin_rows=col(i32),
                context_ids=col(i32), chain_rows=col(i32), acquire=col(i32),
                is_in=col(b), prioritized=col(b), valid=col(b))
            return steps[2].lower(rules, state, entries, times,
                                  on_chip((2,), jnp.float32),
                                  **DECIDE_FLAGS).compile()
        if step == "exit":
            exits = pipeline.ExitBatch(
                rows=col(i32), origin_rows=col(i32), chain_rows=col(i32),
                acquire=col(i32), rt_ms=col(i32), error=col(b),
                is_in=col(b), valid=col(b))
            return steps[5].lower(rules, state, exits, times,
                                  skip_threads=True).compile()
        return steps[7].lower(state, col(i32), col(i32), col(i32), col(i32),
                              col(b), col(b), times).compile()
    build.spec, build.state, build.steps, build.on_chip = \
        spec, state, steps, on_chip
    yield build
    small.close()


def largest_while_operand(program) -> int:
    """Elements of the largest array any ``while`` of the program carries."""
    most = 0
    for line in program.as_text().splitlines():
        if " while(" in line:
            for dims in ARRAY.findall(line.split(" while(")[0]):
                most = max(most, int(np.prod([int(d) for d in
                                              dims.split(",")])))
    return most


@pytest.mark.parametrize("lanes", [512, 1024, 65_536])
@pytest.mark.parametrize("step", ["decide", "exit", "blocks"])
def test_no_step_relays_out_the_ring(compiled, step, lanes):
    """On both sides of the lane count where the scatter's lowering
    switches (in place up to 512 indices, flatten-and-rebuild from 1,024)
    and at the batch cells' 65,536: temp under 256 MB (the ring is 2,013 MB)
    and every ``while`` carries planes, not the ring."""
    program = compiled(step, lanes)
    assert program.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    assert largest_while_operand(program) < RING


STATE_BYTES = 3_277_086_720     # what a 1,048,576-row engine keeps on the chip


@pytest.mark.parametrize("program", ["extract", "invalidate", "restore"])
def test_what_the_chip_makes_of_tier_migration(compiled, program):
    """The three migration programs at the size ``tier-16m.batch-scalar``
    pads a batch's evictions to (8,192 rows; PR 33). The invalidate and
    the restore rewrite the donated state in place — a restore that is
    not donated holds the 3.3 GB state twice (ROADMAP R3, closed). What
    the restore still pays is S1's relayout: a copy of the minute ring
    into the scatter's layout and one back, 4.3 GB of temp — the limit
    below is that reading's, for the ``perf_opt`` that takes it out to
    lower."""
    from sentinel_tpu.stats.window import WindowState
    from sentinel_tpu.tiering import manager
    spec, state, on_chip = compiled.spec, compiled.state, compiled.on_chip
    i32, f32 = jnp.int32, jnp.float32
    k, ka, B, ne, hb = 8192, 8, spec.second.buckets, 8, spec.hist_buckets
    mb = spec.minute.buckets

    def window(rows, buckets):
        return WindowState(on_chip((rows, buckets, ne), i32),
                           on_chip((rows, buckets), i32),
                           on_chip((rows, buckets), f32),
                           on_chip((rows, buckets), i32))
    rows, alt = on_chip((k,), i32), on_chip((ka,), i32)
    if program == "extract":
        made = manager._jit_extract(spec).lower(state, rows, alt).compile()
    elif program == "invalidate":
        made = compiled.steps[6].lower(state, rows, alt).compile()
    else:
        payload = pipeline.ResourceRowSlice(
            second=window(k, B), minute=window(k, mb),
            threads=on_chip((k,), i32), occ_cnt=on_chip((k, B + 1), f32),
            occ_win=on_chip((k, B + 1), i32), alt_second=window(ka, B),
            alt_threads=on_chip((ka,), i32), rt_hist=on_chip((k, hb), i32))
        made = compiled.steps[8].lower(state, rows, payload, alt).compile()
    memory = made.memory_analysis()
    assert "jit_tier_" + program in made.as_text()[:200]   # its trace name
    if program == "extract":
        # fresh buffers (it is read back while later steps donate the state)
        assert memory.alias_size_in_bytes == 0
        assert memory.temp_size_in_bytes < 1 << 30
    else:
        assert memory.alias_size_in_bytes >= STATE_BYTES
        limit = TEMP_LIMIT if program == "invalidate" else 5 << 30
        assert memory.temp_size_in_bytes < limit

