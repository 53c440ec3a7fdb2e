"""The front end's hold on the collector (frontend/heap.py): the first
live batcher of the process freezes the set-up heap after one full
collection, the last one to close thaws it, and every gen-2 collection
while a batcher lives is counted in its engine's counters.

All quick-tier, CPU.
"""

import asyncio
import gc

import pytest

import sentinel_tpu as stpu
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.frontend import heap
from sentinel_tpu.frontend.batcher import AdaptiveBatcher
from sentinel_tpu.obs import counters as ck

pytestmark = pytest.mark.quick


@pytest.fixture
def base():
    """No batcher lives; → the permanent generation's size as a
    collection leaves it. It is not 0: CPython 3.12's collector parks
    the interpreter's immortal objects there (375 tuples on this
    interpreter), again after every ``gc.unfreeze``."""
    assert len(heap._live) == 0
    gc.collect()
    yield gc.get_freeze_count()
    assert len(heap._live) == 0


@pytest.fixture
def sph(base):
    s = stpu.Sentinel(config=stpu.load_config(max_resources=64),
                      clock=ManualClock(start_ms=1_785_000_000_000))
    s.load_flow_rules([stpu.FlowRule(resource="api", count=1e9)])
    yield s
    s.close()


def _start(*batchers):
    """One served request through each batcher."""
    async def run():
        for b in batchers:
            assert (await b.submit("api")).allow
    asyncio.run(run())


@pytest.mark.parametrize("closing", ["first_built_first", "reverse"])
def test_the_last_close_thaws_what_the_first_batcher_froze(sph, base,
                                                           closing):
    a = AdaptiveBatcher(sph, batch_max=1)
    assert gc.get_freeze_count() > base and len(heap._live) == 1
    b = AdaptiveBatcher(sph, batch_max=1)
    _start(a, b)                                # both serve, frozen
    assert len(heap._live) == 2
    first, last = (a, b) if closing == "first_built_first" else (b, a)
    first.close()
    first.close()                               # idempotent: one release
    assert len(heap._live) == 1 and gc.get_freeze_count() > base
    last.close()
    last.close()
    assert len(heap._live) == 0
    gc.collect()
    assert gc.get_freeze_count() == base


def test_a_batcher_closed_without_live_hands_the_heap_back(sph, base):
    b = AdaptiveBatcher(sph, batch_max=1)
    assert gc.get_freeze_count() > base
    b.close()
    b.close()
    gc.collect()
    assert len(heap._live) == 0 and gc.get_freeze_count() == base


def test_nothing_freezes_while_the_collector_is_off(sph, base):
    gc.disable()
    try:
        b = AdaptiveBatcher(sph, batch_max=1)
        _start(b)
        assert len(heap._live) == 1 and gc.get_freeze_count() == base
    finally:
        gc.enable()
        b.close()
    assert gc.get_freeze_count() == base


def test_gen2_counts_a_forced_full_collection_while_live(sph):
    counters = sph.obs.counters
    b = AdaptiveBatcher(sph, batch_max=1)
    # the freeze's own full collection is counted: the hook is in first
    assert counters.get(ck.GC_GEN2) >= 1
    _start(b)
    n, us = counters.get(ck.GC_GEN2), counters.get(ck.GC_GEN2_US)
    gc.collect(1)                               # not a full collection
    assert counters.get(ck.GC_GEN2) == n
    gc.collect(2)
    assert counters.get(ck.GC_GEN2) >= n + 1
    assert counters.get(ck.GC_GEN2_US) >= us
    b.close()
    n = counters.get(ck.GC_GEN2)
    gc.collect(2)                               # no batcher: not counted
    assert counters.get(ck.GC_GEN2) == n
