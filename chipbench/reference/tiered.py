"""Plain reference of the embedded engine with more names than rows.

Admission is :class:`~chipbench.reference.engine.EngineReference`'s,
unchanged: its table is a dict, it never forgets a name — which IS the
semantics a tiered engine promises ("the verdict a promoted key receives
is exactly the verdict it would have received had it never left the
device"). Added is what a name owns beside its windows and must keep
through any number of demotions and promotions: the cumulative histogram
of its completions' response times, written from docs/OBSERVABILITY.md
"Per-resource RT histograms (round 20)" — 32 buckets, bucket 0 covers
[0, 1] ms, bucket i covers (2**(i-1), 2**i] ms, the top bucket is open
above; one count per completion, an erring one too.

It imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from chipbench.reference.engine import EngineReference

HIST_BUCKETS = 32


def rt_bucket(rt_ms: int) -> int:
    """The bucket of a whole number of milliseconds."""
    if rt_ms <= 1:
        return 0
    return min((int(rt_ms) - 1).bit_length(), HIST_BUCKETS - 1)


class TieredReference(EngineReference):
    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        #: every name with a completion -> its place in order of first one
        self.completed: Dict[str, int] = {}
        #: place * HIST_BUCKETS + bucket -> completions counted there
        self.cells: Dict[int, int] = {}

    def completions(self, names: Sequence[str], rt_ms: Sequence[int],
                    errors: Sequence[bool], now_ms: int) -> None:
        """One ``exit_batch`` call: the breakers' feed, then one count per
        completion in its name's histogram."""
        self.exits(names, errors, now_ms)
        completed, cells = self.completed, self.cells
        for name, rt in zip(names, rt_ms):
            cell = completed.setdefault(name, len(completed)) * HIST_BUCKETS \
                + rt_bucket(rt)
            cells[cell] = cells.get(cell, 0) + 1

    def histogram(self, name: str) -> List[int]:
        base = self.completed.get(name)
        if base is None:
            return [0] * HIST_BUCKETS
        base *= HIST_BUCKETS
        return [self.cells.get(base + b, 0) for b in range(HIST_BUCKETS)]
