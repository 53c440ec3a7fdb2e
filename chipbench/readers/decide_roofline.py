"""``decide_batch_roofline``: the bytes a decide step HAS to move, from the
shapes and the batch alone, over the chip's memory bandwidth, against the
device-busy time of the traced window. The count does not look at how the
step is implemented (nor at the exits' work, which the busy time holds:
the share is the lower for it)."""

from __future__ import annotations

from typing import Optional

from chipbench.readers.common import Facts

EVENTS = 8          # int32 counters in one bucket of a statistics window
I32 = F32 = 4


def decide_min_bytes(n_events: int) -> int:
    """Per event: its columns in (row, acquire: int32; entry type, valid,
    prioritized: 1 byte), its verdict out (allow, reason: 1 byte; wait_ms:
    int32), and one bucket read and written in the second window (8 int32
    and the stamp) and in the minute ring (the same, with the RT sum and
    the least RT)."""
    columns_in = 2 * I32 + 3
    verdict_out = 2 + I32
    second = 2 * (EVENTS * I32 + I32)
    minute = 2 * (EVENTS * I32 + I32 + F32 + I32)
    return n_events * (columns_in + verdict_out + second + minute)


def decide_roofline(metric: dict, facts: Facts) -> Optional[float]:
    found = facts.cycles(metric["span"])
    if found is None or found[1] <= 0:
        return None
    ns, busy_s = found
    least_s = sum(decide_min_bytes(n) for n in ns) / facts.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s


READERS = {"decide_roofline": decide_roofline}
