"""``token_step_roofline``: the bytes a token step HAS to move, from the
shapes and the batch alone, over the chip's memory bandwidth, against the
device-busy time of the steps in the traced window. The count does not
look at how the step is implemented."""

from __future__ import annotations

from typing import Optional

from chipbench.readers.common import Facts

EVENTS = 8          # int32 counters in one bucket of the cluster window
I32 = 4


def token_step_min_bytes(n_requests: int) -> int:
    """Per request: its columns in (local row, acquire: int32; prioritized,
    valid: 1 byte), its verdict out (status, wait_ms, remaining: int32),
    one 100 ms bucket of 8 int32 and its stamp read and written for the
    flow, and the same for its namespace row."""
    columns_in = 2 * I32 + 2
    verdict_out = 3 * I32
    bucket = 2 * (EVENTS * I32 + I32)       # read + write, counters + stamp
    return n_requests * (columns_in + verdict_out + 2 * bucket)


def token_step_roofline(metric: dict, facts: Facts) -> Optional[float]:
    found = facts.cycles(metric["span"])
    if found is None or found[1] <= 0:
        return None
    ns, busy_s = found
    least_s = sum(token_step_min_bytes(n) for n in ns) / facts.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s


READERS = {"token_step_roofline": token_step_roofline}
