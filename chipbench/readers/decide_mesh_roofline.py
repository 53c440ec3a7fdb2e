"""``decide_mesh_roofline``: ``decide_batch_roofline`` for an engine whose
state is split over several chips. The bytes a decide step HAS to move are
the same whatever implements it (``decide_roofline.decide_min_bytes``);
the mesh can move them at the HBM peak of all its chips together, and the
busy time is the trace's mean over the device planes."""

from __future__ import annotations

from typing import Optional

from chipbench.readers.common import Facts
from chipbench.readers.decide_roofline import decide_min_bytes


def decide_mesh_roofline(metric: dict, facts: Facts) -> Optional[float]:
    found = facts.cycles(metric["span"])
    if found is None or found[1] <= 0:
        return None
    ns, busy_s = found
    peak = facts.trace["n_devices"] * facts.peaks["hbm_bytes_per_s"]
    return 100.0 * sum(decide_min_bytes(n) for n in ns) / peak / busy_s


READERS = {"decide_mesh_roofline": decide_mesh_roofline}
