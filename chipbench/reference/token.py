"""Plain reference of the cluster token server's admission.

Follows alibaba/Sentinel 1.8.6 ``ClusterFlowChecker.acquireClusterToken``
over ``ClusterMetric`` (sampleCount 10 x 100 ms) behind the per-namespace
``GlobalRequestLimiter``: strictly sequential, one request at a time,
integers only. Imports nothing of the program and takes nothing it made:
its inputs are the rules of the configuration and the requests in the
order, and at the clock readings, at which the engine was given them.

``buckets``/``win_ms`` other than 10 x 100 give the CONTROL: the window
the configuration states, coarsened (1 x 1000 ms tumbles instead of
sliding, so a flow can be granted up to twice its count inside one
second) — the saving in state that would tempt a later PR.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

OK, BLOCKED, NO_RULE, TOO_MANY, BAD_REQUEST = 0, 1, 3, -2, -4


class _Window:
    """Per-key pass counts in buckets of ``win_ms``, summed over the last
    ``buckets`` of them."""

    def __init__(self, buckets: int, win_ms: int) -> None:
        self.buckets, self.win_ms = buckets, win_ms
        self._c: Dict[int, Dict[int, int]] = {}

    def total(self, key: int, now_ms: int) -> int:
        per = self._c.get(key)
        if not per:
            return 0
        idx = now_ms // self.win_ms
        lo = idx - self.buckets
        dead = [b for b in per if b <= lo]
        for b in dead:
            del per[b]
        return sum(v for b, v in per.items() if b <= idx)

    def add(self, key: int, now_ms: int, n: int) -> None:
        per = self._c.setdefault(key, {})
        idx = now_ms // self.win_ms
        per[idx] = per.get(idx, 0) + n


class TokenReference:
    def __init__(self, rules: Dict[int, Tuple[int, int]], ns_qps: int,
                 buckets: int = 10, win_ms: int = 100) -> None:
        """``rules``: flowId -> (GLOBAL count x exceedCount, namespace)."""
        self.rules = rules
        self.ns_qps = ns_qps
        self.flows = _Window(buckets, win_ms)
        self.ns = _Window(buckets, win_ms)

    def step(self, flow_ids: Sequence[int], acquire: Sequence[int],
             now_ms: int) -> List[Tuple[int, int, int]]:
        """One batch as the server hands it to the engine, decided one
        request after the other at one clock reading. Returns
        ``(status, wait_ms, remaining)`` per request."""
        out = []
        for fid, acq in zip(flow_ids, acquire):
            fid, acq = int(fid), int(acq)
            if acq <= 0:
                out.append((BAD_REQUEST, 0, 0))
                continue
            rule = self.rules.get(fid)
            if rule is None:
                out.append((NO_RULE, 0, 0))
                continue
            count, ns = rule
            if self.ns.total(ns, now_ms) + 1 > self.ns_qps:
                out.append((TOO_MANY, 0, 0))
                continue
            self.ns.add(ns, now_ms, 1)
            used = self.flows.total(fid, now_ms)
            if used + acq <= count:
                self.flows.add(fid, now_ms, acq)
                out.append((OK, 0, count - used - acq))
            else:
                out.append((BLOCKED, 0, 0))
        return out
