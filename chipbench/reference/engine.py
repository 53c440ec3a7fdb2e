"""Plain reference of the embedded engine's admission: ``FlowSlot`` with
``DefaultController`` (QPS over the second window, 2 x 500 ms) followed by
``DegradeSlot`` with exception-ratio circuit breakers, after
alibaba/Sentinel 1.8.6 sentinel-core. Strictly sequential within a call,
integers and one float ratio, no arrays.

It imports nothing of the program. Its inputs are the rules of the
configuration and the calls in the order, and at the clock readings, at
which the engine was given them. Two departures from the Java original,
both because the program's API is batched and a batch is the operation:

* completions arrive as one ``exit_batch`` call; the breaker's window
  takes the whole call and is judged once, after it (the original judges
  after every completion);
* a half-open probe is resolved by the first completion of that resource
  in the call, whichever entry it belonged to.

The breaker's statistics window tumbles every ``stat_interval_ms`` counted
from the engine's start (``epoch_ms``), not from the wall clock's zero.

``buckets``/``win_ms`` other than 2 x 500 give the CONTROL: one bucket of
1000 ms, which tumbles instead of sliding.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence

from chipbench.reference.token import _Window

PASS, FLOW, DEGRADE = 0, 1, 2
CLOSED, OPEN, HALF_OPEN = 0, 1, 2


class BreakerRule(NamedTuple):
    ratio: float                # trip when errors/total is ABOVE this
    retry_ms: int               # how long it stays open before a probe
    min_requests: int = 5
    interval_ms: int = 1000


class _Breaker:
    def __init__(self, rule: BreakerRule) -> None:
        self.rule = rule
        self.state = CLOSED
        self.next_retry = 0
        self.stamp = None
        self.bad = self.total = 0


class EngineReference:
    def __init__(self, flow: Dict[str, int], degrade: Dict[str, BreakerRule],
                 epoch_ms: int, buckets: int = 2, win_ms: int = 500) -> None:
        self.flow = flow
        self.epoch_ms = epoch_ms
        self.window = _Window(buckets, win_ms)
        self.keys: Dict[str, int] = {}
        self.breakers = {name: _Breaker(r) for name, r in degrade.items()}
        self.trips = 0

    def _key(self, name: str) -> int:
        return self.keys.setdefault(name, len(self.keys))

    def entries(self, names: Sequence[str], now_ms: int) -> List[int]:
        """One ``entry_batch`` call, acquire 1 each → the reason code of
        each event (0 = allowed)."""
        rel = now_ms - self.epoch_ms
        out = []
        for name in names:
            limit = self.flow.get(name)
            if limit is not None:
                key = self._key(name)
                if self.window.total(key, now_ms) + 1 > limit:
                    out.append(FLOW)
                    continue
            br = self.breakers.get(name)
            if br is not None and br.state != CLOSED:
                if br.state == OPEN and rel >= br.next_retry:
                    br.state = HALF_OPEN            # this event is the probe
                else:
                    out.append(DEGRADE)
                    continue
            if limit is not None:
                self.window.add(key, now_ms, 1)
            out.append(PASS)
        return out

    def exits(self, names: Sequence[str], errors: Sequence[bool],
              now_ms: int) -> None:
        """One ``exit_batch`` call: the completions of earlier entries."""
        rel = now_ms - self.epoch_ms
        seen: Dict[str, List[bool]] = {}
        for name, err in zip(names, errors):
            if name in self.breakers:
                seen.setdefault(name, []).append(bool(err))
        for name, errs in seen.items():
            br = self.breakers[name]
            if br.state == HALF_OPEN:
                if errs[0]:
                    br.state, br.next_retry = OPEN, rel + br.rule.retry_ms
                else:
                    br.state, br.stamp = CLOSED, None
            widx = rel // br.rule.interval_ms
            if br.stamp != widx:
                br.stamp, br.bad, br.total = widx, 0, 0
            br.bad += sum(errs)
            br.total += len(errs)
            if (br.state == CLOSED and br.total >= br.rule.min_requests
                    and br.bad / br.total > br.rule.ratio):
                br.state, br.next_retry = OPEN, rel + br.rule.retry_ms
                self.trips += 1
