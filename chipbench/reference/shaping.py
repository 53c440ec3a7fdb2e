"""Plain reference of the embedded engine's admission under all four
traffic-shaping controllers and both ratio circuit breakers, after
alibaba/Sentinel 1.8.6 sentinel-core: ``FlowSlot`` →  ``DegradeSlot`` →
``StatisticSlot`` counts the pass. Strictly sequential, one event after the
other at the call's one clock reading, Python ints and float64, no arrays.

The controllers (``slots/block/flow/controller/``), as the Java classes
compute them:

* ``DefaultController.canPass``: ``(int) passQps + acquire <= count`` over
  the second window (2 x 500 ms).
* ``WarmUpController``: ``warningToken = (int)(period * count) /
  (coldFactor - 1)`` and ``maxToken = warningToken + (int)(2 * period *
  count / (1.0 + coldFactor))`` are ints, ``coldFactor`` is an int,
  ``storedTokens`` a long. ``syncToken`` runs once a second with the
  PREVIOUS second's pass count: ``coolDownTokens`` refills ``(long)(old +
  elapsed_ms * count / 1000)`` below the warning line, and above it only
  while ``passQps < (int)count / coldFactor`` (an integer division), caps at
  ``maxToken``, then the previous second's passes are taken off. At or
  above the warning line an event passes iff ``passQps + acquire <=
  Math.nextUp(1.0 / (aboveToken * slope + 1.0 / count))``, below it iff
  ``passQps + acquire <= count``.
* ``RateLimiterController``: ``costTime = Math.round(1.0 * acquire / count *
  1000)``; an event whose ``latestPassedTime + costTime`` is not after now
  passes at once and sets ``latestPassedTime = now``; otherwise it waits
  ``latestPassedTime + costTime - now`` ms if that is no more than
  ``maxQueueingTimeMs`` (and moves ``latestPassedTime`` on by ``costTime``),
  else it is refused and moves nothing.
* ``WarmUpRateLimiterController``: the same pacing with ``costTime`` from
  the warm-up rate while the tokens are at or above the warning line.

The breakers (``slots/block/degrade/circuitbreaker/``): CLOSED → OPEN when,
in a window of ``stat_interval_ms`` with at least ``min_requests``
completions, the share of slow (``rt > max_rt_ms``) or failed completions is
ABOVE the threshold; OPEN for ``retry_ms``, then the first event to arrive
is the probe (HALF_OPEN) and passes, everything else is refused; the probe's
completion closes the breaker (good) or re-opens it (slow / failed).

The order of the chain decides what an event spends. ``StatisticSlot``
counts a pass only when the whole chain passed, so an event the breaker
refuses spends NOTHING of a count-based budget (Default, WarmUp). The two
pacing controllers move ``latestPassedTime`` inside ``canPass``, before
``DegradeSlot`` is asked, so a paced slot IS spent on an event the breaker
then refuses. ``charge_refused=True`` plants the fault the program had
before PR 35 — refused events charged to the count-based budget — and is
the cell's CONTROL.

It imports nothing of the program. Departures from the Java original, all
because the program's API is batched and a batch is the operation, or
because its clock counts from its own start:

* completions arrive as one ``exit_batch`` call; a breaker's window takes
  the whole call and is judged once, after it;
* a half-open probe is resolved by the first completion of its resource in
  the call, whichever entry it belonged to;
* a breaker's window tumbles every ``stat_interval_ms`` counted from the
  engine's start (``epoch_ms``), and ``syncToken``'s second is counted from
  there too (the pass count it reads is the previous second's of the wall
  clock, as the original's minute array is aligned);
* a waiting event is not slept: it is answered ``(PASS, wait_ms)`` and
  counted as a pass at once, at the call's clock reading.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench.reference.token import _Window

PASS, FLOW, DEGRADE = 0, 1, 2
CLOSED, OPEN, HALF_OPEN = 0, 1, 2
DEFAULT, WARM_UP, RATE_LIMITER, WARM_UP_RATE_LIMITER = 0, 1, 2, 3
SLOW_RATIO, ERROR_RATIO = 0, 1

#: what a run has to contain for its comparison to mean something
EXERCISES = ("paced_pass", "cold_block", "slow_ratio_trip", "error_ratio_trip",
             "probe_closed", "probe_reopened", "refused_on_spent_budget")


class FlowShape(NamedTuple):
    count: float
    behavior: int = DEFAULT
    warm_up_period_s: int = 10
    max_queue_ms: int = 500
    cold_factor: int = 3


class Breaker(NamedTuple):
    grade: int                  # SLOW_RATIO or ERROR_RATIO
    threshold: float            # trip when bad/total is ABOVE this
    retry_ms: int               # how long it stays open before a probe
    max_rt_ms: float = 0.0      # SLOW_RATIO: a completion slower than this is bad
    min_requests: int = 5
    interval_ms: int = 1000


def java_round(x: float) -> int:
    """``Math.round(double)``."""
    return math.floor(x + 0.5)


class _Name:
    """Everything the chain keeps for one resource."""

    __slots__ = ("key", "shape", "warning", "max_token", "slope", "stored",
                 "filled_sec", "latest", "secs", "rule", "state",
                 "next_retry", "stamp", "bad", "total", "call", "refused")

    def __init__(self, key: int, shape: Optional[FlowShape],
                 rule: Optional[Breaker]) -> None:
        self.key, self.shape, self.rule = key, shape, rule
        self.latest: Optional[int] = None       # latestPassedTime
        self.secs: Dict[int, int] = {}          # passes by wall-clock second
        self.stored = 0                         # storedTokens
        self.filled_sec: Optional[int] = None   # lastFilledTime, in seconds
        self.warning = self.max_token = 0
        self.slope = 0.0
        if shape is not None and shape.behavior in (WARM_UP,
                                                    WARM_UP_RATE_LIMITER):
            period, cold = shape.warm_up_period_s, shape.cold_factor
            self.warning = int(period * shape.count) // (cold - 1)
            self.max_token = self.warning + int(
                2 * period * shape.count / (1.0 + cold))
            self.slope = (cold - 1.0) / shape.count / (
                self.max_token - self.warning)
        self.state = CLOSED
        self.next_retry = 0
        self.stamp: Optional[int] = None
        self.bad = self.total = 0
        self.call = -1                          # the call `refused` counts in
        self.refused = 0


class ShapingReference:
    def __init__(self, flow: Dict[str, FlowShape],
                 breakers: Dict[str, Breaker], epoch_ms: int,
                 buckets: int = 2, win_ms: int = 500,
                 charge_refused: bool = False) -> None:
        self.epoch_ms = epoch_ms
        self.window = _Window(buckets, win_ms)
        self.charge_refused = charge_refused
        self.names: Dict[str, _Name] = {}
        for name in flow.keys() | breakers.keys():
            self.names[name] = _Name(len(self.names), flow.get(name),
                                     breakers.get(name))
        self.seen = dict.fromkeys(EXERCISES, 0)
        self._calls = 0

    # -- FlowSlot -----------------------------------------------------------
    def _sync_token(self, st: _Name, now_ms: int) -> None:
        """``WarmUpController.syncToken`` + ``coolDownTokens``."""
        sec = (now_ms - self.epoch_ms) // 1000
        if st.filled_sec is not None and sec <= st.filled_sec:
            return
        shape = st.shape
        previous = st.secs.get(now_ms // 1000 - 1, 0)
        old = new = st.stored
        refill = old < st.warning or (
            old > st.warning
            and previous < int(shape.count) // shape.cold_factor)
        if refill:
            if st.filled_sec is None:           # lastFilledTime 0: an age
                new = st.max_token
            else:
                new = int(old + (sec - st.filled_sec) * 1000 * shape.count
                          / 1000)
        st.stored = max(min(new, st.max_token) - previous, 0)
        st.filled_sec = sec

    def _warm_qps(self, st: _Name) -> Optional[float]:
        """``warningQps`` while the tokens are at or above the warning
        line, else None (the rule runs at ``count``)."""
        if st.stored < st.warning:
            return None
        above = st.stored - st.warning
        return math.nextafter(
            1.0 / (above * st.slope + 1.0 / st.shape.count), math.inf)

    def _pace(self, st: _Name, qps: float, acquire: int,
              now_ms: int) -> Optional[int]:
        """``RateLimiterController.canPass`` at ``qps`` → the wait, or
        None when refused."""
        shape = st.shape
        if shape.count <= 0:
            return None
        cost = java_round(1.0 * acquire / qps * 1000)
        if st.latest is None or st.latest + cost <= now_ms:
            st.latest = now_ms
            return 0
        wait = st.latest + cost - now_ms
        if wait > shape.max_queue_ms:
            return None
        st.latest += cost
        return wait

    def _flow(self, st: _Name, used: int, acquire: int,
              now_ms: int) -> Tuple[bool, int]:
        """→ (passes, wait_ms) of one event, ``used`` = ``(int) passQps``."""
        shape = st.shape
        if shape.behavior == DEFAULT:
            return used + acquire <= shape.count, 0
        if shape.behavior == RATE_LIMITER:
            wait = self._pace(st, shape.count, acquire, now_ms)
            return wait is not None, wait or 0
        self._sync_token(st, now_ms)
        warm = self._warm_qps(st)
        if shape.behavior == WARM_UP:
            if warm is None:
                return used + acquire <= shape.count, 0
            ok = used + acquire <= warm
            if not ok and used + acquire <= shape.count:
                self.seen["cold_block"] += 1
            return ok, 0
        wait = self._pace(st, shape.count if warm is None else warm,
                          acquire, now_ms)
        return wait is not None, wait or 0

    # -- the chain ----------------------------------------------------------
    def entries(self, names: Sequence[str], now_ms: int,
                acquire: int = 1) -> Tuple[List[int], List[int]]:
        """One ``entry_batch`` call → (reason, wait_ms) of each event, as
        two lists; reason 0 = admitted."""
        rel = now_ms - self.epoch_ms
        self._calls += 1
        call = self._calls
        lookup = self.names.get
        window = self.window
        reasons: List[int] = []
        waits: List[int] = []
        for name in names:
            st = lookup(name)
            if st is None:
                reasons.append(PASS)
                waits.append(0)
                continue
            wait = 0
            shape = st.shape
            if shape is not None:
                used = window.total(st.key, now_ms)
                ok, wait = self._flow(st, used, acquire, now_ms)
                if not ok:
                    reasons.append(FLOW)
                    waits.append(0)
                    continue
            if st.rule is not None and st.state != CLOSED:
                if st.state == OPEN and rel >= st.next_retry:
                    st.state = HALF_OPEN            # this event is the probe
                else:
                    if shape is not None:
                        self._refused(st, shape, used, acquire, now_ms, call)
                    reasons.append(DEGRADE)
                    waits.append(0)
                    continue
            if shape is not None:
                self._count_pass(st, acquire, now_ms)
            if wait:
                self.seen["paced_pass"] += 1
            reasons.append(PASS)
            waits.append(wait)
        return reasons, waits

    def _count_pass(self, st: _Name, acquire: int, now_ms: int) -> None:
        """``StatisticSlot``: the second window, and the wall-clock second
        that ``previousPassQps`` will read."""
        self.window.add(st.key, now_ms, acquire)
        sec = now_ms // 1000
        secs = st.secs
        secs[sec] = secs.get(sec, 0) + acquire
        if len(secs) > 2:
            for old in [s for s in secs if s < sec - 1]:
                del secs[old]

    def _refused(self, st: _Name, shape: FlowShape, used: int, acquire: int,
                 now_ms: int, call: int) -> None:
        """An event the flow slot admitted and the breaker refuses: it
        spends nothing of a count-based budget. Counted as exercised where
        that decides an answer: where charging this call's refused events
        would have spent the budget."""
        if shape.behavior in (RATE_LIMITER, WARM_UP_RATE_LIMITER):
            return
        if st.call != call:
            st.call, st.refused = call, 0
        warm = self._warm_qps(st) if shape.behavior == WARM_UP else None
        limit = shape.count if warm is None else warm
        if used + st.refused + acquire > limit:
            self.seen["refused_on_spent_budget"] += 1
        st.refused += acquire
        if self.charge_refused:                     # the planted fault
            self._count_pass(st, acquire, now_ms)

    # -- DegradeSlot.exit ---------------------------------------------------
    def exits(self, names: Sequence[str], rt_ms: Sequence[int],
              errors: Sequence[bool], now_ms: int) -> None:
        """One ``exit_batch`` call: the completions of earlier entries."""
        rel = now_ms - self.epoch_ms
        seen: Dict[str, List[bool]] = {}
        lookup = self.names.get
        for name, rt, err in zip(names, rt_ms, errors):
            st = lookup(name)
            if st is None or st.rule is None:
                continue
            bad = rt > st.rule.max_rt_ms if st.rule.grade == SLOW_RATIO \
                else bool(err)
            seen.setdefault(name, []).append(bad)
        for name, bads in seen.items():
            st = self.names[name]
            rule = st.rule
            if st.state == HALF_OPEN:
                if bads[0]:
                    st.state, st.next_retry = OPEN, rel + rule.retry_ms
                    self.seen["probe_reopened"] += 1
                else:
                    st.state, st.stamp = CLOSED, None
                    self.seen["probe_closed"] += 1
            widx = rel // rule.interval_ms
            if st.stamp != widx:
                st.stamp, st.bad, st.total = widx, 0, 0
            st.bad += sum(bads)
            st.total += len(bads)
            if (st.state == CLOSED and st.total >= rule.min_requests
                    and st.bad / st.total > rule.threshold):
                st.state, st.next_retry = OPEN, rel + rule.retry_ms
                self.seen["slow_ratio_trip" if rule.grade == SLOW_RATIO
                          else "error_ratio_trip"] += 1
