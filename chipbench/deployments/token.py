"""The cluster token server, standalone, behind real TCP frames."""

from __future__ import annotations

import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench import stats
from chipbench.cell import Checks, Context, Measured, Span, Tracer
from chipbench.generators.arrivals import rank_permutation
from chipbench.loadgen.parent import ChildLoad
from chipbench.reference.token import TokenReference


class _EngineLog:
    """Every call the server makes into the engine, in the engine's order:
    the requests as handed over, the clock reading, and what came back.
    Recorded from here because the token server has no spans of its own."""

    def __init__(self, engine) -> None:
        import jax
        self.calls: List[tuple] = []
        self._inner = engine.request_tokens
        self._note = jax.profiler.TraceAnnotation
        engine.request_tokens = self._call

    def _call(self, flow_ids, acquire, prioritized=None, *, now_ms):
        t0 = time.monotonic()
        # the annotation carries the batch size into the trace, where the
        # device's work is counted per whole cycle of this call
        with self._note("bench.token_step", n=len(flow_ids)):
            res = self._inner(flow_ids, acquire, prioritized, now_ms=now_ms)
        self.calls.append((t0, time.monotonic(), int(now_ms),
                           list(flow_ids), list(acquire), res))
        return res


class TokenServerCell:
    def __init__(self, ctx: Context) -> None:
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.mix = ctx.cell.traffic
        self.child = None
        self.server = None
        self.engine = None
        self.log = None
        self.client: Dict[str, np.ndarray] = {}

    # -- the rules of the configuration, from the seed ------------------
    def _rules(self) -> Tuple[np.ndarray, np.ndarray]:
        """(count, namespace) per flowId: a permutation from the seed maps
        popularity rank to flowId and the thresholds follow it."""
        n = self.cfg["flows"]
        perm = rank_permutation(self.ctx.seed, n)
        count = np.full(n, self.cfg["cold_count"], np.int64)
        count[perm[: self.cfg["hot_flows"]]] = self.cfg["hot_count"]
        return count, np.arange(n) % self.cfg["namespaces"]

    def _ns_name(self, k: int) -> str:
        return f"ns-{k}"

    def set_up(self) -> None:
        cfg, mix = self.cfg, self.mix
        n_ns = cfg["namespaces"]
        self.child = ChildLoad("chipbench.loadgen.tcp_child", {
            **mix, "seed": self.ctx.seed, "seconds": self.ctx.seconds,
            "universe": cfg["flows"], "namespaces": n_ns,
            "namespace_names": [self._ns_name(k) for k in range(n_ns)],
            "host": "127.0.0.1"})

        from sentinel_tpu.cluster.server import ClusterTokenServer
        from sentinel_tpu.parallel.cluster import (
            THRESHOLD_GLOBAL, ClusterEngine, ClusterFlowRule, ClusterSpec)

        self.engine = ClusterEngine(ClusterSpec(
            n_shards=cfg["chips"], flows_per_shard=cfg["flows"] // cfg["chips"],
            namespaces=n_ns))
        self.server = ClusterTokenServer(
            self.engine, host="127.0.0.1", port=0,
            log_dir=str(self.ctx.workdir / "logs"))
        count, ns = self._rules()
        self.count, self.ns = count, ns
        for k in range(n_ns):
            self.server.load_flow_rules(self._ns_name(k), [
                ClusterFlowRule(flow_id=int(f), count=float(count[f]),
                                threshold_type=THRESHOLD_GLOBAL,
                                exceed_count=cfg["exceed_count"],
                                max_occupy_ratio=cfg["max_occupy_ratio"])
                for f in range(k, cfg["flows"], n_ns)])
        self.log = _EngineLog(self.engine)
        # warm every batch size pad_pow2 can produce up to the mix's
        # largest, through the same entry the server calls; the clock
        # reading is the server's own, so the reference replays these too
        b = 8
        while b <= mix["warm_max_batch"]:
            self.engine.request_tokens(
                list(range(b)), [1] * b, [False] * b,
                now_ms=self.server.clock.now_ms())
            b *= 2
        self.warm_calls = len(self.log.calls)
        self.server.start()
        self.child.wait_scheduled()
        self.child.connect(self.server.port)

    def run_window(self, tracer: Tracer) -> Measured:
        mix, seconds = self.mix, self.ctx.seconds
        t0 = self.child.go(lead_s=0.25 + mix["warm_seconds"])
        tracer.arm(t0)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c = self.child.collect(mix["timeout_ms"] / 1e3 + mix["grace_s"] + 30)
        self.client = c
        timed = c["due_s"] >= 0.0
        lat = stats.due_latency_ms(c["due_s"][timed], c["recv_s"][timed],
                                   mix["timeout_ms"])
        late = (c["sent_s"][timed] - c["due_s"][timed]) * 1e3
        print(f"token cell: generator late p99 {np.percentile(late, 99):.3f} "
              f"max {late.max():.3f} ms at {c['due_s'][timed][late.argmax()]:.2f} s; "
              f"largest batch {max(len(x[3]) for x in self.log.calls[self.warm_calls:])}",
              file=sys.stderr)
        spans = [Span(a - t0, b - t0, len(f))
                 for a, b, _, f, _, _ in self.log.calls[self.warm_calls:]
                 if 0.0 <= a - t0 and b - t0 <= seconds]
        return Measured(
            t0=t0, window_s=seconds, attempted=int(timed.sum()),
            failed=stats.failed(lat, mix["timeout_ms"]),
            end_to_end={"grant_p50_ms": stats.percentile_exact(lat, 50),
                        "grant_p99_ms": stats.percentile_exact(lat, 99)},
            spans={"bench.token_step": spans},
            samples={"grant_ms": lat,
                     "late_ms": late, "due_s": c["due_s"][timed]})

    def release(self) -> None:
        self.server.stop()
        self.server.stat_log.close()
        self.engine.state = None
        self.engine._table = None
        self.engine = None

    # -- correct ---------------------------------------------------------
    def reference(self, **window) -> TokenReference:
        window = {"buckets": self.cfg["window_buckets"],
                  "win_ms": self.cfg["window_ms"], **window}
        rules = {int(f): (int(self.count[f] * self.cfg["exceed_count"]),
                          int(self.ns[f]))
                 for f in range(self.cfg["flows"])}
        return TokenReference(rules, self.cfg["namespace_qps"], **window)

    def check(self) -> Checks:
        """Every answer of the run against the plain reference: what the
        engine returned, request by request in the engine's order, and
        what each client was told, flow by flow (the wire carries no
        engine position, so per flow the (status, remaining) answers are
        compared as sorted lists)."""
        c = self.client
        answered = c["answers"] > 0
        told = sorted(zip(c["flow_id"][answered].tolist(),
                          c["status"][answered].tolist(),
                          c["remaining"][answered].tolist()))
        return self._judge([got for *_, got in self.log.calls], told, {
            "unanswered": (int((~answered).sum()), 0),
            "xid_faults": (int((c["answers"] > 1).sum()
                               + c["faults"].sum()), 0)})

    def control(self) -> Checks:
        """The reference in the program's place with one stated guarantee
        broken: one bucket of 1000 ms instead of 10 x 100 ms, which
        tumbles instead of sliding. It has to come out as not correct."""
        ctl = self.reference(buckets=1, win_ms=1000)
        answers = [ctl.step(fids, acq, now_ms)
                   for _, _, now_ms, fids, acq, _ in self.log.calls]
        told = sorted(
            (f, s, r)
            for (_, _, _, fids, _, _), res in zip(
                self.log.calls[self.warm_calls:], answers[self.warm_calls:])
            for f, (s, _, r) in zip(fids, res))
        return self._judge(answers, told, {})

    def _judge(self, engine_answers, told, more: Checks) -> Checks:
        ref = self.reference()
        engine_wrong = 0
        want: List[tuple] = []
        for k, ((_, _, now_ms, fids, acq, _), got) in enumerate(
                zip(self.log.calls, engine_answers)):
            exp = ref.step(fids, acq, now_ms)
            engine_wrong += sum(tuple(g) != e for g, e in zip(got, exp))
            if k >= self.warm_calls:
                want.extend((f, s, r) for f, (s, _, r) in zip(fids, exp))
        want.sort()
        client_wrong = abs(len(told) - len(want)) + sum(
            a != b for a, b in zip(told, want))
        return {"engine_wrong": (engine_wrong, 0),
                "client_wrong": (client_wrong, 0), **more}

    def close(self) -> None:
        if self.child is not None:
            self.child.kill()
        if self.server is not None and self.server._thread is not None:
            self.server.stop()


BUILDERS = {"token_server": TokenServerCell}
