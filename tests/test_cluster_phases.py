"""The token server's cycle and the engine call, named from inside: a tiny
``ClusterTokenServer`` on loopback leaves, for every engine call,
``server.step`` ⊃ ``token.route`` → ``.put`` → ``.dispatch`` →
``.readback`` → ``.gather`` in ``engine.obs``, with the three
``cluster.server.*`` counters, and answers exactly what it answers with
observability off."""

import pytest

from sentinel_tpu.cluster.client import ClusterTokenClient
from sentinel_tpu.cluster.server import ClusterTokenServer
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.obs import RuntimeObs, _NULL_CTX
from sentinel_tpu.obs import counters as ck
from sentinel_tpu.parallel.cluster import (
    THRESHOLD_GLOBAL, ClusterEngine, ClusterFlowRule, ClusterSpec,
)

CHILDREN = ["token.route", "token.put", "token.dispatch", "token.readback",
            "token.gather"]
# (flow_id, count, prioritized): 7 has a rule of 3 a second, 8 has none
ROUNDS = [[(7, 1, False)] * 2 + [(8, 1, False)], [(7, 1, False)] * 3,
          [(7, 1, False), (9, 1, False)]]
SENT = sum(len(r) for r in ROUNDS)


def _serve(obs):
    """The rounds through a server on loopback → (answers, engine)."""
    engine = ClusterEngine(
        ClusterSpec(n_shards=1, flows_per_shard=16, namespaces=2), obs=obs)
    server = ClusterTokenServer(
        engine, clock=ManualClock(start_ms=50_000_000), host="127.0.0.1",
        port=0, batch_window_ms=0.5)
    server.load_flow_rules("ns", [
        ClusterFlowRule(flow_id=7, count=3, threshold_type=THRESHOLD_GLOBAL),
        ClusterFlowRule(flow_id=9, count=5, threshold_type=THRESHOLD_GLOBAL)])
    server.start()
    # generous timeout: the first request compiles the step on the CPU
    client = ClusterTokenClient("127.0.0.1", server.port, namespace="ns",
                                request_timeout_ms=60_000,
                                auto_reconnect=False)
    client.start()
    try:
        answers = [[(r.status, r.wait_ms, r.remaining)
                    for r in client.request_tokens_batch(items)]
                   for items in ROUNDS]
    finally:
        client.stop()
        server.stop()
        server.stat_log.close()
    return answers, engine


@pytest.fixture(scope="module")
def served():
    answers, engine = _serve(None)          # the engine makes its own bundle
    return answers, engine, engine.obs.spans.snapshot()


def test_the_engine_makes_its_own_bundle_or_takes_the_one_given():
    given = RuntimeObs(enabled=False)
    spec = ClusterSpec(n_shards=1, flows_per_shard=16, namespaces=2)
    assert ClusterEngine(spec, obs=given).obs is given
    own = ClusterEngine(spec).obs
    assert isinstance(own, RuntimeObs) and own is not given


def test_every_engine_call_leaves_its_phases_in_order_inside_the_step(served):
    _, _, spans = served
    steps = [s for s in spans if s["name"] == "server.step"]
    assert steps and sum(s["n"] for s in steps) == SENT
    for step in steps:
        kids = sorted((s for s in spans if s["parent"] == step["id"]),
                      key=lambda s: s["start_ns"])
        assert [k["name"] for k in kids] == CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a["end_ns"] <= b["start_ns"]
        assert step["start_ns"] <= kids[0]["start_ns"]
        assert kids[-1]["end_ns"] <= step["end_ns"]
        assert {k["trace"] for k in kids} == {step["trace"]}
        # the children ran in the worker, across asyncio.to_thread
        assert {k["thread"] for k in kids} != {step["thread"]}
        route, put, disp, back, gather = kids
        assert route["n"] == back["n"] == gather["n"] == step["n"]
        assert put["n"] == disp["n"] >= step["n"]       # padded lanes


def test_the_cycle_has_its_collect_and_respond_spans(served):
    _, _, spans = served
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    n_steps = len(by["server.step"])
    assert len(by["server.respond"]) == n_steps
    assert sum(s["n"] for s in by["server.respond"]) == SENT
    assert sum(s["n"] for s in by["server.collect"]) == SENT
    for s in by["server.respond"] + by["server.collect"]:
        assert s["parent"] == 0 and s["dur_ns"] >= 0
    # loop-thread phases of a cycle follow one another
    for step, respond in zip(sorted(by["server.step"],
                                    key=lambda s: s["start_ns"]),
                             sorted(by["server.respond"],
                                    key=lambda s: s["start_ns"])):
        assert step["end_ns"] <= respond["start_ns"]
        assert step["thread"] == respond["thread"]


def test_the_three_counters_add_up_to_the_requests_sent(served):
    _, engine, spans = served
    counts = engine.obs.counters.snapshot()
    assert counts[ck.CLUSTER_SERVER_TAKEN] == SENT
    cycles = counts[ck.CLUSTER_SERVER_CYCLES]
    assert cycles == sum(s["name"] == "server.collect" for s in spans)
    assert 1 <= cycles <= SENT
    # each request waited at least part of the 0.5 ms window, none a minute
    assert 0 < counts[ck.CLUSTER_SERVER_QUEUE_WAIT_US] < 60e6 * SENT


def test_answers_are_those_of_the_run_with_observability_off(served,
                                                             monkeypatch):
    answers, _, _ = served
    monkeypatch.setenv("SENTINEL_OBS_DISABLE", "1")
    quiet, engine = _serve(None)
    assert engine.obs.enabled is False
    # every new site reduced to one attribute check: nothing was recorded
    assert engine.obs.phase("token.route", n=3) is _NULL_CTX
    assert engine.obs.spans.snapshot() == []
    assert engine.obs.counters.snapshot() == {}
    assert quiet == answers
    flat = [a for r in answers for a in r]
    assert [s for s, _, _ in flat].count(0) == 4        # 3 of flow 7, 1 of 9
    assert [s for s, _, _ in flat].count(3) == 1        # flow 8: no rule


def test_the_fallback_route_and_the_param_path_keep_the_phases():
    """Flow ids the dense lookup cannot hold take the loop route; both
    routes leave the same five phases (here without a server: roots)."""
    engine = ClusterEngine(
        ClusterSpec(n_shards=1, flows_per_shard=16, namespaces=2))
    engine.load_rules("ns", [ClusterFlowRule(
        flow_id=-5, count=2, threshold_type=THRESHOLD_GLOBAL)])
    got = engine.request_tokens([-5, -5, -5], [1, 1, 1], now_ms=50_000_000)
    assert [s for s, _, _ in got] == [0, 0, 1]
    spans = sorted(engine.obs.spans.snapshot(), key=lambda s: s["start_ns"])
    assert [s["name"] for s in spans] == CHILDREN
    assert all(s["parent"] == 0 for s in spans)
    assert [s["n"] for s in spans] == [3, 8, 8, 3, 3]
