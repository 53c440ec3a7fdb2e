"""The benchmark's own frame encoder against the program's codec."""

import numpy as np
import pytest

from chipbench.loadgen import frames
from sentinel_tpu.cluster import codec


def test_flow_requests_decode_as_the_program_reads_them():
    xids = np.array([0, 1, 70000, 2**31 - 1])
    fids = np.array([0, 5, 1048575, 2**40])
    wire = frames.encode_flow_requests(xids, fids)
    asm = codec.FrameAssembler()
    got = [codec.decode_request(f) for f in asm.feed(wire)]
    assert [(r.xid, r.type, r.data) for r in got] == [
        (int(x), codec.MSG_TYPE_FLOW, (int(f), 1, False))
        for x, f in zip(xids, fids)]
    assert wire == b"".join(codec.encode_request(codec.Request(
        int(x), codec.MSG_TYPE_FLOW, (int(f), 1, False)))
        for x, f in zip(xids, fids))


def test_prioritized_flag_and_count_are_carried():
    wire = frames.encode_flow_requests(np.array([9]), np.array([3]), count=4,
                                       prioritized=True)
    req = codec.decode_request(codec.FrameAssembler().feed(wire)[0])
    assert req.data == (3, 4, True)


def test_ping_matches_the_programs_encoding():
    assert frames.encode_ping(-3, "ns-1") == codec.encode_request(
        codec.Request(-3, codec.MSG_TYPE_PING, "ns-1"))


@pytest.mark.parametrize("status", [0, 1, 2, -2, -4])
def test_flow_responses_are_read_as_the_program_writes_them(status):
    raw = b"".join(codec.encode_response(codec.Response(
        xid, codec.MSG_TYPE_FLOW, status, (remaining, wait)))
        for xid, remaining, wait in [(1, 49, 0), (123456, 0, 400)])
    rec = frames.decode_flow_responses(raw + b"\x00\x0e")   # + a partial
    assert rec["xid"].tolist() == [1, 123456]
    assert rec["status"].tolist() == [status, status]
    assert rec["remaining"].tolist() == [49, 0]
    assert rec["wait_ms"].tolist() == [0, 400]
    assert set(rec["len"].tolist()) == {14}
