"""The benchmark's own encoder of the upstream cluster frame format
(``sentinel-cluster-common-default``): ``[len:2][xid:4][type:1][payload]``,
big-endian. Vectorized over a whole schedule with packed numpy records."""

from __future__ import annotations

import struct

import numpy as np

MSG_PING, MSG_FLOW = 0, 1

#: FLOW request: body = xid, type, flowId:8, count:4, priority:1 (18 bytes)
FLOW_REQUEST = np.dtype([("len", ">u2"), ("xid", ">i4"), ("type", "i1"),
                         ("flow_id", ">i8"), ("count", ">i4"),
                         ("prio", "i1")])
#: FLOW response: body = xid, type, status:1 signed, remaining:4, waitMs:4
FLOW_RESPONSE = np.dtype([("len", ">u2"), ("xid", ">i4"), ("type", "i1"),
                          ("status", "i1"), ("remaining", ">i4"),
                          ("wait_ms", ">i4")])
assert FLOW_REQUEST.itemsize == 20 and FLOW_RESPONSE.itemsize == 16


def encode_flow_requests(xids, flow_ids, count: int = 1,
                         prioritized: bool = False) -> bytes:
    """All frames of a schedule, back to back; frame ``i`` is bytes
    ``[20*i, 20*i+20)``."""
    rec = np.zeros(len(xids), FLOW_REQUEST)
    rec["len"] = FLOW_REQUEST.itemsize - 2
    rec["xid"] = xids
    rec["type"] = MSG_FLOW
    rec["flow_id"] = flow_ids
    rec["count"] = count
    rec["prio"] = 1 if prioritized else 0
    return rec.tobytes()


def encode_ping(xid: int, namespace: str) -> bytes:
    raw = namespace.encode("utf-8")
    body = struct.pack(">ibi", xid, MSG_PING, len(raw)) + raw
    return struct.pack(">H", len(body)) + body


def decode_flow_responses(buf: bytes) -> np.ndarray:
    """Whole FLOW response frames at the head of ``buf`` as records."""
    n = len(buf) // FLOW_RESPONSE.itemsize
    return np.frombuffer(buf, FLOW_RESPONSE, count=n)
