"""Open-loop TCP load generator — a process of its own.

It never imports JAX: the parent holds the chip. It speaks the upstream
frame format from ``frames.py``, sends each request when it is DUE
(whatever the server's backlog), and stamps each answer on arrival.

Protocol with the parent, over stdin/stdout:
  argv[1]            JSON: the traffic file's parameters, the
                     configuration's universe/namespaces, seed, seconds
  <- "scheduled N"   the schedule is made (N requests, warm phase included)
  -> "connect PORT"  the server listens
  <- "ready"         connections open, namespaces registered
  -> "go T0"         T0 on CLOCK_MONOTONIC (shared by all processes)
  <- npz bytes       per-request arrays, then EOF
"""

from __future__ import annotations

import asyncio
import gc
import io
import json
import sys
import time

import numpy as np

from chipbench import registry
from chipbench.generators.arrivals import rank_permutation
from chipbench.loadgen import frames


#: how close to a due time the sender stops sleeping and starts spinning
SPIN_S = 0.002


class _Conn(asyncio.Protocol):
    def __init__(self, idx: int, book: "_Book") -> None:
        self.idx, self.book = idx, book
        self.buf = bytearray()
        self.transport = None
        self.ping = asyncio.get_event_loop().create_future()

    def connection_made(self, transport) -> None:
        self.transport = transport

    def data_received(self, data: bytes) -> None:
        now = time.monotonic()
        self.buf += data
        if not self.ping.done():
            # PING response: [len:2][xid:4][type:1][status:1][count:4]
            if len(self.buf) < 12:
                return
            self.ping.set_result(self.buf[7])
            del self.buf[:12]
        size = frames.FLOW_RESPONSE.itemsize
        whole = len(self.buf) // size * size
        if whole:
            self.book.land(self.idx, now, bytes(self.buf[:whole]))
            del self.buf[:whole]


class _Book:
    """What came back, by request index (the xid IS the index)."""

    def __init__(self, n: int, conn_of: np.ndarray) -> None:
        self.recv = np.full(n, np.nan)
        self.status = np.full(n, -128, np.int16)
        self.remaining = np.zeros(n, np.int32)
        self.wait_ms = np.zeros(n, np.int32)
        self.answers = np.zeros(n, np.int32)
        self.conn_of = conn_of
        self.bad_frames = 0
        self.wrong_conn = 0
        self.outstanding = n

    def land(self, conn: int, now: float, raw: bytes) -> None:
        rec = frames.decode_flow_responses(raw)
        ok = (rec["len"] == frames.FLOW_RESPONSE.itemsize - 2) \
            & (rec["type"] == frames.MSG_FLOW)
        xid = rec["xid"].astype(np.int64)
        ok &= (xid >= 0) & (xid < self.recv.size)
        self.bad_frames += int((~ok).sum())
        xid, rec = xid[ok], rec[ok]
        self.wrong_conn += int((self.conn_of[xid] != conn).sum())
        first = self.answers[xid] == 0
        np.add.at(self.answers, xid, 1)
        x1 = xid[first]
        self.recv[x1] = now
        self.status[x1] = rec["status"][first]
        self.remaining[x1] = rec["remaining"][first]
        self.wait_ms[x1] = rec["wait_ms"][first]
        self.outstanding -= int(first.sum())


async def _drive(p: dict) -> dict:
    gen = registry.find("generators", p["generator"])
    sched = gen(p, p["seed"], p["seconds"], p["universe"])
    n = sched.due_s.size
    flow = rank_permutation(p["seed"], p["universe"])[sched.rank]
    n_ns, conns = p["namespaces"], p["connections"]
    per_ns = conns // n_ns
    conn_of = ((flow % n_ns) * per_ns
               + np.arange(n) % per_ns).astype(np.int64)
    wire = frames.encode_flow_requests(np.arange(n), flow)
    size = frames.FLOW_REQUEST.itemsize
    book = _Book(n, conn_of)
    print(f"scheduled {n}", flush=True)

    loop = asyncio.get_running_loop()
    port = int((await loop.run_in_executor(None, sys.stdin.readline))
               .split()[1])
    links = []
    for c in range(conns):
        _, proto = await loop.create_connection(
            lambda c=c: _Conn(c, book), p["host"], port)
        proto.transport.write(frames.encode_ping(
            -1 - c, p["namespace_names"][c // per_ns]))
        links.append(proto)
    for proto in links:
        if await proto.ping != 0:
            raise RuntimeError("PING refused")
    print("ready", flush=True)
    t0 = float((await loop.run_in_executor(None, sys.stdin.readline))
               .split()[1])

    gc.disable()
    due = sched.due_s
    due_list = due.tolist()
    conn_list = conn_of.tolist()
    sent = np.full(n, np.nan)
    writes = [proto.transport.write for proto in links]
    i = 0
    while i < n:
        now = time.monotonic() - t0
        j = i
        while j < n and due_list[j] <= now:
            writes[conn_list[j]](wire[size * j: size * j + size])
            j += 1
        if j > i:
            sent[i:j] = time.monotonic() - t0
            i = j
        if i < n:
            # the loop's timer is good to a millisecond or two: sleep to
            # just short of the next due time and yield-spin the rest
            wait = due_list[i] - (time.monotonic() - t0) - SPIN_S
            await asyncio.sleep(wait if wait > 0 else 0)
    # every answer is waited for: the client's timeout past the last due
    # time, and a grace beyond it for one that is only late
    deadline = t0 + p["seconds"] + p["timeout_ms"] / 1e3 + p["grace_s"]
    while book.outstanding > 0 and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    for proto in links:
        proto.transport.close()
    return {"due_s": due, "sent_s": sent, "recv_s": book.recv - t0,
            "flow_id": flow, "conn": conn_of, "status": book.status,
            "remaining": book.remaining, "wait_ms": book.wait_ms,
            "answers": book.answers,
            "faults": np.array([book.bad_frames, book.wrong_conn])}


def main() -> int:
    params = json.loads(sys.argv[1])
    out = asyncio.run(_drive(params))
    blob = io.BytesIO()
    np.savez(blob, **out)
    sys.stdout.flush()
    sys.stdout.buffer.write(blob.getvalue())
    sys.stdout.buffer.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
