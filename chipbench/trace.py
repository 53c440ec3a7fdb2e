"""The one reduction from a profiler trace to numbers.

``load_xplane`` turns an ``.xplane.pb`` into plain planes -> lines ->
events (name, start_ns, duration_ns, and for a host annotation that
carries one its ``n``); ``reduce`` works on that plain form alone, so it
is checked against a small recorded trace kept as JSON
(``tests/chipbench/data``). Every number is on the trace's own clock: the
window runs from the first device operation to the end of the last, so
busy time can never pass it. From it come: the seconds in which an
operation ran on the device (the union of the ``XLA Ops`` intervals,
averaged over the device planes), the time per operation name, the idle
gaps, shared out among the host annotations they fell under, and the
starts of each annotation, between which ``busy_between`` counts the
device's work for whole cycles of a call.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Sequence                      # name, start_ns, duration_ns[, n]
Plane = Dict[str, object]             # {"name": str, "lines": [{"name", "events"}]}

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: host annotations that name an idle gap, most specific first: the
#: benchmark's own (``bench.*``) and the program's ``TraceAnnotation``s
ANNOTATION_PREFIXES = ("bench.", "sentinel_tpu.")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> List[Plane]:
    """Device planes whole; of the host planes only the annotations that
    can name a gap (a host plane holds millions of runtime events)."""
    from jax.profiler import ProfileData
    planes: List[Plane] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns),
                       *_n_of(ev, device))
                      for ev in line.events
                      if device or ev.name.startswith(ANNOTATION_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _n_of(ev, device: bool) -> tuple:
    """``TraceAnnotation(name, n=...)``: how many requests or events the
    annotated call carried, where the annotation says."""
    if device:
        return ()
    return tuple(int(v) for k, v in ev.stats if k == "n")[:1]


def op_name(raw: str) -> str:
    """An HLO event's text cut to a name: ``%copy.130 = s32[1048576,10,8]{..}
    copy(..)`` -> ``copy.130_s32_1048576_10_8``."""
    m = re.match(r"%?([\w.\-]+)(?: = (\w+\[[\d,]*\]))?", raw)
    if not m:
        return raw[:64]
    shape = re.sub(r"[\[\],]+", "_", m.group(2)).strip("_") if m.group(2) else ""
    return (m.group(1) + ("_" + shape if shape else ""))[:64]


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _split_gap(a: int, b: int, notes, note_ends) -> Dict[str, int]:
    """The idle gap ``[a, b)`` shared out: each instant goes to the
    innermost host annotation that covers it (the one that started last),
    and what no annotation covers to ``idle__no_annotation``."""
    live = []                                   # (start, end, name)
    for name, spans in notes.items():
        i = bisect.bisect_right(note_ends[name], a)
        while i < len(spans) and spans[i][0] < b:
            live.append((max(a, spans[i][0]), min(b, spans[i][1]), name))
            i += 1
    out: Dict[str, int] = {}
    cuts = sorted({a, b, *(x for x, _, _ in live), *(y for _, y, _ in live)})
    for x, y in zip(cuts, cuts[1:]):
        cover = [(s, n) for s, e, n in live if s <= x and y <= e]
        name = ("idle_under_" + max(cover)[1]) if cover \
            else "idle__no_annotation"
        out[name] = out.get(name, 0) + (y - x)
    return out


def reduce(planes: Sequence[Plane], top: int = 10) -> dict:
    """``busy_s`` (mean over device planes), ``window_s`` (first device
    operation to the end of the last, so ``busy_s`` is inside it),
    per-operation seconds (summed over devices, ``top`` largest), idle
    gaps by annotation, and ``marks``: each annotation's starts with the
    ``n`` it carried. A trace in which no operation ran on a device is an
    error."""
    devices = [p for p in planes if str(p["name"]).startswith(DEVICE_PLANE)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    notes: Dict[str, List[Tuple[int, int]]] = {}
    marks: Dict[str, List[Tuple[int, int]]] = {}
    for p in planes:
        if p in devices:
            continue
        for line in p["lines"]:
            for name, start, dur, *n in line["events"]:
                notes.setdefault(name, []).append((start, start + dur))
                marks.setdefault(name, []).append((start, n[0] if n else 1))
    notes = {k: _union(v) for k, v in notes.items()}
    note_ends = {k: [b for _, b in v] for k, v in notes.items()}

    ops: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    busy: List[List[Tuple[int, int]]] = []      # per device, merged
    for p in devices:
        spans = []
        for line in p["lines"]:
            for name, start, dur, *_ in line["events"]:
                name = op_name(name)
                ops[name] = ops.get(name, 0) + dur
                spans.append((start, start + dur))
        merged = _union(spans)
        busy.append(merged)
        for (_, a), (b, _) in zip(merged, merged[1:]):
            for name, ns in _split_gap(a, b, notes, note_ends).items():
                gaps[name] = gaps.get(name, 0) + ns
    busy_s = sum(b - a for m in busy for a, b in m) / 1e9 / len(devices)
    if busy_s <= 0:
        raise ValueError("no operation ran on the device in the trace")
    first = min(m[0][0] for m in busy if m)
    last = max(m[-1][1] for m in busy if m)

    def ranked(d: Dict[str, int]) -> List[list]:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": (last - first) / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps),
            "n_devices": len(devices),
            "marks": {k: sorted(v) for k, v in marks.items()},
            "busy_intervals": busy}


def busy_between(reduced: dict, a_ns: int, b_ns: int) -> float:
    """Seconds in which an operation ran on the device inside
    ``[a_ns, b_ns)``, mean over the devices."""
    total = sum(max(0, min(b, b_ns) - max(a, a_ns))
                for merged in reduced["busy_intervals"] for a, b in merged)
    return total / 1e9 / reduced["n_devices"]


def cycles(reduced: dict, annotation: str):
    """Whole cycles of an annotated call: from the first start of
    ``annotation`` in the trace to the last, the ``n`` of each cycle begun
    in between and the device's busy seconds there. Counting from start
    to start keeps the share of a cycle that is cut off at either end of
    the trace out of both sides of the division."""
    marks = reduced["marks"].get(annotation, [])
    if len(marks) < 2:
        return None
    ns = [n for _, n in marks[:-1]]
    return ns, busy_between(reduced, marks[0][0], marks[-1][0])


def read(trace_dir: str) -> dict:
    return reduce(load_xplane(find_xplane(trace_dir)))
