"""Idle time of the device under one of the program's phases, per call.

``RuntimeObs.phase(name)`` writes each phase into the profiler's trace as
the annotation ``sentinel_tpu.<name>``, on the device's clock; the trace
reduction shares every idle gap out to the innermost annotation over it
and keeps each annotation's starts. The seconds the device idled under a
phase over the calls of it are exact for a phase during which the device
does nothing (routing, placing, gathering, responding) and are the phase
less the device's work under it otherwise."""

from __future__ import annotations

from typing import Optional

from chipbench.readers.common import Facts


def idle_ms_per_call(metric: dict, facts: Facts) -> Optional[float]:
    """``idle_under_<span>`` seconds of the trace over the calls of
    ``span`` it holds, in ms. ``None`` where the trace holds no such
    annotation (a program without the phase) or its gap is not among the
    gaps the reduction kept."""
    reduced = facts.trace
    if not reduced:
        return None
    calls = len(reduced["marks"].get(metric["span"], ()))
    seconds = dict(reduced["idle_gaps"]).get("idle_under_" + metric["span"])
    if not calls or seconds is None:
        return None
    return seconds * 1e3 / calls


READERS = {"idle_ms_per_call": idle_ms_per_call}
