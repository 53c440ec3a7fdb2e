"""Readers of the tiering layer: the landing time a batch, the hot-hit
share from the program's counters, and ``migrate_roofline.tier`` — the
bytes a batch's demotions, invalidations and promotions HAVE to move,
from the configuration's shapes alone, at the chip's memory bandwidth,
against the device time of the three migration programs in the trace.

The harness reduces a trace to its operations (``trace.reduce`` reads the
``XLA Ops`` line) and removes it before the readers run. A program's
device time is on another line of the same device plane, ``XLA Modules``,
whose events are named ``jit_<function>(<fingerprint>)``; the builder
reads it while the trace is still there (:func:`program_seconds`) and
leaves the seconds under ``Measured.samples["program_device_s"]``."""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

from chipbench import trace
from chipbench.readers.common import Facts

I32 = F32 = 4
MODULES_LINE = "XLA Modules"


def span_sum_ms_per_batch(metric: dict, facts: Facts) -> Optional[float]:
    """All of span ``span`` inside the window, over the window's batches
    (a span that need not come once a batch: landings run on a thread of
    their own, several records at a time or none)."""
    spans = facts.spans(metric["span"])
    batches = facts.measured.counters.get("batches")
    if not spans or not batches:
        return None
    return sum(s.end_s - s.start_s for s in spans) * 1e3 / batches


def counter_share(metric: dict, facts: Facts) -> Optional[float]:
    """``hit / (hit + miss)`` of two of the program's counters over the
    window, in per cent."""
    counters = facts.measured.counters
    hit, miss = counters.get(metric["hit"]), counters.get(metric["miss"])
    if hit is None or miss is None or hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)


# -- migrate_roofline.tier ------------------------------------------------

def row_payload_bytes(cfg: dict) -> int:
    """What one name owns on the device, from the configuration's shapes:
    per statistics window, for each bucket its event counters, its stamp,
    its RT sum and its least RT; the thread gauge; the booking ring of
    ``window_buckets + 1`` slots (a count and a target window each); the
    cumulative RT histogram."""
    def window(buckets: int) -> int:
        return buckets * (cfg["events"] * I32 + I32 + F32 + I32)
    minute = window(cfg["minute_buckets"]) if cfg["minute_ring"] else 0
    booking = (cfg["window_buckets"] + 1) * (F32 + I32)
    return (window(cfg["window_buckets"]) + minute + I32 + booking
            + cfg["hist_buckets"] * I32)


def row_reset_bytes(cfg: dict) -> int:
    """What forgetting a row has to write: every bucket's stamp, the
    thread gauge, the booking ring, the histogram. The counters stay (the
    next write resets a bucket whose stamp is stale)."""
    buckets = cfg["window_buckets"] + (
        cfg["minute_buckets"] if cfg["minute_ring"] else 0)
    return (buckets * I32 + I32 + (cfg["window_buckets"] + 1) * (F32 + I32)
            + cfg["hist_buckets"] * I32)


def migrate_min_bytes(demoted: int, promoted: int, cfg: dict) -> int:
    """A demoted row is read once and written once (into the payload that
    goes to the host) and then reset; a promoted row's payload is read
    once and written once into its row."""
    payload = row_payload_bytes(cfg)
    return (demoted * (2 * payload + row_reset_bytes(cfg))
            + promoted * 2 * payload)


def module_events(planes: Iterable) -> List[Sequence]:
    """(name, duration_ns) of every event on the ``XLA Modules`` line of
    every device plane of a ``jax.profiler.ProfileData``."""
    out = []
    for plane in planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out.extend((ev.name, int(ev.duration_ns))
                           for ev in line.events)
    return out


def seconds_by_program(events: Iterable[Sequence],
                       programs: Sequence[str]) -> Dict[str, float]:
    """Device seconds of each of ``programs`` (a module event is named
    ``<program>(<fingerprint>)``), summed over the devices. A program
    the trace does not hold is left out."""
    out: Dict[str, float] = {}
    for name, duration_ns in events:
        program = name.split("(", 1)[0]
        if program in programs:
            out[program] = out.get(program, 0.0) + duration_ns / 1e9
    return out


def program_seconds(trace_dir: str,
                    programs: Sequence[str]) -> Optional[Dict[str, float]]:
    """:func:`seconds_by_program` of the trace under ``trace_dir``; None
    where there is no trace to read (an untraced run, a rehearsal)."""
    try:
        path = trace.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    return seconds_by_program(
        module_events(ProfileData.from_file(path).planes), programs)


def migrate_roofline(metric: dict, facts: Facts) -> Optional[float]:
    """Rows moved come from the trace's own annotations (``n`` of each
    ``tier.demote`` / ``tier.promote``), the programs' device time from
    the same trace: both sides of the division cover the same seconds."""
    seconds = facts.measured.samples.get("program_device_s")
    if not facts.trace or not seconds:
        return None
    busy_s = sum(seconds.get(p, 0.0) for p in metric["programs"])
    marks = facts.trace["marks"]
    demoted = sum(n for _, n in marks.get(metric["demote"], ()))
    promoted = sum(n for _, n in marks.get(metric["promote"], ()))
    if busy_s <= 0 or demoted + promoted <= 0:
        return None
    least_s = migrate_min_bytes(demoted, promoted, facts.cell.config) \
        / facts.peaks["hbm_bytes_per_s"]
    # busy_s is summed over the devices, as their bandwidths add up
    return 100.0 * least_s / busy_s


READERS = {"span_sum_ms_per_batch": span_sum_ms_per_batch,
           "counter_share": counter_share,
           "migrate_roofline": migrate_roofline}
