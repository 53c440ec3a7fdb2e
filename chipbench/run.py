"""One cell, once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the deployment from the seed, warms that cell's shapes (set-up),
measures for ``--seconds``, checks every answer against the plain
reference, and prints one JSON object as the last line of stdout. Fails,
and prints no result, when JAX finds no TPU, fewer chips than the cell
asks for, or a device that ``peaks.json`` does not know.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()        # set-up is counted from here

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
from pathlib import Path             # noqa: E402

if __package__ in (None, ""):       # `python3 chipbench/run.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import CHECKOUT, ROOT, registry, spec    # noqa: E402
from chipbench.cell import Context, Tracer              # noqa: E402

#: of a traced run's window, the part the profiler records
TRACE_START_S, TRACE_SECONDS = 2.0, 6.0


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device without peaks."""


def device_row(chips: int, require_chip: bool) -> dict:
    """The devices as JAX reports them, checked against the cell and the
    table of peaks. ``require_chip=False`` is for the tests, which drive
    the rest of a run on the CPU."""
    import jax
    devs = jax.devices()
    with open(ROOT / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    row = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_chip:
        if row["platform"] != "tpu":
            raise NoChip(f"JAX found platform {row['platform']!r}, not a TPU")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
        if row["kind"] not in peaks:
            raise NoChip(f"no peaks for device kind {row['kind']!r}")
    row["peaks"] = peaks.get(row["kind"], next(iter(peaks.values())))
    return row


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return peak


def _beside_limits(checks: dict) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             checkout: Path = CHECKOUT, require_chip: bool = True,
             t_process: float | None = None, sabotage=None,
             traffic: dict | None = None, control: bool = False,
             read_trace=None, keep: bool = False) -> dict:
    """The whole of a run → the result object. ``sabotage(cell_object)``
    is called when set-up ends: the tests break the timed path underneath
    with it, the rate sweep plants its stall; ``traffic``
    overrides parameters of the mix (the rate sweep); ``control`` adds the
    control's readings under ``"control"``; ``read_trace`` stands in for
    ``trace.read`` where there is no chip to trace; ``keep`` hands the
    window's raw samples and spans back under ``"_measured"``."""
    t_process = T_PROCESS if t_process is None else t_process
    cell = spec.resolve(checkout, workload)
    cell.traffic.update(traffic or {})
    device = device_row(cell.chips, require_chip)
    peaks = device.pop("peaks")
    if require_chip:
        enable_compile_cache()
    from chipbench.compile_meter import CompileMeter
    meter = CompileMeter()

    workdir = checkout / ".chipbench_tmp" / f"{workload}.{seed}.{int(trace)}"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  workdir=workdir)
    tracer = Tracer(workdir / "trace" if trace else None,
                    min(TRACE_START_S, 0.2 * seconds),
                    min(TRACE_SECONDS, 0.5 * seconds))
    obj = registry.find("deployments", cell.config["builder"])(ctx)
    try:
        obj.set_up()
        if sabotage is not None:
            sabotage(obj)
        import jax
        jax.config.update("jax_log_compiles", True)     # names on stderr
        measured = obj.run_window(tracer)
        jax.config.update("jax_log_compiles", False)
        tracer.finish()
        # the builder's warm phase runs inside run_window, before t0
        compiled_inside = sum(
            0.0 <= t - measured.t0 <= measured.window_s for t in meter.at)
        compiled_before = sum(t < measured.t0 for t in meter.at)
        setup_s = measured.t0 - t_process
        print(f"compilations inside the window: {compiled_inside} "
              f"(before it: {compiled_before}, of which the cache served "
              f"{meter.cache_hits})"
              + "".join(f"; one at {t - measured.t0:.2f} s"
                        for t in meter.at if t >= measured.t0),
              file=sys.stderr)
        device["memory_peak_bytes"] = memory_peak_bytes()
        obj.release()
        reduced = None
        if trace:
            if read_trace is None:
                from chipbench.trace import read as read_trace
            reduced = read_trace(str(workdir / "trace"))
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        checks = obj.check()
        controls = obj.control() if control else None
    finally:
        obj.close()
        shutil.rmtree(workdir, ignore_errors=True)

    metrics = {}
    if trace:
        from chipbench.readers.common import Facts
        facts = Facts(measured, reduced, cell, peaks)
        readers = registry.load("readers")
        for m in cell.per_layer:
            value = readers[m["reader"]](m, facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(measured.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": measured.attempted, "failed": measured.failed,
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compilations_in_window"] = compiled_inside
    if controls is not None:
        result["control"] = _beside_limits(controls)
    result["checks"] = _beside_limits(checks)       # last, as the driver reads
    if keep:
        result["_measured"] = measured
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
