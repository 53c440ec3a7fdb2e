"""The builder's tools: runs of several windows in ONE process, so that
imports and backend start-up are paid once per chip call.

    python3 -m chipbench.tools sweep --workload W --rates 4000,8000 --seconds 6
    python3 -m chipbench.tools prove --workload W --seeds 11,12,13 --seconds 6
    python3 -m chipbench.tools sets --workload W --seeds 21,22,23

``sweep`` offers each rate once and prints how the backlog behaved;
``prove`` runs the cell and its control on each seed and prints the
numbers compared. Neither is run by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import numpy as np

from chipbench import run, stats


def plant_stall(obj, after_s: float, stall_ms: float) -> None:
    """One stall of the load generator, ``after_s`` from now: nothing is
    sent or read for ``stall_ms``, then the backlog goes out at once —
    what a host hiccup does to the server. A generator in a child process
    is stopped and continued; one in this process (it runs in the main
    thread, as the alarm's handler does) sleeps. The stall is the sweep's
    alone: the timed generators know nothing of it."""
    child = getattr(obj, "child", None)

    def stall(*_):
        if child is not None:
            os.kill(child.proc.pid, signal.SIGSTOP)
        time.sleep(stall_ms / 1e3)
        if child is not None:
            os.kill(child.proc.pid, signal.SIGCONT)
    signal.signal(signal.SIGALRM, stall)
    signal.setitimer(signal.ITIMER_REAL, after_s)


def sweep(args) -> None:
    """Each rate once, in one process: how the backlog behaved (medians of
    the two halves and of the last ``--tail`` seconds) and the engine
    call's time by padded batch size. ``--stall-at S --stall-ms MS``
    plants one stall of the generator about S seconds into the window."""
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = {"rate_per_s": rate}
        # the hook is called when set-up ends: the warm phase and the
        # generator's lead still lie before the window
        lead = 0.35 + _warm_seconds(args.workload, traffic)
        r = run.run_cell(
            args.workload, args.seed, args.seconds, False,
            t_process=time.monotonic(), keep=True, traffic=traffic,
            sabotage=(lambda obj: plant_stall(
                obj, lead + args.stall_at, args.stall_ms))
            if args.stall_ms > 0 else None)
        m = r.pop("_measured")
        lat, due = m.samples["grant_ms"], m.samples["due_s"]
        half = len(lat) // 2
        steps = (m.spans.get("bench.token_step")
                 or m.spans.get("bench.entry", []))
        ms = np.array([(s.end_s - s.start_s) * 1e3 for s in steps])
        size = np.array([s.n for s in steps])
        pad = np.maximum(8, 2 ** np.ceil(np.log2(np.maximum(size, 1)))
                         ).astype(int)
        print(json.dumps({
            "rate_per_s": rate, "attempted": r["attempted"],
            "failed": r["failed"], "correct": r["correct"],
            "p50_ms": stats.percentile_exact(lat, 50),
            "p99_ms": stats.percentile_exact(lat, 99),
            "p50_first_half_ms": float(np.median(lat[:half])),
            "p50_second_half_ms": float(np.median(lat[half:])),
            "p50_last_ms": float(np.median(
                lat[due >= args.seconds - args.tail])),
            "late_p99_ms": float(np.percentile(m.samples["late_ms"], 99)),
            "setup_s": r["metrics"]["setup_s"]["value"],
            "steps": len(steps),
            "batch_mean": float(size.mean()) if len(steps) else None,
            "batch_max": int(size.max()) if len(steps) else None,
            "step_p50_ms": float(np.median(ms)) if len(steps) else None,
            "step_ms_by_pad": {int(k): [int((pad == k).sum()),
                                        round(float(np.median(ms[pad == k])), 2)]
                               for k in np.unique(pad)},
        }), flush=True)


def _warm_seconds(workload: str, traffic: dict) -> float:
    """The mix's warm phase, which lies between set-up and the window."""
    from chipbench import CHECKOUT, spec
    mix = {**spec.resolve(CHECKOUT, workload).traffic, **traffic}
    return float(mix.get("warm_seconds", 0.0))


def prove(args) -> None:
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.monotonic()
        r = run.run_cell(args.workload, seed, args.seconds, bool(args.trace),
                         t_process=t, control=True)
        print(json.dumps({"seed": seed, **r}), flush=True)


def sets(args) -> None:
    """One fresh process per run, as the driver makes them: the command
    of ``BENCHMARK.json`` with each seed in turn, every result line kept."""
    import subprocess
    from chipbench import CHECKOUT, spec
    bench = spec.load_benchmark(CHECKOUT)
    seconds = args.seconds or bench["run_seconds"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        cmd = bench["command"] + [
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(args.trace)]
        t = time.monotonic()
        p = subprocess.run(cmd, cwd=str(CHECKOUT), capture_output=True,
                           text=True)
        lines = p.stdout.strip().splitlines()
        print(json.dumps({"seed": seed, "rc": p.returncode,
                          "wall_s": time.monotonic() - t,
                          "stderr_tail": p.stderr.strip().splitlines()[-8:],
                          "result": json.loads(lines[-1]) if lines else None}),
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tool", choices=("sweep", "prove", "sets"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=2147484001)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tail", type=float, default=3.0,
                    help="sweep: the last seconds whose median is shown")
    ap.add_argument("--stall-at", type=float, default=1.0,
                    help="sweep: seconds into the window of the planted stall")
    ap.add_argument("--stall-ms", type=float, default=0.0,
                    help="sweep: length of the planted stall (0: none)")
    args = ap.parse_args()
    {"sweep": sweep, "prove": prove, "sets": sets}[args.tool](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
