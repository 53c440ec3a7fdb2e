"""What the harness and a deployment builder say to each other.

A builder (``chipbench/deployments/*.py``, found by the name in the
configuration file) is called with a :class:`Context` and returns an
object with five methods, called in this order::

    set_up()                 build the deployment from the seed, warm the
                             cell's own shapes; everything before the
                             window
    run_window(tracer)       measure for ctx.seconds -> Measured
    release()                stop the program, free its device state
    check()                  run the plain reference -> {name: (value, limit)}
    close()                  stop whatever is still running (always called)

``check`` runs after the window has closed, after the harness has read
the device's memory peak and after ``release``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from chipbench import spec


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    workdir: Path           # scratch inside the checkout, removed afterwards


@dataclasses.dataclass
class Span:
    """One timed call recorded from the benchmark's files: seconds on
    ``time.monotonic``'s clock relative to the window start, and how many
    requests or events it carried."""
    start_s: float
    end_s: float
    n: int = 1


@dataclasses.dataclass
class Measured:
    t0: float                               # window start, time.monotonic()
    window_s: float                         # measured window, as run
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    spans: Dict[str, List[Span]] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    samples: Dict[str, Any] = dataclasses.field(default_factory=dict)


Checks = Dict[str, Tuple[float, float]]     # name -> (value, limit)


class Tracer:
    """Traces ``length_s`` of the window from ``start_s`` on with the JAX
    profiler, from a thread of its own: starting and stopping a trace
    takes seconds, which the thread that drives the window must not lose.
    A builder calls :meth:`arm` once it knows the window's start; the
    harness calls :meth:`finish`. Nothing happens in an untraced run."""

    #: the profiler's host tracer level: 2 keeps the ``TraceAnnotation``s
    #: that name the idle gaps and count the cycles
    HOST_TRACER_LEVEL = 2

    def __init__(self, out_dir: Optional[Path], start_s: float,
                 length_s: float) -> None:
        self.out_dir, self.start_s, self.length_s = out_dir, start_s, length_s
        self._thread: Optional[threading.Thread] = None

    def arm(self, t0: float) -> None:
        if self.out_dir is not None and self._thread is None:
            self._thread = threading.Thread(
                target=self._trace, args=(t0,), name="chipbench-tracer",
                daemon=True)
            self._thread.start()

    def _trace(self, t0: float) -> None:
        import jax
        time.sleep(max(0.0, t0 + self.start_s - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = self.HOST_TRACER_LEVEL
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        time.sleep(self.length_s)
        jax.profiler.stop_trace()

    def finish(self) -> None:
        if self._thread is not None:
            self._thread.join()
