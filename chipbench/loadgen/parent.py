"""The parent's end of the load-generator child (see ``tcp_child.py``)."""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

import numpy as np

from chipbench import CHECKOUT


class ChildLoad:
    def __init__(self, module: str, params: dict) -> None:
        env = dict(os.environ)
        # the child computes on the host and must never reach for the chip
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(CHECKOUT) + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module, json.dumps(params)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=str(CHECKOUT),
            env=env)
        self.scheduled: Optional[int] = None

    def _line(self, want: str) -> str:
        line = self.proc.stdout.readline().decode()
        if not line.startswith(want):
            self.kill()
            raise RuntimeError(f"load generator said {line!r}, not {want!r}")
        return line

    def _tell(self, text: str) -> None:
        self.proc.stdin.write((text + "\n").encode())
        self.proc.stdin.flush()

    def wait_scheduled(self) -> int:
        self.scheduled = int(self._line("scheduled").split()[1])
        return self.scheduled

    def connect(self, port: int) -> None:
        self._tell(f"connect {port}")
        self._line("ready")

    def go(self, lead_s: float = 0.25) -> float:
        """Start the schedule ``lead_s`` from now → T0 on CLOCK_MONOTONIC."""
        t0 = time.monotonic() + lead_s
        self._tell(f"go {t0!r}")
        return t0

    def collect(self, timeout_s: float) -> Dict[str, np.ndarray]:
        try:
            blob, _ = self.proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            raise RuntimeError("load generator did not finish")
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"load generator exited {self.proc.returncode}")
        with np.load(io.BytesIO(blob)) as z:
            return {k: z[k] for k in z.files}

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
