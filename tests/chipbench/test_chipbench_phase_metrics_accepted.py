"""``test_chipbench_phase_metrics.py`` (PR 26) against the ACCEPTED view of
``BENCHMARK.json``.

That file pins the benchmark to its shape at PR 26 while it is imported:
``TOKEN, BATCH, REQ = (w["name"] for w in BENCH["workloads"])`` takes
exactly three cells, and ``names == ACCEPTED + REQ_METRICS`` exactly its
per-layer entries. A later PR may only append (a fourth cell, two
metrics: PR 29), and may not edit that file, so from the first appended
entry on it fails to collect. Until a ``benchmark`` PR relaxes those two
lines (``PERF.md`` §7), its tests run from here, unchanged, with
``BENCHMARK.json`` read as what it held for the three accepted cells: the
first three ``workloads`` and the per-layer entries that list one of
them. Everything else they touch — ``spec.resolve`` on the real file,
the readers, the rehearsals — is the repo as it stands.
"""

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCHMARK = HERE.parents[1] / "BENCHMARK.json"


def _accepted_view() -> str:
    bench = json.loads(BENCHMARK.read_text())
    bench["workloads"] = bench["workloads"][:3]
    cells = {w["name"] for w in bench["workloads"]}
    bench["per_layer"] = [
        {**m, "workloads": [c for c in m["workloads"] if c in cells]}
        for m in bench["per_layer"] if cells & set(m["workloads"])]
    return json.dumps(bench)


def _load_against(view: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_phase_metrics_accepted",
        HERE / "test_chipbench_phase_metrics.py")
    module = importlib.util.module_from_spec(spec)
    read_text = Path.read_text

    def accepted(self, *args, **kwargs):
        if self == BENCHMARK:
            return view
        return read_text(self, *args, **kwargs)
    Path.read_text = accepted
    try:
        spec.loader.exec_module(module)
    finally:
        Path.read_text = read_text
    return module


# its tests, fixtures and helpers, under this module's name
globals().update({name: value for name, value in
                  vars(_load_against(_accepted_view())).items()
                  if not name.startswith("__")})
