"""``BENCHMARK.json`` and the data files it names, resolved for one cell."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # chipbench/configs/<config>.json, as run
    traffic: dict           # chipbench/traffic/<mix>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]   # entries merged with chipbench/metrics/<m>.json


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(checkout: Path) -> dict:
    return _json(checkout / "BENCHMARK.json")


def resolve(checkout: Path, workload: str) -> Cell:
    """The cell ``workload`` with its configuration, traffic mix and
    metrics. Raises ``KeyError``/``FileNotFoundError`` on anything that
    does not resolve — there is no default cell."""
    bench = load_benchmark(checkout)
    pkg = Path(bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(checkout / configs[w["config"]]["file"])
    traffic = _json(checkout / pkg / "traffic" / f"{w['traffic']}.json")

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer: List[dict] = []
    for m in bench["per_layer"]:
        if not reports(m):
            continue
        if m["moves"] not in e2e_names:
            raise ValueError(
                f"{m['name']} moves {m['moves']}, which {workload} "
                "does not report")
        layer.append({**_json(checkout / pkg / "metrics" / f"{m['name']}.json"),
                      **m})
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)
