"""TUNED.json: the per-hardware pinned-config artifact.

A sweep (:mod:`sentinel_tpu.tune.runner`, ``python -m
sentinel_tpu.tune``) ends by writing one small JSON document — the
winning knob values, the hardware fingerprint they were measured on,
and the scores that justify them — so every later deployment on the
same hardware starts pre-tuned: point ``SENTINEL_TUNED_CONFIG`` at the
artifact and ``Sentinel`` / ``Sentinel.frontend()`` / the benches pick
the knobs up at startup.

Fingerprint (:func:`fingerprint`): backend name, device kind, visible
device count, host CPU cores, and the serving mesh layout
(``parallel/local_shard.mesh_topology()`` — mesh device count, axis,
sharded-or-not). A config tuned for an 8-device row-sharded engine is
NOT a config for a 1-device engine. Deliberately EXCLUDED:
``rows_per_device`` and anything else derived from the
``SentinelConfig`` geometry — geometry is configuration, not hardware,
and folding it in would mean a sweep run at bench geometry could never
warm-start a production engine on the same chips.

Mismatch semantics (documented fallback): :func:`overrides_for` returns
``None`` when the stored fingerprint differs from the live one in ANY
field — the engine then runs on defaults exactly as if
``SENTINEL_TUNED_CONFIG`` were unset, logs the first differing field
via RecordLog, and ticks ``tune.fingerprint_fallback`` so the silent
half of the failure mode (stale artifact after a hardware change) is
observable. A matching load ticks ``tune.config_loaded``.

Precedence (the per-knob override path, docs/OPERATIONS.md
"Autotuning"): explicit env always beats the artifact — a knob whose
``SENTINEL_*`` variable is set in the environment keeps the env value;
the artifact only fills knobs the operator left unset.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

from sentinel_tpu.tune import knobs as knobs_mod

SCHEMA = "sentinel_tune/1"
TUNED_CONFIG_ENV = "SENTINEL_TUNED_CONFIG"


def fingerprint(spec=None, mesh=None) -> Dict:
    """The live hardware/layout fingerprint (see module docstring)."""
    import jax
    dev = jax.devices()[0]
    if mesh is None:
        mesh_block = {"n_devices": 1, "axis": None, "sharded": False}
    elif spec is not None:
        from sentinel_tpu.parallel.local_shard import mesh_topology
        topo = mesh_topology(spec, mesh)
        mesh_block = {k: topo.get(k)
                      for k in ("n_devices", "axis", "sharded")}
    else:
        from sentinel_tpu.parallel.local_shard import MESH_AXIS
        axis = (MESH_AXIS if MESH_AXIS in mesh.axis_names
                else mesh.axis_names[0])
        mesh_block = {"n_devices": int(mesh.shape[axis]), "axis": axis,
                      "sharded": True}
    return {
        "backend": jax.default_backend(),
        "device_kind": str(dev.device_kind),
        "n_devices_visible": int(jax.device_count()),
        "host_cores": int(os.cpu_count() or 1),
        "mesh": mesh_block,
    }


def fingerprints_match(stored: Dict, live: Dict) -> Tuple[bool, str]:
    """(match, first differing field) — exact equality field by field."""
    for k in ("backend", "device_kind", "n_devices_visible", "host_cores"):
        if stored.get(k) != live.get(k):
            return False, f"{k}: {stored.get(k)!r} != {live.get(k)!r}"
    sm, lm = stored.get("mesh") or {}, live.get("mesh") or {}
    for k in ("n_devices", "axis", "sharded"):
        if sm.get(k) != lm.get(k):
            return False, f"mesh.{k}: {sm.get(k)!r} != {lm.get(k)!r}"
    return True, ""


def save_tuned(path: str, *, fingerprint: Dict, knob_values: Dict,
               score: Dict, baseline: Dict, slo_p99_ms: float,
               workload: Dict, trials: int, parity_checks: int) -> Dict:
    """Write the artifact (atomically: temp + rename) and return it."""
    doc = {
        "schema": SCHEMA,
        "fingerprint": fingerprint,
        "knobs": knobs_mod.coerce_config(knob_values),
        "score": score,
        "baseline": baseline,
        "slo_p99_ms": float(slo_p99_ms),
        "workload": workload,
        "trials": int(trials),
        "parity_checks": int(parity_checks),
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1)
    os.replace(tmp, path)
    return doc


def load_tuned(path: str) -> Dict:
    """Read + schema/knob-validate an artifact (raises on malformation —
    a corrupt artifact must fail loudly at the tool layer; the startup
    path below downgrades every failure to a logged fallback)."""
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("schema") != SCHEMA:
        raise ValueError(f"{path}: unknown schema {doc.get('schema')!r} "
                         f"(want {SCHEMA})")
    doc["knobs"] = knobs_mod.coerce_config(doc.get("knobs") or {})
    return doc


def overrides_for(doc: Dict, live_fp: Dict) -> Optional[Dict]:
    """Artifact knobs when the fingerprint matches, else ``None``."""
    ok, _why = fingerprints_match(doc.get("fingerprint") or {}, live_fp)
    return dict(doc["knobs"]) if ok else None


def resolve_startup(spec=None, mesh=None, environ=None):
    """Everything ``Sentinel.__init__`` needs, in one call that must
    never raise: (overrides, events).

    * ``overrides`` — knob env → value from a fingerprint-matching
      artifact, MINUS any knob explicitly set in the environment (env
      wins per-knob); ``{}`` when ``SENTINEL_TUNED_CONFIG`` is unset,
      unreadable, or mismatched.
    * ``events`` — ``(counter_key, message)`` pairs for the caller to
      route to RecordLog + obs counters once telemetry exists (the
      knob-validation warnings ride along here too).
    """
    from sentinel_tpu.obs import counters as obs_keys
    env = os.environ if environ is None else environ
    events = [(obs_keys.TUNE_KNOB_REJECTED, w)
              for w in knobs_mod.validate_environ(env)]
    path = env.get(TUNED_CONFIG_ENV, "")
    if not path:
        return {}, events
    try:
        doc = load_tuned(path)
    except (OSError, ValueError) as e:
        events.append((obs_keys.TUNE_FALLBACK,
                       f"tuned config {path}: unreadable ({e}); "
                       f"serving on defaults"))
        return {}, events
    live = fingerprint(spec, mesh)
    ok, why = fingerprints_match(doc.get("fingerprint") or {}, live)
    if not ok:
        events.append((obs_keys.TUNE_FALLBACK,
                       f"tuned config {path}: fingerprint mismatch "
                       f"({why}); serving on defaults"))
        return {}, events
    overrides = {e: v for e, v in doc["knobs"].items() if e not in env}
    events.append((obs_keys.TUNE_LOADED,
                   f"tuned config {path}: loaded "
                   f"{len(overrides)}/{len(doc['knobs'])} knobs "
                   f"(env-set knobs keep their env values)"))
    return overrides, events


def provenance(spec=None, mesh=None, environ=None) -> Dict:
    """The bench-artifact provenance block (round-11 satellite): did a
    tuned config apply, from where, under which fingerprint, and which
    per-knob values — so a result is reproducible without the machine
    it ran on."""
    env = os.environ if environ is None else environ
    path = env.get(TUNED_CONFIG_ENV, "")
    block: Dict = {"tuned": False, "artifact": path or None}
    if not path:
        return block
    try:
        doc = load_tuned(path)
    except (OSError, ValueError) as e:
        block["error"] = str(e)
        return block
    live = fingerprint(spec, mesh)
    ok, why = fingerprints_match(doc.get("fingerprint") or {}, live)
    if not ok:
        block["fingerprint_mismatch"] = why
        return block
    block.update(tuned=True, fingerprint=doc["fingerprint"],
                 knobs=doc["knobs"])
    return block
