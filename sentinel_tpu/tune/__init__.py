"""Measurement-driven serving autotuner (round 11).

Five serving-perf rounds exploded the knob space —
``SENTINEL_PIPELINE_DEPTH``, the ``SENTINEL_FRONTEND_*`` batcher set,
donation/staging, the sort-free switch and its table sizing — and
closing the 50M decisions/s bar on real silicon still meant hand-
sweeping them. This package makes
the engine tune itself (ROADMAP item 1's second half):

* :mod:`~sentinel_tpu.tune.knobs` — the typed knob registry
  (type / clamp / default / runtime-vs-trace scope) + the startup
  ``SENTINEL_*`` environment validator;
* :mod:`~sentinel_tpu.tune.search` — the PURE coordinate-descent +
  successive-halving policy core (virtual-clock-driven, injected
  trials, unit-tested on CPU CI);
* :mod:`~sentinel_tpu.tune.runner` — real trials: seeded workload-zoo
  episodes through the full serving path, scored from obs plumbing,
  with a verdict bit-parity spot-check per trial;
* :mod:`~sentinel_tpu.tune.artifact` — ``TUNED.json``: the
  hardware-fingerprinted pinned config ``SENTINEL_TUNED_CONFIG`` loads
  at ``Sentinel`` startup (fingerprint mismatch → defaults, logged).

Operator entry points: ``python -m sentinel_tpu.tune`` runs a sweep;
docs/OPERATIONS.md "Autotuning (round 11)" is the runbook.
"""

from sentinel_tpu.tune.artifact import (           # noqa: F401
    TUNED_CONFIG_ENV, fingerprint, fingerprints_match, load_tuned,
    overrides_for, provenance, resolve_startup, save_tuned,
)
from sentinel_tpu.tune.knobs import (              # noqa: F401
    FRONTEND_KWARG_ENVS, KNOB_BY_ENV, KNOBS, KnobSpec, coerce_config,
    env_overrides, env_strings, known_envs, trace_knobs, validate_environ,
)
from sentinel_tpu.tune.runner import (             # noqa: F401
    ServingTrialRunner, build_space, run_sweep,
)
from sentinel_tpu.tune.search import (             # noqa: F401
    SearchResult, TrialOutcome, TuneSearch, score_outcome,
)

__all__ = [
    "TUNED_CONFIG_ENV", "KNOBS", "KNOB_BY_ENV", "KnobSpec",
    "FRONTEND_KWARG_ENVS", "TuneSearch", "TrialOutcome", "SearchResult",
    "score_outcome", "fingerprint", "fingerprints_match", "save_tuned",
    "load_tuned", "overrides_for", "provenance", "resolve_startup",
    "validate_environ", "known_envs", "coerce_config", "trace_knobs",
    "env_strings", "env_overrides", "ServingTrialRunner", "build_space",
    "run_sweep",
]
