"""Substrate telemetry (DESIGN.md §8) — three observability tiers.

1. **In-step counters** (:mod:`repro.obs.counters`): a jit-pure
   :class:`Telemetry` pytree threaded through the batched step's carry
   behind the static ``make_runner(telemetry=True)`` knob — off
   compiles to nothing, on adds zero traces.
2. **Flight recorder** (:mod:`repro.obs.trace`): host-side
   :class:`TraceSession` emitting Chrome/Perfetto JSON per macro-step,
   plus the serving engine's structured-event converter; and the named
   phases (:mod:`repro.obs.phases`): ``step:*`` scopes on the step's
   device ops and ``setup:*`` spans on the host.
3. **Provenance** (:mod:`repro.obs.manifest`): the ``RunManifest`` dict
   stamped onto benchmark rows so trend diffs are attributable.

``trace`` imports the simulator lazily — importing :mod:`repro.obs`
from inside ``array_sim`` is cycle-free by construction.
"""

from .counters import (  # noqa: F401
    N_BINS,
    Telemetry,
    count,
    hist,
    init_telemetry,
    lane_slice,
    log2_bin,
    summarize,
)
from .manifest import collect as collect_manifest, spec_hash  # noqa: F401
from .phases import (  # noqa: F401
    Record,
    phase,
    phase_of,
    scope,
    span,
)

__all__ = [
    "N_BINS", "Telemetry", "count", "hist", "init_telemetry",
    "lane_slice", "log2_bin", "summarize", "collect_manifest",
    "spec_hash", "Record", "phase", "phase_of", "scope", "span",
]
