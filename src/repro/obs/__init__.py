"""``repro.obs`` — in-jit engine telemetry, host spans, run reports.

Three layers, importable in any combination:

* :mod:`repro.obs.metrics` — :class:`MetricsSpec` selects counter
  groups; the engines carry the resulting metrics pytree through their
  jit-scanned super-ticks (``EngineConfig(metrics=...)``), so
  collection adds no host round-trips and leaves Theta bit-exact; and
  the serving tier's host counters (:data:`SERVE_COUNTERS`);
* :mod:`repro.obs.trace` — :func:`span`, the host spans the run driver
  and the serving tier open at their boundaries. They land in a
  ``jax.profiler`` trace on the device's clock; the super-tick's phases
  are ``obs.*`` named scopes, found in the same trace through the HLO;
* :mod:`repro.obs.report` — :class:`RunReport` (periodic metric drains,
  JSONL round-trip) and the ``python -m repro.obs.report`` CLI that
  renders summaries and merges ``obs_*`` rows into
  ``BENCH_summary.json``.
"""

from repro.obs.metrics import (
    SERVE_COUNTERS,
    TOPOLOGY_COUNTERS,
    ExchangeVolume,
    MetricsAccumulator,
    MetricsSpec,
    serve_counters_init,
    summarize_counters,
    topology_log_init,
)
from repro.obs.report import RunReport, merge_bench_summary
from repro.obs.trace import span

__all__ = [
    "SERVE_COUNTERS",
    "TOPOLOGY_COUNTERS",
    "ExchangeVolume",
    "MetricsAccumulator",
    "MetricsSpec",
    "RunReport",
    "merge_bench_summary",
    "serve_counters_init",
    "span",
    "summarize_counters",
    "topology_log_init",
]
