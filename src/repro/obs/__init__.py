"""Observability subsystem: protocol tracing, metrics and reports.

The paper's central claim — adaptive, graceful handling of heterogeneous
computation — is only testable if the protocol can be *measured from the
inside*. This package is that layer:

  trace.py      — span tracer of the engines' run loops: ``repro.run``,
                  ``repro.schedule``, ``repro.boundary``,
                  ``repro.execute`` and ``repro.readback`` spans and the
                  ``host_syncs`` counter. Spans never fence; each is also
                  a ``jax.profiler.TraceAnnotation``, on the profiler's
                  clock; per-wave facts (widths, halo rows/bytes per
                  comm-ladder rung, owned tasks) are read after the run.
                  Exported as Chrome trace-event JSON (Perfetto-
                  loadable). Off by default, and no cost when off.
  stats.py      — typed, versioned stats registry: every engine stat is
                  declared once (type, group, docstring); engine ``run``
                  stats are validated against it and normalized to
                  host-native Python scalars at the registry boundary.
  profiler.py   — device-profile integration: ``jax.profiler.trace``
                  context helper plus the ``annotate`` named-scope alias
                  used to label protocol phases (levels/conflict kernels,
                  halo gathers, window executors) in device profiles.
  provenance.py — environment header (jax version, backend, device kind
                  and count, timestamp, git sha) stamped into the
                  benchmark artifacts.
  costs.py      — compiled-cost telemetry: AOT cost_analysis FLOPs /
                  bytes, memory decomposition and HLO-parsed per-device
                  collective traffic of the jitted window executors,
                  cross-checked against the runtime comm ledger.

See docs/observability.md for the spans, the counter and the reports.
"""
from repro.obs.costs import (
    CrossCheck,
    ExecutorCost,
    HloCollectives,
    executor_cost,
    ledger_cross_check,
    parse_collectives,
)
from repro.obs.provenance import provenance
from repro.obs.stats import (
    STATS_VERSION,
    StatSpec,
    finalize_stats,
    registry,
    row_keys,
)
from repro.obs.trace import (
    SpanTracer,
    current_tracer,
    tracing,
    validate_chrome_trace,
)

__all__ = [
    "SpanTracer",
    "current_tracer",
    "tracing",
    "validate_chrome_trace",
    "StatSpec",
    "STATS_VERSION",
    "finalize_stats",
    "registry",
    "row_keys",
    "provenance",
    "ExecutorCost",
    "HloCollectives",
    "CrossCheck",
    "executor_cost",
    "parse_collectives",
    "ledger_cross_check",
]
