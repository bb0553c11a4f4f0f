"""Unified telemetry subsystem (DESIGN.md section 13).

Two data planes:

  * `repro.obs.registry` — process-wide metrics (counters, gauges,
    fixed-bucket histograms) for the host-side control plane. Strictly
    one boolean check when disabled.
  * `repro.obs.trace`    — program spans as `jax.profiler`
    annotations, recorded while a profiler session is active, on the
    same clock as the device operations. One check when not tracing.

Device-side solver signals (per-bundle accepted alpha, backtrack depth
q^t, active-set size) do NOT go through host callbacks: the engine
iteration surfaces them as extra device outputs behind the `record_aux`
config flag and the host loop folds them into `SolveHistory` (and, when
the registry is enabled, into histograms) at the per-iteration sync it
already performs. With `record_aux=False` the compiled step is
byte-identical to the uninstrumented solver.

Convenience facade: `obs.enable()` switches the metrics plane on (the
trace plane follows the profiler); the module-level helpers (`inc`,
`observe`, `span`, ...) proxy to the respective plane's zero-cost gate.
"""
from __future__ import annotations

from repro.obs import registry, trace
from repro.obs.registry import (ALPHA_BOUNDS, LATENCY_BOUNDS_S, Q_BOUNDS,
                                Histogram, Registry, get_registry, inc,
                                observe, observe_many, set_gauge,
                                write_metrics)
from repro.obs.trace import instant, span

__all__ = [
    "registry", "trace", "Registry", "Histogram",
    "LATENCY_BOUNDS_S", "Q_BOUNDS", "ALPHA_BOUNDS",
    "inc", "observe", "observe_many", "set_gauge", "write_metrics",
    "span", "instant",
    "enable", "disable", "metrics_enabled", "trace_enabled",
]


def enable() -> None:
    """Switch the metrics plane on. REPRO_METRICS=off still wins
    (registry.env_force_off). Spans need no switch: they record while a
    profiler session is active."""
    registry.enable()


def disable() -> None:
    registry.disable()


def metrics_enabled() -> bool:
    return registry.enabled()


def trace_enabled() -> bool:
    return trace.enabled()
