"""Device-resident telemetry subsystem (DESIGN.md §14).

The compiled engines are black boxes once ``lax.scan`` starts — this
package opens them up without breaking the DESIGN §3 host-plans /
device-executes invariant:

- :mod:`repro.telemetry.spec` — the host f64 planner side: a static
  :class:`MetricsSpec` (staleness-histogram bin edges placed a safe margin
  away from every planned sample, so f32 device values bucket identically).
- :mod:`repro.telemetry.device` — the device side: fixed-shape counter /
  histogram state carried through the scan, plus the bf16 snapshot-ring
  finiteness guard.  No host round-trips.
- :mod:`repro.telemetry.replay` — the f64 conformance oracle: re-drives the
  event timeline on the host and produces the exact channel values the
  device accumulators must reproduce.
- :mod:`repro.telemetry.timers` — host-side phase timers (world / plan /
  stage / run / guard / eval / report wall clock, each a ``repro.<phase>``
  profiler span), compile counters and peak memory.
- :mod:`repro.telemetry.report` — the typed, versioned :class:`RunReport`
  every engine attaches to ``SimResult.report`` (replacing the ad-hoc
  ``extras["selection"]`` dict entries).
- :mod:`repro.telemetry.runlog` — versioned JSONL structured run logs;
  ``python -m repro.telemetry report|diff`` renders or compares them.

The hard invariant: ``metrics=off`` (the default) compiles the exact
legacy program — a bitwise no-op, machine-checked by ``repro.check``
rule TEL001 and golden-pinned by ``tests/test_telemetry.py``.
"""
from repro.telemetry.report import RunReport
from repro.telemetry.spec import MetricsSpec, metrics_requested, resolve_metrics
from repro.telemetry.timers import PhaseTimers, memory_stats

__all__ = ["MetricsSpec", "RunReport", "PhaseTimers", "memory_stats",
           "metrics_requested", "resolve_metrics"]
