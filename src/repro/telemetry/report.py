"""Typed, versioned run reports (DESIGN.md §14).

Every engine attaches a :class:`RunReport` to ``SimResult.report`` —
replacing the ad-hoc ``extras["selection"]`` dict entries with a stable,
schema-tagged record that serializes to JSON deterministically.  The
report splits into:

- identity: engine / scheme / rounds / seed (+ scenario name when run
  through ``run_scenario``),
- host instrumentation (always on): ``phases`` wall-clock seconds,
  ``compile`` counters (executables built or loaded, and the seconds
  spent tracing, lowering and compiling them) and ``memory`` peaks from
  :mod:`repro.telemetry.timers`, and ``world`` counts (vehicles, shard
  rows, host bytes of the pool and its row indices, rows gathered into
  minibatches, samplers built) from ``ShardPool.world_counts``, and
  ``plan`` counts (the planner's uploads scheduled as one column block,
  ``bulk``, and one at a time, ``single``) on the device engines,
- plan-derived statics: ``selection`` (the former extras entry) and
  ``waves`` fill/utilization — known before the device runs,
- device channels (``metrics=on`` only): staleness histogram, occupancy
  and pop-wait traces, per-RSU handover counters, bandit reward traces,
  bf16 ring guards — everything the scan carry accumulated.

``channels`` values arrive as numpy/JAX arrays and are converted to
plain lists at serialization time; ``from_json`` round-trips them as
lists (the JSONL log is the interchange format, not a tensor store).
"""
from __future__ import annotations

from dataclasses import dataclass, field, asdict
from typing import Optional

import numpy as np

SCHEMA = "repro.telemetry/v1"


def _plain(x):
    """Recursively convert numpy/JAX scalars and arrays to JSON-safe
    Python values."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if hasattr(x, "tolist"):          # np.ndarray, jax.Array, np scalars
        return _plain(np.asarray(x).tolist())
    if isinstance(x, (np.floating, float)):
        return float(x)
    if isinstance(x, (np.integer, int)) and not isinstance(x, bool):
        return int(x)
    return x


@dataclass
class RunReport:
    """One run's structured telemetry record (schema ``repro.telemetry/v1``)."""
    engine: str = ""
    scheme: str = ""
    rounds: int = 0
    seed: int = 0
    scenario: Optional[str] = None
    metrics_on: bool = False
    spec: Optional[dict] = None          # MetricsSpec.to_json() when on
    phases: dict = field(default_factory=dict)
    compile: dict = field(default_factory=dict)     # compile_counts()
    world: dict = field(default_factory=dict)       # world_counts()
    plan: dict = field(default_factory=dict)        # uploads scheduled
    memory: dict = field(default_factory=dict)
    selection: Optional[dict] = None     # SelectionPlan.summary()
    faults: Optional[dict] = None        # fault spec + decision counts
    waves: Optional[dict] = None         # wave_stats() (device engines)
    channels: dict = field(default_factory=dict)
    schema: str = SCHEMA

    def to_json(self) -> dict:
        d = asdict(self)
        return {k: _plain(v) for k, v in d.items()}

    @classmethod
    def from_json(cls, d: dict) -> "RunReport":
        if d.get("schema") != SCHEMA:
            raise ValueError(
                f"unsupported run-report schema {d.get('schema')!r} "
                f"(this reader understands {SCHEMA})")
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in d.items() if k in known})


def wave_stats(waves, k: int) -> dict:
    """Fill/utilization statistics for a plan's wave partition.

    ``waves`` is the planner tuple ``((train_rounds, seg_start, seg_end),
    ...)``: each wave batch-trains ``len(train_rounds)`` uploads in one
    vmapped ``_wave_train`` call.  Fill is measured against the fleet
    size ``k`` (the widest batch the wave trainer could ever form)."""
    sizes = [len(T) for T, _s, _e in waves]
    n = len(sizes)
    total = int(sum(sizes))
    return {
        "n_waves": n,
        "sizes": sizes,
        "total_trained": total,
        "mean_fill": (total / n) if n else 0.0,
        "max_fill": max(sizes) if sizes else 0,
        "utilization_vs_fleet": (total / (n * k)) if n and k else 0.0,
    }
