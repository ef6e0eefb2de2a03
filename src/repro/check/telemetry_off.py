"""TEL001 probe: ``metrics=off`` must stage the exact legacy program.

The telemetry subsystem's hard contract (DESIGN.md §14) is that any falsy
``metrics`` setting is a *bitwise no-op*: the engines map it to ``None``
before the program-cache key is formed, so an off run and a no-metrics run
share one executable object — not merely equivalent programs, the same
program.  This probe stages the real jit and corridor quick worlds three
ways (no metrics, ``"off"``, ``"on"``) and verifies

- ``resolve_metrics`` collapses every falsy spelling to ``None``,
- the off staging returns the *identical* compiled-program object the
  no-metrics staging produced (cache identity — the strongest possible
  "same program" statement), and
- the on staging does NOT reuse that entry (a shared key would leak
  telemetry ops into off runs or vice versa).

Like the dtype-flow probes, this exercises the engines' own ``_stage_run``
helpers on tiny synthetic worlds, so it checks the program that would
actually run, not a reconstruction of it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.check.findings import Finding

_PATH_JIT = "<probe:telemetry-off-jit>"
_PATH_COR = "<probe:telemetry-off-corridor>"


def _resolve_findings() -> list[Finding]:
    from repro.telemetry.spec import resolve_metrics

    out = []
    stale = np.array([0.5, 1.0, 2.0])
    times = np.array([1.0, 2.0, 3.0])
    for falsy in (None, False, "off"):
        if resolve_metrics(falsy, stale=stale, times=times) is not None:
            out.append(Finding(
                "TEL001", "<probe:telemetry-off-resolve>", 0,
                f"resolve_metrics({falsy!r}) did not return None — the "
                "falsy path must carry zero telemetry state"))
    return out


def _jit_findings() -> list[Finding]:
    from repro.check.dtype_flow import _small_fleet
    from repro.core.jit_engine import _stage_run

    veh, p = _small_fleet()
    kw = dict(scheme="mafl", rounds=6, l_iters=1, lr=0.05, params=p,
              seed=0, eval_every=3, use_kernel=False, init_params=None,
              interpretation="mixing", batch_size=32, mesh=None,
              selection=None, ring_dtype="f32")
    base, *_ = _stage_run(veh, metrics=None, **kw)
    off, *_ = _stage_run(veh, metrics="off", **kw)
    on, *_ = _stage_run(veh, metrics="on", **kw)
    out = []
    if off is not base:
        out.append(Finding(
            "TEL001", _PATH_JIT, 0,
            "jit engine: metrics='off' staged a new program instead of "
            "reusing the legacy cache entry"))
    if on is base:
        out.append(Finding(
            "TEL001", _PATH_JIT, 0,
            "jit engine: metrics='on' reused the legacy cache entry — "
            "the metrics spec is missing from the program-cache key"))
    return out


def _corridor_findings() -> list[Finding]:
    from repro.core.scenarios import build_world, get_scenario
    from repro.corridor.engine import _stage_run

    sc = dataclasses.replace(get_scenario("corridor-quick-r2-k8"),
                             rounds=6, l_iters=1)
    veh, _, _, p = build_world(sc, seed=0)
    kw = dict(seed=0, eval_every=3, interpretation="mixing",
              use_kernel=False, batch_size=32, mesh=None,
              record_cohorts=False, init_params=None, selection=None)
    base, *_ = _stage_run(sc, veh, p, metrics=None, **kw)
    off, *_ = _stage_run(sc, veh, p, metrics="off", **kw)
    on, *_ = _stage_run(sc, veh, p, metrics="on", **kw)
    out = []
    if off is not base:
        out.append(Finding(
            "TEL001", _PATH_COR, 0,
            "corridor engine: metrics='off' staged a new program instead "
            "of reusing the legacy cache entry"))
    if on is base:
        out.append(Finding(
            "TEL001", _PATH_COR, 0,
            "corridor engine: metrics='on' reused the legacy cache entry "
            "— the metrics spec is missing from the program-cache key"))
    return out


def probe_telemetry_off() -> list[Finding]:
    return (_resolve_findings() + _jit_findings() + _corridor_findings())
