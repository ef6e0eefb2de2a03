"""FLT001 probe: ``faults=None`` must stage the exact legacy program.

The fault subsystem's hard contract (DESIGN.md §16) mirrors TEL001: any
falsy or no-op ``faults`` spelling is a *bitwise no-op* — the engines
normalize it to ``None`` through :func:`repro.faults.resolve_faults`
before the program-cache key is formed, so a faults-off run and a
pre-faults run share one executable object — not merely equivalent
programs, the same program.  This probe stages the real jit and corridor
quick worlds three ways (no faults, ``"off"``, the ``"flaky"`` profile)
and verifies

- ``resolve_faults`` collapses every falsy and no-op spelling (including
  an all-zero :class:`~repro.faults.spec.FaultSpec`) to ``None``,
- the off staging returns the *identical* compiled-program object the
  no-faults staging produced (cache identity — the strongest possible
  "same program" statement), and
- a live fault profile does NOT reuse that entry (a shared key would bake
  fault folds into clean runs or vice versa).

Like the telemetry-off probe, this exercises the engines' own
``_stage_run`` helpers on tiny synthetic worlds, so it checks the program
that would actually run, not a reconstruction of it.
"""
from __future__ import annotations

import dataclasses

from repro.check.findings import Finding

_PATH_JIT = "<probe:faults-off-jit>"
_PATH_COR = "<probe:faults-off-corridor>"


def _resolve_findings() -> list[Finding]:
    from repro.faults import FaultSpec, resolve_faults

    out = []
    for falsy in (None, False, "off", "none", "", FaultSpec()):
        if resolve_faults(falsy) is not None:
            out.append(Finding(
                "FLT001", "<probe:faults-off-resolve>", 0,
                f"resolve_faults({falsy!r}) did not return None — the "
                "falsy/no-op path must carry zero fault state"))
    return out


def _jit_findings() -> list[Finding]:
    from repro.check.dtype_flow import _small_fleet
    from repro.core.jit_engine import _stage_run

    veh, p = _small_fleet()
    kw = dict(scheme="mafl", rounds=6, l_iters=1, lr=0.05, params=p,
              seed=0, eval_every=3, use_kernel=False, init_params=None,
              interpretation="mixing", batch_size=32, mesh=None,
              selection=None, ring_dtype="f32")
    base, *_ = _stage_run(veh, faults=None, **kw)
    off, *_ = _stage_run(veh, faults="off", **kw)
    on, *_ = _stage_run(veh, faults="flaky", **kw)
    out = []
    if off is not base:
        out.append(Finding(
            "FLT001", _PATH_JIT, 0,
            "jit engine: faults='off' staged a new program instead of "
            "reusing the legacy cache entry"))
    if on is base:
        out.append(Finding(
            "FLT001", _PATH_JIT, 0,
            "jit engine: faults='flaky' reused the legacy cache entry — "
            "the fault plan is missing from the program-cache key"))
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
    base, *_ = _stage_run(sc, veh, p, faults=None, **kw)
    off, *_ = _stage_run(sc, veh, p, faults="off", **kw)
    on, *_ = _stage_run(sc, veh, p, faults="flaky", **kw)
    out = []
    if off is not base:
        out.append(Finding(
            "FLT001", _PATH_COR, 0,
            "corridor engine: faults='off' staged a new program instead "
            "of reusing the legacy cache entry"))
    if on is base:
        out.append(Finding(
            "FLT001", _PATH_COR, 0,
            "corridor engine: faults='flaky' reused the legacy cache "
            "entry — the fault plan is missing from the program-cache "
            "key"))
    return out


def probe_faults_off() -> list[Finding]:
    return (_resolve_findings() + _jit_findings() + _corridor_findings())
