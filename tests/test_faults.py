"""Fault-injection subsystem (DESIGN.md §16): the faults-off bitwise
no-op + program-cache identity contract on all four engines, exact
f64-replay conformance of every injected decision, property tests over
the stochastic client-state sampler (hypothesis shim), seed determinism,
the FLT001 lint, and the engine scope gates.
"""
import dataclasses

import numpy as np
import pytest

from repro.channel.params import ChannelParams
from repro.checkpointing.checkpoint import tree_digest
from repro.core.scenarios import build_world, get_scenario, run_scenario
from repro.faults import (FaultSpec, check_faults_reconcile, named_profile,
                          replay_corridor_faults, replay_fleet_faults,
                          resolve_faults, scenario_faults)

from tests._hypothesis_compat import given, settings, st

# churn-heavy spec used wherever the tests need faults to actually fire
# on short runs (the named profiles are tuned for long mega-fleet runs)
HEAVY = FaultSpec(p_dropout=0.25, p_blackout=0.15, blackout_mean=20.0,
                  p_partial=0.5, straggler_frac=0.4, straggler_mult=3.0,
                  staleness_cap=6, recheck_every=2)


# ---------------------------------------------------------------------------
# spec resolution and scenario registry
# ---------------------------------------------------------------------------
def test_resolve_faults_collapses_falsy_and_noop():
    for falsy in (None, False, "off", "none", "", FaultSpec(),
                  FaultSpec(straggler_frac=0.5, straggler_mult=1.0)):
        assert resolve_faults(falsy) is None
    assert resolve_faults("flaky") == named_profile("flaky")
    with pytest.raises(KeyError):
        resolve_faults("no-such-profile")
    with pytest.raises(TypeError):
        resolve_faults(42)
    with pytest.raises(ValueError):
        resolve_faults(FaultSpec(p_dropout=1.5))


def test_fault_scenarios_registered():
    for name, profile in (("fleet-k1000-flaky", "flaky"),
                          ("corridor-rush-hour-deadzone-r8-k4000",
                           "deadzone"),
                          ("fleet-k1000-throttled", "throttled")):
        sc = get_scenario(name)
        assert sc.faults == profile
        assert scenario_faults(sc) == named_profile(profile)
    # a fault-free scenario resolves to no fault model
    assert scenario_faults(get_scenario("fleet-k1000")) is None


# ---------------------------------------------------------------------------
# faults-off: bitwise no-op + program-cache identity on all four engines
# ---------------------------------------------------------------------------
def test_faults_off_bitwise_noop_host_engines():
    """serial/batched: faults='off' produces bit-identical models to the
    legacy no-faults call and carries no fault report."""
    sc = get_scenario("quick-k5")
    veh, te_i, te_l, p = build_world(sc, seed=0)
    from repro.core.mafl import run_simulation
    kw = dict(scheme=sc.scheme, rounds=6, l_iters=1, lr=sc.lr, params=p,
              seed=0, eval_every=6)
    for engine in ("serial", "batched"):
        base = run_simulation(veh, te_i, te_l, engine=engine, **kw)
        off = run_simulation(veh, te_i, te_l, engine=engine,
                             faults="off", **kw)
        assert tree_digest(off.final_params) == \
            tree_digest(base.final_params)
        assert "faults" not in off.extras
        assert off.report.faults is None


def test_faults_off_cache_identity_jit():
    """jit: faults=None/'off' reuse the legacy executable object; a live
    profile stages a different program (the TEL001-dual contract)."""
    sc = get_scenario("quick-k5")
    veh, _, _, p = build_world(sc, seed=0)
    from repro.core.jit_engine import _stage_run
    kw = dict(scheme=sc.scheme, rounds=6, l_iters=1, lr=sc.lr, params=p,
              seed=0, eval_every=3, use_kernel=False, init_params=None,
              interpretation="mixing", batch_size=32, mesh=None,
              selection=None, ring_dtype="f32")
    base, *_ = _stage_run(veh, faults=None, **kw)
    off, *_ = _stage_run(veh, faults="off", **kw)
    noop, *_ = _stage_run(veh, faults=FaultSpec(), **kw)
    live, *_ = _stage_run(veh, faults=HEAVY, **kw)
    assert off is base
    assert noop is base
    assert live is not base


def test_faults_off_cache_identity_corridor():
    sc = get_scenario("corridor-quick-r2-k8")
    veh, _, _, p = build_world(sc, seed=0)
    from repro.corridor.engine import _stage_run
    kw = dict(seed=0, eval_every=4, interpretation="mixing",
              use_kernel=False, batch_size=32, mesh=None,
              record_cohorts=False, init_params=None, selection=None)
    base, *_ = _stage_run(sc, veh, p, faults=None, **kw)
    off, *_ = _stage_run(sc, veh, p, faults="off", **kw)
    live, *_ = _stage_run(sc, veh, p, faults=HEAVY, **kw)
    assert off is base
    assert live is not base


# ---------------------------------------------------------------------------
# exact f64-replay conformance (the oracle contract)
# ---------------------------------------------------------------------------
def test_fleet_k100_replay_conformance():
    """fleet-k100 under flaky churn: batched and jit reproduce every
    drop/blackout/partial/cap decision of the f64 replay exactly."""
    sc = dataclasses.replace(get_scenario("fleet-k100"), rounds=20,
                             l_iters=2, faults="flaky")
    oracle = replay_fleet_faults(sc.channel(), 0, sc.rounds, "flaky",
                                 l_iters=sc.l_iters)
    expected = oracle.summary(sc.l_iters)
    rb = run_scenario(sc, engine="batched", eval_every=sc.rounds)
    rj = run_scenario(sc, engine="jit", eval_every=sc.rounds)
    assert rb.extras["faults"] == expected
    assert rj.extras["faults"] == expected
    assert rb.report.faults["counts"] == expected["counts"]
    # the flaky profile actually fired on this world (not a vacuous pass)
    assert any(c != 0 for c in expected["cause"]) or \
        not all(expected["admit0"])


def test_corridor_quick_replay_conformance():
    """corridor-quick-r2-k8 under heavy churn: the device-resident engine
    and the serial reference both match the corridor replay exactly."""
    sc = dataclasses.replace(get_scenario("corridor-quick-r2-k8"))
    oracle = replay_corridor_faults(
        sc.channel(), sc.n_rsus, 0, sc.rounds, HEAVY, l_iters=sc.l_iters,
        entry=sc.corridor_entry, reconcile_every=sc.reconcile_every)
    expected = oracle.summary(sc.l_iters)
    rc = run_scenario(sc, engine="corridor", eval_every=sc.rounds,
                      faults_overrides=_as_overrides(HEAVY),
                      faults="flaky")
    rs = run_scenario(sc, engine="serial", eval_every=sc.rounds,
                      faults_overrides=_as_overrides(HEAVY),
                      faults="flaky")
    assert rc.extras["faults"] == expected
    assert rs.extras["faults"] == expected


def _as_overrides(spec: FaultSpec) -> tuple:
    return tuple(dataclasses.asdict(spec).items())


# ---------------------------------------------------------------------------
# seed determinism and cross-seed shape stability
# ---------------------------------------------------------------------------
def test_replay_seed_determinism():
    p = dataclasses.replace(ChannelParams(), K=20)
    a = replay_fleet_faults(p, 3, 30, HEAVY, l_iters=2)
    b = replay_fleet_faults(p, 3, 30, HEAVY, l_iters=2)
    assert a.signature() == b.signature()
    c = replay_fleet_faults(p, 4, 30, HEAVY, l_iters=2)
    assert c.signature() != a.signature()
    # FLT001 shape discipline: tables depend on (rounds, K), not the seed
    ta, tc = a.tables(30), c.tables(30)
    assert set(ta) == set(tc)
    for k in ta:
        assert ta[k].shape == tc[k].shape and ta[k].dtype == tc[k].dtype
    assert a.counts_table(2).shape == c.counts_table(2).shape == (30, 4)


# ---------------------------------------------------------------------------
# sampler properties (hypothesis shim)
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.floats(min_value=0.0, max_value=0.4))
def test_dropout_fraction_matches_spec_rate(p_drop):
    """Each pop draws its dropout independently at probability
    ``p_dropout``, so the recorded drop fraction concentrates around the
    spec rate (zero exactly at zero)."""
    spec = FaultSpec(p_dropout=p_drop, recheck_every=4)
    p = dataclasses.replace(ChannelParams(), K=50)
    plan = replay_fleet_faults(p, 0, 400, spec, l_iters=1)
    if p_drop == 0.0:
        assert plan is None          # no-op spec collapses to faults-off
        return
    frac = float(np.mean(np.asarray(plan.cause) == 1))
    assert abs(frac - p_drop) < 0.12


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=5))
def test_partial_epoch_counts_bounded_by_configured(l_iters):
    spec = FaultSpec(p_partial=0.6, recheck_every=4)
    p = dataclasses.replace(ChannelParams(), K=20)
    plan = replay_fleet_faults(p, 1, 120, spec, l_iters=l_iters)
    eps = np.asarray(plan.epochs)
    assert np.all((1 <= eps) & (eps <= l_iters))
    assert plan.counts(l_iters)["partial_rounds"] == \
        int(np.sum(eps < l_iters))
    # with partial disabled every cycle runs the full epoch count
    clean = replay_fleet_faults(
        p, 1, 120, FaultSpec(p_dropout=0.1, recheck_every=4),
        l_iters=l_iters)
    assert np.all(np.asarray(clean.epochs) == l_iters)


def test_dropped_vehicles_never_contribute_until_readmitted():
    """A suppressed re-schedule removes the vehicle from the event queue:
    it must not appear again in the pop sequence before a re-admission
    boundary brings it back."""
    from repro.telemetry.replay import replay_fleet_channels
    p = dataclasses.replace(ChannelParams(), K=30)
    rounds = 200
    plan = replay_fleet_faults(p, 2, rounds, HEAVY, l_iters=2)
    veh = replay_fleet_channels(p, 2, rounds, faults=HEAVY,
                                l_iters=2)["veh"]
    suppressed = [r for r in range(rounds) if not plan.sched[r]]
    assert suppressed, "HEAVY spec produced no suppressions on 200 rounds"
    readmits = plan.readmit_lists()
    for r in suppressed:
        v = int(veh[r])
        later = np.nonzero(veh[r + 1:] == v)[0]
        if later.size == 0:
            continue                 # never came back before the end
        r2 = r + 1 + int(later[0])
        assert any(r < b <= r2 and v in vs
                   for b, vs in readmits.items()), (
            f"vehicle {v} suppressed at pop {r} reappeared at {r2} "
            "without a re-admission boundary in between")


# ---------------------------------------------------------------------------
# telemetry fault counters (scan-carry accumulators vs f64 replay)
# ---------------------------------------------------------------------------
def test_fault_counters_conform_jit():
    sc = dataclasses.replace(get_scenario("quick-k5"), rounds=12)
    plan = replay_fleet_faults(sc.channel(), 0, sc.rounds, HEAVY,
                               l_iters=sc.l_iters)
    r = run_scenario(sc, engine="jit", eval_every=sc.rounds,
                     metrics="on", faults="flaky",
                     faults_overrides=_as_overrides(HEAVY))
    got = r.report.channels["fault_counts"]
    np.testing.assert_array_equal(
        got, plan.counts_table(sc.l_iters).sum(axis=0))
    # faults off -> no fault counter channel rides the carry
    clean = run_scenario(sc, engine="jit", eval_every=sc.rounds,
                         metrics="on")
    assert "fault_counts" not in clean.report.channels


def test_fault_counters_conform_corridor():
    sc = get_scenario("corridor-quick-r2-k8")
    plan = replay_corridor_faults(
        sc.channel(), sc.n_rsus, 0, sc.rounds, HEAVY, l_iters=sc.l_iters,
        entry=sc.corridor_entry, reconcile_every=sc.reconcile_every)
    r = run_scenario(sc, engine="corridor", eval_every=sc.rounds,
                     metrics="on", faults="flaky",
                     faults_overrides=_as_overrides(HEAVY))
    np.testing.assert_array_equal(
        r.report.channels["fault_counts"],
        plan.counts_table(sc.l_iters).sum(axis=0))


# ---------------------------------------------------------------------------
# scope gates
# ---------------------------------------------------------------------------
def test_ema_reconcile_rejects_timeline_faults():
    """Recovery re-admission needs an RSU-independent download model, so
    timeline-active faults are fedavg-only on corridor worlds."""
    with pytest.raises(ValueError, match="ema"):
        check_faults_reconcile(named_profile("flaky"), "ema")
    # compute-only faults never touch the timeline: ema stays legal
    check_faults_reconcile(named_profile("throttled"), "ema")
    check_faults_reconcile(named_profile("flaky"), "fedavg")
    sc = dataclasses.replace(get_scenario("corridor-quick-r2-k8"),
                             reconcile_mode="ema", faults="flaky")
    for engine in ("corridor", "serial"):
        with pytest.raises(ValueError, match="ema"):
            run_scenario(sc, engine=engine, eval_every=sc.rounds)


def test_vmap_engine_rejects_fault_worlds():
    with pytest.raises(ValueError, match="vmap.*fault"):
        run_scenario("fleet-k1000-flaky", engine="vmap", K=5, rounds=6,
                     l_iters=1, n_train=400, n_test=80)


# ---------------------------------------------------------------------------
# FLT001 lint (the faults dual of PLN001/PLN002)
# ---------------------------------------------------------------------------
def test_flt001_flags_engine_imports_and_f32_in_fault_modules():
    from repro.check.boundary import check_source
    bad = ("import jax\n"
           "from repro.core.jit_engine import plan_fleet\n"
           "import numpy as np\n"
           "x = np.zeros(3, np.float32)\n")
    findings = check_source("src/repro/faults/runtime.py", bad)
    rules = [f.rule for f in findings]
    assert rules.count("FLT001") == 3      # jax, engine import, f32 drop
    # the real fault modules are clean under their own rule
    from pathlib import Path
    from repro.check.boundary import check_file
    for name in ("spec.py", "runtime.py", "replay.py", "__init__.py"):
        path = Path("src/repro/faults") / name
        assert not [f for f in check_file(path) if not f.waived], name


def test_faults_off_probe_is_green():
    from repro.check.faults_off import _resolve_findings
    assert _resolve_findings() == []
