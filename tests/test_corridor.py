"""Corridor subsystem unit tests (DESIGN.md §10): the vectorized
CorridorMobility geometry, EMA/FedAvg cloud-tier reconciliation, the
engine's dispatch/validation surface, and the RSU-sharded mesh path
(subprocess with forced host devices)."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import ChannelParams, CorridorMobility
from repro.core.hierarchical import ema_toward, reconcile_models
from repro.core.scenarios import get_scenario, run_scenario


@pytest.fixture
def p():
    return dataclasses.replace(ChannelParams(), K=6)


# ---------------------------------------------------------------------------
# CorridorMobility — the promoted, vectorized geometry
# ---------------------------------------------------------------------------
def test_corridor_vectorized_over_vehicles_and_times(p):
    c = CorridorMobility(p, n_rsus=3)
    # whole-fleet broadcast forms agree with per-vehicle scalar calls
    t = 7.5
    xs = c.positions(t)
    cells = c.serving_cells(t)
    ds = c.distance(np.arange(p.K), t)
    assert xs.shape == cells.shape == ds.shape == (p.K,)
    for i in range(p.K):
        assert xs[i] == c.x(i, t)
        assert cells[i] == c.serving_rsu(i, t)
        assert ds[i] == c.distance(i, t)
    # time-vectorized: one vehicle across an array of times
    ts = np.linspace(0, 100, 17)
    assert c.x(0, ts).shape == ts.shape
    assert c.serving_rsu(0, ts).shape == ts.shape


def test_corridor_segment_geometry(p):
    c = CorridorMobility(p, n_rsus=4)
    assert c.span == 8 * p.coverage and len(c.centers) == 4
    # a vehicle at segment j's center is served by j at overhead distance
    for j in range(4):
        t = (c.centers[j] - c.x0[0]) / p.v
        assert c.serving_rsu(0, t) == j
        assert c.distance(0, t) == pytest.approx(
            np.sqrt(p.d_y ** 2 + p.H ** 2))
    # wrap-around re-entry keeps positions inside the corridor forever
    assert np.all(np.abs(c.x(np.arange(p.K), 1e6)) <= c.span / 2)


def test_corridor_boundary_crossing_is_the_handover_instant(p):
    c = CorridorMobility(p, n_rsus=3)
    t0 = 3.0
    tc = c.next_boundary_crossing(np.arange(p.K), t0)
    assert np.all(tc > t0)
    eps = 1e-6
    before = c.serving_rsu(np.arange(p.K), tc - eps)
    after = c.serving_rsu(np.arange(p.K), tc + eps)
    # crossing a segment edge changes the serving cell (modulo corridor
    # re-entry, which also lands in a different cell for n_rsus > 1)
    assert np.all(before != after)


def test_corridor_entry_profiles(p):
    uni = CorridorMobility(p, n_rsus=4)
    rush = CorridorMobility(p, n_rsus=4, entry="rush")
    # uniform: initial cells cover the corridor; rush: everyone starts in
    # the westmost segment
    assert len(set(uni.serving_cells(0.0).tolist())) > 1
    assert set(rush.serving_cells(0.0).tolist()) == {0}
    with pytest.raises(ValueError, match="entry profile"):
        CorridorMobility(p, n_rsus=4, entry="gridlock")


def test_corridor_alias_still_importable():
    # the ad-hoc helper's old home keeps working
    from repro.core.scenarios import _Corridor
    assert _Corridor is CorridorMobility


# ---------------------------------------------------------------------------
# cloud tier: EMA / FedAvg reconciliation
# ---------------------------------------------------------------------------
def test_reconcile_models_ema_mode():
    models = [{"w": jnp.full((256,), float(v))} for v in (1.0, 3.0)]
    mean = reconcile_models(models)
    np.testing.assert_allclose(np.asarray(mean["w"]), 2.0)
    stepped = [ema_toward(m, mean, 0.5) for m in models]
    np.testing.assert_allclose(np.asarray(stepped[0]["w"]), 1.5)
    np.testing.assert_allclose(np.asarray(stepped[1]["w"]), 2.5)
    # tau=1 EMA == FedAvg assignment
    np.testing.assert_allclose(
        np.asarray(ema_toward(models[0], mean, 1.0)["w"]), 2.0)
    # kernel-routed mix agrees with the jnp path
    k = ema_toward(models[0], mean, 0.5, use_kernel=True)
    np.testing.assert_allclose(np.asarray(k["w"]),
                               np.asarray(stepped[0]["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# engine dispatch and validation (the silently-substituting bug is gone)
# ---------------------------------------------------------------------------
def test_single_rsu_scenario_rejects_corridor_engine():
    with pytest.raises(ValueError, match="multi-RSU"):
        run_scenario("quick-k5", engine="corridor", rounds=2)


def test_corridor_scenario_rejects_single_rsu_engines():
    for eng in ("batched", "jit"):
        with pytest.raises(ValueError, match="cannot run multi-RSU"):
            run_scenario("corridor-quick-r2-k8", engine=eng, rounds=2)
    with pytest.raises(ValueError, match="unknown engine"):
        run_scenario("quick-k5", engine="warp", rounds=2)


def test_corridor_engine_rejects_fedbuff():
    with pytest.raises(ValueError, match="fedbuff"):
        run_scenario("corridor-quick-r2-k8", scheme="fedbuff", rounds=2)


def test_corridor_engine_rejects_unknown_reconcile_mode():
    with pytest.raises(ValueError, match="reconcile_mode"):
        run_scenario("corridor-quick-r2-k8", reconcile_mode="psum",
                     rounds=2)


def test_serial_reference_rejects_corridor_only_kwargs():
    with pytest.raises(ValueError, match="require engine='corridor'"):
        run_scenario("corridor-quick-r2-k8", engine="serial",
                     record_cohorts=True, rounds=2)


def test_rsu_mesh_must_tile_the_corridor():
    from types import SimpleNamespace

    from repro.corridor.engine import _rsu_shards
    assert _rsu_shards(None, 8) == 1
    assert _rsu_shards(SimpleNamespace(shape={"data": 4}), 8) == 1
    assert _rsu_shards(SimpleNamespace(shape={"rsu": 4}), 8) == 4
    with pytest.raises(ValueError, match="divisible"):
        _rsu_shards(SimpleNamespace(shape={"rsu": 3}), 8)


# ---------------------------------------------------------------------------
# corridor engine surface: records, extras, cohort snapshots
# ---------------------------------------------------------------------------
def test_corridor_engine_records_and_extras():
    r = run_scenario("corridor-quick-r2-k8", rounds=6, eval_every=3,
                     l_iters=1, record_cohorts=True)
    assert r.scheme == "mafl+corridor"
    assert len(r.rounds) == 6
    times = [rec.time for rec in r.rounds]
    assert times == sorted(times)
    sc = get_scenario("corridor-quick-r2-k8")
    # per-RSU round numbering: each RSU's records count its own arrivals
    counters = {}
    for rec in r.rounds:
        assert 0 <= rec.rsu < sc.n_rsus
        counters[rec.rsu] = counters.get(rec.rsu, 0) + 1
        assert rec.round == counters[rec.rsu]
    assert list(r.extras["up_rsu"]) == [rec.rsu for rec in r.rounds]
    assert r.extras["eval_rounds"] == [3, 6]
    # cohort snapshots: one [R, ...] stack per eval round
    snaps = r.extras["cohort_snapshots"]
    assert len(snaps) == 2
    leaf = jax.tree_util.tree_leaves(snaps[0])[0]
    assert leaf.shape[0] == sc.n_rsus
    # consensus of the final snapshot is the final params
    cons = jax.tree_util.tree_map(
        lambda x: jnp.mean(x.astype(jnp.float32), 0), snaps[-1])
    for a, b in zip(jax.tree_util.tree_leaves(cons),
                    jax.tree_util.tree_leaves(r.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_corridor_rush_hour_world_starts_in_cell_zero():
    sc = get_scenario("corridor-rush-hour-r8-k4000")
    assert sc.corridor_entry == "rush" and sc.n_rsus == 8
    p = sc.channel()
    assert p.platoon == 50 and p.K == 4000
    c = CorridorMobility(p, sc.n_rsus, entry=sc.corridor_entry)
    assert set(c.serving_cells(0.0).tolist()) == {0}


def test_corridor_engine_use_kernel_matches_plain():
    r0 = run_scenario("corridor-quick-r2-k8", rounds=5, eval_every=5,
                      l_iters=1)
    r1 = run_scenario("corridor-quick-r2-k8", rounds=5, eval_every=5,
                      l_iters=1, use_kernel=True)
    assert [(x.round, x.vehicle, x.rsu) for x in r0.rounds] == \
           [(x.round, x.vehicle, x.rsu) for x in r1.rounds]
    for a, b in zip(jax.tree_util.tree_leaves(r0.final_params),
                    jax.tree_util.tree_leaves(r1.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# RSU-sharded mesh path (forced host devices, isolated subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mesh_form", [
    "jax.make_mesh((2,), ('rsu',))",
    "Mesh(np.asarray(jax.devices()[:2]), ('rsu',))"])
def test_corridor_rsu_sharded_matches_unsharded(mesh_form):
    """The cohort stack sharded over an "rsu" mesh, from either mesh
    constructor: Explicit axes (``jax.make_mesh``) and Auto axes
    (``Mesh``) run the same program as the unsharded corridor."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.scenarios import build_world, get_scenario
        from repro.corridor.engine import run_corridor_simulation
        import dataclasses

        sc = dataclasses.replace(get_scenario("corridor-quick-r2-k8"),
                                 rounds=6, l_iters=1)
        veh, te_i, te_l, p = build_world(sc, seed=0)
        kw = dict(seed=0, eval_every=3)
        r0 = run_corridor_simulation(sc, veh, te_i, te_l, p, **kw)
        mesh = MESH_FORM
        r1 = run_corridor_simulation(sc, veh, te_i, te_l, p, mesh=mesh,
                                     **kw)
        assert ([(x.round, x.vehicle, x.rsu) for x in r0.rounds]
                == [(x.round, x.vehicle, x.rsu) for x in r1.rounds])
        for a, b in zip(jax.tree_util.tree_leaves(r0.final_params),
                        jax.tree_util.tree_leaves(r1.final_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        print("CORRIDOR_MESH_OK")
    """).replace("MESH_FORM", mesh_form)
    from test_hierarchical import SUBPROC_ENV
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=SUBPROC_ENV)
    assert "CORRIDOR_MESH_OK" in res.stdout, res.stderr[-3000:]
