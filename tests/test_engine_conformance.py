"""Property-based cross-engine conformance (DESIGN.md §9): for randomly
drawn worlds — fleet size, rounds, data heterogeneity, channel coherence —
the serial, batched, and jit engines must produce the same
(round, vehicle) arrival sequence, event times equal to f32 tolerance (the
jit engine carries time in ``f32[K]`` slot arrays; the host engines use
f64), and allclose final global parameters.

Property cases run under the ``_hypothesis_compat`` shim, so without
``hypothesis`` they degrade to deterministic bound/midpoint sweeps instead
of being skipped.  The fast lane drives the orchestration with the stubbed
trainer from ``test_engine_equivalence``; one small real-CNN world runs
un-stubbed, and the heavier real-CNN world is slow-marked.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import repro.core.client as client_mod
from repro.channel import RayleighAR1, slot_gain_table
from repro.channel.params import ChannelParams
from repro.core import run_simulation
from repro.data import partition_vehicles, synth_mnist

ENGINES = ("serial", "batched", "jit")


def _fake_local_scan(params, images, labels, lr):
    """Pure-jnp trainer stub (shared with test_engine_equivalence): folds
    the exact minibatch stream into the parameters so any divergence in
    payload snapshots, batch pairing, or RNG order shows up in the
    result."""
    h = (jnp.mean(images.astype(jnp.float32))
         + jnp.mean(labels.astype(jnp.float32)))
    out = jax.tree_util.tree_map(
        lambda w: w * (1.0 - lr * 0.01) + 1e-3 * h, params)
    return out, h


@pytest.fixture()
def stub_trainer(monkeypatch):
    monkeypatch.setattr(client_mod, "_local_scan", _fake_local_scan)
    monkeypatch.setattr(client_mod, "_local_scan_jit", _fake_local_scan)
    monkeypatch.setattr(
        client_mod, "_local_scan_vmap",
        jax.vmap(_fake_local_scan, in_axes=(0, 0, 0, None)))


_WORLD_CACHE = {}


def _world(K: int, scale: float, rho: float, noniid: bool = False):
    key = (K, scale, rho, noniid)
    if key not in _WORLD_CACHE:
        tr_i, tr_l, te_i, te_l = synth_mnist(n_train=600, n_test=120,
                                             seed=0, noise=0.35)
        p = dataclasses.replace(ChannelParams(), K=K, fading_rho=rho)
        veh = partition_vehicles(tr_i, tr_l, p, seed=0, scale=scale,
                                 dirichlet_alpha=0.3 if noniid else None)
        _WORLD_CACHE[key] = (veh, te_i, te_l, p)
    return _WORLD_CACHE[key]


def _run(world, engine, rounds, l_iters=2, scheme="mafl", **kw):
    veh, te_i, te_l, p = world
    return run_simulation(veh, te_i, te_l, scheme=scheme, rounds=rounds,
                          l_iters=l_iters, lr=0.05, eval_every=max(rounds, 1),
                          seed=0, params=p, engine=engine, **kw)


def _assert_conformant(results: dict, param_atol=1e-5, ref="serial"):
    ref = results[ref]
    ref_seq = [(r.round, r.vehicle) for r in ref.rounds]
    ref_t = np.array([r.time for r in ref.rounds])
    ref_w = np.array([r.weight for r in ref.rounds])
    for name, res in results.items():
        seq = [(r.round, r.vehicle) for r in res.rounds]
        assert seq == ref_seq, f"{name}: arrival sequence diverged"
        t = np.array([r.time for r in res.rounds])
        np.testing.assert_allclose(t, ref_t, rtol=2e-5, atol=1e-3,
                                   err_msg=f"{name}: event times")
        w = np.array([r.weight for r in res.rounds])
        np.testing.assert_allclose(w, ref_w, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}: delay weights")
        for x, y in zip(jax.tree_util.tree_leaves(ref.final_params),
                        jax.tree_util.tree_leaves(res.final_params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=param_atol,
                                       err_msg=f"{name}: final params")


@given(st.integers(2, 6), st.integers(3, 10), st.floats(0.008, 0.03),
       st.floats(0.6, 0.99))
@settings(max_examples=5, deadline=None)
def test_random_worlds_conform(K, rounds, scale, rho):
    """The property: any (K, rounds, heterogeneity, coherence) world gives
    identical traces and allclose params across all three engines."""
    # fixture-free stubbing: @given composes awkwardly with fixtures under
    # the shim, so patch manually around the body
    saved = (client_mod._local_scan, client_mod._local_scan_jit,
             client_mod._local_scan_vmap)
    client_mod._local_scan = _fake_local_scan
    client_mod._local_scan_jit = _fake_local_scan
    client_mod._local_scan_vmap = jax.vmap(_fake_local_scan,
                                           in_axes=(0, 0, 0, None))
    try:
        world = _world(K, scale, rho)
        results = {e: _run(world, e, rounds) for e in ENGINES}
        _assert_conformant(results)
    finally:
        (client_mod._local_scan, client_mod._local_scan_jit,
         client_mod._local_scan_vmap) = saved


def test_noniid_world_conforms(stub_trainer):
    world = _world(4, 0.015, 0.95, noniid=True)
    results = {e: _run(world, e, 8) for e in ENGINES}
    _assert_conformant(results)


def test_afl_and_fedasync_conform(stub_trainer):
    world = _world(3, 0.015, 0.95)
    for scheme in ("afl", "fedasync"):
        results = {e: _run(world, e, 6, scheme=scheme) for e in ENGINES}
        _assert_conformant(results)


def test_literal_interpretation_conforms(stub_trainer):
    world = _world(3, 0.015, 0.95)
    results = {e: _run(world, e, 6, interpretation="literal")
               for e in ENGINES}
    _assert_conformant(results)


def test_kernel_aggregation_conforms(stub_trainer):
    """use_kernel=True routes aggregation through the Pallas weighted_agg
    kernel inside the jit engine's scan as well as the host path."""
    world = _world(3, 0.015, 0.95)
    results = {e: _run(world, e, 5, use_kernel=True) for e in ENGINES}
    _assert_conformant(results, param_atol=1e-4)


def test_real_cnn_small_world_conforms():
    """Un-stubbed end-to-end conformance on a small world: real CNN local
    training through all three engines."""
    world = _world(3, 0.01, 0.95)
    results = {e: _run(world, e, 5, l_iters=1) for e in ENGINES}
    _assert_conformant(results, param_atol=2e-3)
    accs = {e: [a for _, a in r.acc_history] for e, r in results.items()}
    np.testing.assert_allclose(accs["jit"], accs["serial"], atol=0.05)


@pytest.mark.slow
def test_real_cnn_k5_world_conforms():
    world = _world(5, 0.02, 0.95)
    results = {e: _run(world, e, 10, l_iters=2) for e in ENGINES}
    _assert_conformant(results, param_atol=5e-3)


def test_jit_mesh_shard_map_matches_unsharded(stub_trainer):
    """Wave training sharded over the (data, model) host mesh via
    shard_map must agree with the unsharded program (DESIGN.md §5, §9)."""
    from repro.core.jit_engine import run_simulation_jit
    from repro.launch.mesh import make_host_mesh
    veh, te_i, te_l, p = _world(3, 0.015, 0.95)
    kw = dict(scheme="mafl", rounds=5, l_iters=1, lr=0.05, eval_every=5,
              seed=0, params=p)
    r0 = run_simulation_jit(veh, te_i, te_l, **kw)
    r1 = run_simulation_jit(veh, te_i, te_l, mesh=make_host_mesh(), **kw)
    assert ([(x.round, x.vehicle) for x in r0.rounds]
            == [(x.round, x.vehicle) for x in r1.rounds])
    for x, y in zip(jax.tree_util.tree_leaves(r0.final_params),
                    jax.tree_util.tree_leaves(r1.final_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_slot_gain_table_matches_sequential_cache():
    """The vectorized prefix-scan table must reproduce the sequential
    AR(1) chain (same RNG bitstream, f64 round-off only)."""
    p = dataclasses.replace(ChannelParams(), K=7)
    table = slot_gain_table(p, seed=3, n_slots=50)
    ref = RayleighAR1(p, seed=3)
    seq = ref.steps_block(50)
    np.testing.assert_allclose(table, seq, rtol=1e-10, atol=1e-12)
    assert table.shape == (50, 7)
    assert slot_gain_table(p, seed=3, n_slots=0).shape == (0, 7)


def test_platoon_params_share_leader_delays():
    """platoon=n gives convoys identical Table-I compute/data (bursty
    arrivals for platoon-burst-k500)."""
    from repro.channel import training_delay
    p = dataclasses.replace(ChannelParams(), K=9, platoon=3)
    delays = [training_delay(p, i) for i in range(1, 10)]
    assert delays[0] == delays[1] == delays[2]
    assert delays[3] == delays[4] == delays[5]
    assert delays[0] != delays[3] != delays[6]
    # platoon=0 keeps per-vehicle heterogeneity
    p0 = dataclasses.replace(ChannelParams(), K=9)
    d0 = [training_delay(p0, i) for i in range(1, 10)]
    assert len(set(d0)) == 9


def test_jit_rejects_fedbuff():
    world = _world(2, 0.015, 0.95)
    with pytest.raises(ValueError, match="fedbuff"):
        _run(world, "jit", 3, scheme="fedbuff")


# ---------------------------------------------------------------------------
# corridor conformance: serial handover reference vs engine="corridor"
# (DESIGN.md §10) — identical event traces, allclose final models
# ---------------------------------------------------------------------------
def _assert_corridor_conformant(ref, res, param_atol=1e-5):
    assert res.scheme.endswith("+corridor")
    # identical arrival traces: (per-RSU round, vehicle, serving RSU)
    assert ([(r.round, r.vehicle, r.rsu) for r in res.rounds]
            == [(r.round, r.vehicle, r.rsu) for r in ref.rounds]), \
        "corridor: arrival sequence diverged"
    np.testing.assert_allclose([r.time for r in res.rounds],
                               [r.time for r in ref.rounds],
                               rtol=2e-5, atol=1e-3,
                               err_msg="corridor: event times")
    np.testing.assert_allclose([r.weight for r in res.rounds],
                               [r.weight for r in ref.rounds],
                               rtol=1e-4, atol=1e-4,
                               err_msg="corridor: delay weights")
    assert [rd for rd, _ in res.acc_history] == \
           [rd for rd, _ in ref.acc_history]
    for x, y in zip(jax.tree_util.tree_leaves(ref.final_params),
                    jax.tree_util.tree_leaves(res.final_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=param_atol,
                                   err_msg="corridor: final params")


def _corridor_pair(name, param_atol=1e-5, **kw):
    from repro.core.scenarios import run_scenario
    ref = run_scenario(name, engine="serial", seed=0, **kw)
    res = run_scenario(name, engine="corridor", seed=0, **kw)
    _assert_corridor_conformant(ref, res, param_atol=param_atol)
    return ref, res


def test_corridor_conforms_highway_k40(stub_trainer):
    """The acceptance world: engine='corridor' reproduces the serial
    handover trace exactly on highway-k40-handover."""
    ref, res = _corridor_pair("highway-k40-handover", rounds=12,
                              eval_every=6, l_iters=1)
    # handover actually exercised: uploads land on several RSUs
    assert len({r.rsu for r in ref.rounds}) > 1


def test_corridor_conforms_r4_k400(stub_trainer):
    """Conformance-sized mega-corridor world (400 vehicles, 4 RSUs)."""
    _corridor_pair("corridor-r4-k400", rounds=10, eval_every=5)


def test_corridor_conforms_ema_mode(stub_trainer):
    """EMA cloud tier: cohorts keep identity between reconciliations on
    both engines."""
    _corridor_pair("corridor-quick-r2-k8", rounds=8, eval_every=4,
                   reconcile_mode="ema", reconcile_tau=0.3)


def test_corridor_conforms_afl_fedasync(stub_trainer):
    for scheme in ("afl", "fedasync"):
        _corridor_pair("corridor-quick-r2-k8", rounds=6, eval_every=6,
                       scheme=scheme)


def test_corridor_real_cnn_small_world_conforms():
    """Un-stubbed end-to-end corridor conformance: real CNN training
    through both engines, accuracy histories equal."""
    ref, res = _corridor_pair("corridor-quick-r2-k8", rounds=6,
                              eval_every=3, param_atol=2e-3)
    np.testing.assert_allclose([a for _, a in res.acc_history],
                               [a for _, a in ref.acc_history], atol=0.05)


@pytest.mark.slow
def test_corridor_real_cnn_rush_hour_conforms():
    """Rush-hour entry profile (platoon bursts at the west end) through
    a shrunken r2 world, un-stubbed."""
    _corridor_pair("corridor-quick-r2-k8", rounds=8, eval_every=4,
                   corridor_entry="rush", param_atol=5e-3,
                   channel_overrides=(("platoon", 4),))
