"""Compile rehearsals for one TPU v5e chip, made without a chip.

The TPU compiler ships with JAX and compiles for a described, unattached
chip.  These tests catch what interpret mode cannot — Mosaic tiling or VMEM
refusals, a program that outgrows the chip's memory — before any chip time
is spent.  Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
from collections import OrderedDict

import jax
import jax.numpy as jnp
import pytest

P_CNN = 422_016                  # the paper CNN packed into 128-lane rows
V5E_HBM_BYTES = 16 * 10 ** 9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A TPU executable written to the persistent cache cannot be read back
    without a chip, so the cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("uploads", [1, 8, 30])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ring_agg_compiles_for_v5e(one_chip, no_compile_cache, dtype,
                                   uploads):
    from repro.kernels.weighted_agg.kernel import LANE, ring_agg_2d

    rows = P_CNN // LANE
    assert rows == 3297

    def shape(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    compiled = ring_agg_2d.lower(
        shape(rows, LANE), shape(uploads, rows, LANE, dt=jnp.dtype(dtype)),
        shape(uploads, 2), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _as_tpu_program(engine_module, monkeypatch):
    """Stage the engine as it stages on a TPU: the backend answers "tpu",
    so aggregation leaves the event scan and runs as compiled ring_agg
    chains.  A fresh program cache keeps the CPU programs other tests
    built from being reused here, and this program from being reused by
    them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(engine_module, "_PROGRAM_CACHE", OrderedDict())


def _assert_compiles_for_one_chip(prog, args, one_chip):
    compiled = prog.lower(*jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


def test_fleet_k10000_program_compiles_for_v5e(one_chip, no_compile_cache,
                                               monkeypatch):
    """The whole fleet-k10000 jit program (bf16 ring)."""
    import repro.core.jit_engine as jit_engine
    from repro.core.scenarios import build_world, get_scenario

    _as_tpu_program(jit_engine, monkeypatch)
    sc = get_scenario("fleet-k10000")
    veh, _, _, p = build_world(sc)
    prog, args, _, layout, *_ = jit_engine._stage_run(
        veh, scheme=sc.scheme, rounds=sc.rounds, l_iters=sc.l_iters,
        lr=sc.lr, params=p, seed=0, eval_every=10, use_kernel=False,
        init_params=None, interpretation="mixing", batch_size=128,
        mesh=None, selection=None, ring_dtype=sc.ring_dtype)
    assert layout.P == P_CNN
    _assert_compiles_for_one_chip(prog, args, one_chip)


def test_corridor_r8_k4000_program_compiles_for_v5e(one_chip,
                                                    no_compile_cache,
                                                    monkeypatch):
    """The whole corridor-r8-k4000 program: per-RSU ring_agg chains
    between the cloud reconciles."""
    import repro.corridor.engine as corridor_engine
    from repro.core.scenarios import build_world, get_scenario

    _as_tpu_program(corridor_engine, monkeypatch)
    sc = get_scenario("corridor-r8-k4000")
    veh, _, _, p = build_world(sc)
    prog, args, _, layout, *_ = corridor_engine._stage_run(
        sc, veh, p, seed=0, eval_every=10, interpretation="mixing",
        use_kernel=False, batch_size=128, mesh=None, record_cohorts=False,
        init_params=None, selection=None)
    assert layout.P == P_CNN
    _assert_compiles_for_one_chip(prog, args, one_chip)


@pytest.mark.parametrize("engine,axis", [("jit", "data"),
                                         ("corridor", "rsu")])
def test_program_over_four_chips_compiles_for_v5e(topo, no_compile_cache,
                                                  monkeypatch, engine, axis):
    """The main path over a four-chip mesh: the fleet's training waves over
    ``"data"``, the corridor's cohort stack over ``"rsu"``.  XLA cannot
    partition a Mosaic kernel, so under a mesh the ``ring_agg`` chains
    must run inside a ``shard_map``; interpret mode on the CPU cannot show
    that, a compile for the TPU does."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import repro.core.jit_engine as jit_engine
    import repro.corridor.engine as corridor_engine
    from repro.core.scenarios import build_world, get_scenario

    mesh = Mesh(np.asarray(topo.devices[:4]), (axis,))
    if engine == "jit":
        _as_tpu_program(jit_engine, monkeypatch)
        sc = get_scenario("fleet-k10000")
        veh, _, _, p = build_world(sc)
        prog, args, *_ = jit_engine._stage_run(
            veh, scheme=sc.scheme, rounds=sc.rounds, l_iters=sc.l_iters,
            lr=sc.lr, params=p, seed=0, eval_every=10, use_kernel=False,
            init_params=None, interpretation="mixing", batch_size=128,
            mesh=mesh, selection=None, ring_dtype=sc.ring_dtype)
    else:
        _as_tpu_program(corridor_engine, monkeypatch)
        sc = get_scenario("corridor-r8-k4000")
        veh, _, _, p = build_world(sc)
        prog, args, *_ = corridor_engine._stage_run(
            sc, veh, p, seed=0, eval_every=10, interpretation="mixing",
            use_kernel=False, batch_size=128, mesh=mesh,
            record_cohorts=False, init_params=None, selection=None)
    replicated = NamedSharding(mesh, PartitionSpec())
    compiled = prog.lower(*jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated),
        args)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    if axis == "rsu":
        assert "all-reduce" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES
