"""The device engines' packed layout against the host oracles (DESIGN.md
§12), on the golden worlds and on fedasync / active-selection worlds:
exact (round, vehicle[, rsu]) sequence, event times and delay weights to
f32 tolerance, final parameters at the real-CNN conformance tolerance of
``tests/test_engine_conformance.py``.

Also covers the bf16 ring mode: explicit opt-in, exact timeline, bounded
accuracy drift, and the host-engine gates that refuse it.
"""
import json
import os

import numpy as np
import pytest

from repro.checkpointing.checkpoint import tree_digest
from repro.core.scenarios import run_scenario
from test_engine_conformance import (_assert_conformant,
                                     _assert_corridor_conformant)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
# the real-CNN bound of test_real_cnn_small_world_conforms
REAL_CNN_ATOL = 2e-3


def _load(name):
    with open(os.path.join(GOLDEN_DIR, f"{name}.json")) as f:
        return json.load(f)


_RUNS = {}


def _run(name, engine, **kw):
    key = (name, engine, tuple(sorted(kw.items())))
    if key not in _RUNS:
        fx = _load(name)
        _RUNS[key] = run_scenario(name, engine=engine, seed=fx["seed"],
                                  eval_every=fx["eval_every"],
                                  **dict(fx["overrides"]), **kw)
    return _RUNS[key]


def _trace(r):
    return [(rec.round, rec.vehicle, rec.rsu, rec.time) for rec in r.rounds]


QUICK = dict(seed=1, eval_every=4, rounds=12)
ORACLE_CASES = {
    # golden worlds: the device engine against the serial reference
    "paper-k10": ("paper-k10", "jit", "serial", None),
    "highway-k40-handover": ("highway-k40-handover", "corridor", "serial",
                             None),
    "corridor-quick-r2-k8": ("corridor-quick-r2-k8", "corridor", "serial",
                             None),
    # fedasync's staleness coefficient and the selection fold, against
    # the batched engine
    "quick-k5-fedasync": ("quick-k5", "jit", "batched",
                          dict(QUICK, scheme="fedasync")),
    "quick-k5-topk": ("quick-k5", "jit", "batched",
                      dict(QUICK, selection="weighted-topk", selection_k=3,
                           resel_every=4)),
}


@pytest.mark.parametrize("case", list(ORACLE_CASES))
def test_device_engine_conforms_to_host_oracle(case):
    name, engine, oracle, kw = ORACLE_CASES[case]
    if kw is None:
        ref, res = _run(name, oracle), _run(name, engine)
    else:
        ref = run_scenario(name, engine=oracle, **kw)
        res = run_scenario(name, engine=engine, **kw)
    if engine == "corridor":
        _assert_corridor_conformant(ref, res, param_atol=REAL_CNN_ATOL)
    else:
        _assert_conformant({oracle: ref, engine: res},
                           param_atol=REAL_CNN_ATOL, ref=oracle)


@pytest.mark.parametrize("name,engine", [
    ("paper-k10", "jit"), ("corridor-quick-r2-k8", "corridor")])
def test_flat_admit_all_selection_is_bitwise_noop(name, engine):
    base = _run(name, engine)
    sel = _run(name, engine, selection="admit-all")
    assert tree_digest(sel.final_params) == tree_digest(base.final_params)
    assert _trace(sel) == _trace(base)


# ---------------------------------------------------------------------------
# bf16 ring mode
# ---------------------------------------------------------------------------
def test_bf16_ring_exact_timeline_bounded_drift_jit():
    f32 = run_scenario("quick-k5", engine="jit", seed=1, eval_every=4,
                       rounds=12)
    b16 = run_scenario("quick-k5", engine="jit", seed=1, eval_every=4,
                       rounds=12, ring_dtype="bf16")
    assert _trace(f32) == _trace(b16)        # timeline never sees params
    assert abs(f32.final_accuracy() - b16.final_accuracy()) <= 0.05
    for k in f32.final_params:
        np.testing.assert_allclose(
            np.asarray(f32.final_params[k]),
            np.asarray(b16.final_params[k]), atol=3e-2, err_msg=k)


def test_bf16_ring_exact_timeline_bounded_drift_corridor():
    f32 = run_scenario("corridor-quick-r2-k8", engine="corridor", seed=0,
                       eval_every=4)
    b16 = run_scenario("corridor-quick-r2-k8", engine="corridor", seed=0,
                       eval_every=4, ring_dtype="bf16")
    assert _trace(f32) == _trace(b16)
    assert abs(f32.final_accuracy() - b16.final_accuracy()) <= 0.05


def test_bf16_requires_device_engine():
    with pytest.raises(ValueError, match="bf16"):
        run_scenario("quick-k5", engine="batched", ring_dtype="bf16")
    with pytest.raises(ValueError, match="bf16"):
        run_scenario("quick-k5", engine="serial", ring_dtype="bf16")


def test_fleet_k10000_scenario_registered_with_bf16_ring():
    from repro.core.scenarios import get_scenario
    sc = get_scenario("fleet-k10000")
    assert sc.K == 10000 and sc.ring_dtype == "bf16"


# ---------------------------------------------------------------------------
# fused-chain variant (use_kernel routes aggregation through ring_agg)
# ---------------------------------------------------------------------------
def test_fused_chain_matches_default_to_tolerance():
    a = run_scenario("quick-k5", engine="jit", seed=1, eval_every=4,
                     rounds=12)
    b = run_scenario("quick-k5", engine="jit", seed=1, eval_every=4,
                     rounds=12, use_kernel=True)
    assert _trace(a) == _trace(b)
    for k in a.final_params:
        np.testing.assert_allclose(
            np.asarray(a.final_params[k]), np.asarray(b.final_params[k]),
            rtol=2e-5, atol=1e-5, err_msg=k)


def test_fused_chain_matches_default_to_tolerance_corridor():
    a = run_scenario("corridor-quick-r2-k8", engine="corridor", seed=0,
                     eval_every=4)
    b = run_scenario("corridor-quick-r2-k8", engine="corridor", seed=0,
                     eval_every=4, use_kernel=True)
    assert _trace(a) == _trace(b)
    for k in a.final_params:
        np.testing.assert_allclose(
            np.asarray(a.final_params[k]), np.asarray(b.final_params[k]),
            rtol=2e-5, atol=1e-5, err_msg=k)
