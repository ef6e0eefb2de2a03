"""`correct` on the CPU at a test size: a sound run passes, and the
control and every fault the cells can have fail.

The harness's look for a chip is skipped (``run_cell`` takes the CPU
device); the rest of a run is driven as on the chip, with the timed path
broken underneath where a test plants a fault."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

import control
import run

SEED = 2_147_483_659            # past 32 signed bits


def tiny(cfg):
    cfg = json.loads(json.dumps(cfg))
    cfg["scenario"].update(K=30, rounds=12, n_train=600, n_test=100)
    if cfg["scenario"]["n_rsus"] > 1:
        cfg["scenario"].update(K=24, n_rsus=3, reconcile_every=4)
    return cfg


@pytest.fixture(scope="module")
def counters():
    return run.Counters()


def drive(cell_name, counters, run_study=None, cfg_edit=None):
    spec, cell, cfg, traffic = run.load_cell(cell_name)
    cfg = tiny(cfg)
    if run_study is not None:
        run_study = run_study(cfg, traffic)
    return run.run_cell(spec, cell, cfg, traffic, SEED, 0.01, False,
                        jax.devices(), counters, "none",
                        run.peaks_of("TPU v5 lite"), run_study=run_study)


def program(edit_sc=None, edit_result=None):
    """A ``run_study`` factory: the program at the cell's configuration,
    its scenario or its result edited."""
    from repro.core.scenarios import run_scenario

    def make(cfg, traffic):
        sc = run.scenario(cfg)
        if edit_sc:
            sc = edit_sc(sc)

        def study(lr):
            res = run_scenario(sc, engine=cfg["engine"], seed=SEED,
                               eval_every=traffic["eval_every"], lr=lr)
            if edit_result:
                edit_result(res)
            return res
        return study
    return make


CELLS = ["fleet-k10000.study", "corridor-r8-k4000.uniform"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, counters):
    line = drive(cell, counters)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cfg_name,traffic", [("fleet-k10000", "eval10"),
                                              ("corridor-r8-k4000",
                                               "eval10")])
def test_control_fails_and_program_passes(cfg_name, traffic):
    cfg = tiny(run.load_json("bench", "configs", cfg_name + ".json"))
    traffic = run.load_json("bench", "traffic", traffic + ".json")
    limits = cfg["correct"]["limits"]
    sound = control.readings(cfg, traffic, SEED, control=False)
    low = control.readings(cfg, traffic, SEED, control=True)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(low[k] > limits[k] for k in limits), low


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_training_fault_is_incorrect(cell, fault, counters, monkeypatch):
    from repro.core import client
    monkeypatch.setattr(client, "_local_scan",
                        control.FAULTS[fault](client._local_scan))
    line = drive(cell, counters)
    assert not line["correct"], line["checks"]


def _alter_vehicle(res):
    res.rounds[3].vehicle += 1


def _alter_loss(res):
    r, loss = res.loss_history[0]
    res.loss_history[0] = (r, loss + 0.05)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("edit", [_alter_vehicle, _alter_loss],
                         ids=["arrival", "eval_loss"])
def test_altered_answer_is_incorrect(cell, edit, counters):
    line = drive(cell, counters, program(edit_result=edit))
    assert not line["correct"], line["checks"]


def test_reconcile_left_out_is_incorrect(counters):
    """The corridor's exchange between RSUs: the cohorts never meet."""
    line = drive("corridor-r8-k4000.uniform", counters, program(
        edit_sc=lambda sc: dataclasses.replace(sc, reconcile_every=10 ** 6)))
    assert not line["correct"], line["checks"]


def test_no_tpu_exits_nonzero_and_prints_no_result():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fleet-k10000.study", "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
