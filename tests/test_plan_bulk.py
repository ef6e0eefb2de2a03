"""The planners' initial schedule as one column block (DESIGN.md §9).

``plan_fleet`` and ``plan_corridor`` schedule every initial upload at
t = 0 through ``_Timeline.schedule_initial``, a vectorised f64 evaluation
that enters the ``EventQueue`` as one sorted column block.  The contract
is bitwise: every plan array equals the one a loop of scalar
``_Timeline.schedule(k, 0.0)`` calls gives, on every scenario family the
planners serve (ties in time, selection subsets, straggler scales,
blackout gates, both corridor entries).  ``tests/test_event_block.py``
holds the queue's side of the contract.
"""
import numpy as np
import pytest

from repro.channel import training_delay
from repro.core import mafl
from repro.core.jit_engine import plan_fleet
from repro.core.scenarios import get_scenario, run_scenario
from repro.corridor.plan import plan_corridor
from repro.telemetry.runlog import render

SEED = 123

PLAN_CASES = [
    "fleet-k1000",
    "fleet-k10000",
    "platoon-burst-k500",
    "fleet-k1000-topk",
    "fleet-k1000-throttled",
    "fleet-k1000-flaky",
    "corridor-r8-k4000",
    "corridor-rush-hour-r8-k4000",
    "corridor-r4-k400-bandit",
]

FIELDS = ("veh", "cycle", "dl_round", "times", "train_delay", "upload_delay",
          "download_time")
CORRIDOR_FIELDS = ("up_rsu", "row0")


def _scalar_initial(self, vehicles):
    """The initial schedule as one scalar call per vehicle."""
    for k in vehicles:
        self.schedule(k, 0.0)


def _plan(name):
    sc = get_scenario(name)
    p = sc.channel()
    if sc.n_rsus > 1:
        return plan_corridor(p, sc.n_rsus, SEED, sc.rounds,
                             entry=sc.corridor_entry,
                             selection=sc.selection_spec(),
                             reconcile_every=sc.reconcile_every,
                             faults=sc.faults, l_iters=sc.l_iters)
    return plan_fleet(p, SEED, sc.rounds, sc.selection_spec(),
                      faults=sc.faults, l_iters=sc.l_iters)


def _assert_tables_equal(a: dict, b: dict):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


@pytest.mark.parametrize("name", PLAN_CASES)
def test_bulk_plan_bitwise_equals_scalar_schedule(name, monkeypatch):
    bulk = _plan(name)
    monkeypatch.setattr(mafl._Timeline, "schedule_initial", _scalar_initial)
    ref = _plan(name)

    sc = get_scenario(name)
    fields = FIELDS + (CORRIDOR_FIELDS if sc.n_rsus > 1 else ())
    for f in fields:
        a, b = getattr(bulk, f), getattr(ref, f)
        assert a.dtype == b.dtype, f
        assert np.array_equal(a, b), f
    assert bulk.waves == ref.waves
    assert bulk.n_slots == ref.n_slots
    assert bulk.q0.keys() == ref.q0.keys()
    for k in bulk.q0:
        assert np.array_equal(bulk.q0[k], ref.q0[k]), k
    _assert_tables_equal(bulk.tables(), ref.tables())
    if bulk.sel is not None:
        _assert_tables_equal(bulk.sel.tables(sc.rounds),
                             ref.sel.tables(sc.rounds))
    if bulk.sel_bandit is not None:
        for a, b in zip(bulk.sel_bandit, ref.sel_bandit):
            assert np.array_equal(a, b)
    if bulk.flt is not None:
        _assert_tables_equal(bulk.flt.tables(sc.rounds),
                             ref.flt.tables(sc.rounds))

    # q0's Eq. 8 column against one scalar training_delay per vehicle
    p = sc.channel()
    c_l = np.array([training_delay(p, i) for i in range(1, p.K + 1)])
    if bulk.flt is not None:
        c_l = c_l * np.asarray(bulk.flt.cl_scale, np.float64)
    assert np.array_equal(bulk.q0["train_delay"], c_l)

    # every initial upload went through the block, every re-schedule alone
    n_live = int(np.isfinite(ref.q0["time"]).sum())
    assert bulk.scheduled["bulk"] + bulk.scheduled["single"] \
        == ref.scheduled["single"]
    if bulk.sel is None and bulk.flt is None:
        assert bulk.scheduled == {"bulk": p.K, "single": sc.rounds}
        assert n_live == p.K


@pytest.mark.parametrize("name,engine", [("quick-k5", "jit"),
                                         ("corridor-quick-r2-k8",
                                          "corridor")])
def test_run_report_counts_bulk_and_single_uploads(name, engine):
    sc = get_scenario(name)
    res = run_scenario(name, seed=0, engine=engine, rounds=4, eval_every=4)
    assert res.report.plan == {"bulk": sc.K, "single": 4}
    assert (f"plan: {sc.K} uploads scheduled as one block, 4 one at a time"
            in render([res.report.to_json()]))
