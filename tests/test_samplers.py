"""Minibatch samplers built on a vehicle's first draw (DESIGN.md §3).

Every engine reaches its vehicles through ``Samplers``, which builds a
``Vehicle`` when the vehicle first draws.  Each vehicle's stream is
``default_rng(seed + index)``, independent of every other, so the lazy map
must draw the minibatches an eager list of all K samplers draws, bit for
bit, and build only the vehicles that draw."""
import numpy as np
import pytest

from repro.core import Vehicle, build_world, get_scenario, run_scenario
from repro.core import jit_engine
from repro.core.client import Samplers
from repro.corridor import engine as corridor_engine

SEED = 3

ENGINES = [
    ("quick-k5", "jit"), ("quick-k5", "vmap"), ("quick-k5", "serial"),
    ("quick-k5", "batched"), ("corridor-quick-r2-k8", "corridor"),
    ("corridor-quick-r2-k8", "serial"),
]


def _world(scenario, seed=SEED):
    veh = build_world(get_scenario(scenario), seed=seed)[0]
    return veh, min(128, min(d.size for d in veh))


def _record_draws(monkeypatch):
    """Every minibatch stack drawn from now on, with its vehicle's index."""
    draws = []
    sample = Vehicle.sample_batches

    def spy(self, l_iters):
        im, lab = sample(self, l_iters)
        draws.append((self.data.index, im, lab))
        return im, lab

    monkeypatch.setattr(Vehicle, "sample_batches", spy)
    return draws


def _record_plans(monkeypatch):
    """The plan of every device-engine study run from now on."""
    plans = []
    for mod, name in ((jit_engine, "plan_fleet"),
                      (corridor_engine, "plan_corridor")):
        def spy(*a, _plan=getattr(mod, name), **kw):
            plans.append(_plan(*a, **kw))
            return plans[-1]
        monkeypatch.setattr(mod, name, spy)
    return plans


@pytest.mark.parametrize("scenario,engine", ENGINES)
def test_lazy_samplers_draw_the_eager_minibatches(scenario, engine,
                                                  monkeypatch):
    sc = get_scenario(scenario)
    draws = _record_draws(monkeypatch)
    plans = _record_plans(monkeypatch)
    result = run_scenario(scenario, engine=engine, seed=SEED)
    monkeypatch.undo()

    veh, batch = _world(scenario)
    eager = [Vehicle(d, lr=sc.lr, batch_size=batch, seed=SEED) for d in veh]
    order = [i for i, _, _ in draws]
    if engine in ("jit", "corridor"):
        (plan,) = plans
        assert order == [int(k) + 1 for k in plan.veh[:sc.rounds]]
    # every consumed round trained on a draw of its vehicle
    assert {r.vehicle for r in result.rounds} <= {i - 1 for i in order}
    for i, im, lab in draws:
        want_im, want_lab = eager[i - 1].sample_batches(sc.l_iters)
        np.testing.assert_array_equal(im, want_im)
        np.testing.assert_array_equal(lab, want_lab)


@pytest.mark.parametrize("access", [
    [], [0], [3, 3, 3], [4, 0, 4, 2, 0], [np.int64(1), 1, np.int32(1)],
    list(range(5)) * 2,
])
def test_samplers_build_each_accessed_vehicle_once(access):
    veh, batch = _world("quick-k5")
    pool = veh[0].pool
    before = pool.samplers
    samplers = Samplers(veh, lr=0.05, batch_size=batch, seed=SEED)
    assert pool.samplers == before
    got = [samplers[k] for k in access]
    assert pool.samplers - before == len({int(k) for k in access})
    for k, v in zip(access, got):
        assert v is samplers[k]
        assert v.data is veh[int(k)]
        assert (v.lr, v.batch_size) == (0.05, batch)
    assert pool.samplers - before == len({int(k) for k in access})


@pytest.mark.parametrize("scenario,engine,overrides", [
    ("quick-k5", "jit", {}),
    ("quick-k5", "jit", {"K": 24, "rounds": 6}),
    ("corridor-quick-r2-k8", "corridor", {}),
    ("corridor-quick-r2-k8", "corridor", {"K": 24, "rounds": 6}),
])
def test_report_counts_the_samplers_of_the_plan(scenario, engine, overrides,
                                                monkeypatch):
    plans = _record_plans(monkeypatch)
    report = run_scenario(scenario, engine=engine, seed=SEED,
                          **overrides).report
    (plan,) = plans
    rounds = overrides.get("rounds", get_scenario(scenario).rounds)
    drawn = len(set(plan.veh[:rounds].tolist()))
    assert report.world["samplers"] == drawn
    if overrides:
        assert drawn <= rounds < report.world["vehicles"]
