"""Vehicle shards as rows of the shared training pool (DESIGN.md §6).

``partition_vehicles`` deals each vehicle the rows its ``rng.choice`` draw
picks and copies no image; minibatches gather from the pool.  The draws are
those of the eager copy it replaced, so every shard, every minibatch and
every engine's result stay bitwise what they were: the eager copy is
rebuilt here from the same ``default_rng(seed)`` sequence and compared."""
import dataclasses

import numpy as np
import pytest

from repro.channel.params import ChannelParams
from repro.core import Vehicle, build_world, get_scenario, run_scenario
from repro.core.client import ROW_BYTES
from repro.data import partition_vehicles, synth_mnist
from repro.telemetry import runlog


def _eager_shards(images, labels, params, seed, scale, dirichlet_alpha,
                  max_per_vehicle):
    """The eager partition: the same draw sequence, each shard a copy."""
    rng = np.random.default_rng(seed)
    out = []
    for i1 in range(1, params.K + 1):
        d_i = max(int(params.data_count(i1) * scale), 8)
        if max_per_vehicle is not None:
            d_i = min(d_i, max_per_vehicle)
        if dirichlet_alpha is None:
            sel = rng.choice(len(labels), size=min(d_i, len(labels)),
                             replace=False)
        else:
            probs = rng.dirichlet([dirichlet_alpha] * 10)
            weights = probs[labels]
            weights = weights / weights.sum()
            sel = rng.choice(len(labels), size=min(d_i, len(labels)),
                             replace=False, p=weights)
        out.append((sel, images[sel], labels[sel]))
    return out


def _small(dirichlet_alpha):
    tr_i, tr_l, _, _ = synth_mnist(n_train=600, n_test=16, seed=0,
                                   noise=0.35)
    p = dataclasses.replace(ChannelParams(), K=6)
    return (tr_i, tr_l, p, dict(seed=3, scale=0.02,
                                dirichlet_alpha=dirichlet_alpha,
                                max_per_vehicle=None))


def _scenario(name):
    sc = get_scenario(name)
    tr_i, tr_l, _, _ = synth_mnist(n_train=sc.n_train, n_test=sc.n_test,
                                   seed=0, noise=sc.noise)
    return (tr_i, tr_l, sc.channel(),
            dict(seed=0, scale=sc.scale, dirichlet_alpha=sc.dirichlet_alpha,
                 max_per_vehicle=sc.max_per_vehicle))


WORLDS = {
    "iid": lambda: _small(None),
    "dirichlet": lambda: _small(0.5),
    "paper-k10": lambda: _scenario("paper-k10"),
    "fleet-k100": lambda: _scenario("fleet-k100"),
}


@pytest.fixture(scope="module", params=sorted(WORLDS))
def world(request):
    tr_i, tr_l, p, kw = WORLDS[request.param]()
    return tr_i, tr_l, p, kw, partition_vehicles(tr_i, tr_l, p, **kw)


def test_shards_equal_eager_copies(world):
    tr_i, tr_l, p, kw, veh = world
    eager = _eager_shards(tr_i, tr_l, p, **kw)
    assert len(veh) == len(eager) == p.K
    for i, (d, (sel, im, lab)) in enumerate(zip(veh, eager)):
        assert d.index == i + 1
        np.testing.assert_array_equal(d.rows, sel)
        assert d.size == len(sel)
        got_im, got_lab = d.gather(np.arange(d.size))
        np.testing.assert_array_equal(got_im, im)
        np.testing.assert_array_equal(got_lab, lab)
        assert got_im.dtype == im.dtype and got_lab.dtype == lab.dtype


def test_shards_own_no_image_memory(world):
    tr_i, tr_l, p, kw, veh = world
    cap = kw["max_per_vehicle"] or max(d.size for d in veh)
    for d in veh:
        assert d.pool.images is tr_i and d.pool.labels is tr_l
        assert np.shares_memory(d.pool.images, tr_i)
        assert not np.shares_memory(d.rows, tr_i)
    assert len({id(d.pool) for d in veh}) == 1
    assert sum(d.rows.nbytes for d in veh) <= p.K * cap * 8
    counts = veh[0].pool.world_counts(len(veh))
    assert counts["shard_rows"] == sum(d.size for d in veh)
    assert counts["host_bytes"] == (tr_i.nbytes + tr_l.nbytes
                                    + counts["shard_rows"] * ROW_BYTES)


def test_sample_batches_match_copy_path(world):
    tr_i, tr_l, p, kw, veh = world
    eager = _eager_shards(tr_i, tr_l, p, **kw)
    seed, l_iters, n_draws = 7, 2, 20
    batch = min(8, min(d.size for d in veh))
    pool = veh[0].pool
    before = pool.rows_gathered
    for d, (_sel, im, lab) in zip(veh[:3], eager[:3]):
        v = Vehicle(d, batch_size=batch, seed=seed)
        rng = np.random.default_rng(seed + d.index)
        for _ in range(n_draws):
            want = np.stack([rng.choice(len(lab), batch, replace=False)
                             for _ in range(l_iters)])
            got_im, got_lab = v.sample_batches(l_iters)
            np.testing.assert_array_equal(got_im, im[want])
            np.testing.assert_array_equal(got_lab, lab[want])
    assert pool.rows_gathered - before == 3 * n_draws * l_iters * batch


def test_run_report_world_counts():
    sc = get_scenario("quick-k5")
    veh = build_world(sc, seed=0)[0]
    batch = min(128, min(d.size for d in veh))
    result = run_scenario("quick-k5", engine="jit")
    report = result.report
    assert report.world == {
        "vehicles": sc.K,
        "shard_rows": sum(d.size for d in veh),
        "host_bytes": veh[0].pool.world_counts(sc.K)["host_bytes"],
        "rows_gathered": sc.rounds * sc.l_iters * batch,
        # a sampler for each vehicle that drew, none for the others
        "samplers": len({r.vehicle for r in result.rounds}),
    }
    assert (f"world: {sc.K} vehicles, {report.world['shard_rows']} shard "
            "rows") in runlog.render([report.to_json()])


@pytest.mark.parametrize("scenario,engine", [
    ("quick-k5", "serial"), ("quick-k5", "batched"), ("quick-k5", "vmap"),
    ("corridor-quick-r2-k8", "corridor"), ("corridor-quick-r2-k8", "serial"),
])
def test_every_engine_reports_world(scenario, engine):
    sc = get_scenario(scenario)
    veh = build_world(sc, seed=0)[0]
    batch = min(128, min(d.size for d in veh))
    world = run_scenario(scenario, engine=engine).report.world
    assert world["vehicles"] == sc.K
    assert world["shard_rows"] == sum(d.size for d in veh)
    # every engine draws the l_iters minibatches of at least each
    # consumed round, and nothing but whole minibatches
    assert world["rows_gathered"] >= sc.rounds * sc.l_iters * batch
    assert world["rows_gathered"] % (sc.l_iters * batch) == 0
