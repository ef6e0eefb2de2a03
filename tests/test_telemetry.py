"""Telemetry subsystem invariants (DESIGN.md §14).

The two contracts pinned here:

1. **Off is a bitwise no-op.**  ``metrics='off'`` (or None) must produce
   bit-identical models and identical arrival traces to a run with no
   telemetry argument at all, on every engine — and on the device engines
   it must not even stage a new program (cache identity, rule TEL001).
   All comparisons are fresh-run vs fresh-run in this process, never
   against stored fixtures, so they hold on any host/BLAS combination.

2. **Channels conform to the f64 replay.**  The device accumulators (f32,
   in-scan) must reproduce the host f64 oracle exactly for the staleness
   histogram, occupancy, and handover counters (safe-margin edges make
   exact equality achievable), and to divergence-guard tolerance for the
   pop-wait trace.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.channel import ChannelParams
from repro.core import run_simulation
from repro.core.scenarios import build_world, get_scenario, run_scenario
from repro.checkpointing.checkpoint import tree_digest
from repro.data import partition_vehicles, synth_mnist
from repro.telemetry import RunReport, metrics_requested
from repro.telemetry.replay import (replay_corridor_channels,
                                    replay_fleet_channels)
from repro.telemetry.report import SCHEMA, wave_stats
from repro.telemetry.runlog import append, diff, load, render
from repro.telemetry.spec import (MetricsSpec, bucket_indices,
                                  plan_stale_edges, resolve_metrics,
                                  stale_histogram, stale_margin)

ROUNDS = 8


@pytest.fixture(scope="module")
def small_world():
    tr_i, tr_l, te_i, te_l = synth_mnist(n_train=256, n_test=64, seed=0)
    p = dataclasses.replace(ChannelParams(), K=4)
    veh = partition_vehicles(tr_i, tr_l, p, seed=0, scale=0.03)
    return veh, te_i, te_l, p


def _run(world, engine, **kw):
    veh, te_i, te_l, p = world
    return run_simulation(veh, te_i, te_l, scheme="mafl", rounds=ROUNDS,
                          l_iters=1, lr=0.05, params=p, seed=0,
                          eval_every=ROUNDS, engine=engine, batch_size=32,
                          **kw)


def _trace(result):
    return [(r.round, r.vehicle, r.time, r.upload_delay, r.train_delay)
            for r in result.rounds]


# ---------------------------------------------------------------------------
# contract 1: off is a bitwise no-op
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["serial", "batched", "jit"])
def test_metrics_off_is_bitwise_noop(small_world, engine):
    base = _run(small_world, engine)
    off = _run(small_world, engine, metrics="off")
    assert tree_digest(off.final_params) == tree_digest(base.final_params)
    assert _trace(off) == _trace(base)
    assert off.report is not None and not off.report.metrics_on
    assert off.report.channels == {} and off.report.spec is None


@pytest.mark.parametrize("engine", ["serial", "batched", "jit"])
def test_metrics_on_does_not_change_models(small_world, engine):
    """Telemetry rides in dead-code-free extra carries/columns: turning it
    on must not perturb the aggregation arithmetic."""
    base = _run(small_world, engine)
    on = _run(small_world, engine, metrics="on")
    assert tree_digest(on.final_params) == tree_digest(base.final_params)
    assert _trace(on) == _trace(base)
    assert on.report.metrics_on and on.report.spec["enabled"]


def test_metrics_off_reuses_jit_program(small_world):
    from repro.core.jit_engine import _PROGRAM_CACHE

    _run(small_world, "jit")
    n = len(_PROGRAM_CACHE)
    _run(small_world, "jit", metrics="off")
    assert len(_PROGRAM_CACHE) == n, \
        "metrics='off' staged a new jit program (TEL001)"


def test_telemetry_off_probe_clean():
    """The repro.check TEL001 probe sees no findings on the live tree."""
    from repro.check.telemetry_off import probe_telemetry_off

    assert probe_telemetry_off() == []


@pytest.mark.parametrize("engine", ["corridor", "serial"])
def test_corridor_metrics_off_is_bitwise_noop(engine):
    sc = get_scenario("corridor-quick-r2-k8")
    base = run_scenario(sc, seed=0, engine=engine, eval_every=sc.rounds)
    off = run_scenario(sc, seed=0, engine=engine, eval_every=sc.rounds,
                       metrics="off")
    on = run_scenario(sc, seed=0, engine=engine, eval_every=sc.rounds,
                      metrics="on")
    assert tree_digest(off.final_params) == tree_digest(base.final_params)
    assert tree_digest(on.final_params) == tree_digest(base.final_params)
    assert _trace(off) == _trace(base)
    assert _trace(on) == _trace(base)
    assert on.report.scenario == sc.name


# ---------------------------------------------------------------------------
# contract 2: channels conform to the f64 replay
# ---------------------------------------------------------------------------
def _fleet_channels_vs_replay(result, p, rounds, selection=None):
    rep = replay_fleet_channels(p, 0, rounds, selection=selection)
    spec = resolve_metrics("on", stale=rep["stale"], times=rep["times"])
    ch = {k: np.asarray(v) for k, v in result.report.channels.items()}
    assert result.report.spec["edges"] == list(spec.edges)
    assert np.array_equal(ch["stale_hist"],
                          stale_histogram(spec.edges, rep["stale"]))
    assert np.array_equal(ch["occupancy"], rep["occupancy"])
    assert np.allclose(ch["gap"], rep["gap"], rtol=1e-4, atol=1e-3)
    assert len(ch["reward"]) == rounds and np.all(ch["reward"] > 0)


@pytest.mark.parametrize("engine", ["serial", "batched", "jit"])
def test_small_fleet_channels_match_replay(small_world, engine):
    on = _run(small_world, engine, metrics="on")
    _fleet_channels_vs_replay(on, small_world[3], ROUNDS)


def test_fleet_k100_jit_channels_match_replay():
    sc = dataclasses.replace(get_scenario("fleet-k100"), rounds=12,
                             l_iters=1)
    _, _, _, p = build_world(sc, seed=0)
    on = run_scenario(sc, seed=0, engine="jit", eval_every=sc.rounds,
                      metrics="on")
    _fleet_channels_vs_replay(on, p, sc.rounds,
                              selection=sc.selection_spec())
    # K=100, one upload in flight per vehicle: occupancy is pinned at K
    assert np.all(np.asarray(on.report.channels["occupancy"]) == sc.K)
    assert on.report.waves["total_trained"] == sc.rounds


def _corridor_channels_vs_replay(result, sc, p):
    from repro.selection import scenario_spec

    rep = replay_corridor_channels(
        p, sc.n_rsus, 0, sc.rounds,
        entry=getattr(sc, "corridor_entry", "uniform"),
        selection=scenario_spec(sc), reconcile_every=sc.reconcile_every)
    spec = resolve_metrics("on", stale=rep["stale"], times=rep["times"],
                           n_rsus=sc.n_rsus)
    ch = {k: np.asarray(v) for k, v in result.report.channels.items()}
    assert np.array_equal(
        ch["stale_hist"],
        stale_histogram(spec.edges, rep["stale"], rsu=rep["up_rsu"],
                        n_rsus=sc.n_rsus))
    assert np.array_equal(ch["occupancy"], rep["occupancy"])
    assert np.array_equal(ch["handover"].astype(bool), rep["handover"])
    assert np.array_equal(ch["handover_count"], rep["handover_count"])
    assert np.allclose(ch["gap"], rep["gap"], rtol=1e-4, atol=1e-3)
    return rep


@pytest.mark.parametrize("engine", ["corridor", "serial"])
def test_corridor_channels_match_replay(engine):
    sc = get_scenario("corridor-quick-r2-k8")
    _, _, _, p = build_world(sc, seed=0)
    on = run_scenario(sc, seed=0, engine=engine, eval_every=sc.rounds,
                      metrics="on")
    _corridor_channels_vs_replay(on, sc, p)


def test_highway_handover_channel_counts():
    """A corridor world whose vehicles actually cross coverage boundaries:
    the handover counters must match the replay and be non-trivial."""
    # 24 pops is the earliest this world crosses a cell boundary (the f64
    # replay puts the first handover at pop 22)
    sc = dataclasses.replace(get_scenario("highway-k40-handover"),
                             rounds=24, l_iters=1)
    _, _, _, p = build_world(sc, seed=0)
    on = run_scenario(sc, seed=0, engine="corridor", eval_every=sc.rounds,
                      metrics="on")
    rep = _corridor_channels_vs_replay(on, sc, p)
    assert int(rep["handover_count"].sum()) > 0


def test_jit_bf16_ring_guard(small_world):
    on = _run(small_world, "jit", ring_dtype="bf16", metrics="on")
    ch = on.report.channels
    assert int(ch["ring_nonfinite"]) == 0
    assert float(ch["ring_max_abs"]) > 0.0
    assert on.report.spec["ring_guard"]


# ---------------------------------------------------------------------------
# planner: safe-margin edges
# ---------------------------------------------------------------------------
def test_edges_keep_safe_margin_from_samples():
    rng = np.random.default_rng(7)
    for trial in range(20):
        times = np.sort(rng.uniform(0.0, 3000.0, 64))
        stale = rng.uniform(0.0, 50.0, 64)
        edges = plan_stale_edges(stale, times)
        margin = stale_margin(times)
        for e in edges:
            assert np.min(np.abs(stale - e)) > margin
        # the margin guarantee is exactly what makes f32 and f64
        # staleness bucket identically
        f32_stale = np.float64(np.float32(stale))
        assert np.array_equal(bucket_indices(edges, stale),
                              bucket_indices(edges, f32_stale))
        assert np.all(np.diff(edges) > 0)


def test_metrics_requested_normalization():
    assert not metrics_requested(None)
    assert not metrics_requested(False)
    assert not metrics_requested("off")
    assert metrics_requested("on") and metrics_requested(True)
    assert metrics_requested(MetricsSpec(enabled=True))
    assert not metrics_requested(MetricsSpec(enabled=False))
    with pytest.raises(ValueError):
        metrics_requested("sometimes")
    assert resolve_metrics("off", stale=np.ones(3), times=np.ones(3)) is None


def test_wave_stats():
    waves = (((0, 1, 2), 0, 3), ((3, 4), 3, 5))
    s = wave_stats(waves, k=4)
    assert s["n_waves"] == 2 and s["sizes"] == [3, 2]
    assert s["total_trained"] == 5 and s["max_fill"] == 3
    assert s["utilization_vs_fleet"] == pytest.approx(5 / 8)


# ---------------------------------------------------------------------------
# run log + report schema + CLI
# ---------------------------------------------------------------------------
def test_report_json_roundtrip(small_world):
    on = _run(small_world, "jit", metrics="on")
    d = on.report.to_json()
    json.dumps(d)                      # fully serializable
    back = RunReport.from_json(d)
    assert back.engine == "jit" and back.metrics_on
    assert back.channels["stale_hist"] == d["channels"]["stale_hist"]
    bad = dict(d, schema="repro.telemetry/v0")
    with pytest.raises(ValueError):
        RunReport.from_json(bad)
    assert d["schema"] == SCHEMA


def test_runlog_roundtrip_and_diff(small_world, tmp_path):
    on = _run(small_world, "jit", metrics="on")
    off = _run(small_world, "jit", metrics="off")
    log = tmp_path / "runs.jsonl"
    append(log, on.report)
    append(log, off.report)
    runs = load(log)                   # schema-checked dicts
    assert len(runs) == 2
    assert runs[0]["metrics_on"] and not runs[1]["metrics_on"]
    text = render(runs)
    assert "jit" in text and "staleness hist" in text
    dtext = diff(runs[0], runs[1])
    assert "metrics_on" in dtext


def test_cli_report_and_diff(small_world, tmp_path, capsys):
    from repro.telemetry.__main__ import main

    on = _run(small_world, "jit", metrics="on")
    log = tmp_path / "runs.jsonl"
    append(log, on.report)
    assert main(["report", str(log)]) == 0
    assert "jit" in capsys.readouterr().out
    assert main(["diff", str(log), str(log)]) == 0
    capsys.readouterr()


def test_phase_timers_and_memory(small_world):
    on = _run(small_world, "jit", metrics="on")
    phases = on.report.phases
    # run_simulation builds no world: every other phase, in study order
    assert list(phases) == ["plan", "stage", "run", "guard", "eval",
                            "report"]
    assert all(v > 0.0 for v in phases.values())
    assert set(on.report.compile) == {"executables", "trace_s", "lower_s",
                                      "backend_s"}
    assert on.report.memory.get("peak_rss_bytes", 0) > 0
