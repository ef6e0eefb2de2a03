"""Program phases as profiler spans (DESIGN.md §14).

The contracts pinned here, on the two device engines through
``run_scenario``:

1. ``report.phases`` partitions the study: exactly the phases of
   ``telemetry.timers.PHASES``, in order, summing to the call's wall time.
2. Each phase is a ``repro.<phase>`` span in a profiler trace (the world
   phase twice: its build and its teardown); the spans do not overlap and
   cover the study.
3. ``report.compile`` counts what the study compiled: nothing on a repeat.
4. The fused aggregation chains carry a ``ring_chain_*`` scope on both
   engines.
"""
import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import time

import jax
import pytest

from repro.core.scenarios import build_world, get_scenario, run_scenario
from repro.telemetry.timers import PHASES, PhaseTimers

WORLDS = {"jit": "quick-k5", "corridor": "corridor-quick-r2-k8"}
ROUNDS = 6


def _study(engine, **kw):
    return run_scenario(WORLDS[engine], engine=engine, rounds=ROUNDS,
                        eval_every=3, **kw)


def _spans(log_dir, prefix):
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.trace.json.gz"))
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    return sorted((e for e in events if e.get("ph") == "X"
                   and e.get("name", "").startswith(prefix)),
                  key=lambda e: e["ts"])


@pytest.mark.parametrize("engine", ["jit", "corridor"])
def test_phases_partition_the_study(engine):
    _study(engine)                               # compile outside the clock
    t0 = time.perf_counter()
    res = _study(engine)
    wall = time.perf_counter() - t0
    phases = res.report.phases
    assert list(phases) == list(PHASES)
    assert all(v > 0.0 for v in phases.values())
    assert 0.95 * wall <= sum(phases.values()) <= wall


@pytest.mark.parametrize("engine", ["jit", "corridor"])
def test_phases_are_profiler_spans(engine, tmp_path):
    _study(engine)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.study"):
            _study(engine)
    finally:
        jax.profiler.stop_trace()
    (study,) = _spans(str(tmp_path), "test.study")
    spans = _spans(str(tmp_path), "repro.")
    # one span per phase, and the world phase again at the end: the
    # world's teardown
    assert [s["name"] for s in spans] == (
        [f"repro.{p}" for p in PHASES] + ["repro.world"])
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    lo, hi = study["ts"], study["ts"] + study["dur"]
    assert all(lo <= s["ts"] and s["ts"] + s["dur"] <= hi for s in spans)
    assert sum(s["dur"] for s in spans) >= 0.95 * study["dur"]


@pytest.mark.parametrize("engine", ["jit", "corridor"])
def test_compile_counts_in_report(engine, monkeypatch):
    # an empty program cache makes the first study build its program
    import repro.core.jit_engine as jit_engine
    import repro.corridor.engine as corridor_engine
    mod = jit_engine if engine == "jit" else corridor_engine
    monkeypatch.setattr(mod, "_PROGRAM_CACHE", collections.OrderedDict())
    first = _study(engine).report.compile
    again = _study(engine).report.compile
    assert first["executables"] >= 1 and first["trace_s"] > 0.0
    assert first["lower_s"] > 0.0 and first["backend_s"] > 0.0
    assert again == {"executables": 0, "trace_s": 0.0, "lower_s": 0.0,
                     "backend_s": 0.0}


def test_nested_phase_pauses_the_outer_one():
    timers = PhaseTimers()
    t0 = time.perf_counter()
    with timers.phase("run"):
        time.sleep(0.01)
        with timers.phase("eval"):
            time.sleep(0.2)
    wall = time.perf_counter() - t0
    ph = timers.snapshot()
    assert set(ph) == {"run", "eval"}
    assert ph["run"] >= 0.01 and ph["eval"] >= 0.2
    assert ph["run"] + ph["eval"] <= wall


def _lowered_text(engine):
    import repro.core.jit_engine as jit_engine
    import repro.corridor.engine as corridor_engine
    sc = dataclasses.replace(get_scenario(WORLDS[engine]), rounds=ROUNDS)
    veh, _, _, p = build_world(sc)
    if engine == "jit":
        prog, args, *_ = jit_engine._stage_run(
            veh, scheme=sc.scheme, rounds=sc.rounds, l_iters=sc.l_iters,
            lr=sc.lr, params=p, seed=0, eval_every=3, use_kernel=True,
            init_params=None, interpretation="mixing", batch_size=128,
            mesh=None, selection=None, ring_dtype=sc.ring_dtype)
    else:
        prog, args, *_ = corridor_engine._stage_run(
            sc, veh, p, seed=0, eval_every=3, interpretation="mixing",
            use_kernel=True, batch_size=128, mesh=None,
            record_cohorts=False, init_params=None, selection=None)
    return prog.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("engine", ["jit", "corridor"])
def test_fused_chains_carry_ring_chain_scope(engine):
    text = _lowered_text(engine)
    assert re.search(r"/ring_chain_[0-9]+_[0-9]+/", text)
    # a trace keys an op by its first scope: a chain inside a scan's
    # scope would be counted as the scan
    assert not re.search(r"event_scan_[0-9_]+/ring_chain_", text)


def test_report_cli_renders_compile_counts(tmp_path, capsys):
    from repro.telemetry.__main__ import main
    from repro.telemetry.runlog import append

    log = tmp_path / "runs.jsonl"
    append(log, _study("jit").report)
    append(log, _study("jit").report)
    assert main(["report", str(log)]) == 0
    out = capsys.readouterr().out
    assert "phases: world=" in out and "compile: 0 executables" in out
    assert main(["diff", str(log), str(log), "--index-a", "0"]) == 0
    out = capsys.readouterr().out
    assert "compile executables" in out and "phase guard" in out
