"""Benchmark harness: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration
(``bench/configs/<name>.json``: the scenario's fields, the channel's Table I
values, the model it names and that model's widths, the engine, and the
limits of the comparison) and a traffic mix (``bench/traffic/<name>.json``:
the evaluation cadence and the cycle of learning rates).  The model is the
module ``bench/models/<model>.py``, loaded before the warm-up: a
configuration that names a missing one exits 1 at once.

Set-up (``setup_s``, from process start): imports, device start, and two
whole studies as the warm-up: the first builds the world, plans, stages
and compiles the cell's program, the second brings the host's memory
allocator to the state of the studies that follow.  The window then runs back-to-back studies,
one ``run_scenario`` call each, for ``--seconds`` and until the study in
progress ends; each study takes the next learning rate of the cycle, which
is a program argument and compiles nothing.  ``rounds_per_s`` is the
simulated rounds of the completed studies over the window's wall time.

After the window the plain reference (``bench/reference.py``) runs every
learning rate the window used, and the first study of each is compared
with it (``bench/compare.py``).  ``--trace 1`` profiles the window's first
studies and reports the per-layer metrics instead, each read by
``bench/metrics/<name>.py`` from the studies' phases, the traced studies'
run reports and the trace, reduced over the named scopes and kernels that
the cell's readers declare.

The last line of standard output is the result as one JSON object.  The
run needs a TPU with as many chips as the cell asks for and exits 1 without
one, printing no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import ExitStack, nullcontext  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "_out")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
# the first study compiles; the second lets the host's allocator reach the
# steady state of later studies (its world build runs twice as long)
WARM_UP = 2
# a traced run profiles the window's first studies only: the profiler's
# trace.json keeps a bounded number of events, and a longer trace loses some
TRACED_STUDIES = 3


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    """``(spec, workload, configuration, traffic)`` of cell ``name``."""
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return (spec, cell, load_json(conf["file"]),
            load_json("bench", "traffic", cell["traffic"] + ".json"))


class Counters:
    """Executables compiled (fresh or from the persistent cache) and
    persistent-cache hits, from JAX's monitoring events, and the seconds
    the interpreter spent in cyclic garbage collection."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.gc_s = 0.0
        self._gc_start = None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc_s += time.perf_counter() - self._gc_start
            self._gc_start = None

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == CACHE_HITS:
            self.cache_hits += 1


def require_devices(chips: int):
    """The cell's TPU chips; exits 1 on any other platform or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:chips]


def configure_cache() -> str:
    """The program's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in this
    checkout), keeping every executable however fast it compiled."""
    import jax
    from repro.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def scenario(cfg: dict):
    """The configuration as the program's ``Scenario``."""
    from repro.core.scenarios import Scenario
    return Scenario(name=cfg["name"], description=cfg["deployment"],
                    channel_overrides=tuple(cfg["channel"].items()),
                    **cfg["scenario"])


def lr_cycle(cfg, traffic, seed: int) -> list:
    """The traffic's learning rates, in an order drawn from the seed."""
    cyc = [cfg["scenario"]["lr"] * m for m in traffic["lr_cycle"]]
    k = seed % len(cyc)
    return cyc[k:] + cyc[:k]


def answer(result) -> dict:
    """What the comparison reads of one study, on the host."""
    import jax
    return {"trace": [(r.vehicle, r.rsu or 0) for r in result.rounds],
            "final": jax.device_get(result.final_params),
            "losses": [(r, float(v)) for r, v in result.loss_history]}


class Spans:
    """``TraceAnnotation`` spans around the program's layer entry points
    (``bench/spans.json``), installed for a traced window only."""

    def __init__(self, entries):
        self.entries = entries
        self.saved = []

    def __enter__(self):
        import functools
        import importlib

        import jax
        from trace_reduce import SPAN_PREFIX
        for e in self.entries:
            mod, attr = e["target"].split(":")
            m = importlib.import_module(mod)
            fn = getattr(m, attr)

            def wrap(fn=fn, label=SPAN_PREFIX + e["label"]):
                @functools.wraps(fn)
                def inner(*a, **k):
                    with jax.profiler.TraceAnnotation(label):
                        return fn(*a, **k)
                return inner
            self.saved.append((m, attr, fn))
            setattr(m, attr, wrap())
        return self

    def __exit__(self, *exc):
        for m, attr, fn in reversed(self.saved):
            setattr(m, attr, fn)


class Tracer:
    """The profiler over the window's first ``n`` studies, each in a
    ``bench.study`` ``TraceAnnotation``; the studies after run untraced."""

    def __init__(self, log_dir: str, n: int):
        self.log_dir, self.left, self.on = log_dir, n, False

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.log_dir,
                                 profiler_options=_quiet_profiler())
        self.on = True
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            self.on = False

    def study(self):
        import jax
        from trace_reduce import STUDY
        return (jax.profiler.TraceAnnotation(STUDY) if self.on
                else nullcontext())

    def study_done(self):
        """Stops the profiler after the ``n``-th study, outside its wall
        clock."""
        self.left -= 1
        if not self.left:
            self.__exit__()


def window(run, lrs, seconds, counters, tracer=None) -> tuple:
    """Back-to-back studies for ``seconds``, ending with the study in
    progress.  Returns ``(wall seconds, studies, attempted, failed)``; a
    traced study keeps its run report, which is read after the window."""
    studies, attempted, failed = [], 0, 0
    t0 = time.perf_counter()
    while True:
        lr = lrs[attempted % len(lrs)]
        attempted += 1
        n0, gc0 = counters.compiles, counters.gc_s
        use0 = resource.getrusage(resource.RUSAGE_SELF)
        traced = bool(tracer and tracer.on)
        s0 = time.perf_counter()
        try:
            with tracer.study() if tracer else nullcontext():
                res = run(lr)
        except Exception as exc:  # a study that raises counts as failed
            print(f"study {attempted - 1} raised {exc!r}", file=sys.stderr,
                  flush=True)
            res = None
        wall = time.perf_counter() - s0
        use = resource.getrusage(resource.RUSAGE_SELF)
        if tracer:
            tracer.study_done()
        compiled = counters.compiles - n0
        if res is None or compiled:
            failed += 1
        if res is not None:
            # the first study of each learning rate is the sample compared
            # with the reference; holding every answer for the whole
            # window would grow the host heap the later studies allocate in
            first = all(s["lr"] != lr for s in studies)
            # host CPU seconds (all threads) and garbage collection tell a
            # stall of this process's own work from one in which it waited
            studies.append({"lr": lr, "wall": wall, "compiled": compiled,
                            "cpu": (use.ru_utime + use.ru_stime
                                    - use0.ru_utime - use0.ru_stime),
                            "gc": counters.gc_s - gc0,
                            "phases": dict(res.report.phases),
                            "rounds": len(res.rounds),
                            "report": res.report if traced else None,
                            "answer": answer(res) if first else None})
        if time.perf_counter() - t0 >= seconds:
            return time.perf_counter() - t0, studies, attempted, failed


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def check(cfg, traffic, seed, studies) -> tuple:
    """Numbers of the worst of ``studies`` (one per learning rate)
    against the reference at the stated storage width, and whether all
    are within the configuration's limits.  The reference starts once the
    window's device results are freed, so that a large model's fits."""
    import jax

    import compare
    import reference
    gc.collect()
    live = jax.live_arrays()
    print(f"before the reference: {len(live)} device arrays live, "
          f"{sum(a.nbytes for a in live)} B", flush=True)
    del live
    world = reference.World(cfg, seed)
    storage = cfg["scenario"]["ring_dtype"]
    start = world.start(storage)
    worst = {k: 0.0 for k in cfg["correct"]["limits"]}
    for s in studies:
        ref = world.answer(s["lr"], traffic["eval_every"], storage)
        nums = compare.numbers(s["answer"], ref, start)
        worst = {k: max(worst[k], nums[k]) for k in worst}
        leaves = compare.leaf_readings(s["answer"]["final"], ref["final"],
                                       start)
        print(f"compared lr={s['lr']!r}: {nums}; model by leaf {leaves}",
              flush=True)
    ok = bool(studies) and compare.within(worst, cfg["correct"]["limits"])
    return worst, ok


def load_readers(spec, cell) -> list:
    """``[(metric entry, module)]``: the reader ``bench/metrics/<name>.py``
    of each per-layer metric that ``cell`` reports."""
    out = []
    for m in spec["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        path = os.path.join(ROOT, "bench", "metrics", m["name"] + ".py")
        found = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(found)
        found.loader.exec_module(mod)
        out.append((m, mod))
    return out


def reduce_trace(events, readers) -> dict:
    """The trace reduced over the default named scopes and kernels and
    those the readers declare (``SCOPES``, ``KERNELS``), in that order."""
    import trace_reduce as tr
    scopes, kernels = list(tr.SCOPES), list(tr.KERNELS)
    for _, mod in readers:
        scopes += [s for s in getattr(mod, "SCOPES", ()) if s not in scopes]
        kernels += [k for k in getattr(mod, "KERNELS", ())
                    if k not in kernels]
    return tr.reduce(events, tuple(scopes), tuple(kernels))


def read_metrics(readers, ctx) -> dict:
    """Each per-layer metric from its reader's ``read``; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m, mod in readers:
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks_of(kind: str) -> dict:
    """The chip's published peaks (``bench/peaks.json``); a device that is
    not in the table is an error."""
    table = load_json("bench", "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}; "
                         f"known: {', '.join(sorted(table))}")
    return table[kind]


def run_cell(spec, cell, cfg, traffic, seed, seconds, traced, devs,
             counters, cache, peaks, run_study=None) -> dict:
    """One run of the cell after the device check; returns the result
    object.  ``run_study(lr)`` defaults to the program's ``run_scenario``
    at the cell's configuration."""
    import jax

    import models
    models.of(cfg)          # a missing model fails here, before any study
    if run_study is None:
        from repro.core.scenarios import run_scenario
        sc = scenario(cfg)

        def run_study(lr):
            with jax.default_matmul_precision(cfg["precision"]["matmul"]):
                return run_scenario(sc, engine=cfg["engine"], seed=seed,
                                    eval_every=traffic["eval_every"], lr=lr)
    lrs = lr_cycle(cfg, traffic, seed)
    t_warm = time.perf_counter()
    phases = []
    for lr in lrs[:WARM_UP]:
        warm = run_study(lr)
        phases.append(", ".join(f"{k} {v!r} s"
                                for k, v in warm.report.phases.items()))
    warm_s = time.perf_counter() - t_warm
    P = sum(x.size for x in jax.tree_util.tree_leaves(warm.final_params))
    del warm
    setup_s = time.perf_counter() - T_START
    setup = {"compile_s": counters.compile_s, "compiles": counters.compiles,
             "cache_hits": counters.cache_hits}
    print(f"set-up: {setup_s!r} s, of which {t_warm - T_START!r} s imports "
          f"and device start, {warm_s!r} s the {WARM_UP} warm-up studies "
          f"({'; '.join(phases)}); "
          f"compile cache {cache}; "
          f"{setup['compiles']} executables built, {setup['cache_hits']} "
          f"served by the cache, {setup['compile_s']!r} s compiling; "
          f"{P} parameters", flush=True)

    trace_dir = os.path.join(OUT, "trace", f"{cell['name']}-{seed}")
    with ExitStack() as stack:
        tracer = None
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = stack.enter_context(Tracer(trace_dir, TRACED_STUDIES))
            stack.enter_context(Spans(load_json("bench", "spans.json")))
        wall, studies, attempted, failed = window(
            run_study, lrs, seconds, counters, tracer)
    mem = memory_peak(devs)
    rounds = sum(s["rounds"] for s in studies
                 if s["rounds"] == cfg["scenario"]["rounds"])
    failed += sum(s["rounds"] != cfg["scenario"]["rounds"] for s in studies)
    for i, s in enumerate(studies):
        ph = s["phases"]
        print(f"study {i}: lr={s['lr']!r} wall_s={s['wall']!r} "
              f"no_phase_s={s['wall'] - sum(ph.values())!r} "
              + " ".join(f"{k}_s={v!r}" for k, v in ph.items())
              + f" rounds={s['rounds']} compiles={s['compiled']}"
              f" cpu_s={s['cpu']!r} gc_s={s['gc']!r}",
              flush=True)
    print(f"window: {wall!r} s, {attempted} studies, {failed} failed, "
          f"{rounds} rounds; peak HBM {mem} B", flush=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {}
    if traced:
        import trace_reduce as tr
        readers = load_readers(spec, cell)
        red = reduce_trace(tr.load(trace_dir), readers)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(
            studies=studies, trace=red, cfg=cfg, traffic=traffic,
            peaks=peaks, setup=setup,
            reports=[s["report"].to_json() for s in studies if s["report"]])
        metrics = read_metrics(readers, ctx)
        if red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = {
                "device_ops": red["device_ops"],
                "idle_gaps": sorted(([k, v] for k, v in red["idle"].items()),
                                    key=lambda kv: -kv[1])[:10]}
    else:
        metrics = {"rounds_per_s": {"value": rounds / wall,
                                    "unit": "rounds/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    sample = [s for s in studies if s["answer"] is not None
              and s["rounds"] == cfg["scenario"]["rounds"]]
    t_ref = time.perf_counter()
    worst, ok = check(cfg, traffic, seed, sample)
    print(f"reference and comparison: {time.perf_counter() - t_ref!r} s "
          f"for {len(sample)} learning rates", flush=True)
    limits = cfg["correct"]["limits"]
    for k in limits:
        print(f"check {k}: {worst[k]!r} limit {limits[k]!r}",
              file=sys.stderr, flush=True)
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    line.update(out)
    line["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                      for k in limits}
    return line


def _quiet_profiler():
    """Device and host-span tracing only: the Python tracer would record
    every function call and slow the host it measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, traffic = load_cell(args.workload)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import jax  # noqa: F401
    counters = Counters()
    devs = require_devices(cell["chips"])
    peaks = peaks_of(devs[0].device_kind)
    cache = configure_cache()
    line = run_cell(spec, cell, cfg, traffic, args.seed, args.seconds,
                    bool(args.trace), devs, counters, cache, peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
