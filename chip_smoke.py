"""Smoke run of the MAFL device engines on a TPU.

    python chip_smoke.py             # one chip: kernel, main path, conformance
    python chip_smoke.py --chips 4   # the corridor's "rsu" mesh on four chips

One chip runs, in order:

1. the fused ``ring_agg`` kernel at the paper CNN's width (P = 422,016)
   against its jnp reference, f32 and bf16 uploads, U in {1, 8, 30};
2. the main path through ``run_scenario`` at full model width and the
   largest registered worlds — ``fleet-k10000`` on the jit engine and
   ``corridor-r8-k4000`` on the corridor engine — each cold, then warm
   (the warm run must compile nothing);
3. each device engine against its host oracle on a short world, traces
   exactly and final parameters within ``CONFORMANCE_ATOL``.

``--chips 4`` runs only ``corridor-r8-k4000`` with its packed cohort
stack sharded two RSUs per chip, and the same world unsharded on one
device.

Everything runs in this one process and nothing is caught: any failed
check is a non-zero exit.  Without a TPU the script exits before any
phase.  The last line of standard output is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

KERNEL_P = 422_016          # the paper CNN packed into lane-aligned rows
KERNEL_UPLOADS = (1, 8, 30)
# the bf16 uploads widen to f32 before the same f32 chain, so the kernel
# and the jnp reference should agree to f32 rounding at most
BF16_KERNEL_ATOL = 1e-5
# the repo's own CPU conformance bound for real-CNN training across
# engines (tests/test_engine_conformance.py, real-CNN small world)
CONFORMANCE_ATOL = 2e-3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA executables built in this process (fresh or loaded from
    the persistent cache) — a warm run must add none."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if event == BACKEND_COMPILE:
            self.n += 1


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform "
                 f"{devs[0].platform!r}; this script has no CPU fallback")
    print(f"jax {jax.__version__}, device_kind {devs[0].device_kind!r}, "
          f"{len(devs)} device(s)", flush=True)
    return devs


def max_abs_diff(a, b) -> float:
    import jax
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def kernel_phase(P: int = KERNEL_P, seed: int = 0):
    import jax
    import jax.numpy as jnp

    from repro.kernels.dispatch import kernel_report, select_impl
    from repro.kernels.weighted_agg import ops, ref

    mode = select_impl(kernel_report("weighted_agg.ring_agg_2d"),
                       fallback="ref")
    if mode != "compiled":
        raise RuntimeError(f"ring_agg resolved to {mode!r}, not 'compiled'")
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(P), jnp.float32)
    ref_chain = jax.jit(ref.ring_agg)
    for dtype in (jnp.float32, jnp.bfloat16):
        for U in KERNEL_UPLOADS:
            locs = jnp.asarray(rng.standard_normal((U, P)),
                               jnp.float32).astype(dtype)
            c = rng.uniform(0.5, 1.0, U)
            coeffs = jnp.asarray(np.stack([c, 1.0 - c], axis=1), jnp.float32)
            got = np.asarray(ops.ring_agg(g, locs, coeffs))
            want = np.asarray(ref_chain(g, locs, coeffs))
            err = float(np.max(np.abs(got - want)))
            name = jnp.dtype(dtype).name
            print(f"kernel ring_agg {name} U={U} P={P}: mode={mode} "
                  f"bitwise={np.array_equal(got, want)} max_abs={err!r}",
                  flush=True)
            if not np.isfinite(got).all():
                raise RuntimeError(f"ring_agg {name} U={U}: non-finite")
            if dtype == jnp.float32 and not np.array_equal(got, want):
                raise RuntimeError(
                    f"ring_agg f32 U={U} is not bitwise equal to the "
                    f"sequential reference (max_abs {err!r})")
            if err > BF16_KERNEL_ATOL:
                raise RuntimeError(f"ring_agg {name} U={U}: max_abs {err!r} "
                                   f"> {BF16_KERNEL_ATOL}")


def main_path_phase(name: str, engine: str, counter: CompileCounter):
    """Cold then warm ``run_scenario``; the engines raise on their own
    divergence guards (pop order, serving RSU, event times, bf16
    finiteness)."""
    import jax

    from repro.core.flat import ParamLayout
    from repro.core.scenarios import get_scenario, run_scenario

    sc = get_scenario(name)
    for run in ("cold", "warm"):
        n0 = counter.n
        t0 = time.perf_counter()
        r = run_scenario(name, engine=engine)
        wall = time.perf_counter() - t0
        compiles = counter.n - n0
        mem = r.report.memory
        if "device_peak_bytes_in_use" not in mem:
            raise RuntimeError(f"{name}: the device reported no "
                               f"peak_bytes_in_use ({sorted(mem)})")
        leaves = jax.tree_util.tree_leaves(r.final_params)
        if not all(np.isfinite(np.asarray(x)).all() for x in leaves):
            raise RuntimeError(f"{name}: non-finite final parameters")
        print(f"main {name} engine={engine} {run}: wall_s={wall!r} "
              f"K={sc.K} P={ParamLayout.from_tree(r.final_params).P} "
              f"rounds={len(r.rounds)} compiles={compiles} "
              f"device_peak_bytes_in_use={mem['device_peak_bytes_in_use']} "
              f"final_acc={r.final_accuracy()!r}", flush=True)
        if run == "warm" and compiles:
            raise RuntimeError(f"{name}: the warm run compiled {compiles} "
                               "new executable(s)")


def compare_runs(label: str, a, b, atol: float, fields=("round", "vehicle")):
    """Identical event traces, final parameters within ``atol``."""
    ta = [tuple(getattr(x, f) for f in fields) for x in a.rounds]
    tb = [tuple(getattr(x, f) for f in fields) for x in b.rounds]
    err = max_abs_diff(a.final_params, b.final_params)
    print(f"{label}: rounds={len(ta)} traces_equal={ta == tb} "
          f"params_max_abs={err!r} atol={atol}", flush=True)
    if ta != tb:
        raise RuntimeError(f"{label}: event traces differ")
    if not err <= atol:
        raise RuntimeError(f"{label}: final parameters differ by {err!r} "
                           f"> {atol}")


def conformance_phase(fleet=("fleet-k1000", 10),
                      corridor=("corridor-r4-k400", 8)):
    """Device engines against their host oracles.  Asserted under
    ``highest`` matmul precision, where both sides compute the CNN in
    f32; the default-precision error is printed alongside."""
    import jax

    from repro.core.scenarios import run_scenario

    f_name, f_rounds = fleet
    c_name, c_rounds = corridor
    for precision in ("default", "highest"):
        atol = CONFORMANCE_ATOL if precision == "highest" else float("inf")
        with jax.default_matmul_precision(precision):
            kw = dict(rounds=f_rounds, eval_every=f_rounds)
            compare_runs(f"conformance {f_name} jit vs batched "
                         f"precision={precision}",
                         run_scenario(f_name, engine="jit", **kw),
                         run_scenario(f_name, engine="batched", **kw), atol)
            kw = dict(rounds=c_rounds, eval_every=c_rounds)
            compare_runs(f"conformance {c_name} corridor vs serial "
                         f"precision={precision}",
                         run_scenario(c_name, engine="corridor", **kw),
                         run_scenario(c_name, engine="serial", **kw), atol,
                         fields=("round", "vehicle", "rsu"))


def rsu_mesh_phase(devs, n_chips: int, name: str = "corridor-r8-k4000"):
    """The corridor's packed cohort stack sharded over an ``"rsu"`` mesh
    against the same world unsharded on one device."""
    from jax.sharding import Mesh

    from repro.core.scenarios import run_scenario

    if len(devs) < n_chips:
        raise RuntimeError(f"--chips {n_chips} needs {n_chips} devices, "
                           f"found {len(devs)}")
    mesh = Mesh(np.asarray(devs[:n_chips]), ("rsu",))
    t0 = time.perf_counter()
    sharded = run_scenario(name, engine="corridor", mesh=mesh)
    t1 = time.perf_counter()
    single = run_scenario(name, engine="corridor")
    t2 = time.perf_counter()
    print(f"rsu-mesh {name}: sharded over {n_chips} wall_s={t1 - t0!r}, "
          f"one device wall_s={t2 - t1!r}", flush=True)
    compare_runs(f"rsu-mesh {name} sharded vs one device", sharded, single,
                 CONFORMANCE_ATOL, fields=("round", "vehicle", "rsu"))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the rsu-mesh corridor phase")
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import configure_compile_cache

    devs = require_tpu()
    print(f"compile cache: {configure_compile_cache()}", flush=True)

    if args.chips == 4:
        phases = [("rsu-mesh", lambda: rsu_mesh_phase(devs, args.chips))]
    else:
        counter = CompileCounter()
        phases = [
            ("kernel", kernel_phase),
            ("main fleet-k10000",
             lambda: main_path_phase("fleet-k10000", "jit", counter)),
            ("main corridor-r8-k4000",
             lambda: main_path_phase("corridor-r8-k4000", "corridor",
                                     counter)),
            ("conformance", conformance_phase)]
    for label, phase in phases:
        t0 = time.perf_counter()
        phase()
        print(f"phase {label}: wall_s={time.perf_counter() - t0!r}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
