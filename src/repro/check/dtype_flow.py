"""Jaxpr-level dtype-flow checker (DTF rules, DESIGN.md §13).

The bf16 snapshot-ring contract (DESIGN.md §12) is: bf16 is a *storage*
dtype only — ring rows and upload buffers may hold bf16, but every
arithmetic consumer (the mix/aggregation chain, the trainer, evaluation
heads) must first widen to f32.  The engines uphold this by construction
today; this checker re-derives it from the staged programs themselves, so
a future edit that, say, dots a bf16 upload against f32 weights (silently
truncating the accumulation on some backends) is caught at check time, not
in a golden-digest bisect.

The probes stage the *real* engine programs via the engines' ``_stage_run``
helpers and walk ``jax.make_jaxpr``'s output: bf16 may flow through data
*movement* primitives only; any arithmetic primitive touching bf16 is
DTF001 (dot/conv — an MXU contraction in reduced precision) or DTF002
(everything else); in an f32-ring program any bf16 anywhere is DTF003.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.check.findings import Finding

# primitives that relocate or reinterpret values without doing arithmetic
# on them — the only places a storage dtype is allowed to appear
MOVEMENT_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "slice",
    "dynamic_slice", "dynamic_update_slice", "gather", "scatter",
    "concatenate", "pad", "select_n", "convert_element_type", "copy",
    "stop_gradient", "optimization_barrier", "rev", "device_put",
    "copy_p",
})
CONTRACTION_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
# structured control flow / call primitives: their bodies are walked
# separately, so the wrapper eqn itself is not an arithmetic consumer
_WRAPPER_PRIMS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "xla_call", "scan", "while",
    "cond", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "remat", "remat2", "checkpoint", "custom_lin", "pallas_call",
})


def _sub_jaxprs(params):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _has_bf16(var) -> bool:
    dt = getattr(getattr(var, "aval", None), "dtype", None)
    return dt == jnp.bfloat16


def walk_jaxpr(jaxpr, visit) -> None:
    """Depth-first over every eqn, recursing into sub-jaxpr params
    (pjit bodies, scan/while carries, cond branches, custom-vjp calls)."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _sub_jaxprs(eqn.params):
            walk_jaxpr(sub, visit)


def check_jaxpr(jaxpr, *, allow_bf16: bool, path: str) -> list[Finding]:
    """DTF findings for one (closed or open) jaxpr.  One finding per
    (rule, primitive) with an occurrence count — a single bad chain shows
    up in hundreds of eqns and a per-eqn flood would bury the report."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    counts: dict = {}

    def visit(eqn):
        prim = eqn.primitive.name
        touches = (any(_has_bf16(v) for v in eqn.invars)
                   or any(_has_bf16(v) for v in eqn.outvars))
        if not touches or prim in _WRAPPER_PRIMS:
            return
        if not allow_bf16:
            rule = "DTF003"
        elif prim in CONTRACTION_PRIMS:
            rule = "DTF001"
        elif prim in MOVEMENT_PRIMS:
            return
        else:
            rule = "DTF002"
        counts[(rule, prim)] = counts.get((rule, prim), 0) + 1

    walk_jaxpr(jaxpr, visit)
    out = []
    for (rule, prim), n in sorted(counts.items()):
        what = {"DTF001": "contraction consumes bf16 operands",
                "DTF002": "arithmetic on bf16 (storage dtype escaped "
                          "into compute)",
                "DTF003": "bf16 present in an f32-ring program"}[rule]
        out.append(Finding(rule, path, 0,
                           f"{what}: primitive {prim!r} x{n}"))
    return out


# ---------------------------------------------------------------------------
# engine probes — stage the real programs and check their jaxprs
# ---------------------------------------------------------------------------
def _small_fleet(k: int = 4):
    import dataclasses

    from repro.channel.params import ChannelParams
    from repro.data import partition_vehicles, synth_mnist

    tr_i, tr_l, _, _ = synth_mnist(n_train=240, n_test=16, seed=0,
                                   noise=0.35)
    p = dataclasses.replace(ChannelParams(), K=k)
    veh = partition_vehicles(tr_i, tr_l, p, seed=0, scale=0.03)
    return veh, p


def _jit_probe(ring_dtype: str) -> list[Finding]:
    from repro.core.jit_engine import _stage_run

    veh, p = _small_fleet()
    prog, args, *_ = _stage_run(
        veh, scheme="mafl", rounds=6, l_iters=1, lr=0.05, params=p,
        seed=0, eval_every=3, use_kernel=False, init_params=None,
        interpretation="mixing", batch_size=32, mesh=None, selection=None,
        ring_dtype=ring_dtype)
    jaxpr = jax.make_jaxpr(prog)(*args)
    return check_jaxpr(jaxpr, allow_bf16=ring_dtype == "bf16",
                       path=f"<probe:jit-{ring_dtype}>")


def _corridor_probe(ring_dtype: str) -> list[Finding]:
    import dataclasses

    from repro.core.scenarios import build_world, get_scenario
    from repro.corridor.engine import _stage_run

    sc = dataclasses.replace(get_scenario("corridor-quick-r2-k8"),
                             rounds=6, l_iters=1, ring_dtype=ring_dtype)
    veh, _, _, p = build_world(sc, seed=0)
    prog, args, *_ = _stage_run(
        sc, veh, p, seed=0, eval_every=3, interpretation="mixing",
        use_kernel=False, batch_size=32, mesh=None, record_cohorts=False,
        init_params=None, selection=None)
    jaxpr = jax.make_jaxpr(prog)(*args)
    return check_jaxpr(jaxpr, allow_bf16=ring_dtype == "bf16",
                       path=f"<probe:corridor-{ring_dtype}>")


def probe_dtype_flow() -> list[Finding]:
    """Stage four engine configurations and dtype-check their jaxprs:
    jit f32 (must be bf16-free), jit bf16 and corridor bf16 (bf16 in
    storage roles only), corridor f32 (bf16-free)."""
    findings: list[Finding] = []
    findings += _jit_probe("f32")
    findings += _jit_probe("bf16")
    findings += _corridor_probe("f32")
    findings += _corridor_probe("bf16")
    return findings
