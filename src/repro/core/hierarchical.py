"""Hierarchical multi-pod MAFL (beyond paper, DESIGN.md §7).

Maps the vehicular hierarchy onto the production mesh: each **pod is one RSU
cohort** running the paper's asynchronous aggregation locally; a cross-pod
EMA periodically reconciles the cohort models (the "cloud" tier the paper
alludes to but does not model).  Built on ``shard_map`` over the ``pod``
axis so each cohort's Eq. 10+11 update stays pod-local (zero inter-pod
traffic) and only the reconciliation step touches ICI.

Used by ``tests/test_hierarchical.py`` and the multi-pod dry-run notes in
EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pod_local_mafl(global_params, local_params, beta, weight):
    """Eq. 10+11 per pod — identical math to ``aggregation.mafl_update`` but
    expressed per-shard so it composes under ``shard_map``."""
    alpha = jnp.clip((1.0 - beta) * weight, 0.0, 1.0)
    return jax.tree_util.tree_map(
        lambda g, l: ((1 - alpha) * g.astype(jnp.float32) +
                      alpha * l.astype(jnp.float32)).astype(g.dtype),
        global_params, local_params)


def ema_toward(params, target, tau: float, use_kernel: bool = False):
    """One EMA step of every leaf toward ``target``:
    ``(1 - tau) * params + tau * target``.  ``tau = 1`` is plain
    assignment (FedAvg-style consensus); ``tau < 1`` keeps each cohort's
    identity between reconciliations (the cloud tier's EMA mode).
    ``use_kernel`` routes the mix through the fused Pallas
    ``weighted_agg`` kernel (beta = 1 - tau, weight = 1)."""
    if use_kernel:
        from repro.kernels.weighted_agg import ops as agg_ops
        return agg_ops.weighted_agg_tree(params, target, 1.0 - tau, 1.0)
    return jax.tree_util.tree_map(
        lambda g, c: ((1.0 - tau) * g.astype(jnp.float32) +
                      tau * c.astype(jnp.float32)).astype(g.dtype),
        params, target)


def cross_pod_reconcile(params, mesh, pod_axis: str = "pod",
                        shard_spec: P | None = None, tau: float = 1.0,
                        use_kernel: bool = False):
    """Reconcile the per-pod cohort models over the pod axis — the only
    inter-pod traffic in the hierarchy.  One pmean per leaf produces the
    cross-pod mean; ``tau`` selects the mode:

    - ``tau = 1`` (default, FedAvg): every pod adopts the mean outright —
      the original consensus behavior.
    - ``tau < 1`` (EMA): each pod moves a ``tau`` fraction toward the mean,
      keeping some cohort identity between reconciliations (what the
      corridor subsystem calls "ema" mode, DESIGN.md §10).

    ``shard_spec`` describes how each leaf's leading dim is laid out
    (default: sharded over (pod, data) — the FSDP layout the launcher
    uses); the pmean averages corresponding shards across pods."""
    spec = shard_spec if shard_spec is not None else P((pod_axis, "data"))

    def step(t):
        mean = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, pod_axis), t)
        if tau == 1.0:
            return mean
        return ema_toward(t, mean, tau, use_kernel=use_kernel)

    fn = jax.shard_map(step, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return fn(params)


def reconcile_models(models):
    """Host-level analogue of :func:`cross_pod_reconcile` for the serial
    multi-RSU reference engine (``corridor.reference``): plain mean of N
    cohort models held as separate pytrees (no mesh required).  EMA-mode
    callers apply :func:`ema_toward` per cohort on top of this mean."""
    n = len(models)
    return jax.tree_util.tree_map(
        lambda *xs: (sum(x.astype(jnp.float32) for x in xs) / n).astype(
            xs[0].dtype), *models)


def make_hierarchical_round(mesh, beta: float, pod_axis: str = "pod",
                            reconcile_every: int = 4):
    """Returns ``round_fn(step, cohort_models, upload, weight)`` that applies
    the pod-local MAFL update every call and the cross-pod pmean every
    ``reconcile_every`` rounds (jit-able; ``step`` is a traced scalar)."""

    def round_fn(step, cohort_models, upload, weight):
        updated = pod_local_mafl(cohort_models, upload, beta, weight)

        def do_reconcile(t):
            return cross_pod_reconcile(t, mesh, pod_axis)

        return jax.lax.cond(
            (step % reconcile_every) == reconcile_every - 1,
            do_reconcile, lambda t: t, updated)

    return round_fn
