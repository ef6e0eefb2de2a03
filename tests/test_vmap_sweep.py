"""Multi-world vmap sweep conformance (DESIGN.md §15).

The contract under test: every world slice w of an ``engine="vmap"``
batch is BITWISE the run ``engine="jit"`` would produce for that world
solo — same final-parameter digest, same accuracy/loss history, same
event structure (pop order, rounds, vehicles).  That holds because the
sweep program splits its scan at the union of all worlds' boundaries
(scan splitting is carry-transparent), keeps batch-uniform channel
scalars as trace-time constants (varied ones become traced ``[W]``
inputs), and trains timeline-groups through the exact solo wave-train
closure (nested vmap for multi-world groups).

One carve-out, stated rather than hidden: the *reported delay floats*
in the event trace (upload/train delay, weight) are pinned to f32-ulp
closeness, not bit equality — the union segmentation compiles the scan
body in a different fusion context than the solo program, and XLA:CPU's
context-dependent FMA contraction can move those reported expressions
by one ulp (observed: 2e-10 relative on ``upload_delay``) while the
aggregation path itself stays bit-identical (the digest assertions
below are exact and would fail otherwise).

Also pinned here: the padded plan-table stacking contract (PLN003), the
SweepSpec grid order, and every unsupported-configuration gate.
"""
import dataclasses

import numpy as np
import pytest

from repro.checkpointing.checkpoint import tree_digest
from repro.core.scenarios import (SweepSpec, get_scenario, run_scenario,
                                  run_sweep)
from repro.core.sweep import stack_plan_tables


def _assert_world_matches_solo(vm_r, solo_r, label=""):
    assert tree_digest(vm_r.final_params) == tree_digest(
        solo_r.final_params), f"final params diverge {label}"
    # discrete event structure: exact
    assert [(rec.round, rec.vehicle) for rec in vm_r.rounds] == \
        [(rec.round, rec.vehicle) for rec in solo_r.rounds], \
        f"pop order diverges {label}"
    # reported delay floats: f32-ulp (see module docstring)
    for fld in ("time", "upload_delay", "train_delay", "weight"):
        a = np.array([getattr(rec, fld) for rec in vm_r.rounds])
        b = np.array([getattr(rec, fld) for rec in solo_r.rounds])
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=0,
                                   err_msg=f"{fld} diverges {label}")
    assert vm_r.acc_history == solo_r.acc_history, f"acc diverges {label}"
    assert vm_r.loss_history == solo_r.loss_history


# ---------------------------------------------------------------------------
# bitwise conformance
# ---------------------------------------------------------------------------
def test_w1_batch_is_bitwise_the_solo_jit_run():
    """A W=1 sweep degenerates to the solo program: same bits out."""
    solo = run_scenario("quick-k5", engine="jit", seed=1, eval_every=5,
                        rounds=10)
    vm = run_scenario("quick-k5", engine="vmap", seed=1, eval_every=5,
                      rounds=10)
    _assert_world_matches_solo(vm, solo, "(W=1 quick-k5)")
    assert vm.report.engine == "vmap"
    assert vm.report.channels["n_worlds"] == 1


def test_heterogeneous_beta_seed_batch_bitwise():
    """W=4 (2 betas x 2 seeds) — every slice matches its solo run, and
    same-seed worlds share a timeline group (beta never splits one)."""
    spec = SweepSpec(
        scenario="quick-k5", seeds=(0, 1),
        variants=tuple((("channel_overrides", (("beta", b),)),)
                       for b in (0.3, 0.7)),
        overrides=(("rounds", 8),), eval_every=4)
    vm = run_sweep(spec)
    solo = run_sweep(spec, engine="jit")
    assert len(vm) == len(solo) == 4
    for w, (v, s) in enumerate(zip(vm, solo)):
        _assert_world_matches_solo(v, s, f"(world {w})")
        assert v.report.channels["world_index"] == w
        assert v.report.channels["n_worlds"] == 4
    # worlds 0/2 are seed 0 at beta 0.3/0.7: identical timelines, one group
    groups = [r.report.channels["group"] for r in vm]
    assert groups[0] == groups[2] and groups[1] == groups[3]
    assert groups[0] != groups[1]


def test_selection_heterogeneous_batch_bitwise():
    """Admit-all and weighted-topk worlds coexist in one batch."""
    base = dataclasses.replace(get_scenario("quick-k5"), rounds=8)
    sel = dataclasses.replace(base, selection="weighted-topk",
                              selection_k=3, resel_every=4)
    spec = SweepSpec(scenario=base, seeds=(0,),
                     variants=((), (("selection", "weighted-topk"),
                                    ("selection_k", 3),
                                    ("resel_every", 4))),
                     eval_every=4)
    vm = run_sweep(spec)
    _assert_world_matches_solo(
        vm[0], run_scenario(base, engine="jit", seed=0, eval_every=4),
        "(admit-all)")
    _assert_world_matches_solo(
        vm[1], run_scenario(sel, engine="jit", seed=0, eval_every=4),
        "(weighted-topk)")
    # the selected world really ran under the k=3 admission cap
    assert len({r.vehicle for r in vm[1].rounds}) <= 3


@pytest.mark.slow
def test_paper_k10_grid_bitwise_vs_serial():
    """ISSUE acceptance pin: the Fig. 5-shaped grid on paper-k10."""
    spec = SweepSpec(
        scenario="paper-k10", seeds=(0, 1),
        variants=tuple((("channel_overrides", (("beta", b),)),)
                       for b in (0.1, 0.9)),
        overrides=(("rounds", 8), ("l_iters", 2)), eval_every=4)
    vm = run_sweep(spec)
    solo = run_sweep(spec, engine="jit")
    for w, (v, s) in enumerate(zip(vm, solo)):
        _assert_world_matches_solo(v, s, f"(paper-k10 world {w})")


@pytest.mark.slow
def test_fleet_k100_bitwise_vs_serial():
    spec = SweepSpec(scenario="fleet-k100", seeds=(0, 1),
                     overrides=(("rounds", 10), ("l_iters", 1)),
                     eval_every=5)
    vm = run_sweep(spec)
    solo = run_sweep(spec, engine="jit")
    for w, (v, s) in enumerate(zip(vm, solo)):
        _assert_world_matches_solo(v, s, f"(fleet-k100 world {w})")


# ---------------------------------------------------------------------------
# SweepSpec grid + plan-table stacking
# ---------------------------------------------------------------------------
def test_sweepspec_world_order_is_variant_major():
    spec = SweepSpec(scenario="quick-k5", seeds=(0, 1, 2),
                     variants=tuple((("channel_overrides", (("beta", b),)),)
                                    for b in (0.2, 0.8)),
                     overrides=(("rounds", 6),))
    worlds = spec.worlds()
    assert len(worlds) == 6
    assert [seed for _sc, seed in worlds] == [0, 1, 2, 0, 1, 2]
    betas = [dict(sc.channel_overrides)["beta"] for sc, _ in worlds]
    assert betas == [0.2, 0.2, 0.2, 0.8, 0.8, 0.8]
    assert all(sc.rounds == 6 for sc, _ in worlds)


def test_stack_plan_tables_accepts_uniform_rejects_ragged():
    a = {"veh": np.zeros((8,), np.int32),
         "times": np.ones((8,), np.float32)}
    b = {k: v.copy() for k, v in a.items()}
    out = stack_plan_tables([a, b])
    assert out["veh"].shape == (2, 8)
    # ragged shapes must be rejected with the PLN003 pointer, never
    # silently broadcast
    bad = dict(b, times=np.ones((9,), np.float32))
    with pytest.raises(ValueError, match="PLN003"):
        stack_plan_tables([a, bad])
    with pytest.raises(ValueError, match="PLN003"):
        stack_plan_tables([a, {"veh": a["veh"]}])
    with pytest.raises(ValueError):
        stack_plan_tables([])


# ---------------------------------------------------------------------------
# unsupported-configuration gates (clear errors, never silent fallback)
# ---------------------------------------------------------------------------
def test_run_sweep_rejects_unknown_engine():
    with pytest.raises(ValueError, match="vmap.*jit|jit.*vmap"):
        run_sweep(SweepSpec(scenario="quick-k5"), engine="batched")


def test_vmap_rejects_nonuniform_rounds():
    base = get_scenario("quick-k5")
    spec = SweepSpec(scenario=base, seeds=(0,),
                     variants=((("rounds", 6),), (("rounds", 8),)))
    with pytest.raises(ValueError, match="uniform rounds"):
        run_sweep(spec)


def test_vmap_rejects_corridor_and_fedbuff_and_varied_alpha():
    with pytest.raises(ValueError, match="multi-RSU"):
        run_scenario("corridor-quick-r2-k8", engine="vmap", seed=0)
    with pytest.raises(ValueError, match="fedbuff"):
        run_sweep(SweepSpec(scenario="quick-k5",
                            overrides=(("scheme", "fedbuff"),)))
    spec = SweepSpec(
        scenario="quick-k5", seeds=(0,),
        variants=tuple((("channel_overrides", (("alpha", a),)),)
                       for a in (2.0, 3.0)))
    with pytest.raises(ValueError, match="alpha"):
        run_sweep(spec)


def test_vmap_rejects_metrics_and_kernel():
    with pytest.raises(ValueError, match="telemetry|metrics"):
        run_scenario("quick-k5", engine="vmap", seed=0, metrics="on")
    with pytest.raises(ValueError, match="use_kernel"):
        run_scenario("quick-k5", engine="vmap", seed=0, use_kernel=True)
