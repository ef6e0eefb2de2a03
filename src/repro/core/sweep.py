"""Multi-world vmap sweep engine: ``engine="vmap"`` (DESIGN.md §15).

Every sweep the paper demands — the Fig. 5 beta ablation, the 3-seed
averaging behind every reported curve, selection-policy comparisons — is a
set of *independent* worlds that differ only in scalars (beta, seed,
channel constants) or static plan data (admission tables).  Running them
serially pays one compiled program and one Python round-trip per world.
This engine batches W worlds through ONE compiled flat-path program:

- **World axis on the flat buffer.**  The packed ``ParamLayout`` already
  broadcasts leading batch axes, so the W models are a single ``[W, P]``
  buffer, the slot queues are ``[W, K]`` columns, and the event-loop scan
  body is ``jax.vmap`` of the solo per-world step.

- **Padded plan tables.**  The host f64 planners emit fixed-shape tables
  (``FleetPlan.tables()``, ``SelectionPlan.tables()`` — shapes depend only
  on ``(M, K)``, PLN003-probed) that stack along a leading world axis;
  ragged residue (gain-table heights) zero-pads to the batch maximum.

- **Bitwise per-world conformance.**  World ``w`` of a batch reproduces
  its solo ``engine="jit"`` run bit-for-bit — final parameters, accuracy
  history, event structure (pinned by ``tests/test_vmap_sweep.py``; the
  *reported* per-event delay floats are f32-ulp instead: the union
  segmentation changes the scan body's fusion context, and XLA:CPU's
  context-dependent FMA contraction can move reporting-only expressions
  by an ulp — holds under the default thunk runtime, the tier-1
  environment; the legacy CPU runtime loses bit equality outright, see
  EXPERIMENTS.md §Sweep).  Three rules make that possible: (1) the
  program splits its scans at the *union* of all worlds' wave/readmit/
  checkpoint boundaries — scan splitting is carry-transparent, so extra
  split points are bitwise no-ops for the other worlds; (2) a channel
  scalar equal across the batch stays a trace-time constant (the exact
  solo codepath — and a W=1 batch degenerates to the solo program), while
  a differing one becomes a traced ``[W]`` input (linear/pow-base/log uses
  only — bitwise-stable under vmap on this backend); (3) worlds sharing a
  timeline (same seed/plan/data) train as one nested ``vmap`` block,
  worlds that don't get their own solo-shaped ``_wave_train`` call.

- **Constant path-loss exponent.**  ``ChannelParams.alpha`` is a pow
  *exponent*, and XLA special-cases constant exponents (``x**2 -> x*x``,
  ``x**-0.5 -> rsqrt``) — tracing it would change every world's codegen.
  The engine therefore requires ``alpha`` uniform across the batch.

Always the in-scan mix (the CPU-default form that reproduces the golden
digests); no ``use_kernel``/``mesh``/``metrics`` —
those stay solo-tier features and are rejected loudly, never silently
dropped.  The entry points are :class:`repro.core.scenarios.SweepSpec` /
``run_sweep`` (grids over a base scenario) and ``run_scenario(...,
engine="vmap")`` (a W=1 batch).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import Mobility, slot_gain_table
from repro.core import client as client_mod
from repro.core.client import Samplers
from repro.core.jit_engine import _SUPPORTED_SCHEMES, _wave_train, plan_fleet
from repro.core.server import DEFAULT_FEDASYNC_MIX, RoundRecord


def stack_plan_tables(tables: Sequence[dict]) -> dict:
    """Stack per-world plan tables along a leading world axis.

    Every world must emit the same keys with identical ``(shape, dtype)``
    — the PLN003 invariant; a mismatch raises with the offending field
    instead of silently broadcasting."""
    if not tables:
        raise ValueError("stack_plan_tables: empty world batch")
    keys = list(tables[0])
    for i, t in enumerate(tables[1:], 1):
        if list(t) != keys:
            raise ValueError(
                f"plan tables not stackable: world 0 has fields {keys}, "
                f"world {i} has {list(t)} — planner emissions must be "
                "field-stable across worlds (rule PLN003)")
    out = {}
    for k in keys:
        arrs = [np.asarray(t[k]) for t in tables]
        base = (arrs[0].shape, arrs[0].dtype)
        for i, a in enumerate(arrs[1:], 1):
            if (a.shape, a.dtype) != base:
                raise ValueError(
                    f"plan table {k!r} not stackable: world 0 is {base}, "
                    f"world {i} is {(a.shape, a.dtype)} — planner shapes "
                    "must depend only on (M, K) (rule PLN003)")
        out[k] = np.stack(arrs)
    return out


def stack_gain_tables(ps, seeds, n_slots_list) -> np.ndarray:
    """``f32[W, S_max, K]`` slot-gain tables, zero-padded to the batch's
    tallest table — padded rows are unreachable (the Eq. 3 slot clip is
    bounded by each world's own ``n_slots``)."""
    S = max(int(n) for n in n_slots_list)
    K = ps[0].K
    out = np.zeros((len(ps), S, K), np.float32)
    for w, (p, seed, ns) in enumerate(zip(ps, seeds, n_slots_list)):
        out[w, :int(ns)] = np.asarray(slot_gain_table(p, seed, int(ns)),
                                      np.float32)
    return out


# per-world ChannelParams scalars that enter the compiled program's f32
# arithmetic.  Uniform across the batch -> trace-time constant (exact solo
# codepath); varying -> traced [W] input.  All appear linearly, as pow
# *base*, or inside log2 — lowerings that are operand-stable whether the
# scalar is a constant or a traced input (pinned by test_vmap_sweep).
def _world_scalars(p, plan) -> dict:
    return {
        "beta": float(p.beta), "gamma": float(p.gamma),
        "zeta": float(p.zeta), "v": float(p.v),
        "coverage": float(p.coverage),
        "dy2H2": float(p.d_y ** 2 + p.H ** 2),
        "p_m": float(p.p_m), "sigma2": float(p.sigma2),
        "B": float(p.B), "model_bits": float(p.model_bits),
        "n_slots": int(plan.n_slots),
    }


# ---------------------------------------------------------------------------
# the compiled multi-world program
# ---------------------------------------------------------------------------
_SWEEP_CACHE: OrderedDict = OrderedDict()
_SWEEP_CACHE_SIZE = 8


def _build_sweep_program(plans, ps, groups, *, scheme, interpretation,
                         layout, ring_dtype, eval_rounds, fedasync_mix):
    """One compiled program for the whole W-world batch.  Structure (wave
    partitions, boundary union, groups) is trace-time constant; per-world
    values (queues, gains, minibatches, varied scalars) are inputs."""
    W = len(plans)
    M = len(plans[0].veh)
    K = ps[0].K
    d_list = [np.asarray(plan.dl_round) for plan in plans]

    bf16 = ring_dtype == "bf16"
    store_dtype = jnp.bfloat16 if bf16 else jnp.float32
    store = ((lambda x: x.astype(jnp.bfloat16)) if bf16 else (lambda x: x))

    # scalar split: uniform -> closure constant, varying -> traced [W]
    scal = [_world_scalars(p, plan) for p, plan in zip(ps, plans)]
    varied_names = tuple(sorted(
        n for n in scal[0] if len({s[n] for s in scal}) > 1))
    consts = {n: (int(v) if n == "n_slots" else jnp.float32(v))
              for n, v in scal[0].items() if n not in varied_names}
    f_mix = jnp.float32(fedasync_mix)
    alpha_pl = jnp.float32(ps[0].alpha)        # uniform (validated): pow exp

    # selection (DESIGN.md §11): stacked [W, M, K] admission tables — a
    # policy-free world is the all-True row, and where(True, x, inf) == x
    # bitwise, so mixing selection and no-selection worlds is exact
    any_sel = any(plan.sel is not None and not plan.sel.is_noop
                  for plan in plans)
    any_state = any(plan.sel is not None and not plan.sel.is_noop
                    and plan.sel.spec.policy == "eps-bandit"
                    for plan in plans)
    readmit_at = []
    sel_tabs = []
    for plan in plans:
        if plan.sel is not None and not plan.sel.is_noop:
            readmit_at.append({b: np.asarray(n, np.int32)
                               for b, n, _ in plan.sel.boundaries if len(n)})
            sel_tabs.append(plan.sel.tables(M)["mask"])
        else:
            readmit_at.append({})
            sel_tabs.append(np.ones((M, K), bool))
    if any_sel:
        adm_tab = jnp.asarray(np.stack(sel_tabs))

    # rounds whose post-round [W, P] snapshot must materialize: the union
    # of every world's later-wave payload rounds plus the eval rows
    needed = set(int(x) for x in eval_rounds)
    for plan, d in zip(plans, d_list):
        for T, _s, _e in plan.waves:
            needed |= {int(d[t]) + 1 for t in T if d[t] >= 0}

    # scan-split union: every world's wave boundaries, re-admission points
    # and checkpoints.  Splitting a scan is carry-transparent, so a point
    # another world needs is a bitwise no-op for this one.
    pts = {0, M} | needed
    for plan, ra in zip(plans, readmit_at):
        for _T, s, e in plan.waves:
            pts |= {s, e}
        pts |= set(ra)
    pts = sorted(b for b in pts if 0 <= b <= M)

    # per-(group, wave-start) static training-block data, precomputed here
    # so the traced program body does no host math on plan tables (the
    # boundary lint's taint rules, DESIGN.md §13); members share the group
    # plan's partition by the grouping key
    group_train = {}
    for gi, G in enumerate(groups):
        d_g = d_list[G[0]]
        for T, s, _e in plans[G[0]].waves:
            if not len(T):
                continue
            T_np = np.asarray(T, np.int32)
            pay = tuple(int(x) for x in (d_g[T_np] + 1))
            group_train[(gi, s)] = (T_np, pay, len(set(pay)) == 1)

    # per-world trace-time constants for the boundary re-admission helper
    # (solo codepath: readmits run at trace level with baked scalars)
    wconsts = [{n: (int(v) if n == "n_slots" else jnp.float32(v))
                for n, v in s.items()} for s in scal]

    def eq36_upload_delay(gains_w, x0_w, idx, t_up, S):
        """Eq. 3-6 re-schedule pipeline — expression-for-expression the
        solo engine's (``jit_engine.eq36_upload_delay``); ``S`` resolves
        each channel scalar to the world's constant or traced value."""
        slot = jnp.clip(t_up.astype(jnp.int32), 0, S["n_slots"] - 1)
        gain = gains_w[slot, idx]
        dx = x0_w[idx] + S["v"] * t_up                        # Eq. 3
        dx = jnp.mod(dx + S["coverage"],
                     2.0 * S["coverage"]) - S["coverage"]     # re-entry wrap
        dist = jnp.sqrt(dx * dx + S["dy2H2"])                 # Eq. 4
        snr = S["p_m"] * gain * dist ** (-alpha_pl) / S["sigma2"]
        rate = S["B"] * jnp.log2(1.0 + snr)                   # Eq. 5
        return S["model_bits"] / jnp.maximum(rate, 1e-12)     # Eq. 6

    def aggregate(g_w, loc, t, cu, cl, dl_t, S):
        """One arrival's Eq. 10+11 mix on the packed [P] buffer — the solo
        in-scan form verbatim (the one the golden digests pin)."""
        if scheme == "mafl":
            weight = S["gamma"] ** (cu - 1.0) * S["zeta"] ** (cl - 1.0)
        else:
            weight = jnp.float32(1.0)
        if scheme == "mafl" and interpretation == "literal":
            new = jax.tree_util.tree_map(
                lambda a, b: (S["beta"] * a.astype(jnp.float32) +
                              (1.0 - S["beta"]) * weight *
                              b.astype(jnp.float32)).astype(a.dtype),
                g_w, loc)
            return new, weight
        if scheme == "mafl":
            alpha = jnp.clip((1.0 - S["beta"]) * weight, 0.0, 1.0)
        elif scheme == "afl":
            alpha = 1.0 - S["beta"]
        else:                                                 # fedasync
            stale = jnp.maximum(t - dl_t, 0.0)
            alpha = f_mix * (stale + 1.0) ** (-0.5)
        new = jax.tree_util.tree_map(
            lambda a, b: ((1.0 - alpha) * a.astype(jnp.float32) +
                          alpha * b.astype(jnp.float32)).astype(a.dtype),
            g_w, loc)
        return new, weight

    def program(w0s, gains, x0s, qt, qdl, qcu, qcl, g_imgs, g_labs, lrs,
                var):
        local_scan = client_mod._local_scan
        g = layout.pack(w0s)                        # f32[W, P] masters
        locals_buf = jnp.zeros((W, M, layout.P), store_dtype)
        snaps = {0: store(g)}
        rs = rc = None
        if any_state:
            rs = jnp.zeros((W, K), jnp.float32)
            rc = jnp.zeros((W, K), jnp.float32)
        traces = []

        def make_body(locals_buf):
            # fresh body per segment — locals_buf rebinds per wave (the
            # lax.scan traced-body cache pitfall, DESIGN.md §9)
            stat = {"qcl": qcl, "x0": x0s, "gains": gains,
                    "lb": locals_buf, "var": var}
            if any_sel:
                stat["adm"] = adm_tab

            def body(carry, r):
                def step_w(cw, sw):
                    # the solo flat in-scan body over one world's slices
                    S = dict(consts)
                    S.update(sw["var"])
                    g_w, qt_w, qdl_w, qcu_w = (cw["g"], cw["qt"],
                                               cw["qdl"], cw["qcu"])
                    i = jnp.argmin(qt_w)                      # pop
                    t, cu, cl, dl_t = (qt_w[i], qcu_w[i], sw["qcl"][i],
                                       qdl_w[i])
                    g_w, weight = aggregate(g_w, sw["lb"][r], t, cu, cl,
                                            dl_t, S)
                    out = {"g": g_w}
                    if any_state:
                        rew = (S["gamma"] ** (cu - 1.0)
                               * S["zeta"] ** (cl - 1.0))
                        out["rs"] = cw["rs"].at[i].add(rew)
                        out["rc"] = cw["rc"].at[i].add(1.0)
                    t_up = t + cl
                    cu_new = eq36_upload_delay(sw["gains"], sw["x0"], i,
                                               t_up, S)
                    t_new = t_up + cu_new
                    if any_sel:
                        t_new = jnp.where(sw["adm"][r, i], t_new, jnp.inf)
                    out["qt"] = qt_w.at[i].set(t_new)
                    out["qdl"] = qdl_w.at[i].set(t)
                    out["qcu"] = qcu_w.at[i].set(cu_new)
                    return out, (i, t, cu, cl, dl_t, weight)
                return jax.vmap(step_w)(carry, stat)
            return body

        def readmit_world(qt, qdl, qcu, w, A, t_b):
            # boundary re-admission for ONE world — trace-level, with that
            # world's baked scalar constants (the solo readmit verbatim)
            A = jnp.asarray(A)
            t_up = t_b + qcl[w, A]
            cu_new = eq36_upload_delay(gains[w], x0s[w], A, t_up,
                                       wconsts[w])
            return (qt.at[w, A].set(t_up + cu_new),
                    qdl.at[w, A].set(t_b), qcu.at[w, A].set(cu_new))

        a = 0
        for b in pts:
            if b > a:
                carry = {"g": g, "qt": qt, "qdl": qdl, "qcu": qcu}
                if any_state:
                    carry["rs"], carry["rc"] = rs, rc
                with jax.named_scope(f"sweep_scan_{a}_{b}"):
                    carry, ys = jax.lax.scan(make_body(locals_buf), carry,
                                             jnp.arange(a, b))
                g, qt, qdl, qcu = (carry["g"], carry["qt"], carry["qdl"],
                                   carry["qcu"])
                if any_state:
                    rs, rc = carry["rs"], carry["rc"]
                traces.append(ys)
            if b > 0 and b in needed:
                snaps[b] = store(g)
            for w, ra in enumerate(readmit_at):
                if b in ra:
                    # t_b = world w's boundary pop timestamp (last of the
                    # sub-segment that just ran)
                    qt, qdl, qcu = readmit_world(qt, qdl, qcu, w, ra[b],
                                                 traces[-1][1][-1, w])
            for gi, G in enumerate(groups):
                tg = group_train.get((gi, b))
                if tg is None:
                    continue
                T_np, pay_rounds, shared = tg
                imgs_g, labs_g = g_imgs[gi], g_labs[gi]
                lr_g = lrs[G[0]]        # equal across the group (group key)
                T_dev = jnp.asarray(T_np)
                if len(G) == 1:
                    # singleton group: the exact solo wave-training block
                    w = G[0]
                    if shared:
                        pay = layout.unpack(snaps[pay_rounds[0]][w])
                    else:
                        pay = layout.unpack(jnp.stack(
                            [snaps[pr][w] for pr in pay_rounds]))
                    train = _wave_train(local_scan, None, len(T_np), shared)
                    with jax.named_scope(f"sweep_wave_{b}_w{w}"):
                        loc, _ = train(pay, imgs_g[T_np], labs_g[T_np],
                                       lr_g)
                    locals_buf = locals_buf.at[w, T_dev].set(
                        layout.pack(loc, dtype=store_dtype))
                    continue
                # shared-timeline group: nested vmap — worlds stack on the
                # payload axis, members broadcast (shared payload) or stack
                G_np = np.asarray(G, np.int32)
                G_dev = jnp.asarray(G_np)
                if shared:
                    pay = layout.unpack(snaps[pay_rounds[0]][G_np])
                    vf = jax.vmap(jax.vmap(local_scan,
                                           in_axes=(None, 0, 0, None)),
                                  in_axes=(0, None, None, None))
                else:
                    rows = jnp.stack([snaps[pr][G_np]
                                      for pr in pay_rounds], axis=1)
                    pay = layout.unpack(rows)       # leaves [nG, |T|, ...]
                    vf = jax.vmap(jax.vmap(local_scan,
                                           in_axes=(0, 0, 0, None)),
                                  in_axes=(0, None, None, None))
                with jax.named_scope(f"sweep_wave_{b}_g{gi}"):
                    loc, losses = vf(pay, imgs_g[T_np], labs_g[T_np], lr_g)
                    loc, _ = jax.lax.optimization_barrier((loc, losses))
                locals_buf = locals_buf.at[
                    G_dev[:, None], T_dev[None, :]].set(
                    layout.pack(loc, dtype=store_dtype))
            a = b

        trace = tuple(jnp.concatenate([tr[k] for tr in traces])
                      for k in range(6))             # each [M, W]
        evals = jnp.stack([snaps[rr] for rr in eval_rounds])
        ret = (layout.unpack(g), evals, trace)
        if any_state:
            ret = ret + ((rs, rc),)
        return ret

    return jax.jit(program)


def _get_sweep_program(plans, ps, lrs, groups, *, scheme, interpretation,
                       layout, ring_dtype, eval_rounds, group_shapes):
    key = (tuple((plan.waves, tuple(plan.dl_round.tolist()),
                  tuple(plan.veh.tolist()), plan.n_slots, p, lr,
                  None if plan.sel is None else plan.sel.signature())
                 for plan, p, lr in zip(plans, ps, lrs)),
           tuple(tuple(G) for G in groups), group_shapes, scheme,
           interpretation, layout.signature(), ring_dtype, eval_rounds,
           client_mod._local_scan)
    prog = _SWEEP_CACHE.get(key)
    if prog is None:
        prog = _build_sweep_program(
            plans, ps, groups, scheme=scheme, interpretation=interpretation,
            layout=layout, ring_dtype=ring_dtype, eval_rounds=eval_rounds,
            fedasync_mix=DEFAULT_FEDASYNC_MIX)
        _SWEEP_CACHE[key] = prog
        while len(_SWEEP_CACHE) > _SWEEP_CACHE_SIZE:
            _SWEEP_CACHE.popitem(last=False)
    else:
        _SWEEP_CACHE.move_to_end(key)
    return prog


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------
def run_simulation_vmap(worlds, *, eval_every: int = 10, batch_size: int = 128,
                        progress=None, metrics=None):
    """Run ``W = len(worlds)`` independent single-RSU worlds as one vmap
    batch; ``worlds`` is a sequence of ``(Scenario, seed)`` pairs (built
    by :func:`repro.core.scenarios.run_sweep`).  Returns one ``SimResult``
    per world, in order, each carrying an ``engine="vmap"`` RunReport.

    Uniform across the batch (validated, clear errors): ``K``, ``rounds``,
    ``scheme``, ``ring_dtype``, topology (single-RSU), and the path-loss
    exponent ``alpha``.  Free to vary per world: seed, any linear channel
    scalar (beta/gamma/zeta/v/coverage/geometry/power/noise/bandwidth/
    model bits), ``lr``, ``l_iters``, data fields, and the selection spec.

    ``progress`` fires post-hoc as ``progress(world_index, round, acc)``.
    """
    from repro.core.flat import ParamLayout
    from repro.core.mafl import SimResult, evaluate
    from repro.core.scenarios import build_world
    from repro.models.cnn import init_cnn
    from repro.telemetry import RunReport, memory_stats
    from repro.telemetry.report import wave_stats
    from repro.telemetry.spec import metrics_requested
    from repro.telemetry.timers import PhaseTimers

    if metrics_requested(metrics):
        raise ValueError(
            "engine='vmap' does not collect device telemetry yet: the "
            "metrics accumulators are per-world scan state the sweep tier "
            "does not carry (DESIGN.md §15) — run the world solo with "
            "engine='jit', metrics='on'")
    worlds = list(worlds)
    if not worlds:
        raise ValueError("run_simulation_vmap: empty world batch")
    W = len(worlds)
    scs = [sc for sc, _seed in worlds]
    seeds = [int(seed) for _sc, seed in worlds]
    sc0 = scs[0]
    for field, label in (("n_rsus", "topology"), ("K", "fleet size"),
                         ("rounds", "rounds"), ("scheme", "scheme"),
                         ("ring_dtype", "ring_dtype")):
        vals = {getattr(sc, field) for sc in scs}
        if len(vals) > 1:
            raise ValueError(
                f"engine='vmap' needs a uniform {label} across the world "
                f"batch (got {field}={sorted(map(str, vals))}): these set "
                "the compiled program's shapes/structure — split the sweep")
    if sc0.n_rsus > 1:
        raise ValueError(
            "engine='vmap' is single-RSU only: corridor worlds carry "
            "per-RSU cohort rows the [W, P] world axis does not model "
            "(DESIGN.md §15) — use engine='corridor' per world")
    if sc0.scheme not in _SUPPORTED_SCHEMES:
        raise ValueError(
            f"engine='vmap' supports schemes {_SUPPORTED_SCHEMES}, not "
            f"{sc0.scheme!r} (fedbuff keeps host-side buffer state)")
    from repro.faults import scenario_faults
    if any(scenario_faults(sc) is not None for sc in scs):
        raise ValueError(
            "engine='vmap' does not support fault injection yet: the "
            "fault folds (admission, staleness-cap, partial epochs) are "
            "per-world program structure the [W, P] world axis does not "
            "model (DESIGN.md §15/§16) — run the world solo with "
            "engine='jit', faults=...")
    if sc0.ring_dtype not in ("f32", "bf16"):
        raise ValueError(f"unknown ring_dtype {sc0.ring_dtype!r}")
    ps = [sc.channel() for sc in scs]
    if len({float(p.alpha) for p in ps}) > 1:
        raise ValueError(
            "engine='vmap' needs a uniform path-loss exponent alpha: it "
            "is a pow exponent XLA special-cases when constant, so a "
            "traced per-world alpha would change the solo worlds' codegen "
            "(DESIGN.md §15) — sweep it serially")
    M = sc0.rounds
    K = sc0.K

    timers = PhaseTimers()
    # -- host staging: per-world worlds, plans, padded tables --------------
    with timers.phase("world"):
        built = [build_world(sc, seed=seed) for sc, seed in worlds]
    with timers.phase("plan"):
        plans = [plan_fleet(p, seed, M, sc.selection_spec())
                 for sc, seed, p in zip(scs, seeds, ps)]
    tabs = stack_plan_tables([plan.tables() for plan in plans])

    # -- timeline groups: worlds whose training blocks can share one
    #    nested-vmap call.  The key pins everything the minibatch stacks
    #    and wave payload indices depend on: the data world, the seed, the
    #    pop/wave structure, and lr (one traced scalar per group).
    fleet_batches = [min(batch_size, min(d.size for d in veh))
                     for (veh, _i, _l, _p) in built]
    group_of = {}
    groups: list[list[int]] = []
    for w, (sc, seed) in enumerate(worlds):
        key = (seed, sc.n_train, sc.n_test, sc.noise, sc.scale,
               sc.dirichlet_alpha, sc.max_per_vehicle, ps[w].K,
               ps[w].platoon, sc.l_iters, sc.lr, fleet_batches[w],
               plans[w].waves, tuple(plans[w].veh.tolist()),
               tuple(plans[w].dl_round.tolist()))
        if key in group_of:
            groups[group_of[key]].append(w)
        else:
            group_of[key] = len(groups)
            groups.append([w])

    # -- one minibatch stack per GROUP (members share data + pop order;
    #    same per-vehicle RNG streams as every other engine, DESIGN.md §3)
    with timers.phase("stage"):
        g_imgs, g_labs = [], []
        for G in groups:
            w = G[0]
            veh_data = built[w][0]
            clients = Samplers(veh_data, lr=scs[w].lr,
                               batch_size=fleet_batches[w], seed=seeds[w])
            im_list, lab_list = [], []
            for r in range(M):
                im, lab = clients[plans[w].veh[r]].sample_batches(
                    scs[w].l_iters)
                im_list.append(im)
                lab_list.append(lab)
            g_imgs.append(jnp.asarray(np.stack(im_list)))
            g_labs.append(jnp.asarray(np.stack(lab_list)))
        group_shapes = tuple(x.shape for x in g_imgs)

        # -- stacked device inputs -----------------------------------------
        w0_list = [init_cnn(jax.random.PRNGKey(seed)) for seed in seeds]
        w0s = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *w0_list)
        layout = ParamLayout.from_tree(w0_list[0])
        gains = jnp.asarray(stack_gain_tables(ps, seeds,
                                              [plan.n_slots
                                               for plan in plans]))
        x0s = jnp.asarray(np.stack([Mobility(p).x0 for p in ps]), jnp.float32)
        qt = jnp.asarray(tabs["q0_time"], jnp.float32)
        qdl = jnp.asarray(tabs["q0_download_time"], jnp.float32)
        qcu = jnp.asarray(tabs["q0_upload_delay"], jnp.float32)
        qcl = jnp.asarray(tabs["q0_train_delay"], jnp.float32)
        lrs = jnp.asarray(np.asarray([sc.lr for sc in scs], np.float32))

        scal = [_world_scalars(p, plan) for p, plan in zip(ps, plans)]
        varied_names = tuple(sorted(
            n for n in scal[0] if len({s[n] for s in scal}) > 1))
        var = {n: jnp.asarray(np.asarray(
            [s[n] for s in scal],
            np.int32 if n == "n_slots" else np.float32)) for n in varied_names}

        eval_rounds = tuple(rr for rr in range(1, M + 1)
                            if rr % eval_every == 0 or rr == M)
        prog = _get_sweep_program(
            plans, ps, [sc.lr for sc in scs], groups, scheme=sc0.scheme,
            interpretation="mixing", layout=layout, ring_dtype=sc0.ring_dtype,
            eval_rounds=eval_rounds, group_shapes=group_shapes)
        args = (w0s, gains, x0s, qt, qdl, qcu, qcl, tuple(g_imgs),
                tuple(g_labs), lrs, var)

    with timers.phase("run"):
        out = jax.block_until_ready(prog(*args))
    if any(plan.sel is not None and not plan.sel.is_noop
           and plan.sel.spec.policy == "eps-bandit" for plan in plans):
        g_tree, evals, trace, (dev_rs, dev_rc) = out
    else:
        g_tree, evals, trace = out
        dev_rs = dev_rc = None
    t_veh, t_time, t_cu, t_cl, t_dlt, t_w = (np.asarray(x) for x in trace)

    # -- per-world divergence guards + result split ------------------------
    results = []
    with timers.phase("eval"):
        for w, (sc, seed) in enumerate(worlds):
            plan_w = plans[w]
            if not np.array_equal(t_veh[:, w], tabs["veh"][w]):
                bad = int(np.argmax(t_veh[:, w] != tabs["veh"][w]))
                raise RuntimeError(
                    f"vmap engine: world {w} device pop order diverged "
                    f"from the host dry run at round {bad} (device vehicle "
                    f"{int(t_veh[bad, w])}, host {int(tabs['veh'][w][bad])})")
            if not np.allclose(t_time[:, w], tabs["times"][w],
                               rtol=1e-4, atol=1e-3):
                bad = int(np.argmax(~np.isclose(
                    t_time[:, w], tabs["times"][w], rtol=1e-4, atol=1e-3)))
                raise RuntimeError(
                    f"vmap engine: world {w} device event times diverged "
                    f"from the host dry run at round {bad}: "
                    f"{t_time[bad, w]} vs {tabs['times'][w][bad]}")
            if (plan_w.sel is not None and not plan_w.sel.is_noop
                    and plan_w.sel.spec.policy == "eps-bandit"):
                exp_rs, exp_rc = plan_w.sel_bandit
                if not np.array_equal(np.asarray(dev_rc)[w], exp_rc):
                    raise RuntimeError(
                        f"vmap engine: world {w} bandit arrival counts "
                        "diverged from the host selection replay")
                if not np.allclose(np.asarray(dev_rs)[w], exp_rs,
                                   rtol=1e-4, atol=1e-3):
                    raise RuntimeError(
                        f"vmap engine: world {w} bandit reward "
                        "accumulators diverged from the host replay")
            final_w = jax.tree_util.tree_map(lambda x: x[w], g_tree)
            if sc0.ring_dtype == "bf16" and not all(
                    bool(jnp.isfinite(x).all())
                    for x in jax.tree_util.tree_leaves(final_w)):
                raise RuntimeError(
                    f"vmap engine: world {w} non-finite master weights "
                    "under ring_dtype='bf16' — rerun with 'f32' to bisect")
            result = SimResult(scheme=sc.scheme, rounds=[], acc_history=[],
                               loss_history=[], final_params=final_w)
            eval_idx = {rr: k for k, rr in enumerate(eval_rounds)}
            te_i, te_l = built[w][1], built[w][2]
            for r in range(M):
                rec = RoundRecord(round=r + 1, time=float(t_time[r, w]),
                                  vehicle=int(t_veh[r, w]),
                                  upload_delay=float(t_cu[r, w]),
                                  train_delay=float(t_cl[r, w]),
                                  weight=float(t_w[r, w]))
                rr = r + 1
                if rr % eval_every == 0 or rr == M:
                    params_r = layout.unpack(evals[eval_idx[rr], w])
                    acc, loss = evaluate(params_r, te_i, te_l)
                    rec.accuracy, rec.loss = acc, loss
                    result.acc_history.append((rr, acc))
                    result.loss_history.append((rr, loss))
                    if progress:
                        progress(w, rr, acc)
                result.rounds.append(rec)
            results.append(result)
    # shared phase timers: one plan/stage/run/eval cost for the whole batch
    # — every world's report carries the same snapshot plus its world index
    # — and its own world's counts (only a group's first world gathers
    # minibatches, so the others read rows_gathered 0)
    for w, ((sc, seed), result) in enumerate(zip(worlds, results)):
        plan_w = plans[w]
        veh_w = built[w][0]
        result.report = RunReport(
            engine="vmap", scheme=sc.scheme, rounds=M, seed=seed,
            metrics_on=False, spec=None, phases=timers.snapshot(),
            compile=timers.compile_counts(), memory=memory_stats(),
            world=veh_w[0].pool.world_counts(len(veh_w)),
            plan=plan_w.scheduled,
            selection=(None if plan_w.sel is None
                       else plan_w.sel.summary()),
            waves=wave_stats(plan_w.waves, K),
            channels={"world_index": w, "n_worlds": W,
                      "group": next(gi for gi, G in enumerate(groups)
                                    if w in G)})
    return results
