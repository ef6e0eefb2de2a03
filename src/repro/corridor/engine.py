"""Device-resident corridor engine: R RSU cohorts, handover, and the cloud
reconciliation tier in one compiled program (``engine="corridor"``,
DESIGN.md §10).

The retired serial loop (``corridor.reference``) pays Python dispatch per
arrival *and* per RSU bookkeeping step, capping corridors at K≈40.  This
engine extends the mega-fleet layout (DESIGN.md §9) with an RSU axis:

- **Per-RSU slot queues, ``f32[R, K]``.**  The jit engine's per-vehicle
  slot columns gain a leading RSU axis: vehicle i's single in-flight upload
  occupies slot ``(j, i)`` where j is the RSU serving it at *arrival* time
  (positions are pure in t, so the handover target is known at schedule
  time).  Pop is an argmin over the flattened ``R*K`` time column; a
  **handover is a vectorized slot migration** — the re-schedule writes
  ``+inf`` into the old row and the new arrival time into the row of the
  RSU the vehicle will have reached, moving the slot (and with it the
  vehicle's download-time/staleness column and in-flight payload pointer)
  between RSU shards whenever the trajectory crosses a coverage boundary.

- **Cohort stack, ``f32[R, P]``.**  The R cohort models are one packed
  buffer (``core/flat.py``, DESIGN.md §12); an arrival updates exactly one
  row, in the scan (the CPU default) or as per-RSU fused ``ring_agg``
  chains between scans (``use_kernel`` / accelerator backends).

- **Snapshot ring: one model per round, exactly.**  Each round re-schedules
  exactly one vehicle, whose next download reads exactly one cohort — the
  one its upload just landed on (download happens at the arrival position).
  So ``ring[r+1]`` stores that single post-round-r cohort row, and
  ``ring[0]`` is the common init (every cohort starts from the same
  model).  Payload indexing is therefore identical to the single-RSU jit
  engine — the RSU choice is already baked into the row — and rows that no
  later wave reads are dead code to XLA.

- **Reconciliation between scan segments.**  Cloud-tier reconcile rounds
  (every ``reconcile_every`` arrivals) are statically known, so scan
  segments are split at those boundaries and the reconcile runs *between*
  scans at trace level: FedAvg (all cohorts adopt the stack mean) or EMA
  (each cohort moves ``tau`` toward it, optionally through the Pallas
  ``weighted_agg`` kernel).  Because the re-download payload of the
  boundary round must see the *post*-reconcile cohort (the serial
  reference schedules after reconciling), the boundary's ring row is
  overwritten with the reconciled row.

- **Optional sharding over the RSU axis.**  With a mesh that has an
  ``"rsu"`` axis (R divisible by its size), the cohort stack is placed
  sharded over it along its row axis, at creation and after every
  reconcile; the compiler partitions the rest of the program around that
  placement.  The mesh is rebuilt over the same devices with Auto axes,
  so ``jax.make_mesh`` meshes (Explicit axes) and ``Mesh(devices,
  ("rsu",))`` meshes run the same program.  XLA cannot partition a
  Mosaic kernel, so on several devices the Pallas calls (the ``ring_agg``
  chains, the EMA reconcile under ``use_kernel``) run replicated inside a
  ``shard_map``.

Local training is wave-hoisted exactly as in the jit engine (same wave
rule, same shared-payload broadcast fast path, optional ``"data"``-axis
sharding), and the same host dry-run (``corridor.plan``) plans the program
and cross-checks the device trace afterwards — vehicle *and* serving-RSU
divergence raise instead of silently mis-pairing batches or cohorts.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import ChannelParams, CorridorMobility, slot_gain_table
from repro.core import client as client_mod
from repro.core.client import Samplers, VehicleData
from repro.core.jit_engine import (_mesh_key, _replicated, _ring_agg,
                                   _wave_train)
from repro.core.server import DEFAULT_FEDASYNC_MIX, RoundRecord
from repro.corridor.plan import CorridorPlan, plan_corridor
from repro.models.cnn import init_cnn

_SUPPORTED_SCHEMES = ("mafl", "afl", "fedasync")
_RSU_AXIS = "rsu"

_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_SIZE = 16


def _rsu_shards(mesh, n_rsus: int) -> int:
    """Number of RSU shards the mesh requests (1 = unsharded).  A mesh
    whose ``"rsu"`` axis cannot tile the corridor raises — the caller
    explicitly asked for RSU sharding, and silently running replicated
    would misrepresent the measured scaling/memory behavior."""
    if mesh is None or _RSU_AXIS not in mesh.shape:
        return 1
    n = mesh.shape[_RSU_AXIS]
    if n > 1 and n_rsus % n != 0:
        raise ValueError(
            f"mesh '{_RSU_AXIS}' axis of size {n} cannot shard "
            f"{n_rsus} RSU cohorts (n_rsus must be divisible)")
    return n if n > 1 else 1


def _build_program(plan: CorridorPlan, p: ChannelParams, *, scheme: str,
                   interpretation: str, use_kernel: bool, mesh,
                   reconcile_every: int, reconcile_mode: str,
                   reconcile_tau: float, eval_rounds: tuple,
                   fedasync_mix: float, record_cohorts: bool, layout,
                   ring_dtype: str = "f32", metrics=None, l_iters: int = 1):
    """Trace-time constants live in the closure; cached per world structure
    like the jit engine's program.

    ``layout`` packs the cohort stack into one ``f32[R, P]`` buffer and
    each ring row into one ``[P]`` vector (DESIGN.md §12); aggregation is
    either the in-scan one-vector-op mix (CPU default) or fused per-RSU
    ``ring_agg`` chains (``use_kernel`` / accelerator backends).  A mesh
    with an ``"rsu"`` axis of size > 1 (Auto axes, see ``_stage_run``)
    places the cohort stack sharded over it."""
    M = len(plan.veh)
    K = p.K
    R = plan.n_rsus
    d = np.asarray(plan.dl_round)
    up_rsu = np.asarray(plan.up_rsu)
    beta = jnp.float32(p.beta)
    gamma = jnp.float32(p.gamma)
    zeta = jnp.float32(p.zeta)
    f_mix = jnp.float32(fedasync_mix)
    tau = jnp.float32(reconcile_tau if reconcile_mode == "ema" else 1.0)
    v_c = jnp.float32(p.v)
    span = jnp.float32(2.0 * p.coverage * R)
    cell = jnp.float32(2.0 * p.coverage)
    centers = jnp.asarray(
        -float(span) / 2 + (np.arange(R) + 0.5) * float(cell), jnp.float32)
    dy2H2 = jnp.float32(p.d_y ** 2 + p.H ** 2)
    pm = jnp.float32(p.p_m)
    alpha_pl = jnp.float32(p.alpha)
    sigma2 = jnp.float32(p.sigma2)
    bw = jnp.float32(p.B)
    bits = jnp.float32(p.model_bits)
    n_slots = plan.n_slots
    if _rsu_shards(mesh, R) > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        rows = NamedSharding(mesh, PartitionSpec(_RSU_AXIS))

        def place(G):
            return jax.lax.with_sharding_constraint(G, rows)
    else:
        def place(G):
            return G

    # selection (DESIGN.md §11): same fold as the jit engine — a [M, K]
    # static mask table gates every re-schedule (parked slots are +inf in
    # every RSU row), re-admissions run at trace level after the reconcile
    # whose boundary re-scored the fleet, and only the eps-bandit carries
    # f32 reward accumulators through the scan (guard-checked)
    sel_active = plan.sel is not None and not plan.sel.is_noop
    with_state = sel_active and plan.sel.spec.policy == "eps-bandit"

    # faults (DESIGN.md §16): the exact same fold as the jit engine.
    # Suppressed re-schedules AND into the admission table, recovery
    # sweeps merge into the boundary re-admission map (recoveries run at
    # reconcile boundaries, which are already scan-segment splits), the
    # staleness-cap verdicts gate each pop's cohort-row update, and the
    # per-cycle epoch counts feed the masked partial trainer.  flt is
    # None on the off path, so every branch below vanishes and the
    # program is textually the legacy one (rule FLT001).
    from repro.faults import fold_admission, fold_readmits

    flt_plan = plan.flt
    flt_on = flt_plan is not None
    has_partial = flt_on and flt_plan.spec.has_partial
    has_cap = flt_on and flt_plan.spec.has_cap
    adm_active = sel_active or (flt_on and flt_plan.timeline_active)

    # telemetry fold (DESIGN.md §14): every metrics branch below is gated
    # on this *static* flag, so ``metrics=None`` traces a program textually
    # identical to the legacy one (rule TEL001 — bitwise off path)
    met_on = metrics is not None
    if met_on:
        from repro.telemetry import device as tel_dev
        met_edges = jnp.asarray(metrics.edges, jnp.float32)
    if adm_active:
        adm = (np.stack([plan.sel.mask_for_round(r) for r in range(M)])
               if sel_active else np.ones((M, K), bool))
        if flt_on and flt_plan.timeline_active:
            adm = fold_admission(adm, flt_plan, plan.veh)
        adm_tab = jnp.asarray(adm)
        readmit_at = {b: np.asarray(vs, np.int32)
                      for b, vs in fold_readmits(
                          plan.sel if sel_active else None,
                          flt_plan if flt_on else None).items() if len(vs)}
    else:
        readmit_at = {}
    if has_cap:
        keep_tab = jnp.asarray(np.asarray(flt_plan.keep, bool))
    if has_partial:
        ep_tab = jnp.asarray(np.asarray(flt_plan.epochs, np.int32))
    # fault counters (DESIGN.md §16): per-pop i32[4] increments from the
    # fault plan, accumulated in the metrics carry and conformance-checked
    # against the f64 fault replay after the run
    fct_on = met_on and metrics.fault_counters and flt_on
    if fct_on:
        fct_tab = jnp.asarray(flt_plan.counts_table(l_iters))

    def aggregate(g, loc, t, cu, cl, dl_t):
        """One arrival's in-scan cohort-row update — identical math and
        f32 arithmetic to the jit engine / host aggregation paths."""
        if scheme == "mafl":
            weight = gamma ** (cu - 1.0) * zeta ** (cl - 1.0)   # Eqs. 7, 9
        else:
            weight = jnp.float32(1.0)
        if scheme == "mafl" and interpretation == "literal":
            new = (beta * g.astype(jnp.float32) + (1.0 - beta) * weight *
                   loc.astype(jnp.float32)).astype(g.dtype)
            return new, weight
        if scheme == "mafl":
            alpha = jnp.clip((1.0 - beta) * weight, 0.0, 1.0)
        elif scheme == "afl":
            alpha = 1.0 - beta
        else:                                                   # fedasync
            stale = jnp.maximum(t - dl_t, 0.0)
            alpha = f_mix * (stale + 1.0) ** (-0.5)
        new = ((1.0 - alpha) * g.astype(jnp.float32) +
               alpha * loc.astype(jnp.float32)).astype(g.dtype)
        return new, weight

    def stack_mean(G):
        """Mean over the cohort rows, f32 accumulate."""
        return jnp.mean(G.astype(jnp.float32), axis=0)

    def mix_rows(G, cons):
        """EMA of every row toward ``cons`` (tau=1 → adopt outright);
        ``cons`` arrives in f32 and is cast back to the row dtype."""
        if use_kernel and float(tau) != 1.0:
            from repro.kernels.weighted_agg import ops as agg_ops
            return _replicated(
                lambda G, C: agg_ops.weighted_agg_tree(
                    G, C, 1.0 - tau, jnp.float32(1.0)), mesh)(
                G, jnp.broadcast_to(cons.astype(G.dtype), G.shape))
        return ((1.0 - tau) * G.astype(jnp.float32) +
                tau * cons[None]).astype(G.dtype)

    def serving(x):
        j = jnp.floor((x + span / 2.0) / cell).astype(jnp.int32)
        return jnp.clip(j, 0, R - 1)

    def eq36_upload_delay(gains, x0, idx, t_up):
        """Eq. 3-6 with the corridor geometry: slot gain -> span wrap ->
        serving-cell distance -> SNR -> Shannon rate -> upload delay.
        ``idx`` is a scalar pop or a vector of re-admissions; one
        definition serves the scan body and the re-admission helper."""
        slot = jnp.clip(t_up.astype(jnp.int32), 0, n_slots - 1)
        gain = gains[slot, idx]
        dx = x0[idx] + v_c * t_up                       # Eq. 3
        x_up = jnp.mod(dx + span / 2.0, span) - span / 2.0
        j_up = serving(x_up)                 # serving cell at upload
        dist = jnp.sqrt((x_up - centers[j_up]) ** 2 + dy2H2)  # Eq. 4
        snr = pm * gain * dist ** (-alpha_pl) / sigma2
        rate = bw * jnp.log2(1.0 + snr)                 # Eq. 5
        return bits / jnp.maximum(rate, 1e-12)          # Eq. 6

    eval_set = set(eval_rounds)
    reconcile_set = {b for b in range(reconcile_every, M + 1,
                                      reconcile_every)}

    from repro.core.aggregation import chain_coeffs
    from repro.corridor.plan import rsu_chain_groups

    bf16 = ring_dtype == "bf16"
    store_dtype = jnp.bfloat16 if bf16 else jnp.float32
    store = ((lambda x: x.astype(jnp.bfloat16)) if bf16
             else (lambda x: x))
    ring_agg = _ring_agg(use_kernel, mesh)
    fused_chain = use_kernel or jax.default_backend() != "cpu"
    # ring rows later waves read (payload rounds); evals read the
    # consensus, never the ring
    needed = set()
    for T, _s, _e in plan.waves:
        needed |= {int(d[t]) + 1 for t in T if d[t] >= 0}

    def program_flat(w0, gains, x0, qt, qdl, qcu, qcl, imgs, labs, lr):
        local_scan = (client_mod._local_scan_partial if has_partial
                      else client_mod._local_scan)
        G = place(jnp.broadcast_to(layout.pack(w0)[None],
                                   (R, layout.P)).astype(jnp.float32))
        locals_buf = jnp.zeros((M, layout.P), store_dtype)
        mst = ring_stats = None
        store_row = store
        if met_on:
            mst = tel_dev.corridor_state(metrics)
            if metrics.ring_guard and bf16:
                ring_stats = tel_dev.RingStats()
                store_row = ring_stats.wrap(store)
        ring = [store_row(layout.pack(w0))] + [None] * M
        cons_snaps, cohort_snaps, traces, met_traces = [], [], [], []
        rs = rc = None
        if with_state:
            rs = jnp.zeros(K, jnp.float32)
            rc = jnp.zeros(K, jnp.float32)

        def make_flat_body(locals_buf):
            # pop, slot migration and re-schedule; in fused mode the
            # cohort stack leaves the carry and aggregation streams
            # per-RSU afterwards (fresh body per segment — locals_buf
            # rebinds per wave, DESIGN.md §9)
            def body(carry, r):
                if met_on:
                    carry, mst = carry[:-1], carry[-1]
                if fused_chain:
                    G = None
                    if with_state:
                        qt, qdl, qcu, rs, rc = carry
                    else:
                        qt, qdl, qcu = carry
                elif with_state:
                    G, qt, qdl, qcu, rs, rc = carry
                else:
                    G, qt, qdl, qcu = carry
                ji = jnp.argmin(qt)                         # pop
                j = ji // K
                i = ji % K
                t = qt[j, i]
                cu, cl, dl_t = qcu[i], qcl[i], qdl[i]
                if met_on:
                    occ = jnp.sum(jnp.isfinite(qt),
                                  axis=1).astype(jnp.int32)
                if fused_chain:
                    if scheme == "mafl":
                        weight = gamma ** (cu - 1.0) * zeta ** (cl - 1.0)
                    else:
                        weight = jnp.float32(1.0)
                    new_row = None
                else:
                    grow = G[j]
                    new_row, weight = aggregate(grow, locals_buf[r], t,
                                                cu, cl, dl_t)
                    if has_cap:
                        # cap-discarded pop: the cohort row (and the
                        # ring row reading it) stays exactly put
                        new_row = jnp.where(keep_tab[r], new_row, grow)
                    G = G.at[j].set(new_row)
                if with_state:
                    rew = gamma ** (cu - 1.0) * zeta ** (cl - 1.0)
                    rs = rs.at[i].add(rew)
                    rc = rc.at[i].add(1.0)
                t_up = t + cl
                cu_new = eq36_upload_delay(gains, x0, i, t_up)
                t_new = t_up + cu_new
                x_new = jnp.mod(x0[i] + v_c * t_new + span / 2.0,
                                span) - span / 2.0
                j_new = serving(x_new)              # handover target
                if adm_active:
                    t_new = jnp.where(adm_tab[r, i], t_new, jnp.inf)
                qt = qt.at[j, i].set(jnp.inf)
                qt = qt.at[j_new, i].set(t_new)
                qdl = qdl.at[i].set(t)
                qcu = qcu.at[i].set(cu_new)
                if fused_chain:
                    out = ((qt, qdl, qcu, rs, rc) if with_state
                           else (qt, qdl, qcu))
                    ys = (i, j, t, cu, cl, dl_t, weight)
                else:
                    out = ((G, qt, qdl, qcu, rs, rc) if with_state
                           else (G, qt, qdl, qcu))
                    ys = (i, j, t, cu, cl, dl_t, weight, new_row)
                if met_on:
                    ho = (j_new != j)
                    if adm_active:
                        ho = ho & adm_tab[r, i]
                    mst, gap = tel_dev.corridor_pop(
                        mst, met_edges, t=t, dl_t=dl_t, j=j, handover=ho,
                        fault_row=fct_tab[r] if fct_on else None)
                    out = out + (mst,)
                    ys = ys + (occ, gap, ho)
                return out, ys
            return body

        def readmit(qt, qdl, qcu, A, t_b):
            A = jnp.asarray(A)
            t_up = t_b + qcl[A]
            cu_new = eq36_upload_delay(gains, x0, A, t_up)
            t_new = t_up + cu_new
            x_new = jnp.mod(x0[A] + v_c * t_new + span / 2.0,
                            span) - span / 2.0
            j_new = serving(x_new)
            return (qt.at[j_new, A].set(t_new), qdl.at[A].set(t_b),
                    qcu.at[A].set(cu_new))

        for T, s, e in plan.waves:
            T = np.asarray(T, np.int32)
            if len(T):
                pay_rounds = [int(x) for x in d[T] + 1]
                shared = all(pr == pay_rounds[0] for pr in pay_rounds)
                if shared:
                    pay = layout.unpack(ring[pay_rounds[0]])
                else:
                    pay = layout.unpack(jnp.stack(
                        [ring[pr] for pr in pay_rounds]))
                train = _wave_train(local_scan, mesh, len(T), shared,
                                    partial=has_partial)
                extra = (ep_tab[jnp.asarray(T)],) if has_partial else ()
                with jax.named_scope(f"wave_train_{s}"):
                    loc, _ = train(pay, imgs[T], labs[T], lr, *extra)
                locals_buf = locals_buf.at[jnp.asarray(T)].set(
                    layout.pack(loc, dtype=store_dtype))
            points = sorted({b for b in range(s + 1, e + 1)
                             if b in eval_set or b in reconcile_set
                             or b in readmit_at}
                            | {e})
            a = s
            for b in points:
                if b > a:
                    if fused_chain:
                        st = ((qt, qdl, qcu, rs, rc) if with_state
                              else (qt, qdl, qcu))
                    else:
                        st = ((G, qt, qdl, qcu, rs, rc) if with_state
                              else (G, qt, qdl, qcu))
                    if met_on:
                        st = st + (mst,)
                    with jax.named_scope(f"event_scan_{a}_{b}"):
                        st, ys = jax.lax.scan(
                            make_flat_body(locals_buf),
                            st, jnp.arange(a, b))
                    if met_on:
                        st, mst = st[:-1], st[-1]
                        met_traces.append(ys[-3:])
                    if fused_chain:
                        if with_state:
                            qt, qdl, qcu, rs, rc = st
                        else:
                            qt, qdl, qcu = st
                    elif with_state:
                        G, qt, qdl, qcu, rs, rc = st
                    else:
                        G, qt, qdl, qcu = st
                    traces.append(ys[:7])
                    if fused_chain:
                        # per-RSU streaming chains (DESIGN.md §12):
                        # coefficients from the segment's own f32
                        # trace, one ring_agg per checkpoint chunk
                        cc, dd = chain_coeffs(
                            scheme, interpretation, p.beta, ys[6],
                            t=ys[2], dl_t=ys[5],
                            fedasync_mix=fedasync_mix)
                        if has_cap:
                            # cap-discarded pops become exact no-ops
                            keep_seg = keep_tab[a:b]
                            cc = jnp.where(keep_seg, cc, 1.0)
                            dd = jnp.where(keep_seg, dd, 0.0)
                        coeffs = jnp.stack([cc, dd], axis=1)
                        # beside the segment's event_scan scope, not
                        # inside it: a trace keys an op by its first
                        # scope
                        with jax.named_scope(f"ring_chain_{a}_{b}"):
                            for jr, chunks in rsu_chain_groups(
                                    plan, a, b, needed):
                                g_j = G[jr]
                                for chunk in chunks:
                                    idx = np.asarray(chunk)
                                    g_j = ring_agg(
                                        g_j,
                                        locals_buf[jnp.asarray(idx)],
                                        coeffs[jnp.asarray(idx - a)])
                                    last = chunk[-1] + 1
                                    if last in needed:
                                        ring[last] = store_row(g_j)
                                G = G.at[jr].set(g_j)
                    else:
                        rows = ys[7]
                        for r in range(a, b):
                            ring[r + 1] = store_row(rows[r - a])
                if b in reconcile_set:
                    G = place(mix_rows(G, stack_mean(G)))
                    # the boundary round's re-download happens *after*
                    # the reconcile (serial reference order): its ring
                    # row is the reconciled cohort the upload landed on
                    ring[b] = store_row(G[int(up_rsu[b - 1])])
                if b in readmit_at:
                    # t_b = the boundary pop's timestamp
                    qt, qdl, qcu = readmit(qt, qdl, qcu, readmit_at[b],
                                           traces[-1][2][-1])
                if b in eval_set:
                    cons_snaps.append(layout.unpack(
                        jnp.mean(G, axis=0)))
                    if record_cohorts:
                        cohort_snaps.append(layout.unpack(G))
                a = b

        trace = tuple(jnp.concatenate([tr[k] for tr in traces])
                      for k in range(7))
        ret = (layout.unpack(G), cons_snaps, cohort_snaps, trace)
        if with_state:
            ret = ret + ((rs, rc),)
        if met_on:
            met_out = {
                "stale_hist": mst[0],
                "handover_count": mst[2],
                "occupancy": jnp.concatenate(
                    [m[0] for m in met_traces]),
                "gap": jnp.concatenate([m[1] for m in met_traces]),
                "handover": jnp.concatenate(
                    [m[2] for m in met_traces]),
            }
            if fct_on:
                met_out["fault_counts"] = mst[3]
            if ring_stats is not None:
                met_out.update(ring_stats.out())
            ret = ret + (met_out,)
        return ret

    return jax.jit(program_flat)


# ---------------------------------------------------------------------------
# public entry point — signature mirrors corridor.reference
# ---------------------------------------------------------------------------
def run_corridor_simulation(
    sc,
    vehicles_data: Sequence[VehicleData],
    test_images: np.ndarray,
    test_labels: np.ndarray,
    p: Optional[ChannelParams] = None,
    *,
    seed: int = 0,
    eval_every: int = 10,
    interpretation: str = "mixing",
    use_kernel: bool = False,
    progress=None,
    batch_size: int = 128,
    mesh=None,
    record_cohorts: bool = False,
    init_params=None,
    selection=None,
    metrics=None,
    faults=None,
):
    """Run ``sc.rounds`` corridor arrivals entirely on device; returns the
    same ``SimResult`` the serial reference produces (same record fields,
    same eval cadence, per-RSU round numbering, ``rec.rsu`` set).

    ``mesh`` with an ``"rsu"`` axis shards the packed cohort stack over
    it (DESIGN.md §10); a ``"data"`` axis shards the training waves.
    ``sc.ring_dtype="bf16"`` stores ring rows and upload buffers in bf16
    around the f32 cohort stack.

    ``result.extras`` carries the corridor-specific outputs: the per-round
    serving-RSU trace, the final cohort stack, and (``record_cohorts=True``)
    per-eval-round cohort snapshots for per-RSU accuracy curves.  As with
    the jit engine, ``progress`` fires post-hoc in round order.

    ``metrics="on"`` folds device-resident telemetry into the scan
    (DESIGN.md §14): per-RSU staleness histograms, per-RSU occupancy,
    handover counters, and pop-wait traces accumulate in fixed-shape carry
    state, surfaced on ``result.report.channels``.  Any falsy value stages
    the *exact* legacy program (same cache entry, bitwise-identical
    outputs, rule TEL001).

    ``faults`` activates the fault-injection layer (DESIGN.md §16): the
    host f64 planner samples the stochastic client-state processes into
    static per-round tables folded into the compiled program exactly like
    selection — identical decisions on every engine, conformance-checked
    against the f64 replay.  Recovery sweeps run at reconcile boundaries;
    availability faults require ``reconcile_mode='fedavg'``.  Off is the
    exact legacy program (rule FLT001)."""
    from repro.core.mafl import SimResult, evaluate
    from repro.telemetry import RunReport, memory_stats
    from repro.telemetry.report import wave_stats
    from repro.telemetry.timers import PhaseTimers

    timers = PhaseTimers()
    prog, args, plan, layout, eval_rounds, with_state, met = _stage_run(
        sc, vehicles_data, p, seed=seed, eval_every=eval_every,
        interpretation=interpretation, use_kernel=use_kernel,
        batch_size=batch_size, mesh=mesh, record_cohorts=record_cohorts,
        init_params=init_params, selection=selection, metrics=metrics,
        faults=faults, timers=timers)
    p = p if p is not None else sc.channel()
    scheme = sc.scheme
    R = sc.n_rsus
    M = sc.rounds
    ring_dtype = getattr(sc, "ring_dtype", "f32")
    with timers.phase("run"):
        out = jax.block_until_ready(prog(*args))
    with timers.phase("guard"):
        met_dev = None
        if met is not None:
            out, met_dev = out[:-1], out[-1]
        if with_state:
            G, cons_snaps, cohort_snaps, trace, (dev_rs, dev_rc) = out
        else:
            G, cons_snaps, cohort_snaps, trace = out
        t_veh, t_rsu, t_time, t_cu, t_cl, t_dlt, t_w = (
            np.asarray(x) for x in trace)

        # divergence guard (mirrors the jit engine): the minibatch stacks
        # and the cohort/ring pairing were planned on the host — if the
        # device pop order or serving-cell assignment ever disagreed, fail
        # loudly
        if not np.array_equal(t_veh, plan.veh):
            bad = int(np.argmax(t_veh != plan.veh))
            raise RuntimeError(
                "corridor engine: device pop order diverged from the host dry "
                f"run at round {bad} (device vehicle {int(t_veh[bad])}, host "
                f"{int(plan.veh[bad])}) — f32 time ties are not expected")
        if not np.array_equal(t_rsu, plan.up_rsu):
            bad = int(np.argmax(t_rsu != plan.up_rsu))
            raise RuntimeError(
                "corridor engine: device serving-RSU assignment diverged from "
                f"the host dry run at round {bad} (device RSU "
                f"{int(t_rsu[bad])}, host {int(plan.up_rsu[bad])}) — an f32 "
                "boundary flip is not expected")
        if not np.allclose(t_time, plan.times, rtol=1e-4, atol=1e-3):
            bad = int(np.argmax(~np.isclose(t_time, plan.times,
                                            rtol=1e-4, atol=1e-3)))
            raise RuntimeError(
                "corridor engine: device event times diverged from the host "
                f"dry run at round {bad}: {t_time[bad]} vs {plan.times[bad]}")
        if with_state:
            # selection divergence guard (DESIGN.md §11): the carried f32
            # reward accumulators must reproduce the host f64 replay the
            # admission masks were planned from
            exp_rs, exp_rc = plan.sel_bandit
            if not np.array_equal(np.asarray(dev_rc), exp_rc):
                raise RuntimeError(
                    "corridor engine: device bandit arrival counts diverged "
                    "from the host selection replay")
            if not np.allclose(np.asarray(dev_rs), exp_rs,
                               rtol=1e-4, atol=1e-3):
                raise RuntimeError(
                    "corridor engine: device bandit reward accumulators "
                    "diverged from the host selection replay")

        if ring_dtype == "bf16":
            # bf16 divergence guard (DESIGN.md §12): the trace guards above
            # keep the timeline exact; a non-finite cohort stack means the
            # quantized ring diverged — fail loudly
            if not all(bool(jnp.isfinite(x).all())
                       for x in jax.tree_util.tree_leaves(G)):
                raise RuntimeError(
                    "corridor engine: non-finite cohort stack under "
                    "ring_dtype='bf16' — the quantized snapshot ring "
                    "diverged (rerun with ring_dtype='f32' to bisect)")
        result = SimResult(scheme=f"{scheme}+corridor", rounds=[],
                           acc_history=[], loss_history=[])
        per_rsu_round = np.zeros(R, np.int64)
        eval_idx = {rr: k for k, rr in enumerate(eval_rounds)}
    with timers.phase("eval"):
        for r in range(M):
            j = int(t_rsu[r])
            per_rsu_round[j] += 1
            rec = RoundRecord(round=int(per_rsu_round[j]),
                              time=float(t_time[r]), vehicle=int(t_veh[r]),
                              upload_delay=float(t_cu[r]),
                              train_delay=float(t_cl[r]),
                              weight=float(t_w[r]), rsu=j)
            rr = r + 1
            if rr in eval_idx:
                acc, loss = evaluate(cons_snaps[eval_idx[rr]], test_images,
                                     test_labels)
                rec.accuracy, rec.loss = acc, loss
                result.acc_history.append((rr, acc))
                result.loss_history.append((rr, loss))
                if progress:
                    progress(rr, acc)
            result.rounds.append(rec)
    with timers.phase("report"):
        result.final_params = cons_snaps[eval_idx[M]]
        result.extras = {
            "n_rsus": R,
            "up_rsu": t_rsu,
            "eval_rounds": list(eval_rounds),
            "final_cohorts": G,
        }
        if record_cohorts:
            result.extras["cohort_snapshots"] = cohort_snaps
        sel_summary = None if plan.sel is None else plan.sel.summary()
        flt_plan = plan.flt
        flt_report = None
        if flt_plan is not None:
            import dataclasses
            flt_report = {"spec": dataclasses.asdict(flt_plan.spec),
                          "counts": flt_plan.counts(sc.l_iters)}
            result.extras["faults"] = flt_plan.summary(sc.l_iters)
        channels = {}
        if met is not None:
            channels = {k: np.asarray(v) for k, v in met_dev.items()}
            if "fault_counts" in channels:
                # fault-counter divergence guard (DESIGN.md §16): the
                # carried i32[4] accumulator must reproduce the f64 fault
                # replay the counts table was planned from
                exp = flt_plan.counts_table(sc.l_iters).sum(axis=0)
                if not np.array_equal(channels["fault_counts"], exp):
                    raise RuntimeError(
                        "corridor engine: device fault counters diverged "
                        "from the host fault replay "
                        f"({channels['fault_counts']} vs {exp})")
            # per-arrival quality signal (Eqs. 7, 9 delay weight) — the
            # bandit-style reward trace, published for every scheme
            channels["reward"] = (p.gamma ** (t_cu.astype(np.float64) - 1.0)
                                  * p.zeta ** (t_cl.astype(np.float64) - 1.0))
            if with_state:
                channels["reward_sum"] = np.asarray(dev_rs)
                channels["reward_count"] = np.asarray(dev_rc)
        memory = memory_stats()
        waves = wave_stats(plan.waves, p.K)
    result.report = RunReport(
        engine="corridor", scheme=f"{scheme}+corridor", rounds=M,
        seed=seed, metrics_on=met is not None,
        spec=None if met is None else met.to_json(),
        phases=timers.snapshot(), compile=timers.compile_counts(),
        plan=plan.scheduled, memory=memory, selection=sel_summary,
        faults=flt_report, waves=waves, channels=channels)
    return result


def _stage_run(sc, vehicles_data, p=None, *, seed, eval_every,
               interpretation, use_kernel, batch_size, mesh, record_cohorts,
               init_params, selection, metrics=None, faults=None,
               timers=None):
    """Validate, plan, and stage one corridor run — everything up to (but
    not including) executing the compiled program.  Split out of
    :func:`run_corridor_simulation` so ``repro.check.dtype_flow`` can build
    the jaxpr of the exact program the engine would run.

    Returns ``(prog, args, plan, layout, eval_rounds, with_state, met)``
    where ``prog(*args)`` is the staged round loop and ``met`` is the
    resolved :class:`MetricsSpec` (None on the exact legacy off path)."""
    from repro.telemetry.spec import resolve_metrics
    from repro.telemetry.timers import PhaseTimers

    timers = timers if timers is not None else PhaseTimers()
    scheme = sc.scheme
    if scheme not in _SUPPORTED_SCHEMES:
        raise ValueError(
            f"engine='corridor' supports schemes {_SUPPORTED_SCHEMES}, not "
            f"{scheme!r} (fedbuff keeps host-side buffer state — use "
            "engine='serial')")
    mode = getattr(sc, "reconcile_mode", "fedavg")
    if mode not in ("fedavg", "ema"):
        raise ValueError(f"unknown reconcile_mode {mode!r}; "
                         "expected 'fedavg' or 'ema'")
    from repro.selection import check_reconcile_mode, scenario_spec
    spec = selection if selection is not None else scenario_spec(sc)
    check_reconcile_mode(spec, mode)
    from repro.faults import check_faults_reconcile
    check_faults_reconcile(faults, mode)
    p = p if p is not None else sc.channel()
    assert len(vehicles_data) == p.K, (len(vehicles_data), p.K)
    rounds = sc.rounds
    if rounds < 1:
        raise ValueError("rounds must be >= 1")
    R = sc.n_rsus
    entry = getattr(sc, "corridor_entry", "uniform")
    ring_dtype = getattr(sc, "ring_dtype", "f32")
    if ring_dtype not in ("f32", "bf16"):
        raise ValueError(f"unknown ring_dtype {ring_dtype!r}; "
                         "expected 'f32' or 'bf16'")
    if _rsu_shards(mesh, R) > 1:
        # the cohort stack's placement is a constraint the compiler
        # propagates, which needs Auto axes; jax.make_mesh builds
        # Explicit ones
        from jax.sharding import AxisType, Mesh
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))

    with timers.phase("plan"):
        plan = plan_corridor(p, R, seed, rounds, entry=entry,
                             selection=spec,
                             reconcile_every=sc.reconcile_every,
                             faults=faults, l_iters=sc.l_iters)
        met = resolve_metrics(
            metrics, stale=plan.times - plan.download_time,
            times=plan.times, n_rsus=R,
            ring_guard=(ring_dtype == "bf16"),
            fault_counters=plan.flt is not None)
    with timers.phase("stage"):
        M = rounds
        eval_rounds = tuple(sorted({rr for rr in range(1, M + 1)
                                    if rr % eval_every == 0} | {M}))

        key = jax.random.PRNGKey(seed)
        w0 = init_params if init_params is not None else init_cnn(key)

        # one minibatch stack per consumed round, drawn from the same
        # per-vehicle RNG streams in the same pop order as the serial
        # reference, so both engines train identical batches
        fleet_batch = min(batch_size, min(d.size for d in vehicles_data))
        clients = Samplers(vehicles_data, lr=sc.lr, batch_size=fleet_batch,
                           seed=seed)
        im_list, lab_list = [], []
        for r in range(M):
            im, lab = clients[plan.veh[r]].sample_batches(sc.l_iters)
            im_list.append(im)
            lab_list.append(lab)
        imgs = jnp.asarray(np.stack(im_list))
        labs = jnp.asarray(np.stack(lab_list))

        gains = jnp.asarray(slot_gain_table(p, seed, plan.n_slots),
                            jnp.float32)
        x0 = jnp.asarray(CorridorMobility(p, R, entry=entry).x0, jnp.float32)
        qt0 = np.full((R, p.K), np.inf, np.float32)
        qt0[plan.row0, np.arange(p.K)] = plan.q0["time"]
        qt = jnp.asarray(qt0)
        qdl = jnp.asarray(plan.q0["download_time"], jnp.float32)
        qcu = jnp.asarray(plan.q0["upload_delay"], jnp.float32)
        qcl = jnp.asarray(plan.q0["train_delay"], jnp.float32)

        from repro.core.flat import ParamLayout
        layout = ParamLayout.from_tree(w0)
        shapes = (imgs.shape, tuple(
            (str(path), v.shape, str(v.dtype))
            for path, v in jax.tree_util.tree_leaves_with_path(w0)))
        cache_key = (plan.waves, tuple(plan.dl_round.tolist()),
                     tuple(plan.up_rsu.tolist()), plan.n_slots, R, p, scheme,
                     interpretation, use_kernel, mode,
                     float(getattr(sc, "reconcile_tau", 0.5)),
                     sc.reconcile_every, eval_rounds, record_cohorts,
                     _mesh_key(mesh), shapes,
                     None if plan.sel is None else plan.sel.signature(),
                     client_mod._local_scan,
                     layout.signature(), ring_dtype,
                     None if met is None else met.signature(),
                     None if plan.flt is None else
                     (plan.flt.signature(), sc.l_iters,
                      client_mod._local_scan_partial))
        prog = _PROGRAM_CACHE.get(cache_key)
        if prog is None:
            prog = _build_program(
                plan, p, scheme=scheme, interpretation=interpretation,
                use_kernel=use_kernel, mesh=mesh,
                reconcile_every=sc.reconcile_every, reconcile_mode=mode,
                reconcile_tau=float(getattr(sc, "reconcile_tau", 0.5)),
                eval_rounds=eval_rounds, fedasync_mix=DEFAULT_FEDASYNC_MIX,
                record_cohorts=record_cohorts, layout=layout,
                ring_dtype=ring_dtype, metrics=met, l_iters=sc.l_iters)
            _PROGRAM_CACHE[cache_key] = prog
            while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_SIZE:
                _PROGRAM_CACHE.popitem(last=False)
        else:
            _PROGRAM_CACHE.move_to_end(cache_key)

        with_state = (plan.sel is not None and not plan.sel.is_noop
                      and plan.sel.spec.policy == "eps-bandit")
        args = (w0, gains, x0, qt, qdl, qcu, qcl, imgs, labs,
                jnp.float32(sc.lr))
    return prog, args, plan, layout, eval_rounds, with_state, met
