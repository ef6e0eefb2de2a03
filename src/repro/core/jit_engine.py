"""Device-resident mega-fleet engine: the whole round loop as one compiled
program (``engine="jit"``, DESIGN.md §9).

The serial and batched engines (DESIGN.md §2-§3) pay one Python dispatch per
arrival — heap pop, aggregation call, re-schedule — so wall-clock grows with
fleet size even though the training itself is batched.  This engine moves
the *event loop itself* into XLA:

- **Fixed-capacity slot queue.**  Every vehicle has exactly one in-flight
  upload at all times (it re-downloads the instant its upload is consumed,
  Fig. 2), so the event queue is exactly ``K`` structured slots: ``f32[K]``
  times/delays and ``i32[K]`` cycles, indexed by vehicle.  A pop is an
  ``argmin`` over the time column; a re-schedule is a one-slot scatter.

- **Precomputed slot gains.**  The host-side incremental ``SlotGainCache``
  is replaced by :func:`repro.channel.slot_gain_table` — the AR(1) linear
  recurrence evaluated for all slots at once by a vectorized prefix scan —
  loaded as an ``f32[S, K]`` table the in-program re-scheduler indexes.

- **Snapshot ring.**  Stale download-time payloads (DESIGN.md §2 invariant
  1) live in a ring of the last ``M+1`` global models indexed by *round*:
  the payload of an event downloaded after round ``d`` is ``ring[d+1]``
  (``ring[0]`` = the initial model).  Capacity ``M+1`` is exact — an event
  consumed within ``M`` rounds can only have downloaded at one of rounds
  ``0..M-1`` — and for mega-fleets it is far smaller than a per-vehicle
  payload buffer (``M+1`` vs ``K`` models when ``K >> M``).

- **Wave-hoisted training.**  Local training is grouped into the same
  waves the batched engine discovers (every pending upload whose payload
  round has completed trains together) and runs as top-level ``jax.vmap``
  blocks *between* the event-loop scan segments, optionally sharded over
  the ``"data"`` axis of a `launch/mesh.py` mesh via ``shard_map``.  Waves
  whose members all share one payload (every initial-download wave — the
  overwhelmingly common case when ``K >> M``) broadcast the parameters
  instead of stacking them, so the convolutions keep unbatched filters —
  on CPU a stacked-parameter vmap lowers to grouped convolutions that run
  *slower* than serial dispatch, and on TPU the broadcast form feeds the
  MXU one large batch.  The event-loop scan between waves touches only
  argmin/scalar/elementwise-aggregation ops, which lose nothing inside a
  compiled loop body.

- **Packed flat parameters (DESIGN.md §12).**  Every model state is one
  lane-aligned ``f32[P]`` buffer (``core/flat.py``): the model leaves the
  scan carry, the ring materializes only checkpoint rows, aggregation is
  one vector op per pop (or a fused ``ring_agg`` chain under
  ``use_kernel`` / accelerator backends), and ``ring_dtype="bf16"``
  halves the ring + upload buffers around f32 master weights.

Times inside the program are ``f32`` (the event semantics are unchanged;
conformance vs the f64 host engines is to tolerance — pinned exactly on the
(round, vehicle) sequence by ``tests/test_engine_conformance.py``).  The
timeline never depends on training (DESIGN.md §3), so a cheap f64 host dry
run plans the program (pop order, wave partition, gain-table size, one
minibatch stack per round) and afterwards cross-checks the device trace —
any divergence raises instead of silently mis-pairing batches to rounds.

Not handled here (falls back to the host engines): multi-RSU handover
corridors (``run_handover_simulation``) and the buffered ``fedbuff``
scheme, both of which carry host-side state between arrivals.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import (ChannelParams, Mobility, slot_gain_table,
                           training_delay)
from repro.core import client as client_mod
from repro.core.client import Samplers, VehicleData
from repro.core.server import DEFAULT_FEDASYNC_MIX, RoundRecord
from repro.models.cnn import init_cnn
from repro.selection import make_selection_state

_SUPPORTED_SCHEMES = ("mafl", "afl", "fedasync")


@dataclass
class FleetPlan:
    """Host dry-run of the timeline: everything the compiled program needs
    that training cannot change (DESIGN.md §3: times depend only on the
    channel/mobility/data-size processes)."""
    veh: np.ndarray             # i32[M] vehicle popped at round r
    cycle: np.ndarray           # i32[M] that vehicle's upload cycle
    dl_round: np.ndarray        # i32[M] round after which it downloaded (-1 = initial)
    times: np.ndarray           # f64[M] host-reference pop times
    train_delay: np.ndarray     # f64[M]
    upload_delay: np.ndarray    # f64[M]
    download_time: np.ndarray   # f64[M]
    waves: tuple                # ((train_rounds, seg_start, seg_end), ...)
    n_slots: int                # gain-table height
    q0: dict                    # initial per-vehicle slot arrays
    sel: object = None          # SelectionPlan (DESIGN.md §11) or None
    sel_bandit: object = None   # (rew_sum f64[K], rew_cnt f64[K]) or None
    flt: object = None          # FaultPlan (DESIGN.md §16) or None
    scheduled: dict = None      # uploads scheduled {"bulk": n, "single": m}

    def tables(self) -> dict:
        """Fixed-shape padded plan tables for the multi-world sweep tier
        (DESIGN.md §15): every array's shape depends only on ``(M, K)`` —
        never on the seed — so per-world tables stack along a leading
        world axis (``repro.core.sweep.stack_plan_tables``; PLN003 probes
        the stability).  The ragged ``waves`` tuple is re-encoded as two
        per-round columns: ``train_round[r]`` = the wave start at which
        consumed upload ``r`` trains, ``seg_end[r]`` = the end of the
        scan segment containing pop ``r``.  ``n_slots`` pads as a value,
        not a shape — the sweep engine zero-pads the gain tables to the
        batch maximum."""
        M = len(self.veh)
        train_round = np.full(M, -1, np.int32)
        seg_end = np.zeros(M, np.int32)
        for T, s, e in self.waves:
            for t in T:
                train_round[t] = s
            seg_end[s:e] = e
        return {
            "veh": np.asarray(self.veh, np.int32),
            "cycle": np.asarray(self.cycle, np.int32),
            "dl_round": np.asarray(self.dl_round, np.int32),
            "times": np.asarray(self.times, np.float64),
            "train_delay": np.asarray(self.train_delay, np.float64),
            "upload_delay": np.asarray(self.upload_delay, np.float64),
            "download_time": np.asarray(self.download_time, np.float64),
            "train_round": train_round,
            "seg_end": seg_end,
            "n_slots": np.asarray(self.n_slots, np.int32),
            "q0_time": np.asarray(self.q0["time"], np.float64),
            "q0_download_time": np.asarray(self.q0["download_time"],
                                           np.float64),
            "q0_upload_delay": np.asarray(self.q0["upload_delay"],
                                          np.float64),
            "q0_train_delay": np.asarray(self.q0["train_delay"],
                                         np.float64),
        }


def plan_fleet(p: ChannelParams, seed: int, rounds: int,
               selection=None, faults=None, l_iters: int = 5) -> FleetPlan:
    """Dry-run ``rounds`` arrivals (no payloads, no training) and derive the
    pop order, the wave partition, and the initial queue slots.  With a
    selection policy the replay drives a :class:`SelectionState`, so the
    admission masks, re-admission schedule, and (bandit) expected reward
    accumulators come out as static plan data; a fault model drives a
    :class:`FaultState` the same way (DESIGN.md §16), so dropped/blackout
    suppressions, recovery sweeps, staleness-cap verdicts, per-cycle epoch
    counts, and straggler delay inflation are all plan data too."""
    from repro.core.mafl import _Timeline
    from repro.faults import arrival_step, initial_vehicles, make_fault_state

    sel = make_selection_state(selection, p, Mobility(p), seed, rounds)
    flt = make_fault_state(faults, p, seed, rounds, l_iters)
    tl = _Timeline(p, seed, cl_scale=None if flt is None else flt.cl_scale)
    tl.schedule_initial(initial_vehicles(sel, flt, p.K))

    ev0 = tl.queue.as_struct_arrays()
    if sel is None and flt is None:
        assert len(np.unique(ev0["vehicle"])) == p.K, \
            "slot queue invariant: one in-flight upload per vehicle"
    # full-K slot arrays; parked vehicles hold +inf (never popped) until a
    # re-admission boundary writes them a live slot.  train_delay comes from
    # Eq. 8 directly — bit-identical to the event values, and defined for
    # parked vehicles too (the in-program re-admission needs it); the
    # straggler multipliers (faults) scale it exactly as the timeline does.
    q0 = {
        "time": np.full(p.K, np.inf),
        "download_time": np.zeros(p.K),
        "upload_delay": np.zeros(p.K),
        "train_delay": training_delay(p, np.arange(1, p.K + 1)),
    }
    if flt is not None:
        q0["train_delay"] = q0["train_delay"] * flt.cl_scale
    q0["time"][ev0["vehicle"]] = ev0["time"]
    q0["download_time"][ev0["vehicle"]] = ev0["download_time"]
    q0["upload_delay"][ev0["vehicle"]] = ev0["upload_delay"]

    M = rounds
    veh = np.empty(M, np.int32)
    cyc = np.empty(M, np.int32)
    dlr = np.empty(M, np.int32)
    times = np.empty(M)
    c_l = np.empty(M)
    c_u = np.empty(M)
    dlt = np.empty(M)
    last_pop = np.full(p.K, -1, np.int32)
    for r in range(M):
        ev = tl.queue.pop()
        veh[r], cyc[r] = ev.vehicle, ev.cycle
        dlr[r] = last_pop[ev.vehicle]
        times[r], c_l[r], c_u[r] = ev.time, ev.train_delay, ev.upload_delay
        dlt[r] = ev.download_time
        last_pop[ev.vehicle] = r
        if sel is None and flt is None:
            tl.schedule(ev.vehicle, ev.time)
        else:
            if flt is not None:
                flt.on_pop(ev.vehicle, r)

            def _readmit(v, t=ev.time, r=r):
                # a re-admitted vehicle downloads the post-round-r model,
                # so its next pop's payload is ring[r+1] — same indexing
                # rule as an ordinary re-download
                tl.schedule(v, t)
                last_pop[v] = r

            arrival_step(
                sel, flt, r=r, vehicle=ev.vehicle, time=ev.time,
                upload_delay=ev.upload_delay, train_delay=ev.train_delay,
                pending=len(tl.queue),
                schedule=lambda v, t=ev.time: tl.schedule(v, t),
                readmit=_readmit)
        tl.prune()

    # Wave partition — identical to the batched engine's rule: a wave trains
    # every not-yet-trained consumed upload whose payload round has already
    # completed, then the scan segment consumes pops up to the first event
    # scheduled *during* that segment.
    waves = []
    trained = np.zeros(M, bool)
    s = 0
    while s < M:
        T = np.where(~trained & (dlr < s))[0]
        trained[T] = True
        untrained = np.where(~trained)[0]
        e = int(untrained[0]) if len(untrained) else M
        waves.append((tuple(int(x) for x in T), s, e))
        s = e

    return FleetPlan(veh=veh, cycle=cyc, dl_round=dlr, times=times,
                     train_delay=c_l, upload_delay=c_u, download_time=dlt,
                     waves=tuple(waves), n_slots=tl.gains.last_slot + 3,
                     q0=q0, sel=None if sel is None else sel.plan(),
                     sel_bandit=None if sel is None
                     else sel.bandit_expectation(),
                     flt=None if flt is None else flt.plan(),
                     scheduled=dict(tl.counts))


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------
# LRU-bounded: one compiled program per world *structure*; long-lived
# processes sweeping many worlds (hypothesis conformance, seed sweeps) must
# not retain every executable forever (the gain-cache lesson from PR 1)
from collections import OrderedDict

_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_SIZE = 32


def _mesh_key(mesh) -> tuple:
    if mesh is None:
        return ()
    return (tuple(mesh.shape.items()),)


def _wave_train(local_scan, mesh, n_events, shared: bool,
                partial: bool = False):
    """The wave-training block: vmap over events, optionally sharded over
    the mesh ``"data"`` axis via shard_map (DESIGN.md §5, §9).

    ``partial=True`` (faults, DESIGN.md §16) selects the masked partial
    scan — the trainer takes a per-event epoch-count vector as a trailing
    argument, mapped over the event axis like the minibatches.

    The trained weights pass through an ``optimization_barrier``, for
    parity with the host engines: they materialize training outputs at
    their jit-call boundaries by construction, and the barrier gives the
    device programs the same boundary.  Without it XLA:CPU re-fuses the
    SGD epilogue (``w - lr*g``) into whatever consumes the wave — and
    FMA-contracts it differently per consumer, so the *same* training
    would yield low bits that depend on the aggregation around it
    (DESIGN.md §12)."""
    axes = (None if shared else 0, 0, 0, None) + ((0,) if partial else ())
    vf = jax.vmap(local_scan, in_axes=axes)

    def f(pay, imgs, labs, lr, *eps):
        loc, losses = vf(pay, imgs, labs, lr, *eps)
        return jax.lax.optimization_barrier((loc, losses))
    if mesh is None or "data" not in mesh.shape:
        return f
    n_data = mesh.shape["data"]
    if n_events % n_data != 0:
        return f                      # ragged wave: replicate instead
    from jax.sharding import NamedSharding, PartitionSpec as P

    pay_spec = P() if shared else P("data")
    in_specs = ((pay_spec, P("data"), P("data"), P())
                + ((P("data"),) if partial else ()))
    sharded = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                            out_specs=(P("data"), P("data")),
                            check_vma=False)

    def train(*args):
        # the wave's outputs are scattered into the replicated upload
        # buffer; under an explicit-axis mesh that scatter cannot infer its
        # out-sharding from a "data"-sharded update, so replicate first
        return jax.sharding.reshard(sharded(*args), NamedSharding(mesh, P()))
    return train


def _ring_interpret(use_kernel: bool):
    """``ring_agg`` dispatch mode: ``None`` auto-selects (the race
    analyzer's verdict picks compiled Pallas where legal, the jnp chain
    elsewhere); ``use_kernel=True`` forces the Pallas kernel — compiled
    where the verdict allows, the interpreter everywhere else (the
    kernel's cross-chunk accumulation needs a sequential grid)."""
    if not use_kernel:
        return None
    from repro.kernels.dispatch import resolve_interpret
    return resolve_interpret("weighted_agg.ring_agg_2d")


def _replicated(fn, mesh):
    """Wrap ``fn``, which may call a Pallas kernel, for a program over
    ``mesh``: on several devices it runs once per device on replicated
    operands inside a ``shard_map``, since XLA cannot partition a Mosaic
    kernel; on one device (or with no mesh) it is ``fn`` itself."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _ring_agg(use_kernel: bool, mesh):
    """``ring_agg`` as the device programs call it: ``g, locs, coeffs``
    in the mode of :func:`_ring_interpret`, replicated over ``mesh``."""
    from repro.kernels.weighted_agg import ops as agg_ops
    return _replicated(functools.partial(
        agg_ops.ring_agg, interpret=_ring_interpret(use_kernel)), mesh)


def _chain_segment(g, locals_buf, coeffs, snaps, s: int, e: int,
                   needed, store, ring_agg):
    """Advance the f32 master ``g`` across scan segment ``[s, e)`` as fused
    ``ring_agg`` chains, materializing a snapshot row only at the rounds in
    ``needed`` (later-wave payloads / evals) — the global model streams
    once per checkpoint interval instead of once per upload, and the
    arithmetic stays the bitwise sequential chain (DESIGN.md §12).

    ``coeffs`` are the segment's per-upload (c, d) pairs (f32[e-s, 2]);
    ``snaps`` is the trace-level dict of stored ring rows; ``ring_agg``
    is :func:`_ring_agg`'s callable."""
    a = s
    for b in sorted({x for x in needed if s < x <= e} | {e}):
        if b > a:
            g = ring_agg(g, locals_buf[a:b], coeffs[a - s:b - s])
        if b in needed:
            snaps[b] = store(g)
        a = b
    return g


def _build_program(plan: FleetPlan, p: ChannelParams, *, scheme: str,
                   interpretation: str, use_kernel: bool, mesh,
                   fedasync_mix: float, layout, ring_dtype: str = "f32",
                   eval_rounds: tuple = (), metrics=None, l_iters: int = 1):
    """Trace-time constants live in the closure; the returned function is
    cached on the plan/world structure so repeated runs of the same world
    (determinism tests, warm benchmarks) compile exactly once.

    ``layout`` packs each model state into a lane-aligned ``[P]`` buffer
    (DESIGN.md §12): the model leaves the event-loop scan (off the CPU the
    scan carries only queue columns), and each segment's aggregation runs
    as a fused ``ring_agg`` chain.  ``ring_dtype="bf16"`` stores snapshot
    rows and upload buffers in bf16 (f32 master weights, f32
    accumulation)."""
    M = len(plan.veh)
    K = p.K
    d = np.asarray(plan.dl_round)
    beta = jnp.float32(p.beta)
    gamma = jnp.float32(p.gamma)
    zeta = jnp.float32(p.zeta)
    f_mix = jnp.float32(fedasync_mix)
    v_c = jnp.float32(p.v)
    cov = jnp.float32(p.coverage)
    dy2H2 = jnp.float32(p.d_y ** 2 + p.H ** 2)
    pm = jnp.float32(p.p_m)
    alpha_pl = jnp.float32(p.alpha)
    sigma2 = jnp.float32(p.sigma2)
    bw = jnp.float32(p.B)
    bits = jnp.float32(p.model_bits)
    n_slots = plan.n_slots

    # selection (DESIGN.md §11): admission is static plan data folded into
    # the compiled program — a [M, K] mask table gates every re-schedule
    # (an unadmitted vehicle's slot gets +inf, so the argmin pop can never
    # pick it and it occupies no wave), and boundary re-admissions run at
    # trace level between scan sub-segments.  Only the eps-bandit carries
    # live state (f32 reward accumulators) through the scan — its decisions
    # still come from the host f64 replay; the accumulators exist so the
    # divergence guard can prove the device saw the same reward stream.
    sel_active = plan.sel is not None and not plan.sel.is_noop
    with_state = sel_active and plan.sel.spec.policy == "eps-bandit"

    # faults (DESIGN.md §16): the exact same fold as selection.  Dropped
    # and blacked-out re-schedules AND into the admission table (the
    # suppressed vehicle's slot goes +inf), recovery sweeps merge into the
    # boundary re-admission map, the staleness-cap verdicts become a
    # static keep column gating each pop's aggregation, and per-cycle
    # epoch counts feed the masked partial trainer.  flt is None on the
    # off path, so every branch below vanishes and the program is
    # textually the legacy one (rule FLT001, the TEL001 dual).
    from repro.faults import fold_admission, fold_readmits

    flt_plan = plan.flt
    flt_on = flt_plan is not None
    has_partial = flt_on and flt_plan.spec.has_partial
    has_cap = flt_on and flt_plan.spec.has_cap
    adm_active = sel_active or (flt_on and flt_plan.timeline_active)
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

    # telemetry (DESIGN.md §14): the same fold as selection — a static
    # MetricsSpec from the host planner, fixed-shape accumulators appended
    # to the scan carry, occupancy/pop-wait as extra ys columns.  metrics
    # is None on the off path, so every met_on branch vanishes and the
    # program is textually the legacy one (rule TEL001).
    met_on = metrics is not None
    if met_on:
        from repro.telemetry import device as tel_dev
        met_edges = jnp.asarray(metrics.edges, jnp.float32)
    # fault counters (DESIGN.md §16): per-pop i32[4] increments from the
    # fault plan, accumulated in the metrics carry and conformance-checked
    # against the f64 fault replay after the run
    fct_on = met_on and metrics.fault_counters and flt_on
    if fct_on:
        fct_tab = jnp.asarray(flt_plan.counts_table(l_iters))

    def eq36_upload_delay(gains, x0, idx, t_up):
        """Eq. 3-6 re-schedule pipeline: slot gain -> position wrap ->
        distance -> SNR -> Shannon rate -> upload delay.  ``idx`` may be
        a scalar pop or a vector of re-admissions; one definition serves
        the scan body and the re-admission helper."""
        slot = jnp.clip(t_up.astype(jnp.int32), 0, n_slots - 1)
        gain = gains[slot, idx]
        dx = x0[idx] + v_c * t_up                       # Eq. 3
        dx = jnp.mod(dx + cov, 2.0 * cov) - cov         # re-entry wrap
        dist = jnp.sqrt(dx * dx + dy2H2)                # Eq. 4
        snr = pm * gain * dist ** (-alpha_pl) / sigma2
        rate = bw * jnp.log2(1.0 + snr)                 # Eq. 5
        return bits / jnp.maximum(rate, 1e-12)          # Eq. 6

    def aggregate(g, loc, t, cu, cl, dl_t):
        """One arrival's in-scan update of the f32 master ``g`` — mirrors
        the host paths in formula and f32 arithmetic
        (aggregation.mix_update_donated / literal_update_donated)."""
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

    from repro.core.aggregation import chain_coeffs

    bf16 = ring_dtype == "bf16"
    store_dtype = jnp.bfloat16 if bf16 else jnp.float32
    store = ((lambda x: x.astype(jnp.bfloat16)) if bf16
             else (lambda x: x))
    ring_agg = _ring_agg(use_kernel, mesh)
    # Fused-chain mode: aggregation leaves the scan entirely and runs
    # as ring_agg chains between checkpoints (the multi-upload Pallas
    # kernel on TPU/GPU, its jnp form under use_kernel on CPU).  On
    # the CPU default the mix stays *inside* the scan instead,
    # operating on the packed [P] buffer: XLA:CPU FMA-contracts fused
    # elementwise loops by emission context (flags cannot disable it,
    # DESIGN.md §12), and the in-scan form is the one the golden digests
    # were pinned on.
    fused_chain = use_kernel or jax.default_backend() != "cpu"
    # rounds whose post-round model must materialize: later-wave
    # payloads and eval rows — everything else is never read, so the
    # chain streams straight through it
    needed = set(int(x) for x in eval_rounds)
    for T, _s, _e in plan.waves:
        needed |= {int(d[t]) + 1 for t in T if d[t] >= 0}

    def program_flat(w0, gains, x0, qt, qdl, qcu, qcl, imgs, labs, lr):
        local_scan = (client_mod._local_scan_partial if has_partial
                      else client_mod._local_scan)
        g = layout.pack(w0)                 # f32[P] master weights
        locals_buf = jnp.zeros((M, layout.P), store_dtype)
        mst = ring_stats = None
        store_row = store
        if met_on:
            mst = tel_dev.fleet_state(metrics)
            if metrics.ring_guard and bf16:
                # trace-level bf16 ring guard: every stored snapshot
                # row is counted for non-finite / max-|x| (DESIGN §14)
                ring_stats = tel_dev.RingStats()
                store_row = ring_stats.wrap(store)
        snaps = {0: store_row(g)}
        rs = rc = None
        if with_state:
            rs = jnp.zeros(K, jnp.float32)
            rc = jnp.zeros(K, jnp.float32)
        traces = []

        def make_flat_body(locals_buf):
            # fused_chain: queue bookkeeping only — the model is out
            # of the scan carry entirely and aggregation streams
            # per-checkpoint afterwards.  Otherwise the [P]-buffer mix
            # rides in the scan (one fused vector op per pop).  A
            # fresh body per segment: lax.scan caches the traced body
            # on the function's identity, so one closure reused across
            # segments would replay the first wave's locals_buf
            # (DESIGN.md §9).
            def seg_body(carry, r):
                if met_on:
                    carry, mst = carry[:-1], carry[-1]
                if fused_chain:
                    g = None
                    if with_state:
                        qt, qdl, qcu, rs, rc = carry
                    else:
                        qt, qdl, qcu = carry
                elif with_state:
                    g, qt, qdl, qcu, rs, rc = carry
                else:
                    g, qt, qdl, qcu = carry
                i = jnp.argmin(qt)                          # pop
                if met_on:
                    # live slots at the instant of pop (incl. this one)
                    occ = jnp.sum(jnp.isfinite(qt)).astype(jnp.int32)
                t, cu, cl, dl_t = qt[i], qcu[i], qcl[i], qdl[i]
                if fused_chain:
                    if scheme == "mafl":
                        weight = gamma ** (cu - 1.0) * zeta ** (cl - 1.0)
                    else:
                        weight = jnp.float32(1.0)
                else:
                    # Eq. 10+11 on the packed buffer, one vector op;
                    # a cap-discarded pop keeps the old master exactly
                    # (the host skips the update outright)
                    g_new, weight = aggregate(g, locals_buf[r], t, cu,
                                              cl, dl_t)
                    g = (jnp.where(keep_tab[r], g_new, g) if has_cap
                         else g_new)
                if with_state:
                    rew = gamma ** (cu - 1.0) * zeta ** (cl - 1.0)
                    rs = rs.at[i].add(rew)
                    rc = rc.at[i].add(1.0)
                t_up = t + cl
                cu_new = eq36_upload_delay(gains, x0, i, t_up)
                t_new = t_up + cu_new
                if adm_active:
                    t_new = jnp.where(adm_tab[r, i], t_new, jnp.inf)
                qt = qt.at[i].set(t_new)
                qdl = qdl.at[i].set(t)
                qcu = qcu.at[i].set(cu_new)
                if fused_chain:
                    out = ((qt, qdl, qcu, rs, rc) if with_state
                           else (qt, qdl, qcu))
                else:
                    out = ((g, qt, qdl, qcu, rs, rc) if with_state
                           else (g, qt, qdl, qcu))
                ys = (i, t, cu, cl, dl_t, weight)
                if met_on:
                    mst, gap = tel_dev.fleet_pop(
                        mst, met_edges, t=t, dl_t=dl_t,
                        fault_row=fct_tab[r] if fct_on else None)
                    out = out + (mst,)
                    ys = ys + (occ, gap)
                return out, ys
            return seg_body

        def readmit(qt, qdl, qcu, A, t_b):
            A = jnp.asarray(A)
            t_up = t_b + qcl[A]
            cu_new = eq36_upload_delay(gains, x0, A, t_up)
            return (qt.at[A].set(t_up + cu_new), qdl.at[A].set(t_b),
                    qcu.at[A].set(cu_new))

        for T, s, e in plan.waves:
            T = np.asarray(T, np.int32)
            if len(T):
                pay_rounds = d[T] + 1
                shared = bool((pay_rounds == pay_rounds[0]).all())
                if shared:
                    pay = layout.unpack(snaps[int(pay_rounds[0])])
                else:
                    pay = layout.unpack(jnp.stack(
                        [snaps[int(pr)] for pr in pay_rounds]))
                train = _wave_train(local_scan, mesh, len(T), shared,
                                    partial=has_partial)
                extra = (ep_tab[jnp.asarray(T)],) if has_partial else ()
                with jax.named_scope(f"wave_train_{s}"):
                    loc, _ = train(pay, imgs[T], labs[T], lr, *extra)
                locals_buf = locals_buf.at[jnp.asarray(T)].set(
                    layout.pack(loc, dtype=store_dtype))
            seg_traces = []
            # sub-split at re-admission boundaries; the in-scan-mix
            # mode additionally splits at checkpoints so snapshot rows
            # store at trace level between sub-scans
            pts = {b for b in readmit_at if s < b <= e} | {e}
            if not fused_chain:
                pts |= {b for b in needed if s < b <= e}
            a = s
            for b in sorted(pts):
                if b > a:
                    if fused_chain:
                        carry0 = ((qt, qdl, qcu, rs, rc) if with_state
                                  else (qt, qdl, qcu))
                    else:
                        carry0 = ((g, qt, qdl, qcu, rs, rc)
                                  if with_state else (g, qt, qdl, qcu))
                    if met_on:
                        carry0 = carry0 + (mst,)
                    with jax.named_scope(f"event_scan_{a}_{b}"):
                        carry, ys = jax.lax.scan(
                            make_flat_body(locals_buf), carry0,
                            jnp.arange(a, b))
                    if met_on:
                        carry, mst = carry[:-1], carry[-1]
                    if fused_chain:
                        if with_state:
                            qt, qdl, qcu, rs, rc = carry
                        else:
                            qt, qdl, qcu = carry
                    elif with_state:
                        g, qt, qdl, qcu, rs, rc = carry
                    else:
                        g, qt, qdl, qcu = carry
                    traces.append(ys)
                    seg_traces.append(ys)
                if not fused_chain and b in needed:
                    snaps[b] = store_row(g)
                if b in readmit_at:
                    qt, qdl, qcu = readmit(qt, qdl, qcu, readmit_at[b],
                                           traces[-1][1][-1])
                a = b
            if fused_chain:
                # aggregation left the scan entirely: coefficients
                # from the segment's own f32 trace (bitwise the
                # in-scan per-arrival expressions), then one streaming
                # ring_agg chain per checkpoint interval
                t_c, dlt_c, w_c = (
                    jnp.concatenate([tr[k] for tr in seg_traces])
                    for k in (1, 4, 5))
                cc, dd = chain_coeffs(scheme, interpretation, p.beta,
                                      w_c, t=t_c, dl_t=dlt_c,
                                      fedasync_mix=fedasync_mix)
                if has_cap:
                    # cap-discarded pops become exact chain no-ops
                    keep_seg = keep_tab[s:e]
                    cc = jnp.where(keep_seg, cc, 1.0)
                    dd = jnp.where(keep_seg, dd, 0.0)
                coeffs = jnp.stack([cc, dd], axis=1)
                with jax.named_scope(f"ring_chain_{s}_{e}"):
                    g = _chain_segment(g, locals_buf, coeffs, snaps,
                                       s, e, needed, store_row, ring_agg)
        trace = tuple(jnp.concatenate([tr[k] for tr in traces])
                      for k in range(6))
        evals = jnp.stack([snaps[rr] for rr in eval_rounds])
        if with_state:
            ret = (layout.unpack(g), evals, trace, (rs, rc))
        else:
            ret = (layout.unpack(g), evals, trace)
        if met_on:
            met_out = {
                "stale_hist": mst[0],
                "occupancy": jnp.concatenate(
                    [tr[6] for tr in traces]),
                "gap": jnp.concatenate([tr[7] for tr in traces]),
            }
            if fct_on:
                met_out["fault_counts"] = mst[2]
            if ring_stats is not None:
                met_out.update(ring_stats.out())
            ret = ret + (met_out,)
        return ret

    return jax.jit(program_flat)


def _get_program(plan: FleetPlan, p: ChannelParams, *, scheme, interpretation,
                 use_kernel, mesh, fedasync_mix, shapes, layout,
                 ring_dtype="f32", eval_rounds=(), metrics=None,
                 l_iters=1):
    # the trainer function rides in the key as the object itself, not its
    # id(): ids are reused after GC, which could silently replay a program
    # traced against a different (monkeypatched) trainer.  metrics=off is
    # normalized to None *before* this key, so an off run shares the legacy
    # executable object outright (rule TEL001); faults=off likewise
    # contributes a constant None (rule FLT001).
    key = (plan.waves, tuple(plan.dl_round.tolist()), plan.n_slots, p,
           scheme, interpretation, use_kernel, fedasync_mix,
           _mesh_key(mesh), shapes,
           None if plan.sel is None else plan.sel.signature(),
           client_mod._local_scan,
           layout.signature(), ring_dtype, eval_rounds,
           None if metrics is None else metrics.signature(),
           None if plan.flt is None else (plan.flt.signature(), l_iters,
                                          client_mod._local_scan_partial))
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        prog = _build_program(plan, p, scheme=scheme,
                              interpretation=interpretation,
                              use_kernel=use_kernel, mesh=mesh,
                              fedasync_mix=fedasync_mix,
                              layout=layout, ring_dtype=ring_dtype,
                              eval_rounds=eval_rounds, metrics=metrics,
                              l_iters=l_iters)
        _PROGRAM_CACHE[key] = prog
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_SIZE:
            _PROGRAM_CACHE.popitem(last=False)
    else:
        _PROGRAM_CACHE.move_to_end(key)
    return prog


def _stage_run(vehicles_data, *, scheme, rounds, l_iters, lr, params, seed,
               eval_every, use_kernel, init_params, interpretation,
               batch_size, mesh, selection, ring_dtype,
               metrics=None, faults=None, timers=None):
    """Validate, plan, and stage one fleet run — everything up to (but not
    including) executing the compiled program.  Split out of
    :func:`run_simulation_jit` so ``repro.check.dtype_flow`` can build the
    jaxpr of the exact program the engine would run.

    Returns ``(prog, args, plan, layout, eval_rounds, with_state, met)``
    where ``prog(*args)`` is the staged round loop and ``met`` is the
    resolved :class:`MetricsSpec` (None on the exact legacy off path)."""
    from repro.core.flat import ParamLayout
    from repro.telemetry.spec import resolve_metrics
    from repro.telemetry.timers import PhaseTimers

    timers = timers if timers is not None else PhaseTimers()
    if scheme not in _SUPPORTED_SCHEMES:
        raise ValueError(
            f"engine='jit' supports schemes {_SUPPORTED_SCHEMES}, not "
            f"{scheme!r} (fedbuff keeps host-side buffer state — use the "
            "serial or batched engine)")
    if ring_dtype not in ("f32", "bf16"):
        raise ValueError(f"unknown ring_dtype {ring_dtype!r}; "
                         "expected 'f32' or 'bf16'")
    p = params or ChannelParams()
    assert len(vehicles_data) == p.K, (len(vehicles_data), p.K)
    if rounds < 1:
        raise ValueError("rounds must be >= 1")

    with timers.phase("plan"):
        plan = plan_fleet(p, seed, rounds, selection, faults=faults,
                          l_iters=l_iters)
        # the telemetry spec is plan data (DESIGN.md §14): histogram edges
        # derive from the dry run's f64 staleness/pop times, and metrics=off
        # normalizes to None — the exact legacy program
        met = resolve_metrics(
            metrics, stale=plan.times - plan.download_time,
            times=plan.times, n_rsus=1, ring_guard=(ring_dtype == "bf16"),
            fault_counters=plan.flt is not None)
    M = rounds

    with timers.phase("stage"):
        key = jax.random.PRNGKey(seed)
        w0 = init_params if init_params is not None else init_cnn(key)

        # one minibatch stack per consumed round, drawn from the same
        # per-vehicle RNG streams in the same per-cycle order as the host
        # engines (DESIGN.md §3), so every engine trains identical batches
        fleet_batch = min(batch_size, min(d.size for d in vehicles_data))
        clients = Samplers(vehicles_data, lr=lr, batch_size=fleet_batch,
                           seed=seed)
        im_list, lab_list = [], []
        for r in range(M):
            im, lab = clients[plan.veh[r]].sample_batches(l_iters)
            im_list.append(im)
            lab_list.append(lab)
        imgs = jnp.asarray(np.stack(im_list))
        labs = jnp.asarray(np.stack(lab_list))

        gains = jnp.asarray(slot_gain_table(p, seed, plan.n_slots),
                            jnp.float32)
        x0 = jnp.asarray(Mobility(p).x0, jnp.float32)
        qt = jnp.asarray(plan.q0["time"], jnp.float32)
        qdl = jnp.asarray(plan.q0["download_time"], jnp.float32)
        qcu = jnp.asarray(plan.q0["upload_delay"], jnp.float32)
        qcl = jnp.asarray(plan.q0["train_delay"], jnp.float32)

        shapes = (imgs.shape, tuple(
            (str(path), v.shape, str(v.dtype))
            for path, v in jax.tree_util.tree_leaves_with_path(w0)))
        layout = ParamLayout.from_tree(w0)
        eval_rounds = tuple(rr for rr in range(1, M + 1)
                            if rr % eval_every == 0 or rr == rounds)
        prog = _get_program(plan, p, scheme=scheme,
                            interpretation=interpretation,
                            use_kernel=use_kernel, mesh=mesh,
                            fedasync_mix=DEFAULT_FEDASYNC_MIX, shapes=shapes,
                            layout=layout, ring_dtype=ring_dtype,
                            eval_rounds=eval_rounds, metrics=met,
                            l_iters=l_iters)
        with_state = (plan.sel is not None and not plan.sel.is_noop
                      and plan.sel.spec.policy == "eps-bandit")
        args = (w0, gains, x0, qt, qdl, qcu, qcl, imgs, labs,
                jnp.float32(lr))
    return prog, args, plan, layout, eval_rounds, with_state, met


# ---------------------------------------------------------------------------
# public entry point — signature mirrors mafl.run_simulation
# ---------------------------------------------------------------------------
def run_simulation_jit(
    vehicles_data: Sequence[VehicleData],
    test_images: np.ndarray,
    test_labels: np.ndarray,
    *,
    scheme: str = "mafl",
    rounds: int = 60,
    l_iters: int = 5,
    lr: float = 0.01,
    params: Optional[ChannelParams] = None,
    seed: int = 0,
    eval_every: int = 1,
    use_kernel: bool = False,
    init_params=None,
    interpretation: str = "mixing",
    progress=None,
    batch_size: int = 128,
    mesh=None,
    selection=None,
    ring_dtype: str = "f32",
    metrics=None,
    faults=None,
):
    """Run M rounds entirely on device; returns the same ``SimResult`` the
    host engines produce (same record fields, same eval cadence).

    The program keeps every model state as one packed ``[P]`` buffer
    (DESIGN.md §12).  ``ring_dtype="bf16"`` stores snapshot-ring rows and
    upload buffers in bf16 around f32 master weights/accumulation —
    halves ring memory at a documented sub-1e-2 parameter rounding
    (EXPERIMENTS.md §Flat); it must be requested explicitly.

    One behavioral difference from the host engines: the whole round loop
    is a single device program, so ``progress`` fires post-hoc — every
    callback arrives in round order *after* the simulation completes, not
    live per arrival.

    ``metrics="on"`` folds device-resident telemetry into the scan
    (DESIGN.md §14): staleness histogram, slot-queue occupancy and
    argmin-pop wait traces accumulate in fixed-shape carry state, surfaced
    on ``result.report.channels``.  Any falsy value ("off"/None/False)
    stages the *exact* legacy program — same cache entry, bitwise-identical
    outputs (pinned by ``tests/test_telemetry.py``).

    ``faults`` activates the fault-injection layer (DESIGN.md §16): the
    host f64 planner samples the stochastic client-state processes into
    static fault tables folded into the program exactly like selection —
    suppressed re-schedules via the admission table, recovery sweeps via
    boundary re-admissions, staleness-cap discards via a keep column, and
    partial computation via the masked epoch trainer.  Off stages the
    exact legacy program (rule FLT001, pinned by ``tests/test_faults.py``)."""
    from repro.core.mafl import SimResult, evaluate
    from repro.telemetry import RunReport, memory_stats
    from repro.telemetry.report import wave_stats
    from repro.telemetry.timers import PhaseTimers

    timers = PhaseTimers()
    prog, args, plan, layout, eval_rounds, with_state, met = _stage_run(
        vehicles_data, scheme=scheme, rounds=rounds, l_iters=l_iters,
        lr=lr, params=params, seed=seed, eval_every=eval_every,
        use_kernel=use_kernel, init_params=init_params,
        interpretation=interpretation, batch_size=batch_size, mesh=mesh,
        selection=selection, ring_dtype=ring_dtype,
        metrics=metrics, faults=faults, timers=timers)
    M = rounds
    with timers.phase("run"):
        out = jax.block_until_ready(prog(*args))
    with timers.phase("guard"):
        met_dev = None
        if met is not None:
            out, met_dev = out[:-1], out[-1]
        if with_state:
            g, ring, trace, (dev_rs, dev_rc) = out
        else:
            g, ring, trace = out
        t_veh, t_time, t_cu, t_cl, t_dlt, t_w = (np.asarray(x)
                                                 for x in trace)

        # divergence guard: the minibatch stacks were paired to rounds by
        # the host plan — if the device pop order ever disagreed, fail
        # loudly (mirrors the batched engine's dry-run guard) instead of
        # silently training the wrong vehicle's batches.
        if not np.array_equal(t_veh, plan.veh):
            bad = int(np.argmax(t_veh != plan.veh))
            raise RuntimeError(
                "jit engine: device pop order diverged from the host dry "
                f"run at round {bad} (device vehicle {int(t_veh[bad])}, host "
                f"{int(plan.veh[bad])}) — f32 time ties are not expected")
        if not np.allclose(t_time, plan.times, rtol=1e-4, atol=1e-3):
            bad = int(np.argmax(~np.isclose(t_time, plan.times,
                                            rtol=1e-4, atol=1e-3)))
            raise RuntimeError(
                "jit engine: device event times diverged from the host dry "
                f"run at round {bad}: {t_time[bad]} vs {plan.times[bad]}")
        if with_state:
            # selection divergence guard (DESIGN.md §11): the f32 reward
            # accumulators carried through the scan must reproduce the
            # host f64 replay's — the admission decisions were planned from
            # that reward stream, so disagreement means the device saw
            # different arrivals than the masks were computed for
            exp_rs, exp_rc = plan.sel_bandit
            if not np.array_equal(np.asarray(dev_rc), exp_rc):
                raise RuntimeError(
                    "jit engine: device bandit arrival counts diverged "
                    "from the host selection replay")
            if not np.allclose(np.asarray(dev_rs), exp_rs,
                               rtol=1e-4, atol=1e-3):
                raise RuntimeError(
                    "jit engine: device bandit reward accumulators "
                    "diverged from the host selection replay")

        if ring_dtype == "bf16":
            # bf16 divergence guard (DESIGN.md §12): the timeline guards
            # above stay exact (times never depend on params); the
            # parameters may only diverge by bf16 rounding — a non-finite
            # master means the quantized chain blew up, so fail loudly
            # instead of returning it
            if not all(bool(jnp.isfinite(x).all())
                       for x in jax.tree_util.tree_leaves(g)):
                raise RuntimeError(
                    "jit engine: non-finite master weights under "
                    "ring_dtype='bf16' — the quantized snapshot ring "
                    "diverged (rerun with ring_dtype='f32' to bisect)")
        eval_idx = {rr: k for k, rr in enumerate(eval_rounds)}
        result = SimResult(scheme=scheme, rounds=[], acc_history=[],
                           loss_history=[], final_params=g)
    with timers.phase("eval"):
        for r in range(M):
            rec = RoundRecord(round=r + 1, time=float(t_time[r]),
                              vehicle=int(t_veh[r]),
                              upload_delay=float(t_cu[r]),
                              train_delay=float(t_cl[r]),
                              weight=float(t_w[r]))
            rr = r + 1
            if rr % eval_every == 0 or rr == rounds:
                acc, loss = evaluate(layout.unpack(ring[eval_idx[rr]]),
                                     test_images, test_labels)
                rec.accuracy, rec.loss = acc, loss
                result.acc_history.append((rr, acc))
                result.loss_history.append((rr, loss))
                if progress:
                    progress(rr, acc)
            result.rounds.append(rec)
    with timers.phase("report"):
        sel_summary = None if plan.sel is None else plan.sel.summary()
        flt_plan = plan.flt
        flt_report = None
        if flt_plan is not None:
            import dataclasses
            result.extras["faults"] = flt_plan.summary(l_iters)
            flt_report = {"spec": dataclasses.asdict(flt_plan.spec),
                          "counts": flt_plan.counts(l_iters)}
        p = params or ChannelParams()
        channels = {}
        if met is not None:
            channels = {k: np.asarray(v) for k, v in met_dev.items()}
            if flt_plan is not None and "fault_counts" in channels:
                # fault-counter divergence guard (DESIGN.md §16): the scan-
                # carry accumulators must reproduce the f64 fault replay's
                # totals — disagreement means the device consumed a
                # different pop sequence than the fault tables were planned
                # for
                exp = flt_plan.counts_table(l_iters).sum(axis=0)
                if not np.array_equal(channels["fault_counts"], exp):
                    raise RuntimeError(
                        "jit engine: device fault counters diverged from "
                        f"the host fault replay ({channels['fault_counts']} "
                        f"vs {exp})")
            # bandit-style reward trace derived from the pop trace — the
            # per-arrival quality signal the selection layer would score
            # (gamma^(cu-1) * zeta^(cl-1)), published whether or not a
            # bandit policy is active
            channels["reward"] = (
                p.gamma ** (t_cu.astype(np.float64) - 1.0)
                * p.zeta ** (t_cl.astype(np.float64) - 1.0))
            if with_state:
                channels["reward_sum"] = np.asarray(dev_rs)
                channels["reward_count"] = np.asarray(dev_rc)
        memory = memory_stats()
        waves = wave_stats(plan.waves, p.K)
    result.report = RunReport(
        engine="jit", scheme=scheme, rounds=rounds, seed=seed,
        metrics_on=met is not None,
        spec=None if met is None else met.to_json(),
        phases=timers.snapshot(), compile=timers.compile_counts(),
        plan=plan.scheduled, memory=memory, selection=sel_summary,
        faults=flt_report, waves=waves, channels=channels)
    return result
