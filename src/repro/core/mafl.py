"""Top-level MAFL simulation (Algorithm 1) — the paper's experiment engine.

Couples the channel/mobility simulator, the event-driven async scheduler, the
vehicle clients, and the RSU aggregation into ``run_simulation``, which
reproduces Figs. 3-5.

Two engines share identical event semantics (DESIGN.md §2-§3):

``engine="serial"``
    One event at a time, exactly Algorithm 1's arrival order.  Each local
    update is a single ``lax.scan`` dispatch.

``engine="batched"`` (default)
    Wave-based: every pending upload's payload snapshot is frozen at
    schedule time, so all pending local updates are mutually independent
    and train together — full ``wave_chunk``-sized slices under
    ``jax.vmap`` of the same scan (one dispatch per chunk, one compiled
    program for the whole run), remainders through the shared serial
    program.  Aggregation still consumes events strictly in time order, so
    the (round, vehicle, time) sequence is bit-identical to the serial
    engine — verified by ``tests/test_engine_equivalence.py``.

``engine="jit"``
    Device-resident (DESIGN.md §9, ``core/jit_engine.py``): the event
    queue becomes fixed per-vehicle slot arrays, slot gains a precomputed
    table, payload snapshots a round-indexed ring, and pop → aggregate →
    re-schedule for all M rounds runs inside one compiled program with
    training hoisted into per-wave vmap blocks.  Same (round, vehicle)
    trace as the host engines with times carried in f32 — pinned by
    ``tests/test_engine_conformance.py``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import (ChannelParams, Mobility, RayleighAR1,
                           SlotGainCache, shannon_rate, training_delay,
                           upload_delay)
from repro.core.client import Samplers, VehicleData, local_update_many
from repro.core.events import EventQueue
from repro.core.server import RSUServer
from repro.faults import arrival_step, initial_vehicles, make_fault_state
from repro.models.cnn import cnn_forward, init_cnn
from repro.selection import make_selection_state


# accepted run_simulation/run_scenario engine names
ENGINES = ("batched", "serial", "jit")


@dataclass
class SimResult:
    scheme: str
    rounds: list
    acc_history: list          # (round, accuracy)
    loss_history: list         # (round, loss)
    final_params: object = None
    # engine-specific additions (e.g. the corridor engine's per-RSU trace
    # and cohort snapshots) that don't fit the common record schema
    extras: dict = field(default_factory=dict)
    # typed, versioned run telemetry (repro.telemetry.report.RunReport):
    # phase timers and plan statics always; device/host channel data when
    # the run asked for metrics (DESIGN.md §14)
    report: object = None

    def final_accuracy(self) -> float:
        return self.acc_history[-1][1] if self.acc_history else float("nan")


@jax.jit
def _eval_step(params, images, labels, mask):
    """Masked per-batch eval: (#correct, summed NLL) over mask==1 rows."""
    logits = cnn_forward(params, images)
    correct = jnp.sum((jnp.argmax(logits, -1) == labels).astype(
        jnp.float32) * mask)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return correct, jnp.sum(nll * mask)


def evaluate(params, images, labels, batch: int = 1000):
    """Global-model metrics on the test set (Eqs. 1, 12).

    Every slice — including the ragged final one — is padded to exactly
    ``batch`` rows with the padding masked out of both metrics, so all
    rounds of all simulations share one compiled eval program instead of
    retracing ``cnn_forward`` on the leftover shape.  ``batch`` is capped
    at the test-set size — padding a small set up to a large slice would
    waste forward compute on every call."""
    images = np.asarray(images)
    labels = np.asarray(labels)
    n = len(labels)
    batch = max(min(batch, n), 1)
    correct = loss_sum = 0.0
    for s in range(0, n, batch):
        img, lab = images[s:s + batch], labels[s:s + batch]
        m = len(lab)
        if m < batch:
            img = np.concatenate(
                [img, np.zeros((batch - m,) + img.shape[1:], img.dtype)])
            lab = np.concatenate([lab, np.zeros(batch - m, lab.dtype)])
        mask = (np.arange(batch) < m).astype(np.float32)
        c, l = _eval_step(params, jnp.asarray(img), jnp.asarray(lab),
                          jnp.asarray(mask))
        correct += float(c)
        loss_sum += float(l)
    return correct / n, loss_sum / n


def run_simulation(
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
    progress: Optional[Callable[[int, float], None]] = None,
    engine: str = "batched",
    wave_chunk: int = 16,
    batch_size: int = 128,
    selection=None,
    ring_dtype: str = "f32",
    metrics=None,
    faults=None,
) -> SimResult:
    """Run M rounds of the chosen aggregation scheme (Algorithm 1).

    Every vehicle uses the same minibatch size — ``min(batch_size, min_i
    D_i)`` — so one world compiles exactly one local-training shape (the
    per-vehicle *data volume* heterogeneity that Eq. 8 feeds on lives in
    the delays, not the minibatch; DESIGN.md §6).

    ``selection`` (None | policy name | ``SelectionSpec``) activates the
    vehicle-selection layer (DESIGN.md §11): unadmitted vehicles are parked
    at (re-)schedule time — they occupy no queue slot and train no wave —
    and epoch boundaries (``spec.resel_every`` arrivals) re-score the fleet.
    ``None`` runs the exact legacy path.

    ``metrics`` (None/'off' | 'on' | ``MetricsSpec``) activates the
    telemetry channels (DESIGN.md §14); the host engines collect them in
    f64 alongside the event loop, the device engines accumulate them in
    the scan carry.  Off is the exact legacy path; phase timers and the
    ``result.report`` record are always attached.

    ``faults`` (None/'off' | profile name | ``FaultSpec``) activates the
    fault-injection layer (DESIGN.md §16): seeded stochastic dropout,
    blackout, partial computation, straggler inflation and staleness-cap
    discard, identical decision-for-decision on every engine.  Off is the
    exact legacy path."""
    from repro.telemetry import metrics_requested
    from repro.telemetry.timers import PhaseTimers

    if engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r}; expected one of {ENGINES}")
    if engine == "jit":
        # device-resident mega-fleet engine (DESIGN.md §9/§12): whole round
        # loop in one compiled program, same event semantics and records
        from repro.core.jit_engine import run_simulation_jit
        return run_simulation_jit(
            vehicles_data, test_images, test_labels, scheme=scheme,
            rounds=rounds, l_iters=l_iters, lr=lr, params=params, seed=seed,
            eval_every=eval_every, use_kernel=use_kernel,
            init_params=init_params, interpretation=interpretation,
            progress=progress, batch_size=batch_size, selection=selection,
            ring_dtype=ring_dtype, metrics=metrics, faults=faults)
    if ring_dtype != "f32":
        # the bf16 snapshot ring exists only on the device engines
        # (DESIGN.md §12) — an explicit gate, never a silent precision
        # change on the host paths
        raise ValueError(
            f"ring_dtype={ring_dtype!r} requires engine='jit' (or the "
            "corridor engine); the host engines keep full-precision "
            "pytrees")
    p = params or ChannelParams()
    assert len(vehicles_data) == p.K, (len(vehicles_data), p.K)
    key = jax.random.PRNGKey(seed)
    global_params = init_params if init_params is not None else init_cnn(key)

    server = RSUServer(global_params, p, scheme=scheme, use_kernel=use_kernel,
                       interpretation=interpretation)
    fleet_batch = min(batch_size, min(d.size for d in vehicles_data))
    clients = Samplers(vehicles_data, lr=lr, batch_size=fleet_batch,
                       seed=seed)

    timers = PhaseTimers()
    met_req = metrics_requested(metrics)
    # host-side channel collection (DESIGN.md §14): the event loop already
    # sees every value the device accumulators fold, so the host engines
    # record the channels directly in f64
    ch_stale: list = []
    ch_occ: list = []
    ch_gap: list = []
    ch_times: list = []

    sel = make_selection_state(selection, p, Mobility(p), seed, rounds)
    flt = make_fault_state(faults, p, seed, rounds, l_iters)
    timeline = _Timeline(p, seed,
                         cl_scale=None if flt is None else flt.cl_scale)
    queue = timeline.queue
    if engine == "batched":
        # The event timeline depends only on the channel/mobility/data-size
        # processes, never on training results — so a cheap time-only dry
        # run tells us *exactly* which (vehicle, cycle) uploads the M
        # rounds consume, and the wave engine trains nothing else.  (The
        # replay carries its own SelectionState/FaultState, so admission
        # and fault decisions are reproduced byte-for-byte.)
        with timers.phase("plan"):
            consumed = _consumed_events(p, seed, rounds, selection,
                                        faults=faults, l_iters=l_iters)

    def schedule(vehicle: int, t_download: float):
        timeline.schedule(vehicle, t_download, server.global_params)

    for k in initial_vehicles(sel, flt, p.K):
        schedule(k, 0.0)

    result = SimResult(scheme=scheme, rounds=[], acc_history=[],
                       loss_history=[])

    def consume(ev) -> None:
        """One arrival: aggregate in time order, eval, re-download (Fig. 2).

        ``ev.local_params`` must already hold the local update trained from
        the stale payload snapshot."""
        r = server.round                    # 0-based index of this pop
        if met_req:
            # the pop already happened (+1) and the re-schedule has not:
            # the same instant the device engines count isfinite slots at
            ch_occ.append(len(queue) + 1)
            ch_stale.append(ev.time - ev.download_time)
            ch_gap.append(ev.time - (ch_times[-1] if ch_times else 0.0))
            ch_times.append(ev.time)
        # staleness-cap verdict BEFORE aggregation: a discarded arrival
        # still counts as a round, only the model update is skipped
        keep = True if flt is None else flt.on_pop(ev.vehicle, r)[0]
        rec = server.receive(
            ev.local_params, time=ev.time, vehicle=ev.vehicle,
            upload_delay=ev.upload_delay, train_delay=ev.train_delay,
            download_time=ev.download_time, discard=not keep)
        ev.local_params = ev.payload = None
        if server.round % eval_every == 0 or server.round == rounds:
            with timers.phase("eval"):
                acc, loss = evaluate(server.global_params, test_images,
                                     test_labels)
            rec.accuracy, rec.loss = acc, loss
            result.acc_history.append((server.round, acc))
            result.loss_history.append((server.round, loss))
            if progress:
                progress(server.round, acc)
        # mask at schedule: the vehicle re-downloads the fresh global model
        # (Fig. 2) only while admitted AND live; epoch boundaries re-score,
        # recovery sweeps wake dark vehicles whose blackout has passed
        arrival_step(sel, flt, r=r, vehicle=ev.vehicle, time=ev.time,
                     upload_delay=ev.upload_delay,
                     train_delay=ev.train_delay, pending=len(queue),
                     schedule=lambda v: schedule(v, ev.time))
        timeline.prune()

    if engine == "serial":
        with timers.phase("run"):
            while server.round < rounds and len(queue):
                ev = queue.pop()
                # local training from the model the vehicle downloaded (the
                # stale snapshot in the payload); the compute runs now, but
                # the ordering and delays follow the event times
                # (DESIGN.md §2).
                ev.local_params, _ = clients[ev.vehicle].local_update(
                    ev.payload, l_iters,
                    n_ep=(flt.epoch_of(ev.vehicle)
                          if flt is not None and flt.spec.has_partial
                          else None))
                consume(ev)
    else:
        with timers.phase("run"):
            while server.round < rounds and len(queue):
                # Wave: train every pending upload that the dry-run proved
                # will be consumed and whose result is missing.  Payload
                # snapshots are frozen at schedule time, so these trainings
                # are mutually independent and zero of them are wasted.
                untrained = sorted(
                    (ev for ev in queue.pending()
                     if ev.local_params is None
                     and (ev.vehicle, ev.cycle) in consumed),
                    key=lambda ev: (ev.time, ev.seq))
                batches = [clients[ev.vehicle].sample_batches(l_iters)
                           for ev in untrained]
                # partial computation (DESIGN.md §16): the epoch count of
                # each pending cycle was fixed at its schedule, so the wave
                # can read it here — all l_iters batches are still drawn
                # (RNG-stream alignment across engines)
                n_eps = ([flt.epoch_of(ev.vehicle) for ev in untrained]
                         if flt is not None and flt.spec.has_partial
                         else None)
                outs, losses = local_update_many(
                    [ev.payload for ev in untrained], batches, lr,
                    chunk=wave_chunk, n_eps=n_eps)
                for ev, out, lo in zip(untrained, outs, losses):
                    ev.local_params, ev.local_loss = out, lo
                # Drain in time order until an event without a precomputed
                # result (freshly re-scheduled) reaches the front —
                # identical arrival semantics to the serial engine.  A
                # front event that is outside the consumed set can only
                # mean rounds are exhausted (the dry run replicates this
                # pop sequence).
                while (server.round < rounds and len(queue)
                       and queue.peek().local_params is not None):
                    consume(queue.pop())
                if (not untrained and server.round < rounds and len(queue)
                        and queue.peek().local_params is None):
                    # the dry run said the front event is never consumed,
                    # yet rounds remain — the timelines have diverged; fail
                    # loudly rather than silently returning a truncated run
                    raise RuntimeError(
                        "batched engine: dry-run consumed-set diverged "
                        f"from live timeline at round {server.round} "
                        f"(front event vehicle={queue.peek().vehicle} "
                        f"cycle={queue.peek().cycle})")

    result.rounds = server.rounds
    result.final_params = server.global_params
    sel_summary = None if sel is None else sel.plan().summary()
    flt_plan = None if flt is None else flt.plan()
    if flt_plan is not None:
        result.extras["faults"] = flt_plan.summary(l_iters)
    result.report = _host_report(
        engine=engine, scheme=scheme, rounds=rounds, seed=seed,
        metrics=metrics, met_req=met_req, p=p, timers=timers,
        selection=sel_summary, records=result.rounds, stale=ch_stale,
        occ=ch_occ, gap=ch_gap, times=ch_times, faults=flt_plan,
        l_iters=l_iters)
    return result


def _host_report(*, engine, scheme, rounds, seed, metrics, met_req, p,
                 timers, selection, records, stale, occ, gap, times,
                 n_rsus=1, up_rsu=None, handover=None,
                 handover_count=None, faults=None, l_iters=1):
    """Build the host engines' :class:`RunReport` (DESIGN.md §14): f64
    channels collected alongside the event loop, bucketed through the same
    planner edges the device path would use (identical by construction —
    the host values ARE the planner replay)."""
    from repro.telemetry.report import RunReport
    from repro.telemetry.spec import resolve_metrics, stale_histogram
    from repro.telemetry.timers import memory_stats

    report = RunReport(engine=engine, scheme=scheme, rounds=rounds,
                       seed=seed, metrics_on=met_req,
                       phases=timers.snapshot(),
                       compile=timers.compile_counts(),
                       memory=memory_stats(), selection=selection)
    if faults is not None:
        import dataclasses
        report.faults = {"spec": dataclasses.asdict(faults.spec),
                         "counts": faults.counts(l_iters)}
    if met_req:
        st = np.asarray(stale)
        spec = resolve_metrics(metrics, stale=st, times=np.asarray(times),
                               n_rsus=n_rsus,
                               fault_counters=faults is not None)
        report.spec = spec.to_json()
        channels = {
            "stale_hist": stale_histogram(spec.edges, st, rsu=up_rsu,
                                          n_rsus=n_rsus),
            "occupancy": np.asarray(occ, np.int64),
            "gap": np.asarray(gap),
        }
        if records:
            # the bandit reward IS the paper's delay weight (Eqs. 7, 9) —
            # derived per-pop from the recorded delays for every scheme
            cu = np.array([r.upload_delay for r in records])
            cl = np.array([r.train_delay for r in records])
            channels["reward"] = p.gamma ** (cu - 1.0) * p.zeta ** (cl - 1.0)
        if handover is not None:
            channels["handover"] = np.asarray(handover, np.int64)
            channels["handover_count"] = np.asarray(handover_count,
                                                    np.int64)
        report.channels = channels
    return report


class _Timeline:
    """The event timeline: channel gains, mobility, and the pending-upload
    queue.  Times depend only on (params, seed) — never on training — so a
    payload-free instance replays the identical schedule (DESIGN.md §3).

    ``distance_fn(vehicle, t) -> meters`` defaults to the single-RSU
    :class:`Mobility`; the multi-RSU scenario engine substitutes its
    corridor geometry while keeping every other scheduling rule identical.
    It broadcasts over arrays of vehicles and times for
    :meth:`schedule_initial`.

    Channel gains are sampled per discrete slot and kept only for the live
    event window (``SlotGainCache``): pops are globally time-ordered, so
    slots below the earliest pending event can never be read again."""

    def __init__(self, p: ChannelParams, seed: int, distance_fn=None,
                 cl_scale=None):
        self.p = p
        if distance_fn is None:
            mobility = Mobility(p)
            self.distance = mobility.distance
            self.distance_at = mobility.distance_at
        else:
            self.distance = self.distance_at = distance_fn
        self.gains = SlotGainCache(RayleighAR1(p, seed=seed))
        self.queue = EventQueue()
        self._cycle = [0] * p.K
        # per-vehicle straggler multipliers on the Eq. 8 training delay
        # (DESIGN.md §16) — f64, constant over the run, default identity
        self.cl_scale = cl_scale
        # uploads scheduled by schedule_initial's column block and by
        # schedule one at a time (the planners report it)
        self.counts = {"bulk": 0, "single": 0}

    def schedule(self, vehicle: int, t_download: float, payload=None):
        """Vehicle downloads w_g at t_download, trains C_l, uploads C_u.

        The *snapshot of the global model at download time* rides along in
        the event payload — by upload time other vehicles have advanced the
        global model, so this is what makes the uploads genuinely stale
        (the dynamics the paper's weighting is designed around)."""
        p = self.p
        self.counts["single"] += 1
        i1 = vehicle + 1                                    # 1-based index
        c_l = training_delay(p, i1)
        if self.cl_scale is not None:
            c_l = c_l * float(self.cl_scale[vehicle])
        t_up = t_download + c_l
        gain = self.gains.at(t_up)[vehicle]
        rate = shannon_rate(p, gain, self.distance(vehicle, t_up))
        c_u = upload_delay(p, rate)
        cyc = self._cycle[vehicle]
        self._cycle[vehicle] += 1
        return self.queue.push(t_up + c_u, vehicle,
                               download_time=t_download, train_delay=c_l,
                               upload_delay=c_u, payload=payload, cycle=cyc)

    def schedule_initial(self, vehicles) -> None:
        """:meth:`schedule` at t = 0 for each of ``vehicles`` (distinct),
        as one f64 evaluation entering the queue as one column block.

        Bitwise equal to ``for k in vehicles: schedule(k, 0.0)``, sequence
        numbers included: every step is the scalar path's own IEEE
        operation applied elementwise, the gains come from the same AR(1)
        slots (``SlotGainCache.gather``), and the distance must broadcast
        with the scalar call's arithmetic (``Mobility.distance_at``,
        ``CorridorMobility.distance``).  The one exception is the path
        loss ``d ** -alpha``: ``np.power`` takes SIMD kernels whose low
        bits differ from the scalar ``pow``, so it is taken per element."""
        p = self.p
        v = np.asarray(vehicles, np.int64)
        c_l = training_delay(p, v + 1)                      # Eq. 8
        if self.cl_scale is not None:
            c_l = c_l * np.asarray(self.cl_scale, np.float64)[v]
        t_up = 0.0 + c_l
        gain = self.gains.gather(t_up, v)
        path = np.array([d ** (-p.alpha) for d in
                         self.distance_at(v, t_up).tolist()], np.float64)
        rate = p.B * np.log2(1.0 + p.p_m * gain * path / p.sigma2)  # Eq. 5
        c_u = p.model_bits / np.maximum(rate, 1e-12)                # Eq. 6
        cyc = np.array(self._cycle, np.int64)
        self.queue.push_block(t_up + c_u, v, download_time=np.zeros(len(v)),
                              train_delay=c_l, upload_delay=c_u,
                              cycle=cyc[v])
        cyc[v] += 1
        self._cycle = cyc.tolist()
        self.counts["bulk"] += len(v)

    def prune(self):
        if len(self.queue):
            self.gains.prune_below(self.queue.earliest_time())


def _consumed_events(p: ChannelParams, seed: int, rounds: int,
                     selection=None, faults=None,
                     l_iters: int = 5) -> set[tuple[int, int]]:
    """Dry-run the timeline (no training, no payloads): the exact set of
    (vehicle, cycle) uploads consumed within ``rounds`` arrivals.  With a
    selection policy or a fault model, the replay drives identical
    ``SelectionState``/``FaultState`` instances so parked, dropped, and
    blacked-out cycles never enter the set."""
    flt = make_fault_state(faults, p, seed, rounds, l_iters)
    tl = _Timeline(p, seed, cl_scale=None if flt is None else flt.cl_scale)
    sel = make_selection_state(selection, p, Mobility(p), seed, rounds)
    for k in initial_vehicles(sel, flt, p.K):
        tl.schedule(k, 0.0)
    out: set[tuple[int, int]] = set()
    while len(out) < rounds and len(tl.queue):
        ev = tl.queue.pop()
        r = len(out)
        out.add((ev.vehicle, ev.cycle))
        if flt is not None:
            flt.on_pop(ev.vehicle, r)
        if sel is None and flt is None:
            tl.schedule(ev.vehicle, ev.time)
        else:
            arrival_step(
                sel, flt, r=r, vehicle=ev.vehicle, time=ev.time,
                upload_delay=ev.upload_delay, train_delay=ev.train_delay,
                pending=len(tl.queue),
                schedule=lambda v, t=ev.time: tl.schedule(v, t))
        tl.prune()
    return out
