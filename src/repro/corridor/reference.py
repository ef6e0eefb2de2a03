"""The serial multi-RSU handover loop — retired to a reference
implementation (DESIGN.md §10).

This is the original host-Python corridor engine: one heap pop, one local
update, one cohort aggregation per arrival, with periodic cross-RSU
reconciliation.  It pays Python dispatch per event, so it caps out around
K=40 — the device-resident engine (``corridor.engine``) is the production
path, and this loop survives as the executable specification the
conformance suite pins that engine against (identical arrival traces,
allclose models; ``tests/test_engine_conformance.py``).
"""
from __future__ import annotations

from typing import Sequence

from repro.channel import ChannelParams, CorridorMobility
from repro.core.hierarchical import ema_toward, reconcile_models
from repro.faults import (arrival_step, check_faults_reconcile,
                          initial_vehicles, make_fault_state)
from repro.selection import (check_reconcile_mode, make_selection_state,
                             scenario_spec)


def run_handover_simulation(sc, vehicles_data: Sequence,
                            test_images, test_labels, p: ChannelParams,
                            *, seed: int = 0, eval_every: int = 10,
                            interpretation: str = "mixing",
                            use_kernel: bool = False,
                            batch_size: int = 128,
                            progress=None, selection=None, metrics=None,
                            faults=None):
    """Multi-RSU MAFL with handover (beyond paper, DESIGN.md §8/§10).

    Each RSU keeps its own cohort model and applies the paper's per-arrival
    aggregation; a vehicle downloads from the RSU serving its position at
    download time and uploads to the RSU serving it at arrival time.  Every
    ``sc.reconcile_every`` arrivals the cohort models are reconciled — the
    corridor-scale version of the hierarchical cross-pod reconcile, FedAvg
    (``sc.reconcile_mode == "fedavg"``: all cohorts adopt the mean) or EMA
    (``"ema"``: each cohort moves ``sc.reconcile_tau`` toward the mean).

    ``sc`` is any object with the Scenario fields this reads (scheme,
    rounds, l_iters, lr, n_rsus, reconcile_every, reconcile_mode,
    reconcile_tau, corridor_entry)."""
    import jax
    import numpy as np

    from repro.core.client import Samplers
    from repro.core.mafl import SimResult, _Timeline, _host_report, evaluate
    from repro.core.server import RSUServer
    from repro.models.cnn import init_cnn
    from repro.telemetry import metrics_requested
    from repro.telemetry.timers import PhaseTimers

    mode = getattr(sc, "reconcile_mode", "fedavg")
    tau = getattr(sc, "reconcile_tau", 0.5)
    entry = getattr(sc, "corridor_entry", "uniform")
    spec = selection if selection is not None else scenario_spec(sc)
    check_reconcile_mode(spec, mode)
    check_faults_reconcile(faults, mode)

    init = init_cnn(jax.random.PRNGKey(seed))
    servers = [RSUServer(init, p, scheme=sc.scheme, use_kernel=use_kernel,
                         interpretation=interpretation)
               for _ in range(sc.n_rsus)]
    corridor = CorridorMobility(p, sc.n_rsus, entry=entry)
    # same scheduling rules as the single-RSU engine — only the geometry
    # (distance to the serving RSU) differs.  Selection re-scores at every
    # reconcile boundary (handed-over vehicles by their new RSU).
    sel = make_selection_state(spec, p, corridor, seed, sc.rounds,
                               resel_every=sc.reconcile_every)
    # fault recovery sweeps follow the reconcile cadence, like selection
    flt = make_fault_state(faults, p, seed, sc.rounds, sc.l_iters,
                           recheck_every=sc.reconcile_every)
    timeline = _Timeline(p, seed, distance_fn=corridor.distance,
                         cl_scale=None if flt is None else flt.cl_scale)
    queue = timeline.queue
    fleet_batch = min(batch_size, min(d.size for d in vehicles_data))
    clients = Samplers(vehicles_data, lr=sc.lr, batch_size=fleet_batch,
                       seed=seed)

    def schedule(vehicle: int, t_download: float):
        rsu = int(corridor.serving_rsu(vehicle, t_download))
        return timeline.schedule(vehicle, t_download,
                                 payload=servers[rsu].global_params)

    for k in initial_vehicles(sel, flt, p.K):
        schedule(k, 0.0)

    timers = PhaseTimers()
    met_req = metrics_requested(metrics)
    ch_stale, ch_occ, ch_gap, ch_times = [], [], [], []
    ch_rsu, ch_ho = [], []

    result = SimResult(scheme=f"{sc.scheme}+handover", rounds=[],
                       acc_history=[], loss_history=[])
    total = 0
    with timers.phase("run"):
        while total < sc.rounds and len(queue):
            if met_req:
                # per-RSU live slots before the pop — a pending slot's row
                # is the RSU serving the vehicle at its *arrival* time
                # (same rule the device bakes into the slot migration)
                pend = list(queue.pending())
                vs = np.array([pe.vehicle for pe in pend], np.int64)
                ts = np.array([pe.time for pe in pend])
                ch_occ.append(np.bincount(
                    np.asarray(corridor.serving_rsu(vs, ts), np.int64),
                    minlength=sc.n_rsus))
            ev = queue.pop()
            keep = True
            if flt is not None:
                # staleness-cap verdict + this cycle's epoch count, fixed
                # before the gate below draws the *next* cycle's block
                keep, _ = flt.on_pop(ev.vehicle, total)
            local_params, _ = clients[ev.vehicle].local_update(
                ev.payload, sc.l_iters,
                n_ep=(flt.epoch_of(ev.vehicle)
                      if flt is not None and flt.spec.has_partial
                      else None))
            rsu = int(corridor.serving_rsu(ev.vehicle, ev.time))  # handover target
            if met_req:
                ch_stale.append(ev.time - ev.download_time)
                ch_gap.append(ev.time - (ch_times[-1] if ch_times else 0.0))
                ch_times.append(ev.time)
                ch_rsu.append(rsu)
            rec = servers[rsu].receive(
                local_params, time=ev.time, vehicle=ev.vehicle,
                upload_delay=ev.upload_delay, train_delay=ev.train_delay,
                download_time=ev.download_time, discard=not keep)
            rec.rsu = rsu
            total += 1
            consensus = None
            if total % sc.reconcile_every == 0:
                consensus = reconcile_models(
                    [s.global_params for s in servers])
                if mode == "ema":
                    for s in servers:
                        s.global_params = ema_toward(s.global_params,
                                                     consensus, tau)
                else:
                    for s in servers:
                        s.global_params = consensus
            if total % eval_every == 0 or total == sc.rounds:
                if consensus is None or mode == "ema":
                    consensus = reconcile_models(
                        [s.global_params for s in servers])
                with timers.phase("eval"):
                    acc, loss = evaluate(consensus, test_images,
                                         test_labels)
                rec.accuracy, rec.loss = acc, loss
                result.acc_history.append((total, acc))
                result.loss_history.append((total, loss))
                if progress:
                    progress(total, acc)
            result.rounds.append(rec)
            nev = None
            if sel is None and flt is None:
                nev = schedule(ev.vehicle, ev.time)
            else:
                # mask at schedule (post-reconcile, like the ordinary
                # re-download): park unadmitted/faulted vehicles, re-score
                # and sweep recoveries at every reconcile boundary
                res = {}
                arrival_step(
                    sel, flt, r=total - 1, vehicle=ev.vehicle, time=ev.time,
                    upload_delay=ev.upload_delay,
                    train_delay=ev.train_delay, pending=len(queue),
                    schedule=lambda v, t=ev.time: res.__setitem__(
                        "nev", schedule(v, t)),
                    readmit=lambda v, t=ev.time: schedule(v, t))
                nev = res.get("nev")
            if met_req:
                # handover = the admitted re-schedule lands on a new RSU;
                # parked vehicles (and boundary re-admissions) don't count
                ch_ho.append(nev is not None and int(
                    corridor.serving_rsu(ev.vehicle, nev.time)) != rsu)
            timeline.prune()

    result.final_params = reconcile_models(
        [s.global_params for s in servers])
    sel_summary = None if sel is None else sel.plan().summary()
    flt_plan = None if flt is None else flt.plan()
    if flt_plan is not None:
        result.extras["faults"] = flt_plan.summary(sc.l_iters)
    ho_count = (np.bincount(np.asarray(ch_rsu, np.int64)[
        np.asarray(ch_ho, bool)], minlength=sc.n_rsus)
        if met_req else None)
    result.report = _host_report(
        engine="serial", scheme=f"{sc.scheme}+handover", rounds=total,
        seed=seed, metrics=metrics, met_req=met_req, p=p, timers=timers,
        selection=sel_summary, records=result.rounds, stale=ch_stale,
        occ=ch_occ, gap=ch_gap, times=ch_times, n_rsus=sc.n_rsus,
        up_rsu=np.asarray(ch_rsu, np.int64) if met_req else None,
        handover=np.asarray(ch_ho, bool) if met_req else None,
        handover_count=ho_count, faults=flt_plan, l_iters=sc.l_iters)
    return result
