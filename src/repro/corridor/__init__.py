"""Device-resident multi-RSU corridor subsystem (DESIGN.md §10).

An R-RSU highway corridor run entirely on device (``engine="corridor"``):
per-RSU slot event queues batched over a leading RSU axis, handover as a
vectorized slot-migration step, wave-hoisted local training, and a periodic
cloud tier reconciling the R cohort models (FedAvg or EMA, optionally via
the Pallas ``weighted_agg`` kernel), the packed cohort stack optionally
sharded over an ``"rsu"`` mesh axis.  ``corridor.reference`` holds the retired serial
handover loop the engine is conformance-tested against.
"""
from repro.corridor.plan import CorridorPlan, plan_corridor
from repro.corridor.engine import run_corridor_simulation
from repro.corridor.reference import run_handover_simulation

__all__ = ["CorridorPlan", "plan_corridor", "run_corridor_simulation",
           "run_handover_simulation"]
