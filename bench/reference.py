"""Plain reference of one MAFL study: the yardstick that decides `correct`.

A straightforward, sequential implementation of the semantics a
configuration file states, written from the paper (arXiv:2208.01901
Section III, Eqs. 1-11, Table I) and the configuration alone.  It imports
nothing of the program under test and takes nothing the program made: the
data set, the vehicles' shards, the Rayleigh channel, the event
timeline, the initial weights and every minibatch are drawn here from
the seed, in the same order and through the same numpy / jax.random calls
that the configuration's semantics name.  What is the model's (the data
set, the initial weights, the local step and the evaluation) comes from
the module the configuration names (``bench/models/<model>.py``); what is
here the paper fixes for any model.

One study, in order of simulated time:

1. every vehicle downloads the initial model at t = 0, trains for C_l
   (Eq. 8), uploads for C_u (Eqs. 3-6, Rayleigh AR(1) gain of the slot in
   which the upload starts) and arrives at t_download + C_l + C_u;
2. the RSU pops arrivals in time order (ties by scheduling order); the
   arriving vehicle's upload is one SGD step (Eqs. 1-2) from the model it
   downloaded, stored at the configuration's upload width, and is mixed
   into the model of the RSU serving the vehicle at arrival:
   ``g <- (1 - a) g + a l`` with ``a = clip((1 - beta) gamma^(C_u - 1)
   zeta^(C_l - 1), 0, 1)`` (Eqs. 7, 9, 10-11, mixing reading);
3. the vehicle downloads again at once, from that RSU (the stored
   snapshot of its model after this arrival);
4. with several RSUs, every ``reconcile_every`` arrivals all cohort models
   adopt their mean (FedAvg) before the re-download;
5. every ``eval_every`` arrivals and at the last one the test set is
   evaluated on the stored snapshot (one RSU) or on the mean of the cohort
   models (several RSUs).

Arithmetic is float32 (the model module states its matmul precision);
only the stored snapshot and upload rows take the configuration's storage
dtype.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import models

STORAGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                  "fp8": jnp.float8_e4m3fn}
# the nearest storage one step below each stated width: the control
NEXT_LOWER = {"f32": "bf16", "bf16": "fp8"}


# ---------------------------------------------------------------------------
# the channel and the event timeline (Table I, Eqs. 3-9)
# ---------------------------------------------------------------------------
@dataclass
class Channel:
    K: int
    v: float
    H: float
    d_y: float
    C_y: float
    model_bits: float
    B: float
    p_m: float
    alpha: float
    sigma2: float
    beta: float
    zeta: float
    gamma: float
    fading_rho: float
    coverage: float
    platoon: int = 0

    def _leader(self, i1):
        """Vehicles in platoons of ``platoon`` share their leader's data
        volume and CPU, so their training delays are equal."""
        if self.platoon > 1:
            return ((i1 - 1) // self.platoon) * self.platoon + 1
        return i1

    def data_count(self, i1):
        """D_i of the 1-based vehicle index (Section V-A)."""
        return 2250 + 3750 * self._leader(i1)

    def train_delay(self, i1):
        """Eq. 8 with delta_i = 1.5 (i + 5) 1e8 cycles/s."""
        return self.data_count(i1) * self.C_y / (
            1.5 * (self._leader(i1) + 5) * 1e8)

    def upload_delay(self, gain, dist):
        """Eqs. 5-6."""
        rate = self.B * math.log2(1.0 + self.p_m * gain * dist ** -self.alpha
                                  / self.sigma2)
        return self.model_bits / max(rate, 1e-12)


class Road:
    """Positions (Eq. 3) and the distance to the serving RSU (Eq. 4).  RSU
    j sits at the centre of segment j of width 2*coverage; a vehicle is
    served by the segment it is in (hard handover at the edges) and
    re-enters at the west end.  The fleet starts spread evenly over the
    road (``uniform``) or packed into the westmost segment (``rush``)."""

    def __init__(self, ch: Channel, n_rsus: int, entry: str = "uniform"):
        self.ch = ch
        self.n = n_rsus
        self.cell = 2.0 * ch.coverage
        self.span = self.cell * n_rsus
        width = {"uniform": self.span, "rush": self.cell}[entry]
        self.x0 = -self.span / 2 + width * (np.arange(ch.K) / ch.K)

    def x(self, i, t):
        return ((self.x0[i] + self.ch.v * t + self.span / 2) % self.span
                - self.span / 2)

    def rsu(self, i, t):
        j = int((self.x(i, t) + self.span / 2) // self.cell)
        return min(max(j, 0), self.n - 1)

    def distance(self, i, t):
        centre = -self.span / 2 + (self.rsu(i, t) + 0.5) * self.cell
        return math.sqrt((self.x(i, t) - centre) ** 2 + self.ch.d_y ** 2
                         + self.ch.H ** 2)


class RayleighGains:
    """|g|^2 of a per-vehicle complex Gaussian with AR(1) coherence rho,
    sampled once per one-second slot: slot s is s + 1 steps from the
    initial draw (real then imaginary parts, then per slot a (2, K) block
    of innovations)."""

    def __init__(self, ch: Channel, seed):
        self.rng = np.random.default_rng(seed)
        self.rho = ch.fading_rho
        self.K = ch.K
        self.g = (self.rng.normal(size=ch.K)
                  + 1j * self.rng.normal(size=ch.K)) / np.sqrt(2)
        self.slots = []

    def at(self, t):
        s = int(t)
        while len(self.slots) <= s:
            n = s + 1 - len(self.slots)
            z = self.rng.normal(size=(n, 2, self.K))
            z = (z[:, 0] + 1j * z[:, 1]) / np.sqrt(2)
            for row in z:
                self.g = self.rho * self.g + np.sqrt(1 - self.rho ** 2) * row
                self.slots.append(np.abs(self.g) ** 2)
        return self.slots[s]


def timeline(ch: Channel, n_rsus: int, seed, rounds, entry="uniform"):
    """The first ``rounds`` arrivals: per round the vehicle, its serving
    RSU at arrival, its arrival time, C_u, C_l and the round after which
    it downloaded (-1: the initial model)."""
    road = Road(ch, n_rsus, entry)
    gains = RayleighGains(ch, seed)
    heap, seq = [], 0

    def schedule(i, t_dl):
        nonlocal seq
        c_l = ch.train_delay(i + 1)
        t_up = t_dl + c_l
        c_u = ch.upload_delay(gains.at(t_up)[i], road.distance(i, t_up))
        heapq.heappush(heap, (t_up + c_u, seq, i, c_u, c_l))
        seq += 1

    for i in range(ch.K):
        schedule(i, 0.0)
    out = {k: [] for k in ("veh", "rsu", "time", "c_u", "c_l", "dl_round")}
    last = {}
    for r in range(rounds):
        t, _, i, c_u, c_l = heapq.heappop(heap)
        out["veh"].append(i)
        out["rsu"].append(road.rsu(i, t))
        out["time"].append(t)
        out["c_u"].append(c_u)
        out["c_l"].append(c_l)
        out["dl_round"].append(last.get(i, -1))
        last[i] = r
        schedule(i, t)
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# aggregation and storage
# ---------------------------------------------------------------------------
@jax.jit
def _mix(g, l, c, d):
    return jax.tree_util.tree_map(
        lambda a, b: c * a + d * b.astype(jnp.float32), g, l)


@jax.jit
def _mean(*gs):
    return jax.tree_util.tree_map(lambda *xs: sum(xs) / len(xs), *gs)


def _store(p, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), p)


def _widen(p):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


# ---------------------------------------------------------------------------
# one world, many studies
# ---------------------------------------------------------------------------
class World:
    """Everything a seed fixes: data, shards, timeline, initial weights and
    the minibatch of every arrival.  ``study`` then runs one study at a
    learning rate and a storage width."""

    def __init__(self, cfg: dict, seed):
        sc = cfg["scenario"]
        covered = {"scheme": "mafl", "dirichlet_alpha": None,
                   "reconcile_mode": "fedavg", "selection": None,
                   "faults": None}
        for key, value in covered.items():
            if sc.get(key) != value:
                raise ValueError(f"the reference covers {key}={value!r}, "
                                 f"not {sc.get(key)!r}")
        self.cfg = cfg
        self.sc = sc
        self.ch = Channel(K=sc["K"], **cfg["channel"])
        self.model = models.of(cfg)
        tr_x, tr_y, self.te_x, self.te_y = self.model.data(cfg)
        rng = np.random.default_rng(seed)
        shards = []
        for i1 in range(1, sc["K"] + 1):
            d = max(int(self.ch.data_count(i1) * sc["scale"]), 8)
            if sc["max_per_vehicle"] is not None:
                d = min(d, sc["max_per_vehicle"])
            shards.append(rng.choice(len(tr_y), size=min(d, len(tr_y)),
                                     replace=False))
        self.trace = timeline(self.ch, sc["n_rsus"], seed, sc["rounds"],
                              sc["corridor_entry"])
        b = min(128, min(len(s) for s in shards))
        vrng = {}
        xs, ys = [], []
        for i in self.trace["veh"]:
            r = vrng.setdefault(i, np.random.default_rng(seed + i + 1))
            pick = np.stack([r.choice(len(shards[i]), b, replace=False)
                             for _ in range(sc["l_iters"])])
            idx = shards[i][pick]
            xs.append(tr_x[idx])
            ys.append(tr_y[idx])
        self.xs = jnp.asarray(np.stack(xs))
        self.ys = jnp.asarray(np.stack(ys))
        self.w0 = self.model.init(seed, cfg)
        c = self.ch
        a = np.clip((1.0 - c.beta) * c.gamma ** (self.trace["c_u"] - 1.0)
                    * c.zeta ** (self.trace["c_l"] - 1.0), 0.0, 1.0)
        self.coeffs = [(jnp.float32(1.0 - x), jnp.float32(x)) for x in a]

    def start(self, storage="f32"):
        """The model every vehicle first downloads: the initial weights
        at the storage width, widened to f32, on the host."""
        return jax.device_get(_widen(_store(self.w0,
                                            STORAGE_DTYPES[storage])))

    def answer(self, lr, eval_every, storage) -> dict:
        """A study in the form ``compare.numbers`` reads."""
        final, evals = self.study(lr, eval_every, storage)
        return {"trace": list(zip(self.trace["veh"].tolist(),
                                  self.trace["rsu"].tolist())),
                "final": final, "losses": [(r, lo) for r, _, lo in evals]}

    def last_reads(self, eval_every) -> dict:
        """Of each stored model that a study reads, the last round that
        reads it.  Row ``k`` is the model stored after round ``k - 1``
        (row 0 the initial one); the arrivals that downloaded it read it,
        and with one RSU so does the evaluation at round ``k - 1``."""
        M = self.sc["rounds"]
        last = {int(d) + 1: r for r, d in enumerate(self.trace["dl_round"])}
        if self.sc["n_rsus"] == 1:
            for r in range(M):
                if (r + 1) % eval_every == 0 or r + 1 == M:
                    last[r + 1] = max(last.get(r + 1, r), r)
        return last

    def study(self, lr, eval_every, storage="f32"):
        """Final model (f32 pytree) and ``[(round, accuracy, loss)]``.

        Only the stored models that a later round reads are kept, each
        until its last reader: a model of half a billion parameters
        stores a row of a gigabyte a round."""
        dt = STORAGE_DTYPES[storage]
        R = self.sc["n_rsus"]
        M = self.sc["rounds"]
        every = self.sc["reconcile_every"] if R > 1 else 0
        last = self.last_reads(eval_every)
        G = [self.w0] * R
        ring = {0: _store(self.w0, dt)} if 0 in last else {}

        def read(k, r):
            return ring.pop(k) if last[k] == r else ring[k]

        evals = []
        lr = jnp.float32(lr)
        # the downloaded, uploaded and evaluated models are temporaries,
        # freed once used rather than held into the next round
        for r in range(M):
            j = int(self.trace["rsu"][r])
            G[j] = _mix(G[j], _store(self.model.local_update(
                _widen(read(int(self.trace["dl_round"][r]) + 1, r)),
                self.xs[r], self.ys[r], lr), dt), *self.coeffs[r])
            if every and (r + 1) % every == 0:
                G = [_mean(*G)] * R
            if r + 1 in last:
                ring[r + 1] = _store(G[j], dt)
            if (r + 1) % eval_every == 0 or r + 1 == M:
                acc, loss = self.model.evaluate(
                    _widen(read(r + 1, r)) if R == 1 else _mean(*G),
                    self.te_x, self.te_y)
                evals.append((r + 1, float(acc), float(loss)))
        final = G[0] if R == 1 else _mean(*G)
        return jax.device_get(final), evals
