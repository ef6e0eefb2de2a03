"""Vehicle-side local training (Algorithm 1, "Vehicle Update").

A client owns a private data shard and runs ``l`` SGD iterations (Eq. 2) from
the downloaded global model.  The trainable model is pluggable: the paper's
CNN for the faithful reproduction, or any assigned transformer arch via
``lm_local_step`` (the aggregation layer never inspects structure).

The ``l`` iterations are a single ``jax.lax.scan`` program: one dispatch per
local update instead of ``l``, with the loss materialized on the host only
once at the end (DESIGN.md §3).  ``local_update_many`` additionally vmaps the
same scan over a stack of vehicles so a whole wave of pending uploads trains
in one program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn import cnn_forward, cross_entropy_loss


# shard rows are int32 indices into the pool
ROW_DTYPE = np.int32
ROW_BYTES = np.dtype(ROW_DTYPE).itemsize


@dataclass(eq=False)
class ShardPool:
    """The one training pool that every vehicle's shard indexes into.

    ``shard_rows`` counts the rows handed out to shards, ``rows_gathered``
    the images copied out of the pool by minibatch draws, ``samplers`` the
    ``Vehicle`` samplers built over its shards: integer sums that
    ``world_counts`` reports."""
    images: np.ndarray      # [N, 28, 28, 1]
    labels: np.ndarray      # [N]
    shard_rows: int = 0
    rows_gathered: int = 0
    samplers: int = 0

    def world_counts(self, vehicles: int) -> dict:
        """What a world of ``vehicles`` shards over this pool holds on the
        host, and how much of it the minibatch draws have read."""
        return {"vehicles": vehicles, "shard_rows": self.shard_rows,
                "host_bytes": (self.images.nbytes + self.labels.nbytes
                               + self.shard_rows * ROW_BYTES),
                "rows_gathered": self.rows_gathered,
                "samplers": self.samplers}


@dataclass
class VehicleData:
    """Private shard of vehicle i (1-based index per the paper): the rows of
    the shared training pool it was dealt, not a copy of their images."""
    index: int
    pool: ShardPool
    rows: np.ndarray        # [D_i] ROW_DTYPE indices into the pool

    @property
    def size(self) -> int:
        return len(self.rows)

    def gather(self, sel: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Images and labels of shard positions ``sel`` (any shape), copied
        out of the pool in one gather each."""
        r = self.rows[sel]
        self.pool.rows_gathered += r.size
        return self.pool.images[r], self.pool.labels[r]


@jax.jit
def _cnn_sgd_iter(params, images, labels, lr):
    def loss_fn(p):
        return cross_entropy_loss(cnn_forward(p, images), labels)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    params = jax.tree_util.tree_map(lambda w, g: w - lr * g, params, grads)
    return params, loss


def _local_scan(params, images, labels, lr):
    """l SGD iterations (Eq. 2) as one scan.  images [l, b, 28, 28, 1].

    Fully unrolled: XLA:CPU runs conv/dot ops inside a rolled while-loop
    body ~20x slower than the same ops at top level (no parallel thunk
    path), so the rolled form turned a 0.75 s local update into 15 s.
    Unrolling keeps the single-dispatch property and restores per-op
    performance; compile time grows with l but is paid once per shape."""
    def body(p, batch):
        img, lab = batch

        def loss_fn(q):
            return cross_entropy_loss(cnn_forward(q, img), lab)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p = jax.tree_util.tree_map(lambda w, g: w - lr * g, p, grads)
        return p, loss

    params, losses = jax.lax.scan(body, params, (images, labels),
                                  unroll=True)
    return params, losses[-1]


_local_scan_jit = jax.jit(_local_scan)
# vehicle-batched path: vmap the identical scan over stacked (params, data)
_local_scan_vmap = jax.jit(jax.vmap(_local_scan, in_axes=(0, 0, 0, None)))


def _local_scan_partial(params, images, labels, lr, n_ep):
    """Partial-computation variant (faults, DESIGN.md §16): the same l-step
    unrolled scan, but only the first ``n_ep`` updates apply — deadline
    semantics, so the dispatch shape and the per-vehicle minibatch draws
    are identical to the full scan and only steps >= n_ep become no-ops.
    Kept separate from ``_local_scan`` so faults-off runs retain the legacy
    scan's object identity (program-cache keys, rule FLT001)."""
    def body(carry, batch):
        p, step, last = carry
        img, lab = batch

        def loss_fn(q):
            return cross_entropy_loss(cnn_forward(q, img), lab)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        live = step < n_ep
        p = jax.tree_util.tree_map(
            lambda w, g: jnp.where(live, w - lr * g, w), p, grads)
        last = jnp.where(live, loss, last)
        return (p, step + 1, last), loss

    init = (params, jnp.int32(0), jnp.float32(0.0))
    (params, _, last), _ = jax.lax.scan(body, init, (images, labels),
                                        unroll=True)
    return params, last


_local_scan_partial_jit = jax.jit(_local_scan_partial)
_local_scan_partial_vmap = jax.jit(
    jax.vmap(_local_scan_partial, in_axes=(0, 0, 0, None, 0)))


class Vehicle:
    """One FL client.  ``local_update`` = l iterations of Eq. (1)+(2)."""

    def __init__(self, data: VehicleData, lr: float = 0.01,
                 batch_size: int = 128, seed: int = 0):
        self.data = data
        self.lr = lr
        # The paper's Eq. (1) sums the loss over all D_i data each iteration;
        # we use minibatch SGD (batch_size<=D_i) for CPU tractability — a
        # documented deviation (DESIGN.md §6) that preserves Eq. (2).
        self.batch_size = min(batch_size, data.size)
        self.rng = np.random.default_rng(seed + data.index)
        data.pool.samplers += 1

    def sample_batches(self, l_iters: int):
        """Draw the l minibatches for one local update (host RNG).

        Drawn in the same per-iteration order as the legacy python loop, so
        a vehicle's RNG stream advances identically regardless of which
        engine (serial or vehicle-batched) consumes the batches."""
        sel = np.stack([self.rng.choice(self.data.size, self.batch_size,
                                        replace=False)
                        for _ in range(l_iters)])
        return self.data.gather(sel)

    def local_update(self, global_params, l_iters: int, n_ep=None):
        """``n_ep`` truncates the update to the first n_ep of the l_iters
        steps (partial computation, faults); the minibatches for all
        l_iters steps are drawn regardless so the RNG stream stays aligned
        with the fault-free run."""
        imgs, labs = self.sample_batches(l_iters)
        if n_ep is None:
            params, loss = _local_scan_jit(global_params, jnp.asarray(imgs),
                                           jnp.asarray(labs), self.lr)
        else:
            params, loss = _local_scan_partial_jit(
                global_params, jnp.asarray(imgs), jnp.asarray(labs),
                self.lr, jnp.int32(n_ep))
        return params, float(loss)


class Samplers:
    """The fleet's ``Vehicle`` samplers, each built on its vehicle's first
    access and kept for later ones.

    ``samplers[k]`` is ``Vehicle(vehicles_data[k], lr=lr,
    batch_size=batch_size, seed=seed)``.  A vehicle's stream is
    ``default_rng(seed + index)`` whenever it is built, and no stream
    depends on another, so a study whose M arrivals draw from at most M of
    K vehicles builds only those generators and draws the minibatches K
    samplers built up front would (DESIGN.md §3)."""

    def __init__(self, vehicles_data: Sequence[VehicleData], *, lr: float,
                 batch_size: int, seed: int):
        self._data = vehicles_data
        self._kw = dict(lr=lr, batch_size=batch_size, seed=seed)
        self._built: dict[int, Vehicle] = {}

    def __getitem__(self, k) -> Vehicle:
        v = self._built.get(k)
        if v is None:
            v = self._built[k] = Vehicle(self._data[k], **self._kw)
        return v


def local_update_many(payloads: Sequence, batches: Sequence, lr: float,
                      chunk: int = 16, n_eps: Sequence | None = None):
    """Train a wave of vehicles with a bounded number of compiled programs.

    ``payloads``: per-vehicle global-model snapshots (pytrees of identical
    structure); ``batches``: matching [l, b, ...] minibatch arrays, all the
    same shape (the engine gives the fleet one minibatch size, so a world
    compiles exactly one training shape).  Full ``chunk``-sized
    slices of the wave stack their pytrees and run under the vmapped scan —
    one dispatch per chunk, one compiled program per (chunk, batch shape)
    for the whole simulation; the remainder reuses the serial-engine scan
    program per event (on a compute-bound host, looping a short remainder
    is cheaper than padding it to ``chunk``).  Returns the list of updated
    pytrees and the final losses.

    ``n_eps`` (faults, partial computation): matching per-vehicle epoch
    counts; when given, every update runs the masked partial scan (a
    count equal to l_iters is bitwise the full update)."""
    outs, losses = [], []
    n = len(payloads)
    full = (n // chunk) * chunk if chunk > 1 else 0
    for s in range(0, full, chunk):
        pay = payloads[s:s + chunk]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pay)
        imgs = jnp.stack([jnp.asarray(b[0])
                          for b in batches[s:s + chunk]])
        labs = jnp.stack([jnp.asarray(b[1])
                          for b in batches[s:s + chunk]])
        if n_eps is None:
            out, ls = _local_scan_vmap(stacked, imgs, labs, lr)
        else:
            eps = jnp.asarray(n_eps[s:s + chunk], dtype=jnp.int32)
            out, ls = _local_scan_partial_vmap(stacked, imgs, labs, lr, eps)
        ls = np.asarray(ls)
        outs.extend(jax.tree_util.tree_map(lambda x, i=i: x[i], out)
                    for i in range(chunk))
        losses.extend(float(l) for l in ls)
    for i in range(full, n):
        if n_eps is None:
            params, loss = _local_scan_jit(payloads[i],
                                           jnp.asarray(batches[i][0]),
                                           jnp.asarray(batches[i][1]), lr)
        else:
            params, loss = _local_scan_partial_jit(
                payloads[i], jnp.asarray(batches[i][0]),
                jnp.asarray(batches[i][1]), lr, jnp.int32(n_eps[i]))
        outs.append(params)
        losses.append(float(loss))
    return outs, losses


def make_lm_local_step(cfg, forward_fn) -> Callable:
    """Local SGD step factory for transformer clients (examples/)."""

    @jax.jit
    def step(params, tokens, lr):
        def loss_fn(p):
            logits, aux = forward_fn(cfg, p, tokens[:, :-1])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
            return jnp.mean(nll) + aux
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda w, g: w - lr * g, params,
                                        grads)
        return params, loss

    return step
