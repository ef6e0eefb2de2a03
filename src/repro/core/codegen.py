"""Codegen-environment fingerprint for the golden-digest gate (DESIGN.md §12).

The golden fixtures pin *bitwise* sha256 digests of trained parameters.
Those digests depend on more than the (jax, numpy) versions the fixtures
record: XLA:CPU's f32 codegen is hardware-dependent — FMA contraction and
vectorization vary with the host CPU's feature set, so the same program on
the same library versions can legitimately produce different low bits on a
different machine (the traces and the cross-engine tolerances still
hold there; only the absolute bits move).  Version equality alone is
therefore the wrong gate: it passes on a host whose codegen differs from
the fixture machine and the digest assertions fire spuriously.

This module computes a compact fingerprint of the codegen environment by
actually *running* a deterministic probe program through the same kernels
the simulations exercise — local CNN training (solo and vmapped, the two
emission contexts the engines use) plus the staleness-weighted mix / pow /
log2 chain of Eqs. 5-11 — and digesting the f32 results.  Two hosts that
agree on the probe digest agree on the codegen of everything the fixtures
pin; the fixtures record the fingerprint at refresh time and the tests
compare digests only when it matches (``tests/golden/refresh.py``,
``tests/test_golden_traces.py``).
"""
from __future__ import annotations

from functools import lru_cache


@lru_cache(maxsize=1)
def codegen_fingerprint() -> dict:
    """``{"backend": ..., "probe": <sha256>}`` for this process's default
    backend.  Deterministic by construction: fixed PRNG keys, synthetic
    data, no dependence on datasets or wall-clock."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpointing.checkpoint import tree_digest
    from repro.core import client as client_mod
    from repro.models.cnn import init_cnn

    params = init_cnn(jax.random.PRNGKey(0))
    l_iters, batch = 2, 8
    imgs = jnp.asarray(
        np.linspace(-1.0, 1.0, l_iters * batch * 28 * 28,
                    dtype=np.float32).reshape(l_iters, batch, 28, 28, 1))
    labs = jnp.asarray((np.arange(l_iters * batch) % 10).astype(np.int32)
                       .reshape(l_iters, batch))
    lr = jnp.float32(0.03)

    # the two training emission contexts the engines use: a solo local
    # scan and a payload-stacked vmap (grouped-convolution lowering)
    solo, _ = jax.jit(client_mod._local_scan)(params, imgs, labs, lr)
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x * 0.5]),
                                     params)
    wave, _ = jax.jit(jax.vmap(client_mod._local_scan,
                               in_axes=(0, 0, 0, None)))(
        stacked, jnp.stack([imgs, imgs]), jnp.stack([labs, labs]), lr)

    # the Eq. 5-11 arithmetic whose FMA contraction is context-dependent:
    # pow-weighted mix + log2 Shannon rate on a deterministic vector
    @jax.jit
    def chain(a, b):
        weight = jnp.float32(0.9) ** (a - 1.0) * jnp.float32(0.9) ** (b - 1.0)
        alpha = jnp.clip((1.0 - jnp.float32(0.5)) * weight, 0.0, 1.0)
        mix = (1.0 - alpha) * a + alpha * b
        rate = jnp.float32(1e5) * jnp.log2(1.0 + a * b ** jnp.float32(-2.0))
        return mix, rate

    x = jnp.asarray(np.linspace(0.1, 3.0, 1024, dtype=np.float32))
    mix, rate = chain(x, x[::-1])

    probe = {"solo": solo, "wave": wave, "mix": mix, "rate": rate}
    return {"backend": jax.default_backend(),
            "probe": tree_digest(probe)}


def codegen_matches(recorded) -> bool:
    """True iff ``recorded`` (a fixture's ``codegen`` field) matches this
    host.  Fixtures written before the fingerprint existed (no field)
    never match — their digests were pinned blind to the codegen
    environment."""
    if not recorded:
        return False
    return recorded == codegen_fingerprint()
