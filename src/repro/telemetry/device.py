"""Device-side metrics accumulators (DESIGN.md §14).

Fixed-shape counter state carried through the engines' event-loop scans —
no host round-trips, no data-dependent shapes, and every helper is gated
behind the engines' static ``metrics is not None`` check so the off path
compiles the exact legacy program (rule TEL001).

State layout (one nested tuple appended to the scan carry):

- fleet (jit engine): ``(stale_hist i32[B], prev_t f32)``
- corridor: ``(stale_hist i32[R, B], prev_t f32, handover_count i32[R])``

Per-pop scalar channels (occupancy, argmin-pop wait, handover flag) ride
as extra ``ys`` columns of the same scan — stacked by ``lax.scan`` into
per-round arrays with zero additional carries.
"""
from __future__ import annotations

import jax.numpy as jnp


def fleet_state(spec):
    """Initial metrics carry for the single-RSU engines.  When the spec
    arms ``fault_counters`` (a fault model is active, DESIGN.md §16) the
    carry gains an ``i32[4]`` accumulator — (dropped, blackout, partial,
    discarded) — fed per pop from the fault plan's static counts table
    and conformance-checked against the f64 fault replay."""
    st = (jnp.zeros(spec.n_bins, jnp.int32), jnp.float32(0.0))
    if spec.fault_counters:
        st = st + (jnp.zeros(4, jnp.int32),)
    return st


def corridor_state(spec):
    """Initial metrics carry for the corridor engine."""
    st = (jnp.zeros((spec.n_rsus, spec.n_bins), jnp.int32),
          jnp.float32(0.0),
          jnp.zeros(spec.n_rsus, jnp.int32))
    if spec.fault_counters:
        st = st + (jnp.zeros(4, jnp.int32),)
    return st


def stale_bin(edges, stale):
    """Bucket a (traced f32) staleness value against the static edges —
    ``searchsorted`` side='left', the same rule as the f64 replay's
    ``np.searchsorted`` (the planner placed every edge a safe margin away
    from every sample, so both sides agree exactly)."""
    return jnp.searchsorted(edges, stale)


def fleet_pop(mst, edges, *, t, dl_t, fault_row=None):
    """Fold one pop into the fleet metrics carry; returns the new carry
    and the pop's ``(gap,)`` wait column.  ``fault_row`` is the pop's
    ``i32[4]`` fault-counter increment (required iff the carry holds the
    fault accumulator)."""
    hist, prev_t, *rest = mst
    hist = hist.at[stale_bin(edges, t - dl_t)].add(1)
    if rest:
        rest = [rest[0] + fault_row]
    return (hist, t, *rest), t - prev_t


def corridor_pop(mst, edges, *, t, dl_t, j, handover, fault_row=None):
    """Fold one pop into the corridor metrics carry (per-RSU histogram
    row ``j`` — the RSU the upload landed on; handover counted at the
    source row).  Returns the new carry and the pop's wait."""
    hist, prev_t, ho_cnt, *rest = mst
    hist = hist.at[j, stale_bin(edges, t - dl_t)].add(1)
    ho_cnt = ho_cnt.at[j].add(jnp.asarray(handover, jnp.int32))
    if rest:
        rest = [rest[0] + fault_row]
    return (hist, t, ho_cnt, *rest), t - prev_t


class RingStats:
    """Trace-level bf16 snapshot-ring guard counters (DESIGN.md §12/§14).

    Wraps the device program's ``store`` closure: every checkpoint row
    stored to the ring is scanned for non-finite values (bf16 overflow
    saturates to inf) and folded into running counters.  All ``store``
    call sites execute at trace level (between scan segments), so plain
    Python attribute mutation is safe — the accumulation is ordinary
    traced arithmetic, not side effects inside a scan body."""

    def __init__(self):
        self.nonfinite = jnp.int32(0)
        self.max_abs = jnp.float32(0.0)

    def wrap(self, store):
        def wrapped(x):
            y = store(x)
            f = y.astype(jnp.float32)
            finite = jnp.isfinite(f)
            self.nonfinite = (self.nonfinite
                              + jnp.sum(~finite).astype(jnp.int32))
            self.max_abs = jnp.maximum(
                self.max_abs,
                jnp.max(jnp.where(finite, jnp.abs(f), 0.0)))
            return y
        return wrapped

    def out(self) -> dict:
        return {"ring_nonfinite": self.nonfinite,
                "ring_max_abs": self.max_abs}
