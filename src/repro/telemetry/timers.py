"""Host-side phase timers and memory probes (DESIGN.md §14).

Pure host instrumentation around the compiled region: phase timers never
touch traced code, so they are always on — enabling them cannot perturb
the program (the off-is-no-op invariant only concerns the *device*
channels).  The canonical phases the engines record:

- ``plan``    — the f64 dry-run planner (``plan_fleet`` / ``plan_corridor``)
- ``stage``   — world staging: packing slot arrays, flat layouts, rings
- ``build``   — Python tracing of the program body (cache misses only)
- ``run``     — the compiled region end-to-end (includes XLA compile on
                the first call; the bench layer separates compile time by
                differencing a cold and a warm invocation)
- ``eval``    — host-side accuracy evaluation of returned snapshots

``memory_stats()`` reports the process peak RSS and, when the backend
exposes it (TPU/GPU allocators), per-device ``live_bytes`` peaks.
"""
from __future__ import annotations

import time
from contextlib import contextmanager


class PhaseTimers:
    """Accumulating wall-clock phase timers.

    >>> timers = PhaseTimers()
    >>> with timers.phase("plan"):
    ...     do_planning()
    >>> timers.snapshot()
    {'plan': 0.0123}

    Phases nest and repeat; repeated entries accumulate.  ``snapshot``
    returns plain floats (seconds) suitable for JSON."""

    def __init__(self):
        self._acc: dict[str, float] = {}

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._acc[name] = self._acc.get(name, 0.0) + dt

    def add(self, name: str, seconds: float) -> None:
        """Fold an externally measured duration into a phase."""
        self._acc[name] = self._acc.get(name, 0.0) + float(seconds)

    def snapshot(self) -> dict:
        return dict(self._acc)


def memory_stats() -> dict:
    """Process peak RSS plus backend allocator stats when available.

    ``ru_maxrss`` is KiB on Linux; ``device.memory_stats()`` is only
    populated on backends with an instrumented allocator (None on the CPU
    backend — the device keys are omitted there)."""
    import resource

    import jax
    out = {"peak_rss_bytes": int(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024}
    stats = jax.local_devices()[0].memory_stats() or {}
    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if k in stats:
            out[f"device_{k}"] = int(stats[k])
    return out
