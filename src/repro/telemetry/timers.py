"""Host-side phase timers, compile counters and memory probes (DESIGN.md §14).

Pure host instrumentation around the compiled region: phase timers never
touch traced code, so they are always on — enabling them cannot perturb
the program (the off-is-no-op invariant only concerns the *device*
channels).  Each phase is also a ``jax.profiler.TraceAnnotation`` named
``repro.<phase>``, so a profiler trace shows it on the device ops' clock.
The phases of a study, in order:

- ``world``   — the world build (``run_scenario``'s ``build_world``), and
                its teardown at the study's end
- ``plan``    — the f64 dry-run planner (``plan_fleet`` / ``plan_corridor``)
- ``stage``   — world staging: minibatch stacks, gain tables, flat layouts
- ``run``     — the compiled region, ending in ``block_until_ready``
                (Python tracing and XLA compiling included on a cold call)
- ``guard``   — the device-to-host fetch of the event trace and the
                divergence guards
- ``eval``    — host-side accuracy evaluation of returned snapshots
- ``report``  — the run report: summaries, channels, memory probes

The contract: phases do not overlap — a phase opened inside another one
of the same timers pauses it (the host engines evaluate inside their run
loop), so every instant counts once, in the innermost phase — and on the
``jit`` and ``corridor`` engines, through ``run_scenario``, they cover the
study: their sum is its wall time up to the few statements between them.

``compile_counts()`` breaks compilation down without being a phase: a
process-wide ``jax.monitoring`` listener charges each JAX compile event to
every ``PhaseTimers`` with a phase open in the compiling thread.

``memory_stats()`` reports the process peak RSS and, when the backend
exposes it (TPU/GPU allocators), per-device ``live_bytes`` peaks.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager

import jax

# the phases of a study, in the order they run
PHASES = ("world", "plan", "stage", "run", "guard", "eval", "report")
# JAX's compile events (jax._src.dispatch), by the key they feed
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}

_open = threading.local()       # .timers: the timers with a phase open
_listening = False


def _on_duration(event: str, secs: float, **_) -> None:
    key = COMPILE_EVENTS.get(event)
    if key is None:
        return
    for t in {id(t): t for t in getattr(_open, "timers", ())}.values():
        t._compile[key] += secs
        if key == "backend_s":
            t._compile["executables"] += 1


def _listen() -> None:
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


class PhaseTimers:
    """Accumulating wall-clock phase timers.

    >>> timers = PhaseTimers()
    >>> with timers.phase("plan"):
    ...     do_planning()
    >>> timers.snapshot()
    {'plan': 0.0123}

    Repeated entries accumulate; a phase entered inside another pauses
    the outer one.  ``snapshot`` returns plain floats (seconds) suitable
    for JSON."""

    def __init__(self):
        _listen()
        self._acc: dict[str, float] = {}
        self._stack: list[list] = []        # [name, start of its slice]
        self._compile = {"executables": 0, "trace_s": 0.0, "lower_s": 0.0,
                         "backend_s": 0.0}

    def _charge(self, now: float) -> None:
        name, t0 = self._stack[-1]
        self._acc[name] = self._acc.get(name, 0.0) + (now - t0)

    @contextmanager
    def phase(self, name: str):
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            now = time.perf_counter()
            if self._stack:
                self._charge(now)
            self._stack.append([name, now])
            if not hasattr(_open, "timers"):
                _open.timers = []
            _open.timers.append(self)
            try:
                yield
            finally:
                _open.timers.remove(self)
                now = time.perf_counter()
                self._charge(now)
                self._stack.pop()
                if self._stack:
                    self._stack[-1][1] = now

    def snapshot(self) -> dict:
        return dict(self._acc)

    def compile_counts(self) -> dict:
        """Executables built or loaded from the persistent cache, and the
        seconds spent tracing to jaxprs, lowering to MLIR and in the
        backend compiler, while a phase of these timers was open."""
        return dict(self._compile)

    def fold_into(self, report) -> None:
        """Add these phases (first) and compile counts to a ``RunReport``
        whose engine kept timers of its own."""
        names = dict.fromkeys([*self._acc, *report.phases])
        report.phases = {k: self._acc.get(k, 0.0)
                         + report.phases.get(k, 0.0) for k in names}
        report.compile = {k: v + report.compile.get(k, 0)
                          for k, v in self._compile.items()}


def memory_stats() -> dict:
    """Process peak RSS plus backend allocator stats when available.

    ``ru_maxrss`` is KiB on Linux; ``device.memory_stats()`` is only
    populated on backends with an instrumented allocator (None on the CPU
    backend — the device keys are omitted there)."""
    import resource

    out = {"peak_rss_bytes": int(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024}
    stats = jax.local_devices()[0].memory_stats() or {}
    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if k in stats:
            out[f"device_{k}"] = int(stats[k])
    return out
