"""Event-driven asynchronous scheduler.

TPU pods are bulk-synchronous, so wall-clock asynchrony is *simulated*: every
vehicle's (train -> upload) cycle produces an upload-completion event at

    t_done = t_download + C_l^i + C_u^i(t_upload_start)

and the RSU consumes events in time order — exactly the paper's arrival
semantics (Fig. 2), with each local-training burst itself a synchronous jit
program.  See DESIGN.md §2 (hardware adaptation).

The vehicle-batched engine (DESIGN.md §3) additionally stashes the result of
a wave-trained local update on the event itself (``local_params`` /
``local_loss``): an event's payload snapshot is frozen at schedule time, so
its local training is independent of every other pending event and can be
computed early without changing the time-ordered aggregation semantics.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

import numpy as np


@dataclass(order=True)
class UploadEvent:
    time: float
    seq: int
    vehicle: int = field(compare=False)          # 0-based
    download_time: float = field(compare=False, default=0.0)
    train_delay: float = field(compare=False, default=0.0)
    upload_delay: float = field(compare=False, default=0.0)
    payload: Any = field(compare=False, default=None)
    # which train/upload cycle of this vehicle the event belongs to
    cycle: int = field(compare=False, default=0)
    # wave-precomputed local update (vehicle-batched engine only)
    local_params: Any = field(compare=False, default=None, repr=False)
    local_loss: Optional[float] = field(compare=False, default=None)


class EventQueue:
    """Pending uploads in ``(time, seq)`` order: a heap of single events
    and, beside it, at most one sorted column block.

    The planners schedule a fleet's first uploads at t = 0 in one
    vectorised evaluation (``_Timeline.schedule_initial``); the block holds
    its columns sorted by ``(time, seq)`` with a read position, and ``pop``
    builds an :class:`UploadEvent` only for the block rows it hands out.
    Every query counts both sources, so the queue orders exactly as if
    each block row had been pushed on its own."""

    _COLUMNS = ("time", "seq", "vehicle", "download_time", "train_delay",
                "upload_delay", "cycle")

    def __init__(self):
        self._heap: list[UploadEvent] = []
        self._seq = 0
        self._block: Optional[dict] = None
        self._pos = 0

    def push(self, time: float, vehicle: int, **kw) -> UploadEvent:
        ev = UploadEvent(time=time, seq=self._seq, vehicle=vehicle, **kw)
        self._seq += 1
        heapq.heappush(self._heap, ev)
        return ev

    def push_block(self, time, vehicle, *, download_time, train_delay,
                   upload_delay, cycle) -> None:
        """Enqueue ``len(time)`` payload-free events at once.  Their
        sequence numbers continue the queue's in the order given, as that
        many :meth:`push` calls would assign them.  A queue holds one
        block at a time."""
        if self._block_left():
            raise RuntimeError("EventQueue already holds a column block")
        n = len(time)
        seq = np.arange(self._seq, self._seq + n, dtype=np.int64)
        self._seq += n
        order = np.lexsort((seq, time))
        cols = (time, seq, vehicle, download_time, train_delay,
                upload_delay, cycle)
        self._block = {k: np.asarray(c)[order]
                       for k, c in zip(self._COLUMNS, cols)}
        self._pos = 0

    def _block_left(self) -> int:
        if self._block is None:
            return 0
        return len(self._block["seq"]) - self._pos

    def _block_event(self, i: int) -> UploadEvent:
        # the field types a single push would carry: a numpy f64 time and
        # upload delay, Python scalars for the rest
        b = self._block
        return UploadEvent(time=b["time"][i], seq=int(b["seq"][i]),
                           vehicle=int(b["vehicle"][i]),
                           download_time=float(b["download_time"][i]),
                           train_delay=float(b["train_delay"][i]),
                           upload_delay=b["upload_delay"][i],
                           cycle=int(b["cycle"][i]))

    def _block_first(self) -> bool:
        """Whether the block's head precedes the heap's top."""
        if not self._block_left():
            return False
        if not self._heap:
            return True
        b, i, top = self._block, self._pos, self._heap[0]
        return (b["time"][i], b["seq"][i]) < (top.time, top.seq)

    def pop(self) -> UploadEvent:
        if self._block_first():
            self._pos += 1
            return self._block_event(self._pos - 1)
        return heapq.heappop(self._heap)

    def peek(self) -> UploadEvent:
        if self._block_first():
            return self._block_event(self._pos)
        return self._heap[0]

    def pending(self) -> Iterator[UploadEvent]:
        """All queued events, unordered (the heap as-is).  The block's
        remaining rows move into the heap first, so the events handed out
        are the ones later pops return."""
        if self._block_left():
            self._heap.extend(self._block_event(i) for i in
                              range(self._pos, len(self._block["seq"])))
            heapq.heapify(self._heap)
        self._block = None
        return iter(self._heap)

    def earliest_time(self) -> float:
        if self._block_first():
            return self._block["time"][self._pos]
        return self._heap[0].time if self._heap else float("inf")

    def as_struct_arrays(self) -> dict:
        """Pending events as structure-of-arrays, sorted by (time, seq).

        The columnar face of the queue: the device-resident engine
        (DESIGN.md §9) seeds its fixed-capacity slot arrays from this —
        payloads are deliberately excluded (the jit engine keeps snapshots
        in its own device-side ring)."""
        left = self._block_left()
        cols = {k: (self._block[k][self._pos:] if left else ())
                for k in self._COLUMNS}
        if self._heap:
            # merge: the heap's events beside the block's rows, re-sorted
            cols = {k: np.concatenate([[getattr(e, k) for e in self._heap],
                                       cols[k]])
                    for k in self._COLUMNS}
            order = np.lexsort((cols["seq"], cols["time"]))
            cols = {k: c[order] for k, c in cols.items()}
        dtypes = {"time": np.float64, "vehicle": np.int32,
                  "download_time": np.float64, "train_delay": np.float64,
                  "upload_delay": np.float64, "cycle": np.int32}
        return {k: np.array(cols[k], dt) for k, dt in dtypes.items()}

    def __len__(self):
        return len(self._heap) + self._block_left()
