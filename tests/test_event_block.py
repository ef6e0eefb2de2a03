"""An ``EventQueue`` holding a sorted column block beside its heap.

The planners enter a fleet's initial uploads as one block
(``_Timeline.schedule_initial``, DESIGN.md §9).  The queue must then pop,
peek, count and export exactly what an all-heap queue given the same
events one push at a time does, ties in time included; and the gains
the block reads must be those per-event reads would take.
"""
import dataclasses

import numpy as np
import pytest

from repro.channel import ChannelParams, RayleighAR1, SlotGainCache
from repro.core.events import EventQueue


def _columns(n, rng, t0=0.0):
    """A random block with ties in time (times on a coarse grid)."""
    return dict(time=t0 + rng.integers(0, max(n // 3, 1), n) * 0.25,
                vehicle=rng.permutation(10 * n)[:n],
                download_time=rng.uniform(0, 1, n),
                train_delay=rng.uniform(0, 1, n),
                upload_delay=rng.uniform(0, 1, n),
                cycle=rng.integers(0, 4, n))


class _HeapQueue:
    """The all-heap reference: each block row pushed on its own."""

    def __init__(self):
        self.q = EventQueue()

    def push_block(self, time, vehicle, **cols):
        for j in range(len(time)):
            self.q.push(time[j], int(vehicle[j]),
                        **{k: v[j] for k, v in cols.items()})


def _key(ev):
    return (float(ev.time), ev.seq, ev.vehicle, ev.cycle,
            float(ev.download_time), float(ev.train_delay),
            float(ev.upload_delay))


def _assert_struct_equal(a: EventQueue, b: EventQueue):
    sa, sb = a.as_struct_arrays(), b.as_struct_arrays()
    assert sa.keys() == sb.keys()
    for k in sa:
        assert sa[k].dtype == sb[k].dtype, k
        assert np.array_equal(sa[k], sb[k]), k


@pytest.mark.parametrize("seed", range(6))
def test_queue_with_block_matches_all_heap_queue(seed):
    rng = np.random.default_rng(seed)
    q, ref = EventQueue(), _HeapQueue()
    # a few single pushes may precede the block; then the block; then pops
    # interleaved with pushes whose times tie with the block's
    for _ in range(int(rng.integers(0, 4))):
        t, v = float(rng.integers(0, 6) * 0.25), int(rng.integers(0, 50))
        q.push(t, v, cycle=0)
        ref.q.push(t, v, cycle=0)
    cols = _columns(int(rng.integers(1, 60)), rng)
    q.push_block(**cols)
    ref.push_block(**cols)
    _assert_struct_equal(q, ref.q)
    while len(ref.q):
        assert len(q) == len(ref.q)
        assert q.earliest_time() == ref.q.earliest_time()
        assert _key(q.peek()) == _key(ref.q.peek())
        action = rng.integers(0, 4)
        if action == 0:
            t = float(q.earliest_time() + rng.integers(0, 4) * 0.25)
            v = int(rng.integers(0, 50))
            a = q.push(t, v, cycle=1)
            b = ref.q.push(t, v, cycle=1)
            assert a.seq == b.seq
        elif action == 1:
            _assert_struct_equal(q, ref.q)
        else:
            assert _key(q.pop()) == _key(ref.q.pop())
    assert len(q) == 0 and q.earliest_time() == float("inf")
    _assert_struct_equal(q, ref.q)


@pytest.mark.parametrize("seed", range(3))
def test_queue_pending_spills_block_into_heap(seed):
    rng = np.random.default_rng(100 + seed)
    q, ref = EventQueue(), _HeapQueue()
    cols = _columns(int(rng.integers(2, 40)), rng)
    q.push_block(**cols)
    ref.push_block(**cols)
    for _ in range(int(rng.integers(0, len(cols["time"])))):
        assert _key(q.pop()) == _key(ref.q.pop())
    pend = list(q.pending())
    assert sorted(map(_key, pend)) == sorted(map(_key, ref.q.pending()))
    # the events pending() handed out are the ones the pops return
    ids = {id(ev) for ev in pend}
    while len(ref.q):
        ev = q.pop()
        assert id(ev) in ids
        assert _key(ev) == _key(ref.q.pop())


def test_queue_holds_one_block_at_a_time():
    rng = np.random.default_rng(0)
    q = EventQueue()
    q.push_block(**_columns(5, rng))
    with pytest.raises(RuntimeError):
        q.push_block(**_columns(5, rng))
    for _ in range(5):
        q.pop()
    q.push_block(**_columns(5, rng))
    assert len(q) == 5 and q.pop().seq >= 5


def test_gain_gather_matches_per_event_reads():
    """One advance to the latest slot reads the gains that per-event
    ``at`` calls, each advancing as far as it needs, read."""
    p = dataclasses.replace(ChannelParams(), K=64)
    rng = np.random.default_rng(3)
    t = rng.uniform(0, 7, p.K)
    v = rng.permutation(p.K)
    bulk = SlotGainCache(RayleighAR1(p, seed=5))
    one = SlotGainCache(RayleighAR1(p, seed=5))
    got = bulk.gather(t, v)
    want = np.array([one.at(t[j])[v[j]] for j in range(p.K)])
    assert np.array_equal(got, want)
    assert bulk.last_slot == one.last_slot
    assert np.array_equal(bulk.at(9.5), one.at(9.5))
