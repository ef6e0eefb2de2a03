"""The trace reduction on synthetic events with hand-computed answers."""
import re

import pytest

import trace_reduce as tr


def meta(pid, tid=None, name=""):
    if tid is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": name}}


def op(ts, dur, name, tf_op="", pid=3, tid=3):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": tf_op} if tf_op else {}}


def span(ts, dur, label):
    return {"ph": "X", "pid": 700, "tid": 1, "ts": ts, "dur": dur,
            "name": tr.SPAN_PREFIX + label}


def events():
    """One study, 0..1000 us.  Device busy: a module 100-400 holding a
    scan loop (100-300) whose body ops carry the event_scan scope, a
    wave_train op 300-350 and a ring_agg kernel 350-400; then an eval
    module 700-750.  Host spans: stage 0-100 with plan 10-70 inside it,
    eval 600-800."""
    return [
        meta(3, name="/device:TPU:0"), meta(3, 2, "XLA Modules"),
        meta(3, 3, "XLA Ops"), meta(700, name="/host:CPU"),
        op(100, 300, "jit_program_flat(1)", tid=2),
        op(700, 50, "jit__eval_step(2)", tid=2),
        op(100, 200, "while.7"),
        op(110, 50, "fusion.1", "jit(program_flat)/event_scan_0_9/argmin"),
        op(170, 60, "fusion.2", "jit(program_flat)/event_scan_0_9/add"),
        op(300, 50, "fusion.3", "jit(program_flat)/wave_train_0/conv"),
        op(350, 50, "ring_agg_2d.4",
           "jit(program_flat)/ring_chain_0_9/jit(ring_agg_2d)/pallas_call"),
        op(700, 50, "convolution.5", "jit(_eval_step)/conv"),
        span(0, 1000, "study"), span(0, 100, "stage"), span(10, 60, "plan"),
        span(600, 200, "eval"),
    ]


def test_union_and_gaps():
    busy = tr.union([[5, 10], [0, 3], [2, 4], [10, 12]])
    assert busy == [[0, 4], [5, 12]]
    assert tr.gaps(busy, 0, 20) == [[4, 5], [12, 20]]
    assert tr.gaps(busy, -2, 11) == [[-2, 0], [4, 5]]


def test_top_level_collects_nested_scopes():
    tops = tr.top_level([e for e in events()
                         if e.get("tid") == 3 and e["ph"] == "X"])
    assert [e["name"] for e, _ in tops] == [
        "while.7", "fusion.3", "ring_agg_2d.4", "convolution.5"]
    pat = re.compile(r"\b(event_scan|wave_train|ring_chain)_[0-9_]+")
    assert [tr.scope_of(t, pat) for t in tops] == [
        "event_scan", "wave_train", "ring_chain", None]


def test_label_time_takes_the_innermost_span():
    spans = [e for e in events() if e["ph"] == "X"
             and e["name"].startswith(tr.SPAN_PREFIX)]
    got = tr.label_time([[0, 100], [400, 700], [750, 1000]], spans)
    # 400-600 and 800-1000 under the study alone, 600-700 and 750-800
    # under eval
    assert got == pytest.approx({"plan": 60e-6, "stage": 40e-6,
                                 "study": 400e-6, "eval": 150e-6})


def test_reduce():
    r = tr.reduce(events())
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(350e-6)
    assert r["studies"] == 1
    assert r["scope_s"] == pytest.approx({"event_scan": 200e-6,
                                          "wave_train": 50e-6,
                                          "ring_chain": 50e-6})
    assert r["kernel_s"] == pytest.approx({"ring_agg_2d": 50e-6})
    assert r["kernel_events"] == {"ring_agg_2d": 1}
    assert dict(r["device_ops"]) == pytest.approx({
        "event_scan/while": 200e-6, "wave_train/fusion": 50e-6,
        "ring_chain/ring_agg_2d": 50e-6,
        "jit__eval_step/convolution": 50e-6})
    # idle: 0-100, 400-700 and 750-1000, as in the test above
    assert r["idle"] == pytest.approx({"plan": 60e-6, "stage": 40e-6,
                                       "study": 400e-6, "eval": 150e-6})


def test_reduce_without_device_ops_reads_nothing():
    assert tr.reduce([e for e in events() if e.get("pid") != 3]) == {}


def _second_study(with_kernel):
    """The study of ``events`` again at 2000-3000 us, its kernel event
    kept or lost."""
    out = []
    for e in events():
        if e["ph"] != "X" or e["name"].startswith(tr.SPAN_PREFIX + "s"):
            continue
        if not with_kernel and e["name"].startswith("ring_agg_2d"):
            continue
        out.append(dict(e, ts=e["ts"] + 2000))
    return events() + out + [span(2000, 1000, "study")]


def test_reduce_counts_each_studys_kernel_events():
    r = tr.reduce(_second_study(with_kernel=True))
    assert r["studies"] == 2
    assert r["kernel_events"] == {"ring_agg_2d": 1}
    assert r["kernel_s"] == pytest.approx({"ring_agg_2d": 100e-6})


def test_reduce_refuses_a_study_that_lost_kernel_events():
    with pytest.raises(ValueError, match="lost"):
        tr.reduce(_second_study(with_kernel=False))
