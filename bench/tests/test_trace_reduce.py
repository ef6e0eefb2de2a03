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


# reduce's answer on the fixtures above at the parent of the change that
# added ``scope_any_depth_s``: the fields it had, digit for digit
BEFORE = [
    (events, {
        'window_s': 0.001, 'busy_s': 0.00035, 'studies': 1,
        'scope_s': {'event_scan': 0.0002, 'wave_train': 5e-05,
                    'ring_chain': 5e-05},
        'kernel_s': {'ring_agg_2d': 5e-05},
        'kernel_events': {'ring_agg_2d': 1},
        'device_ops': [['event_scan/while', 0.0002],
                       ['wave_train/fusion', 5e-05],
                       ['ring_chain/ring_agg_2d', 5e-05],
                       ['jit__eval_step/convolution', 5e-05]],
        'idle': {'stage': 4e-05, 'plan': 6e-05, 'study': 0.0004,
                 'eval': 0.00015000000000000001}}),
    (lambda: _second_study(with_kernel=True), {
        'window_s': 0.003, 'busy_s': 0.0007, 'studies': 2,
        'scope_s': {'event_scan': 0.0004, 'wave_train': 0.0001,
                    'ring_chain': 0.0001},
        'kernel_s': {'ring_agg_2d': 0.0001},
        'kernel_events': {'ring_agg_2d': 1},
        'device_ops': [['event_scan/while', 0.0004],
                       ['wave_train/fusion', 0.0001],
                       ['ring_chain/ring_agg_2d', 0.0001],
                       ['jit__eval_step/convolution', 0.0001]],
        'idle': {'stage': 4e-05, 'plan': 0.00012, 'study': 0.00084,
                 'eval': 0.00030000000000000003, '(no span)': 0.001}}),
]


@pytest.mark.parametrize("make,want", BEFORE, ids=["one", "two"])
def test_reduce_with_todays_scopes_reads_as_before(make, want):
    r = tr.reduce(make())
    assert set(r) == set(want) | {"scope_any_depth_s"}
    assert {k: r[k] for k in want} == want
    # at any depth: the loop's body ops under event_scan, each op once
    assert r["scope_any_depth_s"] == pytest.approx(
        {k: v * want["studies"] for k, v in
         {"event_scan": 110e-6, "wave_train": 50e-6,
          "ring_chain": 50e-6}.items()})


def loop_events():
    """One study, 0..1000 us, whose wave trains in a loop: a ``while``
    op 100-400 (no scope of its own) holds a dense op (110-160) and an
    ``expert_mm`` kernel (170-230, with a copy 180-200 nested in it) under
    ``wave_train_1/moe_experts_2``, and a router op (240-300) under
    ``wave_train_1`` alone; a ``ring_agg`` kernel 400-450 follows."""
    body = "jit(program_flat)/wave_train_1/"
    return [
        meta(3, name="/device:TPU:0"), meta(3, 2, "XLA Modules"),
        meta(3, 3, "XLA Ops"), meta(700, name="/host:CPU"),
        op(100, 350, "jit_program_flat(1)", tid=2),
        op(100, 300, "while.9"),
        op(110, 50, "fusion.1", body + "moe_experts_2/dot"),
        op(170, 60, "expert_mm.2",
           body + "moe_experts_2/jit(expert_mm)/pallas_call"),
        op(180, 20, "copy.3", body + "moe_experts_2/copy"),
        op(240, 60, "fusion.4", body + "router_0/add"),
        op(400, 50, "ring_agg_2d.5",
           "jit(program_flat)/ring_chain_0_9/jit(ring_agg_2d)/pallas_call"),
        span(0, 1000, "study"),
    ]


def test_declared_scope_reads_ops_nested_in_a_loop():
    plain = tr.reduce(loop_events())
    declared = tr.reduce(loop_events(), tr.SCOPES + ("moe_experts",))
    # the loop's body: the dense op and the kernel, the copy inside the
    # kernel not again
    assert declared["scope_any_depth_s"]["moe_experts"] == pytest.approx(
        110e-6)
    assert "moe_experts" not in plain["scope_any_depth_s"]
    assert plain["scope_any_depth_s"] == pytest.approx(
        {"wave_train": 170e-6, "ring_chain": 50e-6})
    # the top-level rule charges the whole loop to wave_train either way
    assert plain["scope_s"] == pytest.approx(
        {"wave_train": 300e-6, "ring_chain": 50e-6})
    for k in set(plain) | set(declared):
        if k == "scope_any_depth_s":
            assert ({s: v for s, v in declared[k].items()
                     if s != "moe_experts"} == plain[k])
        else:
            assert declared[k] == plain[k], k


def test_any_depth_counts_an_op_once_per_scope():
    pat = re.compile(r"\b(a|b)_[0-9_]+")
    ops = [op(0, 100, "outer", "a_1/x"), op(10, 50, "mid", "a_1/b_2/y"),
           op(20, 10, "inner", "a_1/b_2/z"), op(200, 5, "alone", "b_3/w")]
    assert tr.any_depth(ops, pat) == {"a": 100, "b": 55}
