"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
beside the ``.xplane.pb``: Chrome-trace events in microseconds on one
clock.  Device planes are processes named ``/device:TPU:<n>``; their
``XLA Ops`` thread holds one event per executed HLO op, nested where a
loop (``while``) encloses its body's ops.  An op's ``args.tf_op`` carries
the ``jax.named_scope`` path it was traced under, when it has one.  Host
threads carry the ``TraceAnnotation`` spans the harness opens, named
``bench.<label>``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import Counter, defaultdict

SPAN_PREFIX = "bench."
STUDY = SPAN_PREFIX + "study"
# what every cell's trace is reduced over; a reader adds its own
# (``SCOPES`` and ``KERNELS`` in ``bench/metrics/<name>.py``)
SCOPES = ("wave_train", "event_scan", "ring_chain")
KERNELS = ("ring_agg_2d",)
_SUFFIX = re.compile(r"\.\d+$")


def load(log_dir: str) -> list:
    """Events of the newest trace under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {log_dir}")
    with gzip.open(max(paths, key=os.path.getmtime)) as f:
        return json.load(f)["traceEvents"]


def _names(events, kind):
    return {e["pid"] if kind == "process_name" else (e["pid"], e["tid"]):
            e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == kind}


def split(events) -> tuple:
    """``(device ops by device, XLA module runs by device, host spans)``.
    Ops and modules are complete events with ``ts``/``dur`` in us."""
    procs = _names(events, "process_name")
    threads = _names(events, "thread_name")
    ops, mods, spans = defaultdict(list), defaultdict(list), []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        if proc.startswith("/device:TPU:"):
            line = threads.get((e["pid"], e["tid"]), "")
            if line == "XLA Ops":
                ops[proc].append(e)
            elif line == "XLA Modules":
                mods[proc].append(e)
        elif e.get("name", "").startswith(SPAN_PREFIX):
            spans.append(e)
    return dict(ops), dict(mods), spans


def top_level(ops) -> list:
    """Ops not nested in another op, each with the ``tf_op`` paths of the
    ops nested in it: ``[(event, [tf_op, ...])]`` in time order."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e["ts"], -e["dur"])):
        while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"]:
            stack.pop()
        if stack:
            out[-1][1].append(e.get("args", {}).get("tf_op", ""))
        else:
            out.append((e, []))
        stack.append(e)
    return out


def any_depth(ops, pattern: re.Pattern) -> dict:
    """Microseconds of the ops whose own ``tf_op`` carries each scope
    ``pattern`` finds, at any depth of nesting; an op inside another that
    carries the same scope is already counted by it."""
    out, stack = defaultdict(float), []
    for e in sorted(ops, key=lambda e: (e["ts"], -e["dur"])):
        while stack and stack[-1][0] <= e["ts"]:
            stack.pop()
        outer = stack[-1][1] if stack else frozenset()
        own = set(pattern.findall(e.get("args", {}).get("tf_op", "")))
        for sc in own - outer:
            out[sc] += e["dur"]
        stack.append((e["ts"] + e["dur"], outer | own))
    return out


def scope_of(top, pattern: re.Pattern):
    """The scope ``pattern`` finds in the op's own ``tf_op``, else the one
    most of its nested ops carry, else None."""
    e, inner = top
    m = pattern.search(e.get("args", {}).get("tf_op", ""))
    if m:
        return m.group(1)
    found = Counter(m.group(1) for m in map(pattern.search, inner) if m)
    return found.most_common(1)[0][0] if found else None


def union(intervals) -> list:
    """Merged ``[start, end]`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo, hi) -> list:
    """Idle intervals of ``[lo, hi]`` outside the merged ``busy`` ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append([t, min(a, hi)])
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append([t, hi])
    return [g for g in out if g[1] > g[0]]


def label_time(intervals, spans) -> dict:
    """Seconds of ``intervals`` (us) under each host span label, by the
    innermost span open at each instant; time under no span is
    ``"(no span)"``."""
    spans = sorted(((s["ts"], s["ts"] + s["dur"],
                     s["name"][len(SPAN_PREFIX):]) for s in spans),
                   key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in spans]
    cuts = sorted({x for s in spans for x in s[:2]})
    out = defaultdict(float)
    for a, b in intervals:
        pts = [a] + cuts[bisect.bisect_right(cuts, a):
                         bisect.bisect_left(cuts, b)] + [b]
        for u, v in zip(pts, pts[1:]):
            mid = (u + v) / 2
            # spans nest: of those open at mid, the latest to start is
            # the innermost, and every later one has closed already
            k = bisect.bisect_right(starts, mid) - 1
            while k >= 0 and spans[k][1] <= mid:
                k -= 1
            out[spans[k][2] if k >= 0 else "(no span)"] += (v - u) / 1e6
    return dict(out)


def reduce(events, scopes=SCOPES, kernels=KERNELS) -> dict:
    """The traced window's numbers, averaged over the devices traced:

    - ``window_s``: first study span's start to the last one's end;
    - ``busy_s``: the union of device op intervals inside the window;
    - ``studies``: study spans in the window;
    - ``scope_s``: device seconds of top-level ops per named scope;
    - ``scope_any_depth_s``: device seconds per named scope of the ops
      that carry it at any depth, each op once (``any_depth``): a scope
      inside a loop's body reads here and not in ``scope_s``;
    - ``kernel_s``: device seconds of each Pallas kernel's events;
    - ``kernel_events``: each kernel's events in one study.  Every study
      of a run runs the same plan, so a study that holds fewer than the
      others lost events from the trace: that raises ``ValueError``;
    - ``device_ops``: the ten ``<scope or module>/<op>`` keys of most time;
    - ``idle``: idle seconds by the innermost host span open in them.
    """
    ops, mods, spans = split(events)
    studies = [s for s in spans if s["name"] == STUDY]
    if not ops or not studies:
        return {}
    lo = min(s["ts"] for s in studies)
    hi = max(s["ts"] + s["dur"] for s in studies)
    bounds = sorted((s["ts"], s["ts"] + s["dur"]) for s in studies)
    firsts = [b[0] for b in bounds]
    per_study = {name: [0] * len(bounds) for name in kernels}
    pattern = re.compile(r"\b(%s)_[0-9_]+" % "|".join(map(re.escape,
                                                          scopes)))
    n = len(ops)
    busy_s = 0.0
    scope_s, kernel_s = defaultdict(float), defaultdict(float)
    depth_s = defaultdict(float)
    op_s, idle = defaultdict(float), defaultdict(float)
    for dev, dev_ops in ops.items():
        inside = [e for e in dev_ops if lo <= e["ts"] < hi]
        busy = clip(union([e["ts"], e["ts"] + e["dur"]] for e in inside),
                    lo, hi)
        busy_s += sum(b - a for a, b in busy) / 1e6 / n
        runs = sorted((m["ts"], m["ts"] + m["dur"],
                       m["name"].split("(")[0]) for m in mods.get(dev, []))
        starts = [r[0] for r in runs]
        for top in top_level(inside):
            e = top[0]
            sc = scope_of(top, pattern)
            if sc:
                scope_s[sc] += e["dur"] / 1e6 / n
            k = bisect.bisect_right(starts, e["ts"]) - 1
            where = sc or (runs[k][2] if k >= 0 and e["ts"] < runs[k][1]
                           else "?")
            op_s[f"{where}/{_SUFFIX.sub('', e['name'])}"] += (
                e["dur"] / 1e6 / n)
        for sc, us in any_depth(inside, pattern).items():
            depth_s[sc] += us / 1e6 / n
        for e in inside:
            tf_op = e.get("args", {}).get("tf_op", "")
            for name in kernels:
                if f"jit({name})" in tf_op or e["name"].startswith(name):
                    kernel_s[name] += e["dur"] / 1e6 / n
                    k = bisect.bisect_right(firsts, e["ts"]) - 1
                    if k >= 0 and e["ts"] < bounds[k][1]:
                        per_study[name][k] += 1
        for label, s in label_time(gaps(busy, lo, hi), spans).items():
            idle[label] += s / n
    for name, counts in per_study.items():
        if len(set(counts)) > 1:
            raise ValueError(f"the traced studies hold {counts} {name} "
                             "events: the trace lost some")
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e6, "busy_s": busy_s,
            "studies": len(studies), "scope_s": dict(scope_s),
            "scope_any_depth_s": dict(depth_s),
            "kernel_s": dict(kernel_s),
            "kernel_events": {k: c[0] for k, c in per_study.items()},
            "device_ops": [list(kv) for kv in top_ops],
            "idle": dict(idle)}
