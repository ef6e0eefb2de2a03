"""The model a configuration names: ``"model": "<name>"`` in
``bench/configs/<config>.json`` is the module ``bench/models/<name>.py``.

A model module reads its own block of the configuration and gives the
plain reference and the work counts everything that is the model's:

- ``data(cfg)``: ``(train_x, train_y, test_x, test_y)``, numpy arrays made
  from the configuration alone; the reference draws the vehicles' shards
  and minibatches as rows of the training pair;
- ``init(seed, cfg)``: the initial weights, a dict of f32 arrays;
- ``local_update(params, xs, ys, lr)``: ``len(xs)`` SGD steps of one
  arrival from the model it downloaded, ``xs``/``ys`` its minibatches;
- ``evaluate(params, x, y)``: ``(accuracy, mean loss)`` over the test set;
- ``train_flops(cfg)`` and ``forward_flops(cfg)``: model FLOPs of one
  trained sample (forward and backward) and of one evaluated sample;
- ``packed_params(cfg)``: the length P of the model packed leaf by leaf,
  each leaf padded to the 128-lane width.

A count depends on the configuration's shapes only, never on a trace or on
how the program computes the work.  The module is plain ``jax.numpy`` and
imports nothing of the program under test.

A model may expose further counts from shapes alone, such as the FLOPs of
the experts one chip holds, for its own readers.  Its per-layer metrics
are new reader files ``bench/metrics/<metric>.py`` whose ``read(ctx)``
takes the trace reduced over the named scopes and Pallas kernels the
reader declares (``SCOPES = (...)``, ``KERNELS = (...)``; time inside a
loop's body reads in ``ctx.trace["scope_any_depth_s"]``), or a counter of
the traced studies' run reports (``ctx.reports``, each
``RunReport.to_json()``, in study order).  Such a metric's entry in
``BENCHMARK.json`` lists only that model's cells in its ``workloads``.
"""
from __future__ import annotations

import importlib.util
import os

DIR = os.path.dirname(os.path.abspath(__file__))
_loaded = {}


def of(cfg: dict):
    """The module of the model ``cfg`` names, loaded once per path; a
    module that is not there raises ``SystemExit`` with its path."""
    path = os.path.join(DIR, cfg["model"] + ".py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise SystemExit(f"bench: configuration {cfg.get('name')!r} "
                             f"names model {cfg['model']!r}, but {path} "
                             f"does not exist")
        spec = importlib.util.spec_from_file_location(
            "bench_model_" + cfg["model"], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
