"""The model seam: a configuration names its model (``"model"``), and the
reference, the comparison and the work counts reach the model only through
``bench/models/<model>.py``.

A model other than the CNN joins with new files alone: a stub two-layer
MLP, written to a temporary directory with its configuration and a
``BENCHMARK.json`` that adds one configuration and one cell, drives a whole
run of the harness, and nothing under ``bench/`` is edited."""
import copy
import hashlib
import json
import os
import shutil
import sys
import textwrap
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import counts
import models
import reference
import run
import trace_reduce
from test_trace_reduce import loop_events

SEED = 2_147_483_659            # past 32 signed bits
P_CNN = 422_016
CNN_FORWARD = 8_482_304
CNN_TRAIN = 3 * CNN_FORWARD - 451_584


def config(name):
    return run.load_json("bench", "configs", name + ".json")


def small(cfg):
    """The reference at a size the CPU runs in seconds."""
    cfg = copy.deepcopy(cfg)
    cfg["scenario"].update(K=30, rounds=12, n_train=600, n_test=100)
    if cfg["scenario"]["n_rsus"] > 1:
        cfg["scenario"].update(K=24, n_rsus=3, reconcile_every=4)
    return cfg


# ---------------------------------------------------------------------------
# the CNN through the seam reads what it read before the seam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,flops,reads,writes", [
    # 60 rounds of 8 images, 6 evaluations of 400; bf16 uploads, one chain
    # per evaluation
    ("fleet-k10000", 60 * 8 * CNN_TRAIN + 6 * 400 * CNN_FORWARD,
     60 * P_CNN * 2 + 6 * 4 * P_CNN, 6 * 4 * P_CNN),
    # 40 rounds of 9 images, 4 evaluations; f32 uploads, one RSU's chain
    # ends at rounds 8, 10, 16, 20, 24, 30, 32 and 40
    ("corridor-r8-k4000", 40 * 9 * CNN_TRAIN + 4 * 400 * CNN_FORWARD,
     40 * P_CNN * 4 + 8 * 4 * P_CNN, 8 * 4 * P_CNN),
])
def test_counts_through_the_model_module(name, flops, reads, writes):
    cfg = config(name)
    model = models.of(cfg)
    assert model is models.of(config(name))           # loaded once
    assert model.packed_params(cfg) == P_CNN == cfg["cnn"]["params_packed"]
    assert counts.study_flops(cfg, 10) == flops
    M = cfg["scenario"]["rounds"]
    assert counts.ring_agg_bytes(cfg, list(range(M)), [0] * M, 10) == (
        reads, writes)


def digest(ans) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(ans["trace"]).encode())
    for k in sorted(ans["final"]):
        a = np.asarray(ans["final"][k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    h.update(json.dumps([(r, float(v).hex())
                         for r, v in ans["losses"]]).encode())
    return h.hexdigest()


# computed with the reference as it stood before the CNN moved into
# bench/models/cnn.py, at these sizes and this seed
@pytest.mark.parametrize("name,want", [
    ("fleet-k10000",
     "5e69829c02a82a239591d016adb74d51f1cbd0eb72868381e0e2cc1f1a76b3c9"),
    ("corridor-r8-k4000",
     "350c3eae47e4d876d0c3d67d3636c0bfa4e3a9dfbf748936a13746373e4e5068"),
])
def test_reference_answer_is_bitwise_unchanged(name, want):
    cfg = small(config(name))
    world = reference.World(cfg, SEED)
    ans = world.answer(cfg["scenario"]["lr"], 10,
                       cfg["scenario"]["ring_dtype"])
    assert digest(ans) == want


@pytest.mark.parametrize("name", ["fleet-k10000", "corridor-r8-k4000"])
def test_reference_keeps_only_the_rows_it_reads(name, monkeypatch):
    """Every row ``_store`` makes (a stored model or an upload) is held
    here too; a row that another reference holds is one the study still
    keeps.  The most held at once is what the trace's downloads and
    evaluations need, and each read row is stored once."""
    cfg = small(config(name))
    world = reference.World(cfg, SEED)
    made, most = [], []
    real = reference._store

    def store(p, dtype):
        # a row held only by ``made``, the loop's name and getrefcount's
        # argument has been dropped by the study
        most.append(sum(sys.getrefcount(r) > 3 for r in made))
        made.append(real(p, dtype))
        return made[-1]
    monkeypatch.setattr(reference, "_store", store)
    world.study(cfg["scenario"]["lr"], 10, cfg["scenario"]["ring_dtype"])

    M = cfg["scenario"]["rounds"]
    last = world.last_reads(10)
    dl = world.trace["dl_round"]
    assert set(last) == {d + 1 for d in dl} | (
        {10, 12} if cfg["scenario"]["n_rsus"] == 1 else set())
    assert len(made) == M + len(last)
    # rows stored by round r (row k after round k - 1) that round r or
    # a later one still reads
    need = max(sum(k <= r + 1 and last[k] >= r for k in last)
               for r in range(M))
    assert max(most) <= need < M


# ---------------------------------------------------------------------------
# a model that is not the CNN, from new files only
# ---------------------------------------------------------------------------
STUB = textwrap.dedent('''
    """A two-layer MLP on a linear teacher's labels."""
    import jax
    import jax.numpy as jnp
    import numpy as np


    def data(cfg):
        m, sc = cfg["mlp"], cfg["scenario"]
        rng = np.random.default_rng(0)
        teacher = rng.normal(size=(m["inputs"], m["classes"]))

        def make(n):
            x = rng.normal(size=(n, m["inputs"])).astype(np.float32)
            return x, np.argmax(x @ teacher, -1).astype(np.int32)
        return make(sc["n_train"]) + make(sc["n_test"])


    def init(seed, cfg):
        i, h, c = (cfg["mlp"][k] for k in ("inputs", "hidden", "classes"))
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        return {"w1": jax.random.normal(k1, (i, h)) / np.sqrt(i),
                "b1": jnp.zeros((h,), jnp.float32),
                "w2": jax.random.normal(k2, (h, c)) / np.sqrt(h),
                "b2": jnp.zeros((c,), jnp.float32)}


    def _nll(p, x, y):
        hi = jax.lax.Precision.HIGHEST
        z = jax.nn.relu(jnp.dot(x, p["w1"], precision=hi) + p["b1"])
        logits = jnp.dot(z, p["w2"], precision=hi) + p["b2"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return logits, nll


    @jax.jit
    def local_update(p, xs, ys, lr):
        for x, y in zip(xs, ys):
            g = jax.grad(lambda q: jnp.mean(_nll(q, x, y)[1]))(p)
            p = jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g)
        return p


    @jax.jit
    def evaluate(p, x, y):
        logits, nll = _nll(p, x, y)
        return (jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32)),
                jnp.mean(nll))


    def forward_flops(cfg):
        m = cfg["mlp"]
        return 2 * (m["inputs"] * m["hidden"] + m["hidden"] * m["classes"])


    def train_flops(cfg):
        m = cfg["mlp"]
        return 2 * (2 * m["inputs"] * m["hidden"]
                    + 3 * m["hidden"] * m["classes"])


    def packed_params(cfg):
        i, h, c = (cfg["mlp"][k] for k in ("inputs", "hidden", "classes"))
        return sum(-(-n // 128) * 128 for n in (i * h, h, h * c, c))
''')

MLP = {"inputs": 20, "hidden": 16, "classes": 5}
STUB_FORWARD = 2 * (20 * 16 + 16 * 5)
STUB_TRAIN = 2 * (2 * 20 * 16 + 3 * 16 * 5)
STUB_P = 384 + 128 + 128 + 128


@pytest.fixture
def stub_root(tmp_path, monkeypatch):
    """A checkout that adds a model to the benchmark with new files only:
    ``bench/models/mlp.py``, ``bench/configs/mlp-k30.json``, and one
    configuration and one cell appended to ``BENCHMARK.json``; the harness
    reads that checkout."""
    bench = tmp_path / "bench"
    for sub in ("models", "configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "models" / "mlp.py").write_text(STUB)
    shutil.copy(os.path.join(run.BENCH, "peaks.json"), bench)
    shutil.copy(os.path.join(run.BENCH, "traffic", "eval10.json"),
                bench / "traffic")
    cfg = small(config("fleet-k10000"))
    del cfg["cnn"]
    cfg.update(name="mlp-k30", model="mlp", mlp=MLP)
    (bench / "configs" / "mlp-k30.json").write_text(json.dumps(cfg))
    spec = run.load_json("BENCHMARK.json")
    spec["configs"].append({"name": "mlp-k30", "source": "a test's stub",
                            "file": "bench/configs/mlp-k30.json",
                            "reduced": [], "why": "the seam"})
    spec["workloads"].append({"name": "mlp-k30.study", "config": "mlp-k30",
                              "traffic": "eval10", "chips": 1,
                              "why": "the seam"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(models, "DIR", str(bench / "models"))
    return tmp_path


def reference_program(cfg, traffic, edit=None):
    """A ``run_study`` that answers with the reference itself, shaped as
    the program's result; ``edit`` alters its final model."""
    world = reference.World(cfg, SEED)
    storage = cfg["scenario"]["ring_dtype"]

    def study(lr):
        ans = world.answer(lr, traffic["eval_every"], storage)
        final = dict(ans["final"])
        if edit:
            edit(final)
        return SimpleNamespace(
            report=SimpleNamespace(phases={"run": 0.0}),
            rounds=[SimpleNamespace(vehicle=v, rsu=r)
                    for v, r in ans["trace"]],
            final_params=final, loss_history=ans["losses"])
    return study


@pytest.fixture(scope="module")
def counters():
    return run.Counters()


def drive(run_study, counters):
    spec, cell, cfg, traffic = run.load_cell("mlp-k30.study")
    return run.run_cell(spec, cell, cfg, traffic, SEED, 0.01, False,
                        jax.devices(), counters, "none",
                        run.peaks_of("TPU v5 lite"),
                        run_study=run_study(cfg, traffic))


def test_stub_model_counts(stub_root):
    _, _, cfg, _ = run.load_cell("mlp-k30.study")
    assert models.of(cfg).__file__.startswith(str(stub_root))
    M = cfg["scenario"]["rounds"]              # 12 rounds, evaluated at 10, 12
    assert counts.study_flops(cfg, 10) == (
        M * counts.minibatch(cfg["scenario"]) * STUB_TRAIN
        + 2 * cfg["scenario"]["n_test"] * STUB_FORWARD)
    rd, wr = counts.ring_agg_bytes(cfg, list(range(M)), [0] * M, 10)
    assert (rd, wr) == (M * STUB_P * 2 + 2 * 4 * STUB_P, 2 * 4 * STUB_P)


def test_stub_model_sound_answer_is_correct(stub_root, counters):
    line = drive(reference_program, counters)
    assert line["correct"], line["checks"]
    assert line["checks"]["model"]["value"] == 0.0


def _perturb_w2(final):
    final["w2"] = final["w2"] + 0.05


def test_stub_model_perturbed_leaf_is_incorrect(stub_root, counters):
    line = drive(lambda cfg, traffic: reference_program(
        cfg, traffic, _perturb_w2), counters)
    assert not line["correct"], line["checks"]


# ---------------------------------------------------------------------------
# a configuration that names no module
# ---------------------------------------------------------------------------
def test_missing_model_fails_before_any_study(counters):
    _, cell, cfg, traffic = run.load_cell("fleet-k10000.study")
    cfg = dict(cfg, model="no_such_model")
    calls = []
    with pytest.raises(SystemExit) as exc:
        run.run_cell(run.load_json("BENCHMARK.json"), cell, cfg, traffic,
                     SEED, 0.01, False, jax.devices(), counters, "none",
                     run.peaks_of("TPU v5 lite"), run_study=calls.append)
    assert os.path.join(models.DIR, "no_such_model.py") in str(exc.value)
    assert calls == []
    with pytest.raises(SystemExit):
        reference.World(cfg, SEED)


# ---------------------------------------------------------------------------
# a per-layer metric of that model, from a new reader file only
# ---------------------------------------------------------------------------
STUB_READER = textwrap.dedent('''
    """Rows gathered into minibatches per millisecond of expert time."""
    SCOPES = ("moe_experts",)
    KERNELS = ("expert_mm",)


    def read(ctx):
        t = ctx.trace
        if not t or not t["kernel_s"].get("expert_mm") or not ctx.reports:
            return None
        rows = sum(r["world"]["rows_gathered"] for r in ctx.reports)
        return rows / (t["scope_any_depth_s"]["moe_experts"] * 1e3)
''')


def test_stub_reader_declares_the_scope_and_kernel_it_reads(stub_root,
                                                            monkeypatch):
    (stub_root / "bench" / "metrics").mkdir()
    (stub_root / "bench" / "metrics" / "moe.rows_per_expert_ms.py"
     ).write_text(STUB_READER)
    spec = run.load_json("BENCHMARK.json")
    spec["per_layer"].append({
        "name": "moe.rows_per_expert_ms", "unit": "rows/ms",
        "better": "higher", "source": "device_trace", "layer": "experts",
        "moves": "rounds_per_s", "workloads": ["mlp-k30.study"]})
    (stub_root / "BENCHMARK.json").write_text(json.dumps(spec))
    spec, cell, cfg, traffic = run.load_cell("mlp-k30.study")
    readers = run.load_readers(spec, cell)
    assert [m["name"] for m, _ in readers] == ["moe.rows_per_expert_ms"]

    seen = []
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda events, scopes,
                        kernels: seen.append((scopes, kernels))
                        or real(events, scopes, kernels))
    red = run.reduce_trace(loop_events(), readers)
    assert seen == [(trace_reduce.SCOPES + ("moe_experts",),
                     trace_reduce.KERNELS + ("expert_mm",))]
    assert red["kernel_s"]["expert_mm"] == pytest.approx(60e-6)

    ctx = SimpleNamespace(studies=[], trace=red, cfg=cfg, traffic=traffic,
                          peaks=run.peaks_of("TPU v5 lite"), setup={},
                          reports=[{"world": {"rows_gathered": 8}},
                                   {"world": {"rows_gathered": 12}}])
    got = run.read_metrics(readers, ctx)
    assert got == {"moe.rows_per_expert_ms": {
        "value": pytest.approx(20 / 0.11), "unit": "rows/ms"}}


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  run.load_json("BENCHMARK.json")["workloads"]])
def test_benchmark_cells_reduce_over_the_defaults(cell, monkeypatch):
    """The CNN's readers declare nothing: each cell's trace is reduced
    exactly as before readers could declare."""
    spec, cell, _, _ = run.load_cell(cell)
    seen = []
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda *a: seen.append(a[1:]) or {})
    run.reduce_trace([], run.load_readers(spec, cell))
    assert seen == [(trace_reduce.SCOPES, trace_reduce.KERNELS)]
