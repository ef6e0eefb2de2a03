"""Substrate tests: optimizers, checkpointing, data pipeline, sharding specs."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel.params import ChannelParams
from repro.checkpointing import (latest_checkpoint, load_checkpoint,
                                 save_checkpoint)
from repro.data import TokenPipeline, partition_vehicles, synth_mnist, synth_tokens
from repro.optim import (adam, apply_updates, clip_by_global_norm,
                         cosine_decay, momentum_sgd, sgd)


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("make_opt", [lambda: sgd(0.1),
                                      lambda: momentum_sgd(0.05),
                                      lambda: adam(0.1)])
def test_optimizers_converge_on_quadratic(make_opt):
    opt = make_opt()
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree_util.tree_map(lambda w: 2 * w, params)  # d/dw w^2
        upd, state = opt.update(grads, state, params)
        params = apply_updates(params, upd)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_sgd_is_paper_eq2():
    opt = sgd(0.5)
    params = {"w": jnp.array([2.0])}
    state = opt.init(params)
    upd, _ = opt.update({"w": jnp.array([1.0])}, state, params)
    assert float(apply_updates(params, upd)["w"][0]) == pytest.approx(1.5)


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-4)


def test_cosine_schedule_endpoints():
    fn = cosine_decay(1.0, 100)
    assert float(fn(jnp.int32(0))) == pytest.approx(1.0)
    assert float(fn(jnp.int32(100))) == pytest.approx(0.0, abs=1e-6)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {"layer": {"w": jnp.arange(12.0).reshape(3, 4),
                      "b": jnp.ones((4,), jnp.bfloat16)},
            "stack": [jnp.zeros((2, 2)), jnp.full((1,), 7.0)]}
    d = str(tmp_path)
    save_checkpoint(d, 3, tree, meta={"round": 3})
    path = latest_checkpoint(d)
    assert path and path.endswith("ckpt_00000003.npz")
    restored = load_checkpoint(path, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_checkpoint_retention(tmp_path):
    d = str(tmp_path)
    for step in range(6):
        save_checkpoint(d, step, {"x": jnp.zeros(1)}, keep=2)
    files = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    assert files == ["ckpt_00000004.npz", "ckpt_00000005.npz"]


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_partition_follows_di_profile():
    p = ChannelParams()
    imgs, labels, _, _ = synth_mnist(n_train=5000, n_test=10, seed=0)
    veh = partition_vehicles(imgs, labels, p, seed=0, scale=0.01)
    sizes = [v.size for v in veh]
    # D_i = (2250 + 3750 i) * scale
    expect = [int((2250 + 3750 * i) * 0.01) for i in range(1, 11)]
    assert sizes == expect
    assert veh[0].index == 1 and veh[-1].index == 10


def test_synth_mnist_is_learnably_separable():
    tr_i, tr_l, te_i, te_l = synth_mnist(n_train=512, n_test=128, seed=0,
                                         noise=0.3)
    assert tr_i.shape == (512, 28, 28, 1) and tr_i.min() >= 0
    # nearest-prototype classification should beat chance by a wide margin
    protos = np.stack([tr_i[tr_l == c].mean(0) for c in range(10)])
    d = ((te_i[:, None] - protos[None]) ** 2).sum((2, 3, 4))
    acc = (d.argmin(1) == te_l).mean()
    assert acc > 0.6


def test_token_pipeline_batches():
    corpus = synth_tokens(16, 64, vocab=100, seed=0)
    pipe = TokenPipeline(corpus, batch=4, seq_len=32, seed=0)
    b1 = next(pipe)
    assert b1.shape == (4, 33) and b1.dtype == np.int32
    assert (b1 >= 0).all() and (b1 < 100).all()


def test_synth_tokens_have_bigram_signal():
    toks = synth_tokens(64, 128, vocab=50, seed=0)
    # repeated bigrams far above uniform chance
    big = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            big[(a, b)] = big.get((a, b), 0) + 1
    top = sorted(big.values())[-20:]
    assert sum(top) > len(toks) * 128 * 20 / (50 * 50) * 3


# ---------------------------------------------------------------------------
# sharding specs (AbstractMesh — no devices needed)
# ---------------------------------------------------------------------------
def test_param_specs_structure_and_rules():
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.steps import param_shapes
    from repro.sharding import param_specs

    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("llama3-405b")
    specs = param_specs(cfg, mesh, fsdp=True)
    shapes = param_shapes(cfg)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(shapes)
    # embed [V, d]: vocab on model
    assert specs["embed"]["table"][0] == "model"
    # stacked leaves never shard the leading period axis
    stack_specs = jax.tree_util.tree_leaves(
        specs["stack"], is_leaf=lambda x: isinstance(x, P))
    assert all(s[0] is None for s in stack_specs)


def test_param_specs_degrade_on_indivisible():
    from jax.sharding import AbstractMesh
    from repro.configs import get_config
    from repro.sharding import param_specs

    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("smollm-360m")          # 15 heads: not divisible by 16
    specs = param_specs(cfg, mesh, fsdp=False)
    wq_spec = specs["stack"]["sub0"]["mixer"]["wq"]
    assert wq_spec[2] is None                # heads dim (after period axis)
    mlp_spec = specs["stack"]["sub0"]["mlp"]["w_gate"]
    assert mlp_spec[2] == "model"            # 2560 % 16 == 0 -> sharded


def test_cache_specs_shard_batch_and_seq():
    from jax.sharding import AbstractMesh
    from repro.configs import get_config
    from repro.sharding import cache_specs

    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("mistral-nemo-12b")
    specs = cache_specs(cfg, mesh, batch=128, max_seq=32768)
    kspec = specs["stack"]["sub0"]["mixer"]["k"]
    assert kspec[0] is None                  # leading period axis
    # PartitionSpec entries may be bare axis names or 1-tuples of them
    unwrap = lambda e: e[0] if isinstance(e, tuple) and len(e) == 1 else e
    assert unwrap(kspec[1]) == "data" and unwrap(kspec[2]) == "model"
