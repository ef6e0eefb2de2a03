"""The paper's CNN (arXiv:2208.01901 Section V-A) on synthetic digits: the
model of a configuration with ``"model": "cnn"``, read from its ``"cnn"``
block (image side, kernel, channels, hidden width, classes) and from the
data sizes in its ``"scenario"`` block.

The contract every model module keeps is in ``bench/models/__init__.py``.
Arithmetic is float32, with convolutions and matmuls at ``HIGHEST``
precision; SGD is plain (Eqs. 1-2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# data: the synthetic MNIST stand-in
# ---------------------------------------------------------------------------
def _blur(img):
    k = (0.25, 0.5, 0.25)
    for ax in (0, 1):
        n = img.shape[ax]
        img = (np.take(img, np.arange(n) - 1, axis=ax, mode="clip") * k[0]
               + img * k[1]
               + np.take(img, np.arange(n) + 1, axis=ax, mode="clip") * k[2])
    return img


def synthetic_digits(n_train, n_test, noise, seed=0, n_classes=10):
    """Ten smooth random class prototypes on 28x28, each sample shifted by
    up to 2 px and given Gaussian noise, clipped to [0, 1]."""
    rng = np.random.default_rng(seed)
    protos = []
    for _ in range(n_classes):
        img = _blur(np.kron(rng.normal(size=(7, 7)), np.ones((4, 4))))
        protos.append((img - img.min()) / (np.ptp(img) + 1e-9))
    protos = np.stack(protos)

    def make(n, rng):
        labels = rng.integers(0, n_classes, n)
        base = protos[labels]
        sx = rng.integers(-2, 3, n)
        sy = rng.integers(-2, 3, n)
        imgs = np.empty((n, 28, 28), np.float32)
        for dx in range(-2, 3):
            for dy in range(-2, 3):
                m = (sx == dx) & (sy == dy)
                if m.any():
                    imgs[m] = np.roll(np.roll(base[m], dx, axis=1), dy,
                                      axis=2)
        imgs += rng.normal(scale=noise, size=imgs.shape).astype(np.float32)
        return np.clip(imgs, 0, 1)[..., None], labels.astype(np.int32)

    tr = make(n_train, rng)
    te = make(n_test, np.random.default_rng(seed + 1))
    return tr + te


def data(cfg: dict) -> tuple:
    sc = cfg["scenario"]
    return synthetic_digits(sc["n_train"], sc["n_test"], sc["noise"])


# ---------------------------------------------------------------------------
# the network and plain SGD
# ---------------------------------------------------------------------------
def init(seed, cfg: dict) -> dict:
    """HWIO convolutions and dense layers, N(0, 1/fan_in), zero biases;
    one jax.random split of the seed's key per weight."""
    cnn = cfg["cnn"]
    c1, c2, f1, nc = (cnn["conv1"], cnn["conv2"], cnn["fc1"],
                      cnn["classes"])
    k = cnn["kernel"]
    flat = (cnn["image"] // 4) ** 2 * c2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "conv1_w": jax.random.normal(ks[0], (k, k, 1, c1)) / np.sqrt(k * k),
        "conv1_b": jnp.zeros((c1,), jnp.float32),
        "conv2_w": (jax.random.normal(ks[1], (k, k, c1, c2))
                    / np.sqrt(k * k * c1)),
        "conv2_b": jnp.zeros((c2,), jnp.float32),
        "fc1_w": jax.random.normal(ks[2], (flat, f1)) / np.sqrt(flat),
        "fc1_b": jnp.zeros((f1,), jnp.float32),
        "fc2_w": jax.random.normal(ks[3], (f1, nc)) / np.sqrt(f1),
        "fc2_b": jnp.zeros((nc,), jnp.float32),
    }


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p, x):
    x = _pool(jax.nn.relu(_conv(x, p["conv1_w"]) + p["conv1_b"]))
    x = _pool(jax.nn.relu(_conv(x, p["conv2_w"]) + p["conv2_b"]))
    x = jax.nn.relu(jnp.dot(x.reshape(x.shape[0], -1), p["fc1_w"],
                            precision=HIGHEST) + p["fc1_b"])
    return jnp.dot(x, p["fc2_w"], precision=HIGHEST) + p["fc2_b"]


def _nll(p, x, y):
    logp = jax.nn.log_softmax(forward(p, x), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]


@jax.jit
def local_update(p, xs, ys, lr):
    """len(xs) SGD steps (Eq. 2) on the mean cross-entropy (Eq. 1)."""
    for x, y in zip(xs, ys):
        g = jax.grad(lambda q: jnp.mean(_nll(q, x, y)))(p)
        p = jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g)
    return p


@jax.jit
def evaluate(p, x, y):
    logits = forward(p, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return (jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32)),
            jnp.mean(nll))


# ---------------------------------------------------------------------------
# work counts, from the shapes alone
# ---------------------------------------------------------------------------
def layers(cfg: dict) -> list:
    """``(name, macs per image, has an input gradient)`` of each conv and
    dense layer; SAME 3x3 convolutions, 2x2 pooling after each conv."""
    cnn = cfg["cnn"]
    s, k = cnn["image"], cnn["kernel"]
    c1, c2, f1, nc = cnn["conv1"], cnn["conv2"], cnn["fc1"], cnn["classes"]
    flat = (s // 4) ** 2 * c2
    return [
        ("conv1", s * s * c1 * k * k * 1, False),      # the image needs none
        ("conv2", (s // 2) ** 2 * c2 * k * k * c1, True),
        ("fc1", flat * f1, True),
        ("fc2", f1 * nc, True),
    ]


def forward_flops(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass, two FLOPs each."""
    return sum(2 * m for _, m, _ in layers(cfg))


def train_flops(cfg: dict) -> int:
    """Forward, weight gradient, and input gradient of every layer but the
    first, for one image."""
    return sum(2 * m * (3 if dx else 2) for _, m, dx in layers(cfg))


def packed_params(cfg: dict) -> int:
    """Length P of the model packed leaf by leaf, each leaf padded to a
    multiple of the 128-lane width."""
    cnn = cfg["cnn"]
    s, k = cnn["image"], cnn["kernel"]
    c1, c2, f1, nc = cnn["conv1"], cnn["conv2"], cnn["fc1"], cnn["classes"]
    sizes = [k * k * c1, c1, k * k * c1 * c2, c2,
             (s // 4) ** 2 * c2 * f1, f1, f1 * nc, nc]
    return sum(-(-n // LANE) * LANE for n in sizes)
