"""The MNIST configurations' own pieces, found by the dataset's name
(``config["dataset"]["name"]``): the dataset twin made from the seed, the
plain reference of the paper's Table III CNNs, and their FLOPs per layer.

The twin is a copy of the program's ``make_mnist_twin``
(``data/synthetic.py``) with two changes: every class has the same count,
so that a split's table of counts gives every seed the same shard sizes,
and the per-example translation is one fancy index instead of a Python
loop over the examples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import conv_flops, dense_flops

SIDE = 28


# --------------------------------------------------------------- the twin
def _smooth_prototype(rng, side, cutoff=6):
    coef = rng.normal(size=(cutoff, cutoff))
    u = np.cos(np.pi * np.outer(np.arange(side) + 0.5, np.arange(cutoff))
               / side)
    img = u @ coef @ u.T
    img = (img - img.min()) / (np.ptp(img) + 1e-9)
    return img.astype(np.float32)


def twin(seed, n_train, n_test, noise=0.35, modes_per_class=3):
    """28x28 images, 10 classes, each a mixture of smooth prototypes with
    gain jitter, pixel noise and a translation of up to 2 pixels:
    (x_train, y_train, x_test, y_test)."""
    rng = np.random.default_rng(seed)
    protos = np.stack([_smooth_prototype(rng, SIDE)
                       for _ in range(10 * modes_per_class)]
                      ).reshape(10, modes_per_class, SIDE, SIDE)

    def sample(n):
        y = rng.permutation(np.arange(n) % 10)     # the same count per class
        mode = rng.integers(0, modes_per_class, size=n)
        base = protos[y, mode]
        gain = rng.uniform(0.7, 1.3, size=(n, 1, 1)).astype(np.float32)
        x = base * gain + noise * rng.normal(size=base.shape).astype(
            np.float32)
        shift = rng.integers(-2, 3, size=(n, 2))
        side = np.arange(SIDE)
        rows = (side[None, :] - shift[:, :1]) % SIDE        # np.roll, axis 0
        cols = (side[None, :] - shift[:, 1:]) % SIDE        # np.roll, axis 1
        x = x[np.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :]]
        return (np.clip(x, 0.0, 1.5)[..., None].astype(np.float32),
                y.astype(np.int32))

    xt, yt = sample(n_train)
    xv, yv = sample(n_test)
    return xt, yt, xv, yv


# ------------------------------------------------------ the plain reference
def init(key, filters, num_classes):
    """Conv2D 3x3 stride 2 'same' stack + Dense head, He-normal weights and
    zero biases."""
    ks = jax.random.split(key, len(filters) + 1)
    conv, cin, hw = [], 1, SIDE
    for i, f in enumerate(filters):
        conv.append({"w": _he(ks[i], (3, 3, cin, f), 9 * cin),
                     "b": jnp.zeros((f,))})
        cin, hw = f, (hw + 1) // 2
    flat = hw * hw * filters[-1]
    return {"conv": conv,
            "head": {"w": _he(ks[-1], (flat, num_classes), flat),
                     "b": jnp.zeros((num_classes,))}}


def _he(key, shape, fan_in):
    return jnp.sqrt(2.0 / fan_in) * jax.random.normal(key, shape, jnp.float32)


def forward(p, x, prec):
    """Logits: ReLU after every convolution, no dropout (Table III)."""
    h = x
    for c in p["conv"]:
        h = jax.nn.relu(jax.lax.conv_general_dilated(
            h, c["w"], window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
            + c["b"])
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, p["head"]["w"], precision=prec) + p["head"]["b"]


# -------------------------------------------------------------------- FLOPs
def layer_flops(filters, num_classes):
    """Forward FLOPs per example of each weight layer, input to output."""
    out, hw, cin = [], SIDE, 1
    for f in filters:
        hw = (hw + 1) // 2                           # 3x3, stride 2, 'same'
        out.append(conv_flops(hw * hw, 9, cin, f))
        cin = f
    return out + [dense_flops(hw * hw * cin, num_classes)]
