"""Plain reference for the ``resnet`` family: ResNet-18 with the CIFAR
stem (3x3 convolution, no max-pool), basic blocks [2, 2, 2, 2], and
GroupNorm with gcd(32, channels) groups in place of BatchNorm (the
departure ``pytorch_ps_mpi_tpu/models/resnet.py`` makes; eps 1e-6).
``jax.numpy`` / ``lax.conv`` in float32 at the highest matmul
precision, reading the system's parameter pytree by name.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GN_EPS = 1e-6


def _conv(x, kernel, stride: int, pad: int):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, p):
    p = p["GroupNorm_0"]
    n, h, w, c = x.shape
    groups = math.gcd(32, c)
    g = x.reshape(n, h, w, groups, c // groups)
    mu = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mu) / jnp.sqrt(var + GN_EPS)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def _block(x, p, stride: int):
    y = _conv(x, p["Conv_0"]["kernel"], stride, 1)
    y = jax.nn.relu(_group_norm(y, p["AdaptiveGroupNorm_0"]))
    y = _group_norm(_conv(y, p["Conv_1"]["kernel"], 1, 1),
                    p["AdaptiveGroupNorm_1"])
    if "shortcut" in p:
        x = _group_norm(_conv(x, p["shortcut"]["kernel"], stride, 0),
                        p["shortcut_norm"])
    return jax.nn.relu(y + x)


def logits(params, images, stage_sizes=(2, 2, 2, 2)):
    p = params["params"]
    x = _conv(images.astype(jnp.float32), p["Conv_0"]["kernel"], 1, 1)
    x = jax.nn.relu(_group_norm(x, p["stem_norm"]))
    n = 0
    for stage, count in enumerate(stage_sizes):
        for j in range(count):
            x = _block(x, p[f"ResNetBlock_{n}"],
                       2 if stage > 0 and j == 0 else 1)
            n += 1
    x = x.mean(axis=(1, 2))
    return x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]


def loss(params, batch, stage_sizes=(2, 2, 2, 2)):
    """Mean cross-entropy of (images, labels)."""
    images, labels = batch
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(params, images, stage_sizes))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def int8_roundtrip(g):
    """What the ``int8`` codec delivers of one gradient leaf (numpy):
    q = round(g / scale) clipped to +-127, scale = max|g| / 127."""
    import numpy as np

    scale = max(float(np.max(np.abs(g))) / 127.0, 1e-12)
    return np.clip(np.round(g / np.float32(scale)), -127, 127) * np.float32(scale)
