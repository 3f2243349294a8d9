"""The paper's local-client model (§III-B): Conv2D–Pool–Conv2D–Pool–Flatten–
Dense–Dense, pure JAX, sized for 28×28×1 synthetic images.

This is the model every FL client trains in the reproduction experiments; it
is deliberately tiny ("low computation ability of local clients", §VI).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


def cnn_init(key: Array, num_classes: int = 10, image_size: int = 28,
             channels: int = 1, c1: int = 32, c2: int = 64,
             hidden: int = 128, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 4)
    s = image_size // 4  # two 2× pools
    flat = s * s * c2

    def he(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * math.sqrt(2.0 / fan_in)).astype(dtype)

    return {
        "conv1": {"w": he(ks[0], (3, 3, channels, c1), 9 * channels),
                  "b": jnp.zeros((c1,), dtype)},
        "conv2": {"w": he(ks[1], (3, 3, c1, c2), 9 * c1),
                  "b": jnp.zeros((c2,), dtype)},
        "fc1": {"w": he(ks[2], (flat, hidden), flat), "b": jnp.zeros((hidden,), dtype)},
        "fc2": {"w": he(ks[3], (hidden, num_classes), hidden),
                "b": jnp.zeros((num_classes,), dtype)},
    }


def _taps(x: Array, kh: int, kw: int) -> List[Array]:
    """The kh·kw shifted (..., H, W) views of a one-channel (..., H, W, 1)
    image, SAME-padded for a stride-1 window, in row-major (HWIO) order."""
    h, w = x.shape[-3:-1]
    pad = [(0, 0)] * (x.ndim - 3) + [((kh - 1) // 2, kh // 2),
                                     ((kw - 1) // 2, kw // 2)]
    xp = jnp.pad(x[..., 0], pad)
    return [xp[..., i:i + h, j:j + w] for i in range(kh) for j in range(kw)]


def _conv(x: Array, w: Array, b: Array) -> Array:
    """SAME, stride-1 convolution, NHWC × HWIO.

    A one-channel input is a sum over its window taps, each tap's shifted
    image times that tap's row of weights.  ``lax.conv`` would do the same
    arithmetic, but vmapped over clients with per-client weights it becomes
    a grouped convolution of one input channel per group, which the TPU
    lays out and relays out at several times the cost of the work."""
    kh, kw, cin, cout = w.shape
    if cin == 1:
        wk = w.reshape(kh * kw, cout)
        taps = _taps(x, kh, kw)
        y = taps[0][..., None] * wk[0]
        for k in range(1, kh * kw):
            y = y + taps[k][..., None] * wk[k]
    else:
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _pool(x: Array) -> Array:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def cnn_apply(params: PyTree, images: Array) -> Array:
    """images: (B, H, W, C) → logits (B, num_classes).

    Each layer is a ``jax.named_scope`` (``cnn.conv1`` … ``cnn.dense``), so
    its forward and backward ops are named in the compiled program."""
    with jax.named_scope("cnn.conv1"):
        x = jax.nn.relu(_conv(images, params["conv1"]["w"],
                              params["conv1"]["b"]))
    with jax.named_scope("cnn.pool1"):
        x = _pool(x)
    with jax.named_scope("cnn.conv2"):
        x = jax.nn.relu(_conv(x, params["conv2"]["w"], params["conv2"]["b"]))
    with jax.named_scope("cnn.pool2"):
        x = _pool(x)
    with jax.named_scope("cnn.dense"):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        return x @ params["fc2"]["w"] + params["fc2"]["b"]


def cnn_loss(params: PyTree, images: Array, labels: Array,
             valid: Array | None = None) -> Tuple[Array, Dict[str, Array]]:
    """Categorical cross-entropy (paper's loss), with padding mask support."""
    logits = cnn_apply(params, images).astype(jnp.float32)
    with jax.named_scope("cnn.loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = logz - gold
        if valid is None:
            valid = jnp.ones_like(nll)
        else:
            valid = valid.astype(jnp.float32)
        denom = jnp.maximum(valid.sum(), 1.0)
        loss = (nll * valid).sum() / denom
        acc = ((jnp.argmax(logits, -1) == labels) * valid).sum() / denom
    return loss, {"accuracy": acc, "n": denom}
