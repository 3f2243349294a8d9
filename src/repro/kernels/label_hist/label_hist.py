"""Per-client label-histogram kernel — the server-side statistics hot loop of
Algorithm 1 at fleet scale (millions of labels × thousands of clients).

TPU mapping: scatter-add is hostile to the VPU; instead each (client-block ×
sample-block) tile builds a one-hot comparison matrix against a broadcasted
class iota and reduces with an MXU matmul: hist += onehot(labels)ᵀ·valid.
The sample axis is the sequential grid dimension; the (BB, C) accumulator
tile lives in the output VMEM block across iterations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(labels_ref, valid_ref, o_ref, *, num_classes, block_s):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    labels = labels_ref[...]                     # (BB, BS) int32
    valid = valid_ref[...]                       # (BB, BS) f32
    classes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, num_classes), 2)
    onehot = (labels[..., None] == classes).astype(jnp.float32)
    onehot = onehot * valid[..., None]
    o_ref[...] += onehot.sum(axis=1)             # (BB, C)


@functools.partial(jax.jit,
                   static_argnames=("num_classes", "block_b", "block_s",
                                    "interpret"))
def label_hist_kernel(labels: jax.Array, valid: jax.Array, num_classes: int,
                      block_b: int = 8, block_s: int = 512,
                      interpret: bool = False) -> jax.Array:
    """labels: (B, n) int32, valid: (B, n) bool → (B, C) f32."""
    b, n = labels.shape
    pad_b = (-b) % block_b
    pad_s = (-n) % block_s
    if pad_b or pad_s:
        labels = jnp.pad(labels, ((0, pad_b), (0, pad_s)), constant_values=-1)
        valid = jnp.pad(valid, ((0, pad_b), (0, pad_s)), constant_values=False)
    bb, nn = labels.shape
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_classes=num_classes,
                          block_s=block_s),
        grid=(bb // block_b, nn // block_s),
        in_specs=[
            pl.BlockSpec((block_b, block_s), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_s), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, num_classes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, num_classes), jnp.float32),
        interpret=interpret,
    )(labels, valid.astype(jnp.float32))
    return out[:b]
