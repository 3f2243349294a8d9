"""jit'd public wrapper: masked weighted FedAvg aggregation of a pytree of
stacked client params, kernel-fused per leaf."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .weighted_agg import weighted_agg_kernel

PyTree = Any


def normalized_scales(weights: jax.Array, mask: jax.Array) -> jax.Array:
    w = (weights * mask).astype(jnp.float32)
    return w / jnp.maximum(w.sum(), 1e-12)


@functools.partial(jax.jit, static_argnames=("interpret",))
def aggregate_params(stacked_params: PyTree, weights: jax.Array,
                     mask: jax.Array, interpret: bool = False) -> PyTree:
    """FedAvg over the leading client axis of every leaf, Pallas-fused."""
    scales = normalized_scales(weights, mask)

    def one(leaf):
        k = leaf.shape[0]
        flat = leaf.reshape(k, -1)
        out = weighted_agg_kernel(flat, scales, interpret=interpret)
        return out.reshape(leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, stacked_params)
