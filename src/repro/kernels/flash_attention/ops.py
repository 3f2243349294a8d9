"""jit'd wrapper exposing the model-layer attention signature
(B, S, H, D)×(B, S, KV, D) with GQA head repetition."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def gqa_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, S, KV, D) → (B, S, H, D)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    out = flash_attention(flat(q), flat(kr), flat(vr), causal=causal,
                          window=window, interpret=interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
