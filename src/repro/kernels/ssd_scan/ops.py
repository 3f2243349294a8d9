"""jit'd wrapper exposing the model-layer SSD signature
(b, S, H, P) + per-head A, grouped B/C — flattens (b, H) → BH for the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ssd_scan import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_apply(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
              C: jax.Array, chunk: int = 128, interpret: bool = False):
    """x: (b, S, H, P); dt: (b, S, H); A: (H,); B/C: (b, S, G, N), G | H.
    Returns (y (b, S, H, P), final_state (b, H, P, N)) — matches
    repro.models.layers._ssd_chunked."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)

    def flat(t):  # (b, S, H, ...) → (b·H, S, ...)
        return jnp.moveaxis(t, 2, 1).reshape((b * h, s) + t.shape[3:])

    y, fin = ssd_scan(flat(x), flat(dt[..., None])[..., 0],
                      jnp.tile(A, b), flat(Bh), flat(Ch),
                      chunk=chunk, interpret=interpret)
    y = jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
    return y, fin.reshape(b, h, p, n)
