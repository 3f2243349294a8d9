"""Chunked SSD (Mamba2 state-space duality) Pallas kernel.

TPU mapping (vs the CUDA selective-scan): the sequential scan is hoisted to
the *chunk* level — within a chunk the recurrence is re-expressed as a masked
quadratic form (two MXU matmuls: (C·Bᵀ∘L)·X and state in/out projections),
and only the (P×N) chunk-to-chunk state crosses grid steps, carried in VMEM
scratch across the sequential chunk axis.  chunk=128 aligns the MXU; the
decay matrices are built on the VPU from a cumulative log-decay vector.

Grid: (BH, S/Q) with the chunk axis sequential per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, state_scr,
                *, chunk):
    ci = pl.program_id(1)
    num_chunks = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (1, Q) block → take row
    dt = dt.reshape(-1)                        # (Q,)
    a = a_ref[0, 0]                            # scalar decay rate for this head
    b = b_ref[0].astype(jnp.float32)           # (Q, N)
    c = c_ref[0].astype(jnp.float32)           # (Q, N)

    da = dt * a                                # (Q,) log-decay per step
    cum = jnp.cumsum(da)                       # inclusive
    # Intra-chunk quadratic term: M[t, s] = exp(cum_t − cum_s)·(C_t·B_s)·dt_s, s ≤ t.
    seg = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay_mat = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    m = scores * decay_mat * dt[None, :]
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # Inter-chunk: y += (C ∘ exp(cum)) · state_inᵀ
    state_in = state_scr[...]                  # (P, N)
    c_dec = c * jnp.exp(cum)[:, None]
    y = y + jax.lax.dot_general(c_dec, state_in, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # State update: S ← exp(cum_Q)·S + Σ_s exp(cum_Q − cum_s)·dt_s·x_s⊗B_s.
    w = jnp.exp(cum[-1] - cum) * dt            # (Q,)
    xw = x * w[:, None]                        # (Q, P)
    s_new = jax.lax.dot_general(xw, b, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state_in * jnp.exp(cum[-1]) + s_new

    @pl.when(ci == num_chunks - 1)
    def _done():
        fin_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int = 128, interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S); A: (BH,); B/C: (BH, S, N).
    → (y (BH, S, P) f32, final_state (BH, P, N) f32).  S % chunk == 0."""
    bh, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    y, fin = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bh, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, chunk), lambda b, c_: (b, c_)),
            pl.BlockSpec((1, 1), lambda b, c_: (b, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c_: (b, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c_: (b, c_, 0)),
            pl.BlockSpec((1, p, n), lambda b, c_: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A[:, None], B, C)
    return y, fin
