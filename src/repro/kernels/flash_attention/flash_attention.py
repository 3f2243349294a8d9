"""Blockwise flash attention (causal / sliding-window) with online softmax.

TPU mapping: grid (batch·heads, Sq/BQ, Sk/BK) with the key axis sequential;
running max/denominator and the output accumulator live in VMEM scratch across
key blocks (hardware-aligned BQ×BK tiles, MXU matmuls, f32 accumulation —
the HBM win is never materializing the (S×S) score matrix).  Fully-masked key
blocks (beyond the causal frontier or outside the window) are skipped via
``pl.when``, which is what makes the windowed variant sub-quadratic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, block_q, block_k, num_k_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # Block skip: the whole key block is in the future, or entirely before
    # the window of every query in this q block → no compute issued.
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live, (k_start + block_k - 1) > (q_start - window))

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)              # (BQ, D)
        k = k_ref[0].astype(jnp.float32)              # (BK, D)
        v = v_ref[0].astype(jnp.float32)              # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                            # (BQ, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q/k/v: (BH, S, D) → (BH, S, D).  window=0 → full causal."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad = (-s) % max(block_q, block_k)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nq, nk = sp // block_q, sp // block_k
    kern = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)
    out = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s]
