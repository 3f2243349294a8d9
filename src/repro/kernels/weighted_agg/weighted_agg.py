"""Fused FL-aggregation kernel: y = Σ_k s_k · θ_k over K stacked client
parameter blocks (s = normalized mask·weight, precomputed in ops.py).

TPU mapping: the reduction over clients is a (1×K)·(K×BN) matvec per tile —
MXU-friendly — and the param stream is read exactly once from HBM (the fused
form's point: FedAvg aggregation is pure memory traffic; K separate
mul-adds would re-stream the output K times).  BlockSpec tiles the flattened
parameter axis in VMEM-sized chunks; the client axis stays resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _agg_kernel(s_ref, theta_ref, o_ref):
    # s: (1, K) f32; theta: (K, BN); o: (1, BN)
    s = s_ref[...]
    theta = theta_ref[...].astype(jnp.float32)
    # HIGHEST: Mosaic's default f32 contraction rounds the operands to bf16
    # on the MXU — on a v5e that put the FedAvg mean of 30 trained CNN
    # clients 1.8e-3 off the f32 reference; the f32 contraction matches it
    # to the ulp.
    o_ref[...] = jnp.dot(s, theta, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weighted_agg_kernel(stacked: jax.Array, scales: jax.Array,
                        block_n: int = 2048, interpret: bool = False) -> jax.Array:
    """stacked: (K, N); scales: (K,) f32 (already normalized).  → (N,)."""
    k, n = stacked.shape
    pad = (-n) % block_n
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    npad = n + pad
    out = pl.pallas_call(
        _agg_kernel,
        grid=(npad // block_n,),
        in_specs=[
            pl.BlockSpec((1, k), lambda j: (0, 0)),
            pl.BlockSpec((k, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npad), stacked.dtype),
        interpret=interpret,
    )(scales.astype(jnp.float32)[None], stacked)
    return out[0, :n]
