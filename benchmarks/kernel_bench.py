"""Pallas kernel microbenchmarks.

On a TPU the kernels compile and the us/call numbers time the chip.
Anywhere else they run in interpret mode, so the numbers time the Python
interpreter, not a device; every derived column names the mode.  The
derived column also reports the work size, so TPU projections can be made
from the roofline constants."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import (flash_attention, label_hist_kernel, ssd_scan,
                           weighted_agg_kernel)
from .common import emit, timeit_us

KEY = jax.random.PRNGKey(0)


def main(fast: bool = True) -> dict:
    interpret = jax.default_backend() != "tpu"
    mode = "interpret" if interpret else "tpu"
    rows = {}
    # weighted_agg: 30 clients × 1M params
    k, n = 30, (1 << 18 if fast else 1 << 20)
    stacked = jax.random.normal(KEY, (k, n), jnp.float32)
    scales = jnp.ones((k,)) / k
    us = timeit_us(lambda: weighted_agg_kernel(
        stacked, scales, interpret=interpret).block_until_ready(), n=3)
    rows["weighted_agg"] = us
    emit("kernel/weighted_agg", us, f"K={k} N={n} bytes={k * n * 4} {mode}")

    labels = jax.random.randint(KEY, (64, 1024), 0, 10)
    valid = jnp.ones((64, 1024), bool)
    us = timeit_us(lambda: label_hist_kernel(
        labels, valid, 10, interpret=interpret).block_until_ready(), n=3)
    rows["label_hist"] = us
    emit("kernel/label_hist", us, f"B=64 n=1024 C=10 {mode}")

    s, d = (256, 64) if fast else (1024, 128)
    q = jax.random.normal(KEY, (2, s, d))
    us = timeit_us(lambda: flash_attention(
        q, q, q, causal=True, interpret=interpret).block_until_ready(), n=2)
    rows["flash_attention"] = us
    emit("kernel/flash_attention", us, f"BH=2 S={s} D={d} causal {mode}")

    bh, ss, p, nn = 4, (256 if fast else 1024), 16, 32
    x = jax.random.normal(KEY, (bh, ss, p))
    dt = jax.nn.softplus(jax.random.normal(KEY, (bh, ss)))
    A = -jnp.ones((bh,))
    B = jax.random.normal(KEY, (bh, ss, nn)) * 0.5
    us = timeit_us(lambda: ssd_scan(
        x, dt, A, B, B, chunk=64, interpret=interpret)[0].block_until_ready(), n=2)
    rows["ssd_scan"] = us
    emit("kernel/ssd_scan", us, f"BH={bh} S={ss} P={p} N={nn} chunk=64 {mode}")
    return rows


if __name__ == "__main__":
    main()
