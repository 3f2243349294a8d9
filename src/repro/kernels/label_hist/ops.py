"""jit'd wrapper: histogram + the derived Algorithm-1 statistics in one call.

Always runs the Pallas kernel (``interpret=`` picks the interpreter); prefer
``repro.kernels.client_statistics`` — the backend-dispatched version
(``repro.kernels.dispatch``), which is what the package exports and what the
engines route through."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.label_stats import label_variance_normed
from .label_hist import label_hist_kernel


@functools.partial(jax.jit, static_argnames=("num_classes", "interpret"))
def client_statistics(labels: jax.Array, num_classes: int = 10,
                      interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(B, n) ragged labels (−1 pad) → (hists (B, C), σ²/n scores (B,))."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    hists = label_hist_kernel(safe, valid, num_classes, interpret=interpret)
    return hists, label_variance_normed(hists)
