"""Backend-dispatched compute for the FL round hot path.

The paper's server does exactly two heavy non-training ops per round —
per-client label histograms (the statistics every selection strategy ranks
on) and the masked weighted mean of local models (FedAvg Eq. 1) — and the
repo carries validated Pallas kernels for both (kernels/label_hist,
kernels/weighted_agg).  This module is the trace-time switch that decides,
per call, whether those ops lower to the Pallas kernels or to the pure-XLA
references, so every engine (compiled sim grid, host parity oracle, sharded
SPMD round) compiles the right implementation for the platform it runs on:

* ``tpu`` — the Pallas kernels (``label_hist_kernel``,
  ``weighted_agg_kernel``) with ``interpret=False``: tiled VMEM BlockSpecs,
  MXU-shaped contractions, the param stream read once from HBM.
* ``cpu`` / ``gpu`` — the XLA references
  (``repro.core.label_stats.histogram``,
  ``repro.core.aggregation.masked_mean``).  On CPU, Pallas TPU custom-calls
  do not compile, and the references ARE the numerics the host≡sim≡sharded
  parity pins are defined over — the CPU path of every engine is
  bit-identical to the pre-dispatch code by construction.  GPU also takes
  the references: the kernels' accumulator patterns are TPU-shaped (the
  histogram revisits its output tile across the *sequential* sample-block
  grid axis, which races under a parallel Triton grid, and the (1×K)
  matvec sits below Triton's minimum dot tile) — extend
  ``_PALLAS_PLATFORMS`` only together with GPU-safe kernel forms.

The decision is made at TRACE time (``jax.default_backend()`` is a Python
value), so the dispatch itself costs nothing inside ``jit``/``vmap``/
``lax.scan``/``shard_map`` — each compiled program contains exactly one
implementation.

Backend override — for tests and measurement:

* ``backend=`` accepts ``"auto"`` (default), ``"reference"``, ``"pallas"``,
  or ``"pallas_interpret"``;
* the ``REPRO_COMPUTE_BACKEND`` env var overrides ``"auto"`` resolution
  process-wide (read at trace time), which is how the interpret-mode
  bit-identity tests drive the Pallas path through a full engine on CPU;
* forcing ``"pallas"`` off-TPU raises: the kernels cannot lower to CPU/GPU
  (see the platform note above), and running them in the interpreter
  instead would hide that the accelerator is missing.  Ask for
  ``"pallas_interpret"`` to run the kernel bodies on the CPU.

Numerics contract (pinned by tests/test_compute_dispatch.py):

* ``client_histograms`` — Pallas ≡ reference BIT-IDENTICAL: both are sums of
  0/1 validity weights (exact integer-valued f32 arithmetic), so selection
  decisions cannot depend on the backend.
* ``masked_weighted_mean`` / ``weighted_sum_tree`` — Pallas ≡ reference to
  float32 ulp tolerance: the kernel reduces clients with an MXU dot while
  the reference broadcasts-multiplies-then-sums, and XLA's dot accumulation
  order (blocked FMA) differs from an elementwise reduce at the last bit.
  Bit-identity across that pair is structurally unattainable; what IS pinned
  bit-for-bit is the CPU engine path (reference ≡ the pre-dispatch engines).
"""
from __future__ import annotations

import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import masked_mean
from repro.core.label_stats import histogram, label_variance_normed

# The Pallas kernel modules load lazily, on the first call that actually
# takes the pallas branch: this module sits on the import path of every
# engine and the data layer (repro.data.fl_data), and a CPU-only process
# resolving to the reference backend should not pay for (or depend on)
# jax.experimental.pallas imports it never uses.

Array = jax.Array
PyTree = Any

BACKENDS = ("auto", "reference", "pallas", "pallas_interpret")
ENV_VAR = "REPRO_COMPUTE_BACKEND"
# TPU only: the kernels' sequential-grid accumulators and sub-tile matvec
# are not GPU-safe (see module docstring) — GPU resolves to the references.
_PALLAS_PLATFORMS = ("tpu",)


def compute_backend(backend: str = "auto") -> str:
    """Resolve ``backend`` to a concrete implementation name at trace time.

    ``"auto"`` → the ``REPRO_COMPUTE_BACKEND`` env var if set, else
    ``"pallas"`` on TPU and ``"reference"`` elsewhere.  Returns one of
    ``"reference"`` / ``"pallas"`` / ``"pallas_interpret"``."""
    if backend == "auto":
        backend = os.environ.get(ENV_VAR, "auto") or "auto"
    if backend not in BACKENDS:
        raise ValueError(f"compute backend must be one of {BACKENDS}; "
                         f"got {backend!r}")
    if backend == "auto":
        return ("pallas" if jax.default_backend() in _PALLAS_PLATFORMS
                else "reference")
    return backend


def _interpret(backend: str) -> bool:
    """``interpret=`` for a resolved Pallas backend: True only when the
    caller asked for ``"pallas_interpret"``.  A forced ``"pallas"`` on a
    platform the kernels do not compile for is an error, never a silent
    switch to the interpreter."""
    if backend == "pallas_interpret":
        return True
    platform = jax.default_backend()
    if platform not in _PALLAS_PLATFORMS:
        raise RuntimeError(
            f"compute backend 'pallas' needs one of {_PALLAS_PLATFORMS}, but "
            f"JAX's default backend is {platform!r}; use 'pallas_interpret' "
            "to run the kernels in the interpreter")
    return False


# ---------------------------------------------------------------------------
# Histogram + selection statistics
# ---------------------------------------------------------------------------

def client_histograms(labels: Array, num_classes: int,
                      valid: Optional[Array] = None, *,
                      backend: str = "auto") -> Array:
    """Per-client label histograms: (…, n) int labels → (…, C) f32 counts.

    Out-of-range labels (−1 padding) count toward no bin; ``valid``
    optionally masks entries on top of that.  Pallas path: the tiled
    MXU-friendly ``label_hist_kernel`` over the flattened client axis;
    reference path: the bincount-shaped ``repro.core.histogram`` (which
    never materializes the one-hot either).  Both produce bit-identical
    counts."""
    b = compute_backend(backend)
    if b == "reference":
        return histogram(labels, num_classes, valid)
    from .label_hist.label_hist import label_hist_kernel
    labels = jnp.asarray(labels, jnp.int32)
    lead = labels.shape[:-1]
    n = labels.shape[-1]
    v = (labels >= 0) if valid is None else jnp.asarray(valid, bool)
    v = jnp.broadcast_to(v, labels.shape)
    out = label_hist_kernel(labels.reshape(-1, n), v.reshape(-1, n),
                            num_classes, interpret=_interpret(b))
    return out.reshape(lead + (num_classes,))


def client_statistics(labels: Array, num_classes: int,
                      valid: Optional[Array] = None, *,
                      backend: str = "auto") -> Tuple[Array, Array]:
    """Fused histogram + Algorithm-1 score: → (hists (…, C), σ²/n (…,))."""
    hists = client_histograms(labels, num_classes, valid, backend=backend)
    return hists, label_variance_normed(hists)


# ---------------------------------------------------------------------------
# Masked weighted aggregation (FedAvg / FedSGD reduction over clients)
# ---------------------------------------------------------------------------

def _fused_leaf_sum(leaf: Array, w: Array, interpret: bool) -> Array:
    """Σ_k w_k · leaf_k over the leading client axis, kernel-fused: the
    reduction is a (1×K)·(K×BN) MXU matvec per VMEM tile and the param
    stream is read exactly once from HBM.  f32 accumulate, f32 out."""
    from .weighted_agg.weighted_agg import weighted_agg_kernel
    k = leaf.shape[0]
    flat = leaf.reshape(k, -1)
    out = weighted_agg_kernel(flat.astype(jnp.float32), w,
                              interpret=interpret)
    return out.reshape(leaf.shape[1:])


def masked_weighted_mean(stacked: PyTree, mask: Array,
                         weights: Optional[Array] = None, *,
                         backend: str = "auto") -> PyTree:
    """Weighted mean over the leading (client) axis restricted to ``mask`` —
    the FedAvg/FedSGD server reduction (drop-in for
    ``repro.core.aggregation.masked_mean``; identical signature/semantics).

    Reference path IS ``masked_mean`` (the parity-pinned engine numerics);
    Pallas path fuses each leaf's reduction into ``weighted_agg_kernel`` and
    finishes the ÷Σw mean in f32, preserving ``masked_mean``'s
    ε-denominator count=0 degradation."""
    b = compute_backend(backend)
    if b == "reference":
        return masked_mean(stacked, mask, weights)
    w = mask.astype(jnp.float32)
    if weights is not None:
        w = w * weights.astype(jnp.float32)
    denom = jnp.maximum(w.sum(), 1e-12)
    interp = _interpret(b)
    return jax.tree_util.tree_map(
        lambda p: (_fused_leaf_sum(p, w, interp) / denom).astype(p.dtype),
        stacked)


def weighted_sum_tree(tree: PyTree, weights: Array, *,
                      backend: str = "auto") -> PyTree:
    """Σ_k w_k · x_k over every leaf's leading axis (NO normalization) — the
    in-shard half of the sharded round's weighted-delta scatter
    (``psum_weighted_mean`` psums this then divides, finishing in f32).
    Every leaf keeps ITS OWN dtype on both paths — that is what keeps a
    bf16 ``agg_dtype`` delta tree's cross-client psum at half bytes — the
    paths differ only in accumulation: the reference reduces in leaf dtype
    (exactly the pre-dispatch inline form, bit-identical), the Pallas
    kernel accumulates in f32 and casts back."""
    b = compute_backend(backend)
    w = weights.astype(jnp.float32)
    if b == "reference":
        return jax.tree_util.tree_map(
            lambda x: (w.reshape(w.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
                       * x).sum(axis=0),
            tree)
    interp = _interpret(b)
    return jax.tree_util.tree_map(
        lambda x: _fused_leaf_sum(x, w, interp).astype(x.dtype), tree)
