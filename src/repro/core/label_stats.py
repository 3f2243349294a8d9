"""Per-client label statistics — the paper's §III/§IV measurement layer.

The paper treats class labels as *independent semantic entities*: before any
statistic is computed, the labels present in a client's multiset are remapped
to sequential ranks (``{1, 5, 10} ≡ {0, 1, 2}``, §III-A), so the statistics are
invariant to the numeric identity of the class ids.  Everything here consumes
**label histograms** ``h ∈ N^C`` (counts per class id), which is the quantity a
client can cheaply report to the server without revealing raw data — this is
exactly what Algorithm 1 transmits (a single scalar derived from it).

All functions are pure jnp, jit- and vmap-safe (fixed shapes, no host sync).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def histogram(labels: Array, num_classes: int, valid: Array | None = None) -> Array:
    """Histogram of integer ``labels`` over ``num_classes`` bins.

    ``valid`` optionally masks padding entries (FL clients have ragged n_i;
    we pad to a fixed length for SPMD and mask).

    Bincount-shaped accumulation: one ``(…, n)`` comparison pass per class,
    written into the ``(…, C)`` output column by column — the ``(…, n, C)``
    f32 one-hot the old formulation materialized never exists, so the
    per-round memory high-water mark is O(n) instead of O(n·C) per client.
    A scatter/segment-sum formulation is avoided on purpose: under ``vmap``
    over a trial grid it becomes a batched scatter.
    Counts are sums of {0, 1} (or 0/1 validity weights), so the result is
    bit-identical to the one-hot form (exact integer-valued f32 arithmetic;
    pinned by tests/test_compute_dispatch.py).  Out-of-range labels (−1
    padding) match no class and are dropped, exactly as one_hot dropped them.
    The tiled Pallas version of the same op is kernels/label_hist; the
    backend dispatch layer (repro.kernels.dispatch) picks between them.
    """
    labels = labels.astype(jnp.int32)
    weights = (jnp.ones(labels.shape, jnp.float32) if valid is None
               else valid.astype(jnp.float32))

    def count_class(c, out):
        count_c = jnp.where(labels == c, weights, 0.0).sum(axis=-1)
        return jax.lax.dynamic_update_index_in_dim(out, count_c, c, -1)

    init = jnp.zeros(labels.shape[:-1] + (num_classes,), jnp.float32)
    return jax.lax.fori_loop(0, num_classes, count_class, init)


def rank_remap_values(hist: Array) -> Array:
    """Sequential rank of each present class (absent classes get rank 0).

    Paper §III-A: ``L = {1, 5, 10}`` is treated as ``{0, 1, 2}``; the rank is
    the statistic-bearing "value" of each label.
    """
    present = (hist > 0).astype(jnp.float32)
    ranks = jnp.cumsum(present, axis=-1) - 1.0
    return ranks * present  # absent bins don't matter (zero count) but keep them finite


def label_variance(hist: Array) -> Array:
    """σ²(L_i) of the rank-remapped label multiset (paper's selection statistic).

    A single-label client has σ² = 0 (Algorithm 1 filters these out); a client
    whose histogram is uniform over many classes maximizes σ².
    """
    hist = hist.astype(jnp.float32)
    n = jnp.maximum(hist.sum(axis=-1), 1.0)
    v = rank_remap_values(hist)
    mean = (hist * v).sum(axis=-1) / n
    var = (hist * (v - mean[..., None]) ** 2).sum(axis=-1) / n
    return var


def label_variance_normed(hist: Array) -> Array:
    """Paper Eq. (3) score: σ²(L_i) / n_i — variance adjusted by client size."""
    n = jnp.maximum(hist.sum(axis=-1).astype(jnp.float32), 1.0)
    return label_variance(hist) / n


def coverage(hist: Array) -> Array:
    """Number of distinct labels present, n(ℒ_i) — the cluster-area rank key."""
    return (hist > 0).sum(axis=-1).astype(jnp.int32)


def empirical_pdf(hist: Array, eps: float = 1e-9) -> Array:
    """p(L_i): normalized histogram with ε-smoothing (KL needs full support)."""
    hist = hist.astype(jnp.float32) + eps
    return hist / hist.sum(axis=-1, keepdims=True)


def expected_coverage_per_round(hists: Array) -> Array:
    """Union label coverage of a *set* of clients: n(∪_i ℒ_i) (paper §III-B:
    trainability tracks the per-round union coverage, not per-client)."""
    any_present = (hists > 0).any(axis=-2)
    return any_present.sum(axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Block-reducible (partial) statistics — the population-scale contract.
#
# A statistic is BLOCK-REDUCIBLE when the value over N clients equals a
# merge of values over any disjoint block partition.  The hierarchical
# engine (repro.fl.population) streams client blocks through a lax.scan and
# only ever carries these partials, so per-shard memory stays flat in N:
# the dense (N, C) histogram matrix is never materialized.  Histogram sums
# are sums of exact integer-valued f32 counts, so the merge is BIT-IDENTICAL
# to the dense computation (pinned by tests/test_population.py).
# ---------------------------------------------------------------------------

def partial_label_statistics(hists: Array) -> dict:
    """One block's reducible label statistics from its (B, C) histograms.

    Returns ``{"hist_sum": (C,) f32, "n_valid": f32 scalar,
    "present": (C,) bool}`` — the per-class count partial sum, the number of
    clients with a non-empty histogram, and the per-class presence union
    (``present.sum()`` is §III-B's union coverage n(∪ℒ), the q term of the
    area index — itself block-reducible via OR)."""
    hists = hists.astype(jnp.float32)
    return {"hist_sum": hists.sum(axis=-2),
            "n_valid": (hists.sum(axis=-1) > 0).sum().astype(jnp.float32),
            "present": (hists > 0).any(axis=-2)}


def merge_label_statistics(a: dict, b: dict) -> dict:
    """Merge two :func:`partial_label_statistics` dicts (associative +
    commutative: sum / sum / union), so any block partition reduces to the
    same global statistics as one dense pass."""
    return {"hist_sum": a["hist_sum"] + b["hist_sum"],
            "n_valid": a["n_valid"] + b["n_valid"],
            "present": a["present"] | b["present"]}
