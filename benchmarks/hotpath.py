"""BENCH_hotpath: the FL round's non-training server ops, old vs fused forms.

Two ops dominate every engine's per-round server cost and both now route
through the backend compute dispatch (repro.kernels.dispatch):

* **histogram** — the old reference materialized an ``(N, n, C)`` f32
  one-hot per round; the new bincount-shaped reference
  (repro.core.label_stats.histogram) does one comparison pass per class and
  never builds it.  Timed head-to-head on engine-shaped inputs: the vmapped
  trial grid (what the compiled sim engine runs per scan step) and
  fleet-scale single batches (what the sharded round runs in-shard).
* **aggregation** — the per-leaf tree-map ``masked_mean`` versus (a) the
  SHIPPED dispatch layout: one flattened ``(K, P_leaf)`` matvec per leaf,
  exactly what ``masked_weighted_mean``'s pallas path lowers per leaf (XLA
  stands in for the kernel — Pallas interpret-mode timings measure the
  Python interpreter, not the op), and (b) the single-matrix form over the
  whole concatenated ``(K, P)`` tree — the fusion CEILING, reported for
  context but not what ships.  The kernel itself is still run once for a
  correctness cross-check (compiled on a TPU, interpreted elsewhere).

Every timed program also records its ``compile_s`` (lower+compile, AOT) —
the uniform key all BENCH_*.json reports now carry.

Output: ``BENCH_hotpath.json`` at the repo root + the usual CSV lines.
"""
from __future__ import annotations

import os
import time

import numpy as np

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "BENCH_hotpath.json")

# (tag, leading_shape, n_samples, num_classes): the one-hot buffer the old
# form materialized is prod(leading)·n·C f32 — the "fleet" rows are the
# shapes the ROADMAP's fleet-scale framing cares about, "paper_grid" is the
# compiled Table-I grid's per-scan-step shape (vmapped over 105 trials).
HIST_SHAPES = (
    ("paper_grid_vmap105", (105, 100), 290, 10),
    ("fleet_512c", (512,), 2048, 32),
    ("fleet_wide_256c", (256,), 1024, 256),
)

AGG_CLIENTS = 32


def _one_hot_hist(labels, valid, num_classes):
    """The OLD reference (pre-dispatch): materializes the (…, n, C) one-hot."""
    import jax
    import jax.numpy as jnp
    one_hot = jax.nn.one_hot(labels.astype(jnp.int32), num_classes,
                             dtype=jnp.float32)
    one_hot = one_hot * valid.astype(jnp.float32)[..., None]
    return one_hot.sum(axis=-2)


def _timed(fn, *args, reps: int, name: str):
    """AOT lower+compile (compile_s) then steady-state us/call."""
    import jax
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, compile_s, compiled


def _bench_hist(fast: bool) -> list:
    import jax
    import jax.numpy as jnp

    from repro.core.label_stats import histogram

    reps = 10 if fast else 30
    rows = []
    for tag, lead, n, num_classes in HIST_SHAPES:
        key = jax.random.PRNGKey(0)
        labels = jax.random.randint(key, lead + (n,), -1, num_classes,
                                    dtype=jnp.int32)
        valid = labels >= 0

        def ref(l, v):
            return histogram(l, num_classes, v)

        def old(l, v):
            return _one_hot_hist(l, v, num_classes)

        ref_us, ref_c, ref_fn = _timed(ref, labels, valid, reps=reps,
                                       name=f"hist_ref_{tag}")
        old_us, old_c, old_fn = _timed(old, labels, valid, reps=reps,
                                       name=f"hist_onehot_{tag}")
        assert np.array_equal(np.asarray(ref_fn(labels, valid)),
                              np.asarray(old_fn(labels, valid))), tag
        onehot_mb = (np.prod(lead) * n * num_classes * 4) / 2**20
        rows.append({
            "shape": tag, "clients": int(np.prod(lead)), "samples": n,
            "classes": num_classes,
            "one_hot_buffer_mb": round(float(onehot_mb), 1),
            "one_hot_us": old_us, "one_hot_compile_s": old_c,
            "reference_us": ref_us, "reference_compile_s": ref_c,
            "speedup": old_us / ref_us,
        })
    return rows


def _agg_tree(key):
    """A stacked client-param tree shaped like the paper CNN's scale."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 6)
    k = AGG_CLIENTS
    return {
        "conv1": jax.random.normal(ks[0], (k, 3, 3, 1, 32), jnp.float32),
        "conv2": jax.random.normal(ks[1], (k, 3, 3, 32, 64), jnp.float32),
        "conv3": jax.random.normal(ks[2], (k, 3, 3, 64, 64), jnp.float32),
        "dense_w": jax.random.normal(ks[3], (k, 1024, 128), jnp.float32),
        "head_w": jax.random.normal(ks[4], (k, 128, 10), jnp.float32),
        "biases": jax.random.normal(ks[5], (k, 298), jnp.float32),
    }


def _bench_agg(fast: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.aggregation import masked_mean
    from repro.kernels import weighted_agg_kernel

    reps = 20 if fast else 60
    key = jax.random.PRNGKey(1)
    tree = _agg_tree(key)
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (AGG_CLIENTS,),
                                 minval=0.5, maxval=2.0)
    mask = (jax.random.uniform(jax.random.fold_in(key, 2),
                               (AGG_CLIENTS,)) > 0.4).astype(jnp.float32)
    mask = mask.at[0].set(1.0)
    param_bytes = sum(int(np.prod(l.shape[1:])) * 4
                      for l in jax.tree_util.tree_leaves(tree))

    def treemap(t, m, w):
        return masked_mean(t, m, w)

    # The SHIPPED dispatch layout (masked_weighted_mean's pallas path in XLA
    # form): normalize once, then ONE (1,K)·(K,P_leaf) matvec per flattened
    # leaf — per-leaf kernel launches, no cross-leaf concatenation.
    def per_leaf(t, m, w):
        s = (w * m) / jnp.maximum((w * m).sum(), 1e-12)
        return jax.tree_util.tree_map(
            lambda l: (s[None, :] @ l.reshape(AGG_CLIENTS, -1)
                       ).reshape(l.shape[1:]), t)

    # The fusion CEILING: the whole tree as ONE (K, P) matrix, clients
    # reduced by a single matvec — what a cross-leaf-fused kernel could
    # reach; reported for context, no shipped path implements it.
    flat = jnp.concatenate(
        [l.reshape(AGG_CLIENTS, -1) for l in jax.tree_util.tree_leaves(tree)],
        axis=1)

    def single_matrix(f, m, w):
        s = (w * m) / jnp.maximum((w * m).sum(), 1e-12)
        return s[None, :] @ f

    tm_us, tm_c, _ = _timed(treemap, tree, mask, weights, reps=reps,
                            name="agg_treemap")
    pl_us, pl_c, _ = _timed(per_leaf, tree, mask, weights, reps=reps,
                            name="agg_per_leaf")
    sm_us, sm_c, sm_fn = _timed(single_matrix, flat, mask, weights,
                                reps=reps, name="agg_single_matrix")

    # Correctness cross-check of the Pallas kernel, compiled on a TPU and
    # interpreted elsewhere (not timed: interpret mode would measure the
    # Python interpreter): fused XLA ≡ kernel ≈ 1 ulp.
    interpret = jax.default_backend() != "tpu"
    s = (weights * mask) / jnp.maximum((weights * mask).sum(), 1e-12)
    kern = np.asarray(weighted_agg_kernel(flat, s, interpret=interpret))
    np.testing.assert_allclose(kern,
                               np.asarray(sm_fn(flat, mask, weights))[0],
                               rtol=3e-6, atol=3e-6)

    return {
        "clients": AGG_CLIENTS,
        "param_bytes_per_client": param_bytes,
        "treemap_us": tm_us, "treemap_compile_s": tm_c,
        "per_leaf_fused_us": pl_us, "per_leaf_fused_compile_s": pl_c,
        "per_leaf_fused_speedup": tm_us / pl_us,   # the SHIPPED layout
        "single_matrix_us": sm_us, "single_matrix_compile_s": sm_c,
        "single_matrix_speedup": tm_us / sm_us,    # fusion ceiling, unshipped
        "pallas_checked": "interpret" if interpret else "tpu",
    }


def main(fast: bool = True) -> dict:
    import jax

    from .common import emit, write_report

    cache = jax.config.jax_compilation_cache_dir
    t0 = time.perf_counter()
    hist_rows = _bench_hist(fast)
    agg = _bench_agg(fast)
    report = {
        "config": {"fast": fast, "compile_cache": cache},
        "histogram": hist_rows,
        "aggregation": agg,
        "compile_s": sum(r["one_hot_compile_s"] + r["reference_compile_s"]
                         for r in hist_rows)
        + agg["treemap_compile_s"] + agg["per_leaf_fused_compile_s"]
        + agg["single_matrix_compile_s"],
        "wall_s": time.perf_counter() - t0,
    }
    write_report(OUT_PATH, report)

    for r in hist_rows:
        emit(f"hotpath/hist_{r['shape']}_reference", r["reference_us"],
             f"one_hot={r['one_hot_us']:.0f}us speedup={r['speedup']:.2f}x "
             f"buffer_avoided={r['one_hot_buffer_mb']}MB")
    emit("hotpath/agg_per_leaf_fused", agg["per_leaf_fused_us"],
         f"treemap={agg['treemap_us']:.0f}us "
         f"speedup={agg['per_leaf_fused_speedup']:.2f}x (shipped layout)")
    emit("hotpath/agg_single_matrix", agg["single_matrix_us"],
         f"speedup={agg['single_matrix_speedup']:.2f}x "
         "(fusion ceiling, unshipped)")
    print(f"# -> {OUT_PATH}")
    return report


if __name__ == "__main__":
    main()
