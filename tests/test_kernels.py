"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Every call passes ``interpret=True`` (the kernel bodies execute in Python on
the CPU); the entry points default to compiling for the TPU.  The chip
compiles of the main-path kernels live in tests/test_tpu_compile.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # interpret-mode kernel sweeps; see conftest.py

from repro.kernels import (aggregate_params, attention_ref, client_statistics,
                           flash_attention, gqa_flash_attention,
                           label_hist_kernel, label_hist_ref, ssd_apply,
                           ssd_ref, ssd_scan, weighted_agg_kernel,
                           weighted_agg_ref)

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestWeightedAgg:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("k,n", [(4, 64), (30, 1000), (8, 4096), (3, 7)])
    def test_matches_ref(self, k, n, dtype):
        ks = jax.random.split(KEY, 3)
        stacked = jax.random.normal(ks[0], (k, n), jnp.float32).astype(dtype)
        weights = jax.random.uniform(ks[1], (k,), minval=0.5, maxval=2.0)
        mask = (jax.random.uniform(ks[2], (k,)) > 0.4).astype(jnp.float32)
        mask = mask.at[0].set(1.0)  # at least one selected
        w = weights * mask
        scales = w / w.sum()
        got = weighted_agg_kernel(stacked, scales, block_n=256,
                                  interpret=True)
        want = weighted_agg_ref(stacked, weights, mask)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    def test_pytree_wrapper(self):
        stacked = {"a": jax.random.normal(KEY, (5, 8, 4)),
                   "b": jax.random.normal(KEY, (5, 3))}
        weights = jnp.ones(5)
        mask = jnp.array([1.0, 1, 0, 0, 1])
        got = aggregate_params(stacked, weights, mask, interpret=True)
        want = jax.tree_util.tree_map(
            lambda s: weighted_agg_ref(s.reshape(5, -1), weights, mask
                                       ).reshape(s.shape[1:]), stacked)
        for ka in ("a", "b"):
            np.testing.assert_allclose(np.asarray(got[ka]), np.asarray(want[ka]),
                                       rtol=1e-5, atol=1e-5)


class TestLabelHist:
    @pytest.mark.parametrize("b,n,c", [(4, 100, 10), (30, 290, 10),
                                       (7, 33, 5), (16, 1024, 32)])
    def test_matches_ref(self, b, n, c):
        labels = jax.random.randint(KEY, (b, n), 0, c)
        valid = jax.random.uniform(jax.random.PRNGKey(1), (b, n)) > 0.2
        got = label_hist_kernel(labels, valid, c, block_b=4, block_s=64,
                                interpret=True)
        want = label_hist_ref(labels, c, valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_client_statistics_end_to_end(self):
        # the dispatch version; force the kernel path (this is a kernel test)
        labels = jnp.array([[0, 1, 2, -1], [3, 3, 3, 3]])
        hists, scores = client_statistics(labels, num_classes=5,
                                          backend="pallas_interpret")
        assert float(hists[0].sum()) == 3 and float(hists[1].sum()) == 4
        assert float(scores[0]) > 0 and float(scores[1]) == 0  # σ²=0 single label


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,d,bq,bk", [(64, 32, 16, 16), (128, 64, 32, 64),
                                           (96, 16, 32, 32)])
    def test_causal_matches_ref(self, s, d, bq, bk, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, s, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (2, s, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (2, s, d), jnp.float32).astype(dtype)
        got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    @pytest.mark.parametrize("window", [16, 32, 48])
    def test_sliding_window(self, window):
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (1, 128, 32)) for kk in ks)
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
        want = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_wrapper_matches_model_layer(self):
        from repro.models import layers as L
        ks = jax.random.split(KEY, 3)
        b, s, h, kv, d = 2, 64, 4, 2, 32
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, kv, d))
        v = jax.random.normal(ks[2], (b, s, kv, d))
        got = gqa_flash_attention(q, k, v, causal=True, interpret=True)
        want = L._sdpa(q, k, v, L.causal_mask(s, s), kv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_unaligned_seq_padding(self):
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (1, 50, 16)) for kk in ks)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("s,chunk,p,n", [(64, 16, 8, 16), (128, 32, 16, 8),
                                             (32, 32, 4, 4)])
    def test_matches_sequential_ref(self, s, chunk, p, n):
        bh = 3
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (bh, s, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s)))
        A = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.3)
        B = jax.random.normal(ks[3], (bh, s, n)) * 0.5
        C = jax.random.normal(ks[4], (bh, s, n)) * 0.5
        y, fin = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
        y_ref, fin_ref = ssd_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_ops_wrapper_matches_model_ssd(self):
        """Kernel == the model's XLA chunked SSD (grouped B/C, (b,S,H,P))."""
        from repro.models.layers import _ssd_chunked
        b, s, h, g, p, n = 2, 64, 4, 2, 8, 16
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
        y_k, fin_k = ssd_apply(x, dt, A, B, C, chunk=16, interpret=True)
        y_m, fin_m = _ssd_chunked(x, dt, A, B, C, chunk=16)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fin_k), np.asarray(fin_m),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16])
    def test_bf16_inputs(self, dtype):
        bh, s, p, n = 2, 32, 8, 8
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (bh, s, p)).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s))).astype(dtype)
        A = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.3)
        B = (jax.random.normal(ks[3], (bh, s, n)) * 0.5).astype(dtype)
        C = (jax.random.normal(ks[4], (bh, s, n)) * 0.5).astype(dtype)
        y, _ = ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
        y_ref, _ = ssd_ref(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=5e-2, atol=5e-2)
