"""Unit tests for model building blocks against naive oracles."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # SSD/attention oracles, ~1 min; see conftest.py

from repro.models import layers as L
from repro.models.config import ModelConfig

KEY = jax.random.PRNGKey(0)


def small_cfg(**kw):
    base = dict(name="t", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                dtype="float32", fsdp=False, remat=False, scan_layers=False)
    base.update(kw)
    return ModelConfig(**base)


class TestAttention:
    def test_gqa_equals_naive(self):
        """Grouped SDPA == repeating KV heads then vanilla MHA."""
        cfg = small_cfg()
        b, s, h, kv, hd = 2, 8, 4, 2, 16
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = jax.random.normal(k1, (b, s, h, hd))
        k = jax.random.normal(k2, (b, s, kv, hd))
        v = jax.random.normal(k3, (b, s, kv, hd))
        mask = L.causal_mask(s, s)
        got = L._sdpa(q, k, v, mask, kv)
        kr = jnp.repeat(k, h // kv, axis=2)
        vr = jnp.repeat(v, h // kv, axis=2)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, kr) / math.sqrt(hd)
        scores = scores + mask
        want = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), vr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_causal_mask_window(self):
        m = np.asarray(L.causal_mask(4, 4, window=2))[0, 0]
        assert m[2, 2] == 0 and m[2, 1] == 0
        assert m[2, 0] < -1e20      # outside window
        assert m[1, 3] < -1e20      # future

    def test_rope_relative_shift(self):
        """RoPE inner products depend only on relative distance."""
        cfg = small_cfg()
        x = jax.random.normal(KEY, (1, 6, 1, 32))
        p0 = jnp.arange(6)[None]
        r0 = L.rope(x, p0, 10_000.0)
        r7 = L.rope(x, p0 + 7, 10_000.0)
        dot0 = jnp.einsum("bshd,bthd->st", r0, r0)
        dot7 = jnp.einsum("bshd,bthd->st", r7, r7)
        np.testing.assert_allclose(np.asarray(dot0), np.asarray(dot7), atol=1e-4)

    def test_decode_ring_buffer_eviction(self):
        """After W+k decode steps the ring cache holds exactly the last W keys."""
        cfg = small_cfg(sliding_window=4)
        p, _ = L.attention_init(KEY, cfg)
        cache = L.init_kv_cache(cfg, 1, 4)
        xs = jax.random.normal(KEY, (1, 7, cfg.d_model))
        outs = []
        for t in range(7):
            y, cache = L.attention_apply(p, xs[:, t:t + 1], cfg, mode="decode",
                                         cache=cache, window=4)
            outs.append(y)
        assert int(cache["idx"]) == 7
        # replay: full windowed forward's last position must match last decode
        y_full, _ = L.attention_apply(p, xs, cfg, mode="train", window=4)
        np.testing.assert_allclose(np.asarray(outs[-1][:, 0]),
                                   np.asarray(y_full[:, -1]), atol=1e-4)


class TestMoE:
    @pytest.mark.parametrize("e,k", [(4, 1), (4, 2), (8, 4)])
    def test_matches_per_token_oracle(self, e, k):
        """With ample capacity, sort-based dispatch == dense per-token mixture."""
        cfg = small_cfg(num_experts=e, experts_per_token=k, moe_d_ff=32,
                        capacity_factor=8.0)
        p, _ = L.moe_init(KEY, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
        got, aux = L.moe_apply(p, x, cfg)

        # oracle: per-token dense mixture over its top-k experts
        t = x.reshape(-1, cfg.d_model)
        logits = t @ p["router"]
        probs = jax.nn.softmax(logits, -1)
        gv, gi = jax.lax.top_k(probs, k)
        gv = gv / gv.sum(-1, keepdims=True)
        want = np.zeros_like(t)
        for ti in range(t.shape[0]):
            acc = 0
            for j in range(k):
                eidx = int(gi[ti, j])
                g = jax.nn.silu(t[ti] @ p["w_gate"][eidx]) * (t[ti] @ p["w_up"][eidx])
                acc = acc + float(gv[ti, j]) * (g @ p["w2"][eidx])
            want[ti] = acc
        np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.d_model),
                                   want, rtol=2e-4, atol=2e-4)
        assert float(aux) > 0

    def test_capacity_drops_overflow(self):
        """With capacity 8 (minimum) and 64 tokens routed to 1 hot expert,
        most contributions are dropped, not mis-routed."""
        cfg = small_cfg(num_experts=4, experts_per_token=1, moe_d_ff=32,
                        capacity_factor=0.25)
        p, _ = L.moe_init(KEY, cfg)
        # Bias router so everything goes to expert 0
        p = dict(p)
        p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0)
        x = jax.random.normal(KEY, (1, 64, cfg.d_model))
        y, _ = L.moe_apply(p, x, cfg)
        zero_rows = (np.abs(np.asarray(y)[0]).sum(-1) < 1e-6).sum()
        assert zero_rows >= 40   # ≥ dropped tokens produce exactly zero


class TestSSD:
    @staticmethod
    def _inputs(s, g, b=2, h=4, pdim=8, n=16):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, pdim))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
        return x, dt, A, B, C

    @pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (64, 64)])
    @pytest.mark.parametrize("g", [1, 2, 4])      # 4 = H: one group a head
    def test_chunked_equals_sequential(self, s, chunk, g):
        x, dt, A, B, C = self._inputs(s, g)
        y_chunk, f_chunk = L._ssd_chunked(x, dt, A, B, C, chunk)
        y_ref, f_ref = L._ssd_reference(x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(f_chunk), np.asarray(f_ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("g", [1, 2, 4])
    def test_chunked_gradients_equal_sequential(self, g):
        """The chunked scan's gradients in x, dt, B and C are the
        sequential recurrence's, at float32 precision."""
        s, chunk = 64, 16
        x, dt, A, B, C = self._inputs(s, g)
        w_y = jax.random.normal(jax.random.PRNGKey(7), x.shape)
        w_f = jax.random.normal(jax.random.PRNGKey(8),
                                x.shape[:1] + x.shape[2:] + B.shape[3:])

        def scalar(ssd):
            def f(x, dt, B, C):
                y, final = ssd(x, dt, A, B, C)
                return jnp.sum(y * w_y) + jnp.sum(final * w_f)
            return jax.grad(f, argnums=(0, 1, 2, 3))

        with jax.default_matmul_precision("float32"):
            got = scalar(lambda *a: L._ssd_chunked(*a, chunk))(x, dt, B, C)
            want = scalar(L._ssd_reference)(x, dt, B, C)
        for name, gv, wv in zip("x dt B C".split(), got, want):
            np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    def test_prefill_then_decode_equals_full(self):
        """Mamba block: prefill state + single-step recurrence == full scan."""
        cfg = small_cfg(arch_type="ssm", ssm_state=16, ssm_head_dim=16,
                        ssm_chunk=8, num_heads=0, num_kv_heads=0, d_ff=0)
        p, _ = L.mamba_init(KEY, cfg)
        u = jax.random.normal(jax.random.PRNGKey(3), (2, 17, cfg.d_model)) * 0.5
        y_full, _ = L.mamba_apply(p, u, cfg, mode="train")
        cache = L.init_ssm_cache(cfg, 2)
        y_pre, cache = L.mamba_apply(p, u[:, :16], cfg, mode="prefill", cache=cache)
        np.testing.assert_allclose(np.asarray(y_pre), np.asarray(y_full[:, :16]),
                                   rtol=2e-3, atol=2e-3)
        y_dec, cache = L.mamba_apply(p, u[:, 16:17], cfg, mode="decode", cache=cache)
        np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(y_full[:, 16]),
                                   rtol=2e-3, atol=2e-3)
        assert int(cache["idx"]) == 17


class TestMLP:
    def test_relu2(self):
        cfg = small_cfg(activation="relu2")
        p, _ = L.mlp_init(KEY, cfg, cfg.d_ff)
        x = jax.random.normal(KEY, (1, 3, cfg.d_model))
        y = L.mlp_apply(p, x, cfg)
        want = jnp.square(jax.nn.relu(x @ p["w1"])) @ p["w2"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)

    def test_gated(self):
        cfg = small_cfg(activation="silu_glu")
        p, _ = L.mlp_init(KEY, cfg, cfg.d_ff)
        x = jax.random.normal(KEY, (1, 3, cfg.d_model))
        y = L.mlp_apply(p, x, cfg)
        want = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w2"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


class TestOptim:
    def test_adam_matches_reference_quadratic(self):
        from repro.optim import adam, apply_updates
        opt = adam(0.1)
        params = {"w": jnp.array([1.0, -2.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = jax.tree_util.tree_map(lambda p: 2 * p, params)  # d/dp p²
            ups, state = opt.update(grads, state, params)
            params = apply_updates(params, ups)
        np.testing.assert_allclose(np.asarray(params["w"]), 0.0, atol=1e-2)

    def test_adamw_decays(self):
        from repro.optim import adamw, apply_updates
        opt = adamw(0.1, weight_decay=0.5)
        params = {"w": jnp.array([5.0])}
        state = opt.init(params)
        grads = {"w": jnp.array([0.0])}
        ups, state = opt.update(grads, state, params)
        assert float(ups["w"][0]) < 0  # pure decay pulls toward 0

    def test_bf16_state_dtype(self):
        from repro.optim import adamw
        opt = adamw(0.1, state_dtype=jnp.bfloat16)
        st = opt.init({"w": jnp.ones((4,), jnp.bfloat16)})
        assert st.mu["w"].dtype == jnp.bfloat16

    def test_clip_global_norm(self):
        from repro.optim import clip_by_global_norm
        g = {"a": jnp.ones(4) * 3.0}
        clipped, norm = clip_by_global_norm(g, 1.0)
        np.testing.assert_allclose(float(norm), 6.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]), 0.5, rtol=1e-6)


class TestChunkedAttention:
    @pytest.mark.parametrize("s,chunk,window", [(64, 16, 0), (64, 16, 24),
                                                (50, 16, 0)])
    def test_matches_dense(self, s, chunk, window):
        b, h, kv, d = 2, 4, 2, 16
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, kv, d))
        v = jax.random.normal(ks[2], (b, s, kv, d))
        got = L._chunked_sdpa(q, k, v, kv, chunk=chunk, window=window)
        want = L._sdpa(q, k, v, L.causal_mask(s, s, 0, window), kv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_model_forward_equivalent(self):
        """attention_impl=chunked gives the same logits as dense."""
        from repro.models import init_model, forward
        cfg_d = small_cfg()
        cfg_c = dataclasses.replace(cfg_d, attention_impl="chunked")
        params, _ = init_model(KEY, cfg_d)
        toks = jax.random.randint(KEY, (2, 16), 0, cfg_d.vocab_size)
        batch = {"tokens": toks, "targets": toks}
        ld, _ = forward(params, cfg_d, batch)
        lc, _ = forward(params, cfg_c, batch)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(lc),
                                   rtol=1e-3, atol=1e-3)
