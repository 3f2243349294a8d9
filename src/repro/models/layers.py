"""Model building blocks: norms, RoPE, GQA attention (qk-norm / bias /
sliding-window), gated & relu² MLPs, sort-based top-k MoE, Mamba2 SSD mixer.

Every ``*_init`` returns ``(params, specs)`` where ``specs`` mirrors the param
tree with tuples of *logical* axis names (repro.sharding) — keeping weights
and their sharding contract defined in one place.

Conventions: params bf16 (cfg.dtype); softmax/norm/SSD accumulate in f32;
attention caches carry absolute-position RoPE'd keys.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as sh
from .config import ModelConfig

Array = jax.Array
PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def scope(cfg: ModelConfig, name: str):
    """``jax.named_scope("<cfg.scope_prefix>.<name>")``, or nothing where the
    configuration names no prefix (the program's text is then unchanged)."""
    if not cfg.scope_prefix:
        return contextlib.nullcontext()
    return jax.named_scope(f"{cfg.scope_prefix}.{name}")


def lora_add(p: PyTree, name: str, x: Array, y: Array,
             cfg: ModelConfig) -> Array:
    """``y`` (= x @ W of projection ``name``) plus its adapter's
    ``(α/r)·(x@A)@B`` over x's last axis, where the layer has one
    (``p["lora"][name]`` holds ``A`` (in, r) and ``B`` (r, out))."""
    if name not in p.get("lora", {}):
        return y
    lp = p["lora"][name]
    with scope(cfg, "lora"):
        delta = (cfg.lora_alpha / cfg.lora_rank) * ((x @ lp["A"]) @ lp["B"])
    return y + delta.reshape(y.shape)


def dense_init(key: Array, shape: Tuple[int, ...], dtype, in_axis: int = 0,
               scale: float = 1.0) -> Array:
    fan_in = shape[in_axis]
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Tuple[PyTree, PyTree]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": (sh.EMBED,)}


def rmsnorm_apply(p: PyTree, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def headwise_norm_apply(scale: Array, x: Array, eps: float = 1e-5) -> Array:
    """qk-norm: RMS over head_dim of (..., heads, head_dim)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, D) with D even; positions: (B, S) absolute indices."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq  # (B,S,1,half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "wq": dense_init(ks[0], (d, h, hd), dt),
        "wk": dense_init(ks[1], (d, kv, hd), dt),
        "wv": dense_init(ks[2], (d, kv, hd), dt),
        "wo": dense_init(ks[3], (h, hd, d), dt, in_axis=0, scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }
    specs: Dict[str, Any] = {
        "wq": (sh.EMBED, sh.HEADS, None),
        "wk": (sh.EMBED, sh.KV_HEADS, None),
        "wv": (sh.EMBED, sh.KV_HEADS, None),
        "wo": (sh.HEADS, None, sh.EMBED),
    }
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((h, hd), dt)
        params["bk"] = jnp.zeros((kv, hd), dt)
        params["bv"] = jnp.zeros((kv, hd), dt)
        specs["bq"] = (sh.HEADS, None)
        specs["bk"] = (sh.KV_HEADS, None)
        specs["bv"] = (sh.KV_HEADS, None)
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((hd,), dt)
        params["k_norm"] = jnp.ones((hd,), dt)
        specs["q_norm"] = (None,)
        specs["k_norm"] = (None,)
    return params, specs


def _proj(p: PyTree, name: str, x: Array, cfg: ModelConfig) -> Array:
    """x (b, s, d) @ p[name] (d, heads, hd), with its adapter."""
    return lora_add(p, name, x, jnp.einsum("bsd,dhk->bshk", x, p[name]), cfg)


def _qkv(p: PyTree, x: Array, cfg: ModelConfig, positions: Array):
    q = _proj(p, "wq", x, cfg)
    k = _proj(p, "wk", x, cfg)
    v = _proj(p, "wv", x, cfg)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = headwise_norm_apply(p["q_norm"], q, cfg.norm_eps)
        k = headwise_norm_apply(p["k_norm"], k, cfg.norm_eps)
    if cfg.position_embedding != "none":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _out_proj(p: PyTree, out: Array, cfg: ModelConfig) -> Array:
    """Attention output (b, s, h, hd) @ wo, with its adapter."""
    return lora_add(p, "wo", out.reshape(out.shape[:2] + (-1,)),
                    jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cfg)


def _score_scale(cfg: ModelConfig) -> Optional[float]:
    """The attention score multiplier; None → 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or None


def _sdpa(q: Array, k: Array, v: Array, mask: Array | None, num_kv: int,
          scale: Optional[float] = None) -> Array:
    """Grouped scaled-dot-product attention.  q: (B,Sq,H,D), k/v: (B,Sk,KV,D),
    mask additive f32 broadcastable to (B, 1, Sq, Sk); scores are scaled by
    ``scale``, 1/sqrt(D) where None."""
    b, sq, h, d = q.shape
    groups = h // num_kv
    qg = q.reshape(b, sq, num_kv, groups, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    if mask is not None:
        scores = scores + mask[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def _chunked_sdpa(q: Array, k: Array, v: Array, num_kv: int, *,
                  chunk: int = 1024, window: int = 0,
                  scale: Optional[float] = None) -> Array:
    """Flash-style causal attention: lax.scan over KV chunks with online
    softmax — never materializes the (Sq × Sk) score matrix.  XLA analogue of
    kernels/flash_attention (which is the Pallas/TPU version); used for the
    long-prefill shapes where dense scores are the dominant memory term."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = (sk + pad) // chunk
    groups = h // num_kv
    qg = q.reshape(b, sq, num_kv, groups, dh).astype(jnp.float32)
    qg = qg / math.sqrt(dh) if scale is None else qg * scale
    kc = jnp.moveaxis(k.reshape(b, nkc, chunk, num_kv, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nkc, chunk, num_kv, dh), 1, 0)
    qpos = jnp.arange(sq)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kj, vj, j = xs
        kpos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj.astype(jnp.float32))
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(ok[None, None, None], s, -1e30)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_cur[..., None])
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_prev + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vj.astype(jnp.float32))
        return (m_cur, l_new, acc), None

    m0 = jnp.full((b, num_kv, groups, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, num_kv, groups, sq), jnp.float32)
    a0 = jnp.zeros((b, num_kv, groups, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nkc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, h, dh).astype(q.dtype)


def causal_mask(sq: int, sk: int, q_offset: Array | int = 0,
                window: int = 0) -> Array:
    """Additive (1, 1, Sq, Sk) mask.  q position i (absolute i+q_offset) may
    attend to k position j iff j ≤ i+off and (window==0 or j > i+off−window)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    ok = kpos <= qpos
    if window:
        ok &= kpos > (qpos - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[None, None]


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> PyTree:
    dt = dtype or _dtype(cfg)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dt),
        "v": jnp.zeros((batch, max_len, kv, hd), dt),
        "idx": jnp.zeros((), jnp.int32),
    }


def kv_cache_specs() -> PyTree:
    return {"k": (sh.BATCH, sh.KV_SEQ, sh.KV_HEADS, None),
            "v": (sh.BATCH, sh.KV_SEQ, sh.KV_HEADS, None),
            "idx": ()}


def attention_apply(p: PyTree, x: Array, cfg: ModelConfig, *,
                    mode: str = "train",
                    cache: Optional[PyTree] = None,
                    window: int = 0,
                    pos_offset: Array | int = 0) -> Tuple[Array, Optional[PyTree]]:
    """Self-attention.  mode:
       train   — full causal (or sliding-window) over x, no cache.
       prefill — as train, additionally writes x's K/V into ``cache``.
       decode  — x is (B, 1, d); attends to cache + itself; updates cache.
    """
    b, s, _ = x.shape
    scale = _score_scale(cfg)
    if mode in ("train", "prefill"):
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s)) + pos_offset
        q, k, v = _qkv(p, x, cfg, positions)
        if cfg.attention_impl == "chunked":
            out = _chunked_sdpa(q, k, v, cfg.num_kv_heads, window=window,
                                scale=scale)
        else:
            mask = causal_mask(s, s, 0, window)
            out = _sdpa(q, k, v, mask, cfg.num_kv_heads, scale)
        new_cache = None
        if mode == "prefill":
            assert cache is not None
            max_len = cache["k"].shape[1]
            if window and max_len == window:
                # Ring-buffer window cache: token t lives at slot t % window so
                # that subsequent decode steps evict the oldest token.
                if s <= window:
                    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
                    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
                else:
                    kw = jnp.roll(k[:, s - window:], shift=s % window, axis=1)
                    vw = jnp.roll(v[:, s - window:], shift=s % window, axis=1)
                    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kw, 0, axis=1)
                    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vw, 0, axis=1)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
            new_cache = {"k": ck, "v": cv, "idx": jnp.asarray(s, jnp.int32)}
        return _out_proj(p, out, cfg), new_cache

    assert mode == "decode" and cache is not None and s == 1
    idx = cache["idx"]                       # tokens already in cache
    max_len = cache["k"].shape[1]
    positions = jnp.full((b, 1), idx, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    slot = (idx % max_len) if window and max_len == window else idx
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    kpos = jnp.arange(max_len)
    if window and max_len == window:
        valid = kpos < jnp.minimum(idx + 1, max_len)     # ring buffer: all live slots
    else:
        valid = kpos <= idx
    mask = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, None, None, :]
    out = _sdpa(q, ck, cv, mask, cfg.num_kv_heads, scale)
    return _out_proj(p, out, cfg), {"k": ck, "v": cv, "idx": idx + 1}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attention_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    return attention_init(key, cfg)  # same weight shapes


def cross_attention_apply(p: PyTree, x: Array, enc_kv: Tuple[Array, Array],
                          cfg: ModelConfig) -> Array:
    """x: (B,S,d) decoder states; enc_kv: precomputed (K, V): (B,F,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    out = _sdpa(q, enc_kv[0], enc_kv[1], None, cfg.num_kv_heads)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_cross_kv(p: PyTree, enc_out: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    k = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wk"])
    v = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


# ---------------------------------------------------------------------------
# MLP (gated silu/gelu, or nemotron squared-ReLU non-gated)
# ---------------------------------------------------------------------------

def mlp_init(key: Array, cfg: ModelConfig, d_ff: int) -> Tuple[PyTree, PyTree]:
    d, dt = cfg.d_model, _dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.activation == "relu2":
        params = {"w1": dense_init(ks[0], (d, d_ff), dt),
                  "w2": dense_init(ks[1], (d_ff, d), dt, scale=1.0 / math.sqrt(2 * cfg.num_layers))}
        specs = {"w1": (sh.EMBED, sh.FF), "w2": (sh.FF, sh.EMBED)}
    else:
        params = {"w_gate": dense_init(ks[0], (d, d_ff), dt),
                  "w_up": dense_init(ks[1], (d, d_ff), dt),
                  "w2": dense_init(ks[2], (d_ff, d), dt, scale=1.0 / math.sqrt(2 * cfg.num_layers))}
        specs = {"w_gate": (sh.EMBED, sh.FF), "w_up": (sh.EMBED, sh.FF),
                 "w2": (sh.FF, sh.EMBED)}
    return params, specs


def mlp_apply(p: PyTree, x: Array, cfg: ModelConfig) -> Array:
    if cfg.activation == "relu2":
        h = jnp.einsum("bsd,df->bsf", x, p["w1"])
        h = jnp.square(jax.nn.relu(h))
    else:
        act = jax.nn.silu if cfg.activation == "silu_glu" else jax.nn.gelu
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = act(g) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w2"])


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch with capacity, MaxText-style)
# ---------------------------------------------------------------------------

def moe_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    """The router over all ``num_experts``; the weights of the experts held."""
    d, e, dt = cfg.d_model, cfg.num_experts, _dtype(cfg)
    eh = cfg.held_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    params = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (eh, d, ff), dt),
        "w_up": dense_init(ks[2], (eh, d, ff), dt),
        "w2": dense_init(ks[3], (eh, ff, d), dt, in_axis=1,
                         scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }
    specs = {
        "router": (None, None),
        "w_gate": (sh.EXPERTS, sh.EMBED, sh.MOE_FF),
        "w_up": (sh.EXPERTS, sh.EMBED, sh.MOE_FF),
        "w2": (sh.EXPERTS, sh.MOE_FF, sh.EMBED),
    }
    return params, specs


def _expert_ffn(p: PyTree, xb: Array, cfg: ModelConfig) -> Array:
    """xb: (E, Cap, d) → (E, Cap, d)."""
    act = jax.nn.silu if cfg.activation != "gelu_glu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", xb, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xb, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", act(g) * u, p["w2"])


def moe_apply(p: PyTree, x: Array, cfg: ModelConfig,
              rngs: Optional[Array] = None) -> Tuple[Array, Array]:
    """Top-k MoE over flattened tokens.  x: (B, S, d) → (y, aux_loss).

    Sort-based dispatch: token→expert assignments are sorted by expert id and
    scattered into per-expert capacity buffers (O(T·K·d), no T² one-hot
    einsum) — the XLA collectives this induces under an expert-sharded mesh
    are the all-to-alls of expert parallelism.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E · Σ_e f_e · p̄_e.
    me = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32).mean(0)
    pe = probs.mean(0)
    aux = e * jnp.sum(me * pe)

    if cfg.moe_dropless:
        # Exact per-token routing: every token's top-k experts contribute,
        # independent of the other tokens in the call.  Capacity dropping is
        # call-size dependent (a 1-token decode step never overflows, a full
        # forward can), so it breaks cached-decode ≡ dense-forward parity —
        # dropless is the serving-consistent semantic.  Dense all-experts
        # compute (E/K extra FLOPs): only for small-t / smoke configs.
        act = jax.nn.silu if cfg.activation != "gelu_glu" else jax.nn.gelu
        combine = jnp.zeros((t, e), jnp.float32)
        combine = combine.at[jnp.arange(t)[:, None], gate_idx].add(gate_vals)
        g = jnp.einsum("td,edf->tef", xt, p["w_gate"])
        u = jnp.einsum("td,edf->tef", xt, p["w_up"])
        y = jnp.einsum("tef,efd,te->td", act(g) * u, p["w2"],
                       combine.astype(x.dtype))
        return y.reshape(b, s, d), aux

    cap = int(math.ceil(k * t * cfg.capacity_factor / e))
    cap = max(8, -(-cap // 8) * 8)

    flat_e = gate_idx.reshape(-1)                             # (T·K,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[se]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, 0)
    se_c = jnp.where(keep, se, 0)

    xbuf = jnp.zeros((e, cap, d), x.dtype)
    xbuf = xbuf.at[se_c, pos_c].add(xt[st] * keep[:, None].astype(x.dtype))
    # NOTE (§Perf hillclimb B, refuted): pinning dispatch buffers to
    # (experts→model, capacity→data) was tried to turn the token→expert
    # scatter's data-axis all-reduce into an all-to-all; GSPMD instead added
    # a reshard on top (+49% collective bytes).  The structural fix is a
    # shard_map expert-parallel a2a — see EXPERIMENTS.md §Perf.
    ybuf = _expert_ffn(p, xbuf, cfg)
    contrib = ybuf[se_c, pos_c] * (sg * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    return y.reshape(b, s, d), aux


_GROUPED_DW = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


# ``lax.ragged_dot`` batches only where every operand is batched on axis 0;
# the experts' weights are shared by a vmapped batch, so a batch runs its
# members one after another here.
@jax.custom_batching.sequential_vmap
def _ragged(x: Array, w: Array, sizes: Array) -> Array:
    return jax.lax.ragged_dot(x, w, sizes)


@jax.custom_batching.sequential_vmap
def _ragged_dw(x: Array, dy: Array, sizes: Array) -> Array:
    return jax.lax.ragged_dot_general(x, dy, sizes, _GROUPED_DW)


def grouped_matmul(x: Array, w: Array, sizes: Array) -> Array:
    """Rows of x (M, K) sorted by group, ``sizes[g]`` rows of group g, each
    times its group's w[g] (G, K, N) → (M, N); rows past the groups are 0,
    and so are their rows of the input gradient.  The work scales with the
    rows in the groups.  The backward runs at the matmul precision in force
    where this is called (``jax.default_matmul_precision``), as the forward
    does."""
    return _grouped(x, w, sizes, jax.config.jax_default_matmul_precision)


def _rows_in_groups(y: Array, sizes: Array) -> Array:
    """``y`` with its rows past the groups set to 0.  ``lax.ragged_dot``
    leaves no defined value there on every backend (the TPU's kernel need
    not write them), and the caller scatters every row back to a token."""
    live = jnp.arange(y.shape[0]) < sizes.sum()
    return jnp.where(live[:, None], y, jnp.zeros((), y.dtype))


def _at_precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(x: Array, w: Array, sizes: Array, precision) -> Array:
    return _rows_in_groups(_ragged(x, w, sizes), sizes)


def _grouped_fwd(x, w, sizes, precision):
    return _grouped(x, w, sizes, precision), (x, w, sizes)


def _grouped_bwd(precision, res, dy):
    x, w, sizes = res
    with _at_precision(precision):
        dx = _ragged(dy, jnp.swapaxes(w, 1, 2), sizes)
        return (_rows_in_groups(dx, sizes), _ragged_dw(x, dy, sizes), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def moe_held_apply(p: PyTree, x: Array, cfg: ModelConfig
                   ) -> Tuple[Array, Array, Array]:
    """This chip's share of a top-k MoE, dropless.  x: (B, S, d) →
    (y, aux_loss, load).

    The router scores all ``num_experts`` and keeps the top
    ``experts_per_token`` (softmax, top-k, renormalised: the same weights as
    a softmax over the top-k logits).  ``p``'s expert weights are the
    ``experts_held`` experts from ``expert_offset`` on.  The assignments that
    fall on them are sorted by expert and run as one grouped matmul over
    their rows (:func:`grouped_matmul`), so the expert work scales with the
    tokens routed here; the gate-weighted rows are added back to their
    tokens.  An assignment to an expert not held contributes nothing here.

    The row buffer holds ``tokens × min(k, experts_held)`` rows, the most a
    call can route to the held experts, so no held assignment is ever
    dropped.  ``load`` is (experts_held + 1,) f32: the assignments routed to
    each held expert, then the held assignments dropped (0)."""
    b, s, d = x.shape
    e, k, eh, off = (cfg.num_experts, cfg.experts_per_token, cfg.experts_held,
                     cfg.expert_offset)
    t = b * s
    rows = t * min(k, eh)
    xt = x.reshape(t, d)
    act = jax.nn.silu if cfg.activation != "gelu_glu" else jax.nn.gelu
    with scope(cfg, "moe.route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (T, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)
        me = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32).mean(0)
        aux = e * jnp.sum(me * probs.mean(0))
        local = gate_idx.reshape(-1) - off                        # (T·K,)
        held = (local >= 0) & (local < eh)
        # Held assignments first, by expert; the rest sort past them (key eh).
        key = jnp.where(held, local, eh).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)[:rows]
        se = key[order]
        live = se < eh
        st = order // k                                           # token of each row
        sg = jnp.where(live, gate_vals.reshape(-1)[order], 0.0)
        counts = jnp.zeros((eh + 1,), jnp.int32).at[key].add(1)[:eh]
        xs = xt[st]
    with scope(cfg, "moe.experts"):
        g = grouped_matmul(xs, p["w_gate"].astype(xs.dtype), counts)
        u = grouped_matmul(xs, p["w_up"].astype(xs.dtype), counts)
        ys = grouped_matmul(act(g) * u, p["w2"].astype(xs.dtype), counts)
    with scope(cfg, "moe.route"):
        y = jnp.zeros((t, d), ys.dtype).at[st].add(ys * sg[:, None].astype(ys.dtype))
        dropped = held.sum() - live.sum()
        load = jnp.concatenate([counts, dropped[None]]).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux, load


# ---------------------------------------------------------------------------
# Mamba2 (SSD) mixer
# ---------------------------------------------------------------------------

def mamba_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    d, dt = cfg.d_model, _dtype(cfg)
    din, h, n, g = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    conv_dim = din + 2 * g * n
    ks = jax.random.split(key, 5)
    params = {
        "in_proj": dense_init(ks[0], (d, 2 * din + 2 * g * n + h), dt),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), dt, in_axis=0),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))).astype(jnp.float32),
        "norm_scale": jnp.ones((din,), dt),
        "out_proj": dense_init(ks[4], (din, d), dt, scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }
    specs = {
        "in_proj": (sh.EMBED, sh.SSM_INNER),
        "conv_w": (None, sh.SSM_INNER),
        "conv_b": (sh.SSM_INNER,),
        "A_log": (None,), "D": (None,), "dt_bias": (None,),
        "norm_scale": (sh.SSM_INNER,),
        "out_proj": (sh.SSM_INNER, sh.EMBED),
    }
    return params, specs


def _ssd_chunked(x: Array, dt: Array, A: Array, B: Array, C: Array,
                 chunk: int, init_state: Optional[Array] = None
                 ) -> Tuple[Array, Array]:
    """Chunked state-space-duality scan (Mamba2 §6).

    x: (b, S, H, P) f32; dt: (b, S, H); A: (H,) (negative); B, C: (b, S, G, N)
    with G dividing H.  Returns (y: (b,S,H,P), final_state: (b,H,P,N)).

    Heads are viewed as (G, H/G): head h reads group h // (H/G).  C·B, which
    depends on the group alone, is contracted once per group; B and C meet the
    per-head x, decay and state only inside a contraction, so no head-repeated
    copy of them is formed.
    """
    b, s, h, pdim = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    r = h // g

    dtc = dt.reshape(b, nc, chunk, g, r)
    xdt = x.reshape(b, nc, chunk, g, r, pdim) * dtc[..., None]   # dt_s x_s
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)

    cum = jnp.cumsum(dtc * A.reshape(g, r), axis=2)   # (b,nc,q,g,r) inclusive log decay
    # Intra-chunk (quadratic) term: M[t, s] = exp(cum_t − cum_s) C_t·B_s dt_s, s ≤ t.
    seg = cum[:, :, :, None] - cum[:, :, None, :]                # (b,nc,q,q,g,r)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    L = jnp.exp(jnp.where(tri[None, None, :, :, None, None], seg, -jnp.inf))
    cb = jnp.einsum("bcqgn,bcsgn->bcqsg", Cc, Bc)                # once per group
    y_intra = jnp.einsum("bcqsgr,bcsgrp->bcqgrp", L * cb[..., None], xdt)

    # Per-chunk input→final-state term: S_c = Σ_s exp(cum_Q − cum_s) dt_s B_s ⊗ x_s.
    decay_to_end = jnp.exp(cum[:, :, -1:] - cum)                 # (b,nc,q,g,r)
    state_in = jnp.einsum("bcsgn,bcsgrp->bcgrpn",
                          Bc, xdt * decay_to_end[..., None]).reshape(b, nc, h, pdim, n)

    # Inter-chunk recurrence over nc: S←exp(cum_Q)·S_prev + S_c (scan, f32).
    chunk_decay = jnp.exp(cum[:, :, -1]).reshape(b, nc, h)

    def step(carry, inp):
        dcy, s_in = inp
        new = carry * dcy[:, :, None, None] + s_in
        return new, carry                                        # emit state *entering* the chunk

    s0 = jnp.zeros((b, h, pdim, n), jnp.float32) if init_state is None else init_state
    final, entering = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(state_in, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1).reshape(b, nc, g, r, pdim, n)

    # Inter-chunk output: y_t += exp(cum_t) · (C_t · S_entering).
    y_inter = (jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, entering)
               * jnp.exp(cum)[..., None])
    y = (y_intra + y_inter).reshape(b, s, h, pdim)
    return y, final


def _ssd_reference(x, dt, A, B, C, init_state=None):
    """O(S·N·P) sequential oracle for tests: plain recurrence."""
    b, s, h, pdim = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    st = jnp.zeros((b, h, pdim, n), jnp.float32) if init_state is None else init_state

    def step(carry, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A)[:, :, None, None]
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dtt, xt, bt)
        new = carry * decay + upd
        y = jnp.einsum("bhpn,bhn->bhp", new, ct)
        return new, y

    final, ys = jax.lax.scan(step, st, (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
                                        jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    return jnp.moveaxis(ys, 0, 1), final


def init_ssm_cache(cfg: ModelConfig, batch: int) -> PyTree:
    din, h, n, g = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    conv_dim = din + 2 * g * n
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), _dtype(cfg)),
        "state": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
        "idx": jnp.zeros((), jnp.int32),
    }


def ssm_cache_specs() -> PyTree:
    return {"conv": (sh.BATCH, None, sh.SSM_INNER),
            "state": (sh.BATCH, None, None, sh.SSM_STATE),
            "idx": ()}


def _mamba_split(cfg: ModelConfig, zxbcdt: Array):
    din, g, n, h = cfg.ssm_d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z, xBC, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * g * n], axis=-1)
    return z, xBC, dt


def mamba_apply(p: PyTree, u: Array, cfg: ModelConfig, *,
                mode: str = "train",
                cache: Optional[PyTree] = None) -> Tuple[Array, Optional[PyTree]]:
    """Mamba2 block.  u: (B, S, d_model).  decode: S == 1 with cache."""
    b, s, _ = u.shape
    din, g, n, h, pdim = (cfg.ssm_d_inner, cfg.ssm_groups, cfg.ssm_state,
                          cfg.ssm_heads, cfg.ssm_head_dim)
    cw = cfg.ssm_conv_width
    zxbcdt = lora_add(p, "in_proj", u,
                      jnp.einsum("bsd,de->bse", u, p["in_proj"]), cfg)
    z, xBC, dt = _mamba_split(cfg, zxbcdt)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                # (H,) negative
    dt_full = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    if mode in ("train", "prefill"):
        pad = jnp.zeros((b, cw - 1, xBC.shape[-1]), xBC.dtype)
        xpad = jnp.concatenate([pad, xBC], axis=1)
        windows = jnp.stack([xpad[:, i:i + s] for i in range(cw)], axis=2)  # (b,s,cw,c)
        conv = jnp.einsum("bswc,wc->bsc", windows, p["conv_w"]) + p["conv_b"]
        conv = jax.nn.silu(conv)
        xs, B, C = jnp.split(conv, [din, din + g * n], axis=-1)
        xh = xs.reshape(b, s, h, pdim).astype(jnp.float32)
        Bm = B.reshape(b, s, g, n).astype(jnp.float32)
        Cm = C.reshape(b, s, g, n).astype(jnp.float32)
        pad_to = -s % cfg.ssm_chunk
        if pad_to:
            xh = jnp.pad(xh, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
            dt_full = jnp.pad(dt_full, ((0, 0), (0, pad_to), (0, 0)))
            Bm = jnp.pad(Bm, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
            Cm = jnp.pad(Cm, ((0, 0), (0, pad_to), (0, 0), (0, 0)))
        with scope(cfg, "ssd"):
            y, final = _ssd_chunked(xh, dt_full, A, Bm, Cm, cfg.ssm_chunk)
        y = y[:, :s]
        y = y + xh[:, :s] * p["D"][None, None, :, None]
        y = y.reshape(b, s, din).astype(u.dtype)
        new_cache = None
        if mode == "prefill":
            assert cache is not None
            conv_tail = xpad[:, s:]        # always the trailing cw−1 inputs
            new_cache = {"conv": conv_tail.astype(cache["conv"].dtype),
                         "state": final, "idx": jnp.asarray(s, jnp.int32)}
    else:
        assert mode == "decode" and cache is not None and s == 1
        conv_buf = jnp.concatenate([cache["conv"], xBC], axis=1)   # (b, cw, c)
        conv = jnp.einsum("bwc,wc->bc", conv_buf, p["conv_w"]) + p["conv_b"]
        conv = jax.nn.silu(conv)[:, None, :]
        xs, B, C = jnp.split(conv, [din, din + g * n], axis=-1)
        xh = xs.reshape(b, h, pdim).astype(jnp.float32)
        Bm = jnp.repeat(B.reshape(b, g, n), h // g, axis=1).astype(jnp.float32)
        Cm = jnp.repeat(C.reshape(b, g, n), h // g, axis=1).astype(jnp.float32)
        dt1 = dt_full[:, 0]                                        # (b,h)
        decay = jnp.exp(dt1 * A)[:, :, None, None]
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt1, xh, Bm)
        state = cache["state"] * decay + upd
        y = jnp.einsum("bhpn,bhn->bhp", state, Cm) + xh * p["D"][None, :, None]
        y = y.reshape(b, 1, din).astype(u.dtype)
        new_cache = {"conv": conv_buf[:, 1:], "state": state, "idx": cache["idx"] + 1}

    # Gated RMSNorm then out-projection.
    gated = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    gated = rmsnorm_apply({"scale": p["norm_scale"]}, gated, cfg.norm_eps)
    out = lora_add(p, "out_proj", gated,
                   jnp.einsum("bse,ed->bsd", gated, p["out_proj"]), cfg)
    return out, new_cache


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    dt = _dtype(cfg)
    params = {"table": dense_init(key, (cfg.vocab_size, cfg.d_model), dt, in_axis=1)}
    specs = {"table": (sh.VOCAB, sh.EMBED)}
    return params, specs


def embed_apply(p: PyTree, tokens: Array) -> Array:
    return p["table"][tokens]


def unembed_apply(p: PyTree, x: Array) -> Array:
    return jnp.einsum("bsd,vd->bsv", x, p["table"])
