"""Composable decoder stack covering every assigned architecture family.

A model is: input pathway (text / vlm / audio) → N blocks (mixer ∈ {attn,
mamba} × ffn ∈ {dense, moe, moe+dense, none}) → final norm → tied-or-free
unembed.  Homogeneous-period stacks are ``lax.scan``-ed over *superblocks*
(the smallest repeating (mixer, ffn) pattern — 1 block for dense archs, 8 for
jamba), which keeps compile time flat in depth; ``cfg.remat`` wraps the
superblock in ``jax.checkpoint``.

Three entry points per model:
    loss_fn(params, cfg, batch)               — training loss (next-token CE)
    prefill(params, cfg, batch, max_len)      — build KV/SSM caches
    decode_step(params, cfg, tokens, caches)  — one token, cache-resident
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as sh
from .config import ModelConfig
from . import layers as L

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def block_init(key: Array, cfg: ModelConfig, kind: Tuple[str, str],
               cross_attention: bool = False) -> Tuple[PyTree, PyTree]:
    mixer, ffn = kind
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["mixer_norm"], specs["mixer_norm"] = L.rmsnorm_init(cfg.d_model, L._dtype(cfg))
    if mixer == "attn":
        params["attn"], specs["attn"] = L.attention_init(ks[0], cfg)
    else:
        params["mamba"], specs["mamba"] = L.mamba_init(ks[0], cfg)
    if cross_attention:
        params["cross_norm"], specs["cross_norm"] = L.rmsnorm_init(cfg.d_model, L._dtype(cfg))
        params["cross_attn"], specs["cross_attn"] = L.cross_attention_init(ks[1], cfg)
    if ffn != "none":
        params["ffn_norm"], specs["ffn_norm"] = L.rmsnorm_init(cfg.d_model, L._dtype(cfg))
        if ffn in ("moe", "moe+dense"):
            params["moe"], specs["moe"] = L.moe_init(ks[2], cfg)
            if ffn == "moe+dense":
                params["dense"], specs["dense"] = L.mlp_init(ks[3], cfg, cfg.dense_residual_d_ff)
        else:
            params["mlp"], specs["mlp"] = L.mlp_init(ks[2], cfg, cfg.d_ff)
    return params, specs


def _residual(x: Array, out: Array, cfg: ModelConfig) -> Array:
    """x + residual_multiplier · out (the multiplier only where it is set)."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def block_apply(p: PyTree, x: Array, cfg: ModelConfig, kind: Tuple[str, str], *,
                mode: str = "train", cache: Optional[PyTree] = None,
                enc_kv: Optional[Tuple[Array, Array]] = None,
                window: int = 0, pos_offset: Array | int = 0,
                bidirectional: bool = False):
    """Returns (x, new_cache, aux_loss, counts): ``counts`` is the block's
    expert counters (``moe_held_apply``'s; empty where the block holds no
    share of experts)."""
    mixer, ffn = kind
    aux = jnp.zeros((), jnp.float32)
    counts = {}
    h = L.rmsnorm_apply(p["mixer_norm"], x, cfg.norm_eps)
    if mixer == "attn":
        if bidirectional:
            q, k, v = L._qkv(p["attn"], h,
                             cfg, jnp.broadcast_to(jnp.arange(h.shape[1])[None], h.shape[:2]))
            att = L._sdpa(q, k, v, None, cfg.num_kv_heads)
            mix = jnp.einsum("bshk,hkd->bsd", att, p["attn"]["wo"])
            new_cache = None
        else:
            with L.scope(cfg, "attn"):
                mix, new_cache = L.attention_apply(
                    p["attn"], h, cfg, mode=mode, cache=cache, window=window,
                    pos_offset=pos_offset)
    else:
        with L.scope(cfg, "mamba"):
            mix, new_cache = L.mamba_apply(p["mamba"], h, cfg, mode=mode,
                                           cache=cache)
    x = _residual(x, mix, cfg)
    x = sh.constrain(x, sh.BATCH, sh.SEQ, None)
    if enc_kv is not None:
        hc = L.rmsnorm_apply(p["cross_norm"], x, cfg.norm_eps)
        x = x + L.cross_attention_apply(p["cross_attn"], hc, enc_kv, cfg)
    if ffn != "none":
        h2 = L.rmsnorm_apply(p["ffn_norm"], x, cfg.norm_eps)
        if ffn in ("moe", "moe+dense"):
            if cfg.experts_held:
                mo, aux, counts = L.moe_held_apply(p["moe"], h2, cfg)
            else:
                mo, aux = L.moe_apply(p["moe"], h2, cfg)
            if ffn == "moe+dense":
                with L.scope(cfg, "moe.shared"):
                    mo = mo + L.mlp_apply(p["dense"], h2, cfg)
            x = _residual(x, mo, cfg)
        else:
            x = _residual(x, L.mlp_apply(p["mlp"], h2, cfg), cfg)
        x = sh.constrain(x, sh.BATCH, sh.SEQ, None)
    return x, new_cache, aux, counts


def block_cache_init(cfg: ModelConfig, kind: Tuple[str, str], batch: int,
                     max_len: int) -> PyTree:
    if kind[0] == "attn":
        length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        return L.init_kv_cache(cfg, batch, length)
    return L.init_ssm_cache(cfg, batch)


def block_cache_specs(kind: Tuple[str, str]) -> PyTree:
    return L.kv_cache_specs() if kind[0] == "attn" else L.ssm_cache_specs()


# ---------------------------------------------------------------------------
# Superblock grouping (scan over the repeating pattern)
# ---------------------------------------------------------------------------

def _pattern_period(kinds: List[Tuple[str, str]]) -> int:
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return p
    return n


def stack_plan(cfg: ModelConfig) -> Tuple[List[Tuple[str, str]], int, int]:
    """(period_kinds, period, num_repeats) under the scan policy."""
    kinds = cfg.layer_kinds()
    if not cfg.scan_layers:
        return kinds, len(kinds), 1
    p = _pattern_period(kinds)
    return kinds[:p], p, len(kinds) // p


def stack_init(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    period_kinds, p, reps = stack_plan(cfg)

    def one_superblock(k):
        ks = jax.random.split(k, p)
        ps, ss = [], None
        for i, kind in enumerate(period_kinds):
            pi, si = block_init(ks[i], cfg, kind)
            ps.append(pi)
            ss = ss or []
            ss.append(si)
        return tuple(ps), tuple(ss)

    if reps == 1:
        params, specs = one_superblock(key)
        return {"blocks": params}, {"blocks": specs}
    keys = jax.random.split(key, reps)
    stacked = jax.vmap(lambda k: one_superblock(k)[0])(keys)
    _, spec1 = one_superblock(key)
    specs = jax.tree_util.tree_map(
        lambda ax: (None,) + tuple(ax), spec1,
        is_leaf=lambda x: isinstance(x, tuple) and (not x or not isinstance(x[0], dict)))
    return {"blocks": stacked}, {"blocks": specs}


def _superblock_specs(cfg: ModelConfig):
    """Logical-axis specs for ONE superblock's params (scan-sliced shape)."""
    period_kinds, p, _ = stack_plan(cfg)
    captured = {}

    def f(k):
        ks = jax.random.split(k, p)
        ps, ss = [], []
        for i, kind in enumerate(period_kinds):
            pi, si = block_init(ks[i], cfg, kind)
            ps.append(pi)
            ss.append(si)
        captured["specs"] = tuple(ss)
        return tuple(ps)

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return captured["specs"]


def _constrain_sliced_blocks(blocks: PyTree, cfg: ModelConfig) -> PyTree:
    """Re-pin each scan-sliced weight to its FSDP/TP sharding INSIDE the scan
    body.  Without this, GSPMD hoists the FSDP all-gather of the whole
    stacked weight tree out of the loop — materializing every layer's
    gathered weights at once (§Perf hillclimb C: 42 GiB for nemotron-340b)."""
    if not sh._ACTIVE:
        return blocks
    mesh, rules = sh._ACTIVE[-1]
    specs = _superblock_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten(blocks)
    axes = treedef.flatten_up_to(specs)
    out = []
    for leaf, ax in zip(flat, axes):
        ax = (tuple(ax) + (None,) * leaf.ndim)[:leaf.ndim]
        spec = sh.spec_for_shape(leaf.shape, ax, mesh, rules)
        out.append(jax.lax.with_sharding_constraint(
            leaf, jax.sharding.NamedSharding(mesh, spec)))
    return treedef.unflatten(out)


def stack_apply_train(params: PyTree, x: Array, cfg: ModelConfig,
                      window: int = 0
                      ) -> Tuple[Array, Array, Optional[Dict[str, Array]]]:
    """Training/scoring forward through all blocks.  Returns (x, aux_total,
    loads): ``loads`` stacks each block's expert counters (``block_apply``'s)
    on a leading layer axis: ``expert_load`` (num_layers, experts_held + 1),
    ``expert_rows_run`` (num_layers,); None where the model holds no share
    of experts.  Under ``cfg.remat`` a scanned period is one
    checkpoint, and unstacked blocks are one checkpoint each."""
    period_kinds, p, reps = stack_plan(cfg)

    def superblock(x, blocks, run_block):
        if reps > 1:
            blocks = _constrain_sliced_blocks(blocks, cfg)
        # Entering carry is what the backward pass saves per layer — shard its
        # seq dim under sequence parallelism (no-op otherwise).
        x = sh.constrain(x, sh.BATCH, sh.RESIDUAL_SEQ, None)
        aux = jnp.zeros((), jnp.float32)
        loads = []
        for i, kind in enumerate(period_kinds):
            x, a, load = run_block(blocks[i], x, kind)
            aux = aux + a
            loads.append(load)
        x = sh.constrain(x, sh.BATCH, sh.RESIDUAL_SEQ, None)
        return x, aux, (jax.tree_util.tree_map(lambda *c: jnp.stack(c), *loads)
                        if cfg.experts_held else None)

    def block(bp, x, kind):
        x, _, a, load = block_apply(bp, x, cfg, kind, mode="train", window=window)
        return x, a, load

    if reps == 1:
        def run_block(bp, x, kind):
            f = functools.partial(block, kind=kind)
            return (jax.checkpoint(f) if cfg.remat else f)(bp, x)
        return superblock(x, params["blocks"], run_block)

    def body(x, blocks):
        return superblock(x, blocks, block)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            # Save matmul outputs (no recompute of the big einsums in the
            # backward pass) at the cost of activation memory — the §Perf
            # compute-term lever.
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:
            body = jax.checkpoint(body)

    def scan_fn(carry, blocks):
        x, aux = carry
        x, a, loads = body(x, blocks)
        return (x, aux + a), loads

    (x, aux), loads = jax.lax.scan(scan_fn, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
    loads = jax.tree_util.tree_map(
        lambda c: c.reshape((-1,) + c.shape[2:]), loads)
    return x, aux, loads


def stack_caches_init(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    period_kinds, p, reps = stack_plan(cfg)
    one = tuple(block_cache_init(cfg, kind, batch, max_len) for kind in period_kinds)
    if reps == 1:
        return one
    return jax.tree_util.tree_map(lambda c: jnp.broadcast_to(c, (reps,) + c.shape), one)


def stack_cache_specs(cfg: ModelConfig) -> PyTree:
    period_kinds, p, reps = stack_plan(cfg)
    one = tuple(block_cache_specs(kind) for kind in period_kinds)
    if reps == 1:
        return one
    return jax.tree_util.tree_map(
        lambda ax: ((None,) + tuple(ax)) if ax is not None else (None,), one,
        is_leaf=lambda v: isinstance(v, tuple) and (not v or isinstance(v[0], (str, type(None)))))


def stack_apply_cached(params: PyTree, x: Array, cfg: ModelConfig, caches: PyTree,
                       mode: str, window: int = 0,
                       pos_offset: Array | int = 0) -> Tuple[Array, PyTree]:
    period_kinds, p, reps = stack_plan(cfg)

    def superblock(x, blocks, cs):
        new_cs = []
        for i, kind in enumerate(period_kinds):
            x, nc, _, _ = block_apply(blocks[i], x, cfg, kind, mode=mode,
                                   cache=cs[i], window=window, pos_offset=pos_offset)
            new_cs.append(nc)
        return x, tuple(new_cs)

    if reps == 1:
        return superblock(x, params["blocks"], caches)

    def scan_fn(x, xs):
        blocks, cs = xs
        x, ncs = superblock(x, blocks, cs)
        return x, ncs

    x, new_caches = jax.lax.scan(scan_fn, x, (params["blocks"], caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Whole models
# ---------------------------------------------------------------------------

def init_model(key: Array, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = L.embed_init(ks[0], cfg)
    stack_p, stack_s = stack_init(ks[1], cfg)
    params["stack"], specs["stack"] = stack_p, stack_s
    params["final_norm"], specs["final_norm"] = L.rmsnorm_init(cfg.d_model, L._dtype(cfg))

    if cfg.arch_type == "vlm":
        dt = L._dtype(cfg)
        params["projector"] = {
            "w1": L.dense_init(ks[2], (cfg.vision_embed_dim, cfg.d_model), dt),
            "w2": L.dense_init(ks[3], (cfg.d_model, cfg.d_model), dt),
        }
        specs["projector"] = {"w1": (None, sh.EMBED), "w2": (sh.EMBED, None)}
    if cfg.is_encoder_decoder:
        enc_kinds = [("attn", "dense")] * cfg.encoder_layers
        eks = jax.random.split(ks[4], cfg.encoder_layers + 1)
        enc_blocks, enc_specs = [], []
        for i in range(cfg.encoder_layers):
            bp, bs = block_init(eks[i], cfg, enc_kinds[i])
            enc_blocks.append(bp)
            enc_specs.append(bs)
        # decoder blocks need cross-attention — rebuild stack unrolled w/ cross
        dec_blocks, dec_specs = [], []
        dks = jax.random.split(ks[5], cfg.num_layers)
        for i in range(cfg.num_layers):
            bp, bs = block_init(dks[i], cfg, ("attn", "dense"), cross_attention=True)
            dec_blocks.append(bp)
            dec_specs.append(bs)
        params["encoder"] = {"blocks": tuple(enc_blocks)}
        specs["encoder"] = {"blocks": tuple(enc_specs)}
        params["stack"] = {"blocks": tuple(dec_blocks)}
        specs["stack"] = {"blocks": tuple(dec_specs)}
    return params, specs


def model_param_specs(cfg: ModelConfig) -> PyTree:
    """Logical-axis spec tree without materializing weights.  The spec tree is
    built as a python side-product of tracing init_model abstractly."""
    captured = {}

    def f(k):
        params, specs = init_model(k, cfg)
        captured["specs"] = specs
        return params

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return captured["specs"]


def encode_audio(params: PyTree, frames: Array, cfg: ModelConfig) -> Array:
    x = frames.astype(L._dtype(cfg))
    for bp in params["encoder"]["blocks"]:
        x, _, _, _ = block_apply(bp, x, cfg, ("attn", "dense"), mode="train",
                              bidirectional=True)
    return x


def _embed_inputs(params: PyTree, cfg: ModelConfig, batch: Dict[str, Array]) -> Array:
    """Input pathway → (B, S, d) hidden sequence."""
    x = L.embed_apply(params["embed"], batch["tokens"])
    if cfg.compute_dtype:
        x = x.astype(cfg.compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.arch_type == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        h = jax.nn.gelu(jnp.einsum("bpv,vd->bpd", pe, params["projector"]["w1"]))
        h = jnp.einsum("bpd,de->bpe", h, params["projector"]["w2"])
        x = jnp.concatenate([h, x], axis=1)
    return sh.constrain(x, sh.BATCH, sh.SEQ, None)


def _unembed(params: PyTree, cfg: ModelConfig, x: Array) -> Array:
    """Final-norm output → logits (divided by ``logits_scaling`` where set)."""
    logits = L.unembed_apply(params["embed"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def forward_loads(params: PyTree, cfg: ModelConfig, batch: Dict[str, Array]
                  ) -> Tuple[Array, Array, Optional[Dict[str, Array]]]:
    """Full-sequence logits (training/scoring) with the blocks' expert
    counters.  Returns (logits, aux, loads), ``loads`` as
    :func:`stack_apply_train` gives them (None for an encoder-decoder)."""
    x = _embed_inputs(params, cfg, batch)
    loads = None
    if cfg.is_encoder_decoder:
        enc = encode_audio(params, batch["frames"], cfg)
        aux = jnp.zeros((), jnp.float32)
        for bp in params["stack"]["blocks"]:
            kv = L.encode_cross_kv(bp["cross_attn"], enc, cfg)
            x, _, a, _ = block_apply(bp, x, cfg, ("attn", "dense"), mode="train",
                                  enc_kv=kv, window=cfg.sliding_window)
            aux = aux + a
    else:
        x, aux, loads = stack_apply_train(params["stack"], x, cfg,
                                          window=cfg.sliding_window)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    logits = sh.constrain(logits, sh.BATCH, sh.SEQ, sh.VOCAB)
    return logits, aux, loads


def forward(params: PyTree, cfg: ModelConfig, batch: Dict[str, Array]
            ) -> Tuple[Array, Array]:
    """Full-sequence logits (training/scoring).  Returns (logits, aux)."""
    logits, aux, _ = forward_loads(params, cfg, batch)
    return logits, aux


def token_ce(logits: Array, targets: Array, *, with_accuracy: bool = False
             ) -> Tuple[Array, Dict[str, Array]]:
    """Masked next-token CE over full-sequence logits (−1 = ignore id).

    THE token-level CE convention: ``loss_fn`` (training) and workload evals
    (repro.fl.workloads) share this one implementation, so an eval trajectory
    can never drift from the training loss if the convention changes.
    Returns (loss, metrics) with ``metrics = {"ntok"[, "accuracy"]}`` —
    accuracy (top-1 next-token) is opt-in so training graphs don't carry the
    argmax."""
    logits = logits.astype(jnp.float32)
    valid = (targets >= 0)
    tsafe = jnp.where(valid, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tsafe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    denom = jnp.maximum(valid.sum(), 1)
    loss = nll.sum() / denom
    m: Dict[str, Array] = {"ntok": denom}
    if with_accuracy:
        m["accuracy"] = ((jnp.argmax(logits, -1) == tsafe)
                         * valid).sum() / denom
    return loss, m


def loss_fn(params: PyTree, cfg: ModelConfig, batch: Dict[str, Array]
            ) -> Tuple[Array, Dict[str, Array]]:
    """Next-token CE over targets (−1 = ignore), + MoE aux loss."""
    logits, aux = forward(params, cfg, batch)
    targets = batch["targets"]
    if cfg.arch_type == "vlm":  # logits cover [patches, tokens]; score text only
        logits = logits[:, -targets.shape[1]:]
    loss, m = token_ce(logits, targets)
    total = loss + cfg.router_aux_weight * aux
    return total, {"ce": loss, "aux": aux, "ntok": m["ntok"]}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    caches = stack_caches_init(cfg, batch, max_len)
    if cfg.is_encoder_decoder:
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cross = tuple(
            {"k": jnp.zeros((batch, cfg.num_frames, kv, hd), L._dtype(cfg)),
             "v": jnp.zeros((batch, cfg.num_frames, kv, hd), L._dtype(cfg))}
            for _ in range(cfg.num_layers))
        return {"self": caches, "cross": cross}
    return caches


def prefill(params: PyTree, cfg: ModelConfig, batch: Dict[str, Array],
            max_len: int) -> Tuple[Array, PyTree]:
    """Run the prompt; returns (last-position logits, caches)."""
    x = _embed_inputs(params, cfg, batch)
    caches = init_caches(cfg, x.shape[0], max_len)
    if cfg.is_encoder_decoder:
        enc = encode_audio(params, batch["frames"], cfg)
        new_self, new_cross = [], []
        for i, bp in enumerate(params["stack"]["blocks"]):
            kv = L.encode_cross_kv(bp["cross_attn"], enc, cfg)
            x, nc, _, _ = block_apply(bp, x, cfg, ("attn", "dense"), mode="prefill",
                                   cache=caches["self"][i], enc_kv=kv,
                                   window=cfg.sliding_window)
            new_self.append(nc)
            new_cross.append({"k": kv[0], "v": kv[1]})
        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], x[:, -1:])
        return logits[:, 0], {"self": tuple(new_self), "cross": tuple(new_cross)}
    x, new_caches = stack_apply_cached(params["stack"], x, cfg, caches,
                                       mode="prefill", window=cfg.sliding_window)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, cfg, x[:, -1:])
    logits = sh.constrain(logits, sh.BATCH, None, sh.VOCAB)
    return logits[:, 0], new_caches


def decode_step(params: PyTree, cfg: ModelConfig, tokens: Array, caches: PyTree
                ) -> Tuple[Array, PyTree]:
    """One decode step.  tokens: (B,) int32 → (logits (B,V), caches)."""
    x = L.embed_apply(params["embed"], tokens[:, None])
    x = sh.constrain(x, sh.BATCH, None, None)
    if cfg.is_encoder_decoder:
        new_self = []
        for i, bp in enumerate(params["stack"]["blocks"]):
            kv = (caches["cross"][i]["k"], caches["cross"][i]["v"])
            x, nc, _, _ = block_apply(bp, x, cfg, ("attn", "dense"), mode="decode",
                                   cache=caches["self"][i], enc_kv=kv,
                                   window=cfg.sliding_window)
            new_self.append(nc)
        new_caches: PyTree = {"self": tuple(new_self), "cross": caches["cross"]}
    else:
        x, new_caches = stack_apply_cached(params["stack"], x, cfg, caches,
                                           mode="decode", window=cfg.sliding_window)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, cfg, x)[:, 0]
    logits = sh.constrain(logits, sh.BATCH, sh.VOCAB)
    return logits, new_caches
