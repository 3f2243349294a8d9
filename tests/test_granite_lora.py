"""Federated LoRA fine-tuning of a frozen hybrid Mamba2 + MoE base
(``repro.fl.workloads.granite_lora_workload``): the expert layer that holds
a share of the experts, the adapters, the frozen part's path through the
engines, and the program every other workload compiles, unchanged."""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.granite_4_0_h_small import CONFIG as GRANITE
from repro.configs.paper_cnn import FLConfig
from repro.core import case_label_plan
from repro.fl import ExperimentSpec, ScenarioSpec, run
from repro.fl.population import make_population_round
from repro.fl.sim import make_trial_fn
from repro.fl.workloads import granite_lora_workload
from repro.models import layers as L
from repro.models import lora
from repro.models.config import ModelConfig
from repro.models.transformer import forward_loads, init_model

# The cut at a CPU test's size: the same block types (Mamba2, NoPE
# attention, a routed MoE held in part, a shared expert), the Granite
# scalars, 2 held experts of 8.
TINY = dataclasses.replace(
    GRANITE, num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=32, vocab_size=256, num_experts=8,
    experts_per_token=3, experts_held=2, expert_offset=0, moe_d_ff=32,
    dense_residual_d_ff=48, ssm_state=16, ssm_head_dim=16, ssm_chunk=16,
    attn_layer_period=4, attn_layer_offset=2, attention_multiplier=1 / 16)
FL = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
              local_epochs=1, batch_size=1, lr=3e-3)


def tiny_workload(**kw):
    return granite_lora_workload(TINY, base_seed=5, seq_len=64, lora_rank=4,
                                 lora_alpha=8.0, **kw)


def test_the_preset_carries_the_published_values():
    g = GRANITE
    assert (g.num_layers, g.d_model, g.num_heads, g.num_kv_heads,
            g.resolved_head_dim, g.vocab_size) == (40, 4096, 32, 8, 128, 100352)
    assert (g.ssm_heads, g.ssm_head_dim, g.ssm_state, g.ssm_groups,
            g.ssm_conv_width, g.ssm_chunk) == (128, 64, 128, 1, 4, 256)
    assert (g.num_experts, g.experts_per_token, g.moe_d_ff,
            g.dense_residual_d_ff) == (72, 10, 768, 1536)
    assert (g.attention_multiplier, g.embedding_multiplier,
            g.residual_multiplier, g.logits_scaling) == (0.0078125, 12, 0.22, 16)
    assert g.position_embedding == "none" and g.router_aux_weight == 0
    kinds = g.layer_kinds()
    assert [i for i, (m, _) in enumerate(kinds) if m == "attn"] == [5, 15, 25, 35]
    assert {f for _, f in kinds} == {"moe+dense"}


def test_the_adapters_of_the_cut_count_5199872():
    cut = dataclasses.replace(GRANITE, num_layers=10, vocab_size=12544,
                              experts_held=9, lora_rank=16,
                              lora_targets=("wq", "wk", "wv", "wo",
                                            "in_proj", "out_proj"))
    assert lora.num_adapter_params(cut) == 5_199_872


@pytest.mark.parametrize("offset", [0, 2, 4, 6])
def test_a_share_counts_its_assignments_and_drops_none(offset):
    cfg = dataclasses.replace(TINY, expert_offset=offset)
    p, _ = L.moe_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    _, _, load = L.moe_held_apply(p, x, cfg)
    logits = x.reshape(-1, cfg.d_model) @ p["router"]
    _, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.experts_per_token)
    want = [(np.asarray(idx) == offset + e).sum() for e in range(2)]
    np.testing.assert_array_equal(np.asarray(load), want + [0])


def test_the_shares_add_up_to_the_uncut_layer():
    """Each share routes over all 8 experts and computes its own 2; the four
    shares' parts, with the shared expert counted once, are the uncut
    layer's output (every expert held, computed densely)."""
    full = dataclasses.replace(TINY, experts_held=0, moe_dropless=True,
                               dtype="float32")
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    p, _ = L.moe_init(k[0], full)
    shared, _ = L.mlp_init(k[1], full, full.dense_residual_d_ff)
    x = jax.random.normal(k[2], (2, 32, full.d_model))
    with jax.default_matmul_precision("highest"):
        want = L.moe_apply(p, x, full)[0] + L.mlp_apply(shared, x, full)
        got = L.mlp_apply(shared, x, full)
        for off in range(0, 8, 2):
            cfg = dataclasses.replace(full, experts_held=2, expert_offset=off,
                                      moe_dropless=False)
            share = dict(p, **{w: p[w][off:off + 2]
                               for w in ("w_gate", "w_up", "w2")})
            got = got + L.moe_held_apply(share, x, cfg)[0]
    # float32 sums in another order: a few ulps of outputs of size ~1.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_grouped_matmul_is_ragged_dot_under_vmap_and_grad():
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(k[0], (2, 12, 4))
    w = jax.random.normal(k[1], (3, 4, 5))
    sizes = jnp.array([[3, 4, 2], [1, 0, 5]], jnp.int32)

    def f(fn):
        return lambda x, w: jax.vmap(
            lambda xi, si: (fn(xi, w, si) ** 2).sum())(x, sizes).sum()
    got = jax.value_and_grad(f(L.grouped_matmul), argnums=(0, 1))(x, w)
    want = [jax.value_and_grad(lambda xi, w: (jax.lax.ragged_dot(
        xi, w, sizes[i]) ** 2).sum(), argnums=(0, 1))(x[i], w) for i in range(2)]
    np.testing.assert_allclose(got[0], sum(v for v, _ in want), rtol=1e-6)
    np.testing.assert_allclose(got[1][0], np.stack([g[0] for _, g in want]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[1][1], sum(g[1] for _, g in want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("precision,want", [("float32", {"HIGHEST"}),
                                             (None, {"None"})])
def test_the_grouped_matmuls_backward_keeps_the_callers_precision(
        precision, want):
    import re
    x, w = jnp.ones((8, 4)), jnp.ones((2, 4, 3))
    sizes = jnp.array([3, 5], jnp.int32)

    def f(x):
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            return L.grouped_matmul(x, w, sizes).sum()
    text = str(jax.make_jaxpr(jax.grad(f))(x))
    got = set(re.findall(r"precision=\(?(?:Precision\.)?(\w+)", text))
    assert got == want


def test_rows_past_the_groups_are_zero_whatever_the_backend_leaves(
        monkeypatch):
    """A backend whose ragged dot leaves its rows past the groups unwritten
    (here: 1e3) changes neither the product nor the input gradient: the
    rows past the groups come back 0, so a scatter of every row back to its
    token adds nothing from them."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 3))
    c = jax.random.normal(jax.random.PRNGKey(2), (8, 3))
    sizes = jnp.array([3, 2], jnp.int32)

    def run():
        return jax.value_and_grad(
            lambda x: (L.grouped_matmul(x, w, sizes) * c).sum())(x)
    want_y = L.grouped_matmul(x, w, sizes)
    want_loss, want_dx = run()
    plain = L._ragged

    def unwritten(a, b, s):
        y = plain(a, b, s)
        return jnp.where((jnp.arange(y.shape[0]) < s.sum())[:, None], y, 1e3)
    monkeypatch.setattr(L, "_ragged", unwritten)
    y = L.grouped_matmul(x, w, sizes)
    loss, dx = run()
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(y[5:], 0)
    np.testing.assert_array_equal(dx, want_dx)
    np.testing.assert_array_equal(dx[5:], 0)
    assert loss == want_loss


def test_adapters_start_as_the_base_and_move_only_their_projections():
    cfg = dataclasses.replace(TINY, lora_rank=4, lora_alpha=8.0,
                              lora_targets=("wq", "wo", "in_proj"))
    base = init_model(jax.random.PRNGKey(5), cfg)[0]
    ads = lora.init_adapters(jax.random.PRNGKey(6), cfg)
    toks = {"tokens": jnp.arange(32)[None] % cfg.vocab_size}
    plain = forward_loads(base, cfg, toks)[0]
    np.testing.assert_array_equal(forward_loads(lora.merge(base, ads), cfg,
                                                toks)[0], plain)
    kinds = [m for m, _ in cfg.layer_kinds()]
    for i, blk in enumerate(ads["stack"]["blocks"]):
        (mixer, sub), = blk.items()
        assert mixer == kinds[i]
        assert set(sub["lora"]) == ({"wq", "wo"} if mixer == "attn"
                                    else {"in_proj"})
    moved = jax.tree_util.tree_map(lambda a: a + 0.1, ads)
    assert not np.allclose(forward_loads(lora.merge(base, moved), cfg,
                                         toks)[0], plain)


def test_a_scanned_stack_counts_the_same_loads_as_unstacked_blocks():
    """Two periods of the pattern under ``scan_layers``: the loads come out
    of the scan layer by layer, as the unstacked blocks give them."""
    cfg = dataclasses.replace(TINY, num_layers=8, remat=False)
    scanned = dataclasses.replace(cfg, scan_layers=True)
    params = init_model(jax.random.PRNGKey(5), scanned)[0]
    period = params["stack"]["blocks"]
    blocks = tuple(jax.tree_util.tree_map(lambda a, r=r: a[r], period[i])
                   for r in range(2) for i in range(len(period)))
    flat = dict(params, stack={"blocks": blocks})
    toks = {"tokens": jnp.arange(32)[None] % cfg.vocab_size}
    logits, _, loads = forward_loads(params, scanned, toks)
    want_logits, _, want = forward_loads(flat, cfg, toks)
    assert loads.shape == (8, cfg.experts_held + 1)
    np.testing.assert_array_equal(loads, want)
    # The same float32 ops; the scan only reorders how XLA fuses them.
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=1e-5)


def test_training_and_eval_matmuls_run_in_float32():
    """Every matmul of the loss, of its gradient (the grouped matmul's
    custom backward too) and of the eval runs in full float32."""
    from repro.fl.workloads import FROZEN_KEY, frozen_base

    def lowered():
        wl = tiny_workload()
        ds = wl.make_dataset()
        ads = wl.init(jax.random.PRNGKey(0), ds)
        base = frozen_base(wl, ds)
        toks = jnp.zeros((2, 64), jnp.int32)
        ev = jax.jit(wl.make_eval(ds)).lower(
            ads, {"tokens": toks, "targets": toks, FROZEN_KEY: base})
        loss = wl.make_loss(ds)
        batch = {"tokens": toks[:1], "valid": jnp.ones((1,), bool),
                 "labels": jnp.zeros((1,), jnp.int32), FROZEN_KEY: base}
        grad = jax.jit(jax.grad(lambda a: loss(a, batch)[0])).lower(ads)
        return ev.as_text(), grad.as_text()

    for text in lowered():
        assert "precision = [HIGHEST, HIGHEST]" in text
        assert "precision = [DEFAULT, DEFAULT]" not in text


def test_the_scan_contracts_c_dot_b_once_per_group():
    """The chunked SSD at the cell's widths (4,096 tokens, the preset's
    heads, head width, state, groups and chunk) compiles to the analytic
    count, in which C·B is contracted once per group and not once per
    head: at one group for 128 heads the per-head form counts twice it."""
    g = GRANITE
    b, s, h, p, n, grp, q = (1, 4096, g.ssm_heads, g.ssm_head_dim,
                             g.ssm_state, g.ssm_groups, g.ssm_chunk)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    with jax.default_matmul_precision("float32"):
        compiled = jax.jit(lambda x, dt, a, bm, cm: L._ssd_chunked(
            x, dt, a, bm, cm, q)).lower(
                f32(b, s, h, p), f32(b, s, h), f32(h), f32(b, s, grp, n),
                f32(b, s, grp, n)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    analytic = 2 * b * s * (q * h * p          # intra-chunk scores · x
                            + 2 * h * p * n    # chunk states, inter-chunk output
                            + q * grp * n)     # C·B, once per group
    assert abs(cost["flops"] / analytic - 1) < 0.1, (cost["flops"], analytic)


def test_sim_and_host_engines_agree_through_the_shared_step():
    wl = tiny_workload()
    sc = ScenarioSpec.from_case("case1b", samples_per_client=4)
    out = {}
    for engine in ("sim", "host"):
        spec = ExperimentSpec(scenarios=(sc,), strategies=("labelwise",),
                              seeds=(3,), fl=FL, eval_n_per_class=1,
                              workload=wl, engine=engine,
                              telemetry=("expert_load",))
        out[engine] = run(spec)
    a, b = out["sim"], out["host"]
    np.testing.assert_array_equal(a.num_selected, b.num_selected)
    # One program against a per-round jit of the same ops: op order only.
    np.testing.assert_allclose(a.loss, b.loss, rtol=1e-6)
    assert a.loss[0, 0, 0, 1] != a.loss[0, 0, 0, 0]        # training moved it


@pytest.mark.parametrize("engine", ["sharded", "hier", "async"])
def test_engines_that_train_the_whole_tree_refuse_a_frozen_part(engine):
    spec = ExperimentSpec(
        scenarios=(ScenarioSpec.from_case("case1b", samples_per_client=4),),
        strategies=("labelwise",), fl=FL, eval_n_per_class=1,
        workload=tiny_workload(), engine=engine)
    with pytest.raises(ValueError, match="has a frozen part"):
        run(spec)


def test_the_population_round_refuses_a_frozen_part():
    with pytest.raises(ValueError, match="has a frozen part"):
        make_population_round(plan_fn=lambda ids, t: ids, num_clients=8,
                              block_size=4, budget=2,
                              workload=tiny_workload())


def test_a_trial_without_its_base_is_an_error():
    trial = make_trial_fn(FL, workload=tiny_workload(), rounds=1,
                          eval_n_per_class=1, strategies=("labelwise",))
    plan = case_label_plan("case1b", seed=0, num_rounds=1, num_clients=6,
                           num_classes=10, samples_per_client=4, majority=3)
    with pytest.raises(ValueError, match="needs its base"):
        jax.eval_shape(trial, jnp.asarray(plan), jnp.int32(0), jnp.int32(1),
                       jnp.ones((1, 6)))


# The sim trial's lowered program text, before the frozen part, counters and
# the expert share were added (sha256 of ``jax.jit(trial).lower(...)
# .as_text()`` at the size below, JAX 0.9.0, CPU): a workload without them
# compiles exactly that program.
PINNED = {
    ("cnn", "fedavg"): "07d15de598aa5703cbb761965bc04d53108a36c3e25a4cda662112d58156f437",
    ("cnn", "fedsgd"): "8666cd967ebee66d2cc5b22360371db01529f1ceb5f8262e01b089aa839f64a0",
    ("lm", "fedavg"): "706c0b7aaaadea1956398a448b5832938e4f3c6afa7d5d664b1597e1f397cebf",
    ("lm", "fedsgd"): "42f84131d50bffe5b017207040b29fe31b379c4ddbf16a139f205cf50c4d6fc5",
}


@pytest.mark.parametrize("workload,aggregation", sorted(PINNED))
def test_a_workload_without_a_frozen_part_compiles_the_same_program(
        workload, aggregation):
    cfg = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                   local_epochs=1, batch_size=8)
    trial = make_trial_fn(cfg, aggregation=aggregation, rounds=2,
                          eval_n_per_class=2, strategies=("random", "labelwise"),
                          workload=workload)
    plan = case_label_plan("case1b", seed=0, num_rounds=2, num_clients=6,
                           num_classes=10, samples_per_client=16, majority=11)
    text = jax.jit(trial).lower(jnp.asarray(plan, jnp.int32), jnp.int32(1),
                                jnp.int32(3), jnp.ones((2, 6))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[
        (workload, aggregation)]


def test_default_options_leave_the_model_unchanged():
    """NoPE, the Granite scalars, an expert share and adapters are off by
    default: a plain config's forward is the same function as before."""
    cfg = ModelConfig(name="t", arch_type="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      num_experts=4, experts_per_token=2, dtype="float32",
                      fsdp=False, remat=False, scan_layers=False)
    assert (cfg.position_embedding, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.experts_held, cfg.lora_rank,
            cfg.scope_prefix, cfg.compute_dtype) == ("rope", 0.0, 1.0, 1.0,
                                                     1.0, 0, 0, "", "")
    assert cfg.held_experts == 4
