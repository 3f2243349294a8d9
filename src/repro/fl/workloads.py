"""Pluggable client-workload registry: what each FL client trains.

The paper's selection machinery is architecture-agnostic — Algorithm 1
operates on label histograms, never on weights — yet until this module every
execution engine hard-coded the CNN workload.  A :class:`Workload` bundles
everything an engine needs to run *some* model family over *some*
label-conditioned synthetic data source:

* ``make_dataset()`` — the default dataset object (engines accept an explicit
  ``ds=`` override, e.g. a differently-sized ``TokenDataset``),
* ``init(key, ds)`` — traceable parameter init (the engine hands it the
  trial's already-folded key, so trajectories are reproducible per seed),
* ``make_loss(ds)`` — returns the traceable local-training loss
  ``loss(params, batch) -> (scalar, aux)`` over ONE client minibatch,
* ``materialize(ds, plan_t, key)`` — the plan-conditioned synthetic
  materializer: a (N, n_max) int32 label plan row (−1 padding; labels may be
  image classes, vocab-band domain ids, …) → the round-batch dict,
* ``eval_set(ds, n_per_class)`` / ``make_eval(ds)`` — a held-out eval batch
  plus ``eval(params, eval_batch) -> (loss, {"accuracy": ...})``,
* static shape metadata: ``batch_keys`` (which round-batch leaves carry
  per-sample data and therefore enter ``client_batches``/the sharded batch
  PartitionSpecs) and ``num_classes(ds)`` (the label-space size — histogram
  width for every selection strategy).

Registration contract (mirrors the strategy registry,
repro.core.selection.register_strategy):

* every callable must be traceable JAX — registered workloads compile
  straight into the simulator's ``lax.scan`` round loop and the vmapped grid,
  and into the sharded SPMD round, with zero engine edits;
* ``materialize`` must return a dict containing at least ``"labels"``
  ((N, n_max) int32, −1 pad), ``"valid"`` ((N, n_max) bool) and ``"hists"``
  ((N, num_classes) f32 — ``repro.kernels.dispatch.client_histograms`` of
  the valid labels: Pallas-fused on TPU, XLA reference elsewhere), plus
  any payload leaves named in ``batch_keys``; every ``batch_keys`` leaf is
  shaped (N, n_max, ...) so ``repro.data.client_batches`` can fold it to
  (N, n_batches, batch_size, ...);
* ``make_eval``'s metrics dict must contain ``"accuracy"`` — it is the
  trajectory every engine records (for the LM workload this is next-token
  top-1 accuracy on a uniform-domain held-out stream);
* re-registering a name (``overwrite=True``) swaps the bundle; unknown names
  raise ``KeyError`` at spec-validation time, before anything compiles.

Built-ins:

* ``cnn`` — the paper's 6-layer CNN over class-conditional synthetic images,
  extracted verbatim from the pre-registry engines (bit-identical graphs:
  the Table-I host≡sim parity pins in tests/test_fl_sim.py are unchanged);
* ``lm`` — a micro decoder-only transformer (repro.models.transformer) over
  ``TokenDataset`` streams where "class label" = vocab-band domain id: the
  same non-IID plans, transforms, strategies, and engines drive federated LM
  pretraining (the DESIGN.md §5 mapping, previously a hand-rolled host loop
  in examples/fl_lm_pretrain.py).  ``lm_workload(cfg, ...)`` builds variants
  at any model size.

A workload may declare a **frozen part** (``frozen``): a base that every
client uses and none trains, as in federated fine-tuning with low-rank
adapters (:func:`granite_lora_workload`).  ``frozen(ds)`` builds it from the
workload's own seed; ``init`` builds the trainable tree alone, and local
training, the reported updates, the reduction and the server update act on
that tree.  The engines hand the base to the loss and the eval inside their
batch dict, under :data:`FROZEN_KEY`; the compiled sim trial takes it as one
more input, unbatched (never a per-client or per-trial copy, never a
constant of the program).  Only the ``sim`` and ``host`` engines run such a
workload; the others refuse it (:func:`reject_frozen`).

``counters`` names per-step counts that a workload's loss reports in its aux
dict; local training sums them over its steps and the round reports them per
selected client, where the telemetry metric of the same name reads them.
``map_clients`` trains the selected clients one after another
(``lax.map``) in place of side by side (``vmap``), for clients whose
activations only fit one at a time.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.data import ImageDataset, TokenDataset, materialize_round
from repro.kernels.dispatch import client_histograms
from repro.models import cnn_init, cnn_loss
from repro.models.config import ModelConfig
from repro.models import lora
from repro.models.layers import scope
from repro.models.transformer import forward as lm_forward
from repro.models.transformer import forward_loads
from repro.models.transformer import init_model as lm_init_model
from repro.models.transformer import loss_fn as lm_loss_fn
from repro.models.transformer import token_ce

Array = jax.Array
PyTree = Any
LossFn = Callable[[PyTree, Dict[str, Array]], Tuple[Array, Dict[str, Array]]]
EvalFn = Callable[[PyTree, Dict[str, Array]], Tuple[Array, Dict[str, Array]]]

# The batch-dict key under which the engines hand a workload's loss and eval
# its frozen base.
FROZEN_KEY = "frozen"


@dataclasses.dataclass(frozen=True)
class Workload:
    """One registered client workload — see the module docstring contract.

    ``name`` is the registry key: ``register_workload`` rewrites it to the
    registration name, so ``get_workload(x).name == x`` always holds (an
    unregistered bundle carries whatever its factory chose)."""
    name: str
    make_dataset: Callable[[], Any]
    init: Callable[[Array, Any], PyTree]
    make_loss: Callable[[Any], LossFn]
    materialize: Callable[[Any, Any, Array], Dict[str, Array]]
    eval_set: Callable[[Any, int], Dict[str, Array]]
    make_eval: Callable[[Any], EvalFn]
    batch_keys: Tuple[str, ...]
    num_classes: Callable[[Any], int]
    # Optional chunked materializer for population-scale engines:
    # ``materialize_rows(ds, plan_rows, key, row_ids)`` builds the round
    # batch for an arbitrary SUBSET of clients — ``plan_rows`` is (B, n_max)
    # and ``row_ids`` the (B,) GLOBAL client ids — with the contract that
    # client i's draw depends only on (key, i), never on which other rows
    # share the call.  That id-keyed stability is what makes any block
    # partition (and a selected-clients-only gather) yield identical
    # per-client data, so the chunked path never materializes the dense
    # (N, n, ...) round.  ``None`` → the generic per-row fold_in fallback
    # (:func:`materialize_rows` below) wraps ``materialize``.
    materialize_rows: Optional[
        Callable[[Any, Any, Array, Array], Dict[str, Array]]] = None
    # The frozen base, built from the workload's own seed (module docstring).
    frozen: Optional[Callable[[Any], PyTree]] = None
    counters: Tuple[str, ...] = ()
    map_clients: bool = False

    def dataset(self, ds: Any = None) -> Any:
        """``ds`` if given, else this workload's default dataset."""
        return ds if ds is not None else self.make_dataset()

    def param_shapes(self, ds: Any) -> PyTree:
        """ShapeDtypeStruct tree of the carried model state — what engines
        use to allocate/shard params without materializing them (the sharded
        engine builds its replicated PartitionSpec tree from this).  With a
        frozen part, the trainable tree's."""
        return jax.eval_shape(lambda k: self.init(k, ds),
                              jax.random.PRNGKey(0))


def with_frozen(batch: Dict[str, Array], frozen: PyTree) -> Dict[str, Array]:
    """``batch`` with the frozen base under :data:`FROZEN_KEY` (itself where
    there is no base)."""
    return batch if frozen is None else dict(batch, **{FROZEN_KEY: frozen})


def frozen_base(workload: "str | Workload", ds: Any = None) -> PyTree:
    """The workload's frozen base on the device, built once by one jitted
    call (None where the workload has no frozen part)."""
    wl = get_workload(workload)
    if wl.frozen is None:
        return None
    ds = wl.dataset(ds)
    return jax.jit(lambda: wl.frozen(ds))()


def reject_frozen(wl: "Workload", engine: str) -> None:
    """The engines that train the whole parameter tree refuse a workload
    with a frozen part."""
    if wl.frozen is not None:
        raise ValueError(
            f"engine {engine!r} trains and exchanges the whole parameter tree; "
            f"workload {wl.name!r} has a frozen part (a shared base and a "
            "trainable tree), which only the 'sim' and 'host' engines run")


def materialize_rows(wl: "Workload", ds: Any, plan_rows: Array, key: Array,
                     row_ids: Array) -> Dict[str, Array]:
    """Chunked row materialization: round batch for a client SUBSET.

    Dispatches to ``wl.materialize_rows`` when the workload declares one;
    otherwise wraps ``wl.materialize`` per row under ``vmap`` with a
    per-client key ``fold_in(key, row_ids[i])``.  Either way client i's data
    is a pure function of (key, i) — the id-keyed stability contract the
    population engine's chunked path relies on (tested by
    tests/test_population.py::test_materialize_rows_block_invariant).

    NOTE the fallback's draws intentionally differ from a dense
    ``wl.materialize(ds, full_plan, key)`` call: JAX PRNG array draws are
    shape-dependent, so a (N, n, ...) single-key draw cannot be reproduced
    chunk-wise.  Engines that pin parity against ``sim`` (the hier registry
    engine) therefore materialize with the dense call; the chunked path is
    the population-scale surface where N never fits densely."""
    plan_rows = jnp.asarray(plan_rows, jnp.int32)
    row_ids = jnp.asarray(row_ids, jnp.int32)
    if wl.materialize_rows is not None:
        return wl.materialize_rows(ds, plan_rows, key, row_ids)

    def one(row: Array, rid: Array) -> Dict[str, Array]:
        out = wl.materialize(ds, row[None], jax.random.fold_in(key, rid))
        return jax.tree_util.tree_map(lambda x: x[0], out)

    return jax.vmap(one)(plan_rows, row_ids)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_WORKLOADS: Dict[str, Workload] = {}


def register_workload(name: str, workload: Workload, *,
                      overwrite: bool = False,
                      check: bool = False) -> Workload:
    """Register ``workload`` under ``name``.

    Every engine (compiled sim grid, host parity loop, sharded SPMD round)
    dispatches to registered workloads by name through
    ``ExperimentSpec.workload`` — no engine edits to add a model family.
    Re-registering an existing name requires ``overwrite=True`` and swaps the
    bundle in place; specs naming it pick up the new bundle on their next
    ``run``.  Returns ``workload`` for decorator-style use.

    ``check=True`` runs the jaxpr contract passes (repro.analysis) over the
    bundle BEFORE registering — materialize schema (labels/valid/hists +
    batch_keys, histogram width), traceable init/loss, eval metrics
    containing "accuracy" — raising ``repro.analysis.ContractError`` with
    structured diagnostics."""
    if not name or not isinstance(name, str):
        raise ValueError(f"workload name must be a non-empty str; got {name!r}")
    if name in _WORKLOADS and not overwrite:
        raise ValueError(f"workload {name!r} is already registered; pass "
                         "overwrite=True to replace it")
    if not isinstance(workload, Workload):
        raise TypeError(f"workload {name!r} must be a Workload; "
                        f"got {type(workload)}")
    if workload.name != name:
        workload = dataclasses.replace(workload, name=name)
    if check:
        from repro.analysis import assert_workload_contract
        assert_workload_contract(name, workload)
    _WORKLOADS[name] = workload
    return workload


def registered_workloads() -> Tuple[str, ...]:
    return tuple(_WORKLOADS)


def get_workload(workload: "str | Workload") -> Workload:
    """Resolve a workload name (or pass a Workload instance through)."""
    if isinstance(workload, Workload):
        return workload
    try:
        return _WORKLOADS[workload]
    except KeyError:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{registered_workloads()}") from None


# ---------------------------------------------------------------------------
# Builtin: cnn — the paper's image-classification client, extracted verbatim
# from the pre-registry engines (same call graph, bit-identical trajectories).
# ---------------------------------------------------------------------------

def _cnn_init(key: Array, ds: ImageDataset) -> PyTree:
    return cnn_init(key, num_classes=ds.num_classes, image_size=ds.image_size,
                    channels=ds.channels)


def _cnn_make_loss(ds: ImageDataset) -> LossFn:
    del ds

    def loss(params: PyTree, batch: Dict[str, Array]):
        return cnn_loss(params, batch["images"], batch["labels"],
                        batch["valid"])
    return loss


def _cnn_eval_set(ds: ImageDataset, n_per_class: int) -> Dict[str, Array]:
    x, y = ds.test_set(n_per_class)
    return {"images": x, "labels": y}


def _cnn_make_eval(ds: ImageDataset) -> EvalFn:
    del ds

    def ev(params: PyTree, eval_batch: Dict[str, Array]):
        return cnn_loss(params, eval_batch["images"], eval_batch["labels"])
    return ev


CNN_WORKLOAD = Workload(
    name="cnn",
    make_dataset=ImageDataset,
    init=_cnn_init,
    make_loss=_cnn_make_loss,
    materialize=materialize_round,
    eval_set=_cnn_eval_set,
    make_eval=_cnn_make_eval,
    batch_keys=("images", "labels", "valid"),
    num_classes=lambda ds: ds.num_classes,
)


# ---------------------------------------------------------------------------
# Builtin: lm — federated LM pretraining over domain-skewed token streams.
# "class label" = vocab-band domain id (TokenDataset), so every non-IID plan,
# transform, and selection strategy applies unchanged.
# ---------------------------------------------------------------------------

# Micro config for the default "lm" workload: small enough that the fast test
# tier compiles host+sim parity in seconds; real sizes go through
# lm_workload(cfg) (examples/fl_lm_pretrain.py registers a 12M-param one).
MICRO_LM_CONFIG = ModelConfig(
    name="fl-lm-micro", arch_type="dense", num_layers=2, d_model=64,
    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256, dtype="float32",
    fsdp=False, remat=False, scan_layers=False)


def _lm_targets(tokens: Array, valid: Array) -> Array:
    """Next-token targets: roll left, −1 on the last position and on every
    padded (invalid) sequence — −1 is the transformer loss's ignore id."""
    tgt = jnp.roll(tokens, -1, axis=-1).at[..., -1].set(-1)
    return jnp.where(valid[..., None], tgt, -1)


def _lm_materialize(ds: TokenDataset, plan_t, key: Array) -> Dict[str, Array]:
    """(N, n_max) domain plan row → round batch: token sequences per client
    slot, domain labels, validity, and the (N, D) domain histogram selection
    strategies rank on (a zeroed histogram for all-padded clients keeps the
    validity gates working unchanged)."""
    labels = jnp.asarray(plan_t, jnp.int32)
    valid = labels >= 0
    tokens = ds.sample(key, labels) * valid[..., None]
    hists = client_histograms(jnp.where(valid, labels, 0),
                              ds.num_domains, valid)
    return {"tokens": tokens, "labels": labels, "valid": valid,
            "hists": hists}


def _lm_eval_set(ds: TokenDataset, n_per_class: int) -> Dict[str, Array]:
    """``n_per_class`` held-out sequences of each domain, key 999."""
    domains = jnp.tile(jnp.arange(ds.num_domains), n_per_class)
    tokens = ds.sample(jax.random.PRNGKey(999), domains)
    return {"tokens": tokens,
            "targets": _lm_targets(tokens, jnp.ones(tokens.shape[0], bool))}


def lm_workload(cfg: ModelConfig, *, num_domains: int = 10,
                seq_len: int = 16, concentration: float = 0.85) -> Workload:
    """Build an LM workload around ``cfg`` (any text ModelConfig).

    Clients hold ``seq_len``-token sequences sampled from ``num_domains``
    vocab-band unigram domains; the plan's labels are domain ids.  The local
    loss is next-token cross-entropy over the client's valid sequences; eval
    is loss + top-1 next-token accuracy on a held-out uniform-domain stream
    (one block of ``n_per_class`` sequences per domain)."""

    def make_dataset() -> TokenDataset:
        return TokenDataset(num_domains=num_domains,
                            vocab_size=cfg.vocab_size, seq_len=seq_len,
                            concentration=concentration)

    def _check(ds: TokenDataset) -> None:
        if ds.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"TokenDataset vocab_size ({ds.vocab_size}) must match the "
                f"workload model's vocab_size ({cfg.vocab_size})")

    def init(key: Array, ds: TokenDataset) -> PyTree:
        _check(ds)
        return lm_init_model(key, cfg)[0]

    def make_loss(ds: TokenDataset) -> LossFn:
        _check(ds)

        def loss(params: PyTree, batch: Dict[str, Array]):
            toks = batch["tokens"]
            targets = _lm_targets(toks, batch["valid"])
            return lm_loss_fn(params, cfg, {"tokens": toks,
                                            "targets": targets})
        return loss

    def make_eval(ds: TokenDataset) -> EvalFn:
        _check(ds)

        def ev(params: PyTree, eval_batch: Dict[str, Array]):
            logits, _ = lm_forward(params, cfg,
                                   {"tokens": eval_batch["tokens"]})
            # Same token_ce as the training loss — eval can't drift from it.
            loss, m = token_ce(logits, eval_batch["targets"],
                               with_accuracy=True)
            return loss, {"accuracy": m["accuracy"], "n": m["ntok"]}
        return ev

    return Workload(
        name=f"lm:{cfg.name}",
        make_dataset=make_dataset,
        init=init,
        make_loss=make_loss,
        materialize=_lm_materialize,
        eval_set=_lm_eval_set,
        make_eval=make_eval,
        batch_keys=("tokens", "labels", "valid"),
        num_classes=lambda ds: ds.num_domains,
    )


# ---------------------------------------------------------------------------
# Federated LoRA fine-tuning of a frozen hybrid Mamba2 + MoE base
# ---------------------------------------------------------------------------

LORA_TARGETS = ("wq", "wk", "wv", "wo", "in_proj", "out_proj")


def granite_base(cfg: ModelConfig, base_seed: int, embed_std: float) -> PyTree:
    """The frozen base of :func:`granite_lora_workload`, by one rule.

    The base's tree is ``repro.models.transformer.init_model``'s; its leaf j,
    in ``jax.tree_util`` flattening order (dict keys sorted, blocks in layer
    order), is drawn from ``fold_in(PRNGKey(base_seed), j)``: norm scales
    and ``D`` are ones, ``conv_b`` zeros; ``A_log`` is log U(1, 16) and
    ``dt_bias`` the inverse softplus of dt = exp(U(log 0.001, log 0.1))
    (≥ 1e-4) per head, the Mamba2 rule; the token table is
    N(0, ``embed_std``²); every other weight N(0, 1/fan_in), its fan-in the
    axis it contracts (``wo``: heads × head width; an expert's ``w2``: its
    width).  Every leaf is stored in ``cfg.dtype``."""
    shapes = jax.eval_shape(lambda k: lm_init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(base_seed)
    dt = jnp.dtype(cfg.dtype)
    out = []
    for j, (path, leaf) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, j)
        shape = leaf.shape
        if name in ("scale", "norm_scale", "D"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "conv_b":
            x = jnp.zeros(shape, jnp.float32)
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            d = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                           jnp.log(1e-3), jnp.log(1e-1)))
            d = jnp.maximum(d, 1e-4)
            x = d + jnp.log(-jnp.expm1(-d))
        elif name == "table":
            x = jax.random.normal(k, shape, jnp.float32) * embed_std
        else:
            if name == "wo":
                fan_in = shape[0] * shape[1]
            elif len(shape) == 3 and name in ("w_gate", "w_up", "w2"):
                fan_in = shape[1]                      # an expert's weights
            else:
                fan_in = shape[0]
            x = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                jnp.float32(fan_in))
        out.append(x.astype(dt))
    return jax.tree_util.tree_unflatten(treedef, out)


def granite_lora_workload(cfg: ModelConfig, *, base_seed: int,
                          embed_std: float = 0.05, num_domains: int = 10,
                          seq_len: int = 4096, concentration: float = 0.85,
                          lora_rank: int = 16, lora_alpha: float = 32.0,
                          lora_targets: Tuple[str, ...] = LORA_TARGETS
                          ) -> Workload:
    """Federated LoRA fine-tuning of a frozen hybrid Mamba2 + MoE decoder
    (``repro.configs.granite_4_0_h_small`` and its cuts).

    The frozen part is :func:`granite_base`; the trainable tree is the
    adapters (``repro.models.lora``) on ``lora_targets`` at rank
    ``lora_rank``, scale ``lora_alpha / lora_rank``.  Clients hold
    ``seq_len``-token sequences of ``num_domains`` vocab-band domains drawn
    by inverse CDF from the (sliced) vocabulary; the loss is next-token
    cross-entropy over the valid sequences (the router is frozen and its
    load-balance term weighs 0), and reports ``expert_load``, the tokens
    routed to each held expert of each layer and the held assignments
    dropped, and ``expert_rows_run``, the rows each layer's dispatch ran, as
    ``moe_held_apply`` counts them.  Eval is loss and top-1
    next-token accuracy on ``n_per_class`` held-out sequences per domain,
    one sequence at a time.  The loss, its gradient and the eval run every
    matmul in full float32 (on a TPU the default rounds the operands to
    bfloat16), so that one round of small adapter steps can be held to a
    float32 reference.  The selected clients train one after another.  It
    shares ``lm_workload``'s materializer, eval set and targets."""
    cfg = dataclasses.replace(cfg, lora_rank=lora_rank, lora_alpha=lora_alpha,
                              lora_targets=tuple(lora_targets))

    def make_dataset() -> TokenDataset:
        return TokenDataset(num_domains=num_domains,
                            vocab_size=cfg.vocab_size, seq_len=seq_len,
                            concentration=concentration,
                            sampler="inverse_cdf")

    def logits_and_loads(adapters: PyTree, base: PyTree, tokens: Array):
        return forward_loads(lora.merge(base, adapters), cfg,
                             {"tokens": tokens})

    def make_loss(ds: TokenDataset) -> LossFn:
        del ds

        def loss(adapters: PyTree, batch: Dict[str, Array]):
            toks = batch["tokens"]
            with jax.default_matmul_precision("float32"):
                logits, _, loads = logits_and_loads(
                    adapters, batch[FROZEN_KEY], toks)
                with scope(cfg, "loss"):
                    ce, m = token_ce(logits, _lm_targets(toks, batch["valid"]))
            return ce, {"ce": ce, "ntok": m["ntok"], **loads}
        return loss

    def make_eval(ds: TokenDataset) -> EvalFn:
        del ds

        def ev(adapters: PyTree, eval_batch: Dict[str, Array]):
            base = eval_batch[FROZEN_KEY]

            def one(seq):
                tokens, targets = seq
                logits, _, _ = logits_and_loads(adapters, base, tokens[None])
                with scope(cfg, "loss"):
                    ce, m = token_ce(logits, targets[None], with_accuracy=True)
                n = (targets >= 0).sum()
                return ce * n, m["accuracy"] * n, n

            with jax.default_matmul_precision("float32"):
                nll, hits, n = jax.lax.map(
                    one, (eval_batch["tokens"], eval_batch["targets"]))
            n = jnp.maximum(n.sum(), 1)
            return nll.sum() / n, {"accuracy": hits.sum() / n, "n": n}
        return ev

    return Workload(
        name=f"lora:{cfg.name}",
        make_dataset=make_dataset,
        init=lambda key, ds: lora.init_adapters(key, cfg),
        make_loss=make_loss,
        materialize=_lm_materialize,
        eval_set=_lm_eval_set,
        make_eval=make_eval,
        batch_keys=("tokens", "labels", "valid"),
        num_classes=lambda ds: ds.num_domains,
        frozen=lambda ds: granite_base(cfg, base_seed, embed_std),
        counters=("expert_load", "expert_rows_run"),
        # Four silos side by side need 21.18 GB of a chip's 15.75 GiB at the
        # granite cut's widths (compile rehearsal for a TPU v5e).
        map_clients=True,
    )


register_workload("cnn", CNN_WORKLOAD)
register_workload("lm", lm_workload(MICRO_LM_CONFIG))
