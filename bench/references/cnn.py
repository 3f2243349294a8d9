"""Plain reference of one FL trial of the paper CNN (arXiv:2112.14244 §III-B,
Algorithm 1), in straightforward ``jax.numpy``: no kernels, no vmap, one
client trained after the other, float32 at the highest matmul precision.

It imports nothing of the program under test and takes nothing the program
made.  It rebuilds every input from the definitions the configuration
states and the trial's seed:

* class templates: per-class Gaussian fields from ``template_seed``,
  smoothed by a 5×5 wrap-around box filter and standardised;
* round t's data: key ``fold_in(fold_in(PRNGKey(seed), 1000 + t), 0)``;
  image = template[label] + ``noise`` · N(0, 1), a padded (−1) slot is zero;
* selection key: ``fold_in(…, 1)``; the strategies' scores are computed
  here in float64 from the plan's histograms;
* initial weights: He-normal from ``fold_in(PRNGKey(seed), 1)``;
* eval set: ``eval_n_per_class`` images per class from ``eval_seed``.

Semantics it holds the program to (the FL round of Algorithm 1):

* labels-only statistics: client histograms, rank-remapped σ²(L)/n
  (``labelwise``, valid iff σ² > 0), −KL(p ‖ U) with 1e-9 smoothing
  (``kl``), uniform scores (``random``); the top ``clients_per_round``
  valid clients by score, ties to the lower client index — and, where
  computed scores tie within float32's reach at the edge of the selection,
  each way of filling the open slots (see :func:`selections`);
* local training: every selected client runs ``local_epochs`` passes over
  its samples, padded to whole batches of ``batch_size``; each batch is one
  Adam step (β 0.9/0.999, ε 1e-8) on the mean cross-entropy of its valid
  samples — a batch with none gives a zero gradient and still steps;
* ``fedavg``: the trained weights' mean, weighted by each client's valid
  samples; ``fedsgd``: each client's gradient is the mean of its batches'
  gradients, their weighted mean takes one step of −lr;
* eval: mean cross-entropy and accuracy of the new global model.

``precision=CONTROL_PRECISION`` (bfloat16, the step below the float32
the configuration states) runs the same trial with weights, images,
activations and the optimizer's moments in bfloat16: the control that the
correctness check must refuse.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
CONTROL_PRECISION = "bfloat16"
NEG_INF = -1e30
# Scores this close (relative) are ties float32 arithmetic may order
# either way: about 16 float32 ulps.
TIE_RTOL = 2e-6
MAX_TIE_CHOICES = 8


def templates(cfg: Dict[str, Any]) -> np.ndarray:
    """(C, H, W, ch) float32 class templates."""
    c, s, ch = cfg["num_classes"], cfg["image_size"], cfg["channels"]
    raw = np.random.default_rng(cfg["template_seed"]).normal(size=(c, s, s, ch))
    k = 5
    padded = np.pad(raw, ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0)),
                    mode="wrap")
    smooth = np.zeros_like(raw)
    for dy in range(k):
        for dx in range(k):
            smooth += padded[:, dy:dy + s, dx:dx + s]
    smooth /= k * k
    smooth = (smooth - smooth.mean()) / (smooth.std() + 1e-9)
    return smooth.astype(np.float32)


def init_params(key, cfg: Dict[str, Any], dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    ch, c1, c2 = cfg["channels"], cfg["conv1"], cfg["conv2"]
    hid, ncls = cfg["hidden"], cfg["num_classes"]
    flat = (cfg["image_size"] // 4) ** 2 * c2

    def he(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * math.sqrt(2.0 / fan_in)).astype(dtype)

    return {"conv1": {"w": he(ks[0], (3, 3, ch, c1), 9 * ch),
                      "b": jnp.zeros((c1,), dtype)},
            "conv2": {"w": he(ks[1], (3, 3, c1, c2), 9 * c1),
                      "b": jnp.zeros((c2,), dtype)},
            "fc1": {"w": he(ks[2], (flat, hid), flat),
                    "b": jnp.zeros((hid,), dtype)},
            "fc2": {"w": he(ks[3], (hid, ncls), hid),
                    "b": jnp.zeros((ncls,), dtype)}}


def forward(p, x):
    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1"]["w"], p["conv1"]["b"])))
    x = pool(jax.nn.relu(conv(x, p["conv2"]["w"], p["conv2"]["b"])))
    x = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]


def loss_and_accuracy(p, images, labels, valid):
    logits = forward(p, images).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    w = valid.astype(jnp.float32)
    n = jnp.maximum(w.sum(), 1.0)
    acc = ((jnp.argmax(logits, -1) == labels) * w).sum() / n
    return (nll * w).sum() / n, acc


def _loss(p, images, labels, valid):
    return loss_and_accuracy(p, images, labels, valid)[0]


@functools.partial(jax.jit, static_argnames=("epochs", "lr"))
def train_client(params, images, labels, valid, epochs: int, lr: float):
    """``epochs`` passes of Adam over one client's (nb, bs, …) batches."""
    def step(carry, batch):
        p, m, v, k = carry
        g = jax.grad(_loss)(p, *batch)
        k = k + 1
        bc1 = 1.0 - B1 ** k.astype(jnp.float32)
        bc2 = 1.0 - B2 ** k.astype(jnp.float32)

        def leaf(p, m, v, g):
            g = g.astype(jnp.float32)
            m32 = B1 * m.astype(jnp.float32) + (1 - B1) * g
            v32 = B2 * v.astype(jnp.float32) + (1 - B2) * g * g
            u = -lr * (m32 / bc1) / (jnp.sqrt(v32 / bc2) + EPS)
            return ((p.astype(jnp.float32) + u).astype(p.dtype),
                    m32.astype(m.dtype), v32.astype(v.dtype))

        out = jax.tree_util.tree_map(leaf, p, m, v, g)
        pick = lambda i: jax.tree_util.tree_map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        return (pick(0), pick(1), pick(2), k), None

    def epoch(carry, _):
        return jax.lax.scan(step, carry, (images, labels, valid))[0], None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    carry = (params, zeros, zeros, jnp.zeros((), jnp.int32))
    return jax.lax.scan(epoch, carry, None, length=epochs)[0][0]


@jax.jit
def client_gradient(params, images, labels, valid):
    """Mean over the client's batches of each batch's gradient (f32)."""
    def one(acc, batch):
        g = jax.grad(_loss)(params, *batch)
        return jax.tree_util.tree_map(
            lambda a, x: a + x.astype(jnp.float32), acc, g), None
    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc = jax.lax.scan(one, zero, (images, labels, valid))[0]
    return jax.tree_util.tree_map(lambda a: a / images.shape[0], acc)


@functools.partial(jax.jit, static_argnames=("shape",))
def _round_noise(key, idx, shape):
    """Rows ``idx`` of N(0, 1) over ``shape`` = (N, n, H, W, C).  Drawn as
    (N, n·H·W·C): under JAX's partitionable threefry an element's value
    depends on the key and its flat index alone, and the 2-D form keeps a
    lane-padded (…, 28, 28) layout from multiplying the buffer."""
    flat = jax.random.normal(key, (shape[0], math.prod(shape[1:])))
    return flat[idx].reshape((idx.shape[0],) + shape[1:])


@functools.partial(jax.jit, static_argnames=("shape",))
def _eval_noise(key, shape):
    return jax.random.normal(key, shape)


def histograms(plan_t: np.ndarray, num_classes: int) -> np.ndarray:
    """(N, C) float64 label counts of one round's (N, n) plan (−1 pads)."""
    h = np.zeros((plan_t.shape[0], num_classes))
    for c in range(num_classes):
        h[:, c] = (plan_t == c).sum(-1)
    return h


def scores(strategy: str, hists: np.ndarray, key_sel) -> tuple:
    """(scores, valid) of a strategy over one round's histograms."""
    total = hists.sum(-1)
    if strategy == "random":
        s = np.asarray(jax.random.uniform(key_sel, (hists.shape[0],)),
                       np.float64)
        return s, total > 0
    if strategy == "labelwise":
        present = hists > 0
        ranks = (np.cumsum(present, -1) - 1.0) * present
        n = np.maximum(total, 1.0)
        mean = (hists * ranks).sum(-1) / n
        var = (hists * (ranks - mean[:, None]) ** 2).sum(-1) / n
        return var / n, var > 0
    if strategy == "kl":
        p = hists + 1e-9
        p = p / p.sum(-1, keepdims=True)
        kl = (p * (np.log(p) - np.log(1.0 / hists.shape[1]))).sum(-1)
        return -kl, total > 0
    raise ValueError(f"the reference has no strategy {strategy!r}")


def selections(strategy: str, hists: np.ndarray, key_sel,
               n_sel: int) -> List[tuple]:
    """[(idx, live)]: the clients asked to train and which of them count.

    The top ``n_sel`` valid clients by score, ties to the lower index.  A
    computed score (not ``random``'s draw) that lies within ``TIE_RTOL`` of
    the last selected one is a tie the program's float32 arithmetic may
    resolve either way — two clients with the same label multiset in other
    classes have equal scores, which float32 sums in another order can split
    by an ulp — so every choice of the tied clients for the open slots is
    returned, the index-ordered one first."""
    s, valid = scores(strategy, hists, key_sel)
    masked = np.where(valid, s, NEG_INF)
    order = np.argsort(-masked, kind="stable")
    first = [(order[:n_sel], valid[order[:n_sel]].astype(np.float64))]
    if (strategy == "random" or n_sel >= len(order)
            or not valid[order[n_sel - 1]]):
        return first
    edge = masked[order[n_sel - 1]]
    tied = [int(c) for c in order if valid[c]
            and abs(masked[c] - edge) <= TIE_RTOL * abs(edge)]
    above = [int(c) for c in order[:n_sel] if int(c) not in tied]
    out = []
    for combo in itertools.combinations(tied, n_sel - len(above)):
        chosen = set(above) | set(combo)
        idx = np.array([c for c in order if int(c) in chosen])
        out.append((idx, np.ones(n_sel)))
        if len(out) == MAX_TIE_CHOICES:
            break
    return out


def round_keys(seed: int, t: int):
    kt = jax.random.fold_in(jax.random.PRNGKey(seed), 1000 + t)
    return jax.random.fold_in(kt, 0), jax.random.fold_in(kt, 1)


def selected_samples(cfg, traffic, plan: np.ndarray, strategy: str,
                     seed: int) -> int:
    """Valid samples the selected clients hold, summed over the rounds."""
    total = 0
    for t in range(traffic["rounds_per_call"]):
        plan_t = plan[t % plan.shape[0]]
        hists = histograms(plan_t, cfg["num_classes"])
        idx, live = selections(strategy, hists, round_keys(seed, t)[1],
                               cfg["clients_per_round"])[0]
        total += int((hists.sum(-1)[idx] * live).sum())
    return total


def forward_flops(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Multiply-add FLOPs (2 per MAC) of one sample's forward pass, by layer:
    conv 3×3 SAME at full resolution, then at half, then the two dense
    layers.  Biases, activations and pooling are not counted."""
    s, ch = cfg["image_size"], cfg["channels"]
    c1, c2, hid, ncls = cfg["conv1"], cfg["conv2"], cfg["hidden"], cfg["num_classes"]
    flat = (s // 4) ** 2 * c2
    return {"conv1": 2 * s * s * c1 * 9 * ch,
            "conv2": 2 * (s // 2) ** 2 * c2 * 9 * c1,
            "fc1": 2 * flat * hid,
            "fc2": 2 * hid * ncls}


def train_flops_per_sample(cfg: Dict[str, Any]) -> int:
    """Forward + backward FLOPs one trained sample requires: the forward, the
    weight gradient of every layer (as much again), and the input gradient
    of every layer but the first (the image needs none)."""
    fwd = forward_flops(cfg)
    return 3 * sum(fwd.values()) - fwd["conv1"]


def num_params(cfg: Dict[str, Any]) -> int:
    """The f32 parameters one client reports: what aggregation reads."""
    s, ch = cfg["image_size"], cfg["channels"]
    c1, c2, hid, ncls = cfg["conv1"], cfg["conv2"], cfg["hidden"], cfg["num_classes"]
    flat = (s // 4) ** 2 * c2
    return (9 * ch * c1 + c1 + 9 * c1 * c2 + c2 + flat * hid + hid
            + hid * ncls + ncls)


def trial_train_flops(cfg, traffic, plan: np.ndarray, strategy: str,
                      seed: int) -> int:
    """Forward + backward FLOPs the trial's selected clients' local training
    requires: every valid sample they hold, once per local epoch (once under
    fedsgd, one gradient a client).  Padding is not counted."""
    passes = 1 if traffic["aggregation"] == "fedsgd" else cfg["local_epochs"]
    return (train_flops_per_sample(cfg) * passes
            * selected_samples(cfg, traffic, plan, strategy, seed))


def run_trial(cfg: Dict[str, Any], traffic: Dict[str, Any], plan: np.ndarray,
              strategy: str, seed: int, precision: str = "float32",
              ties: bool = True) -> List[Dict[str, np.ndarray]]:
    """One trial's (rounds,) accuracy, loss and clients trained per round —
    one trajectory for each way of resolving float32-ambiguous selection
    ties (:func:`selections`), the index-ordered one first; ``ties=False``
    gives that one alone."""
    dtype = jnp.dtype(precision)
    mm = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(mm):
        return _run_trial(cfg, traffic, plan, strategy, seed, dtype, ties)


def _run_trial(cfg, traffic, plan, strategy, seed, dtype, ties):
    tpl = jnp.asarray(templates(cfg))
    ncls, bs = cfg["num_classes"], cfg["batch_size"]
    n_sel, noise = cfg["clients_per_round"], cfg["noise"]
    fedsgd = traffic["aggregation"] == "fedsgd"
    rounds = traffic["rounds_per_call"]
    ev_labels = np.tile(np.arange(ncls), cfg["eval_n_per_class"])
    ev_images = (tpl[ev_labels] + noise * _eval_noise(
        jax.random.PRNGKey(cfg["eval_seed"]),
        (len(ev_labels),) + tpl.shape[1:])).astype(dtype)
    ev_labels = jnp.asarray(ev_labels)
    ev_valid = jnp.ones(ev_labels.shape, bool)
    eval_fn = jax.jit(loss_and_accuracy)
    plans = [plan[t % plan.shape[0]] for t in range(rounds)]
    keys = [round_keys(int(seed), t) for t in range(rounds)]
    choices = [selections(strategy, histograms(plans[t], ncls), keys[t][1],
                          n_sel)[:None if ties else 1] for t in range(rounds)]

    def step(params, t, idx, live):
        plan_t = plans[t]
        n, n_max = plan_t.shape
        nb = -(-n_max // bs)
        lab = np.full((len(idx), nb * bs), -1, np.int32)
        lab[:, :n_max] = plan_t[idx]
        valid = lab >= 0
        noise_sel = _round_noise(keys[t][0], jnp.asarray(idx),
                                 (n, n_max) + tpl.shape[1:])
        imgs = (tpl[np.maximum(plan_t[idx], 0)] + noise * noise_sel) * (
            plan_t[idx] >= 0)[..., None, None, None]
        imgs = jnp.pad(imgs, ((0, 0), (0, nb * bs - n_max), (0, 0), (0, 0),
                              (0, 0))).astype(dtype)
        shape_b = lambda x: x.reshape((len(idx), nb, bs) + x.shape[2:])
        imgs, lab_b, val_b = shape_b(imgs), shape_b(jnp.asarray(
            np.maximum(lab, 0))), shape_b(jnp.asarray(valid))
        w = live * valid.sum(-1)
        denom = max(w.sum(), 1e-12)
        if fedsgd:
            outs = [client_gradient(params, imgs[k], lab_b[k], val_b[k])
                    for k in range(len(idx))]
        else:
            outs = [train_client(params, imgs[k], lab_b[k], val_b[k],
                                 epochs=cfg["local_epochs"], lr=cfg["lr"])
                    for k in range(len(idx))]
        mean = jax.tree_util.tree_map(
            lambda *xs: sum(jnp.float32(wk) * x.astype(jnp.float32)
                            for wk, x in zip(w, xs)) / jnp.float32(denom),
            *outs)
        if live.sum() > 0:
            if fedsgd:
                params = jax.tree_util.tree_map(
                    lambda p, g: (p.astype(jnp.float32)
                                  - cfg["lr"] * g).astype(dtype), params, mean)
            else:
                lr_s = cfg["server_lr"]
                params = jax.tree_util.tree_map(
                    lambda p, a: (p.astype(jnp.float32) + lr_s * (
                        a - p.astype(jnp.float32))).astype(dtype),
                    params, mean)
        loss, acc = eval_fn(params, ev_images, ev_labels, ev_valid)
        return params, (float(acc), float(loss), float(live.sum()))

    def paths(params, t, done):
        if t == rounds:
            yield {k: np.asarray([r[i] for r in done]) for i, k in
                   enumerate(("accuracy", "loss", "num_selected"))}
            return
        for idx, live in choices[t]:
            new, rec = step(params, t, idx, live)
            yield from paths(new, t + 1, done + [rec])

    params = init_params(jax.random.fold_in(jax.random.PRNGKey(int(seed)), 1),
                         cfg, dtype)
    return list(paths(params, 0, []))
