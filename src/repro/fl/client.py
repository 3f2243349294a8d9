"""Client-side local training (paper Eq. 2, Algorithm 1 lines 17–24).

``local_train`` runs t local epochs of minibatch gradient descent entirely
inside jit (lax.scan over epochs × batches), so the FL round can vmap it over
the *selected* clients only — the unselected clients never compute, which is
the paper's resource-saving claim made literal.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.optim import apply_updates

Array = jax.Array
PyTree = Any
LossFn = Callable[[PyTree, Dict[str, Array]], Tuple[Array, Dict[str, Array]]]


def local_train(params: PyTree, opt, batches: Dict[str, Array],
                loss_fn: LossFn, local_epochs: int) -> Tuple[PyTree, Dict[str, Array]]:
    """batches: leaves shaped (n_batches, batch_size, ...)."""
    opt_state = opt.init(params)

    def one_batch(carry, batch):
        p, st = carry
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        with jax.named_scope("opt.update"):
            ups, st = opt.update(grads, st, p)
            p = apply_updates(p, ups)
        return (p, st), loss

    def one_epoch(carry, _):
        carry, losses = jax.lax.scan(one_batch, carry, batches)
        return carry, losses.mean()

    (params, _), epoch_losses = jax.lax.scan(
        one_epoch, (params, opt_state), None, length=local_epochs)
    return params, {"loss": epoch_losses[-1]}


def local_gradient(params: PyTree, batches: Dict[str, Array],
                   loss_fn: LossFn) -> Tuple[PyTree, Dict[str, Array]]:
    """FedSGD client: one full-data gradient (mean over batches)."""
    def one_batch(acc, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return acc, loss

    zero = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc, losses = jax.lax.scan(one_batch, zero, batches)
    nb = losses.shape[0]
    grads = jax.tree_util.tree_map(lambda a: a / nb, acc)
    return grads, {"loss": losses.mean()}
