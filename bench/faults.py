"""Faults planted underneath a whole run, to show that the check refuses
them: the tests plant them at a test's size on the CPU, and
``bench/control.py --faults`` at a cell's own size on the chip.

Each fault patches the program (or what the engine hands back) while its
context is open; the engine has to be built inside it.

* ``state_unchanged``    — local training returns the weights it was given;
* ``half_the_clients``   — aggregation leaves out half of the round's
  clients and takes the weighted mean over the rest;
* ``half_of_each_batch`` — every local batch trains on its first half of
  samples (rows), the mean taken over those;
* ``answer_altered``     — the eval loss is scaled by 1.5 where it is made;
* ``strategies_swapped`` — each strategy's trajectories come back under
  the next strategy's name;
* ``seeds_swapped``      — likewise across the trial seeds of a call.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict
from unittest import mock


def _state_unchanged(stack: contextlib.ExitStack) -> None:
    import repro.fl.sim as sim
    stack.enter_context(mock.patch.object(
        sim, "client_update_step", lambda params, *a, **k: (params, {})))


def _half_the_clients(stack: contextlib.ExitStack) -> None:
    import jax.numpy as jnp

    import repro.fl.round as rnd
    orig = rnd.masked_weighted_mean

    def half(stacked, mask, weights=None):
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return orig(stacked, mask * keep, weights)
    stack.enter_context(mock.patch.object(rnd, "masked_weighted_mean", half))


def _wrap_workload(stack: contextlib.ExitStack, field: str,
                   wrap: Callable) -> None:
    """Whatever workload the program's trial is built on, by name or as an
    object, has the function its ``field`` factory builds wrapped by
    ``wrap``: the fault reaches every client model an engine sets up."""
    import repro.fl.sim as sim
    resolve = sim.get_workload

    def wrapped(workload):
        wl = resolve(workload)
        make = getattr(wl, field)
        return dataclasses.replace(wl, **{field: lambda ds: wrap(make(ds))})
    stack.enter_context(mock.patch.object(sim, "get_workload", wrapped))


def _half_of_each_batch(stack: contextlib.ExitStack) -> None:
    import jax.numpy as jnp

    def halved(loss):
        def first_half(params, batch):
            valid = batch["valid"]
            keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
            keep = keep.reshape(keep.shape + (1,) * (valid.ndim - 1))
            return loss(params, dict(batch, valid=jnp.where(
                keep, valid, jnp.zeros_like(valid))))
        return first_half
    _wrap_workload(stack, "make_loss", halved)


def _answer_altered(stack: contextlib.ExitStack) -> None:
    def scaled(ev):
        def altered(params, batch):
            loss, m = ev(params, batch)
            return loss * 1.5, m
        return altered
    _wrap_workload(stack, "make_eval", scaled)


def _rolled(axis: int) -> Callable[[contextlib.ExitStack], None]:
    def plant(stack: contextlib.ExitStack) -> None:
        import numpy as np

        from bench.engines import sim
        call = sim.Engine.call

        def rolled(self, i, seeds):
            return {k: np.roll(v, 1, axis=axis)
                    for k, v in call(self, i, seeds).items()}
        stack.enter_context(mock.patch.object(sim.Engine, "call", rolled))
    return plant


FAULTS: Dict[str, Callable[[contextlib.ExitStack], None]] = {
    "state_unchanged": _state_unchanged,
    "half_the_clients": _half_the_clients,
    "half_of_each_batch": _half_of_each_batch,
    "answer_altered": _answer_altered,
    "strategies_swapped": _rolled(0),
    "seeds_swapped": _rolled(1),
}


@contextlib.contextmanager
def planted(name: str):
    """The fault ``name`` in place while the context is open."""
    with contextlib.ExitStack() as stack:
        FAULTS[name](stack)
        yield
