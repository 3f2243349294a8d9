"""The CNN's convolution: a one-channel input is summed over its window
taps, every other input goes through ``lax.conv``; both equal
``lax.conv_general_dilated`` at ``Precision.HIGHEST``, in values and in the
weight, bias and input gradients, alone and vmapped over clients with
per-client weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cnn

KEY = jax.random.PRNGKey(0)
RTOL = 1e-5


def _lax_conv(x, w, b):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) + b


def _inputs(lead, cin, cout=32, batch=4, size=28):
    kx, kw, kb = jax.random.split(KEY, 3)
    x = jax.random.normal(kx, lead + (batch, size, size, cin))
    w = jax.random.normal(kw, lead + (3, 3, cin, cout)) / 3.0
    b = jax.random.normal(kb, lead + (cout,))
    return x, w, b


def _value_and_grads(conv):
    def loss(w, b, x):
        y = conv(x, w, b)
        return jnp.sum(jnp.tanh(y)), y
    return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= RTOL, err


@pytest.mark.parametrize("lead,cin", [((), 1), ((2, 3), 1), ((2,), 3)],
                         ids=["unbatched", "2x3_clients", "multi_channel"])
def test_equals_lax_conv(lead, cin):
    x, w, b = _inputs(lead, cin)
    f_got, f_want = _value_and_grads(cnn._conv), _value_and_grads(_lax_conv)
    for _ in lead:
        f_got, f_want = jax.vmap(f_got), jax.vmap(f_want)
    with jax.default_matmul_precision("highest"):
        got, y = f_got(w, b, x)
        want, yw = f_want(w, b, x)
    _close(y, yw)
    for g, gw in zip(got, want):          # weights, bias, image
        _close(g, gw)


@pytest.mark.parametrize("lead,cin", [((2, 3), 1), ((2,), 3)],
                         ids=["one_channel_2x3_clients", "multi_channel"])
def test_only_multi_channel_takes_lax_conv(lead, cin):
    """With one input channel and per-client weights under two vmaps there
    is no convolution at all, so no grouped one of a channel per client."""
    x, w, b = _inputs(lead, cin)
    f = _value_and_grads(cnn._conv)
    for _ in lead:
        f = jax.vmap(f)
    jaxpr = str(jax.make_jaxpr(f)(w, b, x))
    assert ("conv_general_dilated" in jaxpr) == (cin > 1)
