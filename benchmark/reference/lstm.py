"""Plain reference of the PTB word-level LSTM of Zaremba, Sutskever &
Vinyals 2014: ``jax.numpy`` in float32, no kernels, no ``shard_map``,
nothing of the program.

    x_t = drop(E[token_t])
    for each layer:  i, f, g, o = sigma(x W_ii + h W_hi + b_i), sigma(.. f),
                                  tanh(.. g), sigma(.. o)
                     c = f * c + i * g ;  h = o * tanh(c) ;  x = drop(h)
    logits_t = x_t W + b ;  loss = mean over rows and steps of the softmax
    cross-entropy

with zero initial state for every batch, as the program runs it (the
reference carries the state from batch to batch; the program's trainer
does not, which this follows and PERF.md notes). Parameters come as the
tree the flax model keeps: ``Embed_0/embedding``, ``OptimizedLSTMCell_l``
with ``ii, if, ig, io`` (kernels) and ``hi, hf, hg, ho`` (kernel and
bias), ``Dense_0``.

Dropout (keep probability ``dropout_keep``, inverted scaling) is applied
to the embedding output and after every layer. Its masks are inputs of
the loss: ``extras`` draws them with flax's own ``Dropout`` from the key
the step was given, so that the program's step and this loss see the same
masks. That is the one thing here that is not ``jax.numpy``, and it
computes nothing of the model.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class _Masks(nn.Module):
    """One ``Dropout`` applied ``count`` times to ones: the masks, scaled."""
    rate: float
    count: int

    @nn.compact
    def __call__(self, ones):
        drop = nn.Dropout(self.rate, deterministic=False)
        return [drop(ones) for _ in range(self.count)]


def extras(spec, batch, key):
    keep = float(spec["dropout_keep"])
    if keep >= 1.0:
        return None
    b, t = batch["tokens"].shape
    ones = jnp.ones((b, t, int(spec["hidden_size"])), jnp.float32)
    layers = int(spec["num_layers"])
    return _Masks(1.0 - keep, layers + 1).apply(
        {}, ones, rngs={"dropout": key})


def _layer(p, x):
    """x [B, T, H] -> h [B, T, H]; zero initial state."""
    b, _, h = x.shape

    def step(carry, x_t):
        c, hid = carry

        def gate(name):
            return (x_t @ p["i" + name]["kernel"]
                    + hid @ p["h" + name]["kernel"] + p["h" + name]["bias"])

        i, f = jax.nn.sigmoid(gate("i")), jax.nn.sigmoid(gate("f"))
        g, o = jnp.tanh(gate("g")), jax.nn.sigmoid(gate("o"))
        c = f * c + i * g
        hid = o * jnp.tanh(c)
        return (c, hid), hid

    zero = jnp.zeros((b, h))
    _, hs = lax.scan(step, (zero, zero), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def loss(params, batch, spec, extra=None):
    masks = extra if extra is not None else [1.0] * (
        int(spec["num_layers"]) + 1)
    x = params["Embed_0"]["embedding"][batch["tokens"]]
    x = x * masks[0]
    for layer in range(int(spec["num_layers"])):
        x = _layer(params[f"OptimizedLSTMCell_{layer}"], x)
        x = x * masks[layer + 1]
    d = params["Dense_0"]
    z = x @ d["kernel"] + d["bias"]
    picked = jnp.take_along_axis(z, batch["targets"][..., None], axis=2)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=2) - picked)
