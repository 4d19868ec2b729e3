"""Plain reference of a feed-forward image classifier: ``jax.numpy`` and
``lax`` in float32, no kernels, no ``shard_map``, nothing of the program.

The network is the configuration's ``layers`` list, read in order:
``["conv", features, k, pad]``, ``["bn"]``, ``["relu"]``,
``["maxpool", k, stride, pad_hi]``, ``["avgpool", k, stride, pad_hi]``,
``["flatten"]``, ``["dense", features]``. VGG-16 (Simonyan & Zisserman,
configuration D) with batch normalisation after every convolution is
thirteen conv-bn-relu triples, five 2x2 max-pools and one dense head, as
the reference's ``VGG/models/vgg.py`` has it for CIFAR.

Parameters come as the tree the flax model keeps: ``Conv_i``/``Dense_i``
with ``kernel`` (HWIO, or [in, out]) and ``bias``; ``BatchNorm_i`` with
``scale`` and ``bias``; numbered in order of appearance. Batch
normalisation is in training mode: the statistics of this worker's own
rows, biased variance, eps 1e-5. The loss is the mean softmax
cross-entropy over the rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def extras(spec, batch, key):
    """Nothing random in this network."""
    return None


def _pool(x, k, stride, pad_hi, init, op):
    return lax.reduce_window(
        x, init, op, (1, k, k, 1), (1, stride, stride, 1),
        ((0, 0), (0, pad_hi), (0, pad_hi), (0, 0)))


def logits(params, image, spec):
    count = {"Conv": 0, "BatchNorm": 0, "Dense": 0}

    def take(kind):
        p = params[f"{kind}_{count[kind]}"]
        count[kind] += 1
        return p

    x = image
    for layer in spec["layers"]:
        op = layer[0]
        if op == "conv":
            p, pad = take("Conv"), int(layer[3])
            x = lax.conv_general_dilated(
                x, p["kernel"], (1, 1), ((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["bias"]
        elif op == "bn":
            p = take("BatchNorm")
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
            x = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
        elif op == "relu":
            x = jnp.maximum(x, 0)
        elif op == "maxpool":
            x = _pool(x, int(layer[1]), int(layer[2]), int(layer[3]),
                      -jnp.inf, lax.max)
        elif op == "avgpool":
            k = int(layer[1])
            x = _pool(x, k, int(layer[2]), int(layer[3]), 0.0, lax.add)
            x = x / (k * k)
        elif op == "flatten":
            x = x.reshape((x.shape[0], -1))
        elif op == "dense":
            p = take("Dense")
            x = x @ p["kernel"] + p["bias"]
        else:
            raise ValueError(f"unknown layer {layer!r}")
    return x.astype(jnp.float32)


def loss(params, batch, spec, extra=None):
    z = logits(params, batch["image"], spec)
    picked = jnp.take_along_axis(z, batch["label"][:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=1) - picked)
