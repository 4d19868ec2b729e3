"""What the decoder models, their expert layer (``models/moe.py``) and
their attention (``models/attention.py``) share and that is neither: the
plain-gain RMSNorm, a bias-free projection's kernel for whoever applies it
as a plain function, the gated feed-forward block and the depthwise causal
convolution (``models/qwen3_next.py``'s delta-rule inputs,
``models/lfm2.py``'s short-convolution mixer). No model file is imported
here.

Flax names a parameter by the attribute and class names on its path, not by
the Python module a class lives in: ``RMSNorm`` is ``RMSNorm`` in every
model's tree.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + self.eps)
        return (y * scale).astype(self.dtype)


class Kernel(nn.Module):
    """A bias-free projection's ``kernel`` [in, out], for whoever applies it
    as a plain function."""
    features: int

    @nn.compact
    def __call__(self, fan_in: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (fan_in, self.features))


def causal_conv(x, w):
    """Depthwise causal convolution, left-padded, no bias: ``y_t = sum_j
    w[j] x[t - (K - 1) + j]``. x [T, C]; w [K, C]."""
    taps, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * w[j] for j in range(taps))


# a gated expert's activation, by the published ``hidden_act``
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def swiglu(x, w_gate, w_up, w_down, act=jax.nn.silu):
    return (act(x @ w_gate) * (x @ w_up)) @ w_down


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``. ``by_sequence``: x [B, T, D] a
    sequence at a time, each recomputed in the backward pass, so that the
    [T, width] intermediates of one sequence are all that is alive (the
    dense layer's width is over five times the hidden size)."""
    width: int
    dtype: Any = jnp.float32
    by_sequence: bool = False

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w = [Kernel(f, name=n)(i).astype(self.dtype) for n, i, f in (
            ("gate_proj", d, self.width), ("up_proj", d, self.width),
            ("down_proj", self.width, d))]
        x = x.astype(self.dtype)
        if self.by_sequence and x.ndim == 3:
            return lax.map(jax.checkpoint(lambda s: swiglu(s, *w)), x)
        return swiglu(x, *w)
