"""Plain reference of the SmallThinker decoder (the published
``config.json`` of ``PowerInfer/SmallThinker-21BA3B-Instruct``;
arXiv:2507.20984) as one chip's share of an expert-parallel job:
``jax.numpy`` in float32, no kernels, no ``shard_map``, nothing of the
program.

On x [T, D] of one sequence, layer l, ``N(x) = x / sqrt(mean(x^2) + eps) *
w``, no biases::

    h   = N_1(x) ;  r = h W_r        the router's logits, from the
                                     PRE-attention h, float32 at highest
    q, k, v = h W_q, h W_k, h W_v    heads of head_dim
    if rope_layout[l]:  rotary on ALL of a head's dims, halves (x1, x2) ->
                        (x1 cos - x2 sin, x2 cos + x1 sin), theta rope_theta
    s_ij = q_i . k_j / sqrt(head_dim), key-value head n // (heads / kv
           heads) for query head n ;  j <= i, and where
           sliding_window_layout[l]:  i - j < sliding_window_size
    x'  = x + softmax_j(s) v W_o
    h'  = N_2(x')
    top = the k largest of r ;  g = softmax over those k logits
    y   = sum_{e in top, e held} g_e W_down,e (relu(W_gate,e h') * W_up,e h')
    out = x' + y

Embedding, final ``N``, untied head; the loss is the mean token
cross-entropy.

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<i>/{attn_norm, ffn_norm}/scale``, ``layers_<i>/attn/{q_proj,
k_proj, v_proj, o_proj}/kernel``, ``layers_<i>/moe/kernel`` (the router),
``moe/routed_{gate,up,down}/experts`` [held, in, out]; ``norm/scale``,
``lm_head/kernel``.

Departures from the published model, each the configuration's: only the
experts of ``spec["held_experts"]`` exist (the router still scores all
``moe_num_primary_experts`` and its softmax is over a token's k, held or
not); the vocabulary is the slice the configuration keeps; no auxiliary
loss and no "secondary experts" (the published config has no key for
them).

Written for a chip the program has filled and a sequence of 16,384 tokens:
a sequence at a time (``lax.map``), each sequence's layer recomputed in the
backward pass; attention ``spec["attn_block"]`` queries at a time against
ALL the sequence's keys, the mask one comparison of positions (no key is
cut away: the program cuts, so the two are independent), each block
recomputed; every held expert a dense product over all rows of a sequence,
masked by the routing, each recomputed; the head and the loss in blocks of
rows, so that no [T, vocabulary] array is alive.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def extras(spec, batch, key):
    return None


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# ---- attention -------------------------------------------------------------

def _rotary(x, theta):
    """x [T, heads, dim]: every dim turned by position * frequency, the two
    halves of a head apart."""
    t, _, dim = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(p, h, spec, layer):
    """One sequence: h [T, D] -> [T, D]."""
    t = h.shape[0]
    nh, nkv, hd = (int(spec[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    q = (h @ p["q_proj"]["kernel"]).reshape(t, nh, hd)
    k = (h @ p["k_proj"]["kernel"]).reshape(t, nkv, hd)
    v = (h @ p["v_proj"]["kernel"]).reshape(t, nkv, hd)
    if spec["rope_layout"][layer]:
        theta = float(spec["rope_theta"])
        q, k = _rotary(q, theta), _rotary(k, theta)
    window = (int(spec["sliding_window_size"])
              if spec["sliding_window_layout"][layer] else None)
    # every query head beside the key-value head it reads
    k, v = (jnp.repeat(x, nh // nkv, axis=1) for x in (k, v))
    block = min(int(spec.get("attn_block", 256)), t)
    pad = -t % block
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(xs):
        qb, rows = xs                       # [block, heads, hd], [block]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        seen = keys[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - keys[None, :] < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    # the rows of padding sit at positions past the end: they see every key
    # and their output is cut away
    out = lax.map(one_block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, nh, hd),
        jnp.arange(t + pad).reshape(-1, block)))
    return out.reshape(-1, nh * hd)[:t] @ p["o_proj"]["kernel"]


# ---- experts ---------------------------------------------------------------

def routing(h, w_router, spec):
    """h [T, D] (the layer's normalised INPUT) -> the combine weight of
    every routed expert, [T, E]: the softmax over the logits of a token's
    k largest, 0 elsewhere."""
    logits = jnp.dot(h, w_router, precision=lax.Precision.HIGHEST)
    left, picked = logits, jnp.zeros(logits.shape, bool)
    for _ in range(int(spec["moe_num_active_primary_experts"])):
        one = jax.nn.one_hot(jnp.argmax(left, axis=-1), logits.shape[-1],
                             dtype=bool)
        picked = picked | one
        left = jnp.where(one, -jnp.inf, left)
    return jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1)


@jax.checkpoint
def _reglu(h, w_gate, w_up, w_down):
    return (jnp.maximum(h @ w_gate, 0.0) * (h @ w_up)) @ w_down


def experts(p, h, weights, spec):
    """The routed experts held here, each over all rows: h [T, D] is the
    post-attention normalised state, ``weights`` [T, E] the routing."""
    out = jnp.zeros_like(h)
    for slot, e in enumerate(spec["held_experts"]):
        y = _reglu(h, p["routed_gate"]["experts"][slot],
                   p["routed_up"]["experts"][slot],
                   p["routed_down"]["experts"][slot])
        out = out + y * weights[:, int(e)][:, None]
    return out


# ---- the model ---------------------------------------------------------------

def layer(p, x, spec, index):
    """One sequence through layer ``index``: x [T, D] -> [T, D]."""
    eps = float(spec["rms_norm_eps"])
    h = _norm(x, p["attn_norm"]["scale"], eps)
    weights = routing(h, p["moe"]["kernel"], spec)
    x = x + attention(p["attn"], h, spec, index)
    return x + experts(p["moe"], _norm(x, p["ffn_norm"]["scale"], eps),
                       weights, spec)


def _head_nll(params, spec, x_targets):
    """A block of rows: the summed token cross-entropy."""
    x, targets = x_targets
    x = _norm(x, params["norm"]["scale"], float(spec["rms_norm_eps"]))
    z = x @ params["lm_head"]["kernel"]
    picked = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(z, axis=1) - picked)


def hidden(params, tokens, spec):
    """tokens [B, T] -> the last layer's output [B, T, D], a sequence and a
    layer at a time."""
    x = params["embed"]["embedding"][tokens]
    for i in range(int(spec["num_hidden_layers"])):
        one = partial(layer, params[f"layers_{i}"], spec=spec, index=i)
        x = lax.map(jax.checkpoint(one), x)
    return x


def loss(params, batch, spec, extra=None):
    x = hidden(params, batch["tokens"], spec)
    b, t, d = x.shape
    rows = b * t
    block = min(int(spec.get("head_block", 2048)), rows)
    if rows % block:
        block = rows
    nll = lax.map(jax.checkpoint(partial(_head_nll, params, spec)),
                  (x.reshape(-1, block, d),
                   batch["targets"].reshape(-1, block)))
    return jnp.sum(nll) / rows
