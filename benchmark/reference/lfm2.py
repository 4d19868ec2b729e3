"""Plain reference of the LFM2 decoder with routed experts (the published
``config.json`` of ``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``; the
block's equations as ISSUE 50 hands them down from HF
``modeling_lfm2_moe.py``) as one chip's share of an expert-parallel job:
``jax.numpy`` in float32, no kernels, no ``shard_map``, nothing of the
program.

On x [T, D] of one sequence, layer l, ``N(x) = x / sqrt(mean(x^2) + eps) *
w``, no bias anywhere; ``spec`` holds the published keys::

    u = N_op(x)
    layer_types[l] "conv":   B, C, z = the three [T, D] thirds of u W_in,
                             in that order
                             a   = B * z
                             c_t = sum_{j=0..K-1} w[j] a_{t-(K-1)+j},
                                   a_s = 0 for s < 0   (K = conv_L_cache)
                             m   = (C * c) W_out
        "full_attention":    q, k, v = u W_q [T, H, d], u W_k, u W_v [T, G, d]
                             q, k <- N_q(q), N_k(k) over a head's d dims
                             (one gain [d] for all heads), THEN rotary:
                             halves (x1, x2) of ALL d dims -> (x1 cos - x2
                             sin, x2 cos + x1 sin), frequencies
                             rope_theta^(-2i/d), positions 0..T-1
                             s_ij = q_i . k_j / sqrt(d), j <= i, key-value
                             head n // (H / G) for query head n
                             m   = concat_n(softmax_j(s) v) W_out
    x' = x + m ;  h = N_ffn(x')
    l < num_dense_layers:  y = W_down (silu(W_gate h) * W_up h)
    otherwise:  s = sigmoid(h W_r), float32 at highest
                sel = the num_experts_per_tok largest of s + b (``lax.top_k``;
                      b the ``expert_bias``: it chooses and no more)
                w_e = routed_scaling_factor s_e / (sum_sel s + 1e-6)
                y   = sum_{e in sel, e held} w_e Expert_e(h)
    out = x' + y

Embedding, final ``N``, and the head is the SAME embedding, transposed
(tied); the loss is the mean token cross-entropy.

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<i>/{operator_norm, ffn_norm}/scale``, ``layers_<i>/conv/{in_proj,
out_proj}/kernel`` and ``conv/taps/kernel`` [K, D] (a conv layer),
``layers_<i>/attn/{q_proj, k_proj, v_proj, out_proj}/kernel`` and
``attn/{q_layernorm, k_layernorm}/scale`` (an attention layer),
``layers_<i>/ffn/{gate_proj, up_proj, down_proj}/kernel`` (a dense layer),
``layers_<i>/moe/kernel`` (the router), ``moe/bias`` (its selection bias),
``moe/routed_{gate,up,down}/experts`` [held, in, out];
``embedding_norm/scale``.

Departures from the published model, each the configuration's: only the
experts of ``spec["held_experts"]`` exist (the router still scores all
``num_experts``, the selection and the renormalisation are over a token's
k, held or not); the vocabulary is the slice the configuration keeps;
``expert_bias`` is a parameter leaf here where the published block keeps a
buffer (its gradient is exactly zero: ``stop_gradient``), and the rule that
would move it is left out; what the config has no key for is left out
(``assumed`` in the configuration's file).

Written for a chip the program has filled and sequences of 8,192 tokens: a
sequence at a time (``lax.map``), each sequence's layer recomputed in the
backward pass; attention a key-value head's query heads at a time and
``spec["attn_block"]`` queries at a time against ALL the sequence's keys,
the mask one comparison of positions, each group and each block
recomputed; the dense layer in blocks of rows; every held expert a dense
product over all rows of a sequence, masked by the routing, each
recomputed; the head and the loss in blocks of rows.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# the published block's normaliser of the k chosen scores
NORM_EPS = 1e-6


def extras(spec, batch, key):
    return None


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _row_blocks(fn, x, block):
    """``fn`` over blocks of ``block`` rows of x [T, ...], each recomputed
    in the backward pass; one block where T is no multiple."""
    t = x.shape[0]
    block = min(int(block), t)
    if t % block:
        block = t
    out = lax.map(jax.checkpoint(fn), x.reshape((-1, block) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


# ---- the two mixers ----------------------------------------------------------

def _delayed(a, steps: int):
    """``a`` [T, D] ``steps`` tokens later: row t holds ``a[t - steps]``,
    zeros before the sequence's start."""
    if not steps:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:steps]), a[:-steps]], axis=0)


def short_conv(p, u, spec):
    """One sequence: u [T, D] -> [T, D]. Tap j weighs the token ``K - 1 -
    j`` back: the last tap the current one."""
    d, taps = u.shape[1], int(spec["conv_L_cache"])
    bcz = u @ p["in_proj"]["kernel"]
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    a, w = b * z, p["taps"]["kernel"]
    conv = sum(w[j] * _delayed(a, taps - 1 - j) for j in range(taps))
    return (c * conv) @ p["out_proj"]["kernel"]


def _rotary(x, theta: float):
    """x [T, heads, d]: all d dims turned by position x frequency, their
    two halves a pair's two parts."""
    t, d = x.shape[0], x.shape[-1]
    freq = jnp.asarray([float(theta) ** (-2.0 * i / d)
                        for i in range(d // 2)], jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _group_attention(u, theta, eps, block, q_gain, k_gain, weights):
    """The query heads of ONE key-value head: u [T, D]; ``weights``: w_q
    [D, R, d], w_k and w_v [D, d] -> their weighted sums, [T, R, d]."""
    w_q, w_k, w_v = weights
    t, hd = u.shape[0], w_k.shape[-1]
    q = jnp.einsum("td,drk->trk", u, w_q)
    k, v = u @ w_k, u @ w_v
    q, k = _norm(q, q_gain, eps), _norm(k, k_gain, eps)
    q = _rotary(q, theta)
    k = _rotary(k[:, None, :], theta)[:, 0, :]
    block = min(block, t)
    pad = -t % block
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(xs):
        qb, rows = xs                       # [block, R, hd], [block]
        s = jnp.einsum("qrd,kd->rqk", qb, k) * hd ** -0.5
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(s, axis=-1), v)

    # the rows of padding sit at positions past the end: they see every key
    # and their output is cut away
    out = lax.map(one_block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            (-1, block) + q.shape[1:]),
        jnp.arange(t + pad).reshape(-1, block)))
    return out.reshape((-1,) + q.shape[1:])[:t]


def attention(p, u, spec):
    """One sequence: u [T, D] -> [T, D]. Query head n reads key-value head
    n // (H / G): the H / G query heads of a key-value head at a time
    (``lax.map`` over the key-value heads), each such group recomputed in
    the backward pass, then the output projection over all heads."""
    t, d = u.shape
    nh, nkv = (int(spec[k]) for k in ("num_attention_heads",
                                      "num_key_value_heads"))
    hd = d // nh
    group = jax.checkpoint(partial(
        _group_attention, u, float(spec["rope_theta"]),
        float(spec["norm_eps"]), int(spec.get("attn_block", 256)),
        p["q_layernorm"]["scale"], p["k_layernorm"]["scale"]))
    # every projection's columns a key-value head at a time, that head first
    by_group = lambda w, *dims: jnp.moveaxis(
        w["kernel"].reshape((d, nkv) + dims), 1, 0)
    out = lax.map(group, (by_group(p["q_proj"], nh // nkv, hd),
                          by_group(p["k_proj"], hd),
                          by_group(p["v_proj"], hd)))     # [G, T, R, d]
    return jnp.moveaxis(out, 0, 1).reshape(t, nh * hd) @ (
        p["out_proj"]["kernel"])


# ---- the two kinds of feed-forward -----------------------------------------

@jax.checkpoint
def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _ffn(p, h):
    return _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"])


def routing(h, w_router, bias, spec):
    """h [T, D] -> the combine weight of every routed expert, [T, E]: the
    sigmoid scores of the k chosen by ``score + bias`` over their sum plus
    1e-6, times the scaling factor, 0 elsewhere. The bias chooses; the
    weights are the unbiased scores."""
    scores = jax.nn.sigmoid(
        jnp.dot(h, w_router, precision=lax.Precision.HIGHEST))
    _, which = lax.top_k(scores + lax.stop_gradient(bias),
                         int(spec["num_experts_per_tok"]))
    top = jnp.take_along_axis(scores, which, axis=-1)
    if spec["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + NORM_EPS)
    top = top * float(spec["routed_scaling_factor"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, which].set(top)


def experts(p, h, weights, spec):
    """The routed experts held here, each over all rows: h [T, D],
    ``weights`` [T, E] the routing. No shared expert."""
    out = jnp.zeros_like(h)
    for slot, e in enumerate(spec["held_experts"]):
        y = _swiglu(h, p["routed_gate"]["experts"][slot],
                    p["routed_up"]["experts"][slot],
                    p["routed_down"]["experts"][slot])
        out = out + y * weights[:, int(e)][:, None]
    return out


# ---- the model ---------------------------------------------------------------

def layer(p, x, spec, index):
    """One sequence through layer ``index``: x [T, D] -> [T, D]."""
    eps = float(spec["norm_eps"])
    u = _norm(x, p["operator_norm"]["scale"], eps)
    if spec["layer_types"][index] == "conv":
        x = x + short_conv(p["conv"], u, spec)
    else:
        x = x + attention(p["attn"], u, spec)
    h = _norm(x, p["ffn_norm"]["scale"], eps)
    if index < int(spec["num_dense_layers"]):
        return x + _row_blocks(partial(_ffn, p["ffn"]), h,
                               spec.get("mlp_block", 2048))
    weights = routing(h, p["moe"]["kernel"], p["moe"]["bias"], spec)
    return x + experts(p["moe"], h, weights, spec)


def _head_nll(params, spec, x_targets):
    """A block of rows: the summed token cross-entropy, the logits from
    the embedding itself."""
    x, targets = x_targets
    x = _norm(x, params["embedding_norm"]["scale"], float(spec["norm_eps"]))
    z = x @ params["embed"]["embedding"].T
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
