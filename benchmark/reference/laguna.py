"""Plain reference of the Laguna decoder (the published ``config.json`` of
``poolside/Laguna-XS.2``, ``model_type: laguna``) as one chip's share of an
expert-parallel job: ``jax.numpy`` in float32, no kernels, no ``shard_map``,
nothing of the program.

On x [T, D] of one sequence, layer l, ``N(x) = x / sqrt(mean(x^2) + eps) *
w``, no biases; ``spec`` holds the published keys::

    h   = N_1(x) ;  H = num_attention_heads_per_layer[l]
    q, k, v = h W_q [T, H, d], h W_k [T, G, d], h W_v [T, G, d]
    g   = sigmoid(h W_g) [T, H]
    R   = rope_parameters[layer_types[l]]:  the first d x
          partial_rotary_factor dims of a head turn, halves (x1, x2) ->
          (x1 cos - x2 sin, x2 cos + x1 sin); frequencies theta^(-2i/dims),
          under "yarn" blended with theirs over ``factor`` (``yarn_freq``)
          and cos, sin times ``attention_factor``
    s_ij = q_i . k_j / sqrt(d), key-value head n // (H / G) for query head
           n ;  j <= i, and in a sliding_attention layer i - j <
           sliding_window
    x'  = x + concat_n(g_n softmax_j(s) v) W_o
    h'  = N_2(x')
    mlp_layer_types[l] "dense":   y = W_down (silu(W_gate h') * W_up h')
                       "sparse":  c = sigmoid(h' W_r), float32 at highest
        top = the num_experts_per_tok largest of c (``lax.top_k``)
        w_e = moe_routed_scaling_factor c_e / sum_top c
        y   = sum_{e in top, e held} w_e Expert_e(h') + Shared(h')
    out = x' + y

Embedding, final ``N``, untied head; the loss is the mean token
cross-entropy.

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<i>/{attn_norm, ffn_norm}/scale``, ``layers_<i>/attn/{q_proj,
k_proj, v_proj, g_proj, o_proj}/kernel``, ``layers_<i>/ffn/{gate_proj,
up_proj, down_proj}/kernel`` (a dense layer), ``layers_<i>/moe/kernel`` (the
router), ``moe/routed_{gate,up,down}/experts`` [held, in, out],
``moe/shared_ffn/{gate_proj, up_proj, down_proj}/kernel``; ``norm/scale``,
``lm_head/kernel``.

Departures from the published model, each the configuration's: only the
experts of ``spec["held_experts"]`` exist (the router still scores all
``num_experts`` and a token's weights are renormalised over its k, held or
not); the vocabulary is the slice the configuration keeps; what the config
has no key for is left out (``assumed`` in the configuration's file).

Written for a chip the program has filled and a sequence of 16,384 tokens:
a sequence at a time (``lax.map``), each sequence's layer recomputed in the
backward pass; attention a key-value head's query heads at a time and
``spec["attn_block"]`` queries at a time against ALL the sequence's keys,
the mask one comparison of positions (no key is cut away: the program
cuts, so the two are independent), each group and each block recomputed;
the dense layer in blocks of rows; every held expert a dense product over
all rows of a sequence, masked by the routing, each recomputed; the head
and the loss in blocks of rows.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


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


# ---- attention -------------------------------------------------------------

def yarn_freq(dims, rope):
    """YaRN's ``dims // 2`` frequencies: pair i turns ``original x freq_i /
    2 pi`` times over the original length; the plain frequency where that
    is over ``beta_fast``, the plain one over ``factor`` where it is under
    ``beta_slow``, and between the two a ramp that is linear in i between
    the (whole) pairs where a turn count of beta_fast and of beta_slow
    would fall."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])
    plain = [theta ** (-2.0 * i / dims) for i in range(dims // 2)]

    def pair_of(turns):     # the (real) i whose pair turns ``turns`` times
        return dims * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rope["beta_slow"]))), dims - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _rotary(x, head_dim, rope):
    """x [T, heads, d]: its first ``d x partial_rotary_factor`` dims turned
    by position x frequency, their two halves a pair's two parts."""
    t = x.shape[0]
    dims = int(head_dim * float(rope["partial_rotary_factor"]))
    if rope.get("rope_type", "default") == "yarn":
        freq, amp = yarn_freq(dims, rope), float(rope["attention_factor"])
    else:
        freq = jnp.asarray([float(rope["rope_theta"]) ** (-2.0 * i / dims)
                            for i in range(dims // 2)], jnp.float32)
        amp = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2, rest = (x[..., :dims // 2], x[..., dims // 2:dims],
                    x[..., dims:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _group_attention(h, rope, window, block, weights):
    """The query heads of ONE key-value head: h [T, D]; ``weights``: w_q
    [D, R, d], w_k and w_v [D, d], w_g [D, R] -> their gated weighted sums,
    [T, R, d]."""
    w_q, w_k, w_v, w_g = weights
    t, hd = h.shape[0], w_k.shape[-1]
    q = jnp.einsum("td,drk->trk", h, w_q)
    k, v = h @ w_k, h @ w_v
    gate = jax.nn.sigmoid(h @ w_g)                            # [T, R]
    q = _rotary(q, hd, rope)
    k = _rotary(k[:, None, :], hd, rope)[:, 0, :]
    block = min(block, t)
    pad = -t % block
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(xs):
        qb, rows = xs                       # [block, R, hd], [block]
        s = jnp.einsum("qrd,kd->rqk", qb, k) * hd ** -0.5
        seen = keys[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - keys[None, :] < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(s, axis=-1), v)

    # the rows of padding sit at positions past the end: they see every key
    # and their output is cut away
    out = lax.map(one_block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            (-1, block) + q.shape[1:]),
        jnp.arange(t + pad).reshape(-1, block)))
    return out.reshape((-1,) + q.shape[1:])[:t] * gate[:, :, None]


def attention(p, h, spec, layer):
    """One sequence: h [T, D] -> [T, D]. Query head n reads key-value head
    n // (H / G): the H / G query heads of a key-value head at a time
    (``lax.map`` over the key-value heads), each such group recomputed in
    the backward pass (64 heads of 16,384 queries at once would keep 537 MB
    an intermediate), then the output projection over all heads."""
    t, d = h.shape
    nh = int(spec["num_attention_heads_per_layer"][layer])
    nkv, hd = int(spec["num_key_value_heads"]), int(spec["head_dim"])
    kind = spec["layer_types"][layer]
    group = jax.checkpoint(partial(
        _group_attention, h, spec["rope_parameters"][kind],
        int(spec["sliding_window"]) if kind == "sliding_attention" else None,
        int(spec.get("attn_block", 256))))
    # every projection's columns a key-value head at a time, that head first
    by_group = lambda w, *dims: jnp.moveaxis(
        w["kernel"].reshape((d, nkv) + dims), 1, 0)
    out = lax.map(group, (by_group(p["q_proj"], nh // nkv, hd),
                          by_group(p["k_proj"], hd),
                          by_group(p["v_proj"], hd),
                          by_group(p["g_proj"], nh // nkv)))  # [G, T, R, d]
    return jnp.moveaxis(out, 0, 1).reshape(t, nh * hd) @ p["o_proj"]["kernel"]


# ---- the two kinds of MLP --------------------------------------------------

@jax.checkpoint
def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _ffn(p, h):
    return _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"])


def routing(h, w_router, spec):
    """h [T, D] -> the combine weight of every routed expert, [T, E]: the
    sigmoid scores of a token's k largest over their sum, times the scaling
    factor, 0 elsewhere."""
    scores = jax.nn.sigmoid(
        jnp.dot(h, w_router, precision=lax.Precision.HIGHEST))
    top, which = lax.top_k(scores, int(spec["num_experts_per_tok"]))
    top = top / jnp.sum(top, axis=-1, keepdims=True) * float(
        spec["moe_routed_scaling_factor"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, which].set(top)


def experts(p, h, weights, spec):
    """The routed experts held here, each over all rows, and the shared
    one: h [T, D], ``weights`` [T, E] the routing."""
    out = _ffn(p["shared_ffn"], h)
    for slot, e in enumerate(spec["held_experts"]):
        y = _swiglu(h, p["routed_gate"]["experts"][slot],
                    p["routed_up"]["experts"][slot],
                    p["routed_down"]["experts"][slot])
        out = out + y * weights[:, int(e)][:, None]
    return out


# ---- the model ---------------------------------------------------------------

def layer(p, x, spec, index):
    """One sequence through layer ``index``: x [T, D] -> [T, D]."""
    eps = float(spec["rms_norm_eps"])
    x = x + attention(p["attn"], _norm(x, p["attn_norm"]["scale"], eps),
                      spec, index)
    h = _norm(x, p["ffn_norm"]["scale"], eps)
    if spec["mlp_layer_types"][index] == "dense":
        return x + _row_blocks(partial(_ffn, p["ffn"]), h,
                               spec.get("mlp_block", 2048))
    weights = routing(h, p["moe"]["kernel"], spec)
    return x + experts(p["moe"], h, weights, spec)


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
