"""Plain reference of the Qwen3-Next decoder (the published ``config.json``
and ``modeling_qwen3_next.py`` of ``Qwen/Qwen3-Next-80B-A3B-Instruct``;
Gated Delta Networks, arXiv:2412.06464) as one chip's share of an
expert-parallel job: ``jax.numpy`` in float32, no kernels, no
``shard_map``, nothing of the program.

Layer ``l`` (from 0) is full attention where ``(l + 1) %
full_attention_interval == 0``, else linear attention. On x [T, D] of one
sequence, no biases, ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``::

    x += Mixer(N(x)) ;  x += Experts(N(x))

    full:    [q | gate] = h W_q a head at a time ;  k = h W_k ;  v = h W_v
             q, k = N_head(q), N_head(k) ;  rotary on the first quarter of
             a head's dims, halves (x1, x2) -> (x1 cos - x2 sin,
             x2 cos + x1 sin) ;  s = q k^T / sqrt(head_dim), causal softmax,
             key-value head j for query heads 8j .. 8j + 7
             out = (softmax(s) v * sigmoid(gate)) W_o

    linear:  [q | k | v | z] = h W_qkvz ;  [b | a] = h W_ba   (flat order)
             [q | k | v] <- silu(conv4([q | k | v]))   causal, depthwise
             beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias)
             q, k <- q / |q|, k / |k| a head ;  q <- q d_k^-1/2
             value head j reads key head j // 2. A head's state S [d_k,
             d_v], zero at the start, TOKEN BY TOKEN (the published
             recurrent_gated_delta_rule; the program computes the chunked
             form, so the two are independent):
                 S <- exp(g_t) S
                 S <- S + k_t (beta_t (v_t - S^T k_t))^T
                 o_t = S^T q_t
             out = (rmsnorm(o_t) * w_n * silu(z_t)) W_out   a head

    experts: s = softmax(h W_r) over ALL experts, float32, highest
             top = the k largest ;  w_e = s_e / sum_top s   (held or not)
             sum_{e in top, e held} w_e E_e(h) + sigmoid(h w_g) E_shared(h)

Embedding, final ``N``, untied head; the loss is the mean token
cross-entropy. The recurrence is elementwise float32 (no matrix product of
the device's default precision touches the state).

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<i>/{attn_norm, ffn_norm}/bias`` (the zero-centred gains),
``layers_<i>/linear_attn/{in_proj_qkvz, in_proj_ba, conv, out_proj}/kernel``
(``conv`` [taps, channels]), ``linear_attn/{A_log, dt_bias}/bias``,
``linear_attn/norm/scale``; ``layers_<i>/attn/{q_proj, k_proj, v_proj,
o_proj}/kernel``, ``attn/{q_norm, k_norm}/bias``; ``layers_<i>/moe/kernel``
(the router), ``moe/routed_{gate,up,down}/experts`` [held, in, out],
``moe/shared_ffn/{gate,up,down}_proj/kernel``, ``moe/shared_gate/kernel``;
``norm/bias``, ``lm_head/kernel``.

Departures from the published model, each the configuration's: only the
experts of ``spec["held_experts"]`` exist (the router still scores all
``num_experts`` and renormalises over a token's k, held or not); the
vocabulary is the slice the configuration keeps; no multi-token prediction
and no auxiliary loss; the fused projections' columns are in the flat order
above.

Written for a chip the program has filled: a sequence at a time
(``lax.map``), each sequence's layer recomputed in the backward pass; the
recurrence in blocks of ``spec["recurrence_block"]`` tokens, each block
recomputed from the state it started with (8,192 states of 32 x 128 x 128
floats would be 17 GB); attention the full causal softmax, a head at a
time; every held expert a dense product over all rows of a sequence,
masked by the routing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def extras(spec, batch, key):
    return None


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


# ---- full attention --------------------------------------------------------

def _rotary(x, spec):
    """x [T, heads, dim]: the first ``partial_rotary_factor`` of the dims
    turned by position * frequency, halves apart; the rest pass."""
    t, _, dim = x.shape
    rot = int(dim * float(spec["partial_rotary_factor"]))
    freq = 1.0 / float(spec["rope_theta"]) ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _attention(p, h, spec):
    """One sequence: h [T, D] -> [T, D]."""
    t = h.shape[0]
    nh, nkv, hd = (int(spec[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    eps = float(spec["rms_norm_eps"])
    qg = (h @ p["q_proj"]["kernel"]).reshape(t, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ p["k_proj"]["kernel"]).reshape(t, nkv, hd)
    v = (h @ p["v_proj"]["kernel"]).reshape(t, nkv, hd)
    q = _rotary(_norm(q, p["q_norm"]["bias"], eps), spec)
    k = _rotary(_norm(k, p["k_norm"]["bias"], eps), spec)
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = nh // nkv

    @jax.checkpoint
    def head(carry, i):
        kh, vh = k[:, i // group], v[:, i // group]
        s = jnp.where(causal, (q[:, i] @ kh.T) * hd ** -0.5, -jnp.inf)
        return carry, jax.nn.softmax(s, axis=-1) @ vh

    _, out = lax.scan(head, 0, jnp.arange(nh))          # [heads, T, hd]
    out = out.swapaxes(0, 1) * jax.nn.sigmoid(gate)
    return out.reshape(t, nh * hd) @ p["o_proj"]["kernel"]


# ---- linear attention: the token recurrence ----------------------------------

def _conv(x, w):
    """Causal depthwise convolution: y_t = sum_j w[j] x[t - (K - 1) + j]."""
    taps, t = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    y = jnp.zeros_like(x)
    for j in range(taps):
        y = y + xp[j:j + t] * w[j]
    return y


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _token(state, tok):
    """One token of every head: state [H, dk, dv]."""
    q, k, v, g, beta = tok
    state = state * jnp.exp(g)[:, None, None]
    seen = jnp.sum(state * k[:, :, None], axis=1)            # S^T k
    state = state + k[:, :, None] * (beta[:, None] * (v - seen))[:, None, :]
    return state, jnp.sum(state * q[:, :, None], axis=1)     # S^T q


def delta_rule(q, k, v, g, beta, block):
    """q, k [T, H, dk]; v [T, H, dv]; g, beta [T, H] -> o [T, H, dv], token
    by token from a zero state; ``block`` tokens at a time, each block
    recomputed in the backward pass from the state it started with."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    block = min(block, t)
    pad = -t % block
    # a token of zeros leaves the state as it is (g = 0, beta = 0, k = 0)
    cut = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)
                            ).reshape((-1, block) + x.shape[1:])
    run = jax.checkpoint(lambda s, xs: lax.scan(_token, s, xs))
    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32),
                    tuple(cut(x) for x in (q, k, v, g, beta)))
    return o.reshape(-1, h, dv)[:t]


def _linear_attention(p, h, spec):
    """One sequence: h [T, D] -> [T, D]."""
    t = h.shape[0]
    hk, hv, dk, dv = (int(spec[k]) for k in (
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim"))
    key_dim, conv_dim = hk * dk, 2 * hk * dk + hv * dv
    qkvz = h @ p["in_proj_qkvz"]["kernel"]
    ba = h @ p["in_proj_ba"]["kernel"]
    qkv = jax.nn.silu(_conv(qkvz[:, :conv_dim], p["conv"]["kernel"]))
    z = qkvz[:, conv_dim:].reshape(t, hv, dv)
    q = _unit(qkv[:, :key_dim].reshape(t, hk, dk)) * dk ** -0.5
    k = _unit(qkv[:, key_dim:2 * key_dim].reshape(t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    v = qkv[:, 2 * key_dim:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]["bias"]) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"]["bias"])
    o = delta_rule(q, k, v, g, beta, int(spec.get("recurrence_block", 64)))
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + float(spec["rms_norm_eps"]))
    o = o * p["norm"]["scale"] * jax.nn.silu(z)
    return o.reshape(t, hv * dv) @ p["out_proj"]["kernel"]


# ---- experts ---------------------------------------------------------------

def routing(h, w_router, spec):
    """h [T, D] -> the combine weight of every routed expert, [T, E]: s_e
    for the k largest of a token, renormalised over those k where
    ``norm_topk_prob``, 0 elsewhere."""
    scores = jax.nn.softmax(
        jnp.dot(h, w_router, precision=lax.Precision.HIGHEST), axis=-1)
    left, picked = scores, jnp.zeros(scores.shape, bool)
    for _ in range(int(spec["num_experts_per_tok"])):
        one = jax.nn.one_hot(jnp.argmax(left, axis=-1), scores.shape[-1],
                             dtype=bool)
        picked = picked | one
        left = jnp.where(one, -1.0, left)
    w = jnp.where(picked, scores, 0.0)
    if spec.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def experts(p, h, spec):
    """Routed experts held here, each over all rows, and the gated shared
    expert."""
    w = routing(h, p["kernel"], spec)
    out = _swiglu(h, *(p["shared_ffn"][n]["kernel"] for n in (
        "gate_proj", "up_proj", "down_proj"))) * jax.nn.sigmoid(
            h @ p["shared_gate"]["kernel"])
    for slot, e in enumerate(spec["held_experts"]):
        y = _swiglu(h, p["routed_gate"]["experts"][slot],
                    p["routed_up"]["experts"][slot],
                    p["routed_down"]["experts"][slot])
        out = out + y * w[:, int(e)][:, None]
    return out


# ---- the model ---------------------------------------------------------------

def is_full(layer, spec):
    return (layer + 1) % int(spec["full_attention_interval"]) == 0


def mixer(p, x, spec, full):
    """One sequence through a layer's first half: x [T, D] -> x + Mixer."""
    h = _norm(x, p["attn_norm"]["bias"], float(spec["rms_norm_eps"]))
    return x + (_attention(p["attn"], h, spec) if full
                else _linear_attention(p["linear_attn"], h, spec))


def _layer(p, x, spec, full):
    x = mixer(p, x, spec, full)
    h = _norm(x, p["ffn_norm"]["bias"], float(spec["rms_norm_eps"]))
    return x + experts(p["moe"], h, spec)


def _head_loss(params, spec, x_targets):
    x, targets = x_targets
    x = _norm(x, params["norm"]["bias"], float(spec["rms_norm_eps"]))
    z = x @ params["lm_head"]["kernel"]
    picked = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=1) - picked)


def hidden(params, tokens, spec):
    """tokens [B, T] -> the last layer's output [B, T, D], a sequence and a
    layer at a time."""
    x = params["embed"]["embedding"][tokens]
    for i in range(int(spec["num_hidden_layers"])):
        one = partial(_layer, params[f"layers_{i}"], spec=spec,
                      full=is_full(i, spec))
        x = lax.map(jax.checkpoint(one), x)
    return x


def loss(params, batch, spec, extra=None):
    x = hidden(params, batch["tokens"], spec)
    per_seq = lax.map(jax.checkpoint(partial(_head_loss, params, spec)),
                      (x, batch["targets"]))
    return jnp.mean(per_seq)
