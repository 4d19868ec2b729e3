"""Plain reference of the Ouro looped decoder (the published ``config.json``
of ``ByteDance/Ouro-2.6B``, ``model_type: ouro``; the family's report
arXiv:2510.25741): ``jax.numpy`` in float32, no kernels, no ``shard_map``,
nothing of the program.

L = ``num_hidden_layers`` layers run R = ``total_ut_steps`` times on the
same weights. On x [T, D] of one sequence, ``N(x) = x / sqrt(mean(x^2) +
eps) * w``, no bias but the gate's; ``spec`` holds the published keys::

    x_0 = Embed(tokens)
    for t = 1 .. R:                           the SAME L layers every pass
        h = x_{t-1}
        for l = 0 .. L-1:
            u     = N_l^1(h)
            q,k,v = u W_q, u W_k, u W_v       [T, H, d] each, H = G: no groups
            q,k  <- all d dims of a head turned, halves (x1, x2) ->
                    (x1 cos - x2 sin, x2 cos + x1 sin), frequencies
                    rope_theta^(-2i/d), positions 0..T-1, every pass alike
            s_ij  = q_i . k_j / sqrt(d), j <= i
            h     = h + N_l^2(concat_n(softmax_j(s) v) W_o)
            h     = h + N_l^4(W_down(silu(W_gate N_l^3(h)) * W_up N_l^3(h)))
        x_t   = N_f(h)                        closes every pass, feeds the next
        L_t   = CE(x_t W_head, target)        a token, an exit
        lam_t = sigmoid(x_t w_g + b_g)        the exit gate
    p_t  = lam_t prod_{s<t}(1 - lam_s), t < R ;  p_R = prod_{s<R}(1 - lam_s)
    loss = mean over tokens of [sum_t p_t L_t - beta H(p)]
    H(p) = - sum_t p_t log p_t ;  beta = spec["entropy_beta"]

Nothing is detached: the gradient reaches the gate through p and everything
else through L_t.

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<l>/{attn_norm, attn_out_norm, ffn_norm, ffn_out_norm}/scale``
(N^1, N^2, N^3, N^4), ``layers_<l>/attn/{q_proj, k_proj, v_proj,
o_proj}/kernel``, ``layers_<l>/ffn/{gate_proj, up_proj, down_proj}/kernel``,
``norm/scale``, ``lm_head/kernel``, ``gate/{kernel, bias}``.
``spec["unshared_passes"]`` (the tests'): pass t reads
``pass_<t>/layers_<l>`` instead, R copies of the stack, so that a shared
weight's gradient can be held against the sum of theirs.

Written for a chip the program has filled: the R passes a Python loop,
each pass recomputed in the backward pass from its input; in a pass a
sequence at a time (``lax.map``), each sequence's layer recomputed from its
own input; attention ``spec["attn_block"]`` queries at a time against
all the sequence's keys, each block recomputed; the MLP and each exit's
head in blocks of rows, each recomputed.
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


def _row_blocks(fn, xs, block):
    """``fn`` over blocks of ``block`` rows of every array of ``xs`` ([T,
    ...] each), each block recomputed in the backward pass. The last block
    is filled with rows of zeros, cut away from every output."""
    t = jax.tree.leaves(xs)[0].shape[0]
    block = min(int(block), t)
    pad = -t % block
    cut = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)
                            ).reshape((-1, block) + x.shape[1:])
    out = lax.map(jax.checkpoint(fn), jax.tree.map(cut, xs))
    return jax.tree.map(
        lambda y: y.reshape((t + pad,) + y.shape[2:])[:t], out)


# ---- a layer ---------------------------------------------------------------

def _rotary(x, theta):
    """x [T, heads, d]: all d dims turned by position x frequency, the two
    halves of a head a pair's two parts."""
    t, _, d = x.shape
    freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                       jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(p, u, spec):
    """One sequence: u [T, D] (normed) -> [T, D]. Every query head has a
    key-value head of its own."""
    t = u.shape[0]
    nh, hd = int(spec["num_attention_heads"]), int(spec["head_dim"])
    if int(spec["num_key_value_heads"]) != nh:
        raise ValueError("this reference is of ungrouped heads")
    theta = float(spec["rope_theta"])
    q = _rotary((u @ p["q_proj"]["kernel"]).reshape(t, nh, hd), theta)
    k = _rotary((u @ p["k_proj"]["kernel"]).reshape(t, nh, hd), theta)
    v = (u @ p["v_proj"]["kernel"]).reshape(t, nh, hd)
    keys = jnp.arange(t)

    def one_block(xs):
        qb, rows = xs                       # [block, H, d], [block]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = _row_blocks(one_block, (q, keys), spec.get("attn_block", 256))
    return out.reshape(t, nh * hd) @ p["o_proj"]["kernel"]


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def layer(p, h, spec):
    """One sequence through one sandwich-normed block: h [T, D] -> [T, D]."""
    eps = float(spec["rms_norm_eps"])
    a = attention(p["attn"], _norm(h, p["attn_norm"]["scale"], eps), spec)
    h = h + _norm(a, p["attn_out_norm"]["scale"], eps)
    m = _row_blocks(partial(_swiglu, p["ffn"]),
                    _norm(h, p["ffn_norm"]["scale"], eps),
                    spec.get("mlp_block", 2048))
    return h + _norm(m, p["ffn_out_norm"]["scale"], eps)


# ---- the loop and its exits ------------------------------------------------

def stack_of(params, spec, t):
    """The L layers' parameters that pass ``t`` (from 0) reads: the same
    every pass, unless the tests ask for a copy a pass."""
    at = params[f"pass_{t}"] if spec.get("unshared_passes") else params
    return [at[f"layers_{i}"] for i in range(int(spec["num_hidden_layers"]))]


def _exit_rows(params, spec, rows):
    """A block of rows of one exit: h [block, D] and targets -> (x = N_f(h),
    the rows' cross-entropy, the rows' gate logit)."""
    h, targets = rows
    x = _norm(h, params["norm"]["scale"], float(spec["rms_norm_eps"]))
    z = x @ params["lm_head"]["kernel"]
    picked = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    nll = jax.nn.logsumexp(z, axis=1) - picked
    gate = (x @ params["gate"]["kernel"])[:, 0] + params["gate"]["bias"][0]
    return x, nll, gate


def one_pass(params, x, targets, spec, step):
    """Pass ``step`` (from 0) and its exit: x [B, T, D] -> (x_t, the next
    pass's input; the exit's per-token cross-entropy; its gate logit)."""
    b, t, d = x.shape
    for p in stack_of(params, spec, step):
        x = lax.map(jax.checkpoint(partial(layer, p, spec=spec)), x)
    x, nll, gate = _row_blocks(
        partial(_exit_rows, params, spec),
        (x.reshape(b * t, d), targets.reshape(b * t)),
        spec.get("head_block", 1024))
    return x.reshape(b, t, d), nll.reshape(b, t), gate.reshape(b, t)


def exits(params, tokens, targets, spec):
    """tokens, targets [B, T] -> (every exit's per-token cross-entropy, its
    gate logit), [R, B, T] each. A pass keeps its input alone for the
    backward pass, which runs the pass again (and in it each layer from
    its own input)."""
    x = params["embed"]["embedding"][tokens]
    nll, gate = [], []
    for step in range(int(spec["total_ut_steps"])):
        x, l, g = jax.checkpoint(partial(one_pass, spec=spec, step=step))(
            params, x, targets)
        nll.append(l)
        gate.append(g)
    return jnp.stack(nll), jnp.stack(gate)


def exit_distribution(gate):
    """gate logits [R, ...] -> p [R, ...]: p_t = lam_t prod_{s<t}(1 -
    lam_s), and the last exit takes the remainder."""
    lam = jax.nn.sigmoid(gate)
    p, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def loss(params, batch, spec, extra=None):
    nll, gate = exits(params, batch["tokens"], batch["targets"], spec)
    p = exit_distribution(gate)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    - float(spec["entropy_beta"]) * entropy)
