"""Plain reference of the DeepSeek-V2 decoder (arXiv:2405.04434 and the
published ``config.json`` of ``deepseek-ai/DeepSeek-V2-Lite``) as one
chip's share of an expert-parallel job: ``jax.numpy`` in float32, no
kernels, no ``shard_map``, nothing of the program.

Per layer, on x [T, D] of one sequence, no biases, RMSNorm eps from the
spec::

    h = RMSNorm(x)
    q = h W_q -> heads x (nope | rope) ;  [c | k_pe] = h W_kva
    c = RMSNorm(c) ;  [k_nope | v] = c W_kvb -> heads x (nope | v)
    q_pe, k_pe = rotary(q_pe), rotary(k_pe)     (k_pe one head for all)
    scores = [q_nope | q_pe] [k_nope | k_pe]^T * (nope + rope)^-0.5 * m^2
    x += softmax_causal(scores) v W_o
    h = RMSNorm(x)
    layer < first_k_dense_replace:  x += W_down(silu(W_gate h) * W_up h)
    else:  s = softmax(h W_r) over ALL routed experts, float32, highest
           top = greedy top-k of s ;  w_e = s_e (unrenormalised) * scaling
           x += sum_{e in top, e held} w_e E_e(h) + Shared(h)

``rotary`` turns adjacent pairs ``(x[2i], x[2i+1])`` of position t by
``t * f_i``, with the YaRN frequencies ``f`` (``_inv_freq``) and cos/sin
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
``m = mscale(factor, mscale_all_dim) = 0.1 * mscale_all_dim * ln(factor) +
1``. Embedding, final RMSNorm, untied head; the loss is the mean token
cross-entropy.

Parameters come as the tree the flax model keeps: ``embed/embedding``,
``layers_<i>/{attn_norm, ffn_norm}/scale``, ``layers_<i>/attn/{q_proj,
kv_a_proj, kv_b_proj, o_proj}/kernel`` and ``attn/kv_a_norm/scale``,
``layers_<i>/ffn/{gate,up,down}_proj/kernel`` (dense layers),
``layers_<i>/moe/kernel`` (the router), ``moe/routed_{gate,up,down}/experts``
[held, in, out], ``moe/shared_ffn/...``, ``norm/scale``, ``lm_head/kernel``.

Departures from the published model, each the configuration's, not this
file's own:

- Only the experts of ``spec["held_experts"]`` exist here (the chip's share
  of an expert-parallel layout). The router still scores all
  ``n_routed_experts``; what the absent experts would add is left out.
- The vocabulary is the slice the configuration keeps; logits and loss are
  over the slice.
- No auxiliary balance loss (the catalog's config row carries no
  coefficient).
- HF's ``apply_rotary_pos_emb`` first moves the pairs apart (evens, then
  odds) and rotates halves; q and k get the same permutation, so the scores
  are those of the adjacent-pair rotation written here.

It is written for a chip the program has filled: every expert is a dense
product over all rows of a sequence, masked by the routing; attention is
the full causal softmax, a head at a time; and everything runs a sequence
at a time (``lax.map``), each sequence's layer recomputed in the backward
pass (``jax.checkpoint``), so that one gradient call holds one sequence's
activations of one layer beside the parameters and their gradient.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def extras(spec, batch, key):
    return None


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _inv_freq(dim, rope):
    """YaRN: frequency i is the original ``theta^(-2i/dim)`` where that
    dimension turns more than ``beta_fast`` times over the original length,
    the original over ``factor`` where it turns less than ``beta_slow``
    times, and a linear blend between."""
    theta, factor = float(rope["theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        blend = min(1.0, max(0.0, (i - low) / (high - low)))
        out.append(f / factor * blend + f * (1.0 - blend))
    return np.asarray(out, np.float32)


def _rotary(x, rope):
    """x [T, heads, dim]: adjacent pairs turned by position * frequency."""
    t, _, dim = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * _inv_freq(dim, rope)
    amp = (_mscale(float(rope["factor"]), float(rope["mscale"]))
           / _mscale(float(rope["factor"]), float(rope["mscale_all_dim"])))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    even, odd = x[..., 0::2], x[..., 1::2]
    r_even = even * cos[:, None, :] - odd * sin[:, None, :]
    r_odd = odd * cos[:, None, :] + even * sin[:, None, :]
    return jnp.stack([r_even, r_odd], axis=-1).reshape(x.shape)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _attention(p, h, spec):
    """One sequence: h [T, D] -> [T, D]."""
    t = h.shape[0]
    nh, dn, dr, dv, rank = (int(spec[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank"))
    rope = spec["rope"]
    q = (h @ p["q_proj"]["kernel"]).reshape(t, nh, dn + dr)
    ckv = h @ p["kv_a_proj"]["kernel"]
    c = _rms_norm(ckv[:, :rank], p["kv_a_norm"]["scale"],
                  float(spec["rms_norm_eps"]))
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(t, nh, dn + dv)
    q_pe = _rotary(q[..., dn:], rope)
    k_pe = _rotary(ckv[:, None, rank:], rope)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    m = _mscale(float(rope["factor"]), float(rope["mscale_all_dim"]))
    scale = (dn + dr) ** -0.5 * m * m
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                         v.swapaxes(0, 1)))          # [heads, T, dv]
    return out.swapaxes(0, 1).reshape(t, nh * dv) @ p["o_proj"]["kernel"]


def routing(h, w_router, spec):
    """h [T, D] -> the combine weight of every routed expert, [T, E]: s_e
    for the top-k of a token (greedy: the largest, k times), 0 elsewhere."""
    scores = jax.nn.softmax(
        jnp.dot(h, w_router, precision=lax.Precision.HIGHEST), axis=-1)
    left, picked = scores, jnp.zeros(scores.shape, bool)
    for _ in range(int(spec["num_experts_per_tok"])):
        best = jnp.argmax(left, axis=-1)
        one = jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
        picked = picked | one
        left = jnp.where(one, -1.0, left)
    w = jnp.where(picked, scores, 0.0)
    if spec.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * float(spec.get("routed_scaling_factor", 1.0))


def _experts(p, h, spec):
    """Routed experts held here, each over all rows, and the shared ones."""
    w = routing(h, p["kernel"], spec)
    out = _swiglu(h, *(p["shared_ffn"][n]["kernel"]
                       for n in ("gate_proj", "up_proj", "down_proj")))
    for slot, e in enumerate(spec["held_experts"]):
        y = _swiglu(h, p["routed_gate"]["experts"][slot],
                    p["routed_up"]["experts"][slot],
                    p["routed_down"]["experts"][slot])
        out = out + y * w[:, int(e)][:, None]
    return out


def _layer(p, x, spec, dense):
    """One sequence through one layer: x [T, D]."""
    eps = float(spec["rms_norm_eps"])
    x = x + _attention(p["attn"], _rms_norm(x, p["attn_norm"]["scale"], eps),
                       spec)
    h = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    if dense:
        return x + _swiglu(h, *(p["ffn"][n]["kernel"] for n in (
            "gate_proj", "up_proj", "down_proj")))
    return x + _experts(p["moe"], h, spec)


def _head_loss(params, spec, x_targets):
    x, targets = x_targets
    x = _rms_norm(x, params["norm"]["scale"], float(spec["rms_norm_eps"]))
    z = x @ params["lm_head"]["kernel"]
    picked = jnp.take_along_axis(z, targets[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=1) - picked)


def hidden(params, tokens, spec):
    """tokens [B, T] -> the last layer's output [B, T, D], a sequence and a
    layer at a time."""
    x = params["embed"]["embedding"][tokens]
    for i in range(int(spec["num_hidden_layers"])):
        one = partial(_layer, params[f"layers_{i}"], spec=spec,
                      dense=i < int(spec["first_k_dense_replace"]))
        x = lax.map(jax.checkpoint(one), x)
    return x


def loss(params, batch, spec, extra=None):
    x = hidden(params, batch["tokens"], spec)
    per_seq = lax.map(jax.checkpoint(partial(_head_loss, params, spec)),
                      (x, batch["targets"]))
    return jnp.mean(per_seq)
