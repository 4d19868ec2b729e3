"""Ouro looped decoder (the published ``config.json`` of
``ByteDance/Ouro-2.6B``, ``model_type: ouro``; the family's report "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): ONE stack
of ``num_hidden_layers`` layers run ``total_ut_steps`` times on the same
weights, a sandwich-normed block with ungrouped heads, an exit gate a pass
and a loss over all the exits. With L layers and R passes, RMSNorm with a
plain gain, no bias but the gate's::

    x_0 = Embed(tokens)
    for t = 1 .. R:                      the SAME L layers, every pass
        h = x_{t-1}
        for l = 0 .. L-1:
            q, k, v = N_l^1(h) W_q, N_l^1(h) W_k, N_l^1(h) W_v   [T, H, d]
            q, k  <- rotate_half on all d dims, rope_theta, positions 0..T-1
            s_ij  = q_i . k_j / sqrt(d), j <= i
            h     = h + N_l^2(concat_n(softmax_j(s) v) W_o)
            h     = h + N_l^4(W_down(silu(W_gate N_l^3(h)) * W_up N_l^3(h)))
        x_t   = N_f(h)                   the final norm closes every pass
        z_t   = x_t W_head ;  L_t = CE(z_t, target)
        lam_t = sigmoid(x_t w_g + b_g)   the exit gate
    p_t  = lam_t prod_{s<t}(1 - lam_s), t < R ;  p_R = prod_{s<R}(1 - lam_s)
    loss = mean over tokens of [sum_t p_t L_t - beta H(p)]

**One set of parameters, R passes**: the parameter tree holds L layers
(``layers_<l>``), ``norm``, ``lm_head`` and ``gate``; the passes are one
``nn.scan`` over the stack with the parameters broadcast, so the compiled
step holds ONE body of L layers, and a weight's gradient is summed over the
passes in the loop's own carry.

**Attention** is ``models/attention.py``'s ``blocked_causal_gqa`` at a
group of ONE head (``num_key_value_heads`` = ``num_attention_heads``): on a
TPU the flash
kernels of ``ops/flash_gqa.py``, whose grid step takes eight of the 16
key-value heads side by side with their eight query heads (a head that
shares its key tile with no other still shares a step's cost:
``flash_gqa.kv_heads_a_step``, from the shapes), anywhere else the blocked
XLA form. Its output is named ``ATTN_OUT`` there. Rotary is
``attention.rotary_table`` of a plain record on all of a head's dims.

**Recomputation** as ``models/laguna.py``: a decoder layer is recomputed in
the backward pass from its input and ``ATTN_OUT`` (with the rows'
log-sum-exp); the SwiGLU recomputes itself a sequence at a time.

**The loss where the hidden state is**: the model is called with the
targets and returns its loss (``computes_loss``; ``train/trainer.py``'s
token-LM rung). An exit's final norm, head product, cross-entropy and gate
run ``head_block`` rows at a time, each block recomputed in the backward
pass, so no ``[T, vocab]`` tensor outlives its block; the exits' per-token
cross-entropies and gate logits ``[R, B, T]`` are what leaves the loop, and
:func:`exit_mixture` is a few elementwise passes over those.

Parameter leaves are ``kernel``, ``embedding``, ``scale`` and ``bias``.
Assumed, where the published config has no key (each with its ground in
``benchmark/configs/ouro_2_6b_l6.json``): the two output norms a block, the
final norm inside the loop, the gate's form, the last exit's remainder, the
loss and its beta. ``early_exit_threshold`` is an inference rule and is not
read: this repo has no serving path.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from oktopk_tpu.models.attention import (ATTN_OUT, Rope, blocked_causal_gqa,
                                         rotary_table, rotate_half_partial)
from oktopk_tpu.models.layers import RMSNorm, SwiGLU
from oktopk_tpu.obs.anatomy import phase_scope


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published ``config.json`` of Ouro-2.6B under its own key names,
    and how this chip computes it."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    # passes over the stack, on the same weights
    total_ut_steps: int = 4
    # not in the model's file: the weight of the exit distribution's
    # entropy in the training loss (the report's early pre-training value)
    entropy_beta: float = 0.1
    # queries a block of the XLA form (the kernels' tiles are their own
    # rule's), and rows a block of an exit's head and cross-entropy
    attn_block: int = 512
    head_block: int = 1024
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are whole groups of "
                             f"{self.num_key_value_heads} key-value heads")
        if self.total_ut_steps < 1 or self.num_hidden_layers < 1:
            raise ValueError("at least one pass over at least one layer")

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (four passes over three layers, 4 ungrouped heads, sandwich norms,
        the gate, a vocabulary of 512 that is several head blocks of a
        row count not a multiple of the block)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, head_dim=32, max_position_embeddings=64,
            rope_theta=10000.0, attn_block=16, head_block=48), **kw})


def exit_mixture(nll, gate_logits, beta: float):
    """The loss over the exits: ``nll`` and ``gate_logits`` [R, ...], one
    entry a pass and token, float32 -> (the tokens' mean of ``sum_t p_t
    nll_t - beta H(p)``, p [R, ...]). ``p_t = lam_t prod_{s<t}(1 - lam_s)``
    with ``lam = sigmoid(gate_logits)``, and the last exit takes what is
    left, so p sums to 1 a token and the last pass's gate is not read.
    Log-space: ``log lam = log_sigmoid(g)``, ``log(1 - lam) =
    log_sigmoid(-g)``."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_p = (before + jax.nn.log_sigmoid(gate_logits)).at[-1].set(before[-1])
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy), p


class Attention(nn.Module):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    key-value heads (the published model's are equal: no grouping), rotary
    on all of a head's dims."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    attn_block: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(nh * hd, name="q_proj")(h).reshape(b, t, nh, hd)
        k = dense(nkv * hd, name="k_proj")(h).reshape(b, t, nkv, hd)
        v = dense(nkv * hd, name="v_proj")(h).reshape(b, t, nkv, hd)
        # plain frequencies on all of a head's dims, the same every pass
        cos, sin = (x.astype(self.dtype)
                    for x in rotary_table(Rope(self.rope_theta), hd, t))
        q = rotate_half_partial(q, cos, sin)
        k = rotate_half_partial(k, cos, sin)
        with phase_scope("fwd_bwd", sub="full_scores"):
            out = blocked_causal_gqa(q, k, v, hd ** -0.5, self.attn_block)
        return dense(d, name="o_proj")(out.reshape(b, t, nh * hd))


class DecoderLayer(nn.Module):
    """One sandwich-normed block: a norm before AND after each branch."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(RMSNorm, c.rms_norm_eps, c.dtype)
        with phase_scope("fwd_bwd", sub="attention"):
            a = Attention(c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim, c.rope_theta, c.attn_block, c.dtype,
                          name="attn")(norm(name="attn_norm")(x))
            x = x + norm(name="attn_out_norm")(a)
        with phase_scope("fwd_bwd", sub="mlp"):
            m = SwiGLU(c.intermediate_size, c.dtype, True, name="ffn")(
                norm(name="ffn_norm")(x))
            return x + norm(name="ffn_out_norm")(m)


def _exit_block(mdl, carry, rows):
    """``head_block`` rows of one exit: h [block, D] (the stack's output)
    and their targets -> (x = N_f(h), the rows' cross-entropy, the rows'
    gate logit). Every module is the model's own: called for every block
    of every pass on the same parameters."""
    c = mdl.cfg
    h, targets = rows
    with phase_scope("fwd_bwd", sub="head"):
        x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(h)
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                          name="lm_head")(x).astype(jnp.float32)
        nll = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                              targets)
    with phase_scope("fwd_bwd", sub="exit_gate"):
        gate = nn.Dense(1, dtype=c.dtype, name="gate")(x)[:, 0]
    return carry, (x, nll, gate.astype(jnp.float32))


def _one_pass(mdl, x, targets):
    """The stack once, and its exit: x [B, T, D] -> (N_f of the stack's
    output, the next pass's input; (the exit's per-token cross-entropy, its
    gate logits), both [B, T] float32)."""
    c = mdl.cfg
    layer_cls = nn.remat(
        DecoderLayer,
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT))
    for i in range(c.num_hidden_layers):
        x = layer_cls(c, name=f"layers_{i}")(x)
    b, t, d = x.shape
    rows = b * t
    block = min(c.head_block, rows)
    pad = -rows % block
    blocks = nn.scan(nn.remat(_exit_block), variable_broadcast="params",
                     split_rngs={"params": False})
    _, (x, nll, gate) = blocks(mdl, None, (
        jnp.pad(x.reshape(rows, d), ((0, pad), (0, 0))).reshape(
            -1, block, d),
        jnp.pad(targets.reshape(rows), (0, pad)).reshape(-1, block)))
    cut = lambda y: y.reshape((rows + pad,) + y.shape[2:])[:rows].reshape(
        (b, t) + y.shape[2:])
    return cut(x), (cut(nll), cut(gate))


class Ouro(nn.Module):
    """tokens, targets [B, T] int32 -> (loss, {"eval_loss": the LAST exit's
    mean cross-entropy, what inference that never leaves the loop early
    would emit (``Trainer.eval_step`` reports it), "exit_p": [R, B, T] a
    token's distribution over the exits, "exit_step_milli_max": 1,000 x
    the tokens' mean of sum_t t p_t})."""
    cfg: OuroConfig
    # the trainer initialises it in one jitted call (train/trainer.py) ...
    jit_init = True
    # ... and calls it with the targets for its loss, not for logits
    computes_loss = True

    @nn.compact
    def __call__(self, tokens, targets, train: bool = True):
        del train   # no dropout
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed")(tokens)
        passes = nn.scan(_one_pass, variable_broadcast="params",
                         split_rngs={"params": False}, in_axes=nn.broadcast,
                         length=c.total_ut_steps)
        _, (nll, gate) = passes(self, x, targets)
        with phase_scope("fwd_bwd", sub="exit_gate"):
            loss, p = exit_mixture(nll, gate, c.entropy_beta)
            steps = jnp.arange(1, c.total_ut_steps + 1, dtype=jnp.float32)
            mean_exit = jnp.mean(jnp.tensordot(steps, p, axes=1))
        return loss, {
            "eval_loss": jnp.mean(nll[-1]), "exit_p": p,
            "exit_step_milli_max": jnp.round(1e3 * mean_exit)}
