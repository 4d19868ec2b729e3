"""LFM2 decoder with routed experts (the published ``config.json`` of
``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``; the equations are
those of HF ``modeling_lfm2_moe.py``): layers whose sequence mixer is a
double-gated SHORT CONVOLUTION (three layers in four) or grouped softmax
attention at 64-wide heads under a query/key norm, ``num_dense_layers``
leading layers with a dense SwiGLU and routed experts after them, chosen by
sigmoid scores under a selection bias, and a tied head. Layer ``l`` (from
0), RMSNorm with a plain gain, no bias anywhere::

    u = operator_norm(x)
    layer_types[l] conv:            B, C, z = u W_in  [T, 3 D], cut in
                                    that order into three [T, D]
                                    a   = B * z
                                    c_t = sum_j w[j] a_{t - (K-1) + j}
                                    m   = (C * c) W_out
                   full_attention:  q, k, v = u W_q [T, H, d], u W_k,
                                    u W_v [T, G, d]
                                    q, k <- N_q(q), N_k(k) over a head's d
                                    dims, THEN rotary (half-split, all d
                                    dims, rope_theta, positions 0..T-1)
                                    s_ij = q_i . k_j / sqrt(d), j <= i
                                    m   = concat_n(softmax_j(s) v) W_out
    x' = x + m ;  h = ffn_norm(x')
    l < num_dense_layers:  y = SwiGLU(h) of intermediate_size
    otherwise:             s = sigmoid(h W_r) ; E = top-k of s + b
                           w_e = routed_scaling_factor s_e / (sum_E s + 1e-6)
                           y = sum_{e in E, held} w_e Expert_e(h)
    out = x' + y ;   logits = embedding_norm(out_L) Embed^T

The convolution is depthwise and causal with ``conv_L_cache`` = K taps,
left-padded with zeros, ``w[K-1]`` on the current token; no activation
(``models/layers.causal_conv``, which ``models/qwen3_next.py`` calls too).
The published ``layer_types`` is 40 long; a model of fewer layers reads
its first ``num_hidden_layers`` entries, and a pipeline stage that starts
further on is handed its own list.

**The gated convolution is memory's**: ``B * z``, the taps and ``C *`` are
a few operations a channel and token over three [T, D] reads and one
write. It is written as elementwise ``jax.numpy`` under one scope
(``gated_conv``) so that XLA fuses it into as few passes as it will; the
two products round it (``short_conv`` is the whole operator).

**Attention** is ``models/attention.py``'s ``blocked_causal_gqa`` at 32
query heads over 8 key-value heads of 64: its output is named ``ATTN_OUT``
there. ``ops/flash_gqa.py`` packs two key-value heads a grid step at this
width (a tile of keys is then [tk, 2 x 64], whole lane rows).

**Routed experts**: ``models/moe.py``'s ``MoE`` with ``scoring="sigmoid"``,
``expert_bias`` (``use_expert_bias``: the selection reads ``s + b``, the
weights the unbiased ``s``; ``b`` is a ``bias`` leaf that gets no
gradient) and the published block's ``norm_eps`` 1e-6; no shared expert.

**Recomputation** as ``models/laguna.py``: a decoder layer is recomputed in
the backward pass from its input and, in an attention layer, ``ATTN_OUT``
(with the rows' log-sum-exp): a ``conv`` layer keeps its input alone and
runs ``W_in``, the gated convolution and ``W_out`` again. The dense
layer's SwiGLU recomputes itself a sequence at a time, the routed experts'
branch itself.

Parameter leaves are ``kernel``, ``embedding``, ``scale``, ``bias`` and
``experts``. Assumed, where the published config has no key (each with its
ground in ``benchmark/configs/lfm2_24b_a2b_ep8.json``): the tied head
(``Lfm2MoeConfig``'s default), ``expert_bias`` zeros and no rule that
moves it, no auxiliary loss. ``conv_bias`` true is refused: the published
model has none and nothing here adds one.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from oktopk_tpu.models.attention import (ATTN_OUT, Rope, blocked_causal_gqa,
                                         rotary_table, rotate_half_partial)
from oktopk_tpu.models.layers import Kernel, RMSNorm, SwiGLU, causal_conv
from oktopk_tpu.models.moe import MoE, held_ids
from oktopk_tpu.obs.anatomy import phase_scope

CONV, FULL = "conv", "full_attention"
# the published list: two leading conv layers, then (full, conv, conv,
# conv) nine times, a full and a conv layer closing it (30 conv, 10 full)
PUBLISHED = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 + (FULL, CONV)
# the published block's normaliser of the k chosen scores
NORM_EPS = 1e-6

# every distinct short-convolution call traced in this process, for
# ``utils/profiling.snapshot``: its shape (static)
_calls = {}


def short_conv_calls():
    """``[{"tokens", "channels", "taps"}, ...]``, an entry a distinct call
    shape (``tokens``: of one sequence), in the order first traced."""
    return [dict(c) for c in _calls.values()]


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published ``config.json`` of LFM2-24B-A2B under its own key names
    (``rope_parameters.rope_theta`` as ``rope_theta``), and what this chip
    holds and how it computes."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    rope_theta: float = 1000000.0
    # by layer; a model of fewer layers reads the first num_hidden_layers
    layer_types: Tuple[str, ...] = PUBLISHED
    # which experts this chip holds (ids under num_experts); None: all
    held_experts: Optional[Tuple[int, ...]] = None
    # queries a block of the XLA form of attention (the kernels' tiles are
    # their own rule's)
    attn_block: int = 512
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "held_experts", held_ids(
            self.held_experts, self.num_experts))
        layout = tuple(self.layer_types)
        if len(layout) < self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(layout)} layers of "
                             f"{self.num_hidden_layers}")
        if not set(layout) <= {CONV, FULL}:
            raise ValueError(f"layer_types: {sorted(set(layout))}, known "
                             f"{(CONV, FULL)}")
        object.__setattr__(self, "layer_types", layout)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are whole groups of "
                             f"{self.num_key_value_heads} key-value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("a head is hidden_size / num_attention_heads")
        if self.conv_bias:
            raise ValueError("conv_bias: the published model has none")
        if self.conv_L_cache < 1:
            raise ValueError("at least one tap")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (a dense conv layer, then full, conv, conv, conv with experts: one
        period; 4 query heads over 2 key-value heads of 32; three taps; 8
        experts, 2 a token, under the selection bias; a tied head over a
        vocabulary of 512)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            moe_intermediate_size=64, num_hidden_layers=5,
            num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            num_experts=8, num_experts_per_tok=2, rope_theta=10000.0,
            layer_types=(CONV, FULL, CONV, CONV, CONV), attn_block=16),
            **kw})


def gated_conv(b, c, z, w):
    """``c * conv(b * z)``, the convolution depthwise and causal over one
    sequence's tokens: b, c, z [T, D]; w [K, D] -> [T, D]. Elementwise all
    through (float32 on any platform: no product rounds it)."""
    return c * causal_conv(b * z, w)


class ShortConv(nn.Module):
    """The double-gated short convolution: ``(C * conv(B * z)) W_out`` with
    ``B, C, z = u W_in``. u [B, T, D] -> [B, T, D]."""
    taps: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        _, t, d = u.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        _calls.setdefault((t, d, self.taps), {
            "tokens": t, "channels": d, "taps": self.taps})
        bcz = dense(3 * d, name="in_proj")(u)
        w = Kernel(d, name="taps")(self.taps).astype(self.dtype)
        with phase_scope("fwd_bwd", sub="gated_conv"):
            y = jax.vmap(gated_conv, (0, 0, 0, None))(
                bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:], w)
        return dense(d, name="out_proj")(y)


class Attention(nn.Module):
    """Grouped-head causal attention under a query/key norm: each head's
    query and key through an RMSNorm over the head's dims (gains
    [head_dim], shared by the heads) BEFORE rotary on all of those dims."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    attn_block: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, d = u.shape
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        norm = partial(RMSNorm, self.norm_eps, self.dtype)
        q = dense(nh * hd, name="q_proj")(u).reshape(b, t, nh, hd)
        k = dense(nkv * hd, name="k_proj")(u).reshape(b, t, nkv, hd)
        v = dense(nkv * hd, name="v_proj")(u).reshape(b, t, nkv, hd)
        q, k = norm(name="q_layernorm")(q), norm(name="k_layernorm")(k)
        cos, sin = (x.astype(self.dtype)
                    for x in rotary_table(Rope(self.rope_theta), hd, t))
        q = rotate_half_partial(q, cos, sin)
        k = rotate_half_partial(k, cos, sin)
        with phase_scope("fwd_bwd", sub="full_scores"):
            out = blocked_causal_gqa(q, k, v, hd ** -0.5, self.attn_block)
        return dense(d, name="out_proj")(out.reshape(b, t, nh * hd))


class DecoderLayer(nn.Module):
    """One pre-norm block of layer ``index``'s kinds. Returns x and the
    rows each held expert computed (i32[held]; zeros in a dense layer)."""
    cfg: Lfm2Config
    index: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(RMSNorm, c.norm_eps, c.dtype)
        if c.layer_types[self.index] == CONV:
            u = norm(name="operator_norm")(x)
            with phase_scope("fwd_bwd", sub="short_conv"):
                x = x + ShortConv(c.conv_L_cache, c.dtype, name="conv")(u)
        else:
            with phase_scope("fwd_bwd", sub="attention"):
                x = x + Attention(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    c.rope_theta, c.norm_eps, c.attn_block, c.dtype,
                    name="attn")(norm(name="operator_norm")(x))
        h = norm(name="ffn_norm")(x)
        if self.index < c.num_dense_layers:
            with phase_scope("fwd_bwd", sub="mlp"):
                y = SwiGLU(c.intermediate_size, c.dtype, True, name="ffn")(h)
            counts = jnp.zeros((len(c.held_experts),), jnp.int32)
        else:
            y, counts = MoE(
                c.num_experts, c.held_experts, c.num_experts_per_tok,
                c.moe_intermediate_size, 0, c.routed_scaling_factor,
                c.norm_topk_prob, c.dtype, scoring="sigmoid",
                expert_bias=c.use_expert_bias, norm_eps=NORM_EPS,
                name="moe")(h)
        return x + y, counts


class Lfm2(nn.Module):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32,
    {"expert_rows": the rows each held expert computed, i32[expert layers,
    held]}). The head is the embedding, transposed (the published model
    ties them: ``Lfm2MoeConfig``'s default, no key in its file)."""
    cfg: Lfm2Config
    # the trainer initialises it in one jitted call (train/trainer.py)
    jit_init = True

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train   # no dropout
        c = self.cfg
        layer_cls = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT))
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         name="embed")
        x = embed(tokens)
        counts = []
        for i in range(c.num_hidden_layers):
            x, rows = layer_cls(c, i, name=f"layers_{i}")(x)
            if i >= c.num_dense_layers:
                counts.append(rows)
        with phase_scope("fwd_bwd", sub="head"):
            x = RMSNorm(c.norm_eps, c.dtype, name="embedding_norm")(x)
            logits = embed.attend(x)    # tied: the embedding, transposed
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, len(c.held_experts)), jnp.int32))
        return logits.astype(jnp.float32), {"expert_rows": counts}
