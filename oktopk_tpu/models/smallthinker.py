"""SmallThinker decoder (the published ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``; arXiv:2507.20984): layers of
two kinds that differ in mask AND in position encoding, a router that reads
the layer's input before attention, and ReLU-gated routed experts with no
shared expert. Layer ``l`` (from 0), RMSNorm with a plain gain, no biases::

    h   = N_1(x) ;  r = h W_r                     router logits, from h
    q, k, v = h W_q, h W_k, h W_v                 heads of head_dim
    if rope_layout[l]:  q, k = rotary(q), rotary(k)   all of a head's dims
    s_ij = q_i . k_j / sqrt(head_dim) ;  j <= i, and where
           sliding_window_layout[l]:  i - j < sliding_window_size
    x'  = x + softmax_j(s) v W_o
    y   = sum_{e in top-k of r} g_e W_down,e (relu(W_gate,e h') * W_up,e h')
          with h' = N_2(x') and g = softmax(r) over the k, renormalised
    out = x' + y ;   logits = N_f(out_L) W_head

The published layouts are ``[0, 1, 1, 1]`` repeated: the first layer of
every four is GLOBAL causal attention with NO position encoding, the other
three are rotary (half-split convention, ``attention.rotate_half_partial``
over the whole head) inside a window of ``sliding_window_size`` keys, the
query's own included.

**Attention** is ``models/attention.py``'s ``blocked_causal_gqa`` for both
kinds (each key-value head serves ``heads / kv_heads`` query heads): a
windowed layer
passes its ``window``, a global layer none. Compiled for a TPU that is the
flash kernels of ``ops/flash_gqa.py``, forward and backward: a tile of
scores lives in VMEM, a query tile visits only the key tiles that its band
(a global layer: the causal triangle) crosses, and the output and the rows'
log-sum-exp are both named ``ATTN_OUT``. Anywhere else it is the blocked
XLA form: ``attn_block`` queries at a time, each block's scores recomputed
in the backward pass and its output named ``ATTN_OUT``; a windowed block
reads, scores and masks only the keys ``[max(0, start - window + 1),
end)``, a global block's scores reach over the whole sequence.

**Routed experts**: ``models/moe.py``'s ``MoE`` with ``router_input`` (the
router scores ``N_1(x)`` over ALL experts in float32 at ``highest``, top-k
renormalised over the k, held or not; its operations depend on nothing the
attention computes, so the compiler may run them under it) and
``hidden_act="relu"``; the held experts' pairs go through the one sorted
buffer and grouped products that the other expert models use, under
the same ``CAPACITY_FACTOR``. The model has no shared expert and no dense
layer, so at untrained weights what attention adds alike to every token
grows with depth, the router's input grows alike with it, and a layer's
pairs at the held experts swing from a twentieth of their mean to three
times it: such a layer's pairs pass the buffer in some batches, and it
then runs every held expert over all rows (``routed_experts``).

**Recomputation** as ``models/qwen3_next.py``: a decoder layer is recomputed
in the backward pass from its input and what its attention named
``ATTN_OUT`` (the weighted sum; on a TPU the log-sum-exp too, so that the
forward kernel runs once a layer), the XLA form's query blocks recompute
their scores and the kernels' backward pass recomputes a tile's from the
log-sum-exp, the routed experts' branch recomputes itself.

Parameter leaves are ``kernel``, ``embedding``, ``scale`` and ``experts``.
Left out: the "secondary experts" of the model's description (the published
config has no key for them) and any auxiliary loss.
"""

from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from oktopk_tpu.models.attention import (ATTN_OUT, blocked_causal_gqa,
                                         rotate_half_partial)
from oktopk_tpu.models.layers import RMSNorm
from oktopk_tpu.models.moe import MoE, held_ids
from oktopk_tpu.obs.anatomy import phase_scope

# the published layouts' period: one global layer without position, three
# rotary layers inside the window
PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """The published ``config.json`` of SmallThinker-21BA3B-Instruct under
    its own key names, and what this chip holds and how it computes."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1500000.0
    max_position_embeddings: int = 16384
    # by layer: 1 = rotary / 1 = windowed; a model of fewer layers reads
    # the first num_hidden_layers of each
    rope_layout: Tuple[int, ...] = PERIOD * 13
    sliding_window_layout: Tuple[int, ...] = PERIOD * 13
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # which experts this chip holds (ids under moe_num_primary_experts);
    # None: all
    held_experts: Optional[Tuple[int, ...]] = None
    # queries a block of the XLA form, both kinds of layer (the kernels'
    # tiles are their own rule's)
    attn_block: int = 512
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "held_experts", held_ids(
            self.held_experts, self.moe_num_primary_experts))
        for name in ("rope_layout", "sliding_window_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            if len(layout) < self.num_hidden_layers:
                raise ValueError(f"{name} names {len(layout)} layers of "
                                 f"{self.num_hidden_layers}")
            object.__setattr__(self, name, layout)

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (one period of 1 + 3 layers, a window and blocks shorter than the
        64-token sequence, 16 experts with 4 a token)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            rope_theta=10000.0, max_position_embeddings=64,
            sliding_window_size=24, moe_num_primary_experts=16,
            moe_num_active_primary_experts=4, moe_ffn_hidden_size=64,
            attn_block=16), **kw})


class Attention(nn.Module):
    """Grouped-head causal attention; ``rotary``: all of a head's dims are
    turned by position; ``window``: the keys a query reads (None: all at or
    before it)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rotary: bool
    window: Optional[int]
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
        if self.rotary:
            inv_freq = 1.0 / self.rope_theta ** (
                jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
            angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
            cos = jnp.cos(angles).astype(self.dtype)
            sin = jnp.sin(angles).astype(self.dtype)
            q = rotate_half_partial(q, cos, sin)
            k = rotate_half_partial(k, cos, sin)
        with (nullcontext() if self.window is None
              else phase_scope("fwd_bwd", sub="window_scores")):
            out = blocked_causal_gqa(q, k, v, hd ** -0.5, self.attn_block,
                                     self.window)
        return dense(d, name="o_proj")(out.reshape(b, t, nh * hd))


class DecoderLayer(nn.Module):
    """One pre-norm block of layer ``index``'s kind. Returns x and the rows
    each held expert computed (i32[held])."""
    cfg: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(RMSNorm, c.rms_norm_eps, c.dtype)
        windowed = bool(c.sliding_window_layout[self.index])
        with phase_scope("fwd_bwd", sub=("window_attention" if windowed
                                         else "attention")):
            h = norm(name="attn_norm")(x)   # the router reads it too
            attn = Attention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                c.rope_theta, bool(c.rope_layout[self.index]),
                c.sliding_window_size if windowed else None,
                c.attn_block, c.dtype, name="attn")
            x = x + attn(h)
        moe = MoE(
            c.moe_num_primary_experts, c.held_experts,
            c.moe_num_active_primary_experts, c.moe_ffn_hidden_size, 0, 1.0,
            c.norm_topk_prob, c.dtype, hidden_act="relu", name="moe")
        y, counts = moe(norm(name="ffn_norm")(x), router_input=h)
        return x + y, counts


class SmallThinker(nn.Module):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32,
    {"expert_rows": the rows each held expert computed, i32[layers,
    held]})."""
    cfg: SmallThinkerConfig
    # the trainer initialises it in one jitted call (train/trainer.py)
    jit_init = True

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train   # no dropout
        c = self.cfg
        layer_cls = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT))
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed")(tokens)
        counts = []
        for i in range(c.num_hidden_layers):
            x, rows = layer_cls(c, i, name=f"layers_{i}")(x)
            counts.append(rows)
        with phase_scope("fwd_bwd", sub="head"):
            x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                              name="lm_head")(x)
        return logits.astype(jnp.float32), {"expert_rows": jnp.stack(counts)}
