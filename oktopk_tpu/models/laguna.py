"""Laguna decoder (the published ``config.json`` of ``poolside/Laguna-XS.2``,
``model_type: laguna``): layers of two kinds that differ in mask, in HEAD
COUNT and in position encoding, a per-head output gate on the attention, a
leading dense layer, and routed experts scored by a sigmoid beside one
shared expert. Layer ``l`` (from 0), RMSNorm with a plain gain, no biases::

    h   = N_1(x) ;  H_l = num_attention_heads_per_layer[l]
    q, k, v = h W_q [T, H_l, d], h W_k [T, G, d], h W_v [T, G, d]
    g   = sigmoid(h W_g) [T, H_l]                    one gate a head
    full_attention:     rotary on the first half of a head's dims under
                        YaRN, cos and sin times ``attention_factor``
    sliding_attention:  rotary on all of a head's dims, plain frequencies
    s_ij = q_i . k_j / sqrt(d) ;  j <= i, and in a sliding layer
           i - j < sliding_window
    x'  = x + concat_n(g_n softmax_j(s) v) W_o
    h'  = N_2(x')
    mlp_layer_types[l] dense:   y = SwiGLU(h') of intermediate_size
                       sparse:  c = sigmoid(h' W_r) ; E = the k largest ;
                                w_e = moe_routed_scaling_factor c_e / sum_E c
                                y = sum_{e in E, held} w_e Expert_e(h')
                                    + Shared(h')
    out = x' + y ;   logits = N_f(out_L) W_head

The published lists are 40 long: ``layer_types`` is (full, sliding, sliding,
sliding) repeated, ``num_attention_heads_per_layer`` 48 in a full layer and
64 in a sliding one over 8 key-value heads (groups of 6 and of 8), layer 0's
MLP is dense. A model of fewer layers reads the first ``num_hidden_layers``
entries of each.

**Rotary**, one record a kind (``attention.Rope``, the two entries of the
published ``rope_parameters``): the half-split convention
(``attention.rotate_half_partial``) over the first ``head_dim x
partial_rotary_factor`` dims of a head; a ``yarn`` record's frequencies are
YaRN's over those dims (``attention.rotary_table``).

**Attention** is ``models/attention.py``'s ``blocked_causal_gqa`` for both
kinds: compiled for a TPU the flash kernels of ``ops/flash_gqa.py`` (a
sliding layer's band is ``sliding_window`` keys wide), anywhere else the
blocked XLA form ``attn_block`` queries at a time. Its output is named
``ATTN_OUT`` there; the gate multiplies after it, so the kernels and what a
layer keeps are those of the other models that call it.

**Routed experts**: ``models/moe.py``'s ``MoE`` with ``scoring="sigmoid"``
(the router's float32 ``highest`` logits through a sigmoid where the other
models' go through a softmax; top-k renormalised over the k, held or not,
times the scaling factor), its shared expert ungated; the held experts'
pairs through the one sorted buffer and grouped products under
``CAPACITY_FACTOR``.

**Recomputation** as ``models/smallthinker.py``: a decoder layer is
recomputed in the backward pass from its input and what its attention named
``ATTN_OUT``; the dense layer's SwiGLU and the routed experts' branch
recompute themselves.

Parameter leaves are ``kernel``, ``embedding``, ``scale`` and ``experts``.
Assumed, where the published config has no key (each with its ground in
``benchmark/configs/laguna_xs2_ep32.json``): the gate's grain, input and
sigmoid; sigmoid scores renormalised over the k; no score-correction bias,
expert groups, query/key norm, logit soft-cap or auxiliary loss; SiLU.
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
from oktopk_tpu.models.layers import RMSNorm, SwiGLU
from oktopk_tpu.models.moe import MoE, held_ids
from oktopk_tpu.obs.anatomy import phase_scope

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# the published lists' period: one full layer, three sliding ones
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published ``config.json`` of Laguna-XS.2 under its own key names
    (``rope_parameters``' two entries as ``rope_full`` and
    ``rope_sliding``), and what this chip holds and how it computes."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    # not in this model's file: the sibling Laguna-S-2.1's key and value
    norm_topk_prob: bool = True
    sliding_window: int = 512
    # by layer; a model of fewer layers reads the first num_hidden_layers
    layer_types: Tuple[str, ...] = PERIOD * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    rope_full: Rope = Rope(
        rope_theta=500000.0, partial_rotary_factor=0.5, rope_type="yarn",
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    rope_sliding: Rope = Rope(rope_theta=10000.0, partial_rotary_factor=1.0)
    # which experts this chip holds (ids under num_experts); None: all
    held_experts: Optional[Tuple[int, ...]] = None
    # queries a block of the XLA form, both kinds of layer (the kernels'
    # tiles are their own rule's)
    attn_block: int = 512
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "held_experts", held_ids(
            self.held_experts, self.num_experts))
        for name, known in (("layer_types", (FULL, SLIDING)),
                            ("mlp_layer_types", (DENSE, SPARSE)),
                            ("num_attention_heads_per_layer", None)):
            layout = tuple(getattr(self, name))
            if len(layout) < self.num_hidden_layers:
                raise ValueError(f"{name} names {len(layout)} layers of "
                                 f"{self.num_hidden_layers}")
            if known and not set(layout) <= set(known):
                raise ValueError(f"{name}: {sorted(set(layout))}, "
                                 f"known {known}")
            object.__setattr__(self, name, layout)
        if any(int(h) % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("a layer's heads are whole groups of "
                             f"{self.num_key_value_heads} key-value heads")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is whole experts' widths")

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (layer 0 dense and full, three sliding, one full; 12 and 16 heads
        over 2 key-value heads, groups of 6 and of 8; a window shorter
        than the 64-token sequence and equal to the XLA form's block; YaRN
        from an original length of 32; 16 experts with 4 a token and one
        shared)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=32,
            max_position_embeddings=64, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=64,
            shared_expert_intermediate_size=64, sliding_window=16,
            num_attention_heads_per_layer=(12, 16, 16, 16) * 10,
            rope_full=Rope(
                rope_theta=10000.0, partial_rotary_factor=0.5,
                rope_type="yarn", factor=4.0,
                original_max_position_embeddings=32, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.1386294361119891),
            attn_block=16), **kw})


class Attention(nn.Module):
    """Grouped-head causal attention of ``num_heads`` query heads behind a
    gate a head; ``rope``: the layer's kind's rotary record; ``window``:
    the keys a query reads (None: all at or before it)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope: Rope
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
        cos, sin = (x.astype(self.dtype)
                    for x in rotary_table(self.rope, hd, t))
        q = rotate_half_partial(q, cos, sin)
        k = rotate_half_partial(k, cos, sin)
        with phase_scope("fwd_bwd", sub=("full_scores" if self.window is None
                                         else "window_scores")):
            out = blocked_causal_gqa(q, k, v, hd ** -0.5, self.attn_block,
                                     self.window)
        with phase_scope("fwd_bwd", sub="attn_gate"):
            gate = jax.nn.sigmoid(dense(nh, name="g_proj")(h))
            out = out * gate[..., None]
        return dense(d, name="o_proj")(out.reshape(b, t, nh * hd))


class DecoderLayer(nn.Module):
    """One pre-norm block of layer ``index``'s kinds. Returns x and the
    rows each held expert computed (i32[held]; zeros in a dense layer)."""
    cfg: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(RMSNorm, c.rms_norm_eps, c.dtype)
        full = c.layer_types[self.index] == FULL
        with phase_scope("fwd_bwd", sub=("attention" if full
                                         else "window_attention")):
            attn = Attention(
                c.num_attention_heads_per_layer[self.index],
                c.num_key_value_heads, c.head_dim,
                c.rope_full if full else c.rope_sliding,
                None if full else c.sliding_window, c.attn_block, c.dtype,
                name="attn")
            x = x + attn(norm(name="attn_norm")(x))
        h = norm(name="ffn_norm")(x)
        if c.mlp_layer_types[self.index] == DENSE:
            with phase_scope("fwd_bwd", sub="mlp"):
                y = SwiGLU(c.intermediate_size, c.dtype, True, name="ffn")(h)
            counts = jnp.zeros((len(c.held_experts),), jnp.int32)
        else:
            y, counts = MoE(
                c.num_experts, c.held_experts, c.num_experts_per_tok,
                c.moe_intermediate_size,
                c.shared_expert_intermediate_size // c.moe_intermediate_size,
                c.moe_routed_scaling_factor, c.norm_topk_prob, c.dtype,
                scoring="sigmoid", name="moe")(h)
        return x + y, counts


class Laguna(nn.Module):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32,
    {"expert_rows": the rows each held expert computed, i32[sparse layers,
    held]})."""
    cfg: LagunaConfig
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
            if c.mlp_layer_types[i] == SPARSE:
                counts.append(rows)
        with phase_scope("fwd_bwd", sub="head"):
            x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                              name="lm_head")(x)
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, len(c.held_experts)), jnp.int32))
        return logits.astype(jnp.float32), {"expert_rows": counts}
