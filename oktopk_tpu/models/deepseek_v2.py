"""DeepSeek-V2 decoder (arXiv:2405.04434; the published ``config.json`` of
``deepseek-ai/DeepSeek-V2-Lite``): multi-head latent attention (MLA) with a
decoupled rotary part under YaRN, RMSNorm, SwiGLU, and a routed-expert layer
that is told which experts it holds.

Per layer, pre-norm residual blocks, no biases::

    x += Attn(RMSNorm(x)) ;  x += FFN(RMSNorm(x))

**MLA** (``q_lora_rank`` null): ``q = h W_q`` -> heads x (nope | rope);
``[c | k_pe] = h W_kva`` (``k_pe`` is one head shared by all);
``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb``. Rotary on ``q_pe`` and
``k_pe`` (adjacent pairs, YaRN frequencies: ``attention.rotate_pairs``,
``attention.yarn_inv_freq``); scores ``(q_nope k_nope^T + q_pe k_pe^T) *
softmax_scale`` with the YaRN ``mscale_all_dim`` squared folded into the
scale; causal softmax in float32. Attention is
``models/attention.py``'s ``blocked_causal_attention``: compiled for a TPU
the flash kernels of ``ops/flash_gqa.py`` (``flash_mla``), anywhere else a
block of queries at a time, a sequence at a time.

**Recomputation**, two levels. Every decoder layer is recomputed in the
backward pass (``nn.remat``) from what the forward pass keeps of it: its
input and what carries the name ``ATTN_OUT``: its attention output ([B, T,
heads, v_head_dim], the size of the input) and, on a TPU, the rows'
log-sum-exp beside it, so that the recomputed layer runs no forward kernel
again (the layer's recomputation starts ``o_proj`` from the kept output).
Inside a layer the routed experts' branch and each sequence of a dense
layer's SwiGLU recompute themselves (``jax.checkpoint``), and so does what
attention keeps no score of (``models/attention.py``).

**Routed experts**: ``models/moe.py``'s ``MoE`` at its defaults (softmax
scores over ALL ``n_routed_experts``, top-k unrenormalised, the shared
experts ungated; the held experts' pairs through one sorted buffer and
grouped products).

Parameter leaves are ``kernel``, ``embedding``, ``scale`` and ``experts``
(a stack of kernels, expert axis first). Flax module names avoid the names
of ``obs/anatomy.SUB_SCOPES["fwd_bwd"]``, which readers of a trace look for
in an operation's scope path.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from oktopk_tpu.models.attention import (ATTN_OUT, blocked_causal_attention,
                                         rotate_pairs, yarn_inv_freq,
                                         yarn_mscale)
from oktopk_tpu.models.layers import RMSNorm, SwiGLU
from oktopk_tpu.models.moe import MoE, held_ids
from oktopk_tpu.obs.anatomy import phase_scope


class MLA(nn.Module):
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope: Tuple[float, ...]     # theta, factor, original length, beta_fast,
    # beta_slow, mscale, mscale_all_dim
    attn_block: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        nh, dn, dr, dv = (self.num_heads, self.qk_nope_head_dim,
                          self.qk_rope_head_dim, self.v_head_dim)
        theta, factor, orig, fast, slow, mscale, mscale_all = self.rope
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(nh * (dn + dr), name="q_proj")(h).reshape(b, t, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        ckv = dense(self.kv_lora_rank + dr, name="kv_a_proj")(h)
        c, k_pe = ckv[..., :self.kv_lora_rank], ckv[..., self.kv_lora_rank:]
        c = RMSNorm(self.rms_norm_eps, self.dtype, name="kv_a_norm")(c)
        kv = dense(nh * (dn + dv), name="kv_b_proj")(c).reshape(
            b, t, nh, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        inv_freq = yarn_inv_freq(dr, theta, factor, int(orig), fast, slow)
        angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
        amp = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
        cos = (jnp.cos(angles) * amp).astype(self.dtype)
        sin = (jnp.sin(angles) * amp).astype(self.dtype)
        q_pe = rotate_pairs(q_pe, cos, sin)
        k_pe = rotate_pairs(k_pe[:, :, None, :], cos, sin)[:, :, 0, :]

        m = yarn_mscale(factor, mscale_all)
        scale = (dn + dr) ** -0.5 * m * m
        out = blocked_causal_attention(q_nope, q_pe, k_nope, k_pe, v, scale,
                                       self.attn_block)
        return dense(d, name="o_proj")(out.reshape(b, t, nh * dv))


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The published ``config.json`` of DeepSeek-V2-Lite under its own key
    names (``rope_scaling`` flattened to ``rope_*``), and what this chip
    holds and how it computes."""
    vocab_size: int = 102400
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    # which experts this chip holds (ids under n_routed_experts); None: all
    held_experts: Optional[Tuple[int, ...]] = None
    attn_block: int = 512
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "held_experts", held_ids(
            self.held_experts, self.n_routed_experts))

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (2 dense + 2 expert layers, 8 experts with 2 a token)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, num_hidden_layers=4,
            first_k_dense_replace=2, intermediate_size=256,
            moe_intermediate_size=64, n_routed_experts=8,
            n_shared_experts=2, num_experts_per_tok=2,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
            rope_original_max_position=32, attn_block=16), **kw})


class DecoderLayer(nn.Module):
    """One pre-norm block; ``dense`` is the SwiGLU of ``intermediate_size``
    in the routed experts' place. Returns x and the rows each held expert
    computed (i32[held]; zeros without experts)."""
    cfg: DeepseekV2Config
    dense: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(RMSNorm, c.rms_norm_eps, c.dtype)
        with phase_scope("fwd_bwd", sub="attention"):
            attn = MLA(c.num_attention_heads, c.kv_lora_rank,
                       c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                       c.rms_norm_eps,
                       (c.rope_theta, c.rope_factor,
                        c.rope_original_max_position, c.rope_beta_fast,
                        c.rope_beta_slow, c.rope_mscale,
                        c.rope_mscale_all_dim), c.attn_block, c.dtype,
                       name="attn")
            x = x + attn(norm(name="attn_norm")(x))
        h = norm(name="ffn_norm")(x)
        if self.dense:
            with phase_scope("fwd_bwd", sub="mlp"):
                y = SwiGLU(c.intermediate_size, c.dtype, True, name="ffn")(h)
            counts = jnp.zeros((len(c.held_experts),), jnp.int32)
        else:
            y, counts = MoE(c.n_routed_experts, c.held_experts,
                            c.num_experts_per_tok, c.moe_intermediate_size,
                            c.n_shared_experts, c.routed_scaling_factor,
                            c.norm_topk_prob, c.dtype, name="moe")(h)
        return x + y, counts


class DeepseekV2(nn.Module):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32,
    {"expert_rows": the rows each held expert computed, i32[expert layers,
    held]})."""
    cfg: DeepseekV2Config
    # the trainer initialises it in one jitted call (train/trainer.py)
    jit_init = True

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train   # no dropout
        c = self.cfg
        # each layer is recomputed in the backward pass from its input and
        # its attention output, all that the forward pass keeps of it: the
        # recomputation starts o_proj and what follows from the kept blocks
        # and runs no block's scores (each block's own checkpoint still
        # does, once, for its backward pass)
        layer_cls = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT))
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed")(tokens)
        counts = []
        for i in range(c.num_hidden_layers):
            dense_layer = i < c.first_k_dense_replace
            x, rows = layer_cls(c, dense_layer, name=f"layers_{i}")(x)
            if not dense_layer:
                counts.append(rows)
        with phase_scope("fwd_bwd", sub="head"):
            x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                              name="lm_head")(x)
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, len(c.held_experts)), jnp.int32))
        return logits.astype(jnp.float32), {"expert_rows": counts}
