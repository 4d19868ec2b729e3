"""Qwen3-Next decoder (the published ``config.json`` and modeling code of
``Qwen/Qwen3-Next-80B-A3B-Instruct``): three gated-DeltaNet
linear-attention layers to one gated softmax-attention layer, every layer's
FFN a routed-expert layer with one gated shared expert, zero-centred
RMSNorm. Layer ``l`` (from 0) is full attention where ``(l + 1) %
full_attention_interval == 0``. No biases::

    x += Mixer_l(N(x)) ;  x += MoE(N(x)) ;  logits = N(x_L) W_head
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)        float32, w starts at 0

**Gated attention** (:class:`GatedAttention`): ``[q | gate] = h W_q`` a head
at a time, ``k = h W_k``, ``v = h W_v``; ``q`` and ``k`` through ``N`` over a
head; rotary on the first ``partial_rotary_factor`` of a head's dims (the
half-split convention, ``attention.rotate_half_partial``); causal softmax
of ``q k^T / sqrt(head_dim)`` in float32, each key-value head serving
``heads / kv_heads`` query heads (``models/attention.py``'s
``blocked_causal_gqa``: flash kernels compiled for a TPU, elsewhere a block
of queries at a time); ``(out * sigmoid(gate)) W_o``.

**Gated DeltaNet** (:class:`GatedDeltaNet`): ``[q | k | v | z] = h W_qkvz``
and ``[b | a] = h W_ba``, both in THIS flat order (the published checkpoint
interleaves them a key head at a time; on seeded weights any fixed layout
is the same function); ``[q | k | v]`` through a causal depthwise
convolution of ``linear_conv_kernel_dim`` taps, then SiLU; ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k
L2-normalised a head, ``q`` scaled by ``d_k^-1/2``, each key head serving
``value heads / key heads`` value heads. A head's state ``S`` [d_k, d_v]
starts at zero and goes token by token::

    S <- exp(g_t) S ;  S <- S + k_t (beta_t (v_t - S^T k_t))^T ;  o_t = S^T q_t

computed in the chunked (WY) form of the published
``chunk_gated_delta_rule`` (:func:`chunk_gated_delta_rule`): inside a chunk
of ``chunk_size`` tokens the unit-lower-triangular system is solved for all
chunks at once, and a walk over the chunks carries ``S``: compiled for a
TPU the two Pallas kernels of ``ops/delta_rule.py``, whose state stays in
VMEM from chunk to chunk, elsewhere a ``lax.scan``. All of it
in float32 with its products at ``highest`` precision. Then ``o_t =
rmsnorm(o_t) * w_n * silu(z_t)`` a head and ``W_out``.

**Recomputation.** A decoder layer is recomputed in the backward pass
(``nn.remat``) from its input and its mixer's core output (``ATTN_OUT``:
the delta rule's ``o`` or the attention's weighted sum, [B, T, heads x
head dim]; compiled for a TPU the attention names its rows' log-sum-exp so
too, and its forward kernel runs once a layer). Inside a layer: the
attention keeps no score (``models/attention.py``); the delta rule runs in
segments of ``scan_segment`` tokens,
each recomputed from the state it started with, so that one segment's
chunk-parallel intermediates and one state a segment are all that is
alive (no per-token state ever is); what comes before the delta rule
(projections, convolution, gates, norms) is recomputed a sequence at a
time; and the routed experts' branch recomputes itself
(``moe.routed_experts``). So the recurrence runs twice before its
backward pass (forward, its segment's recomputation), the input
projections three times.

**Routed experts**: ``models/moe.py``'s ``MoE`` (router over ALL
``num_experts`` in float32 at ``highest``, top-k renormalised over the k,
held or not; the held experts' pairs through one sorted buffer and grouped
products), with its shared expert behind a sigmoid gate.

Parameter leaves are ``kernel``, ``embedding``, ``scale``, ``bias`` and
``experts``: a zero-centred gain, ``A_log`` and ``dt_bias`` are leaves
``bias`` (zeros) inside modules of those names; the gated norm's gain is a
``scale``; the convolution is a ``kernel`` [taps, channels]. Left out:
multi-token prediction and any auxiliary loss.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from oktopk_tpu.models.attention import (ATTN_OUT, blocked_causal_gqa,
                                         rotate_half_partial)
from oktopk_tpu.models.layers import HIGHEST, Kernel, causal_conv
from oktopk_tpu.models.moe import MoE, held_ids
from oktopk_tpu.obs.anatomy import phase_scope
from oktopk_tpu.ops import delta_rule


# ---- norms ------------------------------------------------------------------

def _rms(x32, eps):
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)`` in float32; ``w`` starts at zero and is the
    leaf ``bias``."""
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return (_rms(x.astype(jnp.float32), self.eps)
                * (1.0 + w)).astype(self.dtype)


class Bias(nn.Module):
    """A vector that starts at zero, for whoever uses it as a plain value."""
    size: int

    @nn.compact
    def __call__(self):
        return self.param("bias", nn.initializers.zeros, (self.size,))


# ---- gated softmax attention -------------------------------------------------

class GatedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    rms_norm_eps: float
    attn_block: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        norm = partial(ZeroCentredRMSNorm, self.rms_norm_eps, self.dtype)
        qg = dense(nh * hd * 2, name="q_proj")(h).reshape(b, t, nh, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = dense(nkv * hd, name="k_proj")(h).reshape(b, t, nkv, hd)
        v = dense(nkv * hd, name="v_proj")(h).reshape(b, t, nkv, hd)
        q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)

        rot = int(hd * self.partial_rotary_factor)
        inv_freq = 1.0 / self.rope_theta ** (
            jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
        cos = jnp.cos(angles).astype(self.dtype)
        sin = jnp.sin(angles).astype(self.dtype)
        q = rotate_half_partial(q, cos, sin)
        k = rotate_half_partial(k, cos, sin)
        out = blocked_causal_gqa(q, k, v, hd ** -0.5, self.attn_block)
        out = out.reshape(b, t, nh * hd) * jax.nn.sigmoid(
            gate.reshape(b, t, nh * hd))
        return dense(d, name="o_proj")(out)


# ---- gated delta rule --------------------------------------------------------

def inv_unit_lower(low):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` [..., C, C]: the
    solution of a chunk's unit-lower-triangular system for every right-hand
    side at once. ``L`` is nilpotent, so the Neumann series ends, and its
    first C terms are the product ``(I - L)(I + L^2)(I + L^4)...``:
    matrix products only, where forward substitution is C dependent
    steps."""
    c = low.shape[-1]
    mm = partial(jnp.matmul, precision=HIGHEST)
    inv, power, covered = jnp.eye(c, dtype=low.dtype) - low, low, 2
    while covered < c:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        covered *= 2
    return inv


def _scan_chunks(u, w, qk, q_dec, k_dec, last, state):
    """A segment's chunks walked from ``state`` in plain XLA, a
    ``lax.scan`` step a chunk: the stacked u [N, B, Hv, C, dv], w, q_dec,
    k_dec [N, B, Hv, C, dk], qk [N, B, Hv, C, C] and last [N, B, Hv] of
    :func:`chunk_gated_delta_rule` -> o [N, B, Hv, C, dv] and the state
    after the last chunk."""
    ein = partial(jnp.einsum, precision=HIGHEST)

    def one_chunk(s, xs):
        u_i, w_i, qk_i, q_i, k_i, last_i = xs
        v_new = u_i - ein("bhcd,bhde->bhce", w_i, s)
        o = ein("bhcd,bhde->bhce", q_i, s) + ein("bhij,bhje->bhie", qk_i,
                                                  v_new)
        s = s * last_i[..., None, None] + ein("bhcd,bhce->bhde", k_i, v_new)
        return s, o

    state, o = lax.scan(one_chunk, state, (u, w, qk, q_dec, k_dec, last))
    return o, state


def chunk_gated_delta_rule(q, k, v, g, beta, state, chunk: int):
    """The published ``chunk_gated_delta_rule`` on a stretch of tokens that
    starts from ``state``. q, k [B, T, Hk, dk] (normalised, q scaled); v [B,
    T, Hv, dv]; g (log decay, <= 0), beta [B, T, Hv]; state [B, Hv, dk, dv];
    ``chunk`` divides T. Returns o [B, T, Hv, dv] and the state after the
    last token. Float32, every product at ``highest``.

    With ``G_i`` the running sum of g inside a chunk and ``D_ij = exp(G_i -
    G_j)`` (i >= j): ``L = strict_lower((k beta) k^T * D)``, ``T = (I +
    L)^-1``, ``u = T (v beta)``, ``w = T (k beta exp(G))``; then a chunk at
    a time, with S the state before it: ``v' = u - w S``, ``o = (q exp(G))
    S + lower(q k^T * D) v'``, ``S <- S exp(G_C) + (k exp(G_C - G))^T v'``.
    Two forms of that walk, and the platform and the shapes choose
    (``ops/delta_rule.on_this_platform``, which also records the call for
    ``utils/profiling.snapshot``): compiled for a TPU with dk, dv whole
    lane rows and the chunk whole sublanes, ``ops/delta_rule.delta_rule``,
    Pallas kernels forward and backward that keep S in VMEM; anywhere else
    :func:`_scan_chunks` (under ``OKTOPK_PALLAS_INTERPRET=1`` the kernels,
    interpreted: tests).
    """
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    n = t // chunk
    ein = partial(jnp.einsum, precision=HIGHEST)

    def chunks(x, heads):
        """[B, T, heads', ...] -> [N, B, Hv, C, ...], a key head repeated
        for the value heads it serves."""
        x = x.astype(jnp.float32).reshape((b, n, chunk) + x.shape[2:])
        x = jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)
        return jnp.repeat(x, hv // heads, axis=2) if heads != hv else x

    q, k, v = chunks(q, hk), chunks(k, hk), chunks(v, hv)
    g, beta = chunks(g, hv), chunks(beta, hv)
    gc = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # the exponent is <= 0 at and under the diagonal; above it would
    # overflow, so it is masked before the exp and after
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    low = jnp.where(strict, ein("nbhid,nbhjd->nbhij", k_beta, k) * decay, 0.0)
    since_start = jnp.exp(gc)[..., None]     # a token's decay of S
    solved = ein("nbhij,nbhjd->nbhid", jax.checkpoint(inv_unit_lower)(low),
                 jnp.concatenate([v_beta, k_beta * since_start], axis=-1))
    u, w = solved[..., :dv], solved[..., dv:]
    qk = ein("nbhid,nbhjd->nbhij", q, k) * decay
    q_dec = q * since_start
    k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])

    walk = (delta_rule.delta_rule
            if delta_rule.on_this_platform(n, chunk, hv, dk, dv)
            else _scan_chunks)
    o, state = walk(u, w, qk, q_dec, k_dec, last, state.astype(jnp.float32))
    # [N, B, Hv, C, dv] -> [B, T, Hv, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(b, t, hv, dv)
    return o, state


def gated_delta_rule(q, k, v, g, beta, chunk: int, segment: int):
    """The recurrence over whole sequences from a zero state: o [B, T, Hv,
    dv]. :func:`chunk_gated_delta_rule` a segment of ``segment`` tokens at
    a time under a ``lax.scan`` that carries the state, each segment
    recomputed in the backward pass from the state it started with. T is
    padded to whole chunks (and whole segments) with tokens that leave the
    state as it is (k = 0, beta = 0, g = 0)."""
    b, t, hv = g.shape
    dk, dv = q.shape[-1], v.shape[-1]
    segment = max(chunk, segment // chunk * chunk)
    whole = -(-t // chunk) * chunk
    if whole > segment:
        whole = -(-t // segment) * segment
    else:
        segment = whole
    pad = lambda x: jnp.pad(x, ((0, 0), (0, whole - t))
                            + ((0, 0),) * (x.ndim - 2))
    # [B, T, ...] -> [segments, B, segment, ...]
    cut = lambda x: jnp.moveaxis(pad(x).reshape(
        (b, whole // segment, segment) + x.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_segment(state, xs):
        o, state = chunk_gated_delta_rule(*xs, state, chunk)
        return state, o

    _, o = lax.scan(one_segment, jnp.zeros((b, hv, dk, dv), jnp.float32),
                    tuple(cut(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(b, whole, hv, dv)[:, :t]


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule_inputs(h, w_qkvz, w_ba, w_conv, a_log, dt_bias, hk: int,
                      hv: int, dk: int, dv: int):
    """One sequence h [T, D] -> what the recurrence and the gated norm
    take: q, k [T, Hk, dk] (float32, normalised, q scaled), v, z [T, Hv,
    dv], g (float32), beta [T, Hv]."""
    t = h.shape[0]
    key_dim, conv_dim = hk * dk, 2 * hk * dk + hv * dv
    qkvz, ba = h @ w_qkvz, h @ w_ba
    qkv = jax.nn.silu(causal_conv(qkvz[:, :conv_dim], w_conv))
    q = _l2norm(qkv[:, :key_dim].reshape(t, hk, dk)) * dk ** -0.5
    k = _l2norm(qkv[:, key_dim:2 * key_dim].reshape(t, hk, dk))
    v = qkv[:, 2 * key_dim:].reshape(t, hv, dv)
    z = qkvz[:, conv_dim:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:].astype(jnp.float32) + dt_bias)
    return q, k, v, z, g, beta


class GatedRMSNorm(nn.Module):
    """``rmsnorm(x) * scale * silu(gate)`` over the last axis, float32."""
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, gate):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        y = _rms(x.astype(jnp.float32), self.eps) * scale
        return (y * jax.nn.silu(gate.astype(jnp.float32))).astype(self.dtype)


class GatedDeltaNet(nn.Module):
    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int
    rms_norm_eps: float
    chunk_size: int
    scan_segment: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        hk, hv, dk, dv = (self.num_k_heads, self.num_v_heads,
                          self.head_k_dim, self.head_v_dim)
        conv_dim = 2 * hk * dk + hv * dv
        weights = (Kernel(conv_dim + hv * dv, name="in_proj_qkvz")(d),
                   Kernel(2 * hv, name="in_proj_ba")(d),
                   Kernel(conv_dim, name="conv")(self.conv_kernel))
        weights = tuple(w.astype(self.dtype) for w in weights) + (
            Bias(hv, name="A_log")(), Bias(hv, name="dt_bias")())
        # a sequence at a time, each recomputed in the backward pass: the
        # [T, 12288] projection, the convolution's input and output
        q, k, v, z, g, beta = lax.map(
            jax.checkpoint(lambda s: delta_rule_inputs(
                s, *weights, hk, hv, dk, dv)), h.astype(self.dtype))
        with phase_scope("fwd_bwd", sub="delta_rule"):
            o = checkpoint_name(
                gated_delta_rule(q, k, v, g, beta, self.chunk_size,
                                 self.scan_segment), ATTN_OUT)
        o = GatedRMSNorm(self.rms_norm_eps, self.dtype, name="norm")(o, z)
        return nn.Dense(d, use_bias=False, dtype=self.dtype,
                        name="out_proj")(o.reshape(b, t, hv * dv))


# ---- the model ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json`` of Qwen3-Next-80B-A3B-Instruct under
    its own key names, and what this chip holds and how it computes."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # which experts this chip holds (ids under num_experts); None: all
    held_experts: Optional[Tuple[int, ...]] = None
    attn_block: int = 512
    chunk_size: int = 64        # the published kernel's
    scan_segment: int = 1024    # tokens of the recurrence recomputed at once
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "held_experts", held_ids(
            self.held_experts, self.num_experts))
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is a whole number of routed "
                             "experts wide")

    def full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @classmethod
    def tiny(cls, **kw):
        """CPU-sized: every mechanism of the published model at toy widths
        (one period of 3 + 1 layers, 16 experts with 4 a token, chunks and
        segments shorter than the 64-token sequence)."""
        return cls(**{**dict(
            vocab_size=512, hidden_size=128, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=64,
            shared_expert_intermediate_size=64, rope_theta=10000.0,
            attn_block=16, chunk_size=8, scan_segment=32), **kw})


class DecoderLayer(nn.Module):
    """One pre-norm block, ``full`` attention or linear. Returns x and the
    rows each held expert computed (i32[held])."""
    cfg: Qwen3NextConfig
    full: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = partial(ZeroCentredRMSNorm, c.rms_norm_eps, c.dtype)
        with phase_scope("fwd_bwd", sub=("attention" if self.full
                                         else "linear_attention")):
            mixer = GatedAttention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                c.partial_rotary_factor, c.rope_theta, c.rms_norm_eps,
                c.attn_block, c.dtype, name="attn") if self.full else (
                    GatedDeltaNet(
                        c.linear_num_key_heads, c.linear_num_value_heads,
                        c.linear_key_head_dim, c.linear_value_head_dim,
                        c.linear_conv_kernel_dim, c.rms_norm_eps,
                        c.chunk_size, c.scan_segment, c.dtype,
                        name="linear_attn"))
            x = x + mixer(norm(name="attn_norm")(x))
        y, counts = MoE(
            c.num_experts, c.held_experts, c.num_experts_per_tok,
            c.moe_intermediate_size,
            c.shared_expert_intermediate_size // c.moe_intermediate_size,
            1.0, c.norm_topk_prob, c.dtype, shared_gate=True,
            name="moe")(norm(name="ffn_norm")(x))
        return x + y, counts


class Qwen3Next(nn.Module):
    """tokens [B, T] int32 -> (logits [B, T, vocab] float32,
    {"expert_rows": the rows each held expert computed, i32[layers,
    held]})."""
    cfg: Qwen3NextConfig
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
            x, rows = layer_cls(c, c.full_attention(i),
                                name=f"layers_{i}")(x)
            counts.append(rows)
        with phase_scope("fwd_bwd", sub="head"):
            x = ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
            logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                              name="lm_head")(x)
        return logits.astype(jnp.float32), {"expert_rows": jnp.stack(counts)}
