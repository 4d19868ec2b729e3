"""DeepSeek-V2 decoder (arXiv:2405.04434; the published ``config.json`` of
``deepseek-ai/DeepSeek-V2-Lite``): multi-head latent attention (MLA) with a
decoupled rotary part under YaRN, RMSNorm, SwiGLU, and a routed-expert layer
that is told which experts it holds.

Per layer, pre-norm residual blocks, no biases::

    x += Attn(RMSNorm(x)) ;  x += FFN(RMSNorm(x))

**MLA** (``q_lora_rank`` null): ``q = h W_q`` -> heads x (nope | rope);
``[c | k_pe] = h W_kva`` (``k_pe`` is one head shared by all);
``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb``. Rotary on ``q_pe`` and
``k_pe`` (adjacent pairs, YaRN frequencies, :func:`yarn_inv_freq`); scores
``(q_nope k_nope^T + q_pe k_pe^T) * softmax_scale`` with the YaRN
``mscale_all_dim`` squared folded into the scale; causal softmax in float32.
Attention is :func:`blocked_causal_attention`, two forms of one function
and the platform chooses: compiled for a TPU the flash kernels of
``ops/flash_gqa.py`` (``flash_mla``: a tile of scores lives in VMEM from its
product to its use, forward and backward, and the q, k, v parts are read as
they lie); anywhere else a block of queries at a time against the keys at
or before it, each block recomputed in the backward pass, a sequence at a
time. In neither is a [heads, T, T] score tensor ever alive.

**Recomputation**, two levels. Every decoder layer is recomputed in the
backward pass (``nn.remat``) from what the forward pass keeps of it: its
input and what carries the name ``ATTN_OUT``: its attention output ([B, T,
heads, v_head_dim], the size of the input; the plain form tags it a query
block at a time) and, on a TPU, the rows' log-sum-exp beside it, so that
the recomputed layer runs no forward kernel again. Inside a layer the
routed experts' branch and each sequence of a dense layer's SwiGLU
recompute themselves (``jax.checkpoint``), and so does each query block of
the plain form, whose scores, mask and softmax therefore run twice before
their backward pass (the forward pass and the block's own recomputation:
the layer's starts ``o_proj`` from the kept blocks); the kernels' backward
pass recomputes a tile's probabilities from the log-sum-exp and keeps no
score anywhere. The routed experts' products run twice as well (nothing in
the layer's backward pass needs their output, so the layer's recomputation
of them is dead code).

**Routed experts** (:class:`MoE`): ``s = softmax(h W_r)`` over ALL
``n_routed_experts`` in float32 at ``highest`` precision (``scoring``
``"sigmoid"``: each expert's own sigmoid, ``models/laguna.py``), greedy top-k,
weights unrenormalised unless ``norm_topk_prob`` (then over the k, held or
not); the shared experts sit behind a sigmoid gate where ``shared_gate``
(``models/qwen3_next.py``). ``held_experts`` says
which experts this chip holds (expert parallelism: the others live on other
chips); the layer computes ``sum_{e in topk, e held} s_e E_e(h)`` plus the
shared experts, and what the absent experts would add is left out: no code
stands in for the other chips or their exchange. No token routed to a held
expert is dropped, at any imbalance: the token-expert pairs of the held
experts are sorted by expert into ONE buffer and go through grouped
products (``lax.ragged_dot``), so the work does not follow the busiest
expert. The buffer holds ``CAPACITY_FACTOR`` times their mean number and
its empty rows are computed as zeros, so every step that fits does the same
work; a step with more pairs than that computes every expert over all rows,
masked, instead (``lax.cond`` on the number of pairs).

Parameter leaves are ``kernel``, ``embedding``, ``scale`` and ``experts``
(a stack of kernels, expert axis first). Flax module names avoid the names
of ``obs/anatomy.SUB_SCOPES["fwd_bwd"]``, which readers of a trace look for
in an operation's scope path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from oktopk_tpu.obs.anatomy import phase_scope
from oktopk_tpu.ops import flash_gqa

HIGHEST = lax.Precision.HIGHEST
# what a decoder layer keeps across its own recomputation beside its input:
# the output of blocked_causal_attention (tagged a query block at a time in
# the plain form) and, from the flash kernels, the rows' log-sum-exp
ATTN_OUT = "attn_out"


# ---- rotary embedding under YaRN ------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies: the original ones where a
    dimension turns more than ``beta_fast`` times over the original length,
    the original over ``factor`` where it turns less than ``beta_slow``
    times, a linear ramp between."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    inter = extra / factor

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate_pairs(x, cos, sin):
    """Rotates adjacent pairs ``(x[2i], x[2i+1])`` by the angle of pair i.
    ``x`` [..., T, heads, dim]; ``cos``/``sin`` [T, dim // 2]."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


# ---- attention over query blocks -------------------------------------------

def _attend_block(q_nope, q_pe, k_nope, k_pe, v, start, end, scale):
    """One sequence's queries ``start .. end`` against its keys ``0 ..
    end``. q_* [block, H, d]; k_nope, v [T, H, d]; k_pe [T, d] (one head,
    shared). The keys come whole and are cut here, so that a caller who
    recomputes this keeps no cut copy of them."""
    k_nope, k_pe, v = k_nope[:end], k_pe[:end], v[:end]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe))
    s = s.astype(jnp.float32) * scale
    rows = start + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _blocked_xla(q_nope, q_pe, k_nope, k_pe, v, scale: float, block: int):
    """:func:`blocked_causal_attention` in plain XLA: a sequence at a time
    and ``block`` queries at a time. Each block's scores are recomputed in
    the backward pass (``jax.checkpoint``), so the largest score tensor
    alive is [H, block, T], of one sequence. Each block's output carries
    the name ``ATTN_OUT``, for a caller that recomputes all of this and
    would keep the output (``save_only_these_names``): a block at a time,
    because XLA:TPU packs [B, block, H, dv] pieces into the holes of its
    heap, and one [B, T, H, dv] array that lives as long raises it."""
    t = q_nope.shape[1]

    def one_sequence(seq):
        qn, qp, kn, kp, vv = seq
        outs = []
        for start in range(0, t, block):
            end = min(start + block, t)
            fn = jax.checkpoint(partial(_attend_block, start=start, end=end,
                                        scale=scale))
            outs.append(checkpoint_name(
                fn(qn[start:end], qp[start:end], kn, kp, vv), ATTN_OUT))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    return lax.map(one_sequence, (q_nope, q_pe, k_nope, k_pe, v))


def blocked_causal_attention(q_nope, q_pe, k_nope, k_pe, v, scale: float,
                             block: int):
    """Causal attention with MLA's split heads: q_nope and k_nope [B, T, H,
    d], q_pe [B, T, H, rope], k_pe [B, T, rope] (one head, shared by all),
    v [B, T, H, dv] -> [B, T, H, dv]. Two forms of one function, and the
    platform chooses (``ops/flash_gqa.split_on_this_platform``, which also
    records the call for ``utils/profiling.snapshot``), as for grouped
    heads in ``qwen3_next.blocked_causal_gqa``:

    * compiled for a TPU, ``ops/flash_gqa.flash_mla``: the Pallas kernels,
      forward and backward, whose score tiles live in VMEM. The output and
      the rows' log-sum-exp are both named ``ATTN_OUT``, so a layer
      recomputed from its saved names finds the backward kernels'
      residuals and runs no forward kernel again. ``block`` is not read
      there: the tiles are the kernel's own rule's;
    * anywhere else :func:`_blocked_xla`, ``block`` queries at a time
      (under ``OKTOPK_PALLAS_INTERPRET=1`` the kernels, interpreted: tests).
    """
    t, heads = q_nope.shape[1:3]
    block = min(block, t)
    if flash_gqa.split_on_this_platform(
            t, heads, q_nope.shape[-1], q_pe.shape[-1], v.shape[-1], block):
        return flash_gqa.flash_mla(q_nope, q_pe, k_nope, k_pe, v, scale,
                                   save_as=ATTN_OUT)
    return _blocked_xla(q_nope, q_pe, k_nope, k_pe, v, scale, block)


# ---- routed experts ---------------------------------------------------------

def _grouped_branch(rows: int, x, weights, routed, counts,
                    w_gate, w_up, w_down, act=jax.nn.silu):
    """The token-expert pairs of the held experts, sorted by expert into
    one buffer of ``rows`` rows (enough for all of them: the caller
    checks), through grouped products (``lax.ragged_dot``: expert h's
    weights for the rows of its group), weighted and added back to their
    tokens. The rows past the last pair are zeros in the LAST expert's
    group: XLA:TPU's kernel works on the rows that lie in a group and
    leaves the others unwritten, so with every row in a group each step
    does the work of ``rows`` rows, however many pairs its routing made,
    and every row of a product is written."""
    tokens = x.shape[0]
    # expert-major, so that a stable sort leaves the pairs grouped by
    # expert, in token order
    pair = jnp.argsort(~routed.T.reshape(-1), stable=True)[:rows]
    token = pair % tokens
    pairs = jnp.sum(counts)
    groups = counts.at[-1].add(rows - pairs)
    # a row past the last pair reads nothing and adds nothing (``keep``
    # cuts a cotangent too)
    valid = (lax.iota(jnp.int32, rows) < pairs)[:, None]
    keep = lambda a: jnp.where(valid, a, 0.0)
    xg = keep(x[token])
    g = lax.ragged_dot(xg, w_gate, groups)
    u = lax.ragged_dot(xg, w_up, groups)
    y = lax.ragged_dot(act(g) * u, w_down, groups)
    w = keep(weights.T.reshape(-1)[pair][:, None])
    return jnp.zeros_like(x).at[token].add(y * w.astype(y.dtype))


def _all_rows_branch(x, weights, routed, counts, w_gate, w_up, w_down,
                     act=jax.nn.silu):
    """More pairs than the buffer holds: each held expert over all rows,
    masked by the routing, one expert at a time."""
    del counts

    @jax.checkpoint
    def one(out, operand):
        wg, wu, wd, w = operand
        y = swiglu(x, wg, wu, wd, act)
        return out + y * w[:, None].astype(y.dtype), None

    w = jnp.where(routed, weights, 0.0).T
    out, _ = lax.scan(one, jnp.zeros_like(x), (w_gate, w_up, w_down, w))
    return out


# the grouped branch's buffer, in mean numbers of token-expert pairs. At
# seeded weights a layer's pairs lie 0.75-1.48 of their mean, batch by batch
# (DeepSeek-V2-Lite's widths, 16,384 tokens; 192 readings, s.d. 0.11), and a
# layer that passes the buffer runs all rows, at twice the grouped branch's
# time: the factor keeps that rare
CAPACITY_FACTOR = 1.5


def expert_capacity(tokens: int, held: int, k: int, experts: int) -> int:
    """The grouped branch's buffer in rows: ``CAPACITY_FACTOR`` times the
    mean number of token-expert pairs at the ``held`` of ``experts`` experts
    (``k`` a token), rounded up to 128, and never over the most there can
    be (a token meets a held expert at most once)."""
    mean, most = tokens * k * held / experts, tokens * min(k, held)
    return min(most, -(-math.ceil(CAPACITY_FACTOR * mean) // 128) * 128)


def routed_experts(x, weights, routed, w_gate, w_up, w_down,
                   capacity: int, k: int, act=jax.nn.silu):
    """``sum_h weights[:, h] * E_h(x)`` over the held experts h (``E_h(x) =
    (act(x W_gate) * x W_up) W_down``), for the tokens ``routed`` [T, H]
    gives each (``k`` experts a token, held or not). Returns it and the
    rows each held expert computed, i32[H]. The pairs go through the
    grouped branch where its ``capacity`` rows hold them all, else every
    expert runs over all rows, masked. Each branch is recomputed in the
    backward pass, so that neither's intermediates are kept (a ``cond``
    keeps those of both)."""
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    operands = (x, weights, routed, counts, w_gate, w_up, w_down)
    grouped = jax.checkpoint(partial(_grouped_branch, capacity, act=act))
    if capacity >= x.shape[0] * min(k, routed.shape[1]):    # holds any step
        return grouped(*operands), counts
    return lax.cond(jnp.sum(counts) <= capacity, grouped,
                    jax.checkpoint(_all_rows(act)), *operands), counts


@lru_cache(maxsize=None)
def _all_rows(act):
    """``_all_rows_branch`` under ``act``: one function an activation, so
    that every layer's ``cond`` traces the same branch (jax shares a trace
    by the function's identity, and the step program one body)."""
    return partial(_all_rows_branch, act=act)


# ---- modules ---------------------------------------------------------------

class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + self.eps)
        return (y * scale).astype(self.dtype)


class Kernel(nn.Module):
    """A bias-free projection's ``kernel`` [in, out], for whoever applies it
    as a plain function."""
    features: int

    @nn.compact
    def __call__(self, fan_in: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (fan_in, self.features))


# a gated expert's activation, by the published ``hidden_act``
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


# a router's scores from its logits [T, experts], by the published
# ``scoring_func``: over all experts, or each expert's own
SCORINGS = {"softmax": partial(jax.nn.softmax, axis=-1),
            "sigmoid": jax.nn.sigmoid}


def swiglu(x, w_gate, w_up, w_down, act=jax.nn.silu):
    return (act(x @ w_gate) * (x @ w_up)) @ w_down


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``. ``by_sequence``: x [B, T, D] a
    sequence at a time, each recomputed in the backward pass, so that the
    [T, width] intermediates of one sequence are all that is alive (the
    dense layer's width is over five times the hidden size)."""
    width: int
    dtype: Any = jnp.float32
    by_sequence: bool = False

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w = [Kernel(f, name=n)(i).astype(self.dtype) for n, i, f in (
            ("gate_proj", d, self.width), ("up_proj", d, self.width),
            ("down_proj", self.width, d))]
        x = x.astype(self.dtype)
        if self.by_sequence and x.ndim == 3:
            return lax.map(jax.checkpoint(lambda s: swiglu(s, *w)), x)
        return swiglu(x, *w)


class ExpertStack(nn.Module):
    """One projection of every held expert: ``experts`` [held, in, out]."""
    held: int
    features: int

    @nn.compact
    def __call__(self, fan_in: int):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        return self.param("experts", init,
                          (self.held, fan_in, self.features))


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


class MoE(nn.Module):
    n_routed_experts: int
    held_experts: Tuple[int, ...]
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    dtype: Any = jnp.float32
    # the shared experts' output times sigmoid(x w_g), a scalar a token
    shared_gate: bool = False
    # the routed experts' gate activation, a key of ACTIVATIONS
    hidden_act: str = "silu"
    # what turns the router's logits into scores, a key of SCORINGS
    scoring: str = "softmax"

    @nn.compact
    def __call__(self, h, router_input=None):
        """``router_input`` (None: ``h``): what the router scores, where
        that is not what the experts read (``models/smallthinker.py``
        routes from the layer's normalised input, before attention)."""
        shape = h.shape
        x = h.reshape(-1, shape[-1])
        tokens, d = x.shape
        held, k = len(self.held_experts), self.num_experts_per_tok
        with phase_scope("fwd_bwd", sub="router"):
            w_r = self.param("kernel", nn.initializers.lecun_normal(),
                             (d, self.n_routed_experts))
            r = x if router_input is None else router_input.reshape(-1, d)
            scores = SCORINGS[self.scoring](
                jnp.dot(r.astype(jnp.float32), w_r, precision=HIGHEST))
            top_w, top_i = lax.top_k(scores, k)
            if self.norm_topk_prob:
                top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
            top_w = top_w * self.routed_scaling_factor
            # [T, k, H] -> this chip's experts only
            hit = top_i[..., None] == jnp.asarray(self.held_experts,
                                                  jnp.int32)
            routed = jnp.any(hit, axis=1)
            weights = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)
        with phase_scope("fwd_bwd", sub="experts"):
            f = self.moe_intermediate_size
            w_gate = ExpertStack(held, f, name="routed_gate")(d)
            w_up = ExpertStack(held, f, name="routed_up")(d)
            w_down = ExpertStack(held, d, name="routed_down")(f)
            y, counts = routed_experts(
                x.astype(self.dtype), weights, routed,
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype),
                expert_capacity(tokens, held, k, self.n_routed_experts), k,
                ACTIVATIONS[self.hidden_act])
        if self.n_shared_experts:
            with phase_scope("fwd_bwd", sub="shared"):
                shared = SwiGLU(f * self.n_shared_experts, self.dtype,
                                name="shared_ffn")(x)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        name="shared_gate")(x))
                y = y + shared
        return y.reshape(shape), counts


def held_ids(held, experts: int) -> Tuple[int, ...]:
    """A configuration's ``held_experts`` as a tuple of distinct ids under
    ``experts``, at least one; None: all of them."""
    held = tuple(int(e) for e in (range(experts) if held is None else held))
    if not held or len(set(held)) != len(held) or not all(
            0 <= e < experts for e in held):
        raise ValueError(f"held_experts {held}: distinct ids under "
                         f"{experts}, at least one")
    return held


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
