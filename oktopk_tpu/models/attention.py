"""Causal softmax attention as the decoder models call it, and their rotary
tables. No model file is imported here. Two entry points, one a head layout:

* :func:`blocked_causal_gqa`, grouped heads (``models/qwen3_next.py``'s
  gated attention, ``models/smallthinker.py`` and ``models/laguna.py`` with
  a window, ``models/ouro.py`` at a group of one, ``models/lfm2.py``);
* :func:`blocked_causal_attention`, MLA's split heads
  (``models/deepseek_v2.py``): a score is the sum of two products, and the
  rotary part's key is one head shared by all.

Each is two forms of one function, and the platform chooses at the call
(``ops/flash_gqa.on_this_platform`` / ``split_on_this_platform``, which
also record the call for ``utils/profiling.snapshot``): compiled for a TPU
the flash kernels of ``ops/flash_gqa.py``, whose score tiles live in VMEM
forward and backward; anywhere else :func:`_blocked_xla`, a block of
queries at a time, each recomputed in the backward pass (so its scores,
mask and softmax run twice before their backward pass). In neither is a
[heads, T, T] score tensor ever alive.

**Rotary**: adjacent pairs (:func:`rotate_pairs`, MLA's) or the half-split
convention over the first dims of a head (:func:`rotate_half_partial`);
plain or YaRN frequencies (:func:`yarn_inv_freq`); :class:`Rope` is one
entry of a published ``rope_parameters``, :func:`rotary_table` its cos and
sin.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from oktopk_tpu.ops import flash_gqa

# what a decoder layer keeps across its own recomputation (``nn.remat`` with
# ``save_only_these_names``) beside its input: the output of the entry points
# (tagged a query block at a time in the plain form) and, from the flash
# kernels, the rows' log-sum-exp, so that the recomputed layer runs no
# forward kernel again
ATTN_OUT = "attn_out"


# ---- rotary tables ---------------------------------------------------------

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


def rotate_half_partial(x, cos, sin):
    """Rotary on the first ``2 * cos.shape[-1]`` dims of every head, the
    half-split convention: with ``(x1, x2)`` the two halves of those dims,
    ``(x1 cos - x2 sin, x2 cos + x1 sin)``; the other dims pass. ``x``
    [..., T, heads, dim]; ``cos``/``sin`` [T, rotary dims // 2]."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


@dataclasses.dataclass(frozen=True)
class Rope:
    """One entry of a published ``rope_parameters`` under its own key
    names. ``rope_type`` ``default``: plain frequencies, and the fields
    after ``partial_rotary_factor`` are not read."""
    rope_theta: float
    partial_rotary_factor: float = 1.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}")


def rotary_table(rope: Rope, head_dim: int, tokens: int):
    """(cos, sin) [T, rotary dims // 2] of positions 0 .. T-1, float32:
    the first ``head_dim x partial_rotary_factor`` dims of a head turn;
    under YaRN at that many dims' frequencies, and both tables times the
    record's ``attention_factor``."""
    rot = int(head_dim * rope.partial_rotary_factor)
    if rope.rope_type == "yarn":
        inv_freq = jnp.asarray(yarn_inv_freq(
            rot, rope.rope_theta, rope.factor,
            rope.original_max_position_embeddings, rope.beta_fast,
            rope.beta_slow))
        amp = rope.attention_factor
    else:
        inv_freq = 1.0 / rope.rope_theta ** (
            jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        amp = 1.0
    angles = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angles) * amp, jnp.sin(angles) * amp


# ---- one block of queries --------------------------------------------------

def _attend_block(q_nope, q_pe, k_nope, k_pe, v, start, end, scale):
    """MLA's split heads: one sequence's queries ``start .. end`` against
    its keys ``0 .. end``. q_* [block, H, d]; k_nope, v [T, H, d]; k_pe [T,
    d] (one head, shared). The keys come whole and are cut here, so that a
    caller who recomputes this keeps no cut copy of them."""
    k_nope, k_pe, v = k_nope[:end], k_pe[:end], v[:end]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe))
    s = s.astype(jnp.float32) * scale
    rows = start + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _attend_block_gqa(q, k, v, start, end, scale, first=0, window=None):
    """Grouped heads: one sequence's queries ``start .. end`` against its
    keys ``first .. end``. q [block, G, R, d] (G key-value heads, R query
    heads each); k, v [T, G, d], cut here (a caller who recomputes this
    keeps no cut copy). ``window``: query i reads the keys j with ``i - j <
    window`` only."""
    k, v = k[first:end], v[first:end]
    s = jnp.einsum("qgrd,kgd->grqk", q, k).astype(jnp.float32) * scale
    rows = start + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    if first:   # no add of a zero: without a window nothing is lowered for it
        cols = cols + first
    seen = cols <= rows
    if window is not None:
        seen = seen & (rows - cols < window)
    s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("grqk,kgd->qgrd", p, v)


# ---- the walk over query blocks --------------------------------------------

def _blocked_xla(attend, cut, whole, block: int):
    """The plain XLA form of both entry points, ONE sequence's walk (the
    entry point maps it over the sequences, ``lax.map``): ``block`` queries
    at a time. ``cut``: the arrays [T, ...] of which a block takes its own
    rows (the queries); ``whole``: those every block is handed whole and
    cuts itself (keys and values). ``attend(start, end)`` is the function
    of one block, of ``(*cut[start:end], *whole)``. Each block's scores are
    recomputed in the backward pass (``jax.checkpoint``), so the largest
    score tensor alive is [H, block, T], of one sequence. Each block's
    output carries the name ``ATTN_OUT``, for a caller that recomputes all
    of this and would keep the output (``save_only_these_names``): a block
    at a time, because XLA:TPU packs [B, block, H, dv] pieces into the
    holes of its heap, and one [B, T, H, dv] array that lives as long
    raises it."""
    t = cut[0].shape[0]
    outs = []
    for start in range(0, t, block):
        end = min(start + block, t)
        fn = jax.checkpoint(attend(start, end))
        outs.append(checkpoint_name(
            fn(*(x[start:end] for x in cut), *whole), ATTN_OUT))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


# ---- the two entry points --------------------------------------------------

def blocked_causal_attention(q_nope, q_pe, k_nope, k_pe, v, scale: float,
                             block: int):
    """Causal attention with MLA's split heads: q_nope and k_nope [B, T, H,
    d], q_pe [B, T, H, rope], k_pe [B, T, rope] (one head, shared by all),
    v [B, T, H, dv] -> [B, T, H, dv]. Two forms of one function, and the
    platform chooses (``ops/flash_gqa.split_on_this_platform``), as for
    grouped heads in :func:`blocked_causal_gqa`:

    * compiled for a TPU, ``ops/flash_gqa.flash_mla``: the Pallas kernels,
      forward and backward, whose score tiles live in VMEM and which read
      the q, k, v parts as they lie. The output and the rows' log-sum-exp
      are both named ``ATTN_OUT``, so a layer recomputed from its saved
      names finds the backward kernels' residuals and runs no forward
      kernel again. ``block`` is not read there: the tiles are the
      kernel's own rule's;
    * anywhere else :func:`_blocked_xla`, ``block`` queries at a time
      against the keys at or before them (under
      ``OKTOPK_PALLAS_INTERPRET=1`` the kernels, interpreted: tests).
    """
    t, heads = q_nope.shape[1:3]
    block = min(block, t)
    if flash_gqa.split_on_this_platform(
            t, heads, q_nope.shape[-1], q_pe.shape[-1], v.shape[-1], block):
        return flash_gqa.flash_mla(q_nope, q_pe, k_nope, k_pe, v, scale,
                                   save_as=ATTN_OUT)

    def attend(start, end):
        return partial(_attend_block, start=start, end=end, scale=scale)

    return lax.map(lambda seq: _blocked_xla(attend, seq[:2], seq[2:], block),
                   (q_nope, q_pe, k_nope, k_pe, v))


def blocked_causal_gqa(q, k, v, scale: float, block: int,
                       window: Optional[int] = None):
    """Causal attention with grouped heads: q [B, T, H, d], k and v [B, T,
    G, d] (query head h reads key-value head ``h // (H / G)``) -> [B, T, H,
    d]. ``window`` (None: all keys at or before the query): query i reads
    keys ``i - window < j <= i``. Two forms of one function, and the
    platform chooses (``ops/flash_gqa.on_this_platform``):

    * compiled for a TPU, ``ops/flash_gqa.flash_gqa``: Pallas kernels,
      forward and backward, whose score tiles live in VMEM and whose work
      follows the band. The output and the rows' log-sum-exp are both named
      ``ATTN_OUT``, so a layer recomputed from its saved names finds the
      backward kernels' residuals and runs no forward kernel again.
      ``block`` is not read there: the tiles are the kernel's own rule's;
    * anywhere else :func:`_blocked_xla`, ``block`` queries at a time
      (under ``OKTOPK_PALLAS_INTERPRET=1`` the kernels, interpreted:
      tests). A block of queries reads, scores and masks only the keys
      ``[max(0, start - window + 1), end)`` that any of them can see, so a
      windowed layer's work follows the band and not the causal triangle.
    """
    _, t, h, d = q.shape
    g = k.shape[2]
    block = min(block, t)
    if window is not None and window >= t:
        window = None   # every key at or before a query is in its window
    if flash_gqa.on_this_platform(t, h, g, d, window, block):
        return flash_gqa.flash_gqa(q, k, v, scale, window, save_as=ATTN_OUT)

    def attend(start, end):
        first = 0 if window is None else max(0, start - window + 1)
        return partial(_attend_block_gqa, start=start, end=end, scale=scale,
                       first=first, window=window)

    def one_sequence(seq):
        qq, kk, vv = seq
        out = _blocked_xla(attend, (qq.reshape(t, g, h // g, d),), (kk, vv),
                           block)
        return out.reshape(t, h, d)

    return lax.map(one_sequence, (q, k, v))
