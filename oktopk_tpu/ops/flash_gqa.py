"""Causal or banded softmax attention as Pallas kernels whose scores never
leave VMEM, forward or backward: grouped heads (:func:`flash_gqa`) and
MLA's split heads (:func:`flash_mla`), one set of kernels.

Grouped heads: q [B, T, H, d], k and v [B, T, G, d] (query head h reads
key-value head ``h // R``, ``R = H / G``), ``window`` None (query i reads
the keys ``j <= i``) or an integer (``i - window < j <= i``). The plain form
(``models/attention._attend_block_gqa``) writes a float32 score block of
[G, R, queries, keys] to HBM, masks, exponentiates, sums, normalises and
reads it again for the weighted sum, twice before its backward pass; at
T = 16,384 that traffic was 64 % of a step (PERF.md, Findings, PR 43).

Split heads (DeepSeek-V2's multi-head latent attention,
``models/attention.blocked_causal_attention``): a head's score is TWO
products summed in float32 before the scale, ``q_nope k_nope^T`` over the
head's own d-wide key and ``q_pe k_pe^T`` over a ``rope``-wide rotary key
that is ONE head shared by all; values are ``dv`` wide; no two query heads
share a key-value head. Nothing is packed or padded for it: the five
arrays are read as they lie, the score width and the value width are told
apart (:class:`_Plan`), and the rotary part is a second score term. A grid
step takes R heads WITH their R key-value column blocks (the pack of the
next paragraph, :func:`heads_a_step`): a step's mask and its [tk, rope]
tile of the shared key are built and fetched once for the R of them, and
the rotary slab [tq, R x rope] is whole lane rows. The rotary key's
gradient is summed over a step's heads in the kernel and over the steps'
packs after it. At T = 4,096 the plain form's score blocks were 45 % of a
step (PERF.md, Findings, PR 45).

**What rides a grid step** is a PACK of ``own`` key-value heads side by
side and the ``own x R`` query heads that read them, query head h of the
pack reading columns ``h // R`` of the pack's tile. ``own`` comes from the
call's shapes (:func:`kv_heads_a_step`): 1 where a key-value head's own
group fills a step (R = 6, 7, 8: the group's one tile is loaded once and
its R heads share it), several where a group is small, because a head that
shares its key tile with no other still shares a step's own cost, its mask
and the steps past a tile's run; at a group of ONE head and one head a
step those were over a quarter of the kernels' time (PERF.md, Findings,
PR 47). MLA is the pack ``own = R`` with a rotary part.

Here a tile of scores lives in VMEM from its product to its use:

* **No copy of q, k, v or the output is made.** The arrays are read as
  they lie, [B, T, heads x d]: a grid step takes the [tq, own x R x d]
  slab of a pack's query heads and one [tk, own x d] tile of its keys and
  of its values, and walks the heads in the kernel. A key-value tile is
  fetched once a group and query tile, not once a head.
* **Work follows the band.** Query tile i visits the key tiles
  ``first .. last`` of :func:`kv_tiles` and no other is fetched: the grid's
  innermost extent is the longest such run, a step past a tile's run
  re-addresses the tile it holds (no DMA) and computes nothing. Only the
  tiles that the diagonal or the band's trailing edge crosses build a mask
  (:func:`_interior`).
* **Forward** (``oktopk_flash_gqa_fwd``): two sweeps over a query tile's
  key tiles. The first takes the rows' running max and sum (float32
  scratch); the second accumulates ``exp(x - lse) v`` in the float32 output
  block itself and writes the rows' log-sum-exp beside it. One sweep with
  an online rescale is a product cheaper and rounds another number (below).
* **Backward**, two kernels that recompute a tile's probabilities from the
  log-sum-exp (no second softmax pass): ``oktopk_flash_gqa_dq`` walks like
  the forward one and accumulates dq over a query tile's key tiles;
  ``oktopk_flash_gqa_dkv`` holds a key tile, walks the query tiles that see
  it (:func:`q_tiles`) with the scores TRANSPOSED ([keys, queries]: the
  row statistics lie along lanes and every product is a plain one) and
  sums the R heads of a group into its key-value head's dk and dv (in a
  pack: that head's columns). One fused kernel would
  need dq (58 MB a group at T = 16,384) resident or written once a key
  tile; two kernels cost two products more.
* **Precision**: what the plain form's ``einsum`` is on this chip at JAX's
  default precision, said here because Mosaic's own default for float32
  operands is the six-pass product: operands rounded to bfloat16 AT each
  product, ``preferred_element_type`` float32; scale, mask, max, exp and
  sum in float32. And rounded WHERE the plain form and its transposes
  round: the normalised probabilities for ``p v`` and for dv, the score
  gradient times ``scale`` for dq and dk. A rounding of the same size put
  elsewhere (``exp(x - running max)`` divided after the product, ``scale``
  applied after it) doubled what the benchmark's gradient check reads, past
  its limit: a value that differs by a relative 1e-5 before such a rounding
  differs by ~sqrt(1e-5 x 2^-8) after it (PERF.md, Findings, PR 43). One
  place is left: the rows' sum of ``p dp`` is taken as ``out . dout``
  (``dout`` rounded as the product that makes ``dp`` rounds it), which has
  ``p`` rounded inside ``out`` where the plain form's sum has it whole.

A tile size is :func:`tile_rule`'s, from the shapes and the VMEM a step
needs, never from a model's name. T is padded to a whole tile; the causal
mask hides the padding (a padded key is after every real query, a padded
query's cotangent is zero).

Off a TPU backend the kernels run only interpreted (tests); see
``models/attention.py``'s ``blocked_causal_gqa`` and
``blocked_causal_attention`` for who chooses.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

LANES = 128
# keys a tile, and the most queries (:func:`tile_rule`)
TILE = 512
# what a masked score reads: finite, so that a row whose first visited tile
# holds none of its keys gives exp(0) and no NaN; the first tile that holds
# one wipes that with exp(MASK - max) = 0 exactly (every row sees its own
# key, in the last tile it visits)
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
# a + b^T over the last dims, and a . b
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
# of a v5e's 128 MiB of VMEM: what a kernel may be given, and what the tile
# rule plans for (the compiler's own temporaries come on top)
VMEM_LIMIT = 100 * 2 ** 20
VMEM_PLAN = 48 * 2 ** 20


# ---- which tiles ----------------------------------------------------------

def _floor0(x):
    """max(x, 0) of a Python or a traced integer."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _least(x, top: int):
    return min(x, top) if isinstance(x, int) else jnp.minimum(x, top)


def kv_tiles(i, tq: int, tk: int, window: Optional[int]):
    """(first, last) key tile that query tile ``i`` reads: the keys
    ``[max(0, start - window + 1), end)`` of its queries ``[start, end)``."""
    start = i * tq
    first = 0 if window is None else _floor0(start - window + 1) // tk
    return first, (start + tq - 1) // tk


def q_tiles(j, tq: int, tk: int, window: Optional[int], nq: int):
    """(first, last) query tile that reads key tile ``j``: the queries
    ``[start, end - 1 + window)`` of its keys ``[start, end)``, to the
    sequence's end without a window."""
    start = j * tk
    last = (nq - 1 if window is None
            else _least((start + tk - 1 + window - 1) // tq, nq - 1))
    return start // tq, last


def _interior(i, j, tq: int, tk: int, window: Optional[int]):
    """Whether every pair of query tile ``i`` and key tile ``j`` is seen:
    the tile's last key is at or before its first query, and its first key
    inside the last query's window."""
    inside = j * tk + tk - 1 <= i * tq
    if window is not None:
        inside &= i * tq + tq - 1 - j * tk < window
    return inside


def tile_counts(t: int, tq: int, tk: int, window: Optional[int]):
    """(key tiles a sequence and head group visits, key tiles the causal
    triangle holds), over the query tiles of ``t`` tokens."""
    nq = -(-t // tq)
    runs = [kv_tiles(i, tq, tk, window) for i in range(nq)]
    return (sum(last - first + 1 for first, last in runs),
            sum(last + 1 for _, last in runs))


# every distinct attention call traced in this process, for
# ``utils/profiling.snapshot``: what ran it and what it visits (static)
_calls = {}


def calls():
    """``[{"kernel", "window", "tiles_visited", "tiles_causal",
    "kv_heads_a_step"}, ...]``, an entry a distinct call shape, in the
    order first traced."""
    return [dict(c) for c in _calls.values()]


def _kernels_here(*lanes: int) -> bool:
    """Whether the kernels run here: where the program is compiled for a
    TPU and each of ``lanes`` (the widths that a block cuts the last axis
    by) is whole lane rows, and, interpreted, where
    ``OKTOPK_PALLAS_INTERPRET=1`` asks (tests; on a TPU backend that
    raises, as in ``ops/compaction``)."""
    from oktopk_tpu.ops.compaction import _interpret_default
    return _interpret_default() or (
        jax.default_backend() == "tpu"
        and all(n % LANES == 0 for n in lanes))


def _record(kernel: bool, own: int, t: int, window: Optional[int], tq: int,
            tk: int, *shape) -> bool:
    """``own``: the key-value heads that ride a grid step of the kernels;
    the plain form has no grid and says 0."""
    visited, causal = tile_counts(t, tq, tk, window)
    _calls.setdefault((kernel, t, *shape, window, tq, tk), {
        "kernel": kernel, "window": window, "tiles_visited": visited,
        "tiles_causal": causal, "kv_heads_a_step": own if kernel else 0})
    return kernel


def on_this_platform(t: int, h: int, g: int, d: int, window: Optional[int],
                     block: int) -> bool:
    """Whether the kernels run a call of ``h`` query heads grouped over
    ``g`` key-value heads here (:func:`_kernels_here`: a PACK is whole lane
    rows, a tile of k being [tk, own x d] of [T, G x d], which Mosaic cuts
    by 128 lanes; a head of 64 rides two a step, a 64-lane cut of the tile
    as MLA's rotary part is), and the call's record. Otherwise the caller's
    plain form does, ``block``-wide blocks and key tiles in the record."""
    own = kv_heads_a_step(h, g, d)
    kernel = _kernels_here(own * d)
    tq, tk = (tile_rule(t, own * (h // g), d, own=own) if kernel
              else (block, block))
    return _record(kernel, own, t, window, tq, tk, h, g, d)


def split_on_this_platform(t: int, heads: int, d: int, rope: int, dv: int,
                           block: int) -> bool:
    """:func:`on_this_platform` for a call with split heads
    (:func:`flash_mla`): the no-position part and the value of a head are
    whole lane rows, and so is the rotary slab of the heads that ride a
    step (or it is all heads')."""
    r = heads_a_step(heads, rope)
    kernel = _kernels_here(d, dv, 0 if r == heads else r * rope)
    tq, tk = (tile_rule(t, r, d, dv, rope, own=r) if kernel
              else (block, block))
    return _record(kernel, r, t, None, tq, tk, heads, d, rope, dv)


# the most heads that ride one grid step (PERF.md, Findings, PR 45), and the
# float32 query slab [tq, heads x d] at which a step fell off a cliff in
# PR 43 ([1,024, 2,048]) and in PR 45 ([512, 16 x 128] beside MLA's rotary
# slab): a pack stays under it (alone such a slab ran, PR 47: left as it is)
HEADS_A_STEP = 8
SLAB_CLIFF = 4 * 2 ** 20


def heads_a_step(heads: int, rope: int) -> int:
    """Split heads have a key-value head each, so nothing ties them to a
    grid step but what a step shares: the one rotary key tile, the mask
    and the step's own cost. As many as divide ``heads``, keep the rotary
    slab [tq, r x rope] whole lane rows and are not over ``HEADS_A_STEP``;
    all of them where no such number is."""
    fit = [r for r in range(1, min(heads, HEADS_A_STEP) + 1)
           if heads % r == 0 and (r * rope) % LANES == 0]
    return max(fit, default=heads)


def kv_heads_a_step(h: int, g: int, d: int) -> int:
    """Grouped heads: the key-value heads that ride one grid step, each
    with its R = h / g query heads. One where its own group fills a step;
    where groups are small, as many as divide ``g``, put no more than
    ``HEADS_A_STEP`` query heads on the step, keep their slab at ``TILE``
    queries under ``SLAB_CLIFF`` and their tile [tk, own x d] whole lane
    rows. From the call's shapes alone."""
    r = h // g
    fit = [own for own in range(2, HEADS_A_STEP // r + 1)
           if g % own == 0 and (own * d) % LANES == 0
           and TILE * own * r * d * 4 < SLAB_CLIFF]
    return max(fit, default=1)


def tile_rule(t: int, r: int, d: int, dv: int = 0, rope: int = 0,
              own: int = 1) -> Tuple[int, int]:
    """(tq, tk): 512 keys a tile (four lane rows of scores; fewer where the
    sequence is shorter), and as many queries, halved while what a step
    keeps in VMEM passes ``VMEM_PLAN``: the float32 slabs of q, the output
    and their cotangents ([tq, r x d] for the ``r`` query heads of a step,
    two buffers each), the key-value tiles of its ``own`` key-value heads
    and their gradients, the running statistics and a few score tiles.
    On a v5e (512, 512) was the fastest of seven sizes from 256 to
    1,024 in all three of the benchmark's call shapes, forward and
    backward (PERF.md, Findings, PR 43). ``dv``, ``rope``: split heads
    (:class:`_Plan`)."""
    tk = min(TILE, -(-t // LANES) * LANES)
    tq = tk
    dv = dv or d

    def planned(tq):
        slabs = tq * r * (d + rope + 2 * dv) * 4
        tiles = tk * (own * (d + dv) + rope) * 4
        return (2 * slabs + 2 * 2 * tiles
                + 2 * r * tq * LANES * 4 + 4 * tq * tk * 4)

    while tq > LANES and planned(tq) > VMEM_PLAN:
        tq //= 2
    return tq, tk


# ---- kernels ---------------------------------------------------------------

class _Plan(NamedTuple):
    """What a call is, beside its arrays: static, and hashable."""
    scale: float
    window: Optional[int]
    tq: int
    tk: int
    g: int                  # grid groups: packs of key-value heads
    r: int                  # query heads a pack: a grid step walks them
    d: int                  # a head's score width (split heads: without
    # the rotary part)
    product: str            # the type a product's operands are rounded to
    interpret: bool
    save_as: Optional[str]
    dv: int = 0             # a head's value width; 0: the score width
    rope: int = 0           # split heads (module docstring): the width of the
    # rotary part whose key is one head shared by all; 0: grouped heads
    own: int = 1            # key-value heads a pack: query head h of a step
    # reads the pack's key-value head h // (r / own)

    @property
    def wv(self) -> int:
        return self.dv or self.d

    def name(self, kernel: str) -> str:
        return f"oktopk_flash_{'mla' if self.rope else 'gqa'}_{kernel}"

    def dot(self, a, b, dims):
        """One pass over operands rounded to ``product``, accumulated in
        float32. Said, because Mosaic's own default for float32 operands
        is the six-pass product."""
        return lax.dot_general(a.astype(self.product),
                               b.astype(self.product), dims,
                               preferred_element_type=jnp.float32)

    def scores(self, h: int, q_ref, k, seen, qr_ref=None, kr=None):
        """Head ``h``'s scaled scores [tq, tk], of its columns of the
        queries' slab against its key tile ``k``, masked where ``seen`` is
        given. ``kr``: the shared rotary key, whose product with the head's
        columns of ``qr_ref`` is summed to the first in float32, before
        the scale."""
        x = self.dot(q_ref[:, self.cols(h, self.d)], k, _NT)
        if kr is not None:
            x = x + self.dot(qr_ref[:, self.cols(h, self.rope)], kr, _NT)
        x = x * self.scale
        return x if seen is None else jnp.where(seen, x, MASK)

    def cols(self, h: int, width: int):
        """Head ``h``'s columns of a slab or a pack ``width`` wide a head."""
        return slice(h * width, (h + 1) * width)

    def kv_cols(self, h: int, width: int):
        """The columns of query head ``h``'s key-value head in the pack's
        tile of keys, values or their gradients."""
        return self.cols(h * self.own // self.r, width)

    def tile(self, ref, width: int):
        """head -> its [tk, width] tile of keys or values, rounded for a
        product: the group's one tile, loaded once before the heads are
        walked, or its key-value head's columns of the pack's."""
        if self.own == 1:
            whole = ref[...].astype(self.product)
            return lambda h: whole
        return lambda h: ref[:, self.kv_cols(h, width)].astype(self.product)

    def rotary_key(self, ref):
        """The one [tk, rope] tile of the shared rotary key, rounded for a
        product and loaded once a step; None without a rotary part."""
        return ref[...].astype(self.product) if self.rope else None

    def split(self, refs):
        """A kernel's refs after its plain inputs as ((q_pe, k_pe), the
        rest): the rotary inputs come last of the inputs. (None, None)
        without a rotary part."""
        return (refs[:2], refs[2:]) if self.rope else ((None, None), refs)

    def seen(self, i, j, keys_first: bool):
        """The mask of tile (i, j): [tq, tk], or [tk, tq]."""
        shape = (self.tk, self.tq) if keys_first else (self.tq, self.tk)
        rows = i * self.tq + lax.broadcasted_iota(
            jnp.int32, shape, 1 if keys_first else 0)
        cols = j * self.tk + lax.broadcasted_iota(
            jnp.int32, shape, 0 if keys_first else 1)
        seen = cols <= rows
        if self.window is not None:
            seen &= rows - cols < self.window
        return seen

    def masked_or_not(self, i, j, run, step):
        """``step(masked)`` once where ``run``: unmasked on a tile all of
        whose pairs are seen."""
        import jax.experimental.pallas as pl
        inside = _interior(i, j, self.tq, self.tk, self.window)
        pl.when(run & inside)(functools.partial(step, False))
        pl.when(run & jnp.logical_not(inside))(functools.partial(step, True))


def _wide(x, width: int):
    """[rows, lanes] statistics, every lane alike, at ``width`` lanes."""
    lanes = x.shape[1]
    return (x[:, :width] if width <= lanes
            else jnp.tile(x, (1, width // lanes)))


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, p: _Plan, steps: int):
    """Two sweeps over a query tile's key tiles, ``steps`` grid steps
    each: the rows' max and sum, then the weighted sum of the NORMALISED
    probabilities ``exp(x - lse)``, which is what the plain form rounds for
    its ``p v`` (rounding ``exp(x - running max)`` and dividing after is
    the same precision and another number: on the chip it doubled the
    benchmark's ``grad1_diff_q1``, PERF.md, Findings, PR 43)."""
    import jax.experimental.pallas as pl
    (qr_ref, kr_ref), (o_ref, lse_ref, m_ref, l_ref) = p.split(rest)
    i, s = pl.program_id(2), pl.program_id(3)
    first, last = kv_tiles(i, p.tq, p.tk, p.window)
    second = s >= steps
    j = first + jnp.where(second, s - steps, s)

    @pl.when(s == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(s == steps)
    def _():                      # between the sweeps: m becomes the lse
        m_ref[...] = m_ref[...] + jnp.log(l_ref[...])

    def statistics(masked):
        k, kr = p.tile(k_ref, p.d), p.rotary_key(kr_ref)
        seen = p.seen(i, j, False) if masked else None
        for h in range(p.r):
            x = p.scores(h, q_ref, k(h), seen, qr_ref, kr)
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, x.max(axis=1, keepdims=True))
            e = jnp.exp(x - _wide(m_next, p.tk))
            l_ref[h] = (jnp.exp(m_prev - m_next) * l_ref[h]
                        + e.sum(axis=1, keepdims=True))
            m_ref[h] = m_next

    def weighted_sum(masked):
        k, v = p.tile(k_ref, p.d), p.tile(v_ref, p.wv)
        kr = p.rotary_key(kr_ref)
        seen = p.seen(i, j, False) if masked else None
        for h in range(p.r):
            x = p.scores(h, q_ref, k(h), seen, qr_ref, kr)
            o_ref[:, p.cols(h, p.wv)] += p.dot(
                jnp.exp(x - _wide(m_ref[h], p.tk)), v(h), _NN)

    run = j <= last
    p.masked_or_not(i, j, run & jnp.logical_not(second), statistics)
    p.masked_or_not(i, j, run & second, weighted_sum)

    @pl.when(s == 2 * steps - 1)
    def _():
        lse_ref[...] = m_ref[...]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               p: _Plan, steps: int):
    import jax.experimental.pallas as pl
    (qr_ref, kr_ref), (dq_ref, *dqr_ref) = p.split(rest)
    i, s = pl.program_id(2), pl.program_id(3)
    first, last = kv_tiles(i, p.tq, p.tk, p.window)
    j = first + s

    @pl.when(s == 0)
    def _():
        for ref in (dq_ref, *dqr_ref):
            ref[...] = jnp.zeros_like(ref)

    def step(masked):
        k, v = p.tile(k_ref, p.d), p.tile(v_ref, p.wv)
        kr = p.rotary_key(kr_ref)
        seen = p.seen(i, j, False) if masked else None
        for h in range(p.r):
            x = p.scores(h, q_ref, k(h), seen, qr_ref, kr)
            e = jnp.exp(x - jnp.expand_dims(lse_ref[h, 0], -1))
            de = p.dot(do_ref[:, p.cols(h, p.wv)], v(h), _NT)
            dx = e * (de - jnp.expand_dims(delta_ref[h, 0], -1)) * p.scale
            if p.rope:  # rounded once, for its two products
                dx = dx.astype(p.product)
                dqr_ref[0][:, p.cols(h, p.rope)] += p.dot(dx, kr, _NN)
            dq_ref[:, p.cols(h, p.d)] += p.dot(dx, k(h), _NN)

    p.masked_or_not(i, j, j <= last, step)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                p: _Plan, steps: int, nq: int):
    import jax.experimental.pallas as pl
    (qr_ref, kr_ref), (dk_ref, dv_ref, *dkr_ref) = p.split(rest)
    j, s = pl.program_id(2), pl.program_id(3)
    first, last = q_tiles(j, p.tq, p.tk, p.window, nq)
    i = first + s

    @pl.when(s == 0)
    def _():
        for ref in (dk_ref, dv_ref, *dkr_ref):
            ref[...] = jnp.zeros_like(ref)

    def step(masked):
        k, v = p.tile(k_ref, p.d), p.tile(v_ref, p.wv)
        kr = p.rotary_key(kr_ref)
        seen = p.seen(i, j, True) if masked else None
        # a group's heads sum into their key-value head: where a step
        # holds one, into values written once; in a pack, into that head's
        # columns. Split heads sum into the one rotary key besides
        packed = p.own > 1
        if p.rope:
            dkr = dkr_ref[0][...]
        if not packed:
            dk, dv = dk_ref[...], dv_ref[...]
        for h in range(p.r):
            q = q_ref[:, p.cols(h, p.d)].astype(p.product)
            do = do_ref[:, p.cols(h, p.wv)].astype(p.product)
            x = p.dot(k(h), q, _NT)                      # [keys, queries]
            if p.rope:
                qr = qr_ref[:, p.cols(h, p.rope)].astype(p.product)
                x = x + p.dot(kr, qr, _NT)
            x = x * p.scale
            if masked:
                x = jnp.where(seen, x, MASK)
            e = jnp.exp(x - lse_ref[h])
            if packed:
                dv_ref[:, p.kv_cols(h, p.wv)] += p.dot(e, do, _NN)
            else:
                dv += p.dot(e, do, _NN)
            dx = e * (p.dot(v(h), do, _NT) - delta_ref[h]) * p.scale
            if p.rope:  # rounded once, for its two products
                dx = dx.astype(p.product)
            if packed:
                dk_ref[:, p.kv_cols(h, p.d)] += p.dot(dx, q, _NN)
            else:
                dk += p.dot(dx, q, _NN)
            if p.rope:
                dkr += p.dot(dx, qr, _NN)
        if p.rope:
            dkr_ref[0][...] = dkr
        if not packed:
            dk_ref[...], dv_ref[...] = dk, dv

    p.masked_or_not(i, j, i <= last, step)


# ---- calls -----------------------------------------------------------------

def _longest(runs) -> int:
    return max(last - first + 1 for first, last in runs)


def _specs(p: _Plan, t: int):
    """The block specs by name, for a grid (sequence, group, tile, step)."""
    import jax.experimental.pallas as pl
    tq, tk, window, nq = p.tq, p.tk, p.window, t // p.tq
    sweep = _longest(kv_tiles(i, tq, tk, window) for i in range(nq))

    def of_q(bb, gg, i, s):        # forward and dq: the query tile is held
        return i

    def kv_of_q(bb, gg, i, s):     # ... and its key tiles are walked
        first, last = kv_tiles(i, tq, tk, window)
        return jnp.minimum(first + s, last)

    def k_twice(bb, gg, i, s):     # the forward pass's two sweeps
        return kv_of_q(bb, gg, i, jnp.where(s >= sweep, s - sweep, s))

    def v_second(bb, gg, i, s):    # ... the second alone reads v
        return kv_of_q(bb, gg, i, jnp.maximum(s - sweep, 0))

    def of_kv(bb, gg, j, s):       # dkv: the key tile is held
        return j

    def q_of_kv(bb, gg, j, s):     # ... and its query tiles are walked
        first, last = q_tiles(j, tq, tk, window, nq)
        return jnp.minimum(first + s, last)

    def slab(tile, width=p.d):     # [tq, r x width] of q, out, dq, dout
        return pl.BlockSpec((None, tq, p.r * width), lambda *a: (
            a[0], tile(*a), a[1]))

    def head(tile, width=p.d):     # [tk, own x width] of k, v, dk, dv: the
        # pack's key-value heads side by side
        return pl.BlockSpec((None, tk, p.own * width),
                            lambda *a: (a[0], tile(*a), a[1]))

    def rows(tile):                # [R, 1, tq] of lse, delta: along lanes
        return pl.BlockSpec((None, p.r, 1, tq), lambda *a: (
            a[0], a[1], 0, tile(*a)))

    def shared(tile):              # [tk, rope] of the one rotary key
        return pl.BlockSpec((None, tk, p.rope), lambda *a: (
            a[0], tile(*a), 0))

    sp = dict(
        sweep=sweep, rows=rows(of_q), rows_walked=rows(q_of_kv),
        q=slab(of_q), o=slab(of_q, p.wv), q_walked=slab(q_of_kv),
        o_walked=slab(q_of_kv, p.wv), kv=head(kv_of_q),
        v=head(kv_of_q, p.wv), k_twice=head(k_twice),
        v_second=head(v_second, p.wv), kv_held=head(of_kv),
        v_held=head(of_kv, p.wv),
        # [R, tq, lanes] of the forward pass's log-sum-exp, one lane row a
        # query as the running statistics lie
        lse_out=pl.BlockSpec((None, p.r, tq, min(LANES, tk)), lambda *a: (
            a[0], a[1], a[2], 0)))
    if p.rope:
        sp.update(
            qr=slab(of_q, p.rope), qr_walked=slab(q_of_kv, p.rope),
            kr=shared(kv_of_q), kr_twice=shared(k_twice),
            kr_held=shared(of_kv),
            # a pack's sum into the shared key: [B, packs, T, rope]
            dkr=pl.BlockSpec((None, None, tk, p.rope), lambda *a: (
                a[0], a[1], a[2], 0)))
    return sp


def _call(kernel, name, p: _Plan, grid, steps, in_specs, out_specs,
          out_shape, scratch, args, sweeps=1):
    """``kernel`` over ``grid`` + (``sweeps`` x ``steps``,): the innermost
    extent is the longest run of tiles that a held tile walks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(kernel, p=p, steps=steps),
        grid=grid + (sweeps * steps,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=p.interpret, name=p.name(name))(*args)


def _forward(p: _Plan, q, k, v, rotary=()):
    """q [B, T, H x d], k and v [B, T, G x d], T whole tiles -> the output
    [B, T, H x d] and the log-sum-exp [B, H, T]. Split heads: k and v
    [B, T, H x d], ``rotary`` (q_pe [B, T, H x rope], k_pe [B, T, rope]),
    the output [B, T, H x dv]."""
    from jax.experimental.pallas import tpu as pltpu
    b, t, _ = q.shape
    sp = _specs(p, t)
    lanes = min(LANES, p.tk)
    heads = p.g * p.r
    out, lse = _call(
        _fwd_kernel, "fwd", p, (b, p.g, t // p.tq), sp["sweep"],
        [sp["q"], sp["k_twice"], sp["v_second"]]
        + ([sp["qr"], sp["kr_twice"]] if rotary else []),
        [sp["o"], sp["lse_out"]],
        [jax.ShapeDtypeStruct((b, t, heads * p.wv), jnp.float32),
         jax.ShapeDtypeStruct((b, heads, t, lanes), jnp.float32)],
        [pltpu.VMEM((p.r, p.tq, lanes), jnp.float32)] * 2,
        (q, k, v) + tuple(rotary), sweeps=2)
    return out, lse[..., 0]


def _backward(p: _Plan, q, k, v, rotary, out, lse, dout):
    """(dq, dk, dv, the rotary pair's gradients or ())."""
    b, t, _ = q.shape
    sp = _specs(p, t)
    nq, nk = t // p.tq, t // p.tk
    # sum_j p_j dp_j a row, as the softmax's own backward pass has it: with
    # dout rounded as the product that makes dp rounds it, out . dout is
    # that sum but for p's own rounding in out
    bits = jnp.finfo(p.product)
    delta = jnp.sum((out * lax.reduce_precision(
        dout, bits.nexp, bits.nmant)).reshape(b, t, p.g * p.r, p.wv), axis=-1)
    delta = jnp.moveaxis(delta, 1, 2)[:, :, None]       # [B, H, 1, T]
    lse = lse[:, :, None]

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, jnp.float32)

    dq = _call(
        _dq_kernel, "dq", p, (b, p.g, nq), sp["sweep"],
        [sp["q"], sp["kv"], sp["v"], sp["o"], sp["rows"], sp["rows"]]
        + ([sp["qr"], sp["kr"]] if rotary else []),
        [sp["q"], sp["qr"]] if rotary else sp["q"],
        [like(q), like(rotary[0])] if rotary else like(q), [],
        (q, k, v, dout, lse, delta) + tuple(rotary))

    dkv = _call(
        functools.partial(_dkv_kernel, nq=nq), "dkv", p, (b, p.g, nk),
        _longest(q_tiles(j, p.tq, p.tk, p.window, nq) for j in range(nk)),
        [sp["q_walked"], sp["kv_held"], sp["v_held"], sp["o_walked"],
         sp["rows_walked"], sp["rows_walked"]]
        + ([sp["qr_walked"], sp["kr_held"]] if rotary else []),
        [sp["kv_held"], sp["v_held"]] + ([sp["dkr"]] if rotary else []),
        [like(k), like(v)] + ([jax.ShapeDtypeStruct(
            (b, p.g, t, p.rope), jnp.float32)] if rotary else []), [],
        (q, k, v, dout, lse, delta) + tuple(rotary))
    if not rotary:
        return dq, *dkv, ()
    # the packs' sums into the one shared key
    return dq[0], dkv[0], dkv[1], (dq[1], jnp.sum(dkv[2], axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(p: _Plan, q, k, v, rotary):
    return _forward(p, q, k, v, rotary)[0]


def _flash_fwd(p: _Plan, q, k, v, rotary):
    out, lse = _forward(p, q, k, v, rotary)
    if p.save_as is not None:
        # both, or a layer recomputed from its saved names runs this
        # kernel again for the one it lacks
        out = checkpoint_name(out, p.save_as)
        lse = checkpoint_name(lse, p.save_as)
    return out, (q, k, v, rotary, out, lse)


def _flash_bwd(p: _Plan, saved, dout):
    return _backward(p, *saved, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _product(interpret: bool):
    """The type a product's operands are rounded to: what the platform's
    own ``einsum`` of float32 operands does at JAX's default precision,
    bfloat16 compiled for the chip, float32 where the interpreter stands in
    for a CPU."""
    return jnp.float32 if interpret else jnp.bfloat16


def _padded(t: int, tq: int, tk: int, arrays):
    """``arrays`` [B, T, ...] as [B, T in whole tiles, columns]."""
    pad = -t % math.lcm(tq, tk)

    def flat(x):
        x = x.reshape(x.shape[0], t, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    return [flat(x) for x in arrays]


def flash_gqa(q, k, v, scale: float, window: Optional[int] = None, *,
              save_as: Optional[str] = None,
              interpret: Optional[bool] = None,
              tiles: Optional[Tuple[int, int]] = None,
              heads: Optional[int] = None):
    """softmax(q k^T scale, causal and inside ``window``) v with grouped
    heads: q [B, T, H, d], k and v [B, T, G, d] -> [B, T, H, d] float32
    (a narrower q, k or v is widened to float32 first). Differentiable in
    q, k and v. ``save_as``: the ``checkpoint_name`` that
    the output and the log-sum-exp carry as residuals, for a caller whose
    layer is recomputed from named values. ``interpret``: left out, the
    kernels are compiled on a TPU backend and interpreted off one (a test
    that compiles for a described chip says False). ``tiles`` (tq, tk) is
    :func:`tile_rule`'s where not given (tests give small ones), and
    ``heads``, the key-value heads that ride a grid step,
    :func:`kv_heads_a_step`'s (tests force one).
    A product's operands are rounded as :func:`_product` says."""
    b, t, h, d = q.shape
    g = k.shape[2]
    # the kernels read and write float32: a caller that computes in a
    # narrower type is widened here (its products round to bfloat16 all the
    # same) and the cast's own transpose narrows the three cotangents
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if window is not None and window >= t:
        window = None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    own = heads or kv_heads_a_step(h, g, d)
    r = own * (h // g)
    tq, tk = tiles or tile_rule(t, r, d, own=own)
    plan = _Plan(float(scale), window, tq, tk, g // own, r, d,
                 jnp.dtype(_product(interpret)).name, interpret, save_as,
                 own=own)
    out = _flash(plan, *_padded(t, tq, tk, (q, k, v)), ())
    return out[:, :t].reshape(b, t, h, d)


def flash_mla(q_nope, q_pe, k_nope, k_pe, v, scale: float, *,
              save_as: Optional[str] = None,
              interpret: Optional[bool] = None,
              tiles: Optional[Tuple[int, int]] = None,
              heads: Optional[int] = None):
    """softmax((q_nope k_nope^T + q_pe k_pe^T) scale, causal) v with split
    heads (module docstring): q_nope and k_nope [B, T, H, d], q_pe [B, T,
    H, rope], k_pe [B, T, rope], v [B, T, H, dv] -> [B, T, H, dv] float32.
    Differentiable in all five; ``k_pe``'s gradient is summed over the
    heads. ``heads``: the heads that ride a grid step
    (:func:`heads_a_step`'s where not given). The rest as
    :func:`flash_gqa`."""
    b, t, h, d = q_nope.shape
    rope, dv = q_pe.shape[-1], v.shape[-1]
    arrays = [x.astype(jnp.float32)
              for x in (q_nope, k_nope, v, q_pe, k_pe)]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r = heads or heads_a_step(h, rope)
    tq, tk = tiles or tile_rule(t, r, d, dv, rope, own=r)
    plan = _Plan(float(scale), None, tq, tk, h // r, r, d,
                 jnp.dtype(_product(interpret)).name, interpret, save_as,
                 dv, rope, own=r)
    q, k, vv, qr, kr = _padded(t, tq, tk, arrays)
    out = _flash(plan, q, k, vv, (qr, kr))
    return out[:, :t].reshape(b, t, h, dv)
