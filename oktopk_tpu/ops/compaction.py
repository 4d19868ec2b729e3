"""Stream compaction: pack masked elements into fixed-capacity buffers.

This is the selection hot path of every sparse collective (SURVEY.md §7.3.5).
The portable implementation (ops/select.py ``select_mask``) builds a full-
length cumsum and a full-length scatter — on TPU the n-operand scatter
serialises (~69 ms for n=14.7M on v5e, measured) and dominated the train
step in rounds 1-2. TPU has no scatter unit, so the fast path splits the
work by what the hardware is good at:

1. A Pallas *staging* kernel does the n-scale work: per 1024-element block
   (one [8, 128] f32 tile), threshold-mask -> in-block exclusive prefix sum
   (Hillis-Steele shifted adds on the VPU) -> one [16,128] x [capb,128]^T
   MXU matmul per sublane row, a single bf16 pass, that drops each
   survivor's in-block offset into its packed slot as its two digits (lane
   0-127 and row 0-7: bf16 holds integers up to 256, not an offset up to
   1023), put together once a block (``_stage_tile``). Each
   block writes its own staging row — standard blocked VMEM outputs, no
   cross-block sequencing, so the grid pipelines freely.
2. Plain-XLA post-processing does the cap-scale work with *gathers* (the
   measured costs on v5e: gather 7-23 ns/elem/round, by how close
   together it reads (below), cap-operand scatter ~4.7 ns/elem, n-operand
   scatter ~4700 ns/1000 elem): the per-output-slot
   staging address and element base both *telescope* along the output axis
   (crossing a block's end advances them by fixed per-block jumps), so one
   small scatter-add of the jumps + a cap-scale cumsum replaces any
   searchsorted/base-gather, leaving exactly 2 cap-scale gather rounds
   (the staged offset, then the value) — see ``_materialize``. Both rounds
   run over the *live prefix* of the output only: slots at or past a
   region's count are value 0 / index n by contract, so a loop whose trip
   count follows the traced counts gathers ``CHUNK`` slots a trip and the
   rest stay constants (``_gather_live``). A materialise costs two rounds x
   live slots, not x capacity: at n = 66 M, capacity 2.6-3.3 M and 37-56 %
   live (PERF.md, PR 29) the staged offset is 7 ns a slot (neighbouring
   slots read neighbouring addresses) and the value 20-23 ns (one element
   in ~45 of a 264 MB vector).

Why not DMA-append inside the kernel (the round-3 first attempt): Mosaic
cannot slice a tiled VMEM scratch per row, and 1-D memrefs — HBM included —
carry a (1024) tiling whose dynamic-offset slices need a divisibility
proof that a running element count cannot give. Block-granular staging
sidesteps every such constraint: all kernel outputs are statically blocked.

Exactness: the staging width ``capb`` (128) caps how many survivors one
block can stage. The mean is ~20 survivors/block at the paper's densities,
but conv gradients are spatially correlated: on a real VGG-16 gradient at
d=0.02, 4.3% of blocks overflow (max 826/1024) — every step. The kernel
therefore also emits *raw* per-block survivor counts, and the wrapper
dispatches (``lax.switch``) on the overflow census:

  * no overflow that matters  -> fast rows alone (the common small-n case);
  * <= ``_novf_cap`` blocks   -> a *repair* kernel re-stages only the
    overflowing blocks at full 1024 width (their ids scalar-prefetched
    into the input index_map). Its grid is the static list length
    (nblocks/8), but its cost is the live entries and, inside each, the
    128-slot pages its survivors reach: a padded entry does nothing and a
    block with 300 survivors stages 3 pages of 8 (8 one-hot tiles a page,
    PERF.md section 5) — nobody addresses the rest (``_run_repair``);
    ``_materialize_het`` then reads the mixed 128/1024-wide layout with
    the same two gather rounds over the live prefix: the rows' physical
    starts ride in the jumps, so the cumsum is the address in the
    concatenated [fast | repaired] staging itself, and one extra
    telescoping accumulator carries the per-slot source block;
  * more                       -> the capb=1024 kernel over everything
    (can never drop anything), as before.

All paths reproduce the portable result bit-for-bit in interpret mode
(asserted in tests/test_compaction.py); tests/test_tpu_hw.py mirrors them
compiled through Mosaic on the chip (``OKTOPK_TPU_HW=1``), repair branch
included; CHANGES.md carries the date of the last on-chip pass.

The reference's analogous code is the boolean-mask nonzero select
(``compressbythreshold``, VGG/compression.py:122-142) — a cheap op on GPU,
the wrong shape for TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from oktopk_tpu.comm import compat


def _interpret_default() -> bool:
    """OKTOPK_PALLAS_INTERPRET=1 runs the kernels in the Pallas interpreter
    (CPU-mesh tests of the full pallas-path algorithms). On a TPU backend
    that is an error, not a mode: an interpreted kernel there would pass
    for the compiled one in every record."""
    if os.environ.get("OKTOPK_PALLAS_INTERPRET", "0") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "OKTOPK_PALLAS_INTERPRET=1 on a TPU backend: the selection "
            "kernels must compile through Mosaic there; unset it")
    return True


BLK_ROWS = 8          # f32 min tile is (8, 128)
BLK_COLS = 128
BLK = BLK_ROWS * BLK_COLS

# sub-blocks per grid step: staging rows come 8 at a time so every output
# block is a full (8, capb) tile — 2-D (1, capb) blocks fail the (8, 128)
# divisibility rule and 1-D (capb,) blocks fail XLA's T(1024) layout
SB = 8

CAPB_FAST = 128       # staging width of the fast kernel (one lane row)

# output slots of each region that one trip of the materialise's gather
# loop fills (``_gather_live``), so its cost follows the live slots to
# within a chunk. On a v5e at cap 2.6-3.3 M, 4,096 to 16,384 time alike and
# 65,536 / 262,144 take 1 / 3 ms more a call (PERF.md, PR 29)
CHUNK = 1 << 14


def _shift_right(x, d, axis):
    """x shifted ``d`` slots toward higher indices along ``axis``, zero-fill.

    Concat + static slice only (``jnp.pad`` is not guaranteed a Mosaic
    lowering)."""
    zshape = list(x.shape)
    zshape[axis] = d
    sl = [slice(None), slice(None)]
    sl[axis] = slice(0, x.shape[axis] - d)
    return jnp.concatenate([jnp.zeros(zshape, x.dtype), x[tuple(sl)]],
                           axis=axis)


def _block_prefix(m):
    """Exclusive prefix sum of an [8, 128] i32 tile in row-major order,
    via Hillis-Steele shifted adds (no cumsum primitive needed in-kernel).

    Only static positive slices and full reductions — scalar extraction
    like ``r[-1, 0]`` traces to ``dynamic_slice``, which Mosaic's TC
    lowering rejects (caught on the real chip; the interpreter accepts it).
    The across-row scan runs full-width: a narrow ``[8, 1]`` slice of
    column 127 keeps lane offset 127 in its vreg, and ``tpu.concatenate``
    requires operands to agree on the non-concat (lane) offset — another
    hardware-only constraint the interpreter accepts.
    """
    s = m
    for d in (1, 2, 4, 8, 16, 32, 64):           # within-row inclusive scan
        s = s + _shift_right(s, d, axis=1)
    # per-row totals replicated across lanes (offset-0 layout)
    rt = jnp.broadcast_to(s[:, BLK_COLS - 1:BLK_COLS], (BLK_ROWS, BLK_COLS))
    r = rt
    for d in (1, 2, 4):                           # across-row inclusive scan
        r = r + _shift_right(r, d, axis=0)
    return s - m + (r - rt), jnp.sum(m)           # (excl. positions, total)


# rows of the one-hot product's LHS: bfloat16's minimum tile is (16, 128)
# (8 rows time alike on a v5e: PERF.md, PR 34). Rows 0 and 1 carry the two
# digits of the in-block offset; the rest are zeros that ride along unread
STAGE_ROWS = 16


def _onehot_pass(rows, selr, capb):
    """ONE bf16 pass of the MXU "scatter": ``out[m, j] = rows[m, l]`` of the
    lane ``l`` whose packed slot ``selr[0, l]`` is ``j``, 0 where no lane's
    is; ``[M, capb]`` f32. Exact for ``rows`` that bfloat16 holds (integers
    up to 256): each product is such a number times 1.0 and each sum has at
    most one non-zero term, accumulated in f32.

    Mosaic rejects cross-lane reshapes — the obvious ``[8,128] -> [BLK,1]``
    one-hot layout is an "unsupported shape cast" on real hardware (the
    interpreter accepts it, which is why only a chip run catches it). So
    everything stays in tile layout: broadcast the row's slot vector along
    a fresh sublane axis, compare with a sublane iota to get the transposed
    one-hot [capb, 128], and contract both operands on their lane axis (an
    NT matmul — dimension numbers ((1,),(1,))). Mosaic never builds the
    one-hot: it pushes a constant 1.0 into the MXU under the compare's mask
    (PERF.md section 5)."""
    # i32 iota/compare: tpu.iota verifies only integer result types (a
    # float iota fails Mosaic verification on the real chip; the
    # interpreter accepts it)
    jio = jax.lax.broadcasted_iota(jnp.int32, (capb, BLK_COLS), 0)
    onehot_t = (jnp.broadcast_to(selr, (capb, BLK_COLS)) == jio) \
        .astype(jnp.float32)                                   # [capb, 128]
    # both operands cast to bf16 by hand, not f32 at Precision.DEFAULT: the
    # chip rounds either to bf16 on its way into the MXU, but the Pallas
    # interpreter multiplies f32 exactly, and would hide a row that bf16
    # cannot hold from every CPU test
    return jax.lax.dot_general(
        rows.astype(jnp.bfloat16), onehot_t.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _stage_tile(sel, capb):
    """stage[j] = in-block offset of the element whose packed slot is
    ``sel[r, l] == j``, as one [1, capb] f32 row; ``sel == capb`` (a dropped
    element) matches no slot and stages nothing.

    The offset ``r * 128 + l`` (0-1023) does not fit bf16's 8 significant
    bits, its two digits do: the lane (0-127) and the row (0-7) go through
    ``_onehot_pass`` as two LHS rows, one pass a sublane row, and the offset
    is put together once a block from the [1, capb] sums. The bound on the
    digits is the block's geometry, whatever ``capb``. Slots are distinct
    across rows so the accumulation is collision-free."""
    mio = jax.lax.broadcasted_iota(jnp.int32, (STAGE_ROWS, BLK_COLS), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (STAGE_ROWS, BLK_COLS), 1)
    acc = jnp.zeros((STAGE_ROWS, capb), jnp.float32)
    for r in range(BLK_ROWS):
        digits = jnp.where(mio == 0, lane, jnp.where(mio == 1, r, 0))
        selr = jax.lax.slice(sel, (r, 0), (r + 1, BLK_COLS))   # [1, 128]
        acc = acc + _onehot_pass(digits.astype(jnp.float32), selr, capb)
    return acc[0:1] + acc[1:2] * BLK_COLS


def _stage_kernel(capb, t_ref, r_ref, x_ref, w_ref, cr_ref):
    """Stage SB consecutive blocks: w_ref[s, j] = in-block offset of the
    j-th survivor of sub-block s, cr_ref = raw survivor counts (broadcast
    over 128 lanes; the stored count is min(raw, capb) by construction —
    survivor ranks are dense — so it is derived in the wrapper, not
    written)."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    xs = x_ref[:]                                         # [SB*8, 128] f32
    woff = (jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS), 0)
            * BLK_COLS
            + jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS), 1))
    rows_w, rows_r = [], []
    for sb in range(SB):
        x = jax.lax.slice(xs, (sb * BLK_ROWS, 0),
                          ((sb + 1) * BLK_ROWS, BLK_COLS))
        gidx = (i * SB + sb) * BLK + woff
        # [lo, hi) element-range restriction (region-restricted select);
        # full range by default
        mask = ((jnp.abs(x) >= t_ref[0])
                & (gidx >= r_ref[0]) & (gidx < r_ref[1]))
        m = mask.astype(jnp.int32)
        pos, raw = _block_prefix(m)

        kept = mask & (pos < capb)
        sel = jnp.where(kept, pos, capb)                  # capb = dropped

        rows_w.append(_stage_tile(sel, capb))
        rows_r.append(jnp.full((1, BLK_COLS), raw, jnp.int32))
    w_ref[:] = jnp.concatenate(rows_w, axis=0)
    cr_ref[:] = jnp.concatenate(rows_r, axis=0)


def _run_stage(xp, t, rng, capb, nblocks, interpret, vma):
    """pallas_call wrapper: (w_stage [nb, capb] f32, stored [nb], raw [nb])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_shapes = [
        compat.shape_dtype_struct((nblocks, capb), jnp.float32, vma=vma),
        compat.shape_dtype_struct((nblocks, BLK_COLS), jnp.int32, vma=vma),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblocks // SB,),
        in_specs=[pl.BlockSpec((SB * BLK_ROWS, BLK_COLS),
                               lambda i, t, r: (i, 0))],
        out_specs=[
            pl.BlockSpec((SB, capb), lambda i, t, r: (i, 0)),
            pl.BlockSpec((SB, BLK_COLS), lambda i, t, r: (i, 0)),
        ],
    )
    w, cr = pl.pallas_call(
        functools.partial(_stage_kernel, capb),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name=f"oktopk_stage_w{capb}",
    )(t, rng, xp)
    raw = cr[:, 0]
    return w, jnp.minimum(raw, capb), raw


def _novf_cap(nblocks: int) -> int:
    """Static capacity of the repair list: an eighth of the blocks (3x the
    measured 4.3% overflow rate on real VGG-16 gradients at d=0.02)."""
    return max((nblocks + 7) // 8, 8)


def _repair_kernel(t_ref, r_ref, bl_ref, nv_ref, x_ref, w_ref):
    """Re-stage ONE overflowing block (id scalar-prefetched via ``bl_ref``)
    at full 1024 width, written as eight 128-wide *pages* (page p holds
    packed slots [128p, 128(p+1))) — a [1, 1024] staging row would need a
    cross-lane reshape Mosaic rejects; pages keep every store a [1, 128]
    lane row. Row-major [8, 128] flatten == the 1024-wide row layout.

    Only what a consumer can address is staged: a grid step at or past the
    live count ``nv_ref[0]`` (a padded list entry) does nothing, and a live
    step stages page p only when the block's own survivor count reaches
    into it — slots at or past a block's count are never read (see
    ``_run_repair``)."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i < nv_ref[0])
    def _():
        b = bl_ref[i]
        x = x_ref[:]                                      # [8, 128]
        woff = (jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS), 0)
                * BLK_COLS
                + jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS),
                                           1))
        gidx = b * BLK + woff
        mask = ((jnp.abs(x) >= t_ref[0])
                & (gidx >= r_ref[0]) & (gidx < r_ref[1]))
        pos, raw = _block_prefix(mask.astype(jnp.int32))
        for p in range(BLK_ROWS):
            @pl.when(raw > p * BLK_COLS)
            def _(p=p):
                kept_p = (mask & (pos >= p * BLK_COLS)
                          & (pos < (p + 1) * BLK_COLS))
                sel_p = jnp.where(kept_p, pos - p * BLK_COLS, BLK_COLS)
                w_ref[p:p + 1, :] = _stage_tile(sel_p, BLK_COLS)


def _run_repair(xp, t, rng, bl, novf, novf_cap, interpret, vma):
    """pallas_call wrapper: w_rep [novf_cap * 8, 128] f32 staging pages for
    the first ``novf`` blocks listed in ``bl``.

    The invariant the kernel's skipping rests on: ``w_rep`` is defined only
    in the slots below each listed block's survivor count. The page rows of
    the padded entries (``i >= novf``; they repeat block 0, so the pipeline
    fetches nothing new for them) and a listed block's slots at or past its
    count are written back unwritten and hold whatever VMEM held.
    ``_materialize_het`` and ``_region_counts`` address a block's row below
    its ``stored_v`` only — that count for a listed block; a padded row is
    no block's — and mask every other slot they gather
    (tests/test_compaction.py::TestRepairSkipInvariant poisons the rest).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nv = jnp.reshape(novf, (1,)).astype(jnp.int32)
    if vma:
        bl, nv = _pvary_to(bl, vma), _pvary_to(nv, vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(novf_cap,),
        in_specs=[pl.BlockSpec((BLK_ROWS, BLK_COLS),
                               lambda i, t, r, bl, nv: (bl[i], 0))],
        out_specs=[pl.BlockSpec((BLK_ROWS, BLK_COLS),
                                lambda i, t, r, bl, nv: (i, 0))],
    )
    (w,) = pl.pallas_call(
        _repair_kernel,
        grid_spec=grid_spec,
        out_shape=[compat.shape_dtype_struct((novf_cap * BLK_ROWS, BLK_COLS),
                                             jnp.float32, vma=vma)],
        interpret=interpret,
        name="oktopk_repair",
    )(t, rng, bl, nv, xp)
    return w


def _slot_bases(jump_rb, c_rb, cap, start):
    """``[R, cap]`` per-output-slot base that *telescopes* along the slot
    axis: ``start[r]`` plus every ``jump_rb[b, r]`` whose block ends at or
    before the slot (block b of region r ends at output position
    ``c_rb[b, r]``, the inclusive survivor count). One nb-operand
    scatter-add of the jumps and a per-row cap-scale cumsum; crossings at
    or past ``cap`` land in a spare slot that is cut off."""
    nblocks, R = c_rb.shape
    pos = jnp.minimum(c_rb, cap)
    rgrid = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[None, :],
                             (nblocks, R))
    jumps = jnp.zeros((R, cap + 1), jnp.int32).at[rgrid.T, pos.T].add(
        jump_rb.T)
    return start[:, None] + jnp.cumsum(jumps, axis=1)[:, :cap]


def _gather_live(stage_flat, xflat, addr, blk, counts, n):
    """The two cap-scale gather rounds of a materialise: output slot
    ``[r, j]`` reads its staged in-block offset at ``stage_flat[addr]``
    (round 1) and its value at ``xflat[blk * BLK + offset]`` (round 2).

    Only the live prefix is gathered. Slots ``j >= counts[r]`` are value 0
    / index ``n`` by contract and nobody reads anything else there, so the
    outputs start as those constants and a loop whose trip count follows
    the traced counts fills ``ceil(max(counts) / CHUNK)`` chunks of the
    slot axis in place; the last chunk is masked by ``live``. A capacity of
    at most one chunk is gathered whole, in straight-line code (a static
    shape test). The last chunk of a capacity that is no multiple of
    ``CHUNK`` starts early and rewrites slots of the chunk before it with
    the same values: a slot's result depends on its position alone."""
    R, cap = addr.shape

    def rows(addr, blk, j):
        w = stage_flat[jnp.clip(addr, 0, stage_flat.size - 1)] \
            .astype(jnp.int32)                            # gather round 1
        idx = blk * BLK + w
        live = j < counts[:, None]
        values = jnp.where(live, xflat[jnp.minimum(idx, xflat.size - 1)],
                           0.0)                           # gather round 2
        return values, jnp.where(live, idx, n).astype(jnp.int32)

    if cap <= CHUNK:
        return rows(addr, blk, jnp.arange(cap, dtype=jnp.int32)[None, :])

    def chunk(c, out):
        start = jnp.minimum(c * CHUNK, cap - CHUNK)
        values, indices = rows(
            jax.lax.dynamic_slice(addr, (0, start), (R, CHUNK)),
            jax.lax.dynamic_slice(blk, (0, start), (R, CHUNK)),
            start + jnp.arange(CHUNK, dtype=jnp.int32)[None, :])
        return (jax.lax.dynamic_update_slice(out[0], values, (0, start)),
                jax.lax.dynamic_update_slice(out[1], indices, (0, start)))

    vma = compat.typeof_vma(addr) | compat.typeof_vma(xflat)
    dead = (_pvary_to(jnp.zeros((R, cap), xflat.dtype), vma),
            _pvary_to(jnp.full((R, cap), n, jnp.int32), vma))
    nchunks = (jnp.max(counts) + CHUNK - 1) // CHUNK
    return jax.lax.fori_loop(0, nchunks, chunk, dead)


def _materialize(w_stage, xflat, cnt_rb, off_rb, capb, cap, counts, n):
    """Materialise ``(values [R, cap], indices [R, cap])`` from a packed
    staging ``w_stage [nb, capb]`` whose block b holds (ascending-index)
    the survivors counted by ``cnt_rb [nb, R]`` per region, region r's run
    starting at in-row offset ``off_rb[b, r]`` (None = zeros, the R=1
    whole-vector select).

    Region r's output slot j reads staging slot
        b*capb + off_rb[b, r] + (j - C_excl[b, r])
    of block b = searchsorted(C[:, r], j), and its element index is
    b*BLK + staged offset. Both per-slot bases *telescope* along j:
    crossing block b (at output position C[b, r]) advances the staging
    base by capb + off_rb[b+1, r] - off_rb[b, r] - cnt_rb[b, r] and the
    element base by BLK, starting from off_rb[0, r] and 0. One small
    scatter-add of those jumps + a per-row cap-scale cumsum therefore
    replaces any searchsorted and per-slot base gather (``_slot_bases``;
    the element base needs no accumulator of its own: a live slot's in-row
    offset is < capb, so its block is ``flat // capb``); only two cap-scale
    gather rounds remain (the staged offset, then the value), over the
    live prefix of the slot axis (``_gather_live``).
    """
    if off_rb is None:
        off_rb = jnp.zeros_like(cnt_rb)
    c_rb = jnp.cumsum(cnt_rb, axis=0)                 # [nb, R] inclusive
    off_next = jnp.concatenate([off_rb[1:], off_rb[-1:]], axis=0)
    fval = capb + off_next - off_rb - cnt_rb          # [nb, R]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    flat = _slot_bases(fval, c_rb, cap, off_rb[0]) + j
    # live slots always sit inside their block's staging row (in-row offset
    # < capb), so the source block is just flat // capb — a shift, no
    # second jump accumulator needed
    return _gather_live(w_stage.reshape(-1), xflat, flat, flat // capb,
                        counts, n)


def _het_addresses(ovf, cnt_rb, off_rb, capf, cap):
    """Per output slot ``[R, cap]`` of the repair path's mixed layout: its
    address in the concatenated ``[w_fast | w_rep]`` staging and its source
    block. Block b's row starts at ``phys_base[b]``: ``w_rep`` page-row
    ``rank(b)`` (1024 wide) when ``ovf[b]``, else ``w_fast[b]`` (``capf``
    wide).

    The address is ``_materialize``'s telescoping base with the physical
    row starts in the jumps: crossing block b moves it from b's row to the
    start of b+1's, ``phys_base[b+1] - phys_base[b] + off_rb[b+1, r] -
    off_rb[b, r] - cnt_rb[b, r]``, from ``phys_base[0] + off_rb[0, r]``; so
    the cumsum is the physical address itself and nothing is looked up per
    slot. A second accumulator (jump +1 at every crossing) carries the
    source block, which the mixed widths no longer let one divide out.
    Slots at or past a region's count get an address and a block that
    nobody may read (``_gather_live`` clamps what it gathers there and
    masks the result)."""
    nblocks, R = cnt_rb.shape
    capb_b = jnp.where(ovf, BLK, capf)                    # [nb]
    rank = jnp.cumsum(ovf.astype(jnp.int32)) - ovf        # repair row of b
    phys_base = jnp.where(ovf, nblocks * capf + rank * BLK,
                          jnp.arange(nblocks, dtype=jnp.int32) * capf)
    phys_next = jnp.concatenate([phys_base[1:], phys_base[-1:] + capb_b[-1:]])
    c_rb = jnp.cumsum(cnt_rb, axis=0)                     # [nb, R] inclusive
    off_next = jnp.concatenate([off_rb[1:], off_rb[-1:]], axis=0)
    pval = (phys_next - phys_base)[:, None] + off_next - off_rb - cnt_rb
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    phys = _slot_bases(pval, c_rb, cap, phys_base[0] + off_rb[0]) + j
    b = _slot_bases(jnp.ones_like(pval), c_rb, cap,
                    jnp.zeros((R,), jnp.int32))
    return phys, b


def _materialize_het(w_fast, w_rep, ovf, xflat, cnt_rb, off_rb, capf, cap,
                     counts, n):
    """``_materialize`` over the mixed staging layout of the repair path
    (``_het_addresses``): the same two gather rounds over the live prefix,
    from the concatenated [w_fast | w_rep] array."""
    if off_rb is None:
        off_rb = jnp.zeros_like(cnt_rb)
    phys, b = _het_addresses(ovf, cnt_rb, off_rb, capf, cap)
    stage_all = jnp.concatenate([w_fast.reshape(-1), w_rep.reshape(-1)])
    return _gather_live(stage_all, xflat, phys, b, counts, n)


def _region_counts(stage_flat, phys_base, stored_v, capb_max, bnd, R,
                   nblocks):
    """Per-(block, region) staged-survivor counts [nb, R] for contiguous
    index-range regions, at nb scale: a block's region follows from its
    start index; only the <= R-1 boundary-straddling blocks read their
    staging rows (fetched from ``stage_flat`` at ``phys_base`` — uniform
    and heterogeneous layouts both reduce to a base array)."""
    rgrid = jnp.arange(R, dtype=jnp.int32)
    bi = jnp.arange(nblocks, dtype=jnp.int32)
    rblock = jnp.searchsorted(bnd[1:-1], bi * BLK,
                              side="right").astype(jnp.int32)
    cnt_rb = jnp.where(rblock[:, None] == rgrid[None, :],
                       stored_v[:, None], 0)
    if R > 1:
        # clamp: a boundary equal to n with zero padding puts bm one past
        # the last block; the clamped block's replacement row is recomputed
        # from its own staging, so the overwrite stays exact
        bm = jnp.minimum((bnd[1:-1] // BLK).astype(jnp.int32), nblocks - 1)
        rowidx = phys_base[bm][:, None] + jnp.arange(capb_max,
                                                     dtype=jnp.int32)[None, :]
        wb = stage_flat[jnp.clip(rowidx, 0, stage_flat.size - 1)] \
            .astype(jnp.int32)                            # [R-1, capb_max]
        rid_b = jnp.searchsorted(bnd[1:-1], bm[:, None] * BLK + wb,
                                 side="right").astype(jnp.int32)
        valid_b = (jnp.arange(capb_max, dtype=jnp.int32)[None, :]
                   < stored_v[bm][:, None])
        rowg = jnp.broadcast_to(
            jnp.arange(R - 1, dtype=jnp.int32)[:, None], rid_b.shape)
        cnt_rows = jnp.zeros((R - 1, R), jnp.int32).at[
            rowg, rid_b].add(valid_b.astype(jnp.int32))
        cnt_rb = cnt_rb.at[bm].set(cnt_rows)
    return cnt_rb


def _prep(x, thresh, lo, hi):
    """Shared padding/threshold/range prep. Returns (xp2d, xflat, t, rng,
    n, nblocks)."""
    n = x.size
    pad = (-n) % (SB * BLK)
    xflat = jnp.pad(x.reshape(-1), (0, pad))
    xp = xflat.reshape(-1, BLK_COLS)
    nblocks = xp.shape[0] // BLK_ROWS
    # clamp to the smallest normal f32: a zero/negative threshold selects
    # every nonzero element rather than the padded tail (subnormals flush
    # to zero on TPU anyway)
    t = jnp.reshape(jnp.maximum(jnp.asarray(thresh, x.dtype),
                                jnp.float32(1.17549435e-38)), (1,))
    rng = jnp.stack([
        jnp.asarray(0 if lo is None else lo, jnp.int32),
        jnp.asarray(n if hi is None else hi, jnp.int32)])
    return xp, xflat, t, rng, n, nblocks


def _vma_of(xp):
    # under shard_map's VMA tracking the outputs vary over the same mesh
    # axes as the input shard, and every operand must agree
    return compat.typeof_vma(xp)


def _pvary_to(arr, vma):
    missing = tuple(vma - compat.typeof_vma(arr))
    return compat.pvary(arr, missing)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def select_by_threshold_pallas(x: jnp.ndarray, thresh, cap: int,
                               lo=None, hi=None,
                               interpret: bool | None = None):
    """Fixed-capacity threshold select, Pallas TPU fast path.

    Same contract as ops.select.select_by_threshold, and what the kernels
    did: returns ``(values[cap], indices[cap], count, branch)`` with slots
    >= count holding
    value 0 / index n, elements packed in ascending index order, overflow
    beyond ``cap`` dropped with lowest-index-first retention (identical to
    the portable path). ``lo``/``hi`` restrict selection to the element
    range [lo, hi). ``branch`` is i32[2]: which of fast / repair / wide
    ran (0 / 1 / 2) and the count of overflowing blocks that decided it.
    """
    if interpret is None:
        interpret = _interpret_default()
    xp, xflat, t, rng, n, nblocks = _prep(x, thresh, lo, hi)
    vma = _vma_of(xp)
    if vma:
        t = _pvary_to(t, vma)
        rng = _pvary_to(rng, vma)

    capb_f = CAPB_FAST
    w_f, stored_f, raw = _run_stage(xp, t, rng, capb_f, nblocks, interpret,
                                    vma)
    count = jnp.minimum(jnp.sum(raw), cap)

    def _post(w_stage, stored, capb):
        values, indices = _materialize(
            w_stage, xflat, stored[:, None], None, capb, cap,
            count[None], n)
        return values[0], indices[0]

    if cap > capb_f:
        # A block's drops have in-block position >= capb, hence global
        # survivor rank >= excl_cumsum(raw)[b] + capb. When every drop
        # ranks >= cap, no output slot can see one (a survivor with true
        # rank < cap has no drop before it either, so the stored ordering
        # of the first cap slots is exact) — such blocks need no re-stage.
        excl = jnp.cumsum(raw) - raw
        matters = (raw > capb_f) & (excl + capb_f < cap)
        novf = jnp.sum(matters)
        ncap = _novf_cap(nblocks)
        bl = jnp.nonzero(matters, size=ncap,
                         fill_value=0)[0].astype(jnp.int32)

        def fast(_):
            return _post(w_f, stored_f, capb_f)

        def repair(_):
            w_rep = _run_repair(xp, t, rng, bl, novf, ncap, interpret, vma)
            stored_v = jnp.where(matters, raw, stored_f)
            values, indices = _materialize_het(
                w_f, w_rep, matters, xflat, stored_v[:, None], None,
                capb_f, cap, count[None], n)
            return values[0], indices[0]

        def wide(_):
            w_w, stored_w, _raw = _run_stage(xp, t, rng, BLK, nblocks,
                                             interpret, vma)
            return _post(w_w, stored_w, BLK)

        sel = ((novf > 0).astype(jnp.int32)
               + (novf > ncap).astype(jnp.int32))
        values, indices = jax.lax.switch(sel, [fast, repair, wide], None)
        branch = jnp.stack([sel, novf.astype(jnp.int32)])
    else:
        # drops beyond capb have in-block position >= capb >= cap, hence
        # global position >= cap: they can never make the first-cap prefix
        values, indices = _post(w_f, stored_f, capb_f)
        branch = jnp.zeros((2,), jnp.int32)
    return values, indices, count, branch


def pack_by_region_pallas(x: jnp.ndarray, thresh, boundaries,
                          num_regions: int, cap: int,
                          interpret: bool | None = None):
    """Pack ``|x| >= thresh`` into per-region fixed-capacity buffers in ONE
    pass over ``x`` (the Pallas fast path of ops.select.pack_by_region).

    ``boundaries``: i32 [num_regions + 1] cumulative offsets that MUST span
    exactly [0, n]: ``boundaries[0] == 0`` and ``boundaries[-1] == n``.
    The kernel is region-blind (it stages every survivor over [0, n); the
    post-processing assigns region ids from the interior boundaries only),
    so a survivor outside ``[boundaries[0], boundaries[-1])`` would be
    silently attributed to the first/last region rather than masked out.
    ``_repartition`` maintains the invariant by construction (the
    reference asserts the same: sum of region sizes == n,
    VGG/allreducer.py:648); callers with concrete boundaries get a cheap
    host-side check. Returns ``(values [R, cap], indices [R, cap],
    counts [R], branch)``: the portable path's contract, and ``branch`` as
    ``select_by_threshold_pallas`` gives it. The
    ascending-index staging is already region-grouped (regions are
    contiguous index ranges); all region arithmetic happens in the
    cap-scale post-processing.
    """
    # The invariant check must run BEFORE jit: inside the trace every
    # array is a tracer (isinstance(np.ndarray) is False and np.asarray
    # raises), so a guard in the jitted body can never fire. Concrete
    # boundaries (numpy / committed jax arrays / int sequences) convert;
    # tracers (e.g. the jitted oktopk caller, whose _repartition keeps
    # the invariant by construction) raise and skip the check.
    try:
        b = np.asarray(boundaries)
        concrete = b.dtype != object
    except Exception:
        concrete = False
    if concrete and (b[0] != 0 or b[-1] != x.size):
        raise ValueError(
            f"boundaries must span exactly [0, n={x.size}]; got "
            f"[{b[0]}, {b[-1]}] (the kernel is region-blind — see "
            "docstring)")
    return _pack_by_region_pallas(x, thresh, boundaries, num_regions, cap,
                                  interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_regions", "cap", "interpret"))
def _pack_by_region_pallas(x, thresh, boundaries, num_regions: int,
                           cap: int, interpret: bool | None = None):
    if interpret is None:
        interpret = _interpret_default()
    R = num_regions
    xp, xflat, t, rng, n, nblocks = _prep(x, thresh, None, None)
    vma = _vma_of(xp)
    bnd = jnp.asarray(boundaries, jnp.int32)
    if vma:
        t = _pvary_to(t, vma)
        rng = _pvary_to(rng, vma)

    w_f, stored_f, raw = _run_stage(xp, t, rng, CAPB_FAST, nblocks,
                                    interpret, vma)
    return _pack_finalize(xp, xflat, t, rng, bnd, R, cap, nblocks, n,
                          interpret, vma, w_f, stored_f, raw)


def _pack_finalize(xp, xflat, t, rng, bnd, R, cap, nblocks, n, interpret,
                   vma, w_f, stored_f, raw):
    """Cap-scale region post-processing shared by ``pack_by_region_pallas``
    and the fused selection front-end (ops/fused_select.py): overflow
    census -> fast/repair/wide dispatch over already-staged fast rows.
    Returns ``(values, indices, counts, branch)``; ``branch`` is i32[2], the
    branch taken and the census that chose it."""
    # Region reconstruction requires every survivor staged (fast rows when
    # nothing overflowed, repaired rows for the <= ncap overflow blocks,
    # or the capb=BLK kernel otherwise). _region_counts is nb-scale — the
    # round-4 version ran searchsorted + a scatter-add over the whole
    # [nb, capb] grid, which on the capb=BLK wide path is n-scale:
    # measured 150+ ms of the VGG-16 step on the chip (the very scatter
    # cost this module exists to avoid).
    def _finish(cnt_rb, mat):
        off_rb = jnp.cumsum(cnt_rb, axis=1) - cnt_rb    # region start in row
        counts = jnp.minimum(jnp.sum(cnt_rb, axis=0), cap)  # [R]
        values, indices = mat(cnt_rb, off_rb, counts)
        return values, indices, counts

    bi = jnp.arange(nblocks, dtype=jnp.int32)
    ovf = raw > CAPB_FAST
    novf = jnp.sum(ovf)
    ncap = _novf_cap(nblocks)
    bl = jnp.nonzero(ovf, size=ncap, fill_value=0)[0].astype(jnp.int32)

    def fast(_):
        cnt_rb = _region_counts(w_f.reshape(-1), bi * CAPB_FAST, stored_f,
                                CAPB_FAST, bnd, R, nblocks)
        return _finish(cnt_rb, lambda c, o, ct: _materialize(
            w_f, xflat, c, o, CAPB_FAST, cap, ct, n))

    def repair(_):
        w_rep = _run_repair(xp, t, rng, bl, novf, ncap, interpret, vma)
        stored_v = jnp.where(ovf, raw, stored_f)
        rank = jnp.cumsum(ovf.astype(jnp.int32)) - ovf
        phys_base = jnp.where(ovf, nblocks * CAPB_FAST + rank * BLK,
                              bi * CAPB_FAST)
        stage_all = jnp.concatenate([w_f.reshape(-1), w_rep.reshape(-1)])
        cnt_rb = _region_counts(stage_all, phys_base, stored_v, BLK, bnd,
                                R, nblocks)
        return _finish(cnt_rb, lambda c, o, ct: _materialize_het(
            w_f, w_rep, ovf, xflat, c, o, CAPB_FAST, cap, ct, n))

    def wide(_):
        w_w, stored_w, _raw = _run_stage(xp, t, rng, BLK, nblocks,
                                         interpret, vma)
        cnt_rb = _region_counts(w_w.reshape(-1), bi * BLK, stored_w, BLK,
                                bnd, R, nblocks)
        return _finish(cnt_rb, lambda c, o, ct: _materialize(
            w_w, xflat, c, o, BLK, cap, ct, n))

    sel = (novf > 0).astype(jnp.int32) + (novf > ncap).astype(jnp.int32)
    values, indices, counts = jax.lax.switch(sel, [fast, repair, wide], None)
    return values, indices, counts, jnp.stack([sel, novf.astype(jnp.int32)])


def mesh_supports_pallas(mesh) -> bool:
    """True when every device of the mesh is a TPU — the backend the
    selection kernels compile for."""
    return {d.platform for d in np.asarray(mesh.devices).flat} == {"tpu"}


def resolve_use_pallas(cfg, mesh):
    """Fill OkTopkConfig.use_pallas from the mesh backend when unset."""
    if cfg.use_pallas is not None:
        return cfg
    return cfg.replace(use_pallas=mesh_supports_pallas(mesh))
