"""Fused selection front-end: residual add + select + stage in ONE sweep.

The steady-state oktopk step front-end used to make ~6 separate n-scale
HBM sweeps over the gradient: ``add_residual`` (read grad + residual, write
acc), ``jnp.abs`` (read acc), the threshold mask + realised count (read),
the Newton probe count (read), and the staging pass of
``ops/compaction.py`` (read). This module's kernel makes ONE: it reads
(grad, residual) block by block, computes ``acc = grad + residual``
in-register, and emits in the same grid step

- the acc block itself (the only n-scale write; every later consumer —
  repartition, the residual update — reads this buffer),
- the compaction staging rows + raw per-block survivor counts of
  ``ops/compaction.py`` (same layout, bit-identical — the cap-scale
  post-processing ``_pack_finalize`` is shared),
- the per-block Newton probe counts (``|acc| >= thresh * probe_ratio``,
  previously a separate sweep in collectives/oktopk.py).

The kernel's time is its one-hot tiles, not its bytes (PERF.md section 5,
the tile model): each block pays 8 [128, 128] tiles for its staging row;
on a v5e at n = 66 M the call takes 39.2 ms, what the plain staging kernel
takes (PERF.md, PR 25).

Steady-state sweeps over n after this module: the fused pass (2 reads +
1 write), the phase-(a) scatter, and the single consumer pass (result
scale + winner mask + residual) — see docs/PERF.md.

The staging mask uses the min-normal-clamped threshold exactly as
``_prep`` does; the probe count deliberately uses the UNCLAMPED probe
threshold so it is bit-identical to the portable
``jnp.sum(abs_acc >= lt * probe_ratio)`` (which has no clamp). Both
counts are range-masked, so the zero padding the kernel adds never shows
up in any output.

All outputs reproduce the portable path bit-for-bit in interpret mode
(tests/test_fused_select.py, same contract as ops/compaction.py);
tests/test_tpu_hw.py mirrors them for real-chip Mosaic compilation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from oktopk_tpu.comm import compat
from oktopk_tpu.ops.compaction import (
    BLK,
    BLK_COLS,
    BLK_ROWS,
    CAPB_FAST,
    SB,
    _block_prefix,
    _interpret_default,
    _pack_finalize,
    _pvary_to,
    _stage_tile,
    _vma_of,
)
from oktopk_tpu.obs.anatomy import SUB_FINALIZE, SUB_SWEEP, phase_scope


def _fused_kernel(capb, t_ref, tp_ref, r_ref, g_ref, res_ref,
                  acc_ref, w_ref, cr_ref, pr_ref):
    """Stage SB consecutive blocks of acc = grad + residual in one sweep.

    Outputs per grid step: the acc tile, the staging rows + raw counts of
    ``_stage_kernel`` (identical layout) and per-block probe counts: 8
    one-hot tiles a block.
    """
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    acc = g_ref[:] + res_ref[:]                           # [SB*8, 128] f32
    acc_ref[:] = acc
    woff = (jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS), 0)
            * BLK_COLS
            + jax.lax.broadcasted_iota(jnp.int32, (BLK_ROWS, BLK_COLS), 1))

    rows_w, rows_r, rows_p = [], [], []
    for sb in range(SB):
        x = jax.lax.slice(acc, (sb * BLK_ROWS, 0),
                          ((sb + 1) * BLK_ROWS, BLK_COLS))
        ax = jnp.abs(x)
        gidx = (i * SB + sb) * BLK + woff
        inr = (gidx >= r_ref[0]) & (gidx < r_ref[1])
        mask = (ax >= t_ref[0]) & inr
        m = mask.astype(jnp.int32)
        pos, raw = _block_prefix(m)

        kept = mask & (pos < capb)
        sel = jnp.where(kept, pos, capb)                  # capb = dropped
        rows_w.append(_stage_tile(sel, capb))
        rows_r.append(jnp.full((1, BLK_COLS), raw, jnp.int32))

        # Newton probe: unclamped threshold (bit-parity with the portable
        # jnp.sum(abs_acc >= lt * probe_ratio)), range-masked so padding
        # never counts even when the probe threshold is 0
        probe = jnp.sum(((ax >= tp_ref[0]) & inr).astype(jnp.int32))
        rows_p.append(jnp.full((1, BLK_COLS), probe, jnp.int32))
    w_ref[:] = jnp.concatenate(rows_w, axis=0)
    cr_ref[:] = jnp.concatenate(rows_r, axis=0)
    pr_ref[:] = jnp.concatenate(rows_p, axis=0)


def _run_fused_stage(gp, rp, t, tp, rng, capb, nblocks, interpret, vma):
    """pallas_call wrapper: (acc_p [nb*8, 128], w_stage [nb, capb],
    stored [nb], raw [nb], probe [nb])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def blocked(cols):
        return pl.BlockSpec((SB, cols), lambda i, t, tp, r: (i, 0))

    tile = pl.BlockSpec((SB * BLK_ROWS, BLK_COLS),
                        lambda i, t, tp, r: (i, 0))
    out_shapes = [
        compat.shape_dtype_struct((nblocks * BLK_ROWS, BLK_COLS),
                                  jnp.float32, vma=vma),
        compat.shape_dtype_struct((nblocks, capb), jnp.float32, vma=vma),
        compat.shape_dtype_struct((nblocks, BLK_COLS), jnp.int32, vma=vma),
        compat.shape_dtype_struct((nblocks, BLK_COLS), jnp.int32, vma=vma),
    ]
    out_specs = [tile, blocked(capb), blocked(BLK_COLS), blocked(BLK_COLS)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nblocks // SB,),
        in_specs=[tile, tile],
        out_specs=out_specs,
    )
    acc_p, w, cr, pr = pl.pallas_call(
        functools.partial(_fused_kernel, capb),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="oktopk_fused_select",
    )(t, tp, rng, gp, rp)
    raw = cr[:, 0]
    return acc_p, w, jnp.minimum(raw, capb), raw, pr[:, 0]


class FusedStage(NamedTuple):
    """Single-sweep front-end outputs plus the staging internals the
    region finalisation (``fused_pack_finalize``) consumes."""
    acc: jnp.ndarray           # [n] f32 — grad + residual
    local_count: jnp.ndarray   # i32 — realised count(|acc| >= thresh)
    probe_count: jnp.ndarray   # i32 — count(|acc| >= probe_thresh)
    # staging internals (padded layout)
    accp: jnp.ndarray          # [nb*8, 128] padded acc tiles
    accflat: jnp.ndarray       # [nb*8*128] padded acc flat
    w_f: jnp.ndarray           # [nb, CAPB_FAST] fast staging rows
    stored_f: jnp.ndarray      # [nb] min(raw, CAPB_FAST)
    raw: jnp.ndarray           # [nb] raw per-block survivor counts
    t: jnp.ndarray             # [1] clamped staging threshold
    rng: jnp.ndarray           # [2] element range [0, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_select_stage(grad: jnp.ndarray, residual: jnp.ndarray, thresh,
                       probe_thresh, interpret: bool | None = None
                       ) -> FusedStage:
    """Run the fused kernel over (grad, residual): one sweep computes acc,
    the fast staging rows and the realised/probe counts.

    The staging threshold is min-normal-clamped exactly as
    ``select_by_threshold_pallas`` (``_prep``); ``probe_thresh`` is used
    unclamped (see module docstring). Region assembly is a separate
    cap-scale step (``fused_pack_finalize``) so the caller can compute
    data-dependent boundaries from ``acc`` in between (the repartition
    cadence of collectives/oktopk.py).
    """
    if interpret is None:
        interpret = _interpret_default()
    if grad.shape != residual.shape:
        raise ValueError(f"grad {grad.shape} != residual {residual.shape}")
    # the anatomy scope lives INSIDE the jitted wrapper so the contract
    # name reaches this program's own op metadata (a caller-side scope
    # stops at the nested pjit call op)
    with phase_scope("select", sub=SUB_SWEEP):
        return _fused_select_stage_impl(grad, residual, thresh,
                                        probe_thresh, interpret)


def _fused_select_stage_impl(grad, residual, thresh, probe_thresh, interpret):
    n = grad.size
    pad = (-n) % (SB * BLK)
    gp = jnp.pad(grad.reshape(-1), (0, pad)).reshape(-1, BLK_COLS)
    rp = jnp.pad(residual.reshape(-1), (0, pad)).reshape(-1, BLK_COLS)
    nblocks = gp.shape[0] // BLK_ROWS
    t = jnp.reshape(jnp.maximum(jnp.asarray(thresh, grad.dtype),
                                jnp.float32(1.17549435e-38)), (1,))
    tp = jnp.reshape(jnp.asarray(probe_thresh, grad.dtype), (1,))
    rng = jnp.stack([jnp.asarray(0, jnp.int32), jnp.asarray(n, jnp.int32)])
    vma = _vma_of(gp)
    if vma:
        t = _pvary_to(t, vma)
        tp = _pvary_to(tp, vma)
        rng = _pvary_to(rng, vma)

    accp, w_f, stored_f, raw, probe_blk = _run_fused_stage(
        gp, rp, t, tp, rng, CAPB_FAST, nblocks, interpret, vma)
    accflat = accp.reshape(-1)
    return FusedStage(
        acc=accflat[:n], local_count=jnp.sum(raw),
        probe_count=jnp.sum(probe_blk),
        accp=accp, accflat=accflat, w_f=w_f, stored_f=stored_f, raw=raw,
        t=t, rng=rng)


@functools.partial(jax.jit,
                   static_argnames=("num_regions", "cap", "interpret"))
def fused_pack_finalize(st: FusedStage, boundaries, num_regions: int,
                        cap: int, interpret: bool | None = None):
    """Per-region (values, indices, counts, branch) from an already-run
    fused stage — the cap-scale half of ``pack_by_region_pallas``, shared
    verbatim (``_pack_finalize``): overflowing blocks are re-staged from
    the kernel's own acc output by the repair/wide kernels, so overflow
    costs extra passes only when it happens, exactly as before. ``branch``
    (i32[2]) says which of fast / repair / wide ran and the overflow census
    that chose it."""
    if interpret is None:
        interpret = _interpret_default()
    n = st.acc.size
    nblocks = st.w_f.shape[0]
    bnd = jnp.asarray(boundaries, jnp.int32)
    vma = _vma_of(st.accp)
    with phase_scope("stage", sub=SUB_FINALIZE):
        return _pack_finalize(st.accp, st.accflat, st.t, st.rng, bnd,
                              num_regions, cap, nblocks, n, interpret, vma,
                              st.w_f, st.stored_f, st.raw)


@functools.partial(jax.jit,
                   static_argnames=("num_regions", "cap", "interpret"))
def fused_select_pallas(grad: jnp.ndarray, residual: jnp.ndarray, thresh,
                        probe_thresh, boundaries, num_regions: int,
                        cap: int, interpret: bool | None = None):
    """One-call form (unit tests / profiling): stage + finalize.

    Returns ``(acc, values [R, cap], indices [R, cap], counts [R],
    local_count, probe_count)`` — bit-identical to
    :func:`fused_select_reference`.
    """
    st = fused_select_stage(grad, residual, thresh, probe_thresh,
                            interpret=interpret)
    values, indices, counts, _branch = fused_pack_finalize(
        st, boundaries, num_regions, cap, interpret=interpret)
    return (st.acc, values, indices, counts, st.local_count,
            st.probe_count)


def fused_select_reference(grad: jnp.ndarray, residual: jnp.ndarray,
                           thresh, probe_thresh, boundaries,
                           num_regions: int, cap: int):
    """Portable semantics twin (the parity oracle, and the CPU profile
    probe): the same outputs from the separate portable sweeps. The
    selection mask uses the min-normal-clamped threshold (as the kernel
    and ``pack_by_region_pallas`` do); the probe count uses the raw one
    (as collectives/oktopk.py always has)."""
    from oktopk_tpu.ops.select import pack_by_region

    acc = grad.reshape(-1) + residual.reshape(-1)
    t = jnp.maximum(jnp.asarray(thresh, acc.dtype),
                    jnp.float32(1.17549435e-38))
    abs_acc = jnp.abs(acc)
    mask = abs_acc >= t
    values, indices, counts = pack_by_region(
        acc, mask, jnp.asarray(boundaries, jnp.int32), num_regions, cap)
    local_count = jnp.sum(mask)
    probe_count = jnp.sum(abs_acc >= jnp.asarray(probe_thresh, acc.dtype))
    return acc, values, indices, counts, local_count, probe_count
