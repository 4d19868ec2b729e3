"""Fixed-capacity sparse selection and packing.

This is the load-bearing design decision of the TPU port (SURVEY.md §7.3.1):
every variable-length (index, value) list in the reference — the
``compressbythreshold`` nonzero selects (VGG/compression.py:122-142), the
``Allgatherv`` packed buffers (VGG/allreducer.py:819,1031) and the per-peer
``Isend`` payloads (VGG/allreducer.py:740-754) — becomes a static-shape
``(values[cap], indices[cap], count)`` triple. Slots past ``count`` carry a
sentinel index equal to the source length, which every scatter drops via
``mode='drop'``. The reference's own threshold feedback keeps realised counts
inside a [2k/3, 5k/4] band (VGG/allreducer.py:696-699), which is what makes a
fixed capacity with modest headroom sound; overflow beyond ``cap`` is dropped
deterministically (lowest-index-first retention) and the dropped mass stays in
the error-feedback residual, so nothing is lost from training.
"""

from __future__ import annotations

import jax.numpy as jnp

# Padding slots use index == len(source); scatters with mode='drop' ignore it.
SENTINEL = "index==n sentinel (see module docstring)"


def count_by_threshold(x: jnp.ndarray, thresh) -> jnp.ndarray:
    """Number of elements with |x| >= thresh (reference uses the realised
    nonzero count to adapt thresholds, VGG/allreducer.py:696-699)."""
    return jnp.sum(jnp.abs(x) >= thresh)


def _branch_if(out, with_branch: bool):
    """``(values, indices, count[, branch])`` as the caller asked for it:
    the kernel wrappers always say which branch ran, the portable paths
    have one (``[0, 0]``)."""
    if not with_branch:
        return out[:3]
    return out if len(out) == 4 else (*out, jnp.zeros((2,), jnp.int32))


def select_by_threshold(x: jnp.ndarray, thresh, cap: int,
                        use_pallas: bool = False,
                        with_branch: bool = False):
    """Pack elements with |x| >= thresh into a fixed-capacity triple.

    Replaces reference ``compressbythreshold`` (VGG/compression.py:122-142),
    which returns a ragged nonzero select.

    Returns ``(values[cap], indices[cap], count)`` where slots >= count hold
    value 0 and index n. Elements are packed in ascending index order; if more
    than ``cap`` elements pass the threshold the tail is dropped (and should
    remain in the caller's residual).

    ``use_pallas`` selects the TPU stream-compaction kernel
    (ops/compaction.py) instead of the portable cumsum+scatter, which
    serialises on TPU. Resolved from the mesh backend by the step builders
    (OkTopkConfig.use_pallas). ``with_branch`` appends the kernel wrapper's
    ``branch`` (i32[2]: overflow branch taken, overflowing blocks).
    """
    if use_pallas and x.dtype == jnp.float32:   # kernel is f32-only
        from oktopk_tpu.ops.compaction import select_by_threshold_pallas
        return _branch_if(select_by_threshold_pallas(x, thresh, cap),
                          with_branch)
    return _branch_if(select_mask(x, jnp.abs(x) >= thresh, cap),
                      with_branch)


def select_mask(x: jnp.ndarray, mask: jnp.ndarray, cap: int):
    """Pack elements where ``mask`` is True into a fixed-capacity triple
    (same layout as :func:`select_by_threshold`)."""
    n = x.size
    pos = jnp.cumsum(mask) - 1
    pos = jnp.where(mask & (pos < cap), pos, cap)
    values = jnp.zeros((cap,), x.dtype).at[pos].set(
        jnp.where(mask, x, 0), mode="drop")
    indices = jnp.full((cap,), n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    count = jnp.minimum(jnp.sum(mask), cap)
    return values, indices, count


def select_nonzero(x: jnp.ndarray, cap: int, use_pallas: bool = False):
    """Pack the nonzeros of ``x`` (the reference's plain nonzero extract of
    its reduced region before Allgatherv, VGG/allreducer.py:1326).

    The portable path must NOT emulate this with a tiny threshold:
    subnormal thresholds flush to zero on TPU/XLA and select everything.
    The Pallas path clamps its threshold to the smallest *normal* f32,
    which selects exactly the nonzeros on TPU (subnormals flush there).
    """
    if use_pallas and x.dtype == jnp.float32:   # kernel is f32-only
        from oktopk_tpu.ops.compaction import select_by_threshold_pallas
        return select_by_threshold_pallas(x, 0.0, cap)[:3]
    return select_mask(x, x != 0.0, cap)


def scatter_sparse(n: int, values: jnp.ndarray, indices: jnp.ndarray,
                   base: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scatter-add (values, indices) triples into a dense length-n vector.

    Replaces the reference's result rebuild after Allgatherv
    (VGG/allreducer.py:1038-1044). Sentinel indices (== n) are dropped.
    ``values``/``indices`` may have any leading batch shape.
    """
    if base is None:
        base = jnp.zeros((n,), values.dtype)
    return base.at[indices.reshape(-1)].add(values.reshape(-1), mode="drop")


def pack_by_region(x: jnp.ndarray, mask: jnp.ndarray,
                   boundaries: jnp.ndarray, num_regions: int, cap: int,
                   thresh=None, use_pallas: bool = False,
                   with_branch: bool = False):
    """Pack masked elements of ``x`` into per-region fixed-capacity buffers.

    This is the TPU form of oktopk phase (a)'s send-side: the reference
    physically splits the gradient by region boundaries
    (``torch.split(new_tensor, boundaries)``, VGG/allreducer.py:667-670) and
    threshold-selects each split into a ragged per-peer payload. XLA needs
    static shapes, so instead we compute each element's region id from the
    boundary offsets and scatter hits into a ``[num_regions, cap]`` buffer,
    ready for one ``all_to_all``.

    Args:
      x: flat vector [n].
      mask: boolean [n], which elements to send.
      boundaries: int32 [num_regions + 1] cumulative offsets,
        boundaries[0] == 0, boundaries[-1] == n (the reference's invariant
        ``sum(boundaries) == tensor_size``, VGG/allreducer.py:648).
      cap: per-region capacity.
      thresh: when given (with ``use_pallas``), the mask is known to be
        ``|x| >= thresh`` and the TPU compaction kernel packs each region
        directly (one range-restricted pass per region) instead of the
        portable full-length cumsum + scatter.

    Returns:
      (values [num_regions, cap], indices [num_regions, cap] with global
      element ids, counts [num_regions] clipped to cap); with
      ``with_branch`` also the kernel wrapper's ``branch``.
    """
    n = x.size
    if use_pallas and thresh is not None and x.dtype == jnp.float32:
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        return _branch_if(pack_by_region_pallas(
            x, thresh, boundaries, num_regions, cap), with_branch)
    ids = jnp.arange(n, dtype=jnp.int32)
    # region id per element; boundaries[1:-1] are the interior cut points.
    rid = jnp.searchsorted(boundaries[1:-1], ids, side="right").astype(jnp.int32)

    csum = jnp.cumsum(mask)                          # inclusive hit count
    starts = boundaries[:-1]
    # hits strictly before each region's start offset
    start_counts = jnp.where(starts > 0, csum[jnp.maximum(starts - 1, 0)], 0)
    pos_in_region = csum - 1 - start_counts[rid]
    pos = jnp.where(mask & (pos_in_region < cap), pos_in_region, cap)

    values = jnp.zeros((num_regions, cap), x.dtype).at[rid, pos].set(
        jnp.where(mask, x, 0), mode="drop")
    indices = jnp.full((num_regions, cap), n, jnp.int32).at[rid, pos].set(
        ids, mode="drop")

    ends = boundaries[1:]
    end_counts = jnp.where(ends > 0, csum[jnp.maximum(ends - 1, 0)], 0)
    counts = jnp.minimum(end_counts - start_counts, cap)
    return _branch_if((values, indices, counts), with_branch)


def region_mask(n: int, boundaries: jnp.ndarray, region: jnp.ndarray):
    """Boolean mask of the elements belonging to ``region``.

    The reference slices its own reduced region physically
    (VGG/allreducer.py:894); with static shapes we mask the flat vector.
    """
    ids = jnp.arange(n, dtype=jnp.int32)
    return (ids >= boundaries[region]) & (ids < boundaries[region + 1])
