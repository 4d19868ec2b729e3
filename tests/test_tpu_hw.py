"""On-hardware Pallas kernel checks (skipped on the CPU test mesh).

The test suite runs on a virtual CPU mesh, where the selection kernels run
in the Pallas interpreter. The interpreter accepts constructs Mosaic's
real-chip lowering rejects (scalar fancy-indexing -> dynamic_slice,
cross-lane shape casts, float tpu.iota, ...; see ops/compaction.py
docstrings). This module re-runs the kernel parity checks compiled for the
real chip, and is the regression net for that class of bug.

Run it alone, on a machine with a TPU:
    OKTOPK_TPU_HW=1 python -m pytest tests/test_tpu_hw.py

The opt-in variable is what keeps conftest.py from pinning the CPU
platform, so the default suite never touches the chip.
"""

import os

import numpy as np
import pytest

if os.environ.get("OKTOPK_TPU_HW", "0") != "1":
    pytest.skip("OKTOPK_TPU_HW=1 not set (hardware-only tests)",
                allow_module_level=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from oktopk_tpu.ops.compaction import (  # noqa: E402
    mesh_supports_pallas, pack_by_region_pallas, select_by_threshold_pallas)
from oktopk_tpu.ops.select import pack_by_region, select_by_threshold  # noqa: E402

from test_compaction import (  # noqa: E402
    LP_REGION_COUNTS, LP_SELECT_COUNTS, REPAIR_SURVIVORS,
    STAGE_TILE_LAYOUTS, check_pack_prefix, check_select_prefix,
    check_stage_tile, check_whole_offset_rounds, overflow_vector,
    small_chunk_jits, straddling_bounds)
from test_fused_select import (  # noqa: E402
    assert_all_equal as fused_assert_all_equal, layouts, region_bounds,
    run_both as fused_run_both)


@pytest.fixture(scope="module")
def tpu_dev():
    devs = [d for d in jax.devices() if d.platform == "tpu"]
    if not devs:
        pytest.skip("no TPU device visible")
    return devs[0]


@pytest.mark.parametrize("layout", STAGE_TILE_LAYOUTS)
@pytest.mark.parametrize("capb", [128, 1024])
def test_stage_tile_every_offset_exact_on_chip(tpu_dev, capb, layout):
    """Mirror of tests/test_compaction.py::TestStageTile on silicon: the
    MXU's own bf16 pass stages the lane and row digits of every in-block
    offset exactly, at both staging widths."""
    with jax.default_device(tpu_dev):
        check_stage_tile(capb, layout, interpret=False)


def test_whole_offset_in_one_pass_rounds_on_chip(tpu_dev):
    """The reason for the digits, on silicon: the whole offset as one row
    of the same pass comes back rounded to bf16 above 256."""
    with jax.default_device(tpu_dev):
        check_whole_offset_rounds(interpret=False)


def test_select_parity_on_chip(tpu_dev):
    rng = np.random.RandomState(0)
    n = 1 << 18
    x = rng.randn(n).astype(np.float32)
    cap = 4096
    with jax.default_device(tpu_dev):
        gv, gi, gc, _ = select_by_threshold_pallas(jnp.asarray(x), 2.0, cap,
                                                interpret=False)
        gv, gi, gc = map(np.asarray, (gv, gi, gc))
    wv, wi, wc = map(np.asarray,
                     select_by_threshold(jnp.asarray(x), 2.0, cap))
    assert gc == wc
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv, wv)


def test_pack_by_region_parity_on_chip(tpu_dev):
    rng = np.random.RandomState(1)
    n = 1 << 18
    x = rng.randn(n).astype(np.float32)
    bounds = np.array([0, n // 3, n // 2, n], np.int32)
    cap = 2048
    with jax.default_device(tpu_dev):
        gv, gi, gc, _ = pack_by_region_pallas(jnp.asarray(x), 1.5,
                                           jnp.asarray(bounds), 3, cap,
                                           interpret=False)
        gv, gi, gc = map(np.asarray, (gv, gi, gc))
    wv, wi, wc = map(np.asarray,
                     pack_by_region(jnp.asarray(x),
                                    jnp.abs(jnp.asarray(x)) >= 1.5,
                                    jnp.asarray(bounds), 3, cap))
    np.testing.assert_array_equal(gc, wc)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv, wv)


def test_select_repair_branch_parity_on_chip(tpu_dev):
    """Mirror of tests/test_compaction.py::test_repair_branch_scattered_
    overflow on silicon: a few scattered dense blocks put the dispatch in
    the repair branch (0 < novf <= _novf_cap): the repair kernel's
    scalar-prefetched index_map + _materialize_het run under Mosaic, not
    the interpreter."""
    from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, _novf_cap

    rng = np.random.RandomState(11)
    n = 64 * BLK
    cap = 8 * BLK
    x = rng.randn(n).astype(np.float32) * 0.1
    for b in (3, 17, 40):
        x[b * BLK:(b + 1) * BLK] = rng.randn(BLK) * 10 + 20
    raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
    excl = np.cumsum(raw) - raw
    novf = int(((raw > CAPB_FAST) & (excl + CAPB_FAST < cap)).sum())
    assert 0 < novf <= _novf_cap(64)
    with jax.default_device(tpu_dev):
        gv, gi, gc, _ = select_by_threshold_pallas(jnp.asarray(x), 1.0, cap,
                                                interpret=False)
        gv, gi, gc = map(np.asarray, (gv, gi, gc))
    wv, wi, wc = map(np.asarray,
                     select_by_threshold(jnp.asarray(x), 1.0, cap))
    assert gc == wc
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv, wv)


def test_pack_repair_branch_straddling_boundary_on_chip(tpu_dev):
    """Mirror of tests/test_compaction.py::test_repair_branch_with_
    straddling_boundary on silicon: one overflowed block contains a region
    boundary past the fast-staged slots, so the straddle row must be read
    from the repaired 1024-wide staging through the heterogeneous layout."""
    from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, _novf_cap

    rng = np.random.RandomState(13)
    n = 16 * BLK
    x = rng.randn(n).astype(np.float32) * 0.1
    x[5 * BLK:6 * BLK] = rng.randn(BLK) * 10 + 20
    raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
    assert 0 < int((raw > CAPB_FAST).sum()) <= _novf_cap(16)
    bounds = np.asarray([0, 5 * BLK + 700, n], np.int32)
    with jax.default_device(tpu_dev):
        gv, gi, gc, _ = pack_by_region_pallas(jnp.asarray(x), 1.0,
                                           jnp.asarray(bounds), 2, 2 * BLK,
                                           interpret=False)
        gv, gi, gc = map(np.asarray, (gv, gi, gc))
    wv, wi, wc = map(np.asarray,
                     pack_by_region(jnp.asarray(x),
                                    jnp.abs(jnp.asarray(x)) >= 1.0,
                                    jnp.asarray(bounds), 2, 2 * BLK))
    np.testing.assert_array_equal(gc, wc)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv, wv)


def test_mesh_supports_pallas_on_hw(tpu_dev):
    from oktopk_tpu.comm.mesh import get_mesh
    mesh = get_mesh((1,), ("data",), devices=[tpu_dev])
    assert mesh_supports_pallas(mesh)


@layouts
def test_fused_select_parity_on_chip(tpu_dev, layout):
    """Mirror of tests/test_fused_select.py fast-branch parity on silicon:
    the fused residual+select+stage kernel (ops/fused_select.py) compiled
    through Mosaic must reproduce the portable separate-pass outputs —
    acc, staged regions, realised count and unclamped probe count — bit
    for bit, over one region and over three whose boundaries lie inside a
    block."""
    rng = np.random.RandomState(21)
    n = 1 << 18
    g = rng.randn(n).astype(np.float32)
    r = (0.1 * rng.randn(n)).astype(np.float32)
    bnd = region_bounds(layout, n, (n // 3, n - 300))
    with jax.default_device(tpu_dev):
        got, want, branch = fused_run_both(g, r, 2.0, bnd, 4096,
                                           interpret=False)
    fused_assert_all_equal(got, want)
    assert branch[0] == 0


@layouts
def test_fused_repair_branch_parity_on_chip(tpu_dev, layout):
    """Mirror of tests/test_fused_select.py::test_repair_branch on silicon:
    scattered dense blocks overflow CAPB_FAST so the shared _pack_finalize
    repair kernel re-stages them from the FUSED kernel's own acc output —
    the handoff between the fused staging layout and the repair path under
    Mosaic, with the region boundaries inside two of the hot blocks."""
    from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, _novf_cap

    rng = np.random.RandomState(23)
    n = 64 * BLK
    g = rng.randn(n).astype(np.float32) * 0.1
    for b in (3, 17, 40):
        g[b * BLK:(b + 1) * BLK] = rng.randn(BLK) * 10 + 20
    r = (0.01 * rng.randn(n)).astype(np.float32)
    raw = (np.abs(g + r).reshape(-1, BLK) >= 1.0).sum(axis=1)
    novf = int((raw > CAPB_FAST).sum())
    assert 0 < novf <= _novf_cap(64)
    bnd = region_bounds(layout, n, (3 * BLK + 700, 40 * BLK + 300))
    with jax.default_device(tpu_dev):
        got, want, branch = fused_run_both(g, r, 1.0, bnd, 8 * BLK,
                                           interpret=False)
    fused_assert_all_equal(got, want)
    assert branch.tolist() == [1, novf]


@pytest.mark.parametrize("novf", sorted(REPAIR_SURVIVORS))
@pytest.mark.parametrize("form", ["select", "pack"])
def test_repair_pages_and_list_lengths_on_chip(tpu_dev, form, novf):
    """Mirror of tests/test_compaction.py::test_repair_branch_pages_and_
    list_lengths (both classes) on silicon: the repair kernel's two gates
    under Mosaic — grid steps at or past the live count skipped (1, 2, 4
    and all 8 list entries live), pages past a block's survivor count not
    staged (129, 300, 640 and 1,024 survivors). The skipped rows hold what
    VMEM held; the results may not show it."""
    x, blocks = overflow_vector(REPAIR_SURVIVORS[novf])
    xj = jnp.asarray(x)
    with jax.default_device(tpu_dev):
        if form == "select":
            *got, branch = select_by_threshold_pallas(xj, 1.0, x.size,
                                                      interpret=False)
            want = select_by_threshold(xj, 1.0, x.size)
        else:
            bnd = jnp.asarray(straddling_bounds(blocks))
            *got, branch = pack_by_region_pallas(xj, 1.0, bnd, 2,
                                                 x.size // 2,
                                                 interpret=False)
            want = pack_by_region(xj, jnp.abs(xj) >= 1.0, bnd, 2,
                                  x.size // 2)
        np.testing.assert_array_equal(np.asarray(branch), [1, novf])
        for nm, a, b in zip(("values", "indices", "counts"), got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=nm)


class TestLivePrefixRepairOnChip:
    """Mirror of tests/test_compaction.py::TestLivePrefix for the repair
    branch on silicon: the materialise's gather loop (a ``while`` whose
    trip count follows the survivor count, each chunk written in place)
    compiled by XLA:TPU beside the Mosaic kernels, ``CHUNK`` patched to
    1,024 so that the counts sit at the ends of a chunk. The module's own
    ``CHUNK`` runs in ``test_repair_pages_and_list_lengths_on_chip``
    (capacity 65,536 and 32,768)."""

    @pytest.fixture(scope="class")
    def jits(self):
        with pytest.MonkeyPatch.context() as mp:
            yield small_chunk_jits(mp, interpret=False)

    @pytest.mark.parametrize("count", LP_SELECT_COUNTS["repair"])
    def test_select(self, tpu_dev, jits, count):
        with jax.default_device(tpu_dev):
            check_select_prefix(jits[0], "repair", count)

    @pytest.mark.parametrize("case", sorted(LP_REGION_COUNTS))
    def test_pack_regions_end_in_different_chunks(self, tpu_dev, jits,
                                                  case):
        with jax.default_device(tpu_dev):
            check_pack_prefix(jits[1], "repair", case)


def test_pack_wide_branch_parity_on_chip(tpu_dev):
    """Mirror of tests/test_compaction.py::test_wide_fallback_when_repair_
    list_overflows on silicon: every block overflows CAPB_FAST, far beyond
    the repair list, so the dispatch re-stages everything with the
    capb=1024 kernel ([1024, 128] one-hots under the default scoped-VMEM
    limit) — with a region boundary inside a block."""
    from oktopk_tpu.ops.compaction import BLK, CAPB_FAST, _novf_cap

    rng = np.random.RandomState(12)
    n = 16 * BLK
    x = (rng.randn(n).astype(np.float32) * 0.5 + 20)      # all blocks dense
    raw = (np.abs(x.reshape(16, BLK)) >= 1.0).sum(axis=1)
    assert (raw > CAPB_FAST).sum() > _novf_cap(16)
    bounds = np.asarray([0, 7 * BLK + 300, n], np.int32)
    with jax.default_device(tpu_dev):
        gv, gi, gc, _ = pack_by_region_pallas(jnp.asarray(x), 1.0,
                                           jnp.asarray(bounds), 2, n,
                                           interpret=False)
        gv, gi, gc = map(np.asarray, (gv, gi, gc))
    wv, wi, wc = map(np.asarray,
                     pack_by_region(jnp.asarray(x),
                                    jnp.abs(jnp.asarray(x)) >= 1.0,
                                    jnp.asarray(bounds), 2, n))
    assert gc.sum() == n
    np.testing.assert_array_equal(gc, wc)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv, wv)


def test_oktopk_step_check_vma_matches_portable_on_chip(tpu_dev):
    """The whole oktopk allreduce step over every visible chip with the
    compiled Pallas path under shard_map's varying-axes tracking —
    ``build_allreduce_step``'s default ``check_vma=True``, the combination
    the autotuner's trials use — must lower, and match the portable path
    (mirror of tests/test_compaction.py::test_full_algorithm_matches_
    portable, whose interpreter cannot run with check_vma on).

    f32 wire: with the bf16 wire the two programs differ by one bf16 ulp
    on a single chip, where the one-device all_to_all folds away and
    XLA:TPU may then drop the f32->bf16->f32 round trip in one program and
    not the other (excess precision; seen on v5e in PR 21)."""
    from test_compaction import _run_oktopk_both_paths

    from oktopk_tpu.comm.mesh import get_mesh
    from oktopk_tpu.config import OkTopkConfig

    devs = [d for d in jax.devices() if d.platform == "tpu"]
    P, n = len(devs), 1 << 16
    mesh = get_mesh((P,), ("data",), devices=devs)
    base = np.random.RandomState(4).randn(P, n).astype(np.float32)
    cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.05, warmup_steps=0,
                        local_recompute_every=2, global_recompute_every=4,
                        wire_dtype="float32")
    outs, states = _run_oktopk_both_paths(mesh, cfg0, base, steps=4,
                                          check_vma=True)
    for a, b in zip(outs[False], outs[True]):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(np.asarray(states[False].last_volume),
                               np.asarray(states[True].last_volume))
    np.testing.assert_allclose(np.asarray(states[False].residual),
                               np.asarray(states[True].residual), atol=1e-6)
