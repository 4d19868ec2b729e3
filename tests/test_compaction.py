"""Parity tests for the Pallas stream-compaction fast path
(ops/compaction.py) against the portable ops/select.py implementation.

Runs the kernel in interpret mode on CPU (the compiled path is exercised by
tests/test_tpu_hw.py and chip_smoke.py on hardware); the contract is identical:
(values[cap], indices[cap], count), ascending index order, sentinel n,
overflow dropped lowest-index-first (plus the documented per-block CAPB
bound)."""

import numpy as np
import pytest

import jax.numpy as jnp

from oktopk_tpu.ops.compaction import (BLK, CAPB_FAST, _novf_cap,
                                       select_by_threshold_pallas)
from oktopk_tpu.ops.select import select_by_threshold

# `pytest -m kernels` runs the Pallas parity suites standalone during
# kernel iteration (pytest.ini)
pytestmark = pytest.mark.kernels


def run_both(x, thresh, cap):
    got = select_by_threshold_pallas(jnp.asarray(x), thresh, cap,
                                     interpret=True)[:3]
    want = select_by_threshold(jnp.asarray(x), thresh, cap)
    return [np.asarray(g) for g in got], [np.asarray(w) for w in want]


# Survivor counts of the overflowing blocks of one vector, keyed by how many
# there are (novf): one page and one survivor (129), three pages (300), five
# (640) and all eight (1,024) — what the repair kernel's page gate splits
# on. 64 blocks make the repair list eight entries long, so 8 fills it.
REPAIR_NBLOCKS = 64
REPAIR_SURVIVORS = {
    1: (300,),
    2: (129, 1024),
    4: (129, 300, 640, 1024),
    8: (129, 300, 640, 1024, 1024, 640, 300, 129),
}


def overflow_vector(survivors, seed=21):
    """(x, blocks): ``survivors[i]`` elements of magnitude >= 5 at random
    places of block ``blocks[i]`` (block 0, which a padded list entry
    repeats, and the last block among them), three in every other block,
    the rest under 1: at threshold 1.0 exactly ``len(survivors)`` blocks
    overflow the fast staging width."""
    rng = np.random.RandomState(seed)
    nb = REPAIR_NBLOCKS
    assert _novf_cap(nb) == 8 and min(survivors) > CAPB_FAST
    x = (rng.rand(nb, BLK).astype(np.float32) - 0.5)
    blocks = np.linspace(0, nb - 1, len(survivors)).astype(int)
    count = np.full(nb, 3)
    count[blocks] = survivors
    for b in range(nb):
        at = rng.choice(BLK, count[b], replace=False)
        x[b, at] = (5.0 + rng.rand(count[b])) * rng.choice([-1.0, 1.0],
                                                           count[b])
    raw = (np.abs(x) >= 1.0).sum(axis=1)
    assert int((raw > CAPB_FAST).sum()) == len(survivors)
    return x.reshape(-1), blocks


def straddling_bounds(blocks):
    """Two regions whose boundary lies inside the last overflowing block,
    past its fast-staged slots."""
    return np.asarray([0, blocks[-1] * BLK + 700, REPAIR_NBLOCKS * BLK],
                      np.int32)


class TestCompactionParity:
    @pytest.mark.parametrize("n", [BLK, 3 * BLK, 4 * BLK + 777])
    def test_matches_portable_select(self, n):
        rng = np.random.RandomState(0)
        x = rng.randn(n).astype(np.float32)
        t = 2.0                      # ~2.3% of N(0,1) passes
        cap = max(64, int(0.05 * n))
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, cap)
        assert gc == wc
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_bit_exact_values(self):
        rng = np.random.RandomState(1)
        # adversarial float bit patterns: subnormals excluded (threshold),
        # but mixed signs/exponents must come back bit-exact through the
        # staging offsets + value gather
        x = (rng.randn(2 * BLK) * 10.0 ** rng.randint(-6, 6, 2 * BLK))
        x = x.astype(np.float32)
        t = float(np.quantile(np.abs(x), 0.97))
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, 4096)
        assert gc == wc
        np.testing.assert_array_equal(gv.view(np.int32), wv.view(np.int32))

    def test_cap_overflow_drops_tail(self):
        rng = np.random.RandomState(2)
        x = rng.randn(4 * BLK).astype(np.float32)
        t = 0.5                      # ~62% pass -> far over cap
        cap = 256
        (gv, gi, gc), (wv, wi, wc) = run_both(x, t, cap)
        assert gc == wc == cap
        # lowest-index-first retention identical to the portable path
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_empty_selection(self):
        x = np.zeros(2 * BLK, np.float32)
        gv, gi, gc, _ = [np.asarray(a) for a in
                         select_by_threshold_pallas(jnp.asarray(x), 1.0, 128,
                                                    interpret=True)]
        assert gc == 0
        assert (gi == x.size).all()
        assert (gv == 0).all()

    def test_fully_dense_block(self):
        """cap >= BLK: a fully dense block is retained whole."""
        x = np.ones(2 * BLK, np.float32)
        x[BLK:] = 0.0
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 0.5, 2 * BLK)
        assert gc == wc == BLK
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_repair_branch_scattered_overflow(self):
        """A few scattered overflowing blocks (0 < novf <= _novf_cap):
        the repair-kernel branch, mixed 128/1024-wide staging layout."""
        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(11)
        n = 64 * BLK
        cap = 8 * BLK
        x = rng.randn(n).astype(np.float32) * 0.1
        for b in (3, 17, 40):            # ~5% of blocks, far over CAPB_FAST
            x[b * BLK:(b + 1) * BLK] = rng.randn(BLK) * 10 + 20
        # the repair branch condition of select_by_threshold_pallas,
        # asserted directly: some blocks overflow the fast staging in a
        # way that matters, but fewer than the repair-list capacity
        raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
        excl = np.cumsum(raw) - raw
        novf = int(((raw > CAPB_FAST) & (excl + CAPB_FAST < cap)).sum())
        assert 0 < novf <= _novf_cap(64)
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 1.0, cap)
        assert gc == wc
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    @pytest.mark.parametrize("novf", sorted(REPAIR_SURVIVORS))
    def test_repair_branch_pages_and_list_lengths(self, novf):
        """Repair branch with 1, 2, 4 and exactly ``_novf_cap`` listed
        blocks holding 129 to 1,024 survivors: the kernel skips the padded
        list entries and the pages past a block's count."""
        x, _ = overflow_vector(REPAIR_SURVIVORS[novf])
        gv, gi, gc, branch = [np.asarray(a) for a in
                              select_by_threshold_pallas(
                                  jnp.asarray(x), 1.0, x.size,
                                  interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in
                      select_by_threshold(jnp.asarray(x), 1.0, x.size)]
        np.testing.assert_array_equal(branch, [1, novf])
        assert gc == wc
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_wide_fallback_when_repair_list_overflows(self):
        """More overflowing blocks than the repair-list capacity
        (novf > _novf_cap): the full-width re-stage fallback."""
        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(12)
        n = 16 * BLK
        assert _novf_cap(16) == 8
        # randn*0.5 + 20 guarantees |x| >= 1 everywhere (min ~ 20 - 5*0.5):
        # the earlier randn*10 + 20 left 158/16384 elements below threshold
        # with seed 12, breaking the full-density assumption (ADVICE r5)
        x = (rng.randn(n).astype(np.float32) * 0.5 + 20)  # all blocks dense
        # the wide-fallback branch condition, asserted directly: every
        # block overflows the fast staging, far beyond the repair list
        raw = (np.abs(x.reshape(16, BLK)) >= 1.0).sum(axis=1)
        assert (raw > CAPB_FAST).sum() > _novf_cap(16)
        (gv, gi, gc), (wv, wi, wc) = run_both(x, 1.0, n)
        assert gc == wc == n
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_range_restriction(self):
        rng = np.random.RandomState(3)
        x = rng.randn(3 * BLK).astype(np.float32)
        lo, hi = BLK // 2, 2 * BLK + 17
        gv, gi, gc, _ = [np.asarray(a) for a in
                         select_by_threshold_pallas(
                          jnp.asarray(x), 2.0, 512,
                          lo=jnp.int32(lo), hi=jnp.int32(hi),
                          interpret=True)]
        want = np.where(np.abs(x) >= 2.0)[0]
        want = want[(want >= lo) & (want < hi)]
        assert gc == len(want)
        np.testing.assert_array_equal(gi[:gc], want)
        np.testing.assert_array_equal(gv[:gc], x[want])


class TestPackRegionsParity:
    """Single-sweep multi-region kernel vs the portable pack_by_region."""

    @pytest.mark.parametrize("bounds", [
        [0, 1024, 2048, 3072],          # block-aligned
        [0, 700, 1930, 3072],           # unaligned
        [0, 64, 80, 3072],              # tiny regions inside one block
        [0, 0, 1500, 3072],             # empty first region
    ])
    def test_matches_portable(self, bounds):
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        n = 3 * BLK
        rng = np.random.RandomState(5)
        x = rng.randn(n).astype(np.float32)
        t, cap = 1.0, 256
        R = len(bounds) - 1
        b = jnp.asarray(bounds, jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), t, b, R, cap, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= t, b, R, cap)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_repair_branch_with_straddling_boundary(self):
        """An overflowing block that also contains a region boundary: the
        straddle row must be fetched from the repaired (1024-wide) staging,
        not the truncated fast row."""
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        from oktopk_tpu.ops.compaction import CAPB_FAST, _novf_cap

        rng = np.random.RandomState(13)
        n = 16 * BLK
        x = rng.randn(n).astype(np.float32) * 0.1
        x[5 * BLK:6 * BLK] = rng.randn(BLK) * 10 + 20     # block 5 dense
        # pack's repair branch condition (ovf = raw > CAPB_FAST), directly
        raw = (np.abs(x.reshape(-1, BLK)) >= 1.0).sum(axis=1)
        assert 0 < int((raw > CAPB_FAST).sum()) <= _novf_cap(16)
        # boundary inside the dense block, past the 128 fast-staged slots
        b = jnp.asarray([0, 5 * BLK + 700, n], jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 1.0, b, 2, 2 * BLK, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, b, 2, 2 * BLK)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    @pytest.mark.parametrize("novf", sorted(REPAIR_SURVIVORS))
    def test_repair_branch_pages_and_list_lengths(self, novf):
        """As the select test of the same name, through the region
        finalisation, with the boundary inside an overflowing block."""
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        x, blocks = overflow_vector(REPAIR_SURVIVORS[novf])
        b = jnp.asarray(straddling_bounds(blocks))
        gv, gi, gc, branch = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 1.0, b, 2, x.size // 2, interpret=True)]
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, b, 2,
            x.size // 2)]
        np.testing.assert_array_equal(branch, [1, novf])
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)

    def test_cap_overflow_per_region(self):
        from oktopk_tpu.ops.compaction import pack_by_region_pallas
        from oktopk_tpu.ops.select import pack_by_region

        n = 2 * BLK
        rng = np.random.RandomState(6)
        x = rng.randn(n).astype(np.float32)
        b = jnp.asarray([0, n // 2, n], jnp.int32)
        gv, gi, gc, _ = [np.asarray(a) for a in pack_by_region_pallas(
            jnp.asarray(x), 0.3, b, 2, 64, interpret=True)]  # far over cap
        wv, wi, wc = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 0.3, b, 2, 64)]
        np.testing.assert_array_equal(gc, wc)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gv, wv)


class TestRepairSkipInvariant:
    """What the repair kernel's skipping rests on (``_run_repair``): the
    consumers of ``w_rep`` read a listed block's row below its survivor
    count only, so the rows of padded list entries and the slots at or past
    a count may hold anything."""

    @pytest.mark.parametrize("novf", [2, 4])
    def test_unaddressed_slots_may_hold_anything(self, novf):
        from oktopk_tpu.ops.compaction import (
            BLK_COLS, _materialize_het, _prep, _region_counts, _run_repair,
            _run_stage, _vma_of)
        from oktopk_tpu.ops.select import pack_by_region

        x, blocks = overflow_vector(REPAIR_SURVIVORS[novf])
        R, cap = 2, x.size // 2
        bnd = jnp.asarray(straddling_bounds(blocks))
        xp, xflat, t, rng, n, nb = _prep(jnp.asarray(x), 1.0, None, None)
        vma = _vma_of(xp)
        w_f, stored_f, raw = _run_stage(xp, t, rng, CAPB_FAST, nb, True, vma)
        ovf = raw > CAPB_FAST
        ncap = _novf_cap(nb)
        bl = jnp.nonzero(ovf, size=ncap, fill_value=0)[0].astype(jnp.int32)
        w_rep = _run_repair(xp, t, rng, bl, jnp.sum(ovf), ncap, True, vma)

        # NaN wherever the invariant says nobody looks
        listed = np.arange(ncap) < novf
        count = np.where(listed, np.asarray(raw)[np.asarray(bl)], 0)
        dead = np.arange(BLK)[None, :] >= count[:, None]   # [ncap, 1024]
        assert dead[~listed].all() and not dead[listed].all()
        poisoned = np.where(dead, np.nan,
                            np.asarray(w_rep).reshape(ncap, BLK))
        poisoned = jnp.asarray(poisoned.reshape(-1, BLK_COLS), jnp.float32)

        def finalize(w_rep):
            # _pack_finalize's repair branch, from w_rep on
            stored_v = jnp.where(ovf, raw, stored_f)
            rank = jnp.cumsum(ovf.astype(jnp.int32)) - ovf
            phys_base = jnp.where(
                ovf, nb * CAPB_FAST + rank * BLK,
                jnp.arange(nb, dtype=jnp.int32) * CAPB_FAST)
            stage_all = jnp.concatenate([w_f.reshape(-1),
                                         w_rep.reshape(-1)])
            cnt_rb = _region_counts(stage_all, phys_base, stored_v, BLK,
                                    bnd, R, nb)
            off_rb = jnp.cumsum(cnt_rb, axis=1) - cnt_rb
            counts = jnp.minimum(jnp.sum(cnt_rb, axis=0), cap)
            values, indices = _materialize_het(
                w_f, w_rep, ovf, xflat, cnt_rb, off_rb, CAPB_FAST, cap,
                counts, n)
            return [np.asarray(a) for a in (values, indices, counts)]

        want = [np.asarray(a) for a in pack_by_region(
            jnp.asarray(x), jnp.abs(jnp.asarray(x)) >= 1.0, bnd, R, cap)]
        for nm, clean, dirty, w in zip(("values", "indices", "counts"),
                                       finalize(w_rep), finalize(poisoned),
                                       want):
            np.testing.assert_array_equal(dirty, clean, err_msg=nm)
            np.testing.assert_array_equal(dirty, w, err_msg=nm)


def _run_oktopk_both_paths(mesh8, cfg0, base, steps, check_vma=None):
    """Run the full oktopk step for use_pallas False/True on the same data;
    returns ({use_pallas: [per-step results]}, {use_pallas: final state}).
    ``check_vma=None``: off for the Pallas path — the interpreter cannot
    mix VMA-tracked operands (tests/test_tpu_hw.py passes True: compiled
    through Mosaic it can)."""
    from oktopk_tpu.collectives.api import (batched_init_state,
                                            build_allreduce_step)

    outs, states = {}, {}
    for up in (False, True):
        cfg = cfg0.replace(use_pallas=up)
        step = build_allreduce_step(
            "oktopk", cfg, mesh8, warmup=False,
            check_vma=(not up) if check_vma is None else check_vma)
        state = batched_init_state(cfg)
        rs = []
        for _ in range(steps):
            out, state = step(jnp.asarray(base), state)
            rs.append(np.asarray(out[0]))
        outs[up], states[up] = rs, state
    return outs, states


class TestOkTopkPallasParity:
    # slow: the full oktopk step through the Pallas INTERPRETER (4 steps x
    # 2 selection paths each) is ~2 min on the CPU mesh; the kernel-level
    # parity (every dispatch branch) stays in the tier-1 classes above,
    # and the algorithm-level wiring is also exercised on real hardware
    # via tests/test_tpu_hw.py.
    @pytest.mark.slow
    def test_full_algorithm_matches_portable(self, mesh8, monkeypatch):
        """The whole oktopk step with the Pallas selection path (interpret
        mode) must produce the same reduced result, volumes and state as
        the portable path when counts sit inside the capacity bounds."""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.config import OkTopkConfig

        P, n = 8, 8192
        rng = np.random.RandomState(4)
        base = rng.randn(P, n).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.05,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=4)
        outs, states = _run_oktopk_both_paths(mesh8, cfg0, base, steps=4)
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_allclose(a, b, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(states[False].last_volume),
            np.asarray(states[True].last_volume))
        np.testing.assert_allclose(
            np.asarray(states[False].residual),
            np.asarray(states[True].residual), atol=1e-6)

    @pytest.mark.slow
    def test_full_algorithm_overflow_takes_wide_path(self, mesh8,
                                                     monkeypatch):
        """Spatially concentrated gradients overflow the CAPB_FAST staging
        in the hot blocks, so the algorithm-level step must take the
        capb=BLK wide-kernel cond branch under shard_map — and still match
        the portable path. (The unit tests exercise overflow outside
        shard_map; this pins the cond wiring inside the real step.)"""
        monkeypatch.setenv("OKTOPK_PALLAS_INTERPRET", "1")
        from oktopk_tpu.config import OkTopkConfig
        from oktopk_tpu.ops.compaction import CAPB_FAST

        P, n = 8, 8192
        rng = np.random.RandomState(9)
        # hot first block: far more than CAPB_FAST survivors land in one
        # 1024-element block; elsewhere near-silence
        base = 0.01 * rng.randn(P, n).astype(np.float32)
        base[:, :BLK] = 10.0 * rng.randn(P, BLK).astype(np.float32)
        cfg0 = OkTopkConfig(n=n, num_workers=P, density=0.2,
                            warmup_steps=0, local_recompute_every=2,
                            global_recompute_every=4)
        assert cfg0.cap_pair > CAPB_FAST   # overflow can matter => wide path
        outs, _ = _run_oktopk_both_paths(mesh8, cfg0, base, steps=3)
        # the wide branch really fired: more than CAPB_FAST of the hot
        # block's elements made the global result, so its raw survivor
        # count (a superset) must have exceeded the fast staging width
        assert (outs[False][0][:BLK] != 0).sum() > CAPB_FAST
        for a, b in zip(outs[False], outs[True]):
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, atol=1e-6)
